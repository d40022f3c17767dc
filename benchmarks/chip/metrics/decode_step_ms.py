"""Device time of the runtime's jitted decode program per execution
(device trace)."""

from chipbench.readers import DECODE_MODULE, modules, ms_per


def read(run):
    return ms_per(modules(run, DECODE_MODULE))
