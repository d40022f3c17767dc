"""Device time of the ops tagged ``"kernel": "fused_mvm"`` (every fused
analog site, the head included) per execution of the runtime's jitted
decode program (device trace)."""

from chipbench import tags


def read(run):
    return tags.decode_ms(run, tags.FUSED_MVM)
