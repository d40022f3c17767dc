"""Share of the traced window in which no operation ran on the chip:
1 - union of device op intervals / window (device trace)."""

from chipbench import trace as TR
from chipbench.readers import ops, window


def read(run):
    w = window(run)
    if w is None:
        return None
    busy = TR.busy_ns(ops(run), *w)
    return 100.0 * (1.0 - busy / (w[1] - w[0]))
