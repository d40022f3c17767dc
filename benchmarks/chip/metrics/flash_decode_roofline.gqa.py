"""Roofline share of flash-decode on the row-major cache path: the least
time attention over each decoded lane's fill needs (chipbench.costs: K
and V read once per KV head) over the device time of the ops tagged
``flash_decode`` whose metadata says ``"layout":"row_major"``.

The layout key is parsed here: a program whose flash-decode tag lacks
it (one built before it) reads nothing, and so does a decode step whose
kernel read the cache head-major."""

import re

from chipbench import costs, tags
from chipbench.readers import DECODE_MODULE, decode_steps, share, total_s

LAYOUT = re.compile(r'kernel_metadata=\{[^}]*"layout":\s*"([^"]+)"')
ROW_MAJOR = "row_major"


def layout(op_name):
    """The ``"layout"`` in an op's kernel metadata, or None."""
    m = LAYOUT.search(op_name)
    return m.group(1) if m else None


def read(run):
    found = [o for o in tags.tagged_ops(run, DECODE_MODULE, tags.FLASH_DECODE)
             if layout(o.name) == ROW_MAJOR]
    steps = decode_steps(run)
    if not found or not steps:
        return None
    need = sum(costs.roofline_time(*costs.flash_decode(run.config, s.fills),
                                   run.peaks) for s in steps)
    return share(need, total_s(found))
