"""Roofline share of the fused analog MVM kernels inside decode
programs: the least time their calls need (chipbench.costs) over their
device time."""

from chipbench.readers import roofline_decode_mvm


def read(run):
    return roofline_decode_mvm(run)
