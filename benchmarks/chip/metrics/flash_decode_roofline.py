"""Roofline share of the flash-decode attention kernel: the least time
attention over each decoded lane's fill needs, over the kernel's device
time."""

from chipbench import costs
from chipbench.readers import (DECODE_MODULE, FLASH_OPS, decode_steps,
                               kernel_ops, share, total_s)


def read(run):
    steps = decode_steps(run)
    need = sum(costs.roofline_time(*costs.flash_decode(run.config, s.fills),
                                   run.peaks) for s in steps)
    return share(need, total_s(kernel_ops(run, DECODE_MODULE, FLASH_OPS)))
