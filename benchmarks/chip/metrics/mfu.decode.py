"""Model operations of the decoded tokens (2 per analog-site weight per
live lane, plus attention over each lane's fill) over the decode
program's device time, as a share of the chip's bf16 peak."""

from chipbench import costs
from chipbench.readers import (DECODE_MODULE, decode_steps, modules, share,
                               total_s)


def read(run):
    steps = decode_steps(run)
    flops = sum(costs.model_flops_decode(run.config, s.fills) for s in steps)
    took = total_s(modules(run, DECODE_MODULE))
    return share(flops / run.peaks["bf16_flops"], took) if steps else None
