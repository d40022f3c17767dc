"""95th percentile of every gap between consecutive tokens of one
request, as a streaming client sees them (host clock)."""

from chipbench.readers import gaps_ms
from chipbench.stats import percentile


def read(run):
    g = gaps_ms(run)
    return percentile(g, 95) if g else None
