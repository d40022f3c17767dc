"""Share of the traced window in which no operation ran on the chip while
the host was inside one of the runtime's ``serve.*`` spans: the
program's own part of ``idle_share.serve`` (device trace and program
spans).  Reads ``run.trace.spans`` (``chipbench.spans``); nothing where
the harness did not load them."""

from chipbench import spans as S
from chipbench.readers import ops, window


def read(run):
    w = window(run)
    spans = getattr(run.trace, "spans", None) if w is not None else None
    if not spans:
        return None
    return 100.0 * S.idle_in_spans_ns(ops(run), spans, *w) / (w[1] - w[0])
