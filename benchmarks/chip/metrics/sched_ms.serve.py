"""Host time of the runtime's scheduler phases (maintain, admit, decode
dispatch, collect) per scheduler step in the window, from the runtime's
own counters (``chipbench.spans.window_counters``); nothing where the
harness did not copy them."""

from chipbench import spans as S


def read(run):
    return S.sched_ms(run.counters, len(run.steps))
