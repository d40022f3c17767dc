"""The program's own spans and counters, reduced for the metric readers.

``ServeRuntime`` runs each scheduler phase inside a ``serve.*``
``TraceAnnotation`` on the profiler's clock (``serve.step`` around
``serve.maintain``, ``serve.admit`` holding ``serve.prefill``,
``serve.decode`` and ``serve.collect``; ``serve.submit`` and
``serve.read`` outside steps), with its arguments as event stats, and
times the same phases into ``ServeRuntime.stats``.

The harness does not gather them yet: ``trace.load`` keeps the
``bench.*`` spans alone and ``serve.serve_window`` copies a fixed set of
counters.  Two additions wire them in: ``run.trace.spans = load(xplane)``
beside ``trace.load``, and ``run.counters.update(window_counters(stats0,
stats1))`` in ``serve_window``.  Until then the readers of
``idle_share.runtime`` and ``sched_ms.serve`` find nothing and their
metrics are left out.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence

from chipbench import trace as TR

PREFIX = "serve."
#: the runtime's timed phases (``repro.serve.runtime.PHASES``);
#: ``prefill`` lies inside ``admit``, the other four are disjoint
PHASES = ("maintain", "admit", "prefill", "decode", "collect")
STEP_PHASES = ("maintain", "admit", "decode", "collect")


@dataclasses.dataclass(frozen=True)
class Span(TR.Event):
    args: Dict[str, Any] = dataclasses.field(default_factory=dict)


def load(path: str) -> List[Span]:
    """The ``serve.*`` spans of an ``.xplane.pb``, with their args, in
    order of start."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append(Span(e.name, float(e.start_ns),
                                    float(e.duration_ns), dict(e.stats)))
    return sorted(out, key=lambda s: s.start)


def idle_in_spans_ns(ops: Sequence[TR.Event], spans: Sequence[Span],
                     lo: float, hi: float) -> float:
    """Time of ``[lo, hi]`` in which no device op ran while the host was
    inside a ``serve.*`` span."""
    inside = TR.union([(s.start, s.end) for s in spans])
    total = 0.0
    for g0, g1 in TR.idle_gaps(ops, lo, hi):
        total += sum(max(0.0, min(g1, s1) - max(g0, s0))
                     for s0, s1 in inside)
    return total


def label(spans: Sequence[Span], host: Sequence[TR.Event], t: float) -> str:
    """Innermost ``serve.*`` span covering time ``t``, else what
    ``trace.host_label`` says of the harness's spans."""
    best: Optional[Span] = None
    for s in spans:
        if s.start <= t <= s.end and (best is None or s.dur < best.dur):
            best = s
    return best.name if best is not None else TR.host_label(host, t)


def idle_gaps(tr: TR.Trace, spans: Sequence[Span], plane: str, lo: float,
              hi: float, top: int = 10) -> List[list]:
    """The longest idle gaps of ``[lo, hi]``, each named by
    :func:`label` at its middle, as ``breakdown`` reports them."""
    gaps = TR.idle_gaps(TR.clip(tr.ops.get(plane, []), lo, hi), lo, hi)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return [[label(spans, tr.host, (s + e) / 2), (e - s) * 1e-9]
            for s, e in gaps]


def window_counters(stats0: Dict[str, Any], stats1: Dict[str, Any]
                    ) -> Dict[str, float]:
    """The runtime's phase counters over a window, from its ``stats``
    at the window's start and end: the differences of the cumulative
    ones, and each phase's longest call since the runtime's last reset.
    Empty for a runtime without them."""
    out: Dict[str, float] = {}
    for key in [p + "_s" for p in PHASES] + ["device_syncs"]:
        if key in stats1:
            out[key] = stats1[key] - stats0[key]
    for p in PHASES:
        if p + "_max_s" in stats1:
            out[p + "_max_s"] = stats1[p + "_max_s"]
    return out


def sched_ms(counters: Dict[str, Any], steps: int) -> Optional[float]:
    """The runtime's host time in its phases per scheduler step, in ms,
    or None without the counters."""
    keys = [p + "_s" for p in STEP_PHASES]
    if steps < 1 or any(k not in counters for k in keys):
        return None
    return sum(counters[k] for k in keys) / steps * 1e3
