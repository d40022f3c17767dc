"""Reduction of a profiler trace to what the metric readers read.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  Device planes are named ``/device:TPU:<n>``; on each, the
``XLA Modules`` line holds one event per executed program (named after
the jitted function, ``jit_decode(<id>)``) and the ``XLA Ops`` line one
event per operation, named by its HLO text, Pallas kernels included; a
loop op spans the ops of its body.  The host plane carries the benchmark's own
spans (``bench.*``), which share the trace's clock.

Everything here works on plain tuples so that a test can check it on a
trace recorded on the chip and kept in ``testdata/``.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
HOST_PREFIX = "bench."


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float           # ns on the trace's clock
    dur: float             # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    """Device operations and programs of each chip, and host spans."""

    ops: Dict[str, List[Event]]          # device plane -> op events
    modules: Dict[str, List[Event]]      # device plane -> program events
    host: List[Event]                    # bench.* spans

    def window(self) -> Tuple[float, float]:
        """The traced window: the ``bench.window`` span."""
        w = [e for e in self.host if e.name == "bench.window"]
        if not w:
            raise ValueError("trace holds no bench.window span")
        return w[0].start, w[0].end

    def steps(self) -> List[Event]:
        """``bench.step.<i>`` spans in order."""
        return sorted((e for e in self.host if e.name.startswith(
            "bench.step.")), key=lambda e: e.start)


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` into a :class:`Trace`."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules[plane.name] = [
                        Event(e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events]
                elif line.name == "XLA Ops":
                    ops[plane.name] = [
                        Event(e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append(Event(e.name, float(e.start_ns),
                                          float(e.duration_ns)))
    return Trace(ops=ops, modules=modules, host=host)


def clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    """Events that start inside ``[lo, hi)``."""
    return [e for e in events if lo <= e.start < hi]


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float,
                                                                  float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events: Sequence[Event], lo: float, hi: float) -> float:
    """Length of the union of ``events`` inside ``[lo, hi]``."""
    iv = [(max(e.start, lo), min(e.end, hi)) for e in events
          if e.end > lo and e.start < hi]
    return sum(e - s for s, e in union(iv))


def idle_gaps(events: Sequence[Event], lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    """Intervals of ``[lo, hi]`` in which no device operation ran."""
    iv = union([(max(e.start, lo), min(e.end, hi)) for e in events
                if e.end > lo and e.start < hi])
    gaps, t = [], lo
    for s, e in iv:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def in_modules(ops: Sequence[Event], mods: Sequence[Event]) -> List[Event]:
    """Ops that started inside one of the program executions ``mods``
    (device ops carry no program name, so by time)."""
    spans = sorted((m.start, m.end) for m in mods)
    out, j = [], 0
    for o in sorted(ops, key=lambda e: e.start):
        while j < len(spans) and spans[j][1] < o.start:
            j += 1
        if j < len(spans) and spans[j][0] <= o.start <= spans[j][1]:
            out.append(o)
    return out


def host_label(host: Sequence[Event], t: float) -> str:
    """Innermost ``bench.*`` span (other than the window and steps)
    covering time ``t``, else the step covering it."""
    best: Optional[Event] = None
    for e in host:
        if e.start <= t <= e.end and e.name != "bench.window":
            if best is None or e.dur < best.dur:
                best = e
    if best is None:
        return "outside any step"
    return "bench.step" if best.name.startswith("bench.step.") else best.name


_OPCODE = re.compile(r" ([a-z][a-z0-9_\-]*)\(")
#: ops whose events span the ops of their bodies
CONTAINERS = ("while", "conditional", "call")


def short_name(hlo: str) -> Tuple[str, str]:
    """(``%name opcode shape``, opcode) of an op event named by its HLO
    text; other names pass unchanged."""
    if " = " not in hlo:
        return hlo, ""
    lhs, rhs = hlo.split(" = ", 1)
    m = _OPCODE.search(rhs)
    opcode = m.group(1) if m else ""
    shape = "tuple" if rhs.startswith("(") else rhs.split(" ", 1)[0]
    return f"{lhs} {opcode} {shape}", opcode


def breakdown(tr: Trace, plane: str, lo: float, hi: float,
              top: int = 10) -> Dict[str, list]:
    """The device operations that took most time (loop ops, which span
    their bodies, left out) and the longest idle gaps, each gap named by
    what the host was doing in its middle."""
    ops = clip(tr.ops.get(plane, []), lo, hi)
    by_name: Dict[str, float] = {}
    for o in ops:
        name, opcode = short_name(o.name)
        if opcode not in CONTAINERS:
            by_name[name] = by_name.get(name, 0.0) + o.dur
    dev = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(ops, lo, hi), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, d * 1e-9] for n, d in dev],
            "idle_gaps": [[host_label(tr.host, (s + e) / 2), (e - s) * 1e-9]
                          for s, e in gaps]}
