"""What the metric readers share: the traced window of a run, the host
steps inside it, and the device events of each program and kernel.

A reader that finds nothing to read returns ``None`` and the harness
leaves its metric out; it never returns 0 for a share of a roofline or
of a peak.
"""

from __future__ import annotations

import re
from typing import Iterable, List, Optional, Sequence

from chipbench import costs
from chipbench import trace as TR

#: program name (``jit_<function>``) of the runtime's jitted decode step
DECODE_MODULE = "jit_decode"
#: the Pallas kernels' operations in the device trace.  An op's name is
#: its HLO text; both kernels are ``tpu_custom_call``s, told apart by
#: their first operand: the fused MVM's (1, 1) float32 dequant scale, the
#: flash kernel's int32 per-row fills (its scalar-prefetch operand)
FUSED_MVM_OPS = re.compile(r"custom-call\(f32\[1,1\]")
FLASH_OPS = re.compile(r"custom-call\(s32\[")


def window(run):
    """(start, end) of the traced window in trace ns, or None."""
    if run.trace is None or not run.plane:
        return None
    return run.trace.window()


def traced_steps(run) -> List:
    """Host steps whose span lies inside the traced window."""
    w = window(run)
    if w is None:
        return []
    idx = [int(e.name.rsplit(".", 1)[1]) for e in run.trace.steps()
           if w[0] <= e.start and e.end <= w[1]]
    return [run.steps[i] for i in idx if i < len(run.steps)]


def ops(run) -> List[TR.Event]:
    w = window(run)
    return [] if w is None else TR.clip(run.trace.ops[run.plane], *w)


def modules(run, name: str) -> List[TR.Event]:
    w = window(run)
    if w is None:
        return []
    return [m for m in TR.clip(run.trace.modules.get(run.plane, []), *w)
            if module_is(m.name, name)]


def module_is(event_name: str, name: str) -> bool:
    """``jit_decode(123)`` and ``jit_decode`` are both ``jit_decode``."""
    base = event_name.split("(", 1)[0].strip()
    return base == name or base.startswith(name + ".")


#: every kernel the readers know; a Pallas call that matches none of
#: them is unidentified
KNOWN_KERNELS = (FUSED_MVM_OPS, FLASH_OPS)


class UnidentifiedKernel(RuntimeError):
    """A kernel's pattern matched nothing while Pallas calls that match no
    known pattern ran in the same program: the kernel's operands have
    changed, and its reader must learn them before it can read."""


def kernel_ops(run, module: str, kernel: "re.Pattern") -> List[TR.Event]:
    """Ops of ``kernel`` that ran inside programs ``module``.  Nothing
    found is an empty list where no unidentified Pallas call ran there
    (the kernel is off the path), and an error where one did."""
    inside = [o for o in TR.in_modules(ops(run), modules(run, module))
              if "tpu_custom_call" in o.name]
    found = [o for o in inside if kernel.search(o.name)]
    unknown = [o for o in inside
               if not any(k.search(o.name) for k in KNOWN_KERNELS)]
    if not found and unknown:
        raise UnidentifiedKernel(
            f"no op of {kernel.pattern!r} in {module}, but "
            f"{len(unknown)} Pallas calls match no known kernel, e.g. "
            f"{unknown[0].name[:300]!r}")
    return found


def total_s(events: Iterable[TR.Event]) -> float:
    return sum(e.dur for e in events) * 1e-9


def share(need_s: float, took_s: float) -> Optional[float]:
    """``need / took`` in percent, or None when nothing ran."""
    if took_s <= 0 or need_s <= 0:
        return None
    return 100.0 * need_s / took_s


def decode_steps(run) -> List:
    return [s for s in traced_steps(run) if s.fills]


def roofline_decode_mvm(run) -> Optional[float]:
    c, pk = run.config, run.peaks
    steps = decode_steps(run)
    need = len(steps) * costs.roofline_time(
        *costs.decode_mvms(c, run.slots), pk)
    took = total_s(kernel_ops(run, DECODE_MODULE, FUSED_MVM_OPS))
    return share(need, took) if steps else None


def ms_per(events: Sequence[TR.Event]) -> Optional[float]:
    if not events:
        return None
    return total_s(events) / len(events) * 1e3


def gaps_ms(run) -> List[float]:
    """Every gap between consecutive tokens of one request, host clock."""
    out = []
    for r in run.reqs.values():
        t = r.token_times
        out.extend((b - a) * 1e3 for a, b in zip(t, t[1:]))
    return out
