"""Compile contracts: declared compilation budgets, checked for real.

PRs 3-6 each pinned a compilation-structure invariant by hand — "the
Fig. 19 ``r_hat`` axis is ONE compile group", "``ServeRuntime``'s decode
step compiles once across a ragged trace", "drift's nu x t grid traces,
never re-lowers".  This module turns those ad-hoc pins into declarations
(:class:`CompileContract`) verifiable at two levels:

* **static** — the cheap structural check: expand the contract's sweep
  grid and assert the executor's :func:`~repro.sweep.executor.
  compile_groups` partition matches the declared group budget and traced
  field names.  Runs in tier-1 CI on every push.
* **trace** — run the *real* jitted entry points and count actual XLA
  compilations, via either the jit cache size of named entry points
  (exact, attributable) or a process-wide backend-compile event counter
  (:class:`compile_counter`, for entry points whose jit wrappers are
  created internally).  Runs in the nightly tier-2 job
  (``tools/analyze.py --contracts trace``).

Violations come back as :class:`~repro.analysis.findings.Finding` rows
(rule ``compile-contract``), the same currency as the lint layer, so the
CLI and CI gate treat both uniformly.

The third contract form guards the *bit-exactness* half of the story:
:func:`traced_constant_violations` traces an entry point with sentinel
values substituted into fields declared traced, and scans the jaxpr for
the sentinels appearing as **constants** — the failure mode where a
``float()`` snapshot silently bakes one axis value into the compiled
program (every other point of the axis then reuses the wrong constant).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Optional, Sequence, Tuple

import numpy as np

from repro.analysis.findings import Finding

#: the monitoring event jax records once per XLA backend compilation
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_compiles = 0
_listener_installed = False


def _install_listener() -> None:
    global _listener_installed
    if _listener_installed:
        return
    import jax.monitoring

    def _on_event(name: str, secs: float, **kw) -> None:
        global _compiles
        if name == _COMPILE_EVENT:
            _compiles += 1

    jax.monitoring.register_event_duration_secs_listener(_on_event)
    _listener_installed = True


class compile_counter:
    """Counts XLA backend compilations inside a ``with`` block.

    Process-wide (every jit in the block counts, including incidental
    eager-op compiles), so contracts using it should compare counts
    between workloads rather than pin small absolute numbers.
    """

    def __enter__(self) -> "compile_counter":
        _install_listener()
        self._start = _compiles
        return self

    def __exit__(self, *exc) -> None:
        pass

    @property
    def count(self) -> int:
        return _compiles - self._start


def jit_cache_size(fn) -> int:
    """Number of compiled signatures held by a ``jax.jit`` wrapper."""
    size = getattr(fn, "_cache_size", None)
    if size is None:
        raise ValueError(
            f"{fn!r} exposes no jit compilation cache; contract entries "
            f"must be jax.jit-wrapped callables")
    return size()


@dataclasses.dataclass(frozen=True)
class CompileContract:
    """One declared compilation budget for an entry point.

    Static (sweep-structural) fields — require ``sweep`` + ``evaluator``:

    ``max_groups`` / ``min_groups``
        bounds on the :func:`compile_groups` partition of the expanded
        grid (min catches *merging* regressions: ``r_hat == 0`` traced
        to zero instead of split static would fuse two programs that
        must stay distinct).
    ``expect_dynamic``
        when given, the set of allowed per-group traced-field name
        tuples; every group's dyn names must be one of them.
    ``require_dynamic``
        field paths that must be traced in at least one group (the
        "this axis really batches" half of the pin).

    Trace (compilation-counting) fields:

    ``run``
        the workload.  May return a list of violation strings (e.g.
        from :func:`traced_constant_violations`) — non-empty fails the
        contract.
    ``warmup``
        executed before counting starts (e.g. compile the first point;
        the contract then bounds what the *rest* of the grid adds).
    ``entries``
        zero-arg callable returning the jitted entry points whose cache
        sizes are summed after ``run`` — exact per-entry-point counting
        (``ServeRuntime``'s decode step: 1 across a whole ragged trace).
    ``max_compiles``
        budget for ``entries`` cache sizes, or for the
        :class:`compile_counter` total during ``run`` when no
        ``entries`` are named.  ``None`` skips counting (contracts that
        only use ``run``'s returned violations).
    """

    name: str
    description: str = ""
    # static level
    sweep: Optional[Any] = None                  # SweepSpec
    evaluator: Optional[Callable[[], Any]] = None
    max_groups: Optional[int] = None
    min_groups: Optional[int] = None
    expect_dynamic: Optional[Tuple[Tuple[str, ...], ...]] = None
    require_dynamic: Tuple[str, ...] = ()
    # trace level
    run: Optional[Callable[[], Any]] = None
    warmup: Optional[Callable[[], Any]] = None
    entries: Optional[Callable[[], Sequence[Any]]] = None
    max_compiles: Optional[int] = None

    def declares_static(self) -> bool:
        return self.sweep is not None

    def declares_trace(self) -> bool:
        return self.run is not None


def _static_findings(c: CompileContract) -> List[Finding]:
    from repro.sweep.executor import compile_groups
    from repro.sweep.results import device_tag, point_key

    ev = c.evaluator()
    pts = c.sweep.expand()
    proto = c.sweep.point_protocol()
    dev = device_tag()
    groups = compile_groups(
        [(point_key(ev.signature(), p, proto, dev), p) for p in pts], ev)
    out: List[Finding] = []
    where = f"contract {c.name!r}"
    if c.max_groups is not None and len(groups) > c.max_groups:
        out.append(Finding(
            "compile-contract", where, 0,
            f"{len(pts)}-point grid partitions into {len(groups)} compile "
            f"groups, budget is {c.max_groups} — an axis declared traced "
            f"is recompiling per value"))
    if c.min_groups is not None and len(groups) < c.min_groups:
        out.append(Finding(
            "compile-contract", where, 0,
            f"grid partitions into {len(groups)} compile groups, expected "
            f"at least {c.min_groups} — a static program-structure split "
            f"(e.g. parasitics on/off) is being traced away"))
    dyn_seen = {dyn_names for _, dyn_names, _ in groups}
    if c.expect_dynamic is not None:
        allowed = {tuple(t) for t in c.expect_dynamic}
        for names in sorted(dyn_seen):
            if names not in allowed:
                out.append(Finding(
                    "compile-contract", where, 0,
                    f"group traces fields {names!r}, allowed sets are "
                    f"{sorted(allowed)!r}"))
    for path in c.require_dynamic:
        if not any(path in names for names in dyn_seen):
            out.append(Finding(
                "compile-contract", where, 0,
                f"field {path!r} is declared traced but appears in no "
                f"group's dynamic names — its axis recompiles per value"))
    return out


def _trace_findings(c: CompileContract) -> List[Finding]:
    where = f"contract {c.name!r}"
    out: List[Finding] = []
    if c.warmup is not None:
        c.warmup()
    with compile_counter() as counter:
        violations = c.run() if c.run is not None else None
    if isinstance(violations, (list, tuple)):
        out.extend(Finding("compile-contract", where, 0, str(v))
                   for v in violations)
    if c.max_compiles is not None:
        if c.entries is not None:
            n = sum(jit_cache_size(fn) for fn in c.entries())
            kind = "entry-point jit cache holds"
        else:
            n = counter.count
            kind = "workload performed"
        if n > c.max_compiles:
            out.append(Finding(
                "compile-contract", where, 0,
                f"{kind} {n} compilations, budget is {c.max_compiles}"))
    return out


def check_contract(c: CompileContract,
                   level: str = "static") -> List[Finding]:
    """Verify one contract; returns violations (empty = holds)."""
    if level not in ("static", "trace"):
        raise ValueError(f"level must be 'static' or 'trace', got {level!r}")
    if level == "static":
        if not c.declares_static():
            return []
        return _static_findings(c)
    if not c.declares_trace():
        return []
    return _trace_findings(c)


def check_contracts(contracts: Sequence[CompileContract],
                    level: str = "static") -> List[Finding]:
    out: List[Finding] = []
    for c in contracts:
        out.extend(check_contract(c, level))
    return out


# ---------------------------------------------------------------------------
# traced-field / jaxpr-constant verification
# ---------------------------------------------------------------------------


def jaxpr_scalar_constants(closed) -> List[float]:
    """Every scalar float constant in a closed jaxpr, sub-jaxprs included."""
    import jax.extend.core as jex_core

    out: List[float] = []

    def add(v) -> None:
        arr = np.asarray(v)
        if arr.ndim == 0 and np.issubdtype(arr.dtype, np.floating):
            out.append(float(arr))

    def visit_jaxpr(jaxpr) -> None:
        for eqn in jaxpr.eqns:
            for var in eqn.invars:
                if isinstance(var, jex_core.Literal):
                    add(var.val)
            for p in eqn.params.values():
                for sub in _sub_jaxprs(p):
                    visit_jaxpr(sub)

    def _sub_jaxprs(p):
        if isinstance(p, jex_core.ClosedJaxpr):
            for cv in p.consts:
                add(cv)
            yield p.jaxpr
        elif isinstance(p, jex_core.Jaxpr):
            yield p
        elif isinstance(p, (tuple, list)):
            for item in p:
                yield from _sub_jaxprs(item)

    for cv in closed.consts:
        add(cv)
    visit_jaxpr(closed.jaxpr)
    return out


#: sentinel magnitudes for traced-field checks: distinctive, finite, and
#: never arising from shape arithmetic
TRACE_SENTINELS = (0.0123456789, 0.0987654321, 0.0246813579, 0.0135792468)


def traced_constant_violations(fn: Callable, args: Sequence[Any],
                               sentinels: Sequence[float],
                               label: str = "") -> List[str]:
    """Trace ``fn(*args)`` and report sentinels baked in as constants.

    ``args`` carries the sentinel values in the positions the entry
    point declares traced; if any sentinel value appears as a jaxpr
    *constant*, the value leaked out of the traced path (a ``float()``
    snapshot, a Python-side branch) and every other axis value would
    silently reuse the compiled point's number.
    """
    import jax

    closed = jax.make_jaxpr(fn)(*args)
    consts = jaxpr_scalar_constants(closed)
    out = []
    for s in sentinels:
        if any(abs(cv - s) < 1e-12 for cv in consts):
            out.append(
                f"{label or getattr(fn, '__name__', 'entry point')}: traced "
                f"field value {s} appears as a jaxpr constant — the field "
                f"is being snapshotted out of the traced path")
    return out
