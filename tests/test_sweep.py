"""Sweep-engine tests: grid expansion, compile-group batching, the
vectorized-vs-serial equivalence contract, and cache resume.

The equivalence tests are the acceptance gate for the engine: the same
seeds must produce the same accuracies as the legacy per-point serial
loop.  ADC-free paths are bit-exact; calibrated-ADC paths with traced
dynamic scalars are allowed isolated ADC-rounding-boundary flips
(DESIGN.md §Sweep-engine), bounded here to a few test samples."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.adc import ADCConfig
from repro.core.analog import AnalogSpec, program, program_codes, program_from_codes
from repro.core.errors import state_independent, state_proportional
from repro.core.mapping import MappingConfig
from repro.analysis import CompileContract, check_contract
from repro.sweep import (
    Axis,
    ClassifierEvaluator,
    FunctionEvaluator,
    SweepSpec,
    compile_groups,
    device_tag,
    point_key,
    run_sweep,
    serial_accuracy,
)


@pytest.fixture(scope="module")
def vehicle():
    """Tiny random classifier + splits (the pipeline, not the training)."""
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    dims = (16, 32, 8)
    layers = [
        (jax.random.normal(ks[i], (dims[i], dims[i + 1])) * dims[i] ** -0.5,
         jnp.zeros((dims[i + 1],)))
        for i in range(2)
    ]
    xca = jax.random.normal(ks[3], (64, 16))
    xte = jax.random.normal(ks[4], (128, 16))
    yte = jax.random.randint(ks[5], (128,), 0, 8)
    return layers, xca, xte, yte


def _evaluator(vehicle):
    return ClassifierEvaluator(*vehicle)


# ---------------------------------------------------------------------------
# spec expansion
# ---------------------------------------------------------------------------

def test_expand_cartesian_and_zipped():
    sweep = SweepSpec(
        name="t",
        base=AnalogSpec(adc=ADCConfig(style="none")),
        axes=(
            Axis(("mapping.scheme", "input_accum"),
                 (("differential", "analog"), ("offset", "digital")),
                 labels=("diff", "off")),
            Axis("adc.bits", (6, 8)),
        ),
    )
    pts = sweep.expand()
    assert len(pts) == 4
    assert [p.tag for p in pts] == ["diff_bits6", "diff_bits8",
                                    "off_bits6", "off_bits8"]
    assert pts[0].spec.mapping.scheme == "differential"
    assert pts[0].spec.input_accum == "analog"
    assert pts[2].spec.input_accum == "digital"
    assert pts[3].spec.adc.bits == 8
    assert pts[1].coord("adc.bits") == 8


def test_expand_explicit_points():
    sweep = SweepSpec.from_points(
        "t", [("A", AnalogSpec()), ("B", AnalogSpec(max_rows=72))])
    pts = sweep.expand()
    assert [p.tag for p in pts] == ["A", "B"]
    assert pts[1].spec.max_rows == 72


# ---------------------------------------------------------------------------
# compile-group batching
# ---------------------------------------------------------------------------

def _alpha_sweep():
    return SweepSpec(
        name="t",
        base=AnalogSpec(adc=ADCConfig(style="none"),
                        error=state_proportional(0.0)),
        axes=(Axis("error.alpha", (0.01, 0.02, 0.05, 0.1)),),
        trials=2,
    )


def test_alpha_grid_is_one_compile_group(vehicle):
    """Declared as a CompileContract (repro.analysis): 4 alpha values,
    one compiled program, alpha traced."""
    c = CompileContract(
        name="test/alpha-axis",
        sweep=_alpha_sweep(),
        evaluator=lambda: _evaluator(vehicle),
        max_groups=1,
        expect_dynamic=(("error.alpha",),),
        require_dynamic=("error.alpha",),
    )
    assert check_contract(c, "static") == []


def test_constant_dynamic_field_stays_static(vehicle):
    """A field that does not vary must not be traced (bit-exactness)."""
    c = CompileContract(
        name="test/constant-field-static",
        sweep=SweepSpec(
            name="t",
            base=AnalogSpec(adc=ADCConfig(style="none"),
                            error=state_proportional(0.05)),
            axes=(Axis("max_rows", (72, 1152)),),
            trials=1,
        ),
        evaluator=lambda: _evaluator(vehicle),
        # max_rows is static: separate shapes; alpha/on_off constant ->
        # not dynamic in either group
        max_groups=2, min_groups=2,
        expect_dynamic=((),),
    )
    assert check_contract(c, "static") == []


def test_r_hat_axis_is_one_compile_group(vehicle):
    """The Fig. 19 parasitic axis batches as a traced scalar: every
    ``r_hat > 0`` level shares one compiled program (the tridiagonal solve
    is structurally identical), instead of one compile group per level."""
    c = CompileContract(
        name="test/r-hat-axis",
        sweep=SweepSpec(
            name="t",
            base=AnalogSpec(adc=ADCConfig(style="none"), max_rows=64),
            axes=(Axis("r_hat", (1e-5, 1e-4, 1e-3)),),
            trials=1,
        ),
        evaluator=lambda: _evaluator(vehicle),
        max_groups=1,
        require_dynamic=("r_hat",),
    )
    assert check_contract(c, "static") == []


def test_r_hat_on_off_split_is_static(vehicle):
    """``r_hat == 0`` is a different compiled program (no solve in the
    graph): it must land in its own group, never be traced to zero."""
    c = CompileContract(
        name="test/r-hat-on-off-split",
        sweep=SweepSpec(
            name="t",
            base=AnalogSpec(adc=ADCConfig(style="none"), max_rows=64),
            axes=(Axis("r_hat", (0.0, 1e-4, 1e-3)),),
            trials=1,
        ),
        evaluator=lambda: _evaluator(vehicle),
        max_groups=2, min_groups=2,
        expect_dynamic=((), ("r_hat",)),
        require_dynamic=("r_hat",),
    )
    assert check_contract(c, "static") == []


def test_compile_contract_canary(vehicle):
    """The checker validated against the old method: the original
    hand-written compile_groups assertions for the alpha grid, side by
    side with the CompileContract declaration of the same pin — and a
    falsified declaration must fail."""
    import dataclasses

    ev = _evaluator(vehicle)
    sweep = _alpha_sweep()
    pts = sweep.expand()
    groups = compile_groups(
        [(point_key(ev.signature(), p, sweep.point_protocol(),
                    device_tag()), p)
         for p in pts], ev)
    # the original PR 3 pin, verbatim
    assert len(groups) == 1
    _, dyn_names, members = groups[0]
    assert dyn_names == ("error.alpha",)
    assert len(members) == 4
    # the declaration agrees with the raw partition
    c = CompileContract(
        name="test/canary", sweep=sweep,
        evaluator=lambda: _evaluator(vehicle),
        max_groups=1, expect_dynamic=(("error.alpha",),),
        require_dynamic=("error.alpha",))
    assert check_contract(c, "static") == []
    # and the checker actually discriminates: tighten the budget past
    # what the raw partition shows and it must report
    wrong = dataclasses.replace(c, max_groups=0)
    assert len(check_contract(wrong, "static")) == 1


# ---------------------------------------------------------------------------
# vectorized == serial
# ---------------------------------------------------------------------------

def test_vectorized_matches_serial_bitexact_no_adc(vehicle):
    layers, xca, xte, yte = vehicle
    sweep = SweepSpec(
        name="t",
        base=AnalogSpec(
            mapping=MappingConfig(scheme="differential"),
            adc=ADCConfig(style="none"),
            error=state_proportional(0.0),
            input_accum="analog",
        ),
        axes=(
            Axis("error.alpha", (0.02, 0.1)),
            Axis("mapping.on_off_ratio", (100.0, float("inf"))),
        ),
        trials=3,
        seed=7,
    )
    res = run_sweep(sweep, _evaluator(vehicle))
    pts = sweep.expand()
    assert len(res) == 4
    for r in res:
        _, _, accs = serial_accuracy(
            layers, pts[r.index].spec, xca, xte, yte, trials=3, seed=7)
        np.testing.assert_array_equal(np.asarray(r.values), np.asarray(accs))


def test_vectorized_matches_serial_fig19_r_hat_axis(vehicle):
    """Fig. 19 at engine scale: the whole parasitic axis runs as one
    compile group with ``r_hat`` traced, and still reproduces the serial
    per-point loop bit-exactly (ADC-free path)."""
    layers, xca, xte, yte = vehicle
    sweep = SweepSpec(
        name="t",
        base=AnalogSpec(
            mapping=MappingConfig(scheme="differential", on_off_ratio=1e4),
            adc=ADCConfig(style="none"),
            error=state_proportional(0.02),
            input_accum="analog",
            max_rows=64,
        ),
        axes=(Axis("r_hat", (1e-5, 1e-4, 1e-3)),),
        trials=2,
        seed=7,
    )
    res = run_sweep(sweep, _evaluator(vehicle))
    pts = sweep.expand()
    assert len(res) == 3
    for r in res:
        _, _, accs = serial_accuracy(
            layers, pts[r.index].spec, xca, xte, yte, trials=2, seed=7)
        np.testing.assert_array_equal(np.asarray(r.values), np.asarray(accs))


def test_vectorized_matches_serial_calibrated_adc(vehicle):
    layers, xca, xte, yte = vehicle
    sweep = SweepSpec(
        name="t",
        base=AnalogSpec(
            mapping=MappingConfig(scheme="offset", bits_per_cell=2,
                                  on_off_ratio=1e4),
            adc=ADCConfig(style="calibrated", bits=8),
            error=state_independent(0.0),
            input_accum="digital",
            max_rows=72,
        ),
        axes=(Axis("error.alpha", (0.01, 0.05)),),
        trials=2,
        seed=7,
    )
    res = run_sweep(sweep, _evaluator(vehicle))
    pts = sweep.expand()
    # traced-alpha batching may flip isolated ADC rounding boundaries:
    # allow up to 2 of 128 test samples per trial, no more.
    tol = 2.0 / xte.shape[0] + 1e-9
    for r in res:
        _, _, accs = serial_accuracy(
            layers, pts[r.index].spec, xca, xte, yte, trials=2, seed=7)
        np.testing.assert_allclose(np.asarray(r.values), np.asarray(accs),
                                   atol=tol)


def test_program_split_is_identity(vehicle):
    layers, _, _, _ = vehicle
    w = layers[0][0]
    spec = AnalogSpec(
        mapping=MappingConfig(scheme="differential", bits_per_cell=2,
                              on_off_ratio=1e3),
        error=state_proportional(0.05),
    )
    key = jax.random.PRNGKey(3)
    direct = program(w, spec, key)
    split = program_from_codes(program_codes(w, spec), spec, key)
    np.testing.assert_array_equal(np.asarray(direct.g_pos),
                                  np.asarray(split.g_pos))
    np.testing.assert_array_equal(np.asarray(direct.g_neg),
                                  np.asarray(split.g_neg))


# ---------------------------------------------------------------------------
# cache resume
# ---------------------------------------------------------------------------

class _CountingEvaluator:
    """Delegates to a real evaluator, counting group evaluations."""

    def __init__(self, inner):
        self.inner = inner
        self.calls = 0

    def signature(self):
        return self.inner.signature()

    def dynamic_fields(self, spec):
        return self.inner.dynamic_fields(spec)

    def evaluate_group(self, *a, **kw):
        self.calls += 1
        return self.inner.evaluate_group(*a, **kw)


def _cache_sweep():
    return SweepSpec(
        name="cache_t",
        base=AnalogSpec(adc=ADCConfig(style="none"),
                        error=state_proportional(0.0)),
        axes=(Axis("error.alpha", (0.02, 0.1)),),
        trials=2,
    )


def test_resume_from_cache(vehicle, tmp_path):
    ev = _CountingEvaluator(_evaluator(vehicle))
    res1 = run_sweep(_cache_sweep(), ev, cache_dir=str(tmp_path))
    assert ev.calls == 1
    assert res1.n_cached == 0
    assert (tmp_path / "sweeps" / "cache_t.json").exists()

    # same sweep, fresh run: everything served from disk
    res2 = run_sweep(_cache_sweep(), ev, cache_dir=str(tmp_path))
    assert ev.calls == 1              # no new group evaluations
    assert res2.n_cached == 2
    for r1, r2 in zip(res1, res2):
        assert r1.values == r2.values
        assert r1.tag == r2.tag

    # widened grid: only the new point runs
    wider = dataclasses.replace(
        _cache_sweep(), axes=(Axis("error.alpha", (0.02, 0.1, 0.2)),))
    res3 = run_sweep(wider, ev, cache_dir=str(tmp_path))
    assert ev.calls == 2
    assert res3.n_cached == 2
    assert len(res3) == 3

    # force recomputes everything and agrees with the cached values
    res4 = run_sweep(_cache_sweep(), ev, cache_dir=str(tmp_path), force=True)
    assert ev.calls == 3
    for r1, r4 in zip(res1, res4):
        assert r1.values == r4.values


def test_cache_misses_on_evaluator_signature_change(vehicle, tmp_path):
    """A version bump in the evaluator signature must invalidate points."""
    ev1 = _CountingEvaluator(ClassifierEvaluator(*vehicle, version="v1"))
    run_sweep(_cache_sweep(), ev1, cache_dir=str(tmp_path))
    assert ev1.calls == 1

    ev2 = _CountingEvaluator(ClassifierEvaluator(*vehicle, version="v2"))
    res = run_sweep(_cache_sweep(), ev2, cache_dir=str(tmp_path))
    assert ev2.calls == 1, "changed signature must miss the cache"
    assert res.n_cached == 0


def test_cache_misses_on_spec_change(vehicle, tmp_path):
    """Any spec field outside the axes must be part of the cache key."""
    ev = _CountingEvaluator(_evaluator(vehicle))
    run_sweep(_cache_sweep(), ev, cache_dir=str(tmp_path))
    assert ev.calls == 1

    changed = dataclasses.replace(
        _cache_sweep(),
        base=dataclasses.replace(_cache_sweep().base, input_bits=7))
    res = run_sweep(changed, ev, cache_dir=str(tmp_path))
    assert ev.calls == 2, "changed base spec must miss the cache"
    assert res.n_cached == 0


def test_cache_misses_on_device_change(vehicle, tmp_path, monkeypatch):
    """A result computed on one kind of device is never read back on
    another: the device is part of every point's cache key."""
    import repro.sweep.executor as executor

    ev = _CountingEvaluator(_evaluator(vehicle))
    run_sweep(_cache_sweep(), ev, cache_dir=str(tmp_path))
    assert ev.calls == 1
    assert device_tag() == "cpu/cpu"
    monkeypatch.setattr(executor, "device_tag", lambda: "tpu/TPU v5 lite")
    res = run_sweep(_cache_sweep(), ev, cache_dir=str(tmp_path))
    assert ev.calls == 2, "another device must miss the cache"
    assert res.n_cached == 0
    monkeypatch.undo()
    res = run_sweep(_cache_sweep(), ev, cache_dir=str(tmp_path))
    assert ev.calls == 2 and res.n_cached == 2   # both devices kept


def test_cache_misses_on_trial_protocol_change(vehicle, tmp_path):
    """trials / seed / test_n are part of a point's cache identity."""
    ev = _CountingEvaluator(_evaluator(vehicle))
    run_sweep(_cache_sweep(), ev, cache_dir=str(tmp_path))
    calls = ev.calls
    for change in (dict(trials=3), dict(seed=99), dict(test_n=32)):
        res = run_sweep(
            dataclasses.replace(_cache_sweep(), **change), ev,
            cache_dir=str(tmp_path))
        calls += 1
        assert ev.calls == calls, f"{change} must miss the cache"
        assert res.n_cached == 0


def test_cache_hits_on_axis_reordering(vehicle, tmp_path):
    """Reordering unrelated grid factors yields the same spec set and
    must be served fully from cache (identity is the spec, not the tag
    or expansion order)."""
    ab = SweepSpec(
        name="reorder_t",
        base=AnalogSpec(adc=ADCConfig(style="none"),
                        error=state_proportional(0.0)),
        axes=(Axis("error.alpha", (0.02, 0.1)),
              Axis("max_rows", (72, 1152))),
        trials=1,
    )
    ba = dataclasses.replace(ab, axes=tuple(reversed(ab.axes)))
    ev = _CountingEvaluator(_evaluator(vehicle))
    res1 = run_sweep(ab, ev, cache_dir=str(tmp_path))
    calls = ev.calls
    res2 = run_sweep(ba, ev, cache_dir=str(tmp_path))
    assert ev.calls == calls, "reordered axes must hit the cache"
    assert res2.n_cached == len(res2) == 4
    by_spec1 = {repr(ab.expand()[r.index].spec): r.values for r in res1}
    by_spec2 = {repr(ba.expand()[r.index].spec): r.values for r in res2}
    assert by_spec1 == by_spec2


def test_function_evaluator_vmapped_trials(tmp_path):
    def probe(spec, key):
        return jax.random.normal(key, ()) * 0.0 + spec.mapping.g_min

    sweep = SweepSpec(
        name="fn_t",
        base=AnalogSpec(),
        axes=(Axis("mapping.on_off_ratio", (10.0, 100.0)),),
        trials=3,
    )
    ev = FunctionEvaluator(probe, name="probe", takes_key=True)
    res = run_sweep(sweep, ev, cache_dir=str(tmp_path))
    assert len(res) == 2
    assert res["on_off_ratio10"].values == pytest.approx([0.1] * 3)
    assert res["on_off_ratio100"].values == pytest.approx([0.01] * 3)
    # resume: no recomputation, same values
    res2 = run_sweep(sweep, ev, cache_dir=str(tmp_path))
    assert res2.n_cached == 2
    assert res2["on_off_ratio10"].values == res["on_off_ratio10"].values
