"""Kernel microbenchmarks + sweep-engine wall-clock comparison.

Part 1: Pallas (interpret on CPU) vs jnp oracle at serving-relevant
shapes.  On TPU the same entry points compile to Mosaic; interpret-mode
timings only demonstrate correctness-path overhead, the derived column
carries the work sizes.

Part 2: the tentpole speedup measurement — a 16-point design grid
(2 mapping schemes x 8 error magnitudes, 3 programming trials each)
evaluated (a) by the legacy serial per-point loop the benchmarks used to
hand-roll (``repro.sweep.serial_accuracy``, one eager trial at a time)
and (b) by the vectorized sweep engine (trials vmapped, same-shape
points batched as traced scalars, one jitted call per scheme).  Emits
both wall-clocks and the speedup.

Part 3: the parasitic bit-line production path — (a) the Pallas Thomas
kernel vs the dense vmap-of-scan solve on an (M, N, K) grid, (b) the
fused parasitic Design-A kernel vs its jnp oracle, and (c) the Fig. 19
grid vectorized (one compile group per scheme, ``r_hat`` traced) vs the
legacy serial per-level loop — each row carries the speedup in the
derived column.

Part 4 (also the ``--smoke`` payload, alongside the paged-decode gate):
the fused decode chain — ``analog_matmul`` routed through the
single-launch fused kernels vs the legacy composed per-slice/per-bit
chain at serving decode shapes, parity- and speedup-gated, plus the
flash-decode attention kernel vs its chunked-gather oracle."""

import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.core.adc import ADCConfig
from repro.core.analog import AnalogSpec
from repro.core.errors import state_proportional
from repro.core.mapping import MappingConfig
from repro.core.parasitics import bitline_currents
from repro.kernels import ops, ref
from repro.sweep import Axis, SweepSpec

from benchmarks.common import (
    Timer, analog_accuracy, emit, eval_data, run_bench_sweep,
    surface_error, train_mlp)


def kernel_micro(timer: Timer):
    for (m, p, rows, n) in [(128, 1, 1152, 256), (256, 2, 1152, 512)]:
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        x = jnp.round(jax.random.normal(ks[0], (m, p, rows)) * 40)
        gp = jax.random.uniform(ks[1], (p, rows, n)) * 0.1
        gm = jax.random.uniform(ks[2], (p, rows, n)) * 0.1
        lo, hi = jnp.float32(-50.0), jnp.float32(50.0)
        args = dict(adc_lo=lo, adc_hi=hi, adc_bits=8, gain=127.0)
        f_k = jax.jit(lambda x, gp, gm: ops.analog_mvm(x, gp, gm, **args))
        f_r = jax.jit(lambda x, gp, gm: ref.analog_mvm_diff(x, gp, gm, **args))
        us_k = timer.time(f_k, x, gp, gm)
        us_r = timer.time(f_r, x, gp, gm)
        macs = m * p * rows * n
        emit(f"kernel_analog_mvm_{m}x{p}x{rows}x{n}", us_k,
             f"ref_us={us_r:.1f} macs={macs} interpret=True")

        fb_k = jax.jit(lambda x, gp, gm: ops.analog_mvm_bitserial(
            x, gp, gm, n_bits=7, **args))
        fb_r = jax.jit(lambda x, gp, gm: ref.analog_mvm_bitserial(
            x, gp, gm, n_bits=7, **args))
        us_bk = timer.time(fb_k, x, gp, gm)
        us_br = timer.time(fb_r, x, gp, gm)
        emit(f"kernel_bitserial_{m}x{p}x{rows}x{n}", us_bk,
             f"ref_us={us_br:.1f} bits=7 (in-VMEM planes vs 8x HBM planes)")

    for (m, k, n, r) in [(128, 1152, 128, 1e-5)]:
        kx, kg = jax.random.split(jax.random.PRNGKey(1), 2)
        x = jnp.sign(jax.random.normal(kx, (m, k)))
        g = jax.random.uniform(kg, (k, n))
        f_k = jax.jit(lambda g, x: ops.bitline_mvm(g, x, r))
        f_r = jax.jit(lambda g, x: ref.bitline_mvm(g, x, r))
        us_k = timer.time(f_k, g, x)
        us_r = timer.time(f_r, g, x)
        emit(f"kernel_bitline_{m}x{k}x{n}", us_k,
             f"ref_us={us_r:.1f} tridiag_solves={m*n}")


def paged_decode_bench(timer: Timer):
    """Paged-attention decode grid vs the jnp gather oracle.

    Long-cache decode shapes (B rows x 1 query token x K cached tokens):
    the block-table walk the serving runtime's ``backend="pallas"`` path
    runs every step.  Bitwise equality against
    ``ref.paged_attention_decode`` is a *gate* — any mismatch raises and
    fails the benchmark run, mirroring the ``array_equal`` pin in
    ``tests/test_kernels.py`` at larger shapes."""
    import numpy as np

    # (B, KV heads, group, head dim, page size, pages per row)
    shapes = [
        (1, 4, 2, 64, 8, 16),    # single row, 128-token cache
        (4, 4, 2, 64, 8, 16),
        (8, 2, 4, 64, 8, 32),    # 256-token cache, GQA 4x
        (4, 8, 1, 32, 16, 16),   # MHA, 256-token cache, big pages
    ]
    for (b, kv, g, hd, ps, npg) in shapes:
        h = kv * g
        pool = 1 + b * npg
        rng = np.random.default_rng(b * npg)
        ks = jax.random.split(jax.random.PRNGKey(b + npg), 3)
        q = jax.random.normal(ks[0], (b, h, hd), jnp.float32)
        kp = jax.random.normal(ks[1], (pool, ps, kv, hd), jnp.float32)
        vp = jax.random.normal(ks[2], (pool, ps, kv, hd), jnp.float32)
        perm = rng.permutation(np.arange(1, pool))
        ptab = np.zeros((b, npg), np.int32)
        kv_len = np.zeros((b,), np.int32)
        for i in range(b):       # ragged fills, shuffled non-sink pages
            n = int(rng.integers(ps, npg * ps + 1))
            used = -(-n // ps)
            ptab[i, :used] = perm[i * npg:i * npg + used]
            kv_len[i] = n
        ptab, kv_len = jnp.asarray(ptab), jnp.asarray(kv_len)
        f_k = jax.jit(lambda q, kp, vp, t, l: ops.paged_attention(
            q, kp, vp, t, l))
        f_r = jax.jit(lambda q, kp, vp, t, l: ref.paged_attention_decode(
            q, kp, vp, t, l))
        out_k = f_k(q, kp, vp, ptab, kv_len)
        out_r = f_r(q, kp, vp, ptab, kv_len)
        if not np.array_equal(np.asarray(out_k), np.asarray(out_r)):
            bad = int(np.sum(np.asarray(out_k) != np.asarray(out_r)))
            raise RuntimeError(
                f"paged decode kernel diverged from gather oracle at "
                f"B={b} KV={kv} g={g} hd={hd} ps={ps} NP={npg}: "
                f"{bad} mismatched elements")
        us_k = timer.time(f_k, q, kp, vp, ptab, kv_len)
        us_r = timer.time(f_r, q, kp, vp, ptab, kv_len)
        emit(f"kernel_paged_decode_b{b}_k{kv}x{g}x{hd}_p{ps}x{npg}", us_k,
             f"ref_us={us_r:.1f} cache_toks={int(np.max(kv_len))} "
             f"bitwise=True interpret=True")


def bitline_bench(timer: Timer):
    """Pallas bit-line solve vs the dense vmap-of-scan reference, plus the
    fused parasitic Design-A kernel, on an (M, N, K) grid."""
    r = 1e-4
    for (m, n, k) in [(128, 128, 256), (128, 128, 1152), (256, 256, 576)]:
        kx, kg = jax.random.split(jax.random.PRNGKey(k), 2)
        x = jnp.sign(jax.random.normal(kx, (m, k)))
        g = jax.random.uniform(kg, (k, n))
        f_k = jax.jit(lambda g, x: ops.bitline_mvm(g, x, r))
        f_d = jax.jit(lambda g, x: bitline_currents(g, x, r))
        us_k = timer.time(f_k, g, x)
        us_d = timer.time(f_d, g, x)
        emit(f"bitline_pallas_{m}x{n}x{k}", us_k,
             f"dense_us={us_d:.1f} speedup={us_d / max(us_k, 1e-9):.2f}x "
             f"tridiag_solves={m * n} depth={k} interpret=True")

    for (m, p, rows, n) in [(128, 1, 256, 128), (128, 2, 576, 128)]:
        ks = jax.random.split(jax.random.PRNGKey(m + rows), 3)
        x = jnp.round(jax.random.normal(ks[0], (m, p, rows)) * 40)
        gp = jax.random.uniform(ks[1], (p, rows, n)) * 0.1
        gm = jax.random.uniform(ks[2], (p, rows, n)) * 0.1
        args = dict(r_hat=r, n_bits=7, adc_lo=jnp.float32(-50.0),
                    adc_hi=jnp.float32(50.0), adc_bits=8, gain=127.0)
        f_k = jax.jit(lambda x, gp, gm: ops.analog_mvm_parasitic(
            x, gp, gm, **args))
        f_r = jax.jit(lambda x, gp, gm: ref.analog_mvm_parasitic_diff(
            x, gp, gm, **args))
        us_k = timer.time(f_k, x, gp, gm)
        us_r = timer.time(f_r, x, gp, gm)
        emit(f"bitline_fused_diff_{m}x{p}x{rows}x{n}", us_k,
             f"ref_us={us_r:.1f} speedup={us_r / max(us_k, 1e-9):.2f}x "
             f"bits=7 interpret=True")


def fig19_engine_speedup():
    """Fig. 19 batched (traced r_hat, one compile group per scheme) vs the
    pre-dynamic-r_hat behavior (every parasitic level its own compiled
    program) — the compile-amortization win the dynamic field buys.

    Both paths run the same vectorized evaluator; the serial arm feeds it
    one single-point sweep at a time, so ``r_hat`` is a constant in every
    group and each level pays its own tridiagonal-solve compilation —
    exactly how the grid executed when ``r_hat`` was a static field.
    (The fully-eager legacy loop is minutes per parasitic point; see
    ``sweep_engine_speedup`` for that comparison on the error grid.)
    """
    from benchmarks.fig19_parasitics import fig19_sweep

    train_mlp()
    eval_data()
    sweep = fig19_sweep((1e-5, 3e-5, 1e-4, 3e-4, 1e-3), trials=1,
                        test_n=32)
    points = sweep.expand()

    t0 = time.perf_counter()
    per_point = {}
    for pt in points:
        one = SweepSpec(name=f"fig19_pt{pt.index}", base=pt.spec,
                        trials=sweep.trials, seed=sweep.seed,
                        test_n=sweep.test_n)
        per_point[pt.tag] = run_bench_sweep(one, cache=False).results[0].mean
    t_serial = time.perf_counter() - t0         # one compile per level

    t0 = time.perf_counter()
    res = run_bench_sweep(sweep, cache=False)
    t_cold = time.perf_counter() - t0           # 2 compiles, all levels

    t0 = time.perf_counter()
    run_bench_sweep(sweep, cache=False)         # compiled fns reused
    t_warm = time.perf_counter() - t0

    max_dev = max(abs(res.mean(tag) - acc) for tag, acc in per_point.items())
    n = len(points)
    emit("fig19_per_point_compile", t_serial * 1e6,
         f"points={n} wall_s={t_serial:.2f} (one compile per r_hat level)")
    emit("fig19_batched_cold", t_cold * 1e6,
         f"points={n} wall_s={t_cold:.2f} (r_hat traced: 2 compile groups)")
    emit("fig19_batched_warm", t_warm * 1e6,
         f"points={n} wall_s={t_warm:.2f}")
    emit("fig19_speedup", 0.0,
         f"per_point={t_serial:.2f}s vs batched cold={t_cold:.2f}s "
         f"({t_serial / max(t_cold, 1e-9):.2f}x) / warm={t_warm:.2f}s "
         f"({t_serial / max(t_warm, 1e-9):.2f}x) max_acc_dev={max_dev:.4f}")


def sweep_engine_speedup():
    """Vectorized sweep engine vs the legacy serial loop, 16-point grid."""
    params = train_mlp()
    eval_data()   # warm the dataset cache so neither path pays for it
    alphas = (0.01, 0.02, 0.03, 0.04, 0.05, 0.06, 0.07, 0.08)
    trials = 3
    sweep = SweepSpec(
        name="kernelbench_grid",
        base=AnalogSpec(adc=ADCConfig(style="none"), max_rows=1152),
        axes=(
            Axis(("mapping.scheme", "input_accum"),
                 (("differential", "analog"), ("offset", "digital")),
                 labels=("differential", "offset")),
            Axis("error", tuple(state_proportional(a) for a in alphas),
                 labels=tuple(f"a{a}" for a in alphas)),
        ),
        trials=trials,
    )
    points = sweep.expand()

    t0 = time.perf_counter()
    res = run_bench_sweep(sweep, cache=False)   # no cache: honest timing
    t_cold = time.perf_counter() - t0           # includes jit compilation

    t0 = time.perf_counter()
    run_bench_sweep(sweep, cache=False)         # compiled fns reused
    t_warm = time.perf_counter() - t0

    t0 = time.perf_counter()
    serial = {pt.tag: analog_accuracy(params, pt.spec, trials=trials)[0]
              for pt in points}
    t_serial = time.perf_counter() - t0

    max_dev = max(abs(res.mean(tag) - acc) for tag, acc in serial.items())
    n = len(points)
    emit(f"sweep_vectorized_{n}pt_cold", t_cold * 1e6,
         f"points={n} trials={trials} wall_s={t_cold:.2f} "
         f"(includes compile)")
    emit(f"sweep_vectorized_{n}pt_warm", t_warm * 1e6,
         f"points={n} trials={trials} wall_s={t_warm:.2f}")
    emit(f"sweep_legacy_serial_{n}pt", t_serial * 1e6,
         f"points={len(points)} trials={trials} wall_s={t_serial:.2f}")
    emit("sweep_speedup", 0.0,
         f"serial={t_serial:.2f}s vs vectorized cold={t_cold:.2f}s "
         f"({t_serial / max(t_cold, 1e-9):.2f}x) / warm={t_warm:.2f}s "
         f"({t_serial / max(t_warm, 1e-9):.2f}x) "
         f"max_acc_dev={max_dev:.4f}")


#: fused decode grid: (tag, spec mutation, (M live lanes, K, N)).
#: M=8 is a full continuous-batching decode gang; K/N span the smoke
#: LM's MVM sites up to a max_rows-deep partition.
def _fused_decode_grid():
    from repro.core import analog as A
    from repro.core.errors import ErrorModel

    base = A.design_a(error=ErrorModel())
    sliced = dataclasses.replace(
        base, mapping=MappingConfig(scheme="differential", weight_bits=8,
                                    bits_per_cell=2, on_off_ratio=1e4))
    return [
        ("designA_8x256x256", base, (8, 256, 256)),
        ("designA_8x1152x512", base, (8, 1152, 512)),
        ("designA_P2_8x2304x256", base, (8, 2304, 256)),
        ("digital_8x256x256",
         dataclasses.replace(base, input_accum="digital"), (8, 256, 256)),
        ("sliced_8x576x512", sliced, (8, 576, 512)),
        ("parasitic_8x256x256",
         dataclasses.replace(base, r_hat=1e-4), (8, 256, 256)),
    ]


def fused_decode_bench(timer: Timer):
    """Fused decode chain vs the legacy composed ``analog_matmul`` at
    serving decode shapes — the single-launch-per-site-class payoff.

    Two *gates* (a failure raises; ``benchmarks.run`` exits nonzero):

      * parity — the fused Pallas kernel matches the fused jnp oracle
        within 2 float32 ULPs under jit at every grid point.  The oracle
        is the arithmetic spec of the kernel; XLA may contract the final
        dequant multiply differently per shape, which moves the last
        bit or two but can never flip an ADC code
        (``tests/test_fastpath_routing.py`` pins bitwise equality at the
        shapes the smoke LM actually serves);
      * speedup — the fused chain beats the composed per-slice/per-bit
        chain by >= 1.5x geometric mean, jitted and warm.  The fused arm
        is timed through its jnp lowering (``fused="oracle"``): off-TPU
        the Pallas kernel only runs under the interpreter, whose
        wall-clock measures the emulator, not the launch structure.
    """
    import numpy as np
    from repro.core import analog as A
    from repro.core.calibrate import calibrate_adc_for_matmul

    speedups = {}
    for tag, spec, (m, k, n) in _fused_decode_grid():
        kw_, kx = jax.random.split(jax.random.PRNGKey(k + n))
        w = jax.random.normal(kw_, (k, n)) * 0.1
        x = jax.random.normal(kx, (m, k))
        aw = A.program(w, spec, key=jax.random.PRNGKey(1))
        lo, hi = calibrate_adc_for_matmul(x, aw, spec)
        arms = {
            mode: jax.jit(lambda x, s=dataclasses.replace(spec, fused=mode):
                          A.analog_matmul(x, aw, s, adc_lo=lo, adc_hi=hi))
            for mode in ("off", "oracle", "kernel")
        }
        y_k = np.asarray(arms["kernel"](x))
        y_o = np.asarray(arms["oracle"](x))
        d = np.abs(y_k - y_o)
        mag = np.maximum(np.abs(y_k), np.abs(y_o))
        ulp = float(np.max(np.where(d > 0, d / np.spacing(
            mag.astype(np.float32)), 0.0)))
        if ulp > 2.0:
            raise RuntimeError(
                f"fused kernel diverged from its oracle at {tag}: "
                f"max {ulp:.1f} ULPs (>2) — not an fp-contraction artifact")
        us_c = timer.time(arms["off"], x)
        us_f = timer.time(arms["oracle"], x)
        speedups[tag] = us_c / max(us_f, 1e-9)
        emit(f"fused_decode_{tag}", us_f,
             f"composed_us={us_c:.1f} speedup={speedups[tag]:.2f}x "
             f"kernel_max_ulp={ulp:.1f} slices={aw.g_pos.shape[0]} "
             f"partitions={aw.g_pos.shape[1]}")
    geomean = float(np.exp(np.mean(np.log(list(speedups.values())))))
    emit("fused_decode_claim_speedup", 0.0,
         f"geomean={geomean:.2f}x over composed chain "
         f"(>=1.5 required): {geomean >= 1.5}")
    if geomean < 1.5:
        raise RuntimeError(
            f"fused decode chain speedup {geomean:.2f}x < 1.5x over the "
            f"composed analog_matmul chain: "
            + " ".join(f"{t}={s:.2f}x" for t, s in speedups.items()))


def flash_decode_bench(timer: Timer):
    """Flash-decode attention kernel vs its chunked-gather oracle on
    ragged dense decode caches.  Bitwise equality is a *gate* — the
    serving runtime's fused-vs-oracle agreement contract rests on it."""
    import numpy as np

    # (B rows, cache S, KV heads, GQA group, head dim); the cache in
    # bfloat16 as served, each chunk chosen by the kernel's shape rule
    shapes = [(4, 64, 4, 2, 64), (8, 96, 2, 4, 64), (3, 40, 8, 1, 32),
              (4, 64, 5, 1, 32)]
    for (b, s, kv, g, hd) in shapes:
        h = kv * g
        ks = jax.random.split(jax.random.PRNGKey(b + s), 3)
        q = jax.random.normal(ks[0], (b, h, hd), jnp.float32)
        ck = jax.random.normal(ks[1], (b, s, kv, hd), jnp.bfloat16)
        cv = jax.random.normal(ks[2], (b, s, kv, hd), jnp.bfloat16)
        import numpy.random as npr
        fills = jnp.asarray(npr.default_rng(b).integers(1, s + 1, size=b),
                            jnp.int32)
        f_k = jax.jit(lambda q, ck, cv, f: ops.flash_attention_decode(
            q, ck, cv, f, backend="kernel"))
        f_r = jax.jit(lambda q, ck, cv, f: ops.flash_attention_decode(
            q, ck, cv, f, backend="oracle"))
        out_k, out_r = f_k(q, ck, cv, fills), f_r(q, ck, cv, fills)
        if not np.array_equal(np.asarray(out_k), np.asarray(out_r)):
            bad = int(np.sum(np.asarray(out_k) != np.asarray(out_r)))
            raise RuntimeError(
                f"flash-decode kernel diverged from chunked-gather oracle "
                f"at B={b} S={s} KV={kv} g={g} hd={hd}: {bad} mismatches")
        us_k = timer.time(f_k, q, ck, cv, fills)
        us_r = timer.time(f_r, q, ck, cv, fills)
        emit(f"flash_decode_b{b}_s{s}_k{kv}x{g}x{hd}", us_k,
             f"oracle_us={us_r:.1f} bitwise=True interpret=True")


def main(timer: Timer):
    from benchmarks import common

    # the parts are independent: a Pallas interpret-mode failure (the
    # kernels are TPU-first) must not mask the sweep-engine measurements.
    if not common.SMOKE:
        try:
            kernel_micro(timer)
        except Exception as e:
            emit("kernel_micro_ERROR", 0.0, surface_error("kernel_micro", e))
    # NOT wrapped: the decode gates (paged bitwise equality, fused parity
    # + speedup, flash bitwise equality) must fail the run
    # (benchmarks.run exits nonzero) — this is the whole --smoke payload
    paged_decode_bench(timer)
    fused_decode_bench(timer)
    flash_decode_bench(timer)
    if common.SMOKE:
        return  # the engine-speedup measurements below are minutes-scale
    try:
        bitline_bench(timer)
    except Exception as e:
        emit("bitline_bench_ERROR", 0.0, surface_error("bitline_bench", e))
    sweep_engine_speedup()
    fig19_engine_speedup()
