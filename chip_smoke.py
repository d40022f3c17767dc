"""Bring-up smoke run of the analog serving path on one TPU chip.

Serves qwen1.5-4b at its published widths (d_model 2560, 20 heads of 128,
d_ff 6912, vocab 151936) through the repository's normal entry points,
in one process, with random weights made from ``--seed``:

1. ``get_model(cfg).init_params``, cast to the model's bfloat16;
2. ``program_lm`` at ``design_a(error=state_proportional(0.02),
   fused="kernel")`` — the design point ``benchmarks/servebench.py``
   serves;
3. ``calibrate_lm`` on a random token batch;
4. ``ServeRuntime(..., attn_backend="flash")``: 8 slots, prompts of
   16-128 tokens, 32 new tokens each.

Depth is the only cut (``--layers``, default 8 of 40): the analog pack
holds f32 ``g_pos``/``g_neg`` for every projection, 634 MB a layer plus
3.1 GB for the head, so 8 layers leave a few GB of the chip's 16 GB.

It checks that every analog site class of the pack went through the
fused Pallas kernel in the runtime's own prefill and decode steps, that
every request was served in full, and that the
first decode step's logits under the fused pack agree with the composed
chain on the same pack (``fused="off"``, streaming attention) to within
:data:`MAX_LSB_DEV` codes of the head's ADC, and that the head alone,
fed one hidden batch, differs by no more than one code per partition.

``--four-chips`` runs only the sweep path instead: the ``lm_accuracy``
smoke grid over a 4-chip ``data`` mesh and again unsharded, in one
process, asserting that the point batch spans 4 devices and that the
metrics agree.

Usage::

    python chip_smoke.py [--seed N] [--layers L]
    python chip_smoke.py --four-chips

Without a TPU it exits non-zero and prints no result.  The last line of
standard output is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SLOTS = 8
PROMPT_LO, PROMPT_HI = 16, 128
NEW_TOKENS = 32
BUCKETS = (64, 128)
CALIB_SHAPE = (2, 64)
TIMED_STEPS = 20
DEFAULT_LAYERS = 8

#: Fused vs composed, compared twice on the same programmed pack.  Both
#: lowerings quantize the same pre-ADC values; they differ only where
#: float32 reassociation (kernel tiles vs XLA's einsum) puts a value on
#: the other side of an ADC decision boundary.
#:
#: The head alone, fed one hidden batch: a flip moves a logit by one code
#: of one partition, so no logit may move by more than one code per
#: partition (3 at d_model 2560), and flips stay rare — 3e-5 of logits
#: on the CPU at published widths.  Conductances rounded to bfloat16, as
#: one bf16 MXU pass would take them, flip 13% (measured on the CPU on
#: a random 2560 x 4096 Design-A matrix).
MAX_HEAD_FLIPPED = 1e-2
#: The whole first decode step: one flip in a layer moves that layer's
#: bfloat16 output, which shifts the next layer's 8-bit inputs and so
#: every pre-ADC value downstream by a fraction of a code — a cascade.
#: Measured on the CPU at published widths (vocab cut to 4096): at most
#: 14 head-ADC codes at 2 layers and 10 at 8 layers, mean 0.8 and 1.7
#: codes, 2.5% of the logits' spread at 2 layers.  The bounds are twice
#: to three times that; a layout or partition fault moves logits by
#: their whole spread (~60 codes on average, hundreds at the maximum).
MAX_LSB_DEV = 32.0
MAX_LOGITS_REL_DEV = 0.1

#: mesh-vs-unsharded loss bound of the four-chip sweep: float32
#: reassociation of the mean cross-entropy (1e-7 relative measured on
#: 8 host devices), two orders of magnitude below what one flipped ADC
#: code or token moves it
MAX_LOSS_REL_DIFF = 1e-5

#: extra ``error.alpha`` values of the four-chip sweep: the smoke grid's
#: one alpha becomes four, so each compile group holds 4 points
FOUR_CHIP_ALPHAS = (0.02, 0.05, 0.1, 0.2)


def say(key: str, value) -> None:
    print(f"{key}: {value}", flush=True)


def accelerator():
    """The first JAX device, which must be a TPU: this run measures the
    chip and never falls back to the CPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU found — JAX sees {len(devs)} "
            f"{devs[0].platform} device(s); run it on a TPU host")
    return devs


def peak_bytes(dev) -> int | None:
    stats = dev.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def head_code_size(pack) -> float:
    """Logit-space size of one code of the head's ADC:
    ``gain * w_scale * x_scale * lsb``."""
    import jax.numpy as jnp

    spec = pack.head_spec
    m = spec.mapping
    gain = (m.levels_per_cell - 1) / (1.0 - m.g_min)
    x_scale = pack.head_act / (2 ** (spec.input_bits - 1) - 1)
    lsb = jnp.min((pack.head_hi - pack.head_lo) / (2 ** spec.adc.bits - 1))
    return float(gain * pack.head.w_scale * x_scale * lsb)


def serve_smoke(cfg, *, seed: int, slots: int = SLOTS,
                prompt_lo: int = PROMPT_LO, prompt_hi: int = PROMPT_HI,
                new_tokens: int = NEW_TOKENS, buckets=BUCKETS,
                calib_shape=CALIB_SHAPE, timed_steps: int = TIMED_STEPS,
                max_lsb_dev: float = MAX_LSB_DEV) -> dict:
    """Program, calibrate and serve ``cfg``; returns what was measured,
    with the checks that failed under ``"failures"`` (empty when all
    passed)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import analog as A
    from repro.core import errors as E
    from repro.core.analog import analog_matmul
    from repro.hw import fused_site_classes
    from repro.kernels import fused as kfused
    from repro.models.registry import get_model
    from repro.models.transformer import cast_params
    from repro.serve import ServeRuntime, calibrate_lm, program_lm
    from repro.serve.analog_engine import HEAD, lm_hook_names
    from repro.sweep.serve_eval import pack_with_fused

    dev = jax.devices()[0]
    api = get_model(cfg)
    k_init, k_prog, k_calib, k_head = jax.random.split(
        jax.random.PRNGKey(seed), 4)
    out = {}

    t0 = time.perf_counter()
    params = jax.jit(lambda k: cast_params(
        api.init_params(cfg, k), jnp.dtype(cfg.dtype)))(k_init)
    jax.block_until_ready(params)
    out["init_s"] = time.perf_counter() - t0

    spec = A.design_a(error=E.state_proportional(0.02), fused="kernel")
    t0 = time.perf_counter()
    pack = jax.jit(lambda p, k: program_lm(cfg, p, spec, k))(params, k_prog)
    jax.block_until_ready(pack)
    out["program_s"] = time.perf_counter() - t0

    calib = jax.random.randint(k_calib, calib_shape, 0, cfg.vocab)
    t0 = time.perf_counter()
    pack = calibrate_lm(cfg, params, pack, calib)
    jax.block_until_ready(pack)
    out["calibrate_s"] = time.perf_counter() - t0

    expected = set(fused_site_classes(
        pack.profile, lm_hook_names(cfg) + [HEAD], cfg.n_layers))
    kfused.BUILD_SIGNATURES.clear()
    rt = ServeRuntime(cfg, params, pack=pack, max_slots=slots,
                      max_len=prompt_hi + new_tokens, buckets=buckets,
                      attn_backend="flash")
    rng = np.random.default_rng(seed)
    lens = rng.integers(prompt_lo, prompt_hi + 1, size=slots)
    lens[:2] = (prompt_lo, prompt_hi)          # both ends of the mix
    for i, n in enumerate(lens):
        rt.submit(rng.integers(0, cfg.vocab, size=int(n)),
                  max_new_tokens=new_tokens, uid=i)
    out["prompt_lens"] = [int(n) for n in lens]

    # admission: one ragged prefill per bucket (compiles + runs)
    t0 = time.perf_counter()
    rt._admit()
    jax.block_until_ready(rt._state.tok)
    out["prefill_first_s"] = time.perf_counter() - t0
    state = rt._state

    # steady decode step over the admitted slots, warm, synced — the
    # runtime's own jitted step on its own weights and pack
    fn = rt._decode_fn
    t0 = time.perf_counter()
    jax.block_until_ready(fn(state, rt.params, rt.pack).tok)
    out["decode_first_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(timed_steps):
        nxt = fn(state, rt.params, rt.pack)
    jax.block_until_ready(nxt.tok)
    out["decode_step_ms"] = (time.perf_counter() - t0) / timed_steps * 1e3

    # what the runtime's prefill and decode programs built, taken before
    # the comparisons below trace fused kernels of their own
    built = set(kfused.BUILD_SIGNATURES)

    # first decode step's logits: fused pack + flash attention vs the
    # composed chain on the same conductances + streaming attention
    def first_logits(pk, attn):
        fn = jax.jit(lambda p, pk_, tok, layers, length: api.decode_step(
            cfg, p, tok[:, None], {"layers": layers, "len": length},
            pack=pk_, attn_backend=attn)[0][:, -1])
        return np.asarray(fn(params, pk, state.tok, state.layers,
                             state.length))

    lg_fused = first_logits(pack, "flash")
    lg_comp = first_logits(pack_with_fused(pack, "off"), "stream")
    failures = []
    if lg_fused.shape != (slots, cfg.vocab):
        failures.append(f"logits shape {lg_fused.shape}")
    if not (np.isfinite(lg_fused).all() and np.isfinite(lg_comp).all()):
        failures.append("non-finite logits")
    code = head_code_size(pack)
    dev_codes = np.abs(lg_fused - lg_comp) / code
    out["head_code"] = code
    out["lsb_dev_max"] = float(dev_codes.max())
    out["lsb_dev_mean"] = float(dev_codes.mean())
    out["logits_within_half_code"] = float(np.mean(dev_codes < 0.5))
    out["logits_rel_dev"] = float(np.linalg.norm(lg_fused - lg_comp)
                                  / np.linalg.norm(lg_comp - lg_comp.mean()))
    out["argmax_agree"] = float(np.mean(
        lg_fused.argmax(-1) == lg_comp.argmax(-1)))

    # the head alone, one hidden batch through both lowerings (weights
    # passed as arguments, never captured as constants): here they can
    # differ only by single-partition ADC decision flips
    hidden = jax.random.normal(k_head, (slots, cfg.d_model), jnp.float32)

    def head(mode):
        hs = dataclasses.replace(pack.head_spec, fused=mode)
        fn = jax.jit(lambda x, w, lo, hi, act: analog_matmul(
            x, w, hs, adc_lo=lo, adc_hi=hi, act_hi=act))
        return np.asarray(fn(hidden, pack.head, pack.head_lo, pack.head_hi,
                             pack.head_act))

    head_codes = np.abs(head("kernel") - head("off")) / code
    out["head_dev_max"] = float(head_codes.max())
    out["head_flipped"] = float(np.mean(head_codes > 0.5))
    out["head_partitions"] = pack.head_spec.n_partitions(cfg.d_model)

    t0 = time.perf_counter()
    done = rt.run()
    out["serve_s"] = time.perf_counter() - t0
    out["requests"] = len(done)
    out["tokens_out"] = int(rt.stats["tokens_out"])
    out["peak_bytes_in_use"] = peak_bytes(dev)

    out["site_classes"] = sorted(expected)
    out["built"] = sorted(built)
    if not expected:
        failures.append("the pack has no fused site class")
    if built != expected:
        failures.append(f"site classes that refused the fused kernel: "
                        f"{sorted(expected - built)} (built "
                        f"{sorted(built)})")
    if len(done) != slots:
        failures.append(f"served {len(done)} of {slots} requests")
    for uid, toks in done.items():
        if toks.size != new_tokens or not (
                (toks >= 0) & (toks < cfg.vocab)).all():
            failures.append(f"request {uid}: bad tokens {toks}")
    if out["lsb_dev_max"] > max_lsb_dev:
        failures.append(
            f"fused vs composed logits differ by {out['lsb_dev_max']:.2f} "
            f"head-ADC codes (bound {max_lsb_dev})")
    if out["logits_rel_dev"] > MAX_LOGITS_REL_DEV:
        failures.append(
            f"fused vs composed logits differ by {out['logits_rel_dev']:.3g} "
            f"of their spread (bound {MAX_LOGITS_REL_DEV})")
    if (out["head_dev_max"] > out["head_partitions"] + 0.5
            or out["head_flipped"] > MAX_HEAD_FLIPPED):
        failures.append(
            f"head alone: fused vs composed differ by up to "
            f"{out['head_dev_max']:.2f} codes on {out['head_flipped']:.2%} "
            f"of logits (bounds: one code per partition = "
            f"{out['head_partitions']}, {MAX_HEAD_FLIPPED:.0%})")
    out["failures"] = failures
    return out


def run_one_chip(seed: int, layers: int) -> None:
    import jax

    from repro.configs.qwen1_5_4b import CONFIG
    from repro.runtime.compile_cache import enable_compile_cache

    devs = accelerator()
    say("compile_cache", enable_compile_cache())
    cfg = dataclasses.replace(CONFIG, n_layers=layers)
    say("device", f"{devs[0].device_kind} x{len(devs)} "
                  f"(jax {jax.__version__})")
    say("model", f"{cfg.name} d_model={cfg.d_model} heads={cfg.n_heads}x"
                 f"{cfg.hd} kv_heads={cfg.n_kv_heads} d_ff={cfg.d_ff} "
                 f"vocab={cfg.vocab} dtype={cfg.dtype}")
    say("reduced", f"n_layers {CONFIG.n_layers} -> {layers} "
                   f"(depth only; every width published)")
    r = serve_smoke(cfg, seed=seed)
    say("wall_s", f"init={r['init_s']:.1f} program={r['program_s']:.1f} "
                  f"calibrate={r['calibrate_s']:.1f} "
                  f"prefill_first(compile+run)={r['prefill_first_s']:.1f} "
                  f"decode_first(compile+run)={r['decode_first_s']:.1f} "
                  f"serve={r['serve_s']:.1f}")
    say("served", f"requests={r['requests']} tokens_out={r['tokens_out']} "
                  f"prompt_lens={r['prompt_lens']}")
    say("decode_step_ms", f"{r['decode_step_ms']} (slots={SLOTS}, warm, "
                          f"mean of {TIMED_STEPS}, block_until_ready)")
    say("peak_bytes_in_use", r["peak_bytes_in_use"])
    say("fused_site_classes", f"{r['site_classes']} built={r['built']}")
    say("head_adc_code", f"{r['head_code']!r} logit units")
    say("fused_vs_composed_logits",
        f"first decode step: max={r['lsb_dev_max']:.3f} "
        f"mean={r['lsb_dev_mean']:.4f} head-ADC codes (bound {MAX_LSB_DEV}),"
        f" within half a code {r['logits_within_half_code']:.4f}, "
        f"|diff|/|spread|={r['logits_rel_dev']:.4g} "
        f"(bound {MAX_LOGITS_REL_DEV}), argmax agreement "
        f"{r['argmax_agree']:.3f} (informative)")
    say("fused_vs_composed_head",
        f"same hidden batch: max={r['head_dev_max']:.3f} codes "
        f"(bound {r['head_partitions']}, one per partition), flipped "
        f"{r['head_flipped']:.5f} of logits (bound {MAX_HEAD_FLIPPED})")
    if r["failures"]:
        raise SystemExit("chip_smoke: FAILED: " + "; ".join(r["failures"]))
    emit_ok(devs)


def sweep_four_chips() -> None:
    """The ``lm_accuracy`` smoke grid sharded over the 4-chip ``data``
    mesh and unsharded, in one process; metrics must agree."""
    import jax

    from benchmarks import common
    from benchmarks.lm_accuracy import lm_evaluator, lm_sweep
    from repro.runtime.compile_cache import enable_compile_cache
    from repro.sweep import Axis, run_sweep, sweep_mesh
    from repro.sweep import serve_eval

    devs = accelerator()
    if len(devs) != 4:
        raise SystemExit(f"chip_smoke --four-chips: needs 4 TPU chips, "
                         f"found {len(devs)}")
    say("compile_cache", enable_compile_cache())
    say("device", f"{devs[0].device_kind} x{len(devs)}")
    common.SMOKE = True
    base = lm_sweep(smoke=True)
    sweep = dataclasses.replace(base, axes=base.axes[:-1] + (Axis(
        "error.alpha", FOUR_CHIP_ALPHAS,
        labels=tuple(f"a{a}" for a in FOUR_CHIP_ALPHAS)),))
    ev = lm_evaluator()

    # record how each compile group's point/trial batch was placed
    placed = []
    shard = serve_eval.shard_point_trial_batch

    def recording_shard(dyn, keys, mesh):
        dyn, keys = shard(dyn, keys, mesh)
        placed.append((dyn.shape[0], len(dyn.sharding.device_set)))
        return dyn, keys

    serve_eval.shard_point_trial_batch = recording_shard
    try:
        mesh = sweep_mesh()
        t0 = time.perf_counter()
        sharded = run_sweep(sweep, ev, cache_dir=None, force=True, mesh=mesh)
        t_mesh = time.perf_counter() - t0
        n_mesh_groups = len(placed)
        t0 = time.perf_counter()
        single = run_sweep(sweep, ev, cache_dir=None, force=True, mesh=None)
        t_one = time.perf_counter() - t0
    finally:
        serve_eval.shard_point_trial_batch = shard

    say("sweep", f"{sweep.name}: {len(sharded)} points, "
                 f"trials={sweep.trials}, mesh={dict(mesh.shape)}")
    say("placement", f"mesh run (points, devices) per group "
                     f"{placed[:n_mesh_groups]}; unsharded "
                     f"{placed[n_mesh_groups:]}")
    say("wall_s", f"mesh={t_mesh:.1f} unsharded={t_one:.1f} "
                  f"(compilation included)")
    worst = {}
    for a, b in zip(sharded, single):
        assert a.tag == b.tag
        for ta, tb in zip(a.values, b.values):
            for k in ta:
                d = abs(ta[k] - tb[k]) / (abs(tb[k]) if k == "loss" else 1)
                worst[k] = max(worst.get(k, 0.0), d)
        say(f"point {a.tag}", " ".join(
            f"{k}={a.metric_mean(k)!r}/{b.metric_mean(k)!r}"
            for k in sorted(a.values[0])) + " (mesh/unsharded)")
    say("max_metric_diff", f"{worst} (loss relative, others absolute)")
    assert n_mesh_groups and all(
        p % 4 == 0 and n == 4 for p, n in placed[:n_mesh_groups]), \
        f"point batch not sharded over 4 devices: {placed}"
    # each point is computed independently of the others, but the
    # partitioned program runs one point per chip where the unsharded one
    # batches four, so XLA may sum in another order: the loss agrees to
    # float32 rounding, the ADC codes and hence the tokens exactly
    assert worst["loss"] <= MAX_LOSS_REL_DIFF, worst
    assert worst["top1"] == 0.0 and worst["decode_match"] == 0.0, worst
    emit_ok(devs)


def emit_ok(devs) -> None:
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and requests")
    ap.add_argument("--layers", type=int, default=DEFAULT_LAYERS,
                    help="decoder layers kept (published: 40)")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sweep path and its "
                         "unsharded comparison")
    args = ap.parse_args()
    if args.four_chips:
        sweep_four_chips()
    else:
        run_one_chip(args.seed, args.layers)


if __name__ == "__main__":
    main()
