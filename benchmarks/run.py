"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Usage::

    PYTHONPATH=src python -m benchmarks.run [module ...] [--only fig8_9] [--smoke]

Positional ``module`` names (substring match, like ``--only``) restrict
the run, e.g. ``python -m benchmarks.run lm_accuracy --smoke``.

``--smoke`` is the CI fast path: the Fig. 10 On/Off sweep (a single
compile group exercising the whole vectorized engine), the Fig. 19
parasitic grid (the traced-``r_hat`` bit-line solve path), the LM
serving sweeps (``lm_accuracy`` — program → calibrate → serve end to
end, including the serving-scale parasitic axis), the heterogeneous
per-site precision grid (``hetero_precision`` — mixed attn/MLP ADC
bits through ``repro.hw.Profile``, with the matched-loss claim gate),
the serving runtime (``servebench`` — continuous vs static
batching, with the runtime-vs-``decode_lm`` agreement gate), and the
drift/fault aging story (``driftbench`` — the nu × device-age
degradation surface plus the self-healing-vs-unhealed serving gate),
and the fused decode kernels (``kernelbench`` — fused-vs-oracle parity
and the fused-vs-composed speedup gate on decode shapes);
one programming trial per point, fresh (uncached) evaluation.
"""

import argparse
import sys
import time


MODULES = [
    "fig6_conductance",
    "eq9_snr",
    "fig8_9_cell_errors",
    "fig10_onoff",
    "fig15_16_adc",
    "fig17_lowprec",
    "fig19_parasitics",
    "table3_energy",
    "table4_sonos",
    "lm_accuracy",
    "hetero_precision",
    "servebench",
    "driftbench",
    "kernelbench",
    "roofline",
]

SMOKE_MODULES = ["fig10_onoff", "fig19_parasitics", "lm_accuracy",
                 "hetero_precision", "servebench", "driftbench",
                 "kernelbench"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("modules", nargs="*", default=[],
                    help="restrict to modules matching any of these "
                         "substrings (e.g. lm_accuracy)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="CI fast path: thinned sweeps, one trial per point")
    args = ap.parse_args()

    from benchmarks import common
    from benchmarks.common import Timer, emit
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    common.SMOKE = args.smoke
    # --smoke alone runs the CI subset; an explicit selection (positional
    # or --only) picks from ALL modules, with --smoke just thinning the
    # sweeps — so `run.py fig8_9 --smoke` means the fig8_9 smoke grid.
    modules = MODULES
    if args.smoke and not (args.modules or args.only):
        modules = SMOKE_MODULES
    selected = [
        m for m in modules
        if (not args.only or args.only in m)
        and (not args.modules or any(s in m for s in args.modules))
    ]
    if not selected:
        ap.error(f"no benchmark matches {args.modules or [args.only]}; "
                 f"choose from {', '.join(MODULES)}")

    failed = []
    timer = Timer(reps=3)
    print("name,us_per_call,derived")
    for mod_name in selected:
        t0 = time.time()
        if mod_name == "roofline":
            # roofline reads the dry-run results, no model eval
            from repro.launch import roofline as rl

            rows = rl.load_all()
            for r in rows:
                if r["mesh"] != "pod16x16":
                    continue
                emit(
                    f"roofline_{r['arch']}_{r['shape']}", 0.0,
                    f"comp={r['compute_s']:.2e}s mem={r['memory_s']:.2e}s "
                    f"coll={r['collective_s']:.2e}s dom={r['dominant']} "
                    f"useful={r['useful_ratio']:.2f} "
                    f"roofline={100*r['roofline_fraction']:.1f}%",
                )
            continue
        mod = __import__(f"benchmarks.{mod_name}", fromlist=["main"])
        try:
            mod.main(timer)
        except Exception as e:  # keep the harness running
            emit(f"{mod_name}_ERROR", 0.0, common.surface_error(mod_name, e))
            failed.append(mod_name)
        print(f"# {mod_name} done in {time.time()-t0:.1f}s", file=sys.stderr)

    if failed:
        # every other module still ran, but CI must see the breakage
        print(f"# FAILED: {', '.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
