#!/usr/bin/env python3
"""Readings that the correctness limits are set from.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 \\
        --seconds <s> [--control 1,2,3] [--precisions high,bfloat16] \\
        [--conductances bfloat16]

For each seed, in one process: the cell is set up, served for
``--seconds`` and judged exactly as ``run.py`` does it, and one JSON
line gives every number compared (``checks``) and ``correct``.  For the
seeds in ``--control`` it also reads the reference put in the program's
place at each of ``--precisions`` (``high``: three bfloat16 passes for
each float32 product, the step below the configuration's ``highest``;
``bfloat16``: one pass; ``float8``: activations in float8_e4m3fn): the
share of the first layer's cached values that it puts half an ADC code
or more from the reference at ``highest``.  ``--conductances bfloat16``
serves the program on its own lower-precision path (conductances stored
and read as bfloat16, ``AnalogSpec.compute_dtype``), judged as a run is.
The benchmark's own runs never run this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)


def reference_off_share(run, server, reference, precision: str) -> float:
    """The first-layer comparison of a run, with the reference at
    ``precision`` in the program's place."""
    from chipbench import check

    seqs = [r["seq"] for r in run.cached]
    kw = dict(length=check.shapes(run.cell.traffic)["length"],
              batch=run.slots)
    want, code = reference.first_layer_values(
        run.config, server.params, server.k_prog, server.calib, seqs, **kw)
    got, _ = reference.first_layer_values(
        run.config, server.params, server.k_prog, server.calib, seqs,
        precision=precision, **kw)
    g = check.value_gaps([{"v": v} for v in got], want, code)
    return float((g >= 0.5).mean())


def readings(cell, seed: int, seconds: float, controls: tuple,
             t_start: float) -> dict:
    """Every number a run of this seed compares, and the controls'."""
    from chipbench import check, serve, spec

    server = serve.Server(cell, seed, t_start)
    run = serve.serve_window(server, seconds, False)
    server.free()
    ref = spec.reference_module(cell.config["reference"])
    limits = spec.load_json(os.path.join(spec.BENCH_DIR, "limits",
                                         cell.name + ".json"))
    checks, compared = check.judge(run, server, limits, ref)
    out = {"seed": seed, "compared_tokens": compared,
           "live_slots": len(run.cached),
           "correct": all(v["ok"] for v in checks.values()),
           "checks": {k: v["value"] for k, v in checks.items()}}
    for prec in controls:
        out[prec] = {"first_layer_v_off_share":
                     reference_off_share(run, server, ref, prec)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--conductances", default="")
    ap.add_argument("--precisions", default="high,bfloat16,float8")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    from chipbench import spec
    from run import setup_jax

    cell = spec.load_cell(spec.find_benchmark(ROOT), args.workload)
    if args.conductances:
        cfg = dict(cell.config, analog=dict(cell.config["analog"],
                                            compute_dtype=args.conductances))
        cell = dataclasses.replace(cell, config=cfg)
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 2
    setup_jax(cell.config)
    ctl = {int(s) for s in args.control.split(",") if s}
    for s in args.seeds.split(","):
        t = time.perf_counter()
        r = readings(cell, int(s), args.seconds,
                     tuple(args.precisions.split(",")) if int(s) in ctl
                     else (), t)
        r["seconds"] = time.perf_counter() - t
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    from run import set_xla_flags

    set_xla_flags()
    sys.exit(main())
