#!/usr/bin/env python3
"""Run one cell of the chip benchmark once, on the chip it finds.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

From the root of a checkout.  ``BENCHMARK.json`` names the cell; its
configuration, traffic and metric readers are files under this
directory, found by name.  One process: the weights are made from the
seed on the device, programmed and calibrated, every program this
traffic uses is run once (set-up), the traffic runs for ``--seconds``,
and then a sample of what the window served is compared with the plain
reference.  The last line of standard output is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and,
traced, ``breakdown``) and, last, ``checks``: each number compared with
its limit.  The same numbers are the last lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits with
status 2 and prints no result.  ``--trace 1`` reports the per-layer
metrics from a profiler trace of the first seconds of the window;
``--keep-trace DIR`` also copies that trace's ``.xplane.pb`` to DIR.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)


#: XLA may keep a value in float32 across a cast to bfloat16 (excess
#: precision), and on a TPU it does so inside some fusions: the program's
#: input quantizers then read values that the configuration, with its
#: bfloat16 activations, never produces.  Every run compiles without it.
XLA_FLAGS = ("--xla_allow_excess_precision=false",)


def set_xla_flags():
    """The compiler flags of every run, set before JAX starts."""
    have = os.environ.get("XLA_FLAGS", "").split()
    os.environ["XLA_FLAGS"] = " ".join(
        have + [f for f in XLA_FLAGS if f not in have])


def fail(msg: str, code: int = 2):
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def setup_jax(config) -> str:
    """Compilation cache at a fixed path inside the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says) and the configuration's matmul
    precision for every float32 product the program leaves at default."""
    import jax

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_default_matmul_precision",
                      config["matmul_precision"])
    return cache


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default="")
    args = ap.parse_args(argv)

    from chipbench import result, spec

    bench = spec.find_benchmark(ROOT)
    cell = spec.load_cell(bench, args.workload)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        fail(f"the program under test is not in this checkout "
             f"({os.path.join(ROOT, 'src')})")
    sys.path.insert(0, os.path.join(ROOT, "src"))

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX sees {len(devs)} {devs[0].platform} device(s)")
    if len(devs) < cell.chips:
        fail(f"{cell.name} needs {cell.chips} chips, found {len(devs)}")
    setup_jax(cell.config)

    from chipbench import peaks

    out, checks = execute(cell, args.seed, args.seconds, bool(args.trace),
                          devs, peaks.peaks(devs[0].device_kind),
                          keep=args.keep_trace)
    result.emit(out, checks)
    return 0


def execute(cell, seed: int, seconds: float, trace: bool, devs, pk,
            keep: str = "", limits=None):
    """Set up, run the window, read the metrics and judge the output;
    returns the result line and the checks.  Makes no look for a chip."""
    from chipbench import check, result, serve, spec

    server = serve.Server(cell, seed, T_START)
    run = serve.serve_window(server, seconds, trace, keep=keep)
    run.peaks = pk
    mem = devs[0].memory_stats() or {}
    peak_bytes = mem.get("peak_bytes_in_use")
    print(f"chipbench: window {run.window[1] - run.window[0]:.3f} s, "
          f"counters {run.counters}", file=sys.stderr)

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = spec.metric_reader(m.name)(run)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}

    server.free()
    if limits is None:
        limits = spec.load_json(os.path.join(spec.BENCH_DIR, "limits",
                                             cell.name + ".json"))
    reference = spec.reference_module(cell.config["reference"])
    t = time.perf_counter()
    checks, compared = check.judge(run, server, limits, reference)
    print(f"chipbench: reference over {compared} served tokens took "
          f"{time.perf_counter() - t:.1f} s", file=sys.stderr)

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    return result.line(run, metrics, checks, device), checks


if __name__ == "__main__":
    set_xla_flags()
    sys.exit(main())
