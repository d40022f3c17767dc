"""``repro.sweep`` — the vectorized design-space sweep engine.

Replaces the hand-rolled per-design-point Python loops of the benchmark
scripts with one declarative, batched, cached, mesh-shardable path:

>>> from repro.sweep import Axis, SweepSpec, ClassifierEvaluator, run_sweep
>>> sweep = SweepSpec(
...     name="onoff",
...     base=spec0,
...     axes=(Axis("mapping.on_off_ratio", (10.0, 100.0, float("inf"))),),
...     trials=5,
... )
>>> results = run_sweep(sweep, ClassifierEvaluator(layers, xca, xte, yte),
...                     cache_dir="benchmarks/_cache")
>>> results.mean("on_off_ratio100")

See DESIGN.md §Sweep-engine for the execution model.
"""

from repro.sweep.dispatch import shard_leading, sweep_mesh
from repro.sweep.evaluate import (
    ClassifierEvaluator,
    FunctionEvaluator,
    mapping_signature,
    materialize,
    serial_accuracy,
    trial_accuracy,
    trial_keys,
)
from repro.sweep.executor import compile_groups, run_sweep
from repro.sweep.results import (
    PointResult,
    SweepCache,
    SweepResults,
    device_tag,
    point_key,
)
from repro.sweep.serve_eval import ServeEvaluator, serve_serial_reference
from repro.sweep.spec import Axis, DesignPoint, SweepSpec, get_field, set_field

__all__ = [
    "Axis",
    "ClassifierEvaluator",
    "DesignPoint",
    "FunctionEvaluator",
    "PointResult",
    "ServeEvaluator",
    "SweepCache",
    "SweepResults",
    "SweepSpec",
    "compile_groups",
    "device_tag",
    "get_field",
    "mapping_signature",
    "materialize",
    "point_key",
    "run_sweep",
    "serial_accuracy",
    "serve_serial_reference",
    "set_field",
    "shard_leading",
    "sweep_mesh",
    "trial_accuracy",
    "trial_keys",
]
