"""Mesh-sharded sweep dispatch.

A sweep's batch dimensions — design points within a compile group, and
programming trials within a point — are embarrassingly parallel, so they
shard over the same ``data`` axis the training/serving stack uses
(``repro.launch.mesh`` axis conventions; parameters and calibration data
stay replicated, exactly like FSDP-off serving in ``repro.sharding``).

On a single-device host everything below is a no-op and the jitted sweep
runs unsharded; on a multi-device host (or under
``--xla_force_host_platform_device_count``) the point/trial batch is
placed with a :class:`~jax.sharding.NamedSharding` and GSPMD partitions
the whole evaluation — programming, calibration, ADC, argmax — with no
changes to the evaluator.  See DESIGN.md §Sweep-engine.
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


def sweep_mesh() -> Optional[jax.sharding.Mesh]:
    """1-D ``data`` mesh over all local devices; None when single-device.

    The axis is ``Auto``: GSPMD propagates the batch sharding through the
    unchanged evaluator.  (``jax.make_mesh`` defaults to ``Explicit``
    axes, whose shardings enter every aval and which the evaluator's
    scans reject outside a ``jax.set_mesh`` context.)
    """
    devs = jax.devices()
    if len(devs) < 2:
        return None
    return jax.make_mesh((len(devs),), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))


def shard_leading(arr: jax.Array, mesh: Optional[jax.sharding.Mesh],
                  axis: int = 0) -> jax.Array:
    """Shard ``axis`` of ``arr`` over the mesh's ``data`` axis.

    Falls back to the unsharded array when the mesh is absent or the dim
    does not divide (replication is always correct; the divisibility rule
    mirrors ``repro.sharding.rules``'s per-dim fallback).
    """
    if mesh is None or arr.ndim == 0:
        return arr
    n = mesh.shape["data"]
    if arr.shape[axis] % n != 0:
        return arr
    spec = [None] * arr.ndim
    spec[axis] = "data"
    return jax.device_put(arr, NamedSharding(mesh, P(*spec)))


def shard_point_trial_batch(dyn: jax.Array, keys: jax.Array,
                            mesh: Optional[jax.sharding.Mesh]):
    """Place the (points, dyn) matrix and (trials, key) stack on the mesh.

    Prefers sharding the larger batch axis: design points when they
    divide the axis, else trials.  Exactly one axis is sharded so GSPMD
    never has to all-gather mid-evaluation.
    """
    if mesh is None:
        return dyn, keys
    n = mesh.shape["data"]
    if dyn.shape[0] % n == 0 and dyn.shape[0] >= keys.shape[0]:
        return shard_leading(dyn, mesh), keys
    if keys.shape[0] % n == 0:
        return dyn, shard_leading(keys, mesh)
    # neither axis divides the mesh: replicate explicitly.  (The previous
    # fallback called shard_leading on the points axis, which silently
    # no-ops on the same divisibility check — stating the replication
    # outcome here keeps the contract readable and testable.)
    return dyn, keys
