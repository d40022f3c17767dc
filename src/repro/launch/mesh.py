"""Production mesh definition.

Kept as functions (never module-level constants) so importing this module
never touches JAX device state — the dry-run entry point must set
``XLA_FLAGS`` before the first device query.
"""

from __future__ import annotations

import jax


def _auto(n_axes: int) -> tuple:
    """GSPMD (``Auto``) semantics on every axis; ``jax.make_mesh`` would
    default to ``Explicit``."""
    return (jax.sharding.AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    Axis semantics: ``pod`` is the DCN-connected data-parallel axis (only
    gradient reductions cross it), ``data`` the intra-pod DP/FSDP axis,
    ``model`` the tensor/expert-parallel axis.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_debug_mesh(n_data: int = 2, n_model: int = 4):
    """Small mesh for the 8-device subprocess tests."""
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=_auto(2))


def dp_axes(mesh) -> tuple:
    """The data-parallel axis names present in ``mesh`` (pod included)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def model_size(mesh) -> int:
    return mesh.shape.get("model", 1)


def dp_size(mesh) -> int:
    n = 1
    for a in dp_axes(mesh):
        n *= mesh.shape[a]
    return n
