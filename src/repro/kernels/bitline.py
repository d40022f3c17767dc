"""Pallas TPU kernels for the parasitic bit-line solve (paper Sec. 8).

One (bm, bn) output tile solves bm*bn independent tridiagonal systems of
depth K — one per (input sample, bit line).  The Thomas forward sweep is a
``fori_loop`` over rows carrying the (c', d') elimination state for the
whole tile in VREGs; the full x-tile (bm, K) and conductance tile (K, bn)
live in VMEM.  Only the bottom-node voltage is needed (the column output
current is the current through the bottom segment), so no back-substitution
pass or per-row voltage storage is required — this is the structural win
over a dense solve (O(K) work, O(1) state per line).

``r_hat`` is a *traced* scalar input (a (1, 1) array read inside the
kernel), not a Python-float closure constant: the sweep engine batches a
whole Fig. 19 ``r_hat`` axis through one compiled program by substituting
traced values, so the kernel must not bake the parasitic level into the
compiled artifact.  Whether parasitics are in the program at all is a
*static* bit decided by the caller (``AnalogSpec.parasitics_on``).

Two kernels:

* :func:`bitline_mvm_pallas` — one signed input plane through the
  parasitic circuit (the building block ``core.analog._apply_line``
  dispatches to per (slice, partition)).
* :func:`analog_bitline_diff_pallas` — the fused Design-A fast path:
  in-VMEM input bit-plane extraction, per-bit Thomas solves for both
  differential lines, analog (switched-capacitor) accumulation over bits,
  one ADC per (tile, partition), digital accumulation over partitions —
  the parasitic analogue of ``analog_mvm._diff_kernel``.

Grid: (M // bm, N // bn[, P]); K is kept whole inside the kernel
(K <= 1152 for realistic arrays: x tile 128x1152 f32 = 0.6 MB, g tile
1152x128 = 0.6 MB).

The sweep reads one array row per step through ref slices (``pl.ds``):
row ``i`` of ``g`` is a dynamic sublane slice ``(1, bn)``, and the input
operand arrives *rows-major* (``(K, M)`` / ``(P, rows, M)``), so row ``i``
of the inputs is also a sublane slice ``(1, bm)``, turned into the
``(bm, 1)`` column the outer product needs by a transpose.  Mosaic has no
lowering for ``lax.dynamic_slice`` of a value, nor for a dynamic lane
offset that it cannot prove 128-aligned, which rules out slicing the
``(bm, K)`` layout column by column.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.analog_mvm import _bit_plane


def _thomas_bottom_current(x_row, g_row, r, *, k: int, shape):
    """Bottom-node current ``shape = (bm, bn)`` of one signed plane
    through one line stack: Thomas forward sweep over rows; d'_{K-1} IS
    v_{K-1} since c_{K-1} = 0 in back-substitution, and I = v_{K-1} / r.

    ``x_row(i)`` returns row ``i`` of the signed input plane as a
    ``(bm, 1)`` column (entries in {-1, 0, +1}) and ``g_row(i)`` row
    ``i`` of the conductances as ``(1, bn)``.  Kernels read both through
    ref slices; the ``ref.py`` oracles through ``lax.dynamic_slice`` of
    values — the recurrence itself is this one function on both sides.

    ``g_i * r`` is factored out so that every product feeding an add
    (``gr``, ``rhs``) has an exactly-representable value — ``a_i`` is in
    {0, 1} and ``x_i`` in {-1, 0, +1} — making the sweep FMA-invariant:
    whether LLVM contracts ``a*b + c`` into an FMA or not, the bits come
    out the same.
    """

    def body(i, carry):
        c_prev, d_prev = carry                        # (bm, bn)
        x_i = x_row(i)                                # (bm, 1)
        grr = g_row(i) * r                            # (1, bn)
        gr = jnp.abs(x_i) * grr                       # (bm, bn) exact
        rhs = x_i * grr                               # (bm, bn) exact
        base = jnp.where(i == 0, 1.0, 2.0)
        denom = base + gr + c_prev
        c_new = -1.0 / denom
        d_new = (rhs + d_prev) / denom
        return (c_new, d_new)

    zeros = jnp.zeros(shape, jnp.float32)
    _, d_last = jax.lax.fori_loop(0, k, body, (zeros, zeros))
    return d_last / r


def value_rows(plane, g):
    """``(x_row, g_row)`` readers over in-register values: the oracle
    side of :func:`_thomas_bottom_current` (``plane`` is ``(bm, K)``)."""
    bm, bn = plane.shape[0], g.shape[1]
    return (lambda i: jax.lax.dynamic_slice(plane, (0, i), (bm, 1)),
            lambda i: jax.lax.dynamic_slice(g, (i, 0), (1, bn)))


def plane_rows(xt_ref, b=None):
    """Row reader over a rows-major ``(K, bm)`` integer input block for
    :func:`_thomas_bottom_current`: row ``i`` as a ``(bm, 1)`` column,
    reduced to signed bit plane ``b`` in VMEM (``None`` = the block is
    already a signed plane)."""
    def read(i):
        x_i = xt_ref[pl.ds(i, 1), :].T
        return x_i if b is None else _bit_plane(jnp.abs(x_i), jnp.sign(x_i), b)
    return read


def ref_rows(g_ref):
    """Row reader over a ``(K, bn)`` conductance ref: ``(1, bn)``."""
    return lambda i: g_ref[pl.ds(i, 1), :]


def _bitline_kernel(r_ref, g_ref, xt_ref, o_ref, *, k: int):
    out = _thomas_bottom_current(plane_rows(xt_ref), ref_rows(g_ref),
                                 r_ref[0, 0], k=k, shape=o_ref.shape)
    o_ref[...] = out.astype(o_ref.dtype)


def bitline_mvm_pallas(
    g: jax.Array,          # (K, N)
    x: jax.Array,          # (M, K) signed plane
    r_hat,                 # scalar (traced or concrete) parasitic level
    *,
    bm: int = 128,
    bn: int = 128,
    interpret: bool,
) -> jax.Array:
    """Output currents (M, N) under parasitic bit-line resistance."""
    from repro.core.parasitics import parasitics_off

    if parasitics_off(r_hat):
        return x @ g
    k, n = g.shape
    m = x.shape[0]
    if m % bm or n % bn:
        raise ValueError(
            f"block shape ({bm}, {bn}) does not tile operand ({m}, {n})")
    r2 = jnp.asarray(r_hat, jnp.float32).reshape(1, 1)
    kern = functools.partial(_bitline_kernel, k=k)
    return pl.pallas_call(
        kern,
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((k, bn), lambda i, j: (0, j)),
            pl.BlockSpec((k, bm), lambda i, j: (0, i)),   # rows-major x
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(r2, g, x.T)


def _parasitic_diff_kernel(r_ref, lo_ref, hi_ref, xt_ref, gp_ref, gm_ref,
                           o_ref, *, n_bits: int, adc_bits: int,
                           gain: float, rows: int):
    """Fused parasitic Design-A path: per input bit, Thomas-solve both
    differential lines, analog-accumulate over bits, one ADC per
    partition, digital accumulation over partitions."""
    from repro.kernels.analog_mvm import _adc_epilogue

    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    r = r_ref[0, 0]
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    for b in range(n_bits):                # static unroll: n_bits <= 7
        i_pos = _thomas_bottom_current(plane_rows(xt_ref, b),
                                       ref_rows(gp_ref), r, k=rows,
                                       shape=o_ref.shape)
        i_neg = _thomas_bottom_current(plane_rows(xt_ref, b),
                                       ref_rows(gm_ref), r, k=rows,
                                       shape=o_ref.shape)
        acc += 2.0 ** b * (i_pos - i_neg)  # switched-capacitor bit accum

    v_hat = _adc_epilogue(acc, lo_ref[0, 0], hi_ref[0, 0], adc_bits)
    o_ref[...] += (v_hat * gain).astype(o_ref.dtype)


def analog_bitline_diff_pallas(
    xt_parts: jax.Array,   # (P, rows, M) integer-valued signed
    g_pos: jax.Array,      # (P, rows, N)
    g_neg: jax.Array,      # (P, rows, N)
    r_hat,                 # scalar (traced or concrete) parasitic level
    adc_lo: jax.Array,
    adc_hi: jax.Array,
    *,
    n_bits: int,
    adc_bits: int,
    gain: float,
    bm: int = 128,
    bn: int = 128,
    interpret: bool,
) -> jax.Array:
    """Fused Design-A MVM under parasitic resistance; (M, N) code units."""
    p, rows, m = xt_parts.shape
    _, _, n = g_pos.shape
    if m % bm or n % bn:
        raise ValueError(
            f"block shape ({bm}, {bn}) does not tile operand ({m}, {n})")
    r2 = jnp.asarray(r_hat, jnp.float32).reshape(1, 1)
    lo2 = jnp.asarray(adc_lo, jnp.float32).reshape(1, 1)
    hi2 = jnp.asarray(adc_hi, jnp.float32).reshape(1, 1)
    kern = functools.partial(
        _parasitic_diff_kernel, n_bits=n_bits, adc_bits=adc_bits,
        gain=gain, rows=rows)
    return pl.pallas_call(
        kern,
        grid=(m // bm, n // bn, p),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j, p_: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j, p_: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j, p_: (0, 0)),
            pl.BlockSpec((None, rows, bm), lambda i, j, p_: (p_, 0, i)),
            pl.BlockSpec((None, rows, bn), lambda i, j, p_: (p_, 0, j)),
            pl.BlockSpec((None, rows, bn), lambda i, j, p_: (p_, 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, p_: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r2, lo2, hi2, xt_parts, g_pos, g_neg)
