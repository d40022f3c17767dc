"""Pallas TPU kernels for the analog in-situ MVM simulation hot loop.

TPU-native adaptation (see DESIGN.md): CrossSim's per-array Python loop
becomes MXU matmuls with the ADC model fused into the epilogue, and — for
the bit-serial (digital input accumulation) path — input bit planes are
extracted *inside* the kernel in VMEM instead of being materialized in HBM
(an 8x input-traffic reduction).

Grid/BlockSpec layout, both kernels::

    grid = (M // bm, N // bn, P)          # P = analog K-partitions
    x block  (-, bm, rows)   index (p, i, 0)  -> VMEM   x is (P, M, rows)
    g blocks (-, rows, bn)   index (p, 0, j)  -> VMEM
    out      (bm, bn)        index (i, j)     accumulated over p

(``-`` is a squeezed partition dim.)  x is partition-major so that its
block's last two dims are ``(bm, rows)``: Mosaic requires the last two
block dims to be (8, 128)-aligned or whole, and an ``(M, P, rows)``
layout would put a block dim of 1 over P there.

The innermost grid dimension walks the analog partitions; the output block
is revisited and accumulated, mirroring the digital partial-sum adder that
follows each array's ADC.  ``rows`` (the analog array depth, <= 1152) and
the N tile are chosen so both matmul operands sit in VMEM with
MXU-aligned dims (multiples of 128 after padding in ops.py).

The ADC epilogue is pure VPU work: clip, scale, round — fused with the
matmul so the pre-ADC partial sums never leave VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _adc_epilogue(v, lo, hi, bits: int):
    n_levels = 2 ** bits
    lsb = (hi - lo) / (n_levels - 1)
    code = jnp.clip(jnp.round((v - lo) / lsb), 0.0, n_levels - 1.0)
    return lo + code * lsb


def _bit_plane(mag, sign, b: int):
    """In-VMEM signed bit-plane extraction from float-encoded integers:
    plane_b = bit b of |x|, carrying sign(x).  Shared by every bit-serial
    kernel (Design D here, the parasitic Design-A path in bitline.py) so
    the input-plane encoding cannot diverge between them."""
    return (jnp.floor(mag / 2.0 ** b) % 2.0) * sign


def _diff_kernel(x_ref, gp_ref, gm_ref, lo_ref, hi_ref, o_ref, *,
                 adc_bits: int, gain: float):
    """Design-A fast path: one matmul + ADC per (tile, partition)."""
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]                         # (bm, rows)
    g = gp_ref[...] - gm_ref[...]          # (rows, bn) — analog subtraction
    v = jnp.dot(x, g, preferred_element_type=jnp.float32)
    lo = lo_ref[0, 0]
    hi = hi_ref[0, 0]
    v_hat = _adc_epilogue(v, lo, hi, adc_bits)
    o_ref[...] += (v_hat * gain).astype(o_ref.dtype)


def _bitserial_kernel(x_ref, gp_ref, gm_ref, lo_ref, hi_ref, o_ref, *,
                      n_bits: int, adc_bits: int, gain: float):
    """Design-D path: in-VMEM bit-plane extraction, ADC per input bit,
    digital shift-and-add accumulation."""
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]                         # (bm, rows) integer-valued float
    g = gp_ref[...] - gm_ref[...]
    lo = lo_ref[0, 0]
    hi = hi_ref[0, 0]
    sign = jnp.sign(x)
    mag = jnp.abs(x)
    acc = jnp.zeros_like(o_ref)
    for b in range(n_bits):                # static unroll: n_bits <= 7
        plane = _bit_plane(mag, sign, b)
        v = jnp.dot(plane, g, preferred_element_type=jnp.float32)
        v_hat = _adc_epilogue(v, lo, hi, adc_bits)
        acc += (v_hat * 2.0 ** b).astype(acc.dtype)
    o_ref[...] += acc * gain


def _common_call(kernel, x_parts, g_pos, g_neg, adc_lo, adc_hi, *,
                 bm: int, bn: int, interpret: bool):
    p, m, rows = x_parts.shape
    _, _, n = g_pos.shape
    if m % bm or n % bn:
        raise ValueError(
            f"block shape ({bm}, {bn}) does not tile operand ({m}, {n})")
    grid = (m // bm, n // bn, p)
    lo2 = adc_lo.reshape(1, 1).astype(jnp.float32)
    hi2 = adc_hi.reshape(1, 1).astype(jnp.float32)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, bm, rows), lambda i, j, p_: (p_, i, 0)),
            pl.BlockSpec((None, rows, bn), lambda i, j, p_: (p_, 0, j)),
            pl.BlockSpec((None, rows, bn), lambda i, j, p_: (p_, 0, j)),
            pl.BlockSpec((1, 1), lambda i, j, p_: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j, p_: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, p_: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x_parts, g_pos, g_neg, lo2, hi2)


def analog_mvm_diff_pallas(
    x_parts: jax.Array,    # (P, M, rows) integer-valued
    g_pos: jax.Array,
    g_neg: jax.Array,
    adc_lo: jax.Array,
    adc_hi: jax.Array,
    *,
    adc_bits: int,
    gain: float,
    bm: int = 128,
    bn: int = 128,
    interpret: bool,
) -> jax.Array:
    kern = functools.partial(_diff_kernel, adc_bits=adc_bits, gain=gain)
    return _common_call(kern, x_parts, g_pos, g_neg, adc_lo, adc_hi,
                        bm=bm, bn=bn, interpret=interpret)


def analog_mvm_bitserial_pallas(
    x_parts: jax.Array,    # (P, M, rows) integer-valued signed
    g_pos: jax.Array,
    g_neg: jax.Array,
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
    kern = functools.partial(
        _bitserial_kernel, n_bits=n_bits, adc_bits=adc_bits, gain=gain
    )
    return _common_call(kern, x_parts, g_pos, g_neg, adc_lo, adc_hi,
                        bm=bm, bn=bn, interpret=interpret)
