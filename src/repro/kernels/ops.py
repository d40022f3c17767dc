"""Jit'd public wrappers for the Pallas kernels.

Handles MXU-alignment padding (M/N to tile multiples), backend selection
(``interpret=True`` on CPU — the container's validation mode — and compiled
Mosaic on TPU), and the squeeze/reshape glue to/from the shapes used by
``repro.core.analog``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import analog_mvm as _k_mvm
from repro.kernels import bitline as _k_bl
from repro.kernels import fused as _k_fused
from repro.kernels import paged as _k_paged
from repro.kernels import ref as _k_ref


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _pick_tile(size: int, pref: int, *, lane: bool = False) -> int:
    """Tile size for one grid dimension.

    The lane (last, N) dimension is always the full ``pref`` (128) tile:
    Mosaic requires lane tiles of 128, so small N pads up to one full
    tile rather than shrinking it (interpret mode tolerates any tile,
    which is exactly how a sublane-rounded N tile stayed latent until
    TPU compilation).  Sublane (M) dimensions may shrink to cap padding
    waste on small inputs — but only to a *power-of-two multiple of 8*
    (8, 16, 32, 64, ...): an M that is already a multiple of 8 used to be
    taken verbatim as the tile, and odd multiples of 8 (24, 40, 56, ...)
    are the fragile Mosaic relayout class that small-N tiles fell into
    before PR 3 pinned the lane rule.  Rounding to the next power-of-two
    multiple keeps padding waste under 2x and every tile in the
    well-trodden {8, 16, 32, 64, 128} set.
    """
    if lane:
        return pref
    if size >= pref:
        return pref
    tile = 8
    while tile < size:
        tile *= 2
    return min(tile, pref)


def analog_mvm(
    x_parts: jax.Array,      # (M, P, rows)
    g_pos: jax.Array,        # (S=1, P, rows, N) or (P, rows, N)
    g_neg: jax.Array,
    *,
    adc_lo: jax.Array,
    adc_hi: jax.Array,
    adc_bits: int,
    gain: float,
    interpret: bool | None = None,
) -> jax.Array:
    """Design-A fused analog MVM; returns (M, N) in code units."""
    if g_pos.ndim == 4:
        g_pos, g_neg = g_pos[0], g_neg[0]
    interpret = _use_interpret() if interpret is None else interpret
    m, p, rows = x_parts.shape
    n = g_pos.shape[-1]
    bm = _pick_tile(m, 128)
    bn = _pick_tile(n, 128, lane=True)
    xp = _pad_to(jnp.swapaxes(x_parts, 0, 1).astype(jnp.float32), 1, bm)
    gp = _pad_to(g_pos.astype(jnp.float32), 2, bn)
    gm = _pad_to(g_neg.astype(jnp.float32), 2, bn)
    out = _k_mvm.analog_mvm_diff_pallas(
        xp, gp, gm,
        jnp.asarray(adc_lo), jnp.asarray(adc_hi),
        adc_bits=adc_bits, gain=float(gain),
        bm=bm, bn=bn, interpret=interpret,
    )
    return out[:m, :n]


def analog_mvm_bitserial(
    x_parts: jax.Array,
    g_pos: jax.Array,
    g_neg: jax.Array,
    *,
    n_bits: int,
    adc_lo: jax.Array,
    adc_hi: jax.Array,
    adc_bits: int,
    gain: float,
    interpret: bool | None = None,
) -> jax.Array:
    """Design-D fused bit-serial analog MVM; returns (M, N) code units."""
    if g_pos.ndim == 4:
        g_pos, g_neg = g_pos[0], g_neg[0]
    interpret = _use_interpret() if interpret is None else interpret
    m, p, rows = x_parts.shape
    n = g_pos.shape[-1]
    bm = _pick_tile(m, 128)
    bn = _pick_tile(n, 128, lane=True)
    xp = _pad_to(jnp.swapaxes(x_parts, 0, 1).astype(jnp.float32), 1, bm)
    gp = _pad_to(g_pos.astype(jnp.float32), 2, bn)
    gm = _pad_to(g_neg.astype(jnp.float32), 2, bn)
    out = _k_mvm.analog_mvm_bitserial_pallas(
        xp, gp, gm,
        jnp.asarray(adc_lo), jnp.asarray(adc_hi),
        n_bits=n_bits, adc_bits=adc_bits, gain=float(gain),
        bm=bm, bn=bn, interpret=interpret,
    )
    return out[:m, :n]


def paged_attention(
    q: jax.Array,          # (B, H, hd)
    k_pages: jax.Array,    # (P, page_size, KV, hd)
    v_pages: jax.Array,    # (P, page_size, KV, hd)
    ptab: jax.Array,       # (B, NP) int32 block table
    kv_len: jax.Array,     # (B,) int32 valid positions per row
    *,
    scale: float | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Decode-step attention over a paged KV pool; returns (B, H, hd).

    The page gather happens inside the kernel via scalar-prefetched
    block-table indices, so the dense ``(B, NP*page)`` gather is never
    materialized.  Bit-exact vs ``ref.paged_attention_decode`` in
    float32 (positions >= ``kv_len[b]`` contribute exact zeros).

    TPU alignment pads the head dim (lane) to 128 with zeros — exact, as
    the padded lanes contribute zero dot products and are sliced away.
    ``page_size`` indexes absolute token positions so it can never be
    padded; Mosaic needs it sublane-aligned (multiple of 8).

    ``page_size == 1`` is canonicalized before the kernel runs: a row of
    ``NP`` one-token pages *is* one page of ``NP`` tokens, so the pool is
    pre-gathered into a per-row pool ``(B, NP, KV, hd)`` with the identity
    block table.  Size-1 page einsums degenerate to elementwise code whose
    FMA contraction is fusion-context-dependent on CPU (the same dot can
    round differently between the kernel's two phases), which breaks the
    bitwise contract; the canonical shape keeps every contraction a real
    ``dot_general``.  ``ref.paged_attention_decode`` applies the identical
    rewrite, so the bitwise comparison is over the same canonical problem.
    """
    interpret = _use_interpret() if interpret is None else interpret
    b, h, hd = q.shape
    page_size = k_pages.shape[1]
    n_pages = ptab.shape[1]
    scale = hd ** -0.5 if scale is None else scale
    qp, kp, vp = q, k_pages, v_pages
    if not interpret and page_size % 8:
        raise ValueError(
            f"page_size={page_size} must be a multiple of 8 (sublane) "
            "for the compiled TPU kernel")
    if page_size == 1 and n_pages > 1:
        ptab = jnp.asarray(ptab, jnp.int32)
        kp = kp[:, 0][ptab]                  # (B, NP, KV, hd) per-row pool
        vp = vp[:, 0][ptab]
        ptab = jnp.arange(b, dtype=jnp.int32)[:, None]
    if not interpret:
        qp = _pad_to(qp, 2, 128)
        kp = _pad_to(kp, 3, 128)
        vp = _pad_to(vp, 3, 128)
    out = _k_paged.paged_attention_pallas(
        qp.astype(jnp.float32), kp.astype(jnp.float32),
        vp.astype(jnp.float32),
        jnp.asarray(ptab, jnp.int32), jnp.asarray(kv_len, jnp.int32),
        scale=float(scale), interpret=interpret,
    )
    return out[:, :, :hd].astype(q.dtype)


def fused_mvm(
    x_parts: jax.Array,      # (M, P, rows) integer-valued signed
    g_pos: jax.Array,        # (S, P, rows, N)
    g_neg: jax.Array,        # (S, P, rows, N)
    *,
    adc_lo: jax.Array,       # (S,) per-slice calibrated range
    adc_hi: jax.Array,
    adc_bits: int,
    cell_bits: int,
    n_bits,                  # None = analog input accumulation
    scale,                   # traced scalar: gain * w_scale * x_scale
    backend: str = "kernel",
    interpret: bool | None = None,
) -> jax.Array:
    """Fused analog MVM chain (matmul + ADC + dequant + slice/bit
    shift-and-add in one launch); returns the dequantized (M, N).
    The kernel and its oracle read the activations partition-major
    ``(P, M, rows)`` (see ``kernels.fused``).

    ``backend="kernel"`` runs the Pallas kernel (interpret mode off-TPU),
    ``"oracle"`` the bitwise-identical jnp mirror (``kernels.ref``) —
    the composed multi-op form of the same chain, which is what the
    fused serving runtime is agreement-gated against end to end.
    """
    if backend not in ("kernel", "oracle"):
        raise ValueError(f"unknown fused_mvm backend {backend!r}")
    interpret = _use_interpret() if interpret is None else interpret
    m, p, rows = x_parts.shape
    n = g_pos.shape[-1]
    bm = _pick_tile(m, 128)
    bn = _pick_tile(n, 128, lane=True)
    xp = _pad_to(jnp.swapaxes(x_parts, 0, 1).astype(jnp.float32), 1, bm)
    gp = _pad_to(g_pos.astype(jnp.float32), 3, bn)
    gm = _pad_to(g_neg.astype(jnp.float32), 3, bn)
    if backend == "oracle":
        out = _k_ref.fused_mvm_diff(
            xp, gp, gm, adc_lo, adc_hi, scale,
            adc_bits=adc_bits, cell_bits=cell_bits, n_bits=n_bits,
            bm=bm, bn=bn,
        )
    else:
        out = _k_fused.fused_mvm_pallas(
            xp, gp, gm, adc_lo, adc_hi, scale,
            adc_bits=adc_bits, cell_bits=cell_bits, n_bits=n_bits,
            bm=bm, bn=bn, interpret=interpret,
        )
    return out[:m, :n]


def fused_mvm_parasitic(
    x_parts: jax.Array,      # (M, P, rows) integer-valued signed
    g_pos: jax.Array,        # (S, P, rows, N)
    g_neg: jax.Array,        # (S, P, rows, N)
    *,
    r_hat,                   # scalar parasitic level (traced or concrete)
    adc_lo: jax.Array,       # (S,)
    adc_hi: jax.Array,
    adc_bits: int,
    cell_bits: int,
    n_bits: int,
    scale,                   # traced scalar: gain * w_scale * x_scale
    backend: str = "kernel",
    interpret: bool | None = None,
) -> jax.Array:
    """Fused parasitic analog MVM chain (per-bit Thomas solve + analog
    bit accumulation + ADC + dequant in one launch); dequantized (M, N).
    The kernel and its oracle read the activations rows-major
    ``(P, rows, M)`` (see ``kernels.bitline``)."""
    if backend not in ("kernel", "oracle"):
        raise ValueError(f"unknown fused_mvm_parasitic backend {backend!r}")
    interpret = _use_interpret() if interpret is None else interpret
    m, p, rows = x_parts.shape
    n = g_pos.shape[-1]
    bm = _pick_tile(m, 128)
    bn = _pick_tile(n, 128, lane=True)
    xt = _pad_to(jnp.transpose(x_parts, (1, 2, 0)).astype(jnp.float32), 2, bm)
    gp = _pad_to(g_pos.astype(jnp.float32), 3, bn)
    gm = _pad_to(g_neg.astype(jnp.float32), 3, bn)
    if backend == "oracle":
        out = _k_ref.fused_mvm_parasitic(
            xt, gp, gm, r_hat, adc_lo, adc_hi, scale,
            adc_bits=adc_bits, cell_bits=cell_bits, n_bits=n_bits,
            bm=bm, bn=bn,
        )
    else:
        out = _k_fused.fused_mvm_parasitic_pallas(
            xt, gp, gm, r_hat, adc_lo, adc_hi, scale,
            adc_bits=adc_bits, cell_bits=cell_bits, n_bits=n_bits,
            bm=bm, bn=bn, interpret=interpret,
        )
    return out[:m, :n]


def flash_attention_decode(
    q: jax.Array,          # (B, H, hd)
    k: jax.Array,          # (B, S, KV, hd) dense per-slot cache
    v: jax.Array,          # (B, S, KV, hd)
    kv_len: jax.Array,     # (B,) int32 valid positions per row
    *,
    block: int = 8,
    scale: float | None = None,
    backend: str = "kernel",
    interpret: bool | None = None,
) -> jax.Array:
    """Flash-decode attention over the *dense* per-slot KV cache; returns
    (B, H, hd).  The dense sibling of :func:`paged_attention`: chunks are
    addressed arithmetically as (row, j) blocks — no block table, no
    gather — and per-row fills arrive by scalar prefetch, so positions at
    or beyond ``kv_len[b]`` contribute exact zeros.

    ``backend="oracle"`` runs the bitwise-identical jnp mirror (the
    chunked cache viewed as a paged pool with an arange table).  The
    cache length is zero-padded to a ``block`` multiple — exact, the pad
    sits at positions >= ``kv_len`` behind the mask.  TPU alignment pads
    the head dim (lane) to 128 with zeros, sliced away on return;
    ``block`` must stay sublane-aligned (multiple of 8) when compiled.
    """
    if backend not in ("kernel", "oracle"):
        raise ValueError(f"unknown flash_attention_decode backend "
                         f"{backend!r}")
    interpret = _use_interpret() if interpret is None else interpret
    b, h, hd = q.shape
    scale = hd ** -0.5 if scale is None else scale
    if not interpret and block % 8:
        raise ValueError(
            f"block={block} must be a multiple of 8 (sublane) for the "
            "compiled TPU kernel")
    kp = _pad_to(k, 1, block)
    vp = _pad_to(v, 1, block)
    qp = q
    if not interpret:
        qp = _pad_to(qp, 2, 128)
        kp = _pad_to(kp, 3, 128)
        vp = _pad_to(vp, 3, 128)
    kv_len = jnp.asarray(kv_len, jnp.int32)
    if backend == "oracle":
        out = _k_ref.flash_attention_decode(
            qp.astype(jnp.float32), kp.astype(jnp.float32),
            vp.astype(jnp.float32), kv_len,
            block=block, scale=float(scale),
        )
    else:
        out = _k_fused.flash_attention_pallas(
            qp.astype(jnp.float32), kp.astype(jnp.float32),
            vp.astype(jnp.float32), kv_len,
            block=block, scale=float(scale), interpret=interpret,
        )
    return out[:, :, :hd].astype(q.dtype)


def bitline_mvm(
    g: jax.Array,            # (K, N)
    x: jax.Array,            # (M, K) signed plane
    r_hat,                   # scalar parasitic level (traced or concrete)
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Parasitic bit-line MVM; returns output currents (M, N).

    ``r_hat`` may be a traced scalar (the sweep engine batches a whole
    Fig. 19 axis through one compilation); a *concrete* 0.0 short-circuits
    to the ideal matmul — that on/off decision is a program-structure bit
    and is never traced (``AnalogSpec.parasitics_on``).
    """
    from repro.core.parasitics import parasitics_off

    if parasitics_off(r_hat):
        return x @ g
    interpret = _use_interpret() if interpret is None else interpret
    m, k = x.shape
    n = g.shape[1]
    bm = _pick_tile(m, 128)
    bn = _pick_tile(n, 128, lane=True)
    xp = _pad_to(x.astype(jnp.float32), 0, bm)
    gp = _pad_to(g.astype(jnp.float32), 1, bn)
    out = _k_bl.bitline_mvm_pallas(gp, xp, r_hat, bm=bm, bn=bn,
                                   interpret=interpret)
    return out[:m, :n]


def analog_mvm_parasitic(
    x_parts: jax.Array,      # (M, P, rows) integer-valued signed
    g_pos: jax.Array,        # (S=1, P, rows, N) or (P, rows, N)
    g_neg: jax.Array,
    *,
    r_hat,                   # scalar parasitic level (traced or concrete)
    n_bits: int,
    adc_lo: jax.Array,
    adc_hi: jax.Array,
    adc_bits: int,
    gain: float,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused Design-A analog MVM under parasitic bit-line resistance.

    Per input bit plane: Thomas-solve both differential line stacks,
    analog-accumulate over bits, one ADC per partition, digital partition
    accumulation — all inside one kernel.  Returns (M, N) code units.
    """
    if g_pos.ndim == 4:
        g_pos, g_neg = g_pos[0], g_neg[0]
    interpret = _use_interpret() if interpret is None else interpret
    m, p, rows = x_parts.shape
    n = g_pos.shape[-1]
    bm = _pick_tile(m, 128)
    bn = _pick_tile(n, 128, lane=True)
    xt = _pad_to(jnp.transpose(x_parts, (1, 2, 0)).astype(jnp.float32), 2, bm)
    gp = _pad_to(g_pos.astype(jnp.float32), 2, bn)
    gm = _pad_to(g_neg.astype(jnp.float32), 2, bn)
    out = _k_bl.analog_bitline_diff_pallas(
        xt, gp, gm, r_hat,
        jnp.asarray(adc_lo), jnp.asarray(adc_hi),
        n_bits=n_bits, adc_bits=adc_bits, gain=float(gain),
        bm=bm, bn=bn, interpret=interpret,
    )
    return out[:m, :n]
