"""Pallas TPU paged-attention decode kernel.

Single-token (decode-step) GQA attention over a paged KV pool: the KV
cache lives as fixed-size pages in one global ``(P, page, KV, hd)`` pool
and each batch row owns an ordered page list (its block table).  The
gather happens *in kernel*: the block table is a scalar-prefetch operand,
so each page's DMA source is computed from ``ptab[b, j]`` before the
body runs and pages stream HBM -> VMEM without ever materializing the
``(B, NP*page)`` dense gather in HBM (the same in-VMEM staging idea as
PR 3's ``analog_bitline_diff_pallas``, applied to the KV stream).

Grid/Block layout::

    grid = (B, 3, NP)              # NP = pages per row (block-table width)
    q block   (1, H, hd)      index (b, 0, 0)
    k/v block (1, ps, KV, hd) index (ptab[b, j], 0, 0, 0)   (prefetched)
    out       (1, H, hd)      index (b, 0, 0)  written at the last cell

The middle grid dimension is the *phase*: phase 0 walks the row's pages
accumulating only the running logit max into VMEM scratch; phase 1
re-walks them materializing each page's softmax contribution against
that now-global max into a per-page scratch slot; phase 2 folds the
slots into the output with pure adds.  A classic one-pass flash-decode
recurrence would rescale (``acc * corr + p @ v``) — a multiply-add that
XLA/LLVM may or may not contract into an FMA depending on the
surrounding graph, which breaks bitwise reproducibility between the
kernel and any independently compiled oracle.  Even the two-phase form
is not enough: when ``page_size == 1`` the page contraction degenerates
to a bare multiply and LLVM contracts ``acc + p * v`` into an FMA *even
across an explicit optimization barrier* (one rounding instead of two —
a 1-ulp drift).  Materializing every page's term first forces each
product through a loop-carried scratch buffer, where it must be a
rounded f32 before the phase-2 add ever sees it; the accumulation is
then a plain add of identically-computed terms in page order, so the
kernel matches ``ref.paged_attention_decode`` bit for bit on most inputs
and within a few ULPs of max|v| on all (``tests/test_kernels.py``: the
score dot and ``exp`` still round per fusion context under XLA-CPU), at
the cost of streaming K twice and ``NP`` per-page term slots of VMEM
scratch.

Per-row cache lengths arrive as the second scalar-prefetch operand;
positions at or beyond ``kv_len[b]`` are masked to ``NEG_INF`` exactly
as ``models.layers.streaming_attention`` masks them, so block-table
entries past a row's fill (conventionally the sink page 0) contribute
exact zeros and the result is invariant to how the table tail is padded.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # matches models.layers.NEG_INF


def _paged_kernel(ptab_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, da_ref, *,
                  page_size: int, scale: float):
    b = pl.program_id(0)
    phase = pl.program_id(1)
    j = pl.program_id(2)
    n_pages = pl.num_programs(2)

    @pl.when((phase == 0) & (j == 0))
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_heads, g, hd = acc_ref.shape
    qg = q_ref[0].reshape(kv_heads, g, hd) * scale       # (KV, g, hd) f32
    k = k_ref[0]                                         # (ps, KV, hd)

    s = jnp.einsum("kgd,pkd->kgp", qg, k,
                   preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)  # (KV, g, ps)
    k_pos = j * page_size + jax.lax.iota(jnp.int32, page_size)
    valid = k_pos < len_ref[b]
    s = jnp.where(valid[None, None, :], s, NEG_INF)

    @pl.when(phase == 0)
    def _max_pass():
        m_ref[...] = jnp.maximum(m_ref[...], jnp.max(s, axis=-1))

    @pl.when(phase == 1)
    def _materialize():
        p = jnp.exp(s - m_ref[...][..., None])           # (KV, g, ps)
        l_ref[...] = l_ref[...] + jnp.sum(p, axis=-1)
        # Store this page's numerator term instead of accumulating it in
        # place: `acc + p @ v` contracts to an FMA when page_size == 1
        # degenerates the contraction to a multiply (LLVM contracts even
        # across an optimization barrier), which would drift 1 ulp from
        # the oracle.  The store forces the product through a
        # loop-carried f32 slot; phase 2 adds only rounded values.
        da_ref[j] = jnp.einsum("kgp,pkd->kgd", p, v_ref[0],
                               preferred_element_type=jnp.float32,
                               precision=jax.lax.Precision.HIGHEST)

    @pl.when(phase == 2)
    def _accumulate():
        acc_ref[...] = acc_ref[...] + da_ref[j]

    @pl.when((phase == 2) & (j == n_pages - 1))
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[..., None]
        o_ref[0] = out.reshape(kv_heads * g, hd)


def paged_attention_pallas(
    q: jax.Array,          # (B, H, hd) float32
    k_pages: jax.Array,    # (P, page_size, KV, hd) float32
    v_pages: jax.Array,    # (P, page_size, KV, hd) float32
    ptab: jax.Array,       # (B, NP) int32 block table
    kv_len: jax.Array,     # (B,) int32 valid positions per row
    *,
    scale: float,
    interpret: bool,
) -> jax.Array:
    b, h, hd = q.shape
    _, page_size, kv_heads, _ = k_pages.shape
    n_pages = ptab.shape[1]
    if h % kv_heads:
        raise ValueError(f"{h} query heads not divisible by {kv_heads} "
                         "KV heads")
    g = h // kv_heads
    kern = functools.partial(_paged_kernel, page_size=page_size,
                             scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, 3, n_pages),
        in_specs=[
            pl.BlockSpec((1, h, hd),
                         lambda bi, ph, j, tab, ln: (bi, 0, 0)),
            pl.BlockSpec((1, page_size, kv_heads, hd),
                         lambda bi, ph, j, tab, ln: (tab[bi, j], 0, 0, 0)),
            pl.BlockSpec((1, page_size, kv_heads, hd),
                         lambda bi, ph, j, tab, ln: (tab[bi, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, hd),
                               lambda bi, ph, j, tab, ln: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kv_heads, g), jnp.float32),       # global max
            pltpu.VMEM((kv_heads, g), jnp.float32),       # denominator
            pltpu.VMEM((kv_heads, g, hd), jnp.float32),   # weighted acc
            pltpu.VMEM((n_pages, kv_heads, g, hd),
                       jnp.float32),                      # per-page terms
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, hd), jnp.float32),
        interpret=interpret,
    )(ptab, kv_len, q, k_pages, v_pages)
