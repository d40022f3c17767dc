"""Fused Pallas serving kernels: the whole analog decode chain per site.

One kernel launch covers what ``core.analog.analog_matmul`` otherwise
composes from many small ops: in-VMEM input bit-plane expansion (PR 3's
``analog_bitline_diff_pallas`` technique), the slice/partition-tiled
differential MVM, the per-partition ADC epilogue, and the dequant +
shift-and-add slice accumulation — so the (B, S, P, M, N) pre-ADC
intermediates of the composed path never exist in HBM.  A parasitic
variant runs the Thomas bit-line solve per input bit plane inside the
same launch (Design A under Sec. 8 parasitics).

Grid/BlockSpec layout (both MVM kernels)::

    grid = (M // bm, N // bn, P)           # P = analog K-partitions
    scale    (1, 1)                        # gain * w_scale * x_scale
    lo/hi    (S, 1)                        # per-slice calibrated ADC range
    x block  (-, bm, rows)   index (p, i, 0)   # x is (P, M, rows)
    g blocks (S, 1, rows, bn) index (0, p, 0, j)   # all slices resident
    out      (bm, bn)        index (i, j)  accumulated over p

A layer scan may hand the kernel a site's whole layer stack
``(L, S, P, rows, N)`` and a traced layer index in place of the layer's
slice: the g blocks are then ``(-, S, 1, rows, bn)`` at index
``(l, 0, p, 0, j)``, ``l`` read from a scalar-prefetch operand, and the
grid, tiles and arithmetic are unchanged (``_mvm_call``).

The activations are *partition-major* ``(P, M, rows)`` (``-`` is the
squeezed partition dim): Mosaic requires the last two dims of a block to
be (8, 128)-aligned or whole, so the ``(bm, rows)`` tile must be the
trailing pair — an ``(M, P, rows)`` layout would put a block dim of 1
over ``P`` there, which compiles only when ``P == 1``.  The parasitic
kernel takes the same operand *rows-major*, ``(P, rows, M)`` with block
``(-, rows, bm)``: its Thomas sweep reads one array row per step as a
sublane slice (``kernels.bitline``).  The ``ops`` wrappers take
``(M, P, rows)`` like every other MVM wrapper and make the one transpose
their kernel needs.

All ``S`` slices of a partition are resident at once.  At ``S = 8``
(1-bit cells) and ``rows = 1152`` the two conductance blocks take
2 x 2 buffers x 4.7 MB = 18.9 MB of VMEM, over the 16 MiB default scoped
limit, so the MVM kernels raise the limit to :data:`VMEM_LIMIT_BYTES`.

Slices and input bits are *static unrolled* loops inside one grid step
(S <= 8, n_bits <= 7), so a sliced design still costs one launch; the
innermost grid dimension walks partitions and revisits the output block,
and the final partition's step applies the single dequant multiply.

Exactness contract
------------------
Every kernel here is pinned against its ``ref.py`` oracle
(``tests/test_kernels.py``, ``tests/test_fastpath_routing.py``): bit for
bit where one partition and one slice leave nothing to reassociate, and
within a stated fraction of one ADC code otherwise — the float32 bits of
the partition sum and the dequant multiply follow each compiler's
contraction choices (2 ULP under XLA-CPU in jax 0.9).  Two disciplines
keep the two sides on the same ADC codes across compilation contexts
(the oracle compiles inside an arbitrary XLA fusion; the kernel lowers
through interpret mode on CPU and Mosaic on TPU):

* *Exact-product multiply-adds only.*  LLVM may contract ``a + b*c`` into
  an FMA (one rounding instead of two) depending on the surrounding
  graph — even across optimization barriers (see ``kernels.paged``), and
  XLA's simplifier strips identity ``* 1.0`` weights first, so the
  multiply the add actually sees is whatever produced the term.  An FMA
  is bit-identical to mul-then-add exactly when the product rounds to
  itself, so the epilogue is arranged so that every value feeding an
  accumulation add is produced by an *add* or by an exact power-of-two
  multiply: the ADC stays in code units (``fused_adc_code_units`` ends in
  ``lo/lsb + code``), bit weights ``2**b`` and slice weights are powers
  of two, and the one inexact ``* lsb`` per slice is applied *outside*
  the bit fold where its outer power-of-two weight shields it (single-
  slice designs defer ``lsb`` to the final dequant multiply entirely).
  The result is FMA-*invariant*: any contraction choice yields the same
  bits.
* *Shape-matched dots.*  The oracle mirrors the wrapper's padding and
  walks the identical (bm, rows) x (rows, bn) tiles in the identical
  (i, j, p, s, b) order, so each ``dot_general`` reduction and each
  accumulation add sees the same operands in the same order on both sides.

The dequant scale (``gain * w_scale * x_scale``) is one *traced* (1, 1)
operand — the sweep engine batches traced ``on_off_ratio`` (hence traced
``gain``) points through a single compilation, so the kernel must not
bake it in (same rule as ``r_hat`` in ``kernels.bitline``).

``flash_attention_pallas`` is the dense-cache sibling of the paged
kernel: same three-phase (max / materialize / pure-add) structure and
bitwise discipline, but blocks are addressed arithmetically as
``(row, j)`` chunks of the per-slot cache — no block table, one
scalar-prefetch operand for the per-row fills.  It touches the cache as
little as the arithmetic allows:

* chunks are large, ``ops.flash_decode_block`` positions chosen from the
  cache's shape and dtype (the largest multiple of 8 dividing the cache
  under a VMEM budget), so the grid has few steps;
* every K/V index map stops at the row's last filled chunk and all work
  is gated on it, so a chunk wholly past the fill is neither fetched nor
  computed (bitwise-neutral: the oracle adds exact zeros for it);
* phase 0 keeps each chunk's scores in VMEM scratch, so K is read once,
  V only in phase 1, and phase 2 reads nothing;
* K and V arrive in their stored dtype and are upcast per chunk in VMEM
  (exact), in the layout the chip stores the cache in: ``(B, S, KV, hd)``
  or, for a KV count the TPU lays out head-major, ``(B, KV, S, hd)``
  (``ops.flash_decode_heads_major``), so no copy of the cache is made
  before the call.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.analog_mvm import _bit_plane
from repro.kernels.bitline import _thomas_bottom_current, plane_rows
from repro.kernels.paged import NEG_INF

#: scoped-VMEM limit of the fused MVM kernels.  The largest working set
#: they are built for is S = 8 slices at rows = 1152 with bm = bn = 128
#: and digital input accumulation: 18.9 MB of double-buffered
#: conductance blocks and 1.2 MB of x blocks, plus the seven
#: (bm, rows) input bit planes and per-slice temporaries of the unrolled
#: loops.  Mosaic refuses that kernel at 32 MiB and accepts it at 40 MiB
#: (``tests/test_tpu_compile.py``); 48 MiB leaves a fifth to spare and is
#: under half of a TPU v5e core's 128 MiB of VMEM.  Flash-decode takes the
#: same limit: reading a ``(B, S, KV, hd)`` cache it relays each chunk out
#: per KV head in VMEM, 26.5 MB at 384 positions of 8 heads of 128.
VMEM_LIMIT_BYTES = 48 * 2 ** 20


#: static compile identities of every fused MVM kernel traced in this
#: process, in ``core.analog.fuse_signature`` format.  Populated at trace
#: time (one entry per distinct fused program structure, shapes excluded);
#: the ``serve/fused-compile-per-site-class`` contract clears it, serves a
#: trace, and pins it equal to ``hw.fused_site_classes`` of the profile.
BUILD_SIGNATURES: set = set()


def adc_lsb(lo, hi, bits: int):
    """ADC step size with the ``core.adc`` degenerate-range guard."""
    lsb = (hi - lo) / (2 ** bits - 1)
    return jnp.where(lsb <= 0, 1.0, lsb)


def fused_adc_code_units(v, lo, lsb, bits: int):
    """Clip/quantize to ``2**bits`` levels, returning the dequantized
    value in *code units* (``value / lsb = lo/lsb + code``).

    Keeping the epilogue in code units until a single late ``* lsb`` is
    what makes the accumulation FMA-invariant (see module docstring):
    the value fed to every accumulation add is produced by this *add*
    (or by an exact power-of-two multiply of it), never by the inexact
    ``* lsb`` — so LLVM contracting ``a + b*c`` into an FMA cannot
    change the bits on either the kernel or the oracle side.

    Shared verbatim by the kernels and the ``ref.py`` oracles so the
    epilogue cannot diverge between them.  ``(lo/lsb + code) * lsb``
    matches ``core.adc.adc_quantize`` to within 1 ulp (same grid,
    different rounding of the ``lo`` offset).
    """
    n_levels = 2 ** bits
    code = jnp.clip(jnp.round((v - lo) / lsb), 0.0, n_levels - 1.0)
    return lo / lsb + code


def term_weight(cell_bits: int, s: int, b) -> float:
    """Shift-and-add weight of slice ``s``, input bit ``b`` (``None`` for
    the analog-accumulation single term) — an exact power of two."""
    return 2.0 ** (cell_bits * s + (0 if b is None else b))


def _fused_diff_kernel(scale_ref, lo_ref, hi_ref, x_ref, gp_ref, gm_ref,
                       o_ref, *, adc_bits: int, cell_bits: int, n_bits):
    """Differential MVM chain: per (slice, bit) matmul + ADC, shift-and-add
    in code units, partition accumulation, final dequant multiply.

    ``n_bits is None`` selects analog input accumulation (the quantized
    integer activations feed the array whole, one ADC per slice);
    otherwise bit planes are extracted in VMEM and digitized separately
    (digital shift-and-add, Design D/E style).
    """
    p = pl.program_id(2)
    n_p = pl.num_programs(2)

    @pl.when(p == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]                           # (bm, rows) integer-valued
    n_slices = gp_ref.shape[0]
    if n_bits is not None:
        sign = jnp.sign(x)
        mag = jnp.abs(x)
    acc = jnp.zeros_like(o_ref)
    for s in range(n_slices):                # static unroll: S <= 8
        g = gp_ref[s, 0] - gm_ref[s, 0]      # (rows, bn) analog subtraction
        lo = lo_ref[s, 0]
        lsb = adc_lsb(lo, hi_ref[s, 0], adc_bits)
        a_s = jnp.zeros_like(o_ref)          # slice accum, code units
        for b in (range(n_bits) if n_bits is not None else (None,)):
            plane = x if b is None else _bit_plane(mag, sign, b)
            v = jnp.dot(plane, g, preferred_element_type=jnp.float32,
                        precision=jax.lax.Precision.HIGHEST)
            q = fused_adc_code_units(v, lo, lsb, adc_bits)
            # exact power-of-two product: FMA-invariant accumulation
            a_s = a_s + q * term_weight(0, 0, b)
        if n_slices == 1:
            acc = a_s                        # lsb folds into final dequant
        else:
            # outer multiply is the exact power-of-two slice weight, so
            # contraction into the cross-slice add cannot reround
            acc = acc + (a_s * lsb) * term_weight(cell_bits, s, None)
    o_ref[...] += acc

    @pl.when(p == n_p - 1)
    def _dequant():
        out_scale = scale_ref[0, 0]
        if n_slices == 1:
            out_scale = out_scale * adc_lsb(lo_ref[0, 0], hi_ref[0, 0],
                                            adc_bits)
        o_ref[...] = o_ref[...] * out_scale


def _fused_parasitic_kernel(scale_ref, r_ref, lo_ref, hi_ref, xt_ref,
                            gp_ref, gm_ref, o_ref, *, adc_bits: int,
                            cell_bits: int, n_bits: int, rows: int):
    """Design-A parasitic chain: per (slice, bit) Thomas solves for both
    differential lines, analog (switched-capacitor) accumulation over
    bits, one ADC per slice, shift-and-add, final dequant multiply.
    ``xt_ref`` is the rows-major ``(rows, bm)`` input block."""
    p = pl.program_id(2)
    n_p = pl.num_programs(2)

    @pl.when(p == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    r = r_ref[0, 0]
    n_slices = gp_ref.shape[0]
    acc = jnp.zeros_like(o_ref)
    for s in range(n_slices):                # static unroll: S <= 8
        accb = jnp.zeros_like(o_ref)
        for b in range(n_bits):              # static unroll: n_bits <= 7
            i_pos = _thomas_bottom_current(
                plane_rows(xt_ref, b),
                lambda i, s=s: gp_ref[s, 0, pl.ds(i, 1), :], r,
                k=rows, shape=o_ref.shape)
            i_neg = _thomas_bottom_current(
                plane_rows(xt_ref, b),
                lambda i, s=s: gm_ref[s, 0, pl.ds(i, 1), :], r,
                k=rows, shape=o_ref.shape)
            accb = accb + (i_pos - i_neg) * 2.0 ** b
        lo = lo_ref[s, 0]
        lsb = adc_lsb(lo, hi_ref[s, 0], adc_bits)
        a_s = fused_adc_code_units(accb, lo, lsb, adc_bits)
        if n_slices == 1:
            acc = a_s                        # lsb folds into final dequant
        else:
            acc = acc + (a_s * lsb) * term_weight(cell_bits, s, None)
    o_ref[...] += acc

    @pl.when(p == n_p - 1)
    def _dequant():
        out_scale = scale_ref[0, 0]
        if n_slices == 1:
            out_scale = out_scale * adc_lsb(lo_ref[0, 0], hi_ref[0, 0],
                                            adc_bits)
        o_ref[...] = o_ref[...] * out_scale


def _mvm_call(kern, x_in, x_block, x_index, g_pos, g_neg, extra, adc_lo,
              adc_hi, scale, *, site: tuple, m: int, bm: int, bn: int,
              interpret: bool, layer=None):
    """Shared pallas_call plumbing for both fused MVM kernels.

    ``x_in`` is the activation operand in the layout its kernel reads,
    ``x_block`` its block shape, ``x_index`` its ``(i, j, p) -> index``
    map and ``m`` its (padded) row count.  ``extra`` is a list of
    additional leading (1, 1) scalar operands (the parasitic ``r_hat``);
    ``adc_lo/adc_hi`` are per-slice (S,).  ``site`` is the call's site
    class (a :data:`BUILD_SIGNATURES` entry), recorded here and carried in
    the kernel's metadata beside ``"kernel": "fused_mvm"``, so a profiler
    trace names the op and its site class.

    With ``layer`` (a traced int32 scalar) the conductances are a layer
    stack ``(L, S, P, rows, N)`` and the kernel picks layer ``layer``
    itself: its conductance blocks are indexed ``(layer, 0, p, 0, j)``
    from a scalar-prefetched index, so no per-layer slice of the stack is
    written out before the call.  The (1, 1) scale is then prefetched too,
    ahead of the index, and stays the call's first operand.  Blocks,
    grid and arithmetic are those of the per-layer call, so are the bits;
    metadata ``"stacked"`` says which of the two a call is.
    """
    BUILD_SIGNATURES.add(site)
    n_slices, p, rows, n = g_pos.shape[-4:]
    if m % bm or n % bn:
        raise ValueError(
            f"block shape ({bm}, {bn}) does not tile operand ({m}, {n})")
    scale2 = jnp.asarray(scale, jnp.float32).reshape(1, 1)
    lo2 = jnp.asarray(adc_lo, jnp.float32).reshape(n_slices, 1)
    hi2 = jnp.asarray(adc_hi, jnp.float32).reshape(n_slices, 1)
    extra2 = [jnp.asarray(e, jnp.float32).reshape(1, 1) for e in extra]

    def call(stacked: bool):
        def spec(block, index):
            # index maps see the grid, then any scalar-prefetch refs
            return pl.BlockSpec(block, lambda i, j, p_, *_: index(i, j, p_))

        specs = ([spec((1, 1), lambda i, j, p_: (0, 0)) for _ in extra2]
                 + [spec((n_slices, 1), lambda i, j, p_: (0, 0))
                    for _ in range(2)]
                 + [spec(x_block, x_index)])
        if stacked:
            g_spec = pl.BlockSpec(
                (None, n_slices, 1, rows, bn),
                lambda i, j, p_, scale_ref, layer_ref:
                    (layer_ref[0], 0, p_, 0, j))
            body = lambda scale_ref, layer_ref, *refs: kern(scale_ref, *refs)
        else:
            g_spec = spec((n_slices, 1, rows, bn),
                          lambda i, j, p_: (0, p_, 0, j))
            specs = [spec((1, 1), lambda i, j, p_: (0, 0))] + specs
            body = kern
        grid_kw = dict(grid=(m // bm, n // bn, p),
                       in_specs=specs + [g_spec, g_spec],
                       out_specs=spec((bm, bn), lambda i, j, p_: (i, j)))
        return pl.pallas_call(
            body,
            grid_spec=(pltpu.PrefetchScalarGridSpec(num_scalar_prefetch=2,
                                                    **grid_kw)
                       if stacked else pl.GridSpec(**grid_kw)),
            name="fused_mvm",
            metadata={"kernel": "fused_mvm", "site": str(site),
                      "stacked": stacked},
            out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            interpret=interpret,
        )

    if layer is None:
        return call(False)(scale2, *extra2, lo2, hi2, x_in, g_pos, g_neg)

    @jax.custom_batching.custom_vmap
    def stacked_call(layer1, scale2, *operands):
        return call(True)(scale2, layer1, *operands)

    @stacked_call.def_vmap
    def _batched(axis_size, in_batched, layer1, scale2, *operands):
        # Pallas batches a call over a prefetched operand (here the scale,
        # one per trial) only by a loop that slices every batched operand,
        # whole stacks included; slicing the layer out first and batching
        # the per-layer call reads each stack no more than the scan did
        def one(layer1, scale2, *operands):
            *rest, gp, gm = operands
            return call(False)(scale2, *rest, gp[layer1[0]], gm[layer1[0]])

        axes = [0 if b else None for b in in_batched]
        return jax.vmap(one, in_axes=axes, axis_size=axis_size)(
            layer1, scale2, *operands), True

    layer1 = jnp.asarray(layer, jnp.int32).reshape(1)
    return stacked_call(layer1, scale2, *extra2, lo2, hi2, x_in, g_pos,
                        g_neg)


def fused_mvm_pallas(
    x_parts: jax.Array,    # (P, M, rows) integer-valued signed
    g_pos: jax.Array,      # (S, P, rows, N)
    g_neg: jax.Array,      # (S, P, rows, N)
    adc_lo: jax.Array,     # (S,) per-slice calibrated range
    adc_hi: jax.Array,
    scale,                 # traced scalar: gain * w_scale * x_scale
    *,
    adc_bits: int,
    cell_bits: int,
    n_bits,                # None = analog input accumulation
    bm: int = 128,
    bn: int = 128,
    interpret: bool,
    layer=None,            # traced int32: g_* are (L, S, P, rows, N) stacks
) -> jax.Array:
    """Fused differential analog MVM; returns the dequantized (M, N)."""
    kern = functools.partial(_fused_diff_kernel, adc_bits=adc_bits,
                             cell_bits=cell_bits, n_bits=n_bits)
    _, m, rows = x_parts.shape
    site = ("linear", g_pos.shape[-4], cell_bits, adc_bits, n_bits, None)
    return _mvm_call(kern, x_parts, (None, bm, rows),
                     lambda i, j, p_: (p_, i, 0), g_pos, g_neg, [], adc_lo,
                     adc_hi, scale, site=site, m=m, bm=bm, bn=bn,
                     interpret=interpret, layer=layer)


def fused_mvm_parasitic_pallas(
    xt_parts: jax.Array,   # (P, rows, M) integer-valued signed
    g_pos: jax.Array,      # (S, P, rows, N)
    g_neg: jax.Array,      # (S, P, rows, N)
    r_hat,                 # traced or concrete scalar parasitic level
    adc_lo: jax.Array,     # (S,)
    adc_hi: jax.Array,
    scale,                 # traced scalar: gain * w_scale * x_scale
    *,
    adc_bits: int,
    cell_bits: int,
    n_bits: int,
    bm: int = 128,
    bn: int = 128,
    interpret: bool,
    layer=None,            # traced int32: g_* are (L, S, P, rows, N) stacks
) -> jax.Array:
    """Fused parasitic differential MVM; returns the dequantized (M, N)."""
    _, rows, m = xt_parts.shape
    kern = functools.partial(_fused_parasitic_kernel, adc_bits=adc_bits,
                             cell_bits=cell_bits, n_bits=n_bits, rows=rows)
    site = ("parasitic", g_pos.shape[-4], cell_bits, adc_bits, None, n_bits)
    return _mvm_call(kern, xt_parts, (None, rows, bm),
                     lambda i, j, p_: (p_, 0, i), g_pos, g_neg, [r_hat],
                     adc_lo, adc_hi, scale, site=site, m=m, bm=bm, bn=bn,
                     interpret=interpret, layer=layer)


# ---------------------------------------------------------------------------
# flash-decode attention over the dense per-slot KV cache
# ---------------------------------------------------------------------------


def _flash_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, sc_ref, da_ref, *,
                  block: int, scale: float, heads_major: bool):
    """Three-phase flash-decode over dense cache chunks — the body of
    ``kernels.paged._paged_kernel`` with arithmetic block addressing in
    place of the block-table gather (see that module for why the phases
    and the per-chunk term slots are what make it bit-exact).

    Phase 0 computes each chunk's masked scores once and keeps them in
    ``sc_ref``; phase 1 reads them back (the same values a recomputation
    would give) and reads V; phase 2 only adds.  Chunks past the row's
    last filled one do nothing: in the oracle they contribute
    ``max(m, NEG_INF) = m``, ``l + 0`` and ``acc + 0``."""
    b = pl.program_id(0)
    phase = pl.program_id(1)
    j = pl.program_id(2)
    n_blocks = pl.num_programs(2)
    filled = j <= _last_block(len_ref[b], block, n_blocks)

    @pl.when((phase == 0) & (j == 0))
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_heads, g, hd = acc_ref.shape
    pkd = "kpd" if heads_major else "pkd"                # the chunk's layout

    @pl.when((phase == 0) & filled)
    def _scores():
        qg = q_ref[0].reshape(kv_heads, g, hd) * scale   # (KV, g, hd) f32
        s = jnp.einsum(f"kgd,{pkd}->kgp", qg, k_ref[0].astype(jnp.float32),
                       preferred_element_type=jnp.float32,
                       precision=jax.lax.Precision.HIGHEST)  # (KV, g, block)
        k_pos = j * block + jax.lax.iota(jnp.int32, block)
        valid = k_pos < len_ref[b]
        s = jnp.where(valid[None, None, :], s, NEG_INF)
        sc_ref[j] = s
        m_ref[...] = jnp.maximum(m_ref[...], jnp.max(s, axis=-1))

    @pl.when((phase == 1) & filled)
    def _materialize():
        p = jnp.exp(sc_ref[j] - m_ref[...][..., None])   # (KV, g, block)
        l_ref[...] = l_ref[...] + jnp.sum(p, axis=-1)
        da_ref[j] = jnp.einsum(f"kgp,{pkd}->kgd", p,
                               v_ref[0].astype(jnp.float32),
                               preferred_element_type=jnp.float32,
                               precision=jax.lax.Precision.HIGHEST)

    @pl.when((phase == 2) & filled)
    def _accumulate():
        acc_ref[...] = acc_ref[...] + da_ref[j]

    @pl.when((phase == 2) & (j == n_blocks - 1))
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[..., None]
        o_ref[0] = out.reshape(kv_heads * g, hd)


def _last_block(fill, block: int, n_blocks):
    """Index of the chunk that holds a row's last filled position (a row
    holds at least one)."""
    return jnp.minimum((jnp.maximum(fill, 1) - 1) // block, n_blocks - 1)


def flash_attention_pallas(
    q: jax.Array,          # (B, H, hd) float32
    k: jax.Array,          # (B, S, KV, hd) dense per-slot cache, as stored
    v: jax.Array,          # (B, S, KV, hd); both (B, KV, S, hd) if heads_major
    kv_len: jax.Array,     # (B,) int32 valid positions per row, >= 1
    *,
    block: int,
    scale: float,
    heads_major: bool,
    interpret: bool,
) -> jax.Array:
    b, h, hd = q.shape
    seq, kv_heads = k.shape[1:3]
    if heads_major:
        seq, kv_heads = kv_heads, seq
    if seq % block:
        raise ValueError(f"cache length {seq} not divisible by "
                         f"block {block}")
    if h % kv_heads:
        raise ValueError(f"{h} query heads not divisible by {kv_heads} "
                         "KV heads")
    g = h // kv_heads
    n_blocks = seq // block
    kv_block = (1, kv_heads, block, hd) if heads_major else \
        (1, block, kv_heads, hd)

    def chunk(bi, j):
        return (bi, 0, j, 0) if heads_major else (bi, j, 0, 0)

    # K is read in phase 0 only and V in phase 1 only; elsewhere each
    # holds the index it had (or is about to have), and every index stops
    # at the row's last filled chunk, so Pallas starts no DMA for a
    # chunk twice or for one past the fill
    def k_index(bi, ph, j, ln):
        jk = jnp.where(ph == 0, j, n_blocks - 1)
        return chunk(bi, jnp.minimum(
            jk, _last_block(ln[bi], block, n_blocks)))

    def v_index(bi, ph, j, ln):
        jv = jnp.where(ph == 0, 0, jnp.where(ph == 1, j, n_blocks - 1))
        return chunk(bi, jnp.minimum(
            jv, _last_block(ln[bi], block, n_blocks)))

    kern = functools.partial(_flash_kernel, block=block, scale=scale,
                             heads_major=heads_major)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, 3, n_blocks),
        in_specs=[
            pl.BlockSpec((1, h, hd),
                         lambda bi, ph, j, ln: (bi, 0, 0)),
            pl.BlockSpec(kv_block, k_index),
            pl.BlockSpec(kv_block, v_index),
        ],
        out_specs=pl.BlockSpec((1, h, hd),
                               lambda bi, ph, j, ln: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kv_heads, g), jnp.float32),       # global max
            pltpu.VMEM((kv_heads, g), jnp.float32),       # denominator
            pltpu.VMEM((kv_heads, g, hd), jnp.float32),   # weighted acc
            pltpu.VMEM((n_blocks, kv_heads, g, block),
                       jnp.float32),                      # per-chunk scores
            pltpu.VMEM((n_blocks, kv_heads, g, hd),
                       jnp.float32),                      # per-chunk terms
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, hd), jnp.float32),
        name="flash_decode",
        # the cache layout the kernel reads (``ops.flash_decode_heads_major``)
        # and the query heads per KV head, for a trace to tell the paths apart
        metadata={"kernel": "flash_decode",
                  "layout": "heads_major" if heads_major else "row_major",
                  "group": g},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(kv_len, q, k, v)
