"""Pure-jnp oracles for the Pallas kernels.

These are the ground truth the kernels are tested against
(``assert_allclose`` over shape/dtype sweeps) and what the accuracy model
in ``repro.core.analog`` reduces to on the matching design points.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def adc(v: jax.Array, lo, hi, bits: int) -> jax.Array:
    """Uniform clip+quantize, dequantized levels (see core.adc)."""
    n_levels = 2 ** bits
    lsb = (hi - lo) / (n_levels - 1)
    code = jnp.clip(jnp.round((v - lo) / lsb), 0, n_levels - 1)
    return lo + code * lsb


def analog_mvm_diff(
    x_parts: jax.Array,   # (M, P, rows) integer-valued
    g_pos: jax.Array,     # (P, rows, N)
    g_neg: jax.Array,     # (P, rows, N)
    *,
    adc_lo,
    adc_hi,
    adc_bits: int,
    gain: float,
) -> jax.Array:
    """Design-A path: differential, unsliced, analog input accumulation.

    Per K-partition: analog dot product, analog differential subtraction,
    one ADC conversion, then digital accumulation over partitions in code
    units (x ``gain``).  Output (M, N), code units.
    """
    v = jnp.einsum("mpr,prn->pmn", x_parts, g_pos - g_neg,
                   precision=jax.lax.Precision.HIGHEST)
    v_hat = adc(v, adc_lo, adc_hi, adc_bits)
    return jnp.sum(v_hat, axis=0) * gain


def analog_mvm_bitserial(
    x_parts: jax.Array,   # (M, P, rows) integer-valued, signed
    g_pos: jax.Array,     # (P, rows, N)
    g_neg: jax.Array,     # (P, rows, N)
    *,
    n_bits: int,
    adc_lo,
    adc_hi,
    adc_bits: int,
    gain: float,
) -> jax.Array:
    """Design-D path: differential, unsliced, *digital* input accumulation.

    Every input bit plane is digitized separately and aggregated by digital
    shift-and-add.  The oracle materializes all bit planes; the kernel
    extracts them in VMEM.
    """
    sign = jnp.sign(x_parts)
    mag = jnp.abs(x_parts).astype(jnp.int32)
    acc = None
    g = g_pos - g_neg
    for b in range(n_bits):
        plane = (((mag >> b) & 1).astype(x_parts.dtype)) * sign
        v = jnp.einsum("mpr,prn->pmn", plane, g,
                       precision=jax.lax.Precision.HIGHEST)
        v_hat = adc(v, adc_lo, adc_hi, adc_bits)
        contrib = jnp.sum(v_hat, axis=0) * (2.0 ** b)
        acc = contrib if acc is None else acc + contrib
    return acc * gain


def paged_attention_decode(
    q: jax.Array,          # (B, H, hd)
    k_pages: jax.Array,    # (P, page_size, KV, hd)
    v_pages: jax.Array,    # (P, page_size, KV, hd)
    ptab: jax.Array,       # (B, NP) int32 block table
    kv_len: jax.Array,     # (B,) int32 valid positions per row
    *,
    scale=None,
) -> jax.Array:
    """Gather oracle for the paged-attention decode kernel.

    Walks the block table page by page in the kernel's exact two-phase
    order — a max-only pass, then a pure-add accumulation pass against
    the global max — with the same per-cell einsum contractions and
    masking constant.  The two-phase form has no ``acc * corr + x``
    rescale, so there is no multiply-add for the compiler to contract
    into an FMA differently per compilation context; that is what keeps
    the interpret-mode kernel within a few ULPs of this oracle, and
    bit-exact on most inputs (``tests/test_kernels.py``).  Positions at or
    beyond ``kv_len[b]`` contribute exact zeros, so the result is
    invariant to block-table tail padding.

    ``page_size == 1`` is canonicalized to a single page of ``NP``
    tokens per row — the identical rewrite ``ops.paged_attention``
    applies — because size-1 page einsums degenerate to elementwise
    code whose FMA contraction is fusion-context-dependent, which would
    make "bitwise" ill-defined.
    """
    neg_inf = -1e30                      # layers.NEG_INF / paged.NEG_INF
    b, h, hd = q.shape
    _, page_size, kv_heads, _ = k_pages.shape
    n_pages = ptab.shape[1]
    if page_size == 1 and n_pages > 1:
        tab = jnp.asarray(ptab, jnp.int32)
        return paged_attention_decode(
            q, k_pages[:, 0][tab], v_pages[:, 0][tab],
            jnp.arange(b, dtype=jnp.int32)[:, None], kv_len, scale=scale)
    g = h // kv_heads
    scale = hd ** -0.5 if scale is None else scale
    kv_len = jnp.asarray(kv_len, jnp.int32)
    kpf = k_pages.astype(jnp.float32)
    vpf = v_pages.astype(jnp.float32)

    def row_fn(args):
        q_row, tab_row, len_row = args
        qg = q_row.astype(jnp.float32).reshape(kv_heads, g, hd) * scale

        def logits(j):
            s = jnp.einsum("kgd,pkd->kgp", qg, kpf[tab_row[j]],
                           preferred_element_type=jnp.float32,
                           precision=jax.lax.Precision.HIGHEST)
            k_pos = j * page_size + jnp.arange(page_size)
            return jnp.where((k_pos < len_row)[None, None, :], s, neg_inf)

        def max_pass(m, j):
            return jnp.maximum(m, jnp.max(logits(j), axis=-1)), None

        m, _ = jax.lax.scan(
            max_pass, jnp.full((kv_heads, g), neg_inf, jnp.float32),
            jnp.arange(n_pages))

        def contrib(j):
            p = jnp.exp(logits(j) - m[..., None])
            return jnp.sum(p, axis=-1), jnp.einsum(
                "kgp,pkd->kgd", p, vpf[tab_row[j]],
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)

        # materialize every page's (denominator, numerator) term first,
        # then left-fold with pure adds in a separate scan.  Keeping the
        # multiply out of the accumulation computation stops XLA from
        # contracting `acc + p @ v` into an FMA when the page contraction
        # degenerates to a broadcast multiply (page_size == 1) — the
        # interpret-mode kernel evaluates op by op and never fuses, so an
        # oracle-side FMA would break the bitwise contract by one ulp.
        ls, accs = jax.lax.map(contrib, jnp.arange(n_pages))

        def add_pass(carry, x):
            l, acc = carry
            dl, da = x
            return (l + dl, acc + da), None

        (l, acc), _ = jax.lax.scan(
            add_pass,
            (jnp.zeros((kv_heads, g), jnp.float32),
             jnp.zeros((kv_heads, g, hd), jnp.float32)),
            (ls, accs))
        return acc / jnp.maximum(l, 1e-30)[..., None]

    out = jax.lax.map(row_fn, (q, ptab, kv_len))
    return out.reshape(b, h, hd).astype(q.dtype)


def bitline_mvm(
    g: jax.Array,     # (K, N)
    x: jax.Array,     # (M, K) signed plane in {-1, 0, +1}
    r_hat: float,
) -> jax.Array:
    """Parasitic bit-line currents; delegates to the core Thomas solver."""
    from repro.core.parasitics import bitline_currents

    return bitline_currents(g, x, r_hat)


def fused_mvm_diff(
    x_parts: jax.Array,   # (P, M, rows) integer-valued, padded to bm
    g_pos: jax.Array,     # (S, P, rows, N) padded to bn
    g_neg: jax.Array,     # (S, P, rows, N)
    adc_lo,               # (S,) per-slice calibrated range
    adc_hi,
    scale,                # scalar: gain * w_scale * x_scale
    *,
    adc_bits: int,
    cell_bits: int,
    n_bits,               # None = analog input accumulation
    bm: int,
    bn: int,
) -> jax.Array:
    """Oracle for ``fused.fused_mvm_pallas`` — the composed chain as plain
    jnp ops, walked in the kernel's exact tile order.

    Bitwise equality with the kernel rests on two things (see
    ``kernels.fused``): every dot is taken over the *identical*
    (bm, rows) x (rows, bn) operand tiles in the identical (i, j, p, s, b)
    order — same ``dot_general``, same reduction, same accumulation-add
    sequence — and every value feeding an accumulation add is produced by
    an add or an exact power-of-two multiply (the shared code-unit
    ``fused_adc_code_units`` epilogue), so FMA contraction cannot
    introduce a rounding difference between the two compilation contexts.
    The tile loops are static Python loops: tile counts on serving shapes
    are single digits.
    """
    from repro.kernels.analog_mvm import _bit_plane
    from repro.kernels.fused import (adc_lsb, fused_adc_code_units,
                                     term_weight)

    p, m, rows = x_parts.shape
    n_slices, _, _, n = g_pos.shape
    if m % bm or n % bn:
        raise ValueError(
            f"block shape ({bm}, {bn}) does not tile operand ({m}, {n})")
    scale = jnp.asarray(scale, jnp.float32).reshape(())
    lo = jnp.asarray(adc_lo, jnp.float32).reshape(n_slices)
    hi = jnp.asarray(adc_hi, jnp.float32).reshape(n_slices)
    bits = (None,) if n_bits is None else tuple(range(n_bits))
    out_scale = scale
    if n_slices == 1:
        out_scale = scale * adc_lsb(lo[0], hi[0], adc_bits)

    out_rows = []
    for i in range(m // bm):
        row_tiles = []
        for j in range(n // bn):
            tot = jnp.zeros((bm, bn), jnp.float32)
            for pi in range(p):
                x = x_parts[pi, i * bm:(i + 1) * bm, :]
                if n_bits is not None:
                    sign = jnp.sign(x)
                    mag = jnp.abs(x)
                acc = jnp.zeros((bm, bn), jnp.float32)
                for s in range(n_slices):
                    g = (g_pos[s, pi, :, j * bn:(j + 1) * bn]
                         - g_neg[s, pi, :, j * bn:(j + 1) * bn])
                    lsb = adc_lsb(lo[s], hi[s], adc_bits)
                    a_s = jnp.zeros((bm, bn), jnp.float32)
                    for b in bits:
                        plane = x if b is None else _bit_plane(mag, sign, b)
                        v = jnp.dot(plane, g,
                                    preferred_element_type=jnp.float32,
                                    precision=jax.lax.Precision.HIGHEST)
                        q = fused_adc_code_units(v, lo[s], lsb, adc_bits)
                        a_s = a_s + q * term_weight(0, 0, b)
                    if n_slices == 1:
                        acc = a_s
                    else:
                        acc = acc + (a_s * lsb) * term_weight(
                            cell_bits, s, None)
                tot = tot + acc
            row_tiles.append(tot * out_scale)
        out_rows.append(jnp.concatenate(row_tiles, axis=1))
    return jnp.concatenate(out_rows, axis=0)


def fused_mvm_parasitic(
    xt_parts: jax.Array,  # (P, rows, M) integer-valued, padded to bm
    g_pos: jax.Array,     # (S, P, rows, N) padded to bn
    g_neg: jax.Array,     # (S, P, rows, N)
    r_hat,
    adc_lo,               # (S,)
    adc_hi,
    scale,                # scalar: gain * w_scale * x_scale
    *,
    adc_bits: int,
    cell_bits: int,
    n_bits: int,
    bm: int,
    bn: int,
) -> jax.Array:
    """Oracle for ``fused.fused_mvm_parasitic_pallas``: the same Thomas
    forward sweep (``bitline._thomas_bottom_current`` — shared, so the
    recurrence cannot diverge) over the kernel's exact operand tiles,
    analog bit accumulation, per-slice ADC, shift-and-add, one dequant."""
    from repro.kernels.analog_mvm import _bit_plane
    from repro.kernels.bitline import _thomas_bottom_current, value_rows
    from repro.kernels.fused import (adc_lsb, fused_adc_code_units,
                                     term_weight)

    p, rows, m = xt_parts.shape
    n_slices, _, _, n = g_pos.shape
    if m % bm or n % bn:
        raise ValueError(
            f"block shape ({bm}, {bn}) does not tile operand ({m}, {n})")
    scale = jnp.asarray(scale, jnp.float32).reshape(())
    r = jnp.asarray(r_hat, jnp.float32).reshape(())
    lo = jnp.asarray(adc_lo, jnp.float32).reshape(n_slices)
    hi = jnp.asarray(adc_hi, jnp.float32).reshape(n_slices)
    out_scale = scale
    if n_slices == 1:
        out_scale = scale * adc_lsb(lo[0], hi[0], adc_bits)

    out_rows = []
    for i in range(m // bm):
        row_tiles = []
        for j in range(n // bn):
            tot = jnp.zeros((bm, bn), jnp.float32)
            for pi in range(p):
                x = xt_parts[pi, :, i * bm:(i + 1) * bm].T
                sign = jnp.sign(x)
                mag = jnp.abs(x)
                acc = jnp.zeros((bm, bn), jnp.float32)
                for s in range(n_slices):
                    gp = g_pos[s, pi, :, j * bn:(j + 1) * bn]
                    gm = g_neg[s, pi, :, j * bn:(j + 1) * bn]
                    accb = jnp.zeros((bm, bn), jnp.float32)
                    for b in range(n_bits):
                        plane = _bit_plane(mag, sign, b)
                        i_pos = _thomas_bottom_current(
                            *value_rows(plane, gp), r, k=rows,
                            shape=(bm, bn))
                        i_neg = _thomas_bottom_current(
                            *value_rows(plane, gm), r, k=rows,
                            shape=(bm, bn))
                        accb = accb + (i_pos - i_neg) * 2.0 ** b
                    lsb = adc_lsb(lo[s], hi[s], adc_bits)
                    a_s = fused_adc_code_units(accb, lo[s], lsb, adc_bits)
                    if n_slices == 1:
                        acc = a_s
                    else:
                        acc = acc + (a_s * lsb) * term_weight(
                            cell_bits, s, None)
                tot = tot + acc
            row_tiles.append(tot * out_scale)
        out_rows.append(jnp.concatenate(row_tiles, axis=1))
    return jnp.concatenate(out_rows, axis=0)


def flash_attention_decode(
    q: jax.Array,          # (B, H, hd)
    k: jax.Array,          # (B, S, KV, hd) dense per-slot cache
    v: jax.Array,          # (B, S, KV, hd)
    kv_len: jax.Array,     # (B,) int32 valid positions per row
    *,
    block: int,
    scale=None,
) -> jax.Array:
    """Oracle for ``fused.flash_attention_pallas``.

    A dense per-slot cache chunked into ``block``-sized pieces *is* a
    paged pool whose block table is ``row * n_blocks + j`` — the chunk at
    (b, j) and the page at table entry (b, j) are the same (block, KV,
    hd) array, and the kernels walk them with identical contractions,
    masks, and phase order.  Delegating to ``paged_attention_decode``
    therefore reuses its proven bitwise form verbatim.
    """
    b, seq, kv_heads, hd = k.shape
    if seq % block:
        raise ValueError(f"cache length {seq} not divisible by "
                         f"block {block}")
    n_blocks = seq // block
    kp = k.reshape(b * n_blocks, block, kv_heads, hd)
    vp = v.reshape(b * n_blocks, block, kv_heads, hd)
    tab = (jnp.arange(b, dtype=jnp.int32)[:, None] * n_blocks
           + jnp.arange(n_blocks, dtype=jnp.int32)[None, :])
    return paged_attention_decode(q, kp, vp, tab, kv_len, scale=scale)


def analog_mvm_parasitic_diff(
    x_parts: jax.Array,   # (M, P, rows) integer-valued, signed
    g_pos: jax.Array,     # (P, rows, N)
    g_neg: jax.Array,     # (P, rows, N)
    *,
    r_hat: float,
    n_bits: int,
    adc_lo,
    adc_hi,
    adc_bits: int,
    gain: float,
) -> jax.Array:
    """Design-A path under parasitic bit-line resistance.

    Per input bit plane: both differential line stacks go through the
    tridiagonal bit-line solve; bits are accumulated in analog (the
    switched-capacitor stage after the bit line), then one ADC per
    partition and digital partition accumulation.  Output (M, N), code
    units — the oracle for ``ops.analog_mvm_parasitic``.
    """
    from repro.core.parasitics import bitline_currents

    sign = jnp.sign(x_parts)
    mag = jnp.abs(x_parts).astype(jnp.int32)
    solve = jax.vmap(bitline_currents, in_axes=(0, 1, None))  # over P
    acc = None
    for b in range(n_bits):
        plane = (((mag >> b) & 1).astype(x_parts.dtype)) * sign
        v = solve(g_pos, plane, r_hat) - solve(g_neg, plane, r_hat)
        contrib = v * (2.0 ** b)                              # (P, M, N)
        acc = contrib if acc is None else acc + contrib
    v_hat = adc(acc, adc_lo, adc_hi, adc_bits)
    return jnp.sum(v_hat, axis=0) * gain
