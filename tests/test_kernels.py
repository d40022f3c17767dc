"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref.py oracle.

ADC-bearing kernels are quantizers: a float dot product landing within a
few ULPs of an ADC decision boundary may legally flip by one LSB between
two correct implementations (different fp32 accumulation orders).  The
tolerance policy is therefore: (a) the vast majority of outputs match to
float precision, and (b) every output matches within the worst-case
single-boundary-flip impact (one ADC LSB times the largest shift-and-add
weight times the gain).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.core.parasitics import bitline_currents


def quantizer_allclose(y_k, y_r, *, flip_atol, tight_rtol=1e-4, frac=0.98):
    y_k, y_r = np.asarray(y_k), np.asarray(y_r)
    np.testing.assert_allclose(y_k, y_r, atol=flip_atol, rtol=0)
    tight = np.isclose(y_k, y_r, rtol=tight_rtol, atol=flip_atol * 1e-3)
    assert tight.mean() >= frac, f"only {tight.mean():.2%} bit-exact"


MVM_SHAPES = [
    (8, 1, 64, 16),
    (32, 2, 96, 40),
    (128, 1, 1152, 256),
    (64, 3, 200, 24),
    (16, 2, 8, 8),
]

# off-tile-boundary shapes: rows/N far from multiples of 128 (ops.py pads
# tiles), single-row partitions, a tall-skinny output, and small-M decode
# rows (M = 1 and 2 live lanes, far under one sublane tile)
MVM_EDGE_SHAPES = [
    (4, 1, 1, 8),
    (8, 2, 33, 7),
    (16, 1, 129, 130),
    (8, 4, 72, 3),
    (1, 1, 64, 16),
    (2, 3, 40, 24),
]


@pytest.mark.parametrize("m,p,rows,n", MVM_SHAPES)
@pytest.mark.parametrize("adc_bits", [6, 8])
def test_analog_mvm_diff_matches_ref(m, p, rows, n, adc_bits):
    ks = jax.random.split(jax.random.PRNGKey(m * 7 + p), 3)
    x = jnp.round(jax.random.normal(ks[0], (m, p, rows)) * 40).astype(jnp.float32)
    gp = jax.random.uniform(ks[1], (p, rows, n)) * 0.1
    gm = jax.random.uniform(ks[2], (p, rows, n)) * 0.1
    lo, hi = jnp.float32(-50.0), jnp.float32(50.0)
    gain = 127.0
    args = dict(adc_lo=lo, adc_hi=hi, adc_bits=adc_bits, gain=gain)
    y_k = ops.analog_mvm(x, gp, gm, **args)
    y_r = ref.analog_mvm_diff(x, gp, gm, **args)
    lsb = 100.0 / (2 ** adc_bits - 1)
    quantizer_allclose(y_k, y_r, flip_atol=lsb * gain * p)


@pytest.mark.parametrize("m,p,rows,n", MVM_EDGE_SHAPES)
def test_analog_mvm_diff_edge_shapes(m, p, rows, n):
    ks = jax.random.split(jax.random.PRNGKey(m * 13 + rows), 3)
    x = jnp.round(jax.random.normal(ks[0], (m, p, rows)) * 40).astype(jnp.float32)
    gp = jax.random.uniform(ks[1], (p, rows, n)) * 0.1
    gm = jax.random.uniform(ks[2], (p, rows, n)) * 0.1
    lo, hi = jnp.float32(-50.0), jnp.float32(50.0)
    gain = 127.0
    args = dict(adc_lo=lo, adc_hi=hi, adc_bits=8, gain=gain)
    y_k = ops.analog_mvm(x, gp, gm, **args)
    y_r = ref.analog_mvm_diff(x, gp, gm, **args)
    lsb = 100.0 / 255.0
    quantizer_allclose(y_k, y_r, flip_atol=lsb * gain * p)


@pytest.mark.parametrize("m,p,rows,n", MVM_SHAPES[:4] + MVM_EDGE_SHAPES)
@pytest.mark.parametrize("n_bits", [4, 7])
def test_analog_mvm_bitserial_matches_ref(m, p, rows, n, n_bits):
    ks = jax.random.split(jax.random.PRNGKey(m + p + n_bits), 3)
    qmax = 2 ** n_bits - 1
    x = jnp.round(jax.random.normal(ks[0], (m, p, rows)) * qmax / 3)
    x = jnp.clip(x, -qmax, qmax).astype(jnp.float32)
    gp = jax.random.uniform(ks[1], (p, rows, n)) * 0.1
    gm = jax.random.uniform(ks[2], (p, rows, n)) * 0.1
    lo, hi = jnp.float32(-20.0), jnp.float32(20.0)
    gain = 127.0
    args = dict(n_bits=n_bits, adc_lo=lo, adc_hi=hi, adc_bits=8, gain=gain)
    y_k = ops.analog_mvm_bitserial(x, gp, gm, **args)
    y_r = ref.analog_mvm_bitserial(x, gp, gm, **args)
    lsb = 40.0 / 255.0
    # worst case: one flip at every bit of one partition chain
    quantizer_allclose(y_k, y_r, flip_atol=lsb * gain * p * 2 ** n_bits)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_analog_mvm_dtypes(dtype):
    m, p, rows, n = 16, 1, 64, 24
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jnp.round(jax.random.normal(ks[0], (m, p, rows)) * 30).astype(dtype)
    gp = (jax.random.uniform(ks[1], (p, rows, n)) * 0.1).astype(dtype)
    gm = (jax.random.uniform(ks[2], (p, rows, n)) * 0.1).astype(dtype)
    lo, hi = jnp.float32(-30.0), jnp.float32(30.0)
    y = ops.analog_mvm(x, gp, gm, adc_lo=lo, adc_hi=hi, adc_bits=8, gain=1.0)
    assert y.shape == (m, n)
    assert bool(jnp.all(jnp.isfinite(y)))


@pytest.mark.parametrize("m,k,n,r", [
    (8, 17, 16, 1e-3),
    (32, 96, 24, 1e-4),
    (16, 200, 8, 1e-5),
    (128, 64, 128, 3e-4),
    # solve-shape edges: minimal chain (k=2), single output column,
    # full-depth 1152-row line, and the heaviest sag the sweeps use
    (4, 2, 3, 1e-3),
    (8, 33, 1, 5e-4),
    (4, 1152, 4, 1e-4),
    (16, 72, 8, 5e-3),
    # padding edges: M and N off tile multiples, K not a multiple of 8
    (3, 13, 130, 1e-4),
    (130, 7, 5, 1e-3),
    (9, 129, 127, 1e-4),
])
def test_bitline_kernel_matches_solver(m, k, n, r):
    kx, kg = jax.random.split(jax.random.PRNGKey(k), 2)
    x = jnp.sign(jax.random.normal(kx, (m, k))) * (
        jax.random.uniform(jax.random.PRNGKey(2), (m, k)) > 0.4
    )
    g = jax.random.uniform(kg, (k, n))
    y_k = ops.bitline_mvm(g, x, r)
    y_r = bitline_currents(g, x.astype(jnp.float32), r)
    np.testing.assert_allclose(y_k, y_r, rtol=1e-4, atol=1e-5)


def test_bitline_kernel_zero_r_is_ideal():
    kx, kg = jax.random.split(jax.random.PRNGKey(5), 2)
    x = jnp.sign(jax.random.normal(kx, (8, 32)))
    g = jax.random.uniform(kg, (32, 16))
    # every *concrete* scalar form of zero must short-circuit to the ideal
    # matmul — running the Thomas sweep at r=0 divides into silent NaNs
    for zero in (0.0, 0, np.float32(0.0), jnp.float32(0.0),
                 jnp.zeros(())):
        np.testing.assert_allclose(ops.bitline_mvm(g, x, zero), x @ g,
                                   rtol=1e-6)
        np.testing.assert_allclose(bitline_currents(g, x, zero), x @ g,
                                   rtol=1e-6)
    from repro.core.analog import AnalogSpec

    assert not AnalogSpec(r_hat=np.float32(0.0)).parasitics_on
    assert AnalogSpec(r_hat=np.float32(1e-4)).parasitics_on


@pytest.mark.parametrize("m,k,n,r", [
    (4, 23, 6, 2e-3),
    # padded/edge shapes through the dense jnp.linalg.solve oracle too:
    # M/N off tile multiples, K not a multiple of 8
    (3, 13, 9, 1e-3),
    (5, 130, 2, 1e-4),
])
def test_bitline_vs_dense_oracle(m, k, n, r):
    """Thomas-in-kernel vs dense jnp.linalg.solve, element by element."""
    from repro.core.parasitics import bitline_voltages_dense

    kx, kg = jax.random.split(jax.random.PRNGKey(7), 2)
    x = jnp.sign(jax.random.normal(kx, (m, k))) * (
        jax.random.uniform(jax.random.PRNGKey(8), (m, k)) > 0.3
    )
    g = jax.random.uniform(kg, (k, n))
    y_k = ops.bitline_mvm(g, x, r)
    for mm in range(m):
        for nn in range(n):
            v = bitline_voltages_dense(g[:, nn], x[mm], r)
            np.testing.assert_allclose(y_k[mm, nn], v[-1] / r, rtol=1e-4)


def test_bitline_traced_r_hat_one_compilation():
    """``r_hat`` is a kernel *input*: one jitted function serves every
    parasitic level (the sweep engine's Fig. 19 batching contract)."""
    m, k, n = 8, 33, 7
    kx, kg = jax.random.split(jax.random.PRNGKey(11), 2)
    x = jnp.sign(jax.random.normal(kx, (m, k)))
    g = jax.random.uniform(kg, (k, n))
    traces = []

    @jax.jit
    def f(r):
        traces.append(1)
        return ops.bitline_mvm(g, x, r)

    for r in (1e-5, 1e-4, 1e-3):
        np.testing.assert_allclose(
            f(jnp.float32(r)), bitline_currents(g, x, r),
            rtol=1e-4, atol=1e-6)
    assert len(traces) == 1, "r_hat retraced the kernel"


def test_bitline_vmap_over_slices_partitions():
    """The core parasitic branch vmaps the kernel over (slice, partition)
    stacks; pin the batching rule."""
    p_, m, k, n = 3, 6, 24, 5
    gs = jax.random.uniform(jax.random.PRNGKey(3), (p_, k, n))
    xs = jnp.sign(jax.random.normal(jax.random.PRNGKey(4), (p_, m, k)))
    out = jax.vmap(lambda g, x: ops.bitline_mvm(g, x, 1e-4))(gs, xs)
    want = jnp.stack([bitline_currents(gs[i], xs[i], 1e-4)
                      for i in range(p_)])
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-6)


PARASITIC_SHAPES = [
    (8, 1, 16, 8),
    (16, 2, 33, 7),      # K not a multiple of 8, tiny N
    (8, 2, 8, 130),      # N just over one lane tile
    (130, 1, 72, 24),    # M off tile multiple
]


@pytest.mark.parametrize("m,p,rows,n", PARASITIC_SHAPES)
def test_analog_mvm_parasitic_matches_ref(m, p, rows, n):
    ks = jax.random.split(jax.random.PRNGKey(m * 3 + rows), 3)
    x = jnp.clip(jnp.round(jax.random.normal(ks[0], (m, p, rows)) * 40),
                 -127, 127).astype(jnp.float32)
    gp = jax.random.uniform(ks[1], (p, rows, n)) * 0.1
    gm = jax.random.uniform(ks[2], (p, rows, n)) * 0.1
    lo, hi = jnp.float32(-50.0), jnp.float32(50.0)
    gain = 127.0
    args = dict(r_hat=1e-3, n_bits=7, adc_lo=lo, adc_hi=hi, adc_bits=8,
                gain=gain)
    y_k = ops.analog_mvm_parasitic(x, gp, gm, **args)
    y_r = ref.analog_mvm_parasitic_diff(x, gp, gm, **args)
    lsb = 100.0 / 255.0
    quantizer_allclose(y_k, y_r, flip_atol=lsb * gain * p)


def test_analog_mvm_parasitic_traced_r_hat():
    """The fused Design-A parasitic kernel also takes r_hat as a traced
    scalar — one compiled program across the Fig. 19 axis."""
    m, p, rows, n = 8, 1, 24, 9
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jnp.clip(jnp.round(jax.random.normal(ks[0], (m, p, rows)) * 40),
                 -127, 127).astype(jnp.float32)
    gp = jax.random.uniform(ks[1], (p, rows, n)) * 0.1
    gm = jax.random.uniform(ks[2], (p, rows, n)) * 0.1
    kw = dict(n_bits=7, adc_lo=jnp.float32(-50.0), adc_hi=jnp.float32(50.0),
              adc_bits=8, gain=127.0)
    f = jax.jit(lambda r: ops.analog_mvm_parasitic(x, gp, gm, r_hat=r, **kw))
    for r in (1e-5, 1e-3):
        np.testing.assert_allclose(
            f(jnp.float32(r)),
            ref.analog_mvm_parasitic_diff(x, gp, gm, r_hat=r, **kw),
            rtol=1e-3, atol=100.0 / 255.0 * 127.0)


def test_pick_tile_lane_dim_is_full_tile():
    """Mosaic requires 128-lane tiles: the N (lane) tile must never shrink
    to a sublane-rounded size, however small N is (interpret mode hides
    the violation; TPU compilation does not)."""
    for n in (1, 3, 7, 64, 127):
        assert ops._pick_tile(n, 128, lane=True) == 128, n
    assert ops._pick_tile(200, 128, lane=True) == 128
    # sublane tiles snap up to the next power of two (Mosaic-legal
    # second-minor sizes: 8, 16, 32, 64, 128), capped at the block max
    assert ops._pick_tile(3, 128) == 8
    assert ops._pick_tile(33, 128) == 64
    assert ops._pick_tile(64, 128) == 64
    assert ops._pick_tile(65, 128) == 128
    assert ops._pick_tile(200, 128) == 128


@pytest.mark.parametrize("n", [1, 3, 5])
def test_analog_mvm_small_lane_shapes(n):
    """Tiny-N outputs exercise the lane-padded (bn=128) path in all three
    wrapper entry points."""
    m, p, rows = 8, 2, 40
    ks = jax.random.split(jax.random.PRNGKey(n), 3)
    x = jnp.round(jax.random.normal(ks[0], (m, p, rows)) * 40)
    gp = jax.random.uniform(ks[1], (p, rows, n)) * 0.1
    gm = jax.random.uniform(ks[2], (p, rows, n)) * 0.1
    lo, hi = jnp.float32(-50.0), jnp.float32(50.0)
    args = dict(adc_lo=lo, adc_hi=hi, adc_bits=8, gain=127.0)
    lsb = 100.0 / 255.0

    # frac: with only m*n <= 40 outputs, one boundary-straddling sample row
    # is >10% of the output — the flip_atol bound is the real contract here
    y = ops.analog_mvm(x, gp, gm, **args)
    quantizer_allclose(y, ref.analog_mvm_diff(x, gp, gm, **args),
                       flip_atol=lsb * 127.0 * p, frac=0.8)
    y = ops.analog_mvm_bitserial(x, gp, gm, n_bits=7, **args)
    quantizer_allclose(
        y, ref.analog_mvm_bitserial(x, gp, gm, n_bits=7, **args),
        flip_atol=lsb * 127.0 * p * 2 ** 7, frac=0.8)
    xs = jnp.sign(jax.random.normal(ks[0], (m, rows)))
    g = jax.random.uniform(ks[1], (rows, n))
    np.testing.assert_allclose(
        ops.bitline_mvm(g, xs, 1e-4), bitline_currents(g, xs, 1e-4),
        rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# paged-attention decode: in-kernel block-table gather vs jnp gather oracle
# ---------------------------------------------------------------------------

# (B, H, KV, hd, page_size, NP) decode shapes: multi-page rows, ragged
# last pages, GQA grouping, single-page tables, page_size=1 degenerate
PAGED_SHAPES = [
    (1, 2, 1, 8, 4, 2),
    (3, 4, 2, 8, 4, 4),
    (2, 4, 4, 16, 8, 2),
    (4, 8, 2, 32, 8, 4),
    (2, 2, 2, 8, 4, 1),      # single page
    (3, 2, 1, 8, 1, 6),      # page_size = 1
    (2, 6, 3, 8, 2, 5),
]


def _paged_case(b, h, kv, hd, ps, np_pages, seed=0):
    """Random pool + per-row block tables with ragged fills (last page
    partially valid) and sink-padded table tails."""
    rng = np.random.default_rng(seed)
    num_pages = 1 + b * np_pages
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(k1, (b, h, hd), jnp.float32)
    k_pages = jax.random.normal(k2, (num_pages, ps, kv, hd), jnp.float32)
    v_pages = jax.random.normal(k3, (num_pages, ps, kv, hd), jnp.float32)
    perm = rng.permutation(np.arange(1, num_pages))
    ptab = np.zeros((b, np_pages), np.int32)
    kv_len = np.zeros((b,), np.int32)
    for i in range(b):
        n = int(rng.integers(1, np_pages * ps + 1))   # ragged fill
        used = -(-n // ps)
        ptab[i, :used] = perm[i * np_pages:i * np_pages + used]
        kv_len[i] = n                                 # tail stays sink (0)
    return q, k_pages, v_pages, jnp.asarray(ptab), jnp.asarray(kv_len)


#: paged kernel-vs-oracle bound, in float32 ULPs of max|v|.  Each
#: output is a softmax-weighted mean of v rows (weights sum to 1), and
#: both sides take the same two-phase steps, so any difference is how
#: XLA-CPU contracts or vectorizes the score dot and ``exp`` in each
#: fusion context: measured at most 1 ULP of max|v| over 12 seeds of
#: every shape below (jax 0.9).  A leaked masked position or a wrong
#: running max moves an output by ~max|v| / n_positions, ~1e6 ULPs.
PAGED_ULPS_OF_VMAX = 4


@pytest.mark.parametrize("b,h,kv,hd,ps,np_pages", PAGED_SHAPES)
def test_paged_attention_bit_exact_vs_oracle(b, h, kv, hd, ps, np_pages):
    """The two-phase kernel matches the two-phase jnp oracle to a few
    ULPs of the value scale (see kernels/paged.py on why one-pass online
    softmax cannot: its rescale reassociates per compilation context).
    Most shapes agree bit for bit; the bits themselves are not
    structural — XLA-CPU's contraction choices inside the oracle's
    fusions move them by an ULP for some inputs — so the pin is the
    stated bound :data:`PAGED_ULPS_OF_VMAX`."""
    args = _paged_case(b, h, kv, hd, ps, np_pages)
    out = np.asarray(ops.paged_attention(*args))
    want = np.asarray(ref.paged_attention_decode(*args))
    assert out.dtype == want.dtype and out.shape == (b, h, hd)
    ulp = np.spacing(np.abs(np.asarray(args[2])).max())
    assert float(np.abs(out - want).max()) <= PAGED_ULPS_OF_VMAX * ulp


def test_paged_attention_invariant_to_table_tail_padding():
    """Positions >= kv_len contribute exact zeros, so the result cannot
    depend on what page ids pad the tail of the block table."""
    q, kp, vp, ptab, kv_len = _paged_case(3, 4, 2, 8, 4, 4, seed=1)
    base = np.asarray(ops.paged_attention(q, kp, vp, ptab, kv_len))
    tab = np.asarray(ptab).copy()
    for i, n in enumerate(np.asarray(kv_len)):
        used = -(-int(n) // 4)
        tab[i, used:] = (i + 5) % tab.shape[1] + 1     # garbage, non-sink
    np.testing.assert_array_equal(
        base, np.asarray(ops.paged_attention(q, kp, vp,
                                             jnp.asarray(tab), kv_len)))


def test_paged_attention_matches_streaming_gather():
    """Numerical cross-check against the serving gather path: dense
    streaming attention over pool[ptab] (the runtime's bit-exact
    backend) agrees with the kernel to float tolerance."""
    from repro.models.layers import streaming_attention

    b, h, kv, hd, ps, npg = 3, 4, 2, 16, 4, 4
    q, kp, vp, ptab, kv_len = _paged_case(b, h, kv, hd, ps, npg, seed=2)
    out = np.asarray(ops.paged_attention(q, kp, vp, ptab, kv_len))
    gk = kp[ptab].reshape(b, npg * ps, kv, hd)
    gv = vp[ptab].reshape(b, npg * ps, kv, hd)
    want = streaming_attention(q[:, None], gk, gv,
                               q_offset=kv_len - 1, causal=True,
                               kv_len=kv_len)[:, 0]
    np.testing.assert_allclose(out, np.asarray(want), rtol=2e-5, atol=2e-5)


def test_paged_attention_rejects_unpadded_pallas_page_size():
    """Non-interpret mode requires sublane-aligned pages (positions
    cannot be padded; a padded page would shift k_pos)."""
    q, kp, vp, ptab, kv_len = _paged_case(2, 2, 1, 8, 4, 2)
    with pytest.raises(ValueError, match="page_size"):
        ops.paged_attention(q, kp, vp, ptab, kv_len, interpret=False)


# ---------------------------------------------------------------------------
# fused decode chain: single-launch matmul + ADC + dequant + slice/bit
# shift-and-add vs the jnp oracle (the composed form of the same chain)
# ---------------------------------------------------------------------------

def _fused_case(m, p, s, rows, n, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jnp.round(jax.random.normal(ks[0], (m, p, rows)) * 40)
    gp = jax.random.uniform(ks[1], (s, p, rows, n)) * 0.1
    gm = jax.random.uniform(ks[2], (s, p, rows, n)) * 0.1
    lo = jnp.linspace(-60.0, -40.0, s).astype(jnp.float32)
    hi = jnp.linspace(40.0, 60.0, s).astype(jnp.float32)
    return x, gp, gm, lo, hi


def _max_ulp(y_k, y_r):
    y_k, y_r = np.asarray(y_k), np.asarray(y_r)
    d = np.abs(y_k - y_r)
    mag = np.maximum(np.abs(y_k), np.abs(y_r))
    return float(np.max(np.where(d > 0,
                                 d / np.spacing(mag.astype(np.float32)),
                                 0.0)))


def _assert_close_codes(y_k, y_r, scale, *, ulp=2.0, codes=0.25):
    """Elementwise kernel-vs-oracle bound: within ``ulp`` float32 ulps,
    or — for near-zero outputs, where a sub-lsb absolute drift reads as
    millions of ulps — within ``codes`` dequant grid units (the output
    is ``scale`` times an integer-weighted sum of ADC codes, so its
    grid spacing is ``scale``; a drift under half a grid step can never
    flip which quantized value either side lands on, and slice/plane
    weights up to 2^12 amplify fp32 reassociation into that window)."""
    y_k, y_r = np.asarray(y_k), np.asarray(y_r)
    d = np.abs(y_k - y_r)
    mag = np.maximum(np.abs(y_k), np.abs(y_r))
    ok = ((d <= ulp * np.spacing(mag.astype(np.float32)))
          | (d <= codes * float(scale)))
    assert bool(ok.all()), (
        f"max ulp={_max_ulp(y_k, y_r):.1f}, "
        f"max code diff={float(d.max()) / float(scale):.2e}")


@pytest.mark.parametrize("m,p,rows,n", [
    (1, 1, 64, 16),     # single decode lane
    (2, 1, 33, 7),      # small-M, off-tile rows/N
    (8, 1, 256, 128),   # a full decode gang at the lane tile
    (8, 2, 96, 40),     # multi-partition
    (4, 3, 72, 24),
])
@pytest.mark.parametrize("n_bits", [None, 7])
def test_fused_mvm_single_slice_bitwise(m, p, rows, n, n_bits):
    """S == 1 — the decode MVMs the smoke LM actually serves: the fused
    kernel is BITWISE equal to its oracle under jit (the serving path —
    XLA contracts both sides' dot/epilogue chains identically).  Eager
    dispatch compiles each op separately and may reassociate the bit
    fold differently, so eagerly we pin agreement to a sliver of an ADC
    code unit instead — far below the half-code threshold where any
    quantized output could flip."""
    x, gp, gm, lo, hi = _fused_case(m, p, 1, rows, n, seed=m * 11 + rows)
    kw = dict(adc_lo=lo, adc_hi=hi, adc_bits=8, cell_bits=7,
              n_bits=n_bits, scale=jnp.float32(3e-4))
    y_k = ops.fused_mvm(x, gp, gm, backend="kernel", **kw)
    y_r = ops.fused_mvm(x, gp, gm, backend="oracle", **kw)
    codes = np.abs(np.asarray(y_k) - np.asarray(y_r)) / kw["scale"]
    assert float(codes.max()) <= 1e-2
    yj_k = jax.jit(lambda *a: ops.fused_mvm(*a, backend="kernel", **kw))(
        x, gp, gm)
    yj_r = jax.jit(lambda *a: ops.fused_mvm(*a, backend="oracle", **kw))(
        x, gp, gm)
    np.testing.assert_array_equal(np.asarray(yj_k), np.asarray(yj_r))


@pytest.mark.parametrize("m,p,s,rows,n,n_bits", [
    (8, 1, 2, 40, 24, None),
    (4, 2, 4, 33, 7, 7),
    (8, 1, 3, 96, 130, None),   # N over one lane tile
    (2, 1, 4, 64, 16, 7),       # small-M sliced decode
])
def test_fused_mvm_multi_slice_ulp(m, p, s, rows, n, n_bits):
    """S >= 2 multi-tile slice accumulation: the per-slice lsb factor
    rides outside the bit fold behind an exact power-of-two slice
    weight, so kernel-vs-oracle drift is fp32 reassociation of the
    final sum — a couple of ULPs on full-size outputs, a sub-lsb
    absolute sliver where slices cancel to near zero, never an ADC
    code flip."""
    x, gp, gm, lo, hi = _fused_case(m, p, s, rows, n, seed=s * 17 + n)
    kw = dict(adc_lo=lo, adc_hi=hi, adc_bits=8, cell_bits=2,
              n_bits=n_bits, scale=jnp.float32(3e-4))
    y_k = ops.fused_mvm(x, gp, gm, backend="kernel", **kw)
    y_r = ops.fused_mvm(x, gp, gm, backend="oracle", **kw)
    _assert_close_codes(y_k, y_r, kw["scale"])


@pytest.mark.parametrize("m,p,s,rows,n", [
    (4, 1, 1, 24, 9),
    (8, 2, 2, 33, 7),
    (2, 1, 1, 64, 16),          # small-M decode lane
])
def test_fused_mvm_parasitic_matches_oracle(m, p, s, rows, n):
    """The fused parasitic variant (per-bit Thomas solve inside the same
    launch) against its oracle, with r_hat traced."""
    x, gp, gm, lo, hi = _fused_case(m, p, s, rows, n, seed=rows + n)
    x = jnp.clip(x, -127, 127)
    kw = dict(adc_lo=lo, adc_hi=hi, adc_bits=8, cell_bits=2 if s > 1 else 7,
              n_bits=7, scale=jnp.float32(3e-4))
    f_k = jax.jit(lambda r: ops.fused_mvm_parasitic(
        x, gp, gm, r_hat=r, backend="kernel", **kw))
    f_r = jax.jit(lambda r: ops.fused_mvm_parasitic(
        x, gp, gm, r_hat=r, backend="oracle", **kw))
    traces = []
    for r in (1e-5, 1e-3):
        _assert_close_codes(f_k(jnp.float32(r)), f_r(jnp.float32(r)),
                            kw["scale"])
        traces.append(r)
    assert len(traces) == 2


# ---------------------------------------------------------------------------
# layer-stacked conductances: the kernel picks its layer from a scalar-
# prefetched index in place of a per-layer slice written out before it
# ---------------------------------------------------------------------------

def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of ``jaxpr``, inner jaxprs included."""
    out = []
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            out.append(e)
        for v in e.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out.extend(_pallas_calls(inner))
    return out


def _stacked_tags(f, *args):
    return [dict(e.params["metadata"])["stacked"]
            for e in _pallas_calls(jax.make_jaxpr(f)(*args).jaxpr)]


def _stacked_case(s, rows, n, *, layers=3, m=8, p=2, seed=0,
                  dtype=jnp.float32):
    x, _, _, lo, hi = _fused_case(m, p, s, rows, n, seed=seed)
    ks = jax.random.split(jax.random.PRNGKey(seed + 1), 2)
    gp = (jax.random.uniform(ks[0], (layers, s, p, rows, n)) * 0.1
          ).astype(dtype)
    gm = (jax.random.uniform(ks[1], (layers, s, p, rows, n)) * 0.1
          ).astype(dtype)
    return x, gp, gm, lo, hi


@pytest.mark.parametrize("s", [1, 4])
@pytest.mark.parametrize("n_bits", [None, 7], ids=["analog", "digital"])
def test_fused_mvm_stacked_picks_its_layer_bitwise(s, n_bits):
    """Handed a ``(L, S, P, rows, N)`` stack and a traced layer index, the
    kernel gives, bit for bit, what it gives on that layer's slice: the
    same blocks, grid and arithmetic, addressed through the index.  One
    compilation serves every index, and the call says it read the stack
    (``"stacked": true``) where the sliced one says it did not."""
    x, gp, gm, lo, hi = _stacked_case(s, 64, 128, seed=s * 7)
    kw = dict(adc_lo=lo, adc_hi=hi, adc_bits=8, cell_bits=2 if s > 1 else 7,
              n_bits=n_bits, scale=jnp.float32(3e-4))
    traces = []

    def stacked(x, gp, gm, layer):
        traces.append(layer)
        return ops.fused_mvm(x, gp, gm, layer=layer, **kw)

    f_stacked = jax.jit(stacked)
    f_sliced = jax.jit(lambda x, gp, gm: ops.fused_mvm(x, gp, gm, **kw))
    for layer in range(gp.shape[0]):
        np.testing.assert_array_equal(
            np.asarray(f_stacked(x, gp, gm, jnp.int32(layer))),
            np.asarray(f_sliced(x, gp[layer], gm[layer])))
    assert len(traces) == 1
    assert _stacked_tags(stacked, x, gp, gm, jnp.int32(0)) == [True]
    assert _stacked_tags(lambda *a: ops.fused_mvm(*a, **kw),
                         x, gp[0], gm[0]) == [False]


def test_fused_mvm_parasitic_stacked_picks_its_layer_bitwise():
    """The parasitic kernel shares the call's plumbing, so it reads a
    stack by its index too, with ``r_hat`` traced beside it."""
    x, gp, gm, lo, hi = _stacked_case(2, 24, 128, p=1, m=4, seed=5)
    x = jnp.clip(x, -127, 127)
    kw = dict(adc_lo=lo, adc_hi=hi, adc_bits=8, cell_bits=2, n_bits=7,
              scale=jnp.float32(3e-4))
    f_stacked = jax.jit(lambda r, layer: ops.fused_mvm_parasitic(
        x, gp, gm, r_hat=r, layer=layer, **kw))
    f_sliced = jax.jit(lambda r, g_p, g_m: ops.fused_mvm_parasitic(
        x, g_p, g_m, r_hat=r, **kw))
    r = jnp.float32(1e-4)
    for layer in range(gp.shape[0]):
        np.testing.assert_array_equal(
            np.asarray(f_stacked(r, jnp.int32(layer))),
            np.asarray(f_sliced(r, gp[layer], gm[layer])))
    assert _stacked_tags(f_stacked, r, jnp.int32(1)) == [True]


@pytest.mark.parametrize("rows,dtype", [(854, jnp.float32),
                                        (64, jnp.bfloat16)],
                         ids=["rows-854", "bfloat16"])
def test_fused_mvm_stack_it_cannot_read_as_stored_is_sliced(rows, dtype):
    """A stack the kernel cannot read as stored (854 rows, which the TPU
    tiles ``(1, 128)``; bfloat16, which it would convert) is sliced to
    the layer before a per-layer call, ``"stacked": false``, with the
    same result; the oracle always slices."""
    x, gp, gm, lo, hi = _stacked_case(1, rows, 128, p=1, seed=rows,
                                      dtype=dtype)
    assert not ops.fused_reads_stacked(gp)
    kw = dict(adc_lo=lo, adc_hi=hi, adc_bits=8, cell_bits=7, n_bits=None,
              scale=jnp.float32(3e-4))
    layer = jnp.int32(2)
    stacked = jax.jit(lambda l: ops.fused_mvm(x, gp, gm, layer=l, **kw))
    sliced = jax.jit(lambda g_p, g_m, backend: ops.fused_mvm(
        x, g_p, g_m, backend=backend, **kw), static_argnums=2)
    np.testing.assert_array_equal(np.asarray(stacked(layer)),
                                  np.asarray(sliced(gp[2], gm[2], "kernel")))
    assert _stacked_tags(stacked, layer) == [False]
    oracle = jax.jit(lambda l: ops.fused_mvm(x, gp, gm, layer=l,
                                             backend="oracle", **kw))
    np.testing.assert_array_equal(np.asarray(oracle(layer)),
                                  np.asarray(sliced(gp[2], gm[2], "oracle")))


def test_fused_mvm_stacked_under_vmap_batches_the_per_layer_call():
    """Under ``vmap`` over trials (each its own stack and dequant scale,
    the layer shared), the stacked call becomes the per-layer call on
    the layer's slice, batched by Pallas in its grid, not a loop over
    trials that would slice every trial's whole stack; the bits are the
    per-trial calls'."""
    x, gp, gm, lo, hi = _stacked_case(1, 64, 128, seed=9)
    gp2, gm2 = jnp.stack([gp, gp * 0.5]), jnp.stack([gm, gm * 0.5])
    scales = jnp.asarray([3e-4, 5e-4], jnp.float32)
    kw = dict(adc_lo=lo, adc_hi=hi, adc_bits=8, cell_bits=7, n_bits=None)
    layer = jnp.int32(1)
    f = jax.jit(jax.vmap(lambda g_p, g_m, sc: ops.fused_mvm(
        x, g_p, g_m, scale=sc, layer=layer, **kw)))
    y = f(gp2, gm2, scales)
    sliced = jax.jit(lambda g_p, g_m, sc: ops.fused_mvm(x, g_p, g_m, scale=sc,
                                                        **kw))
    for t in range(2):
        np.testing.assert_array_equal(
            np.asarray(y[t]), np.asarray(sliced(gp2[t, 1], gm2[t, 1],
                                                scales[t])))
    jaxpr = jax.make_jaxpr(f)(gp2, gm2, scales).jaxpr
    calls = _pallas_calls(jaxpr)
    assert [dict(e.params["metadata"])["stacked"] for e in calls] == [False]
    assert "while" not in str(jaxpr)


# ---------------------------------------------------------------------------
# flash-decode attention over the dense per-slot KV cache
# ---------------------------------------------------------------------------

def _flash_case(b, s, kv, g, hd, seed=0, dtype=jnp.float32, fills=None):
    h = kv * g
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, h, hd), jnp.float32)
    ck = jax.random.normal(ks[1], (b, s, kv, hd), jnp.float32).astype(dtype)
    cv = jax.random.normal(ks[2], (b, s, kv, hd), jnp.float32).astype(dtype)
    if fills is None:
        fills = np.random.default_rng(seed).integers(1, s + 1, size=b)
    return q, ck, cv, jnp.asarray(fills, jnp.int32)


@pytest.mark.parametrize("b,s,kv,g,hd,block,dtype,fills", [
    pytest.param(1, 8, 2, 1, 8, 8, jnp.float32, None,
                 id="1-8-2-1-8"),      # single row, single group
    pytest.param(2, 16, 2, 2, 8, 8, jnp.float32, None, id="2-16-2-2-8"),
    pytest.param(3, 40, 2, 1, 32, 8, jnp.float32, None,
                 id="3-40-2-1-32"),    # cache off the block multiple
    pytest.param(4, 33, 4, 2, 16, 8, jnp.float32, None, id="4-33-4-2-16"),
    pytest.param(2, 9, 1, 4, 8, 8, jnp.float32, None,
                 id="2-9-1-4-8"),      # GQA onto one KV head
    # the cache as served: bfloat16, upcast inside the kernel
    pytest.param(3, 40, 2, 1, 32, 8, jnp.bfloat16, None, id="bf16"),
    pytest.param(3, 48, 2, 2, 16, 16, jnp.bfloat16, None, id="bf16-gqa"),
    # fills at the chunk edges: 1, one chunk, one chunk + 1, the whole S
    pytest.param(4, 32, 2, 2, 8, 8, jnp.float32, [1, 8, 9, 32],
                 id="fills-at-chunk-edges"),
    pytest.param(4, 48, 2, 1, 16, 16, jnp.bfloat16, [1, 16, 17, 48],
                 id="bf16-fills-at-chunk-edges"),
    # a KV count the TPU lays out head-major: the kernel reads that view
    pytest.param(3, 32, 5, 2, 8, 8, jnp.float32, [1, 8, 29],
                 id="heads-major"),
    pytest.param(2, 48, 6, 1, 16, 16, jnp.bfloat16, None,
                 id="heads-major-bf16"),
    # block=None: the shape rule (one chunk at these sizes)
    pytest.param(3, 40, 2, 1, 32, None, jnp.float32, None, id="shape-rule"),
    pytest.param(4, 33, 4, 2, 16, None, jnp.bfloat16, None,
                 id="shape-rule-bf16-padded"),
])
def test_flash_decode_bitwise_vs_oracle(b, s, kv, g, hd, block, dtype,
                                        fills):
    """Same two-phase exactness anchor as the paged kernel: the flash
    decode kernel is BITWISE equal to its chunked-gather oracle on
    ragged fills — what the fused runtime's token agreement rests on."""
    q, ck, cv, fills = _flash_case(b, s, kv, g, hd, seed=b * 7 + s,
                                   dtype=dtype, fills=fills)
    out = ops.flash_attention_decode(q, ck, cv, fills, block=block,
                                     backend="kernel")
    want = ops.flash_attention_decode(q, ck, cv, fills, block=block,
                                      backend="oracle")
    assert out.shape == (b, kv * g, hd)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_flash_decode_invariant_to_cache_tail():
    """Positions >= kv_len[b] are exact zeros in both phases: garbage in
    the unwritten tail of the dense cache, in the row's last chunk and in
    whole chunks past it, cannot leak into the output."""
    for kv in (2, 5):                    # both views of the cache
        q, ck, cv, fills = _flash_case(3, 32, kv, 2, 8, seed=9,
                                       fills=[3, 8, 21])
        base = np.asarray(ops.flash_attention_decode(q, ck, cv, fills,
                                                     block=8))
        ckg, cvg = np.asarray(ck).copy(), np.asarray(cv).copy()
        for i, n in enumerate(np.asarray(fills)):
            ckg[i, int(n):] = 1e9
            cvg[i, int(n):] = -1e9
        out = ops.flash_attention_decode(q, jnp.asarray(ckg),
                                         jnp.asarray(cvg), fills, block=8)
        np.testing.assert_array_equal(base, np.asarray(out))


@pytest.mark.parametrize("seq,kv,hd", [(768, 20, 128), (768, 8, 128),
                                       (4096, 8, 128), (8 * 97, 20, 128)],
                         ids=["qwen1.5-4b", "gqa", "gqa-long", "prime"])
def test_flash_decode_block_divides_the_cache_within_budget(seq, kv, hd):
    """The chunk rule: a multiple of 8 dividing the cache (no pad copy)
    whose double-buffered bfloat16 K and V blocks fit the budget — the
    largest such, so 8 where the cache has no larger divisor in it."""
    blk = ops.flash_decode_block(seq, kv, hd, jnp.bfloat16)
    per_pos = 2 * 2 * kv * hd * 2
    assert blk % 8 == 0 and seq % blk == 0
    assert blk * per_pos <= ops.FLASH_DECODE_KV_VMEM_BYTES
    larger = [d for d in range(blk + 8, seq + 1, 8) if seq % d == 0]
    assert not larger or larger[0] * per_pos > ops.FLASH_DECODE_KV_VMEM_BYTES
    if seq == 8 * 97:
        assert blk == 8
    else:
        assert blk >= 128


def test_flash_decode_block_of_a_ragged_cache_pads_under_one_chunk():
    """A cache length off the multiple of 8 is padded to the next one,
    and no further: the chunk divides the padded length."""
    assert ops.flash_decode_block(33, 4, 16, jnp.float32) == 40
    assert ops.flash_decode_block(9, 1, 8, jnp.bfloat16) == 16
    assert ops.flash_decode_block(768, 20, 128, jnp.float32) < \
        ops.flash_decode_block(768, 20, 128, jnp.bfloat16)


@pytest.mark.parametrize("kv,g,layout", [(20, 1, "heads_major"),
                                         (8, 5, "row_major")],
                         ids=["qwen1.5-4b", "qwen3-14b"])
def test_flash_decode_tags_its_layout_and_grouping(kv, g, layout):
    """The compiled kernel's metadata names the cache layout it reads
    (``ops.flash_decode_heads_major``) and the query heads per KV head, at
    the chat cells' shape (16 slots of 768 positions, bfloat16 cache), so
    that a trace tells the two paths apart.  Traced, not run: the
    metadata is a parameter of the Pallas call."""
    def f(q, k, v, fills):
        return ops.flash_attention_decode(q, k, v, fills, interpret=False)

    cache = jax.ShapeDtypeStruct((16, 768, kv, 128), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(f)(
        jax.ShapeDtypeStruct((16, kv * g, 128), jnp.bfloat16), cache, cache,
        jax.ShapeDtypeStruct((16,), jnp.int32))
    calls = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    assert dict(calls[0].params["metadata"]) == {
        "kernel": "flash_decode", "layout": layout, "group": g}
