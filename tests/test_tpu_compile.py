"""Mosaic compiles of the serving kernels for a described TPU v5e.

Interpret mode accepts kernels that the TPU compiler refuses (blocks not
(8, 128)-aligned, dynamic slices of values, scoped VMEM overruns), so
these tests lower each kernel of the main serving path through Mosaic
for one chip of a ``v5e:2x2`` topology that is described, not attached,
at the widths qwen1.5-4b serves: d_model 2560, d_ff 6912, 20 heads of
128, decode batch 8, and Design A's ``max_rows=1152`` partitioning
(K = 2560 -> 3 partitions of 854 rows, K = 6912 -> 6 of 1152).

Nothing runs: a compile that passes says nothing about results or
times.  The topology is described inside a module-scoped fixture, never
at import, so that only the test worker given this file loads the TPU
compiler.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.analog import design_a
from repro.kernels import ops

D_MODEL, D_FF, HEADS, HEAD_DIM, BATCH = 2560, 6912, 20, 128, 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, shapes, sharding) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _fused_shapes(k, n, n_slices, m=BATCH):
    spec = design_a()
    p, rows = spec.n_partitions(k), spec.rows_per_partition(k)
    f32 = jnp.float32
    return p, rows, [((m, p, rows), f32),
                     ((n_slices, p, rows, n), f32),
                     ((n_slices, p, rows, n), f32),
                     ((n_slices,), f32), ((n_slices,), f32), ((), f32)]


@pytest.mark.parametrize("k,n", [(D_MODEL, D_MODEL), (D_MODEL, D_FF),
                                 (D_FF, D_MODEL)],
                         ids=["attn", "mlp_up", "mlp_down"])
def test_fused_mvm_compiles_at_published_widths(one_chip, k, n):
    p, _, shapes = _fused_shapes(k, n, 1)
    assert p > 1          # every published-width site is multi-partition

    def f(x, gp, gm, lo, hi, scale):
        return ops.fused_mvm(x, gp, gm, adc_lo=lo, adc_hi=hi, adc_bits=8,
                             cell_bits=7, n_bits=None, scale=scale,
                             interpret=False)

    assert "tpu_custom_call" in _compile_text(f, shapes, one_chip)


@pytest.mark.parametrize("m", [BATCH, 128], ids=["decode", "prefill"])
@pytest.mark.parametrize("n_bits", [None, 7], ids=["analog", "digital"])
def test_fused_mvm_eight_slices_fit_vmem(one_chip, n_bits, m):
    """1-bit cells (Fig. 16): 8 resident slices of 1152-row partitions,
    at a decode tile and at a full 128-row prefill tile (the digital
    bit fold's live bit planes make that the largest working set)."""
    _, rows, shapes = _fused_shapes(D_FF, 256, 8, m)
    assert rows == 1152

    def f(x, gp, gm, lo, hi, scale):
        return ops.fused_mvm(x, gp, gm, adc_lo=lo, adc_hi=hi, adc_bits=8,
                             cell_bits=1, n_bits=n_bits, scale=scale,
                             interpret=False)

    assert "tpu_custom_call" in _compile_text(f, shapes, one_chip)


@pytest.mark.parametrize("n_slices", [1, 2])
def test_fused_mvm_parasitic_compiles(one_chip, n_slices):
    _, _, shapes = _fused_shapes(D_MODEL, 256, n_slices)

    def f(x, gp, gm, lo, hi, scale):
        return ops.fused_mvm_parasitic(
            x, gp, gm, r_hat=jnp.float32(1e-4), adc_lo=lo, adc_hi=hi,
            adc_bits=8, cell_bits=7 if n_slices == 1 else 4, n_bits=7,
            scale=scale, interpret=False)

    assert "tpu_custom_call" in _compile_text(f, shapes, one_chip)


def test_bitline_mvm_compiles(one_chip):
    rows = design_a().rows_per_partition(D_MODEL)

    def f(g, x):
        return ops.bitline_mvm(g, x, jnp.float32(1e-4), interpret=False)

    shapes = [((rows, 256), jnp.float32), ((BATCH, rows), jnp.float32)]
    assert "tpu_custom_call" in _compile_text(f, shapes, one_chip)


@pytest.mark.parametrize("parasitic", [False, True])
def test_legacy_analog_mvm_compiles(one_chip, parasitic):
    spec = design_a()
    p, rows = spec.n_partitions(D_MODEL), spec.rows_per_partition(D_MODEL)
    kw = dict(adc_lo=jnp.float32(-50.0), adc_hi=jnp.float32(50.0),
              adc_bits=8, gain=127.0, interpret=False)

    def f(x, gp, gm):
        if parasitic:
            return ops.analog_mvm_parasitic(x, gp, gm, r_hat=1e-4,
                                            n_bits=7, **kw)
        return ops.analog_mvm(x, gp, gm, **kw)

    shapes = [((BATCH, p, rows), jnp.float32),
              ((p, rows, 256), jnp.float32), ((p, rows, 256), jnp.float32)]
    assert "tpu_custom_call" in _compile_text(f, shapes, one_chip)


def test_flash_attention_decode_compiles(one_chip):
    cache = 512

    def f(q, k, v, fills):
        return ops.flash_attention_decode(q, k, v, fills, interpret=False)

    shapes = [((BATCH, HEADS, HEAD_DIM), jnp.float32),
              ((BATCH, cache, HEADS, HEAD_DIM), jnp.bfloat16),
              ((BATCH, cache, HEADS, HEAD_DIM), jnp.bfloat16),
              ((BATCH,), jnp.int32)]
    assert "tpu_custom_call" in _compile_text(f, shapes, one_chip)


def test_paged_attention_compiles(one_chip):
    page, per_row = 16, 32

    def f(q, kp, vp, ptab, fills):
        return ops.paged_attention(q, kp, vp, ptab, fills, interpret=False)

    pool = 1 + BATCH * per_row
    shapes = [((BATCH, HEADS, HEAD_DIM), jnp.float32),
              ((pool, page, HEADS, HEAD_DIM), jnp.bfloat16),
              ((pool, page, HEADS, HEAD_DIM), jnp.bfloat16),
              ((BATCH, per_row), jnp.int32), ((BATCH,), jnp.int32)]
    assert "tpu_custom_call" in _compile_text(f, shapes, one_chip)
