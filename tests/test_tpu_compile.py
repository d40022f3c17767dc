"""Mosaic compiles of the serving kernels for a described TPU v5e.

Interpret mode accepts kernels that the TPU compiler refuses (blocks not
(8, 128)-aligned, dynamic slices of values, scoped VMEM overruns), so
these tests lower each kernel of the main serving path through Mosaic
for one chip of a ``v5e:2x2`` topology that is described, not attached,
at the widths qwen1.5-4b serves: d_model 2560, d_ff 6912, 20 heads of
128, decode batch 8, and Design A's ``max_rows=1152`` partitioning
(K = 2560 -> 3 partitions of 854 rows, K = 6912 -> 6 of 1152).  One
case compiles a whole decode step: the ``qwen3-14b.chat`` cell's, with
its memory, its flash-decode layout and the copies it makes.

Each compiled kernel also carries its tag: the Pallas call's ``name=``
names the instruction and its ``metadata=`` appears in the op's own text
as ``kernel_metadata``, which is what a profiler trace's op names hold.

Nothing runs: a compile that passes says nothing about results or
times.  The topology is described inside a module-scoped fixture, never
at import, so that only the test worker given this file loads the TPU
compiler.
"""

import dataclasses
import os
import re

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


def _tagged_calls(text: str):
    """``(instruction name, kernel metadata)`` of every tagged Mosaic call
    in ``text``."""
    return re.findall(r"%(\S+) = [^\n]*custom_call_target=\"tpu_custom_call\""
                      r"[^\n]*kernel_metadata=\{\n(.*?)\n\}\}", text, re.S)


def _assert_tagged(text: str, kernel: str, site=None) -> None:
    """Every Mosaic call in ``text`` is ``kernel``'s, named after it and
    tagged with it (and with its fused site class)."""
    calls = _tagged_calls(text)
    assert calls, "no tagged tpu_custom_call in the compiled text"
    for name, meta in calls:
        assert name.startswith(kernel + "."), name
        assert f'"kernel":"{kernel}"' in meta, meta
        if site is not None:
            assert f'"site":"{site}"' in meta, meta


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

    text = _compile_text(f, shapes, one_chip)
    assert "tpu_custom_call" in text
    _assert_tagged(text, "fused_mvm", "('linear', 1, 7, 8, None, None)")


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

    text = _compile_text(f, shapes, one_chip)
    assert "tpu_custom_call" in text
    _assert_tagged(text, "fused_mvm",
                   f"('linear', 8, 1, 8, {n_bits}, None)")


@pytest.mark.parametrize("n_slices", [1, 2])
def test_fused_mvm_parasitic_compiles(one_chip, n_slices):
    _, _, shapes = _fused_shapes(D_MODEL, 256, n_slices)

    def f(x, gp, gm, lo, hi, scale):
        return ops.fused_mvm_parasitic(
            x, gp, gm, r_hat=jnp.float32(1e-4), adc_lo=lo, adc_hi=hi,
            adc_bits=8, cell_bits=7 if n_slices == 1 else 4, n_bits=7,
            scale=scale, interpret=False)

    text = _compile_text(f, shapes, one_chip)
    assert "tpu_custom_call" in text
    cell_bits = 7 if n_slices == 1 else 4
    _assert_tagged(text, "fused_mvm",
                   f"('parasitic', {n_slices}, {cell_bits}, 8, None, 7)")


@pytest.mark.parametrize("parasitic", [False, True],
                         ids=["linear", "parasitic"])
def test_fused_mvm_compiles_reading_a_layer_stack(one_chip, parasitic):
    """Both fused kernels read a 4-layer stack of 1024-row partitions
    (K = 5120) by a traced layer index: Mosaic takes the scale and the
    index as scalar-prefetch operands, the scale first, and the call is
    tagged ``"stacked": true``."""
    _, rows, shapes = _fused_shapes(5120, 256, 1)
    assert rows == 1024
    shapes[1:3] = [((4,) + shapes[1][0], jnp.float32)] * 2
    shapes.append(((), jnp.int32))

    def f(x, gp, gm, lo, hi, scale, layer):
        kw = dict(adc_lo=lo, adc_hi=hi, adc_bits=8, cell_bits=7,
                  scale=scale, interpret=False, layer=layer)
        if parasitic:
            return ops.fused_mvm_parasitic(x, gp, gm, r_hat=jnp.float32(1e-4),
                                           n_bits=7, **kw)
        return ops.fused_mvm(x, gp, gm, n_bits=None, **kw)

    text = _compile_text(f, shapes, one_chip)
    _assert_tagged(text, "fused_mvm")
    assert [st for _, st in _fused_mvm_calls(text)] == [True]
    _assert_fused_calls_read_as_the_benchmark_finds_them(text, 1)


def test_bitline_mvm_compiles(one_chip):
    rows = design_a().rows_per_partition(D_MODEL)

    def f(g, x):
        return ops.bitline_mvm(g, x, jnp.float32(1e-4), interpret=False)

    shapes = [((rows, 256), jnp.float32), ((BATCH, rows), jnp.float32)]
    text = _compile_text(f, shapes, one_chip)
    assert "tpu_custom_call" in text
    _assert_tagged(text, "bitline_mvm")


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
    text = _compile_text(f, shapes, one_chip)
    assert "tpu_custom_call" in text
    _assert_tagged(text, "analog_bitline_diff" if parasitic
                   else "analog_mvm_diff")


def test_flash_attention_decode_compiles(one_chip):
    cache = 512

    def f(q, k, v, fills):
        return ops.flash_attention_decode(q, k, v, fills, interpret=False)

    shapes = [((BATCH, HEADS, HEAD_DIM), jnp.float32),
              ((BATCH, cache, HEADS, HEAD_DIM), jnp.bfloat16),
              ((BATCH, cache, HEADS, HEAD_DIM), jnp.bfloat16),
              ((BATCH,), jnp.int32)]
    text = _compile_text(f, shapes, one_chip)
    assert "tpu_custom_call" in text
    _assert_tagged(text, "flash_decode")


@pytest.mark.parametrize("kv,g", [(HEADS, 1), (8, 5)], ids=["mha", "gqa"])
def test_flash_decode_reads_the_stored_cache_at_serving_shape(one_chip,
                                                              kv, g):
    """The chat cell's decode shape (16 slots of 768 positions, 20 heads,
    bfloat16 cache) and qwen3-14b's grouping (8 KV heads of 5 queries):
    the kernel takes the cache as stored, in the layout the chip stores
    it in, so nothing outside the kernel makes a cache-sized array (no
    float32 copy, no relayout; a bitcast is free), and its first operand is the int32 fills that the
    benchmark's trace reader matches (``custom-call(s32[``)."""
    batch, cache = 16, 768
    kv_shape = (batch, cache, kv, HEAD_DIM)

    def f(q, k, v, fills):
        return ops.flash_attention_decode(q, k, v, fills, interpret=False)

    shapes = [((batch, kv * g, HEAD_DIM), jnp.bfloat16),
              (kv_shape, jnp.bfloat16), (kv_shape, jnp.bfloat16),
              ((batch,), jnp.int32)]
    text = _compile_text(f, shapes, one_chip)
    _assert_tagged(text, "flash_decode")
    first = re.findall(r"tpu_custom_call\", operand_layout_constraints="
                       r"\{(\w+\[)", text)
    assert first == ["s32["], first
    dims = ",".join(map(str, sorted(kv_shape)))
    for line in text.splitlines():
        shape = re.match(r"\s*(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\]", line)
        if shape and not re.search(r" (parameter|bitcast)\(", line):
            assert ",".join(sorted(shape[2].split(","), key=int)) != dims, \
                line


def test_paged_attention_compiles(one_chip):
    page, per_row = 16, 32

    def f(q, kp, vp, ptab, fills):
        return ops.paged_attention(q, kp, vp, ptab, fills, interpret=False)

    pool = 1 + BATCH * per_row
    shapes = [((BATCH, HEADS, HEAD_DIM), jnp.float32),
              ((pool, page, HEADS, HEAD_DIM), jnp.bfloat16),
              ((pool, page, HEADS, HEAD_DIM), jnp.bfloat16),
              ((BATCH, per_row), jnp.int32), ((BATCH,), jnp.int32)]
    text = _compile_text(f, shapes, one_chip)
    assert "tpu_custom_call" in text
    _assert_tagged(text, "paged_attention")


def _decode_step(cfg, one_chip, monkeypatch):
    """``ServeRuntime``'s jitted decode step for ``cfg`` with a Design A
    pack, 16 slots of 768 bfloat16 positions, compiled for one described
    v5e chip: ``(compiled text, bytes held while it runs, pack)``.  What
    the chip holds is every weight and conductance, the slot state, the
    step's output and its temporaries."""
    import dataclasses

    from repro.core.errors import ErrorModel
    from repro.models.registry import get_model
    from repro.serve import ServeRuntime, calibrate_lm, program_lm

    monkeypatch.setattr(ops, "_use_interpret", lambda: False)
    spec = design_a(ErrorModel(kind="state_proportional", alpha=0.02),
                    fused="kernel")
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params = jax.eval_shape(
        lambda k: jax.tree.map(lambda a: a.astype(cfg.dtype),
                               get_model(cfg).init_params(cfg, k)), key)
    pack = jax.eval_shape(lambda p, k: program_lm(cfg, p, spec, k),
                          params, key)
    pack = jax.eval_shape(
        lambda p, pk, t: dataclasses.replace(calibrate_lm(cfg, p, pk, t),
                                             collect=False),
        params, pack, jax.ShapeDtypeStruct((2, 64), jnp.int32))
    rt = ServeRuntime(cfg, params, pack=pack, max_slots=16, max_len=768,
                      buckets=(128, 256), attn_backend="flash")
    on_chip = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), t)
    state = on_chip(jax.eval_shape(lambda: rt._state))
    compiled = rt._decode_fn.lower(state, on_chip(params),
                                   on_chip(pack)).compile()

    nbytes = lambda t: sum(a.size * a.dtype.itemsize
                           for a in jax.tree.leaves(t))
    mem = compiled.memory_analysis()
    held = (nbytes(params) + nbytes(pack) + nbytes(state)
            + mem.output_size_in_bytes + mem.temp_size_in_bytes)
    return compiled.as_text(), held, pack


def _fused_mvm_calls(text: str):
    """``(operand shapes, stacked)`` of every fused MVM call in ``text``:
    the shapes as its ``operand_layout_constraints`` list them, in
    operand order, and its ``"stacked"`` tag."""
    calls = re.findall(r"%fused_mvm\.\d+ = [^\n]*custom-call\([^\n]*"
                       r"operand_layout_constraints=\{((?:\w+\[[\d,]*\]\{[\d,]*\}(?:, )?)*)\}"
                       r"[^\n]*"
                       r"kernel_metadata=\{\n(.*?)\n\}\}", text, re.S)
    out = []
    for operands, meta in calls:
        stacked = re.search(r'"stacked":(true|false)', meta)
        assert stacked, meta
        out.append((re.findall(r"\w+\[[\d,]*\]", operands),
                    stacked[1] == "true"))
    return out


def _ops_of_shape(text: str, keep):
    """``(line, name, opcode)`` of every instruction whose shape ``keep``
    takes."""
    for line in text.splitlines():
        op = re.match(r"\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(",
                      line)
        if op and keep(tuple(int(d) for d in op[2].split(",") if d)):
            yield line, op[1], op[3]


def _assert_fused_calls_read_as_the_benchmark_finds_them(text, n_calls):
    """Every fused MVM call leads with its (1, 1) float32 dequant scale,
    the operand by which ``chipbench.readers.FUSED_MVM_OPS`` tells it from
    flash-decode (whose int32 fills lead), stacked or not."""
    calls = _fused_mvm_calls(text)
    assert len(calls) == n_calls, calls
    assert all(operands[0] == "f32[1,1]" for operands, _ in calls), calls
    return calls


def test_qwen3_decode_step_fits_and_reads_the_cache_row_major(one_chip,
                                                              monkeypatch):
    """The ``qwen3-14b.chat`` cell's whole decode step, as
    ``ServeRuntime`` jits it: Qwen3-14B's published widths (5120, 40 query
    heads over 8 KV heads of 128, d_ff 17408, per-head q/k norm) on 4
    layers with an eighth of the padded vocabulary (19072), a Design A
    pack, 16 slots of 768 bfloat16 positions.  What the chip holds while
    it runs fits its 16 GiB; flash-decode reads the 8-head cache
    row-major, 5 queries to a KV head; no copy, convert or transpose of a
    layer's cache, or of the stacked cache, is made outside the kernel.
    Every layer site's partitions (1024 and 1088 rows) are read by the
    kernel in the stacked pack, by the layer's index: each of the 7
    layer-site calls is ``"stacked": true`` (the head's is not), and no
    slice, copy or relayout of any site's conductances, one layer's or
    the stack's, is written out before a kernel."""
    from repro.configs.qwen3_14b import CONFIG

    cfg = dataclasses.replace(CONFIG, n_layers=4, vocab=19072, remat=False)
    slots, max_len = 16, 768
    text, held, pack = _decode_step(cfg, one_chip, monkeypatch)
    assert held < 16 * 2 ** 30, held

    calls = _tagged_calls(text)
    flash = [meta for name, meta in calls if name.startswith("flash_decode.")]
    assert {name.split(".")[0] for name, _ in calls} == {"flash_decode",
                                                         "fused_mvm"}
    assert all('"kernel":"flash_decode"' in m and '"layout":"row_major"' in m
               and '"group":5' in m for m in flash), flash
    layer = sorted((slots, max_len, cfg.n_kv_heads, cfg.hd))
    stacked = sorted(layer + [cfg.n_layers])
    cache = lambda d: sorted(d) in (layer, stacked)
    for line, name, opcode in _ops_of_shape(text, cache):
        assert not re.match(r"(copy|convert|transpose)", name), line
        assert opcode not in ("copy", "convert", "transpose"), line

    fused = _assert_fused_calls_read_as_the_benchmark_finds_them(text, 8)
    assert sorted(st for _, st in fused) == [False] + [True] * 7
    sites = {aw.g_pos.shape[-3:] for aw in pack.layer_weights.values()}
    conductance = lambda d: (len(d) >= 4 and d[-3:] in sites
                             and d[:-3] in ((1,), (1, 1), (cfg.n_layers, 1)))
    for line, _, opcode in _ops_of_shape(text, conductance):
        assert opcode in ("parameter", "get-tuple-element"), line


def test_qwen1_5_decode_step_reads_only_its_1152_row_stack_in_place(
        one_chip, monkeypatch):
    """The ``qwen1.5-4b.chat`` cell's decode step (published widths, 8
    layers, the whole vocabulary, 16 slots of 768 positions).  Its
    ``w_down`` partitions have 1152 rows, so the kernel reads that stack
    by the layer's index (``"stacked": true`` on that call alone); the
    other six sites have 854 rows, which the TPU tiles ``(1, 128)``, and
    keep their per-layer slice.  Nothing relays out, copies or converts
    a whole stack (a shape led by the 8 layers), which handing such a
    stack to the kernel would do, and what the chip holds fits 16 GiB."""
    from repro.configs.qwen1_5_4b import CONFIG

    cfg = dataclasses.replace(CONFIG, n_layers=8, remat=False)
    text, held, pack = _decode_step(cfg, one_chip, monkeypatch)
    assert held < 16 * 2 ** 30, held

    fused = _assert_fused_calls_read_as_the_benchmark_finds_them(text, 8)
    down = pack.layer_weights["w_down"].g_pos.shape
    assert down[-2] == 1152
    stacked = [operands for operands, st in fused if st]
    assert len(stacked) == 1
    assert f"f32[{','.join(map(str, down))}]" in stacked[0], stacked
    sites = {aw.g_pos.shape[-3:] for aw in pack.layer_weights.values()}
    whole = lambda d: len(d) >= 4 and d[-3:] in sites and d[0] == cfg.n_layers
    for line, _, opcode in _ops_of_shape(text, whole):
        assert opcode in ("parameter", "get-tuple-element"), line
    one_down = lambda d: d in (down[1:], (1,) + down[1:])
    for line, _, opcode in _ops_of_shape(text, one_down):
        assert opcode not in ("copy", "dynamic-slice", "fusion"), line
