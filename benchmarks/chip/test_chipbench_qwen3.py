"""The ``qwen3-14b`` configuration: grouped-query attention (40 query
heads over 8 KV heads) with a per-head RMSNorm of q and k.

A tiny cell built from ``configs/qwen3-14b.json`` (10 query heads over 2
KV heads, so 5 to a group as at the published width; head_dim 16; no
bias; 2 layers; 3 array partitions) is served through the harness on the
CPU, with the Pallas kernels in interpret mode, and judged against the
plain reference as a run is: a sound run is correct, the program's
bfloat16-conductance path and a token altered where it is produced are
not.  The cost counts are pinned to the committed configuration."""

import dataclasses
import time
import types

import jax
import jax.numpy as jnp
import pytest

from chipbench import check, costs, serve, spec

VOCAB = 512
#: the tiny fault tests' limits (``test_chipbench_faults.py``)
TINY_LIMIT = {"logit_gap_mean_codes": 1.0, "first_layer_v_off_share": 0.01}


def _committed():
    return spec.load_json(f"{spec.BENCH_DIR}/configs/qwen3-14b.json")


def tiny_cell(**analog):
    cfg = _committed()
    cfg.update(hidden_size=96, intermediate_size=192, head_dim=16,
               num_attention_heads=10, num_key_value_heads=2,
               num_hidden_layers=2, vocab_size=VOCAB)
    cfg["analog"] = dict(cfg["analog"], max_rows=32, **analog)  # 3 parts
    tf = {"loop": "closed", "clients": 4, "slots": 4,
          "prompt_tokens": {"log_uniform": [8, 32]},
          "output_tokens": {"log_uniform": [8, 24]}, "max_len": 64,
          "buckets": [16, 32], "sampler": "greedy",
          "calibration_tokens": [2, 16],
          "check": {"min_served_tokens": 64, "max_requests": 6}}
    return spec.Cell(name="tiny-qwen3", chips=1, config=cfg, traffic=tf,
                     end_to_end=[], per_layer=[])


def _judge(srv, decode=None):
    ref = spec.reference_module("dense_analog")
    orig = srv.rt._decode_fn
    srv.rt.reset()
    if decode is not None:
        srv.rt._decode_fn = lambda s, p, pk: decode(orig, s, p, pk)
    try:
        with jax.default_matmul_precision("highest"):
            run = serve.serve_window(srv, 2.0, False)
            checks, _ = check.judge(run, srv, TINY_LIMIT, ref)
    finally:
        srv.rt._decode_fn = orig
    return run, checks


@pytest.fixture(scope="module")
def server():
    with jax.default_matmul_precision("highest"):
        yield serve.Server(tiny_cell(), 1, time.perf_counter())


def test_tiny_cell_keeps_the_grouping_and_qk_norm(server):
    cfg = server.cfg
    assert (cfg.n_heads // cfg.n_kv_heads, cfg.hd) == (5, 16)
    assert cfg.qk_norm and not cfg.qkv_bias
    assert server.params["layers"]["attn"]["q_norm"].shape == (2, 16)
    assert "bq" not in server.params["layers"]["attn"]
    assert server.rt.attn_backend == "flash"


def test_sound_run_is_correct(server):
    run, checks = _judge(server)
    assert all(v["ok"] for v in checks.values()), checks
    assert checks["site_classes_missing"]["value"] == 0
    assert checks["first_layer_v_off_share"]["value"] == 0.0
    assert run.cached and all(r["v"].shape == (r["seq"].size, 2, 16)
                              for r in run.cached)
    assert sum(r.tokens is not None for r in run.reqs.values()) >= 2


def test_token_altered_where_produced_is_caught(server):
    def altered(orig, state, params, pack):
        s = orig(state, params, pack)
        tok = (s.tok + 1) % VOCAB
        hit = (jnp.arange(s.out.shape[1])[None] == (s.emitted - 1)[:, None]) \
            & state.active[:, None]
        return dataclasses.replace(s, tok=tok,
                                   out=jnp.where(hit, tok[:, None], s.out))

    _, checks = _judge(server, altered)
    assert not checks["logit_gap_mean_codes"]["ok"]
    assert checks["logit_gap_mean_codes"]["value"] > 5 * TINY_LIMIT[
        "logit_gap_mean_codes"]


def test_program_bfloat16_path_is_caught():
    """Conductances stored and read as bfloat16: the first layer's
    cached values see it."""
    with jax.default_matmul_precision("highest"):
        srv = serve.Server(tiny_cell(compute_dtype="bfloat16"), 1,
                           time.perf_counter())
    _, checks = _judge(srv)
    assert not checks["first_layer_v_off_share"]["ok"]
    assert checks["first_layer_v_off_share"]["value"] > 10 * TINY_LIMIT[
        "first_layer_v_off_share"]


def test_site_and_decode_counts_of_the_committed_config():
    c = _committed()
    assert (c["num_hidden_layers"], c["vocab_size"]) == (4, 19072)
    # wq, wo: 5120 x 5120; wk, wv: 5120 x 8*128; gate, up, down: x 17408
    layer = 2 * 5120 * 5120 + 2 * 5120 * 1024 + 3 * 5120 * 17408
    assert layer == 330_301_440
    assert costs.site_weight_count(c) == (4 * layer, 5120 * 19072)
    f, b = costs.decode_mvms(c, 16)
    assert f == 2 * 16 * (4 * layer + 5120 * 19072)
    rows = 4 * (2 * (5120 + 5120) + 2 * (5120 + 1024) + 2 * (5120 + 17408)
                + (17408 + 5120)) + (5120 + 19072)
    assert b == 4 * (4 * layer + 5120 * 19072) + 4 * 16 * rows \
        + 8 * (4 * 7 + 1)


# -- the cell's per-layer readers, on synthetic traces --------------------


def _flash_op(name, meta):
    return (f"%{name} = f32[16,40,128] custom-call(s32[16] %fills), "
            f'custom_call_target="tpu_custom_call", '
            f"frontend_attributes={{kernel_metadata={meta}}}")


ROW_MAJOR = '{\n"group":5,\n"kernel":"flash_decode",\n"layout":"row_major"\n}'
HEADS_MAJOR = ROW_MAJOR.replace("row_major", "heads_major").replace(
    '"group":5', '"group":1')
UNTAGGED_LAYOUT = '{\n"kernel":"flash_decode"\n}'
FUSED = '{\n"kernel":"fused_mvm",\n"site":"x"\n}'


def _synthetic(flash_meta):
    """One decode program of 10 ms holding a 1 ms flash-decode op and a
    4 ms fused MVM, one host step of 16 lanes at fill 300."""
    from chipbench import peaks
    from chipbench import trace as TR

    ev = TR.Event
    plane = "/device:TPU:0"
    tr = TR.Trace(
        ops={plane: [ev(_flash_op("flash_decode.1", flash_meta), 10.0, 1e6),
                     ev(_flash_op("fused_mvm.1", FUSED).replace(
                         "s32[16]", "f32[1,1]"), 2e6, 4e6)]},
        modules={plane: [ev("jit_decode(1)", 5.0, 1e7)]},
        host=[ev("bench.window", 0.0, 1e8), ev("bench.step.0", 1.0, 2e7)])
    return types.SimpleNamespace(
        trace=tr, plane=plane, counters={}, config=_committed(),
        steps=[serve.Step(0, 0.0, 0.0, [300] * 16, [])],
        peaks=peaks.peaks("TPU v5 lite"), slots=16)


def test_flash_roofline_reads_only_row_major_ops():
    read = spec.metric_reader("flash_decode_roofline.gqa")
    run = _synthetic(ROW_MAJOR)
    need = costs.roofline_time(*costs.flash_decode(run.config, [300] * 16),
                               run.peaks)
    assert read(run) == pytest.approx(100.0 * need / 1e-3, rel=1e-9)
    # the head-major path, and a program whose tag has no layout: nothing
    assert read(_synthetic(HEADS_MAJOR)) is None
    assert read(_synthetic(UNTAGGED_LAYOUT)) is None


@pytest.mark.parametrize("name", ["decode_step_ms", "mfu.decode",
                                  "decode_glue_ms",
                                  "fused_mvm_roofline.decode",
                                  "idle_share.serve"])
def test_gqa_readers_read_as_the_chat_cells(name):
    for meta in (ROW_MAJOR, UNTAGGED_LAYOUT):
        run = _synthetic(meta)
        got = spec.metric_reader(name + ".gqa")(run)
        assert got is not None
        assert got == spec.metric_reader(name)(run)
