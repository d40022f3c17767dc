"""A run with its timed path broken underneath, or the control in the
program's place, must read ``correct`` false.  The harness's look for a
chip is skipped: these drive the rest of a run (set-up, closed-loop
window, reference comparison) on the CPU at a tiny size of the
qwen1.5-4b configuration, with the Pallas kernels in interpret mode."""

import dataclasses
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import check, serve, spec

VOCAB = 512
#: mean gap allowed at this tiny size: sound runs read 0.09-0.23 codes
#: on the CPU (seeds 1-6); the control, the reference with its
#: activations in float8 in the program's place, reads 2.9-5.5 (seeds
#: 1-3); a served token altered where it is produced reads hundreds
#: share of first-layer cached values half a code or more off the
#: reference: sound runs read 0 on the CPU (seeds 1-2); the program's own
#: bfloat16 path 0.23-0.26 (seeds 1-2)
TINY_LIMIT = {"logit_gap_mean_codes": 1.0, "first_layer_v_off_share": 0.01}


def tiny_cell(**analog):
    cfg = spec.load_json(f"{spec.BENCH_DIR}/configs/qwen1.5-4b.json")
    cfg.update(hidden_size=128, intermediate_size=192, num_attention_heads=4,
               num_key_value_heads=4, num_hidden_layers=2, vocab_size=VOCAB)
    cfg["analog"] = dict(cfg["analog"], max_rows=48, **analog)  # 3 parts
    tf = {"loop": "closed", "clients": 4, "slots": 4,
          "prompt_tokens": {"log_uniform": [8, 32]},
          "output_tokens": {"log_uniform": [8, 24]}, "max_len": 64,
          "buckets": [16, 32], "sampler": "greedy",
          "calibration_tokens": [2, 16],
          "check": {"min_served_tokens": 64, "max_requests": 6}}
    return spec.Cell(name="tiny", chips=1, config=cfg, traffic=tf,
                     end_to_end=[], per_layer=[])


@pytest.fixture(scope="module")
def server():
    with jax.default_matmul_precision("highest"):
        yield serve.Server(tiny_cell(), 1, time.perf_counter())


def _served(run):
    return [dict(prompt=r.prompt, tokens=r.tokens, max_new=r.max_new)
            for r in run.reqs.values() if r.tokens is not None]


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


def test_sound_run_is_correct(server):
    run, checks = _judge(server)
    assert all(v["ok"] for v in checks.values()), checks
    assert checks["site_classes_missing"]["value"] == 0
    assert checks["first_layer_v_off_share"]["value"] == 0.0
    assert run.cached and all(r["v"].shape[0] == r["seq"].size
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


def test_step_that_returns_its_state_unchanged_is_caught(server):
    run, checks = _judge(server, lambda orig, s, p, pk: s)
    assert not all(v["ok"] for v in checks.values())
    assert checks["no_request_compared"]["value"] == 1


def test_control_is_caught(server):
    """The control: the reference with its activations one step below the
    configured bfloat16 (float8_e4m3fn), put in the program's place; at
    each compared position the token it puts first is read against the
    reference."""
    run, _ = _judge(server)
    pick = check.sample(_served(run), 1, 64, 6)
    seqs, starts = check.teacher_forced(pick)
    ref = spec.reference_module("dense_analog")
    c, shapes = server.cell.config, check.shapes(server.cell.traffic)
    with jax.default_matmul_precision("highest"):
        lg, code = ref.logits(c, server.params, server.k_prog, server.calib,
                              seqs, starts, **shapes)
        low, _ = ref.logits(c, server.params, server.k_prog, server.calib,
                            seqs, starts, precision="float8", **shapes)
    control = check.gaps(lg, [x.argmax(axis=1) for x in low], code).mean()
    served = check.gaps(lg, [r["tokens"] for r in pick], code).mean()
    assert served <= TINY_LIMIT["logit_gap_mean_codes"] < control


def test_program_bfloat16_path_is_caught():
    """The control where the program has a lower-precision path of its
    own: conductances stored and read as bfloat16.  The served tokens'
    mean gap does not see it; the first layer's cached values do."""
    with jax.default_matmul_precision("highest"):
        srv = serve.Server(tiny_cell(compute_dtype="bfloat16"), 1,
                           time.perf_counter())
    _, checks = _judge(srv)
    assert not checks["first_layer_v_off_share"]["ok"]
    assert checks["first_layer_v_off_share"]["value"] > 10 * TINY_LIMIT[
        "first_layer_v_off_share"]
