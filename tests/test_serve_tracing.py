"""The serving runtime's spans and counters (tier-1, CPU).

``ServeRuntime.step`` runs each phase inside a ``serve.*`` span on the
profiler's clock and times it into ``stats``.  Under a CPU profiler trace
both runtimes must emit the phases nested in ``serve.step`` with their
args, a request's spans must carry its uid, tracing must not change a
served token, the phase counters must fit inside the steps' wall time,
and ``device_syncs`` must count the collects that read the device."""

import glob
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_smoke_config
from repro.kernels import ops
from repro.models.registry import get_model
from repro.serve import ServeRuntime
from repro.serve.paged import PagedServeRuntime
from repro.serve.runtime import PHASES

#: the phases of one step, in order; ``serve.prefill`` nests in admit
STEP_PHASES = ("serve.maintain", "serve.admit", "serve.decode",
               "serve.collect")


@pytest.fixture(scope="module")
def lm():
    cfg = get_smoke_config("qwen1.5-4b")
    params = get_model(cfg).init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _requests(cfg, n=7, seed=0):
    rng = np.random.default_rng(seed)
    return [(f"req{i}", rng.integers(0, cfg.vocab, size=int(
        rng.integers(3, 15))).astype(np.int32), int(rng.integers(2, 7)))
        for i in range(n)]


def _runtime(cfg, params, paged, **kw):
    if paged:
        return PagedServeRuntime(cfg, params, max_slots=4, max_len=24,
                                 page_size=4, **kw)
    return ServeRuntime(cfg, params, max_slots=4, max_len=24, **kw)


def _serve(rt, reqs):
    """Submit, then step until idle; returns (tokens by uid, step walls)."""
    for uid, prompt, new in reqs:
        rt.submit(prompt, max_new_tokens=new, uid=uid)
    out, walls = {}, []
    while not rt.idle:
        t = time.perf_counter()
        for c in rt.step():
            out[c.uid] = c.tokens
        walls.append(time.perf_counter() - t)
    return out, walls


def _spans(trace_dir):
    """The ``serve.*`` host events of the trace: (name, start, end, args)."""
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda s: s[1])


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.fixture(scope="module", params=[False, True], ids=["dense", "paged"])
def traced(request, lm, tmp_path_factory):
    """One run of each runtime under a trace, and the same run untraced."""
    cfg, params = lm
    paged = request.param
    reqs = _requests(cfg)
    rt = _runtime(cfg, params, paged)
    _serve(rt, reqs[:2])                  # compile outside the trace
    rt.reset()
    d = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(d)
    try:
        out, walls = _serve(rt, reqs)
        rt.stream()
    finally:
        jax.profiler.stop_trace()
    stats = rt.stats
    rt.reset()
    plain, _ = _serve(rt, reqs)
    return dict(reqs=reqs, out=out, plain=plain, walls=walls, stats=stats,
                spans=_spans(d))


def test_phases_nest_in_steps_with_their_args(traced):
    spans = traced["spans"]
    steps = [s for s in spans if s[0] == "serve.step"]
    assert len(steps) == len(traced["walls"])
    assert [s[3]["step"] for s in steps] == sorted(s[3]["step"]
                                                  for s in steps)
    for name, *_ in spans:
        assert name in STEP_PHASES + ("serve.step", "serve.prefill",
                                      "serve.submit", "serve.read"), name
    for step in steps:
        inner = [s for s in spans if s is not step and _inside(s, step)]
        names = [s[0] for s in inner if s[0] in STEP_PHASES]
        # a step is maintain, admit, its decode when a lane was live, and
        # its collect, in that order (collects made during admission sit
        # inside serve.admit)
        top = [s for s in inner if s[0] in STEP_PHASES
               and not any(_inside(s, a) for a in inner
                           if a[0] == "serve.admit" and a is not s)]
        expect = ["serve.maintain", "serve.admit"] + (
            ["serve.decode"] if step[3]["live"] else []) + ["serve.collect"]
        assert [s[0] for s in top] == expect, names
        for p in (s for s in inner if s[0] == "serve.prefill"):
            assert any(_inside(p, a) for a in inner
                       if a[0] == "serve.admit")
    assert sum(s[3]["live"] > 0 for s in steps) == traced["stats"][
        "decode_steps"]


def test_a_requests_spans_carry_its_uid(traced):
    spans, reqs = traced["spans"], traced["reqs"]
    submitted = [s[3]["uid"] for s in spans if s[0] == "serve.submit"]
    assert submitted == [u for u, _, _ in reqs]
    prefilled, finished = [], []
    for name, _, _, args in spans:
        if name == "serve.prefill":
            uids = args["uids"].split()
            assert args["group"] >= len(uids) and args["bucket"] >= 1
            prefilled += uids
        elif name == "serve.collect" and args.get("finished"):
            finished += args["finished"].split()
    assert sorted(prefilled) == sorted(submitted)
    assert sorted(finished) == sorted(submitted)
    assert sum(s[0] == "serve.read" for s in spans) == 1


def test_tracing_changes_no_served_token(traced):
    out, plain = traced["out"], traced["plain"]
    assert sorted(out) == sorted(plain) == sorted(u for u, _, _ in
                                                  traced["reqs"])
    for uid in out:
        np.testing.assert_array_equal(out[uid], plain[uid])


def test_phase_counters_fit_in_the_steps_wall_time(traced):
    s, wall = traced["stats"], sum(traced["walls"])
    top = sum(s[p + "_s"] for p in ("maintain", "admit", "decode",
                                    "collect"))
    assert 0 < top <= wall
    assert s["prefill_s"] <= s["admit_s"]
    for p in PHASES:
        assert 0 < s[p + "_max_s"] <= s[p + "_s"], p
    assert s["decode_max_s"] <= max(traced["walls"])


def test_device_syncs_count_the_collects_that_synced(traced):
    spans, s = traced["spans"], traced["stats"]
    synced = [a["synced"] for n, _, _, a in spans if n == "serve.collect"]
    assert set(synced) <= {0, 1} and 0 < sum(synced) < len(synced)
    # every collect that synced, and the one stream() read
    assert s["device_syncs"] == sum(synced) + 1


def test_stream_reads_each_slots_token_and_count(lm):
    cfg, params = lm
    rt = ServeRuntime(cfg, params, max_slots=4, max_len=24)
    for uid, prompt, new in _requests(cfg, n=3, seed=1):
        rt.submit(prompt, max_new_tokens=new, uid=uid)
    rt.step()
    tok, emitted = rt.stream()
    np.testing.assert_array_equal(tok, np.asarray(rt._state.tok))
    np.testing.assert_array_equal(emitted, np.asarray(rt._state.emitted))
    assert list(emitted[:3]) == [2, 2, 2] and emitted[3] == 0
    assert rt.stats["device_syncs"] == 1


@pytest.mark.parametrize("eos,ttft", [(True, False), (False, True)],
                         ids=["eos", "measure_ttft"])
def test_every_blocking_read_is_a_device_sync(lm, tmp_path, eos, ttft):
    """With EOS stopping on every collect reads the device; with
    ``measure_ttft`` each prefill also waits for its first tokens."""
    cfg, params = lm
    rt = ServeRuntime(cfg, params, max_slots=4, max_len=24,
                      eos_id=0 if eos else None, measure_ttft=ttft)
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(rt, _requests(cfg, n=5, seed=2))
    finally:
        jax.profiler.stop_trace()
    s = rt.stats
    synced = [a["synced"] for n, _, _, a in _spans(str(tmp_path))
              if n == "serve.collect"]
    if eos:
        assert all(synced)
    assert s["device_syncs"] == sum(synced) + (
        s["prefill_calls"] if ttft else 0)


def test_attn_block_counters_follow_the_requests(lm, monkeypatch):
    """``attn_blocks_read`` and ``attn_blocks_cached`` equal what each
    request's prompt and budget imply: a request decodes ``max_new - 1``
    steps at fills ``prompt + 1 .. prompt + max_new - 1``.  Counted on the
    host, they add no device read; ``reset()`` zeroes them."""
    cfg, params = lm
    block = 8                             # three chunks in a 24-position cache
    per_pos = 4 * cfg.n_kv_heads * cfg.hd * jnp.dtype(cfg.dtype).itemsize
    monkeypatch.setattr(ops, "FLASH_DECODE_KV_VMEM_BYTES", block * per_pos)
    assert ops.flash_decode_block(24, cfg.n_kv_heads, cfg.hd,
                                  cfg.dtype) == block
    reqs = _requests(cfg, seed=3)
    read = sum(-(-(prompt.size + i) // block)
               for _, prompt, new in reqs for i in range(1, new))
    cached = sum(new - 1 for _, _, new in reqs) * (24 // block)
    assert read < cached

    stats = {}
    for backend in ("stream", "flash"):
        rt = ServeRuntime(cfg, params, max_slots=4, max_len=24,
                          attn_backend=backend)
        _serve(rt, reqs)
        stats[backend] = rt.stats
    assert stats["flash"]["attn_blocks_read"] == read
    assert stats["flash"]["attn_blocks_cached"] == cached
    assert stats["stream"]["attn_blocks_read"] == 0
    assert stats["stream"]["attn_blocks_cached"] == 0
    assert stats["flash"]["device_syncs"] == stats["stream"]["device_syncs"]
    rt.reset()
    assert rt.stats["attn_blocks_read"] == rt.stats["attn_blocks_cached"] == 0
