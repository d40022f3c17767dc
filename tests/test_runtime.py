"""Continuous-batching runtime tests (tier-1, no training).

The serving contract: scheduling must never change what the model says.
Variable-length prompts drained through the slot-scheduled runtime must
match per-request ``decode_lm`` token-for-token under greedy decoding —
digital and through an analog pack — and sampled decoding must be a
pure function of the per-request key (stable uid hash, never admission
order), mirroring how programming keys fold from stable hook-name
hashes (``tests/test_serve_engine.py``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import analog as A
from repro.core import errors as E
from repro.models import transformer
from repro.models.registry import get_model
from repro.serve import (
    SamplerConfig,
    ServeRuntime,
    calibrate_lm,
    decode_lm,
    program_lm,
)
from repro.sweep.serve_eval import runtime_agreement


@pytest.fixture(scope="module")
def lm():
    cfg = get_smoke_config("qwen1.5-4b")
    params = get_model(cfg).init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _trace(cfg, n, seed=0, lens=(3, 15), new=(2, 9)):
    rng = np.random.default_rng(seed)
    return [
        (rng.integers(0, cfg.vocab, size=int(rng.integers(*lens)))
         .astype(np.int32),
         int(rng.integers(*new)))
        for _ in range(n)
    ]


# ---------------------------------------------------------------------------
# ragged serving: runtime == per-request decode_lm, token for token
# ---------------------------------------------------------------------------


def test_ragged_greedy_matches_decode_lm(lm):
    cfg, params = lm
    agree = runtime_agreement(cfg, params, _trace(cfg, 9),
                              max_slots=4, max_len=32, seed=0)
    assert agree == 1.0


def test_ragged_greedy_matches_decode_lm_through_analog_pack(lm):
    cfg, params = lm
    from repro.data.synthetic import SyntheticLM

    ds = SyntheticLM(cfg=cfg, seq_len=16, global_batch=4, seed=0)
    pack = program_lm(cfg, params, A.design_a(error=E.state_independent(0.05)),
                      jax.random.PRNGKey(5))
    pack = calibrate_lm(cfg, params, pack, ds.batch(1)["tokens"])
    # few distinct (prompt_len, n_new) shapes to bound eager reference cost
    reqs = _trace(cfg, 5, lens=(4, 6), new=(4, 6))
    assert runtime_agreement(cfg, params, reqs, pack=pack,
                             max_slots=2, max_len=24) == 1.0


def test_gang_mode_serves_identically(lm):
    """Static (gang) scheduling is a policy change, not a model change."""
    cfg, params = lm
    reqs = _trace(cfg, 6, seed=3)
    outs = {}
    for gang in (False, True):
        rt = ServeRuntime(cfg, params, max_slots=3, max_len=32, gang=gang)
        uids = [rt.submit(p, max_new_tokens=n, uid=i)
                for i, (p, n) in enumerate(reqs)]
        outs[gang] = rt.run()
        assert sorted(outs[gang]) == sorted(uids)
    for uid in outs[False]:
        np.testing.assert_array_equal(outs[False][uid], outs[True][uid])


def _closed_loop(rt, waves):
    """Serve ``len(waves[0])`` closed-loop clients: client ``c`` sends
    ``waves[k][c]`` once its ``k-1``-th request completes.  Returns the
    outputs and how many steps admitted (ran a prefill)."""
    out, nxt, admitting = {}, {}, 0
    for c, (p, n) in enumerate(waves[0]):
        nxt[rt.submit(p, max_new_tokens=n, uid=(0, c))] = 1
    while not rt.idle:
        calls = rt.stats["prefill_calls"]
        for done in rt.step():
            out[done.uid] = done.tokens
            k, c = nxt.pop(done.uid), done.uid[1]
            if k < len(waves):
                p, n = waves[k][c]
                nxt[rt.submit(p, max_new_tokens=n, uid=(k, c))] = k + 1
        admitting += rt.stats["prefill_calls"] > calls
    return out, admitting


def test_coalesced_admissions_stall_fewer_steps_and_serve_identically(lm):
    """Lanes retiring two steps apart: with coalescing, each retiring
    pair's successors share one admitting step (one prefill group, as
    their prompts share a bucket); the tokens are the same."""
    cfg, params = lm
    rng = np.random.default_rng(7)

    def req(n):
        return rng.integers(0, cfg.vocab, size=5).astype(np.int32), n

    waves = [[req(n) for n in (4, 6, 12, 14)],
             [req(n) for n in (5, 5, 5, 5)]]
    got = {}
    for w in (0, 4):
        rt = ServeRuntime(cfg, params, max_slots=4, max_len=32,
                          coalesce_steps=w)
        got[w] = _closed_loop(rt, waves)
    assert sorted(got[0][0]) == sorted(got[4][0])
    for uid, toks in got[0][0].items():
        np.testing.assert_array_equal(toks, got[4][0][uid])
    assert got[0][1] == 5 and got[4][1] == 3


@pytest.mark.parametrize("budgets,held", [((3, 30), 1), ((9, 30), 0),
                                          ((2, 4, 6, 30), 4)],
                         ids=["until-retired", "too-far", "at-most-w"])
def test_coalescing_holds_a_lone_request_at_most_coalesce_steps(
        lm, budgets, held):
    """A lone request with a free slot waits for a lane retiring within
    ``coalesce_steps`` (4) steps, never longer than that, and not at all
    when none retires that soon."""
    cfg, params = lm
    rt = ServeRuntime(cfg, params, max_slots=len(budgets) + 1, max_len=48,
                      coalesce_steps=4)
    for i, n in enumerate(budgets):
        rt.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=n, uid=i)
    rt.step()
    rt.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=2, uid="x")
    steps = 0
    while rt.stats["prefill_calls"] == 1 and steps < 40:
        rt.step()
        steps += 1
    assert steps - 1 == held           # steps held before the admitting one


def test_coalescing_waits_for_nothing_on_an_idle_server(lm):
    cfg, params = lm
    rt = ServeRuntime(cfg, params, max_slots=4, max_len=32)
    assert rt.coalesce_steps > 0
    rt.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=3, uid=0)
    rt.step()
    assert rt.stats["prefill_calls"] == 1
    with pytest.raises(ValueError, match="coalesce_steps"):
        ServeRuntime(cfg, params, max_slots=2, max_len=16, coalesce_steps=-1)


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------


def test_sampled_streams_invariant_to_admission_order(lm):
    """Per-slot keys fold from the request uid, so a request's sampled
    continuation must not depend on queue position or slot assignment."""
    cfg, params = lm
    reqs = _trace(cfg, 6, seed=1)
    sampler = SamplerConfig(kind="temperature", temperature=0.8)
    outs = []
    for order in (lambda x: x, reversed):
        rt = ServeRuntime(cfg, params, max_slots=3, max_len=32,
                          sampler=sampler, seed=11)
        for i, (p, n) in order(list(enumerate(reqs))):
            rt.submit(p, max_new_tokens=n, uid=i)
        outs.append(rt.run())
    for uid in outs[0]:
        np.testing.assert_array_equal(outs[0][uid], outs[1][uid])


def test_sampled_streams_depend_on_seed(lm):
    cfg, params = lm
    reqs = _trace(cfg, 3, seed=2, new=(8, 9))
    runs = []
    for seed in (0, 1):
        rt = ServeRuntime(cfg, params, max_slots=2, max_len=32,
                          sampler=SamplerConfig(kind="top_k", top_k=16),
                          seed=seed)
        for i, (p, n) in enumerate(reqs):
            rt.submit(p, max_new_tokens=n, uid=i)
        runs.append(rt.run())
    assert any(not np.array_equal(runs[0][u], runs[1][u]) for u in runs[0])


def test_greedy_ignores_sampling_seed(lm):
    cfg, params = lm
    reqs = _trace(cfg, 3, seed=4)
    runs = []
    for seed in (0, 123):
        rt = ServeRuntime(cfg, params, max_slots=2, max_len=32, seed=seed)
        for i, (p, n) in enumerate(reqs):
            rt.submit(p, max_new_tokens=n, uid=i)
        runs.append(rt.run())
    for uid in runs[0]:
        np.testing.assert_array_equal(runs[0][uid], runs[1][uid])


def test_eos_stops_early(lm):
    cfg, params = lm
    prompt = np.arange(5, dtype=np.int32) % cfg.vocab
    ref = np.asarray(decode_lm(cfg, params, jnp.asarray(prompt)[None], 6))[0]
    eos = int(ref[2])                   # greedy emits this 3rd
    rt = ServeRuntime(cfg, params, max_slots=2, max_len=16, eos_id=eos)
    uid = rt.submit(prompt, max_new_tokens=6)
    out = rt.run()[uid]
    np.testing.assert_array_equal(out, ref[:3])   # EOS emitted, then stop


# ---------------------------------------------------------------------------
# self-healing: mid-stream reprogramming must not touch in-flight requests
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def noop_aging_manager(lm):
    """PackManager whose aging is *enabled but numerically inert*:
    ``nu = 0`` makes the drift factor exactly 1.0 while ``aging_on``
    stays True, so heal events really reprogram bands — and with
    ``error=none`` programming is deterministic, so every rewrite is
    bit-identical.  The healed runtime must therefore serve exactly
    what the unhealed one serves."""
    from repro.data.synthetic import SyntheticLM
    from repro.serve import PackManager

    cfg, params = lm
    calib = SyntheticLM(cfg=cfg, seq_len=16, global_batch=4,
                        seed=0).batch(1)["tokens"]
    return lambda: PackManager(
        cfg, params, A.design_a(error=E.none(),
                                drift=E.power_law_drift(0.0)),
        jax.random.PRNGKey(5), calib_tokens=calib)


#: forces a heal on every health probe (threshold below any real loss)
FORCE_HEAL = dict(check_every=1, loss_mult=0.0, loss_add=-1.0)


def test_mid_stream_reprogram_preserves_tokens(lm, noop_aging_manager):
    """Requests admitted before, during, and after heal events complete
    with tokens identical to an unhealed same-seed run when drift is a
    no-op: the background reprogram path swaps packs between decode
    steps without perturbing any in-flight slot."""
    from repro.serve import HealPolicy

    cfg, params = lm
    reqs = _trace(cfg, 6, seed=5, lens=(4, 6), new=(4, 8))
    outs = []
    for heal in (None, HealPolicy(**FORCE_HEAL, bands_per_step=1)):
        rt = ServeRuntime(cfg, params, manager=noop_aging_manager(),
                          max_slots=2, max_len=24, heal=heal)
        for i, (p, n) in enumerate(reqs):
            rt.submit(p, max_new_tokens=n, uid=i)
        outs.append(rt.run())
        if heal is not None:
            s = rt.stats
            assert s["heal_events"] >= 1        # healing really happened
            assert s["bands_reprogrammed"] >= 2
            assert s["recalibrations"] >= 1
    for uid in outs[0]:
        np.testing.assert_array_equal(outs[0][uid], outs[1][uid])


def test_eos_during_reprogram_race(lm, noop_aging_manager):
    """A request whose EOS fires while the heal queue is mid-drain
    (``bands_per_step=1`` spreads one heal event over several scheduler
    steps) must retire exactly at the EOS token, and a request submitted
    during the drain must serve correctly afterwards."""
    from repro.serve import HealPolicy, decode_lm

    cfg, params = lm
    m = noop_aging_manager()
    prompt = np.arange(5, dtype=np.int32) % cfg.vocab
    ref = np.asarray(decode_lm(cfg, params, jnp.asarray(prompt)[None], 8,
                               pack=m.fresh_pack))[0]
    # stop on a token greedy emits at position >= 3 and nowhere earlier:
    # the first probe fires after decode step 1 and drains one band per
    # step after, so retirement at decode step j >= 3 lands mid-drain
    j = next(i for i in range(3, 8) if ref[i] not in ref[:i])
    eos = int(ref[j])
    rt = ServeRuntime(cfg, params, manager=m, max_slots=2, max_len=16,
                      eos_id=eos, heal=HealPolicy(**FORCE_HEAL,
                                                  bands_per_step=1))
    uid = rt.submit(prompt, max_new_tokens=8)
    # step until the EOS request retires; every step is also draining /
    # re-queueing heal targets, so the retirement races a reprogram
    done = {}
    for _ in range(64):
        for c in rt.step():
            done[c.uid] = c.tokens
        if uid in done:
            break
    np.testing.assert_array_equal(done[uid], ref[:j + 1])
    assert rt.stats["bands_reprogrammed"] >= 1   # reprogram raced the EOS
    # a late request admitted into the still-healing server serves fine
    uid2 = rt.submit(prompt, max_new_tokens=2)
    out2 = rt.run()
    np.testing.assert_array_equal(out2[uid2], ref[:2])
    assert not rt._heal_queue            # run() drains leftover healing


# ---------------------------------------------------------------------------
# slot cache insert / evict
# ---------------------------------------------------------------------------


def _captured_consts(closed):
    """Every array a jaxpr (and its sub-jaxprs) closes over."""
    import jax.extend.core as jex_core

    out = list(closed.consts)
    for eqn in closed.jaxpr.eqns:
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                if isinstance(sub, jex_core.ClosedJaxpr):
                    out.extend(_captured_consts(sub))
    return out


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_jitted_steps_take_weights_as_arguments(lm, paged):
    """Weights reach the jitted decode step as a traced argument: arrays
    a jitted function closes over become constants of every compiled
    program, which at published widths copies gigabytes of weights into
    each executable."""
    from repro.serve import PagedServeRuntime

    cfg, params = lm
    kw = dict(page_size=4, num_pages=17) if paged else {}
    rt = (PagedServeRuntime if paged else ServeRuntime)(
        cfg, params, max_slots=2, max_len=16, **kw)
    rt.submit(np.arange(5, dtype=np.int32), max_new_tokens=3, uid=0)
    rt.run()
    extra = (jnp.asarray(rt._ptab),) if paged else ()
    closed = jax.make_jaxpr(rt._decode_fn)(rt._state, rt.params, rt.pack,
                                           *extra)
    smallest = min(a.size for a in jax.tree.leaves(params))
    consts = _captured_consts(closed)
    assert all(np.size(c) < smallest for c in consts), \
        [np.shape(c) for c in consts]


# ---------------------------------------------------------------------------
# sites whose layer stacks the fused kernel reads in place
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def mixed_stack_lm():
    """A smoke LM at lane-wide widths whose fused sites mix the two kinds:
    ``max_rows=100`` splits K = 128 into 2 partitions of 64 rows (a
    multiple of 8, so the kernel reads those stacks in place) and
    K = 256 (``w_down``) into 3 of 86 (not, so it is sliced per layer)."""
    cfg = dataclasses.replace(
        get_smoke_config("qwen1.5-4b"), d_model=128, n_heads=2,
        n_kv_heads=2, d_ff=256, vocab=128, n_layers=2)
    params = get_model(cfg).init_params(cfg, jax.random.PRNGKey(0))
    spec = dataclasses.replace(
        A.design_a(error=E.state_proportional(0.02), fused="kernel"),
        max_rows=100)
    pack = program_lm(cfg, params, spec, jax.random.PRNGKey(5))
    pack = dataclasses.replace(calibrate_lm(
        cfg, params, pack, jnp.arange(64).reshape(2, 32) % cfg.vocab),
        collect=False)
    return cfg, params, spec, pack


def test_mixed_stack_lm_mixes_stacked_and_sliced_sites(mixed_stack_lm):
    """The layer scan hands over whole the stacks the kernel reads as
    stored, and no stack of an oracle pack or of calibration."""
    from repro.sweep.serve_eval import pack_with_fused

    _, _, _, pack = mixed_stack_lm
    assert set(transformer._stacked_sites(pack)) == {
        "wq", "wk", "wv", "wo", "w_gate", "w_up"}
    assert transformer._stacked_sites(pack_with_fused(pack, "oracle")) == {}
    assert transformer._stacked_sites(
        dataclasses.replace(pack, collect=True)) == {}


def _kernel_and_oracle_tokens(cfg, params, pack, paged):
    from repro.serve import PagedServeRuntime
    from repro.sweep.serve_eval import pack_with_fused

    reqs = _trace(cfg, 5, seed=3, lens=(4, 9), new=(3, 6))
    outs = []
    for mode in ("kernel", "oracle"):
        kw = dict(pack=pack_with_fused(pack, mode), max_slots=2, max_len=16)
        rt = (PagedServeRuntime(cfg, params, page_size=4, **kw) if paged
              else ServeRuntime(cfg, params, **kw))
        for i, (p, n) in enumerate(reqs):
            rt.submit(p, max_new_tokens=n, uid=f"r{i}")
        outs.append(rt.run())
    return outs


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_stacked_conductances_change_no_token(mixed_stack_lm, paged):
    """Serving a pack whose stacks the kernel reads by the layer's index
    says, token for token, what the same pack says at ``fused="oracle"``,
    which slices every layer out of the stack, through ``ServeRuntime``
    and through ``PagedServeRuntime``, which shares the layer scan."""
    cfg, params, _, pack = mixed_stack_lm
    kernel, oracle = _kernel_and_oracle_tokens(cfg, params, pack, paged)
    assert sorted(kernel) == sorted(oracle)
    for uid in oracle:
        np.testing.assert_array_equal(kernel[uid], oracle[uid])


def test_stacked_conductances_of_a_banded_pack_change_no_token(
        mixed_stack_lm):
    """A pack of two layer bands (8- and 6-bit ADCs) scans each band
    separately; a stacked site stays whole across both, indexed by its
    absolute layer, and serves what the oracle serves."""
    from repro.hw import Profile, Rule

    cfg, params, spec, _ = mixed_stack_lm
    spec6 = dataclasses.replace(
        spec, adc=dataclasses.replace(spec.adc, bits=6))
    profile = Profile(rules=(Rule("*", spec, layers=(0, 1)),
                             Rule("*", spec6, layers=(1, 2))), default=spec)
    pack = program_lm(cfg, params, profile, jax.random.PRNGKey(5))
    pack = dataclasses.replace(calibrate_lm(
        cfg, params, pack, jnp.arange(64).reshape(2, 32) % cfg.vocab),
        collect=False)
    assert len(pack.bands) == 2
    assert "wq" in transformer._stacked_sites(pack)
    kernel, oracle = _kernel_and_oracle_tokens(cfg, params, pack, False)
    for uid in oracle:
        np.testing.assert_array_equal(kernel[uid], oracle[uid])


def test_sweep_point_with_stacked_sites_matches_the_oracle(mixed_stack_lm):
    """One trial-vmapped ``ServeEvaluator`` point: under ``vmap`` each
    stacked call becomes the per-layer call Pallas batches, and the
    point's metrics are the oracle's (its greedy decode token for token,
    its loss to float32 rounding)."""
    from repro.sweep import ServeEvaluator

    cfg, params, spec, _ = mixed_stack_lm
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 16), 0, cfg.vocab)
    ev = ServeEvaluator(cfg, params, toks, toks, toks, prompts=toks[:, :4],
                        decode_new=3, include_head=False)
    got = {mode: ev.evaluate_group(dataclasses.replace(spec, fused=mode),
                                   ("error.alpha",), [(0.02,)], 2, 0, None)
           for mode in ("kernel", "oracle")}
    for k_trial, o_trial in zip(got["kernel"][0], got["oracle"][0]):
        assert k_trial["decode_match"] == o_trial["decode_match"]
        assert k_trial["top1"] == o_trial["top1"]
        np.testing.assert_allclose(k_trial["loss"], o_trial["loss"],
                                   rtol=1e-6)


def test_cache_slot_insert_and_evict(lm):
    cfg, params = lm
    max_slots, max_len = 3, 24
    cache0 = transformer.init_cache(cfg, max_slots, max_len)
    slot = {"layers": cache0["layers"],
            "len": jnp.zeros((max_slots,), jnp.int32)}
    prompts = jnp.arange(2 * 8, dtype=jnp.int32).reshape(2, 8) % cfg.vocab
    lens = jnp.asarray([8, 5], jnp.int32)
    _, pcache = transformer.prefill_ragged(cfg, params, prompts,
                                           true_lens=lens)
    # row 0 -> slot 2, row 1 -> dummy (dropped)
    ins = transformer.cache_slot_insert(slot, pcache,
                                        jnp.asarray([2, max_slots]))
    assert ins["len"].tolist() == [0, 0, 8]
    k_ins = np.asarray(ins["layers"]["attn"]["k"])
    k_new = np.asarray(pcache["layers"]["attn"]["k"])
    np.testing.assert_array_equal(k_ins[:, 2, :8], k_new[:, 0])
    assert not k_ins[:, :2].any()                 # other slots untouched
    ev = transformer.cache_slot_evict(ins, jnp.asarray([2]))
    assert ev["len"].tolist() == [0, 0, 0]
    assert not np.asarray(ev["layers"]["attn"]["k"]).any()


def test_per_slot_decode_writes_each_rows_tokens_at_its_fill(lm):
    """A decode over per-slot fills writes row ``b``'s tokens at positions
    ``len[b] ..`` of its own cache rows and nowhere else; a position past
    the cache's end is dropped."""
    cfg, params = lm
    c0 = transformer.init_cache(cfg, 3, 12)
    cache = {"layers": jax.tree.map(lambda a: a + 0.5, c0["layers"]),
             "len": jnp.asarray([0, 4, 11], jnp.int32)}
    toks = jnp.asarray([[3, 5], [7, 9], [11, 13]], jnp.int32)
    _, out = transformer.decode_step(cfg, params, toks, cache)
    for name in ("k", "v"):
        new = np.asarray(out["layers"]["attn"][name])
        old = np.asarray(cache["layers"]["attn"][name])
        changed = [np.flatnonzero((new[:, r] != old[:, r]).any(axis=(0, 2, 3)))
                   .tolist() for r in range(3)]
        assert changed == [[0, 1], [4, 5], [11]], (name, changed)


def test_prefill_ragged_matches_exact_prefill(lm):
    cfg, params = lm
    tokens = (jnp.arange(6, dtype=jnp.int32) % cfg.vocab)[None, :]
    ref, _ = transformer.prefill(cfg, params, tokens, 8)
    padded = jnp.pad(tokens, ((0, 0), (0, 4)))
    got, cache = transformer.prefill_ragged(cfg, params, padded,
                                            true_lens=jnp.asarray([6]))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    assert cache["len"].tolist() == [6]


# ---------------------------------------------------------------------------
# user-facing errors
# ---------------------------------------------------------------------------


def test_decode_lm_family_error():
    cfg = get_smoke_config("whisper-large-v3")
    with pytest.raises(ValueError, match="audio.*no batched decode"):
        decode_lm(cfg, {}, jnp.zeros((1, 4), jnp.int32), 2)


def test_runtime_rejects_rwkv():
    cfg = get_smoke_config("rwkv6-3b")
    with pytest.raises(ValueError, match="rwkv"):
        ServeRuntime(cfg, {}, max_slots=2, max_len=16)


def test_runtime_rejects_moe():
    """Capacity routing couples co-batched rows — the scheduling-
    never-changes-outputs contract cannot hold for MoE configs."""
    cfg = get_smoke_config("arctic-480b")
    with pytest.raises(ValueError, match="MoE"):
        ServeRuntime(cfg, {}, max_slots=2, max_len=16)


def test_greedy_decode_rejects_bad_n_new(lm):
    cfg, params = lm
    with pytest.raises(ValueError, match="n_new >= 1"):
        decode_lm(cfg, params, jnp.zeros((1, 4), jnp.int32), 0)


def test_submit_validation(lm):
    cfg, params = lm
    rt = ServeRuntime(cfg, params, max_slots=2, max_len=16, buckets=(8,))
    with pytest.raises(ValueError, match="largest bucket"):
        rt.submit(np.zeros(9, np.int32), max_new_tokens=2)
    with pytest.raises(ValueError, match="max_new_tokens"):
        rt.submit(np.zeros(4, np.int32), max_new_tokens=0)
    with pytest.raises(ValueError, match="KV capacity"):
        rt.submit(np.zeros(8, np.int32), max_new_tokens=12)
    with pytest.raises(ValueError, match="empty prompt"):
        rt.submit(np.zeros(0, np.int32), max_new_tokens=2)
    rt.submit(np.zeros(4, np.int32), max_new_tokens=2, uid=7)
    with pytest.raises(ValueError, match="already in flight"):
        rt.submit(np.zeros(4, np.int32), max_new_tokens=2, uid="7")


def test_sampler_config_validation():
    with pytest.raises(ValueError, match="unknown sampler"):
        SamplerConfig(kind="nucleus")
    with pytest.raises(ValueError, match="temperature"):
        SamplerConfig(kind="temperature", temperature=0.0)
    with pytest.raises(ValueError, match="top_k"):
        SamplerConfig(kind="top_k", top_k=0)
