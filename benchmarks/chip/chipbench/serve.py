"""Set-up and measured window of a served cell (closed-loop traffic).

One process, one chip: the weights are made from the seed on the
device, programmed and calibrated through the program's own entry
points, the runtime's programs for this traffic's prefill buckets and
group sizes and its decode step are run once (that is set-up), and then
``clients`` closed-loop clients drive ``ServeRuntime.step()`` for the
window.  After every step the harness reads each slot's tokens off the
device, as a streaming server must, and stamps them on the host clock.
"""

from __future__ import annotations

import dataclasses
import gc
import os
import shutil
import tempfile
import time
from typing import Any, Dict, List, Optional

import numpy as np

from chipbench import model as M
from chipbench import traffic as T
from chipbench import trace as TR

#: seconds of the window that a ``--trace 1`` run records
TRACE_SECONDS = 10.0
#: the monitoring event JAX records for each backend compilation
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: the fields of an ``AnalogPack`` that ``calibrate_lm`` sets
CALIBRATED = ("layer_lo", "layer_hi", "layer_act", "head_lo", "head_hi",
              "head_act")


@dataclasses.dataclass
class Req:
    uid: str
    client: int
    prompt: np.ndarray
    max_new: int
    submit: float
    token_times: List[float] = dataclasses.field(default_factory=list)
    tokens: Optional[np.ndarray] = None


@dataclasses.dataclass
class Step:
    index: int
    start: float
    end: float
    fills: List[int]                 # KV fill of every lane decoded
    prefills: List[tuple]            # (bucket, group, [true prompt lengths])


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read about one run."""

    cell: Any
    seed: int
    seconds: float
    setup: Dict[str, float]
    reqs: Dict[str, Req]
    steps: List[Step]
    window: tuple                    # host clock (start, end)
    slots: int
    peaks: Dict[str, Any]
    trace: Optional[TR.Trace] = None
    plane: str = ""
    counters: Dict[str, Any] = dataclasses.field(default_factory=dict)
    cached: List[Dict[str, Any]] = dataclasses.field(default_factory=list)

    @property
    def config(self):
        return self.cell.config


def _bucket(buckets, n):
    return next(b for b in sorted(buckets) if n <= b)


def _pow2(n):
    p = 1
    while p < n:
        p *= 2
    return p


class Server:
    """The program under test, set up for one cell."""

    def __init__(self, cell, seed: int, t_start: float):
        import jax
        import jax.numpy as jnp

        from repro.kernels import fused as kfused
        from repro.serve import ServeRuntime, calibrate_lm, program_lm

        self.cell, self.seed = cell, seed
        c, tf = cell.config, cell.traffic
        if tf["loop"] != "closed" or tf["sampler"] != "greedy":
            raise ValueError(f"{cell.name}: this harness runs closed-loop "
                             f"greedy traffic, got {tf['loop']!r} / "
                             f"{tf['sampler']!r}")
        self.cfg = M.model_config(c)
        spec = M.analog_spec(c["analog"])
        cfg = self.cfg
        self.k_weights = jax.random.PRNGKey(T.jax_seed(seed, 10))
        self.k_prog = jax.random.PRNGKey(T.jax_seed(seed, 11))
        self.calib = T.calibration_tokens(tf, cfg.vocab, seed)
        s = {}

        t = time.perf_counter()
        self.params = M.make_params(c, self.k_weights)
        jax.block_until_ready(self.params)
        s["init_s"] = time.perf_counter() - t

        t = time.perf_counter()
        pack = jax.jit(lambda p, k: program_lm(cfg, p, spec, k))(
            self.params, self.k_prog)
        jax.block_until_ready(pack)
        s["program_s"] = time.perf_counter() - t

        # calibrate_lm under jit, returning only what it calibrates: a
        # jitted call that returned the whole pack would copy every
        # conductance into a second pack
        def ranges(p, pk, toks):
            out = calibrate_lm(cfg, p, pk, toks)
            return {f: getattr(out, f) for f in CALIBRATED}

        t = time.perf_counter()
        got = jax.jit(ranges)(self.params, pack, jnp.asarray(self.calib))
        jax.block_until_ready(got)
        pack = dataclasses.replace(pack, collect=False, **got)
        s["calibrate_s"] = time.perf_counter() - t

        t = time.perf_counter()
        kfused.BUILD_SIGNATURES.clear()
        self.rt = ServeRuntime(
            cfg, self.params, pack=pack, max_slots=int(tf["slots"]),
            max_len=int(tf["max_len"]), buckets=tuple(tf["buckets"]),
            attn_backend=c["decode_attention"])
        self._warm()
        s["warm_s"] = time.perf_counter() - t
        self.built = set(kfused.BUILD_SIGNATURES)
        self.expected = self._expected_site_classes(pack)
        self.setup = s
        # what set-up made lives as long as the run: keep the collector
        # from walking it again during the window
        gc.collect()
        gc.freeze()
        self.setup["setup_s"] = time.perf_counter() - t_start

    def _expected_site_classes(self, pack):
        from repro.hw import fused_site_classes
        from repro.serve.analog_engine import HEAD, lm_hook_names

        return set(fused_site_classes(
            pack.profile, lm_hook_names(self.cfg) + [HEAD],
            self.cfg.n_layers))

    def _warm(self):
        """Run each (bucket, group) prefill and the decode step once."""
        import jax

        rt = self.rt
        for bucket, group in T.warm_shapes(self.cell.traffic):
            for j in range(group):
                rt.submit(np.zeros(bucket, np.int32), max_new_tokens=2,
                          uid=f"warm{bucket}.{group}.{j}")
            rt.step()
            jax.device_get((rt._state.tok, rt._state.emitted))
            rt.reset()

    def free(self):
        """Drop the runtime and its pack (the weights stay)."""
        import jax

        pack = self.rt.pack
        self.rt = None
        for leaf in jax.tree_util.tree_leaves(pack):
            if hasattr(leaf, "delete"):
                leaf.delete()


def serve_window(server: Server, seconds: float, trace: bool,
                 keep: str = "") -> Run:
    """Drive the closed loop for ``seconds``; returns the record."""
    import jax

    cell, rt = server.cell, server.rt
    tf = cell.traffic
    loop = T.ClosedLoop(tf, server.cfg.vocab, server.seed)
    buckets = tf["buckets"]
    reqs: Dict[str, Req] = {}
    live: Dict[str, Req] = {}
    steps: List[Step] = []
    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") if trace else None
    tracing = False
    stats0 = dict(rt.stats)

    def submit(r: T.Request, now: float):
        q = Req(r.uid, r.client, r.prompt, r.max_new, now)
        reqs[r.uid] = live[r.uid] = q
        rt.submit(r.prompt, max_new_tokens=r.max_new, uid=r.uid)

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(event)
        if event == COMPILE_EVENT else None)
    t0 = time.perf_counter()
    t_end = t0 + seconds
    t_trace_end = t0 + min(seconds, TRACE_SECONDS)
    if trace:
        jax.profiler.start_trace(trace_dir)
        tracing = True
        win = jax.profiler.TraceAnnotation("bench.window")
        win.__enter__()
    for r in loop.first():
        submit(r, t0)
    seen: Dict[str, int] = {}
    i = 0
    while True:
        before = {p.uid for p in rt._slots if p is not None}
        ts = time.perf_counter()
        if tracing:
            with jax.profiler.TraceAnnotation(f"bench.step.{i}"):
                done = rt.step()
                with jax.profiler.TraceAnnotation("bench.read"):
                    tok, emitted = jax.device_get((rt._state.tok,
                                                   rt._state.emitted))
        else:
            done = rt.step()
            tok, emitted = jax.device_get((rt._state.tok, rt._state.emitted))
        te = time.perf_counter()
        counts = {}
        for slot, p in enumerate(rt._slots):
            if p is not None:
                counts[p.uid] = int(emitted[slot])
        for c in done:
            counts[c.uid] = int(c.tokens.size)
            live[c.uid].tokens = np.asarray(c.tokens)
        admitted = [u for u in counts if u not in before]
        groups: Dict[int, List[int]] = {}
        for u in admitted:
            n = reqs[u].prompt.size
            groups.setdefault(_bucket(buckets, n), []).append(n)
        prefills = [(b, min(_pow2(len(v)), int(tf["slots"])), v)
                    for b, v in sorted(groups.items())]
        fills = [reqs[u].prompt.size + n - 1 for u, n in counts.items()]
        steps.append(Step(i, ts, te, fills, prefills))
        for u, n in counts.items():
            q = reqs[u]
            q.token_times.extend([te] * (n - seen.get(u, 0)))
            seen[u] = n
        for c in done:
            q = live.pop(c.uid)
            if te < t_end:
                submit(loop.next(q.client), te)
        i += 1
        if tracing and te >= t_trace_end:
            win.__exit__(None, None, None)
            jax.profiler.stop_trace()
            tracing = False
        if te >= t_end:
            break
    t1 = time.perf_counter()
    stats1 = dict(rt.stats)
    run = Run(cell=cell, seed=server.seed, seconds=seconds,
              setup=dict(server.setup), reqs=reqs, steps=steps,
              window=(t0, t1), slots=int(tf["slots"]), peaks={})
    run.counters = {
        "prefill_calls": stats1["prefill_calls"] - stats0["prefill_calls"],
        "decode_steps": stats1["decode_steps"] - stats0["decode_steps"],
        "tokens_out": stats1["tokens_out"] - stats0["tokens_out"],
        "window_prefill_groups": sum(len(s.prefills) for s in steps),
        "window_compiles": len(compiles),
        "longest_step_s": max(s.end - s.start for s in steps),
    }
    run.cached = cached_values(rt, reqs)
    if trace:
        xplane = TR.find_xplane(trace_dir)
        if keep:
            os.makedirs(keep, exist_ok=True)
            shutil.copy(xplane, keep)
        run.trace = TR.load(xplane)
        shutil.rmtree(trace_dir, ignore_errors=True)
        planes = sorted(run.trace.ops)
        run.plane = planes[0] if planes else ""
    return run


def cached_values(rt, reqs: Dict[str, Req]) -> List[Dict[str, Any]]:
    """The first layer's cached attention values of every slot still
    serving when the window closed, and the tokens they were computed
    from: the prompt (written by the admitting prefill) and every served
    token but the newest (each written by the decode step that read it).
    """
    import jax

    st = rt._state
    v0, length, out = jax.device_get(
        (st.layers["attn"]["v"][0], st.length, st.out))
    rows = []
    for slot, p in enumerate(rt._slots):
        if p is None:
            continue
        prompt = reqs[p.uid].prompt
        n = int(length[slot])
        seq = np.concatenate([prompt, out[slot, :n - prompt.size]])
        rows.append({"uid": p.uid, "seq": seq.astype(np.int32),
                     "prompt": int(prompt.size),
                     "v": np.asarray(v0[slot, :n], np.float32)})
    return rows
