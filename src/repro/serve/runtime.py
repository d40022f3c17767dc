"""Continuous-batching serving runtime over the programmed analog LM.

The sweep layer measures analog accuracy in a one-shot, equal-length,
greedy configuration (``decode_lm``).  This module is the *request-level*
serving system on top of the same substrate: a slot-based scheduler in
the style of iteration-level ("continuous") batching — ORCA / vLLM-class
scheduling, minus paged KV — where

* a fixed ``max_slots`` decode batch runs as ONE jitted step over the
  whole slot state (no per-request Python dispatch in steady state);
* requests with variable-length prompts queue up and are admitted into
  free slots via a *bucketed ragged prefill*
  (``transformer.prefill_ragged`` + ``cache_slot_insert``), so compile
  groups stay bounded: one program per (prompt bucket, admission-group
  size), both rounded to powers of two;
* each slot carries its own KV fill (``SlotState.length`` — the per-row
  ``cache["len"]`` vector the model layer understands), its own stop
  condition (EOS / ``max_new_tokens``), and its own sampling PRNG key;
* every matmul serves through the :class:`AnalogPack` when one is given
  — programming, calibration, decode and sampling all ride the same
  analog config, with ``r_hat`` / ``error.alpha`` carried in the pack's
  per-site specs, so a running server is a valid design point of the
  sweeps.  Heterogeneous packs (``repro.hw.Profile``: mixed per-site
  ADC precision, digital head, layer bands) serve unchanged — the pack
  carries its own site resolution, and the agreement contract below
  holds per site spec (pinned by ``tests/test_profile.py``).

Sampling keys compose with programming keys the same way hook keys do
(``serve.analog_engine.hook_key``): a request's stream key is folded
from a *stable hash of its uid* (:func:`request_key`), never from an
admission counter, so a request's sampled continuation is invariant to
queue position, slot assignment, and whatever else is being served.

The scheduler loop (one :meth:`ServeRuntime.step`):

1. **admit** — pop waiting requests into free slots; one ragged-prefill
   call per prompt bucket writes their K/V rows, first sampled token,
   fill lengths and keys into the slot state.  Every prefill stalls each
   live stream for its length, so a lone queued request waits (at most
   ``coalesce_steps`` steps) for a lane that retires by then, and shares
   its admission with that lane's successor;
2. **decode** — one jitted ``decode_step`` over all ``max_slots`` slots
   (finished/free slots ride along masked), sample per-slot, append to
   per-slot output buffers, retire slots that hit a stop condition;
3. **collect** — completed requests are returned to the caller and their
   slots freed for the next admission.

Each phase runs inside a ``jax.profiler.TraceAnnotation`` span on the
profiler's own clock (``serve.step`` around ``serve.maintain``,
``serve.admit`` holding one ``serve.prefill`` per group,
``serve.decode`` and ``serve.collect``; ``serve.submit`` and
``serve.read`` outside the step), so a device trace shows which phase
the device waited on; a request's spans carry its uid.  The same phases
feed always-on host-time counters in :attr:`ServeRuntime.stats`.

``gang=True`` degrades the scheduler to static batching (admit only when
every slot is free, pad the whole batch to one bucket) — the baseline
``benchmarks/servebench.py`` measures continuous batching against.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.models.layers import NEG_INF
from repro.models.registry import get_model
from repro.models.transformer import AnalogPack
from repro.runtime.fault import resilient_step
from repro.serve.health import HEAD_BAND


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SamplerConfig:
    """Per-token sampling policy applied identically to every slot.

    ``greedy`` ignores keys entirely (deterministic, the configuration
    the runtime-vs-``decode_lm`` agreement contract is pinned in);
    ``temperature`` samples from the tempered softmax; ``top_k``
    restricts to the k highest logits first.
    """

    kind: str = "greedy"                 # greedy | temperature | top_k
    temperature: float = 1.0
    top_k: int = 0

    def __post_init__(self):
        kinds = ("greedy", "temperature", "top_k")
        if self.kind not in kinds:
            raise ValueError(
                f"unknown sampler kind {self.kind!r}; choose from {kinds}")
        if self.temperature <= 0.0:
            raise ValueError(f"temperature must be > 0, got {self.temperature}")
        if self.kind == "top_k" and self.top_k < 1:
            raise ValueError(f"top_k sampling needs top_k >= 1, got {self.top_k}")


def request_key(key: jax.Array, uid) -> jax.Array:
    """Fold a request's sampling key from a stable hash of its uid.

    The sampling-side sibling of ``serve.analog_engine.hook_key`` — the
    *same* fold, applied to ``str(uid)`` — so keys never derive from
    admission order or slot index and a request's sampled continuation
    is reproducible no matter what it is batched with (pinned by
    ``tests/test_runtime.py``).
    """
    from repro.serve.analog_engine import hook_key

    return hook_key(key, str(uid))


def sample_tokens(logits: jax.Array, keys: jax.Array,
                  sampler: SamplerConfig) -> Tuple[jax.Array, jax.Array]:
    """Sample one token per row: (B, V) logits + (B,) per-slot keys ->
    ((B,) int32 tokens, advanced keys).  Greedy leaves keys untouched."""
    if sampler.kind == "greedy":
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), keys

    def one(lg, k):
        use, nxt = jax.random.split(k)
        lg = lg.astype(jnp.float32) / sampler.temperature
        if sampler.kind == "top_k":
            kth = jax.lax.top_k(lg, sampler.top_k)[0][-1]
            lg = jnp.where(lg < kth, NEG_INF, lg)
        return jax.random.categorical(use, lg).astype(jnp.int32), nxt

    return jax.vmap(one)(logits, keys)


# ---------------------------------------------------------------------------
# slot state
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SlotState:
    """The whole scheduler state as one pytree — the carry of the jitted
    decode step and the target of the prefill-insert scatters."""

    layers: Any            # slot-batched cache tree, leaves (L, B, S_max, ...)
    length: jax.Array      # (B,)  per-slot KV fill
    tok: jax.Array         # (B,)  last sampled token (next decode input)
    active: jax.Array      # (B,)  bool: slot holds a live request
    emitted: jax.Array     # (B,)  tokens generated so far
    max_new: jax.Array     # (B,)  per-request generation budget
    out: jax.Array         # (B, cap) generated-token buffer
    key: jax.Array         # (B, 2) per-slot sampling PRNG key


@dataclasses.dataclass
class _Pending:
    uid: Any
    prompt: np.ndarray
    max_new: int
    submit_t: float
    ttft_s: Optional[float] = None
    # decode-step counter value at which this request retires.  Exact when
    # EOS stopping is off (the budget is the only stop condition), which
    # lets _collect skip device syncs on steps where nothing can finish.
    done_step: int = 0
    # decode-step counter value when the request was queued
    queued_step: int = 0


@dataclasses.dataclass(frozen=True)
class Completion:
    """One finished request: the generated tokens (EOS included when one
    fired) plus scheduling telemetry."""

    uid: Any
    tokens: np.ndarray          # (n_generated,) int32
    prompt_len: int
    ttft_s: float               # submit -> first token wall time


#: the scheduler's phases, each timed into ``stats`` and traced as a
#: ``serve.<phase>`` span
PHASES = ("maintain", "admit", "prefill", "decode", "collect")


def _pow2_at_least(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


# ---------------------------------------------------------------------------
# the runtime
# ---------------------------------------------------------------------------


class ServeRuntime:
    """Slot-scheduled continuous-batching server over one (cfg, params[,
    pack]) — see the module docstring for the scheduling model.

    Parameters
    ----------
    pack:      serve through this :class:`AnalogPack` (program + calibrate
               it first, e.g. via ``repro.serve.analog_engine``); ``None``
               serves the digital model.
    max_slots: decode batch width — the fixed shape of the jitted step.
    max_len:   per-slot KV capacity; every request must satisfy
               ``len(prompt) + max_new_tokens <= max_len``.
    buckets:   allowed padded prompt lengths.  Prompts are right-padded to
               the smallest fitting bucket, so prefill compiles at most
               ``len(buckets) * log2(max_slots)`` programs.
    sampler:   :class:`SamplerConfig`; per-slot keys fold from the root
               seed via :func:`request_key`.
    manager:   a :class:`repro.serve.health.PackManager` owning the pack's
               device state over time — mutually exclusive with ``pack``.
               With a ``clock``, the served pack ages (drift + stuck-cell
               faults) as decode steps accumulate; with a ``heal`` policy,
               the runtime probes its own health and heals itself: per-site
               recalibration plus background band-by-band reprogramming,
               new conductances swapped in *between* decode steps (the
               jitted step takes the pack as a traced argument, so swaps
               never recompile and in-flight requests keep serving).
    clock:     :class:`repro.serve.health.DriftClock` mapping decode steps
               to device age; requires ``manager``.
    heal:      :class:`repro.serve.health.HealPolicy`; requires ``manager``.
    eos_id:    stop token (emitted, then the slot retires); ``None``
               disables EOS stopping (pure ``max_new_tokens`` budget).
    gang:      static-batching mode (admit only into an all-free server,
               one shared bucket) — the servebench baseline.
    coalesce_steps: admission coalescing.  A prefill stalls every live
               stream for its length, one stall per admitting step.
               When one request is queued and a live lane retires within
               this many decode steps, the request waits (at most this
               many steps) so that the retiring lane's successor is
               admitted in the same step: one stall for both.  It costs
               the held request up to this many steps of time to first
               token.  ``0`` admits at once.
    attn_backend: decode-step attention implementation. ``"stream"`` is
               the online-softmax lax.scan; ``"flash"`` the flash-decode
               Pallas kernel over the dense slot cache
               (``kernels.ops.flash_attention_decode``); ``"flash_oracle"``
               its bitwise jnp mirror.  Prefill always streams (flash is
               a decode-shape kernel).  Flash has no sliding-window mask.
    measure_ttft: block on each prefill's results before stamping
               ``ttft_s``, so it measures true submit→first-token wall
               time.  Off by default: blocking defeats dispatch
               pipelining (prefills serialize against in-flight decode
               work), so the default stamps at dispatch — a submit→
               admission latency.  Benchmarks run throughput and TTFT
               as separate passes (``benchmarks/servebench.py``).
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params: dict,
        *,
        pack: Optional[AnalogPack] = None,
        max_slots: int = 8,
        max_len: int = 64,
        buckets: Optional[Sequence[int]] = None,
        sampler: SamplerConfig = SamplerConfig(),
        eos_id: Optional[int] = None,
        seed: int = 0,
        gang: bool = False,
        coalesce_steps: int = 12,
        measure_ttft: bool = False,
        attn_backend: str = "stream",
        manager=None,
        clock=None,
        heal=None,
    ):
        api = get_model(cfg)
        if attn_backend not in ("stream", "flash", "flash_oracle"):
            raise ValueError(f"unknown attn_backend {attn_backend!r}")
        if attn_backend != "stream" and cfg.sliding_window is not None:
            raise ValueError(
                "the flash-decode kernel has no sliding-window mask; "
                "serve windowed configs with attn_backend='stream'")
        self.attn_backend = attn_backend
        if manager is not None and pack is not None:
            raise ValueError(
                "pass either pack= (a static AnalogPack) or manager= (a "
                "PackManager owning the pack's device state), not both")
        if (clock is not None or heal is not None) and manager is None:
            raise ValueError(
                "clock=/heal= need a manager= (repro.serve.health."
                "PackManager) to derive aged packs and reprogram bands")
        self._manager, self._clock, self._heal = manager, clock, heal
        if manager is not None:
            pack = manager.aged(clock.at(0) if clock is not None else 1.0)
        if api.prefill_ragged is None or api.cache_slot_insert is None:
            from repro.models.registry import families_with

            raise ValueError(
                f"family {cfg.family!r} has no continuous-batching support "
                f"(needs ModelApi.prefill_ragged + cache_slot_insert); "
                f"families with it: {sorted(families_with('prefill_ragged'))} "
                f"(rwkv and MoE configs excluded)")
        if cfg.rwkv:
            raise ValueError(
                "continuous batching does not support the rwkv family: "
                "ragged right-padded prefill would fold pad tokens into "
                "the recurrent state (DESIGN.md §Serving-runtime)")
        if cfg.n_experts:
            raise ValueError(
                "continuous batching does not support MoE configs: "
                "capacity-based expert routing computes token keep/drop "
                "from a batch-wide cumsum, so co-batched rows and pad "
                "tokens would change a request's output — the scheduling-"
                "never-changes-outputs contract cannot hold "
                "(DESIGN.md §Serving-runtime)")
        if max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {max_slots}")
        if buckets is None:
            # powers of two up to max_len, topped with max_len itself so
            # every request satisfying len(prompt) + max_new <= max_len
            # has a bucket
            buckets = tuple(b for b in (8, 16, 32, 64, 128, 256, 512, 1024)
                            if b < max_len) + (max_len,)
        buckets = tuple(sorted(set(int(b) for b in buckets)))
        if not buckets or buckets[0] < 1 or buckets[-1] > max_len:
            raise ValueError(
                f"buckets must sit in [1, max_len={max_len}], got {buckets}")
        self.cfg, self.params, self.pack = cfg, params, pack
        self.max_slots, self.max_len = int(max_slots), int(max_len)
        self.buckets, self.sampler, self.gang = buckets, sampler, gang
        if coalesce_steps < 0:
            raise ValueError(
                f"coalesce_steps must be >= 0, got {coalesce_steps}")
        self.coalesce_steps = int(coalesce_steps)
        self.measure_ttft = measure_ttft
        self._api = api
        self._eos_enabled = eos_id is not None
        self._eos = -1 if eos_id is None else int(eos_id)
        self._root_key = jax.random.PRNGKey(seed)
        self._decode_fn = jax.jit(self._make_decode_fn())
        self._prefill_fns: Dict[Tuple[int, int], Any] = {}
        # cache positions per flash-decode chunk, as the kernel chooses
        # them (``None``: the decode step reads no chunks)
        self._attn_block = None
        if attn_backend == "flash":
            from repro.kernels.ops import flash_decode_block

            self._attn_block = flash_decode_block(
                self.max_len, cfg.n_kv_heads, cfg.hd, cfg.dtype)
        self._next_uid = 0
        self.reset()

    # -- state / bookkeeping ----------------------------------------------

    def reset(self) -> None:
        """Drop all queued/active requests and zero the slot state.
        Compiled step functions are kept, so a reset server re-serves
        without recompilation (used by benchmark warmup)."""
        b = self.max_slots
        self._state = SlotState(
            layers=self._init_layers(),
            length=jnp.zeros((b,), jnp.int32),
            tok=jnp.zeros((b,), jnp.int32),
            active=jnp.zeros((b,), bool),
            emitted=jnp.zeros((b,), jnp.int32),
            max_new=jnp.ones((b,), jnp.int32),
            out=jnp.zeros((b, self.max_len), jnp.int32),
            key=jnp.zeros((b, 2), jnp.uint32),
        )
        self._queue: Deque[_Pending] = deque()
        self._slots: List[Optional[_Pending]] = [None] * b
        self._early: List[Completion] = []
        self._live_uids: set = set()
        self._heal_queue: Deque[Any] = deque()
        self._last_health = 0
        self._stats = {"decode_steps": 0, "prefill_calls": 0,
                       "occupancy_sum": 0, "tokens_out": 0, "ttft_s": [],
                       "heal_events": 0, "bands_reprogrammed": 0,
                       "recalibrations": 0, "probe_losses": [],
                       "device_syncs": 0, "attn_blocks_read": 0,
                       "attn_blocks_cached": 0}
        for phase in PHASES:
            self._stats[phase + "_s"] = 0.0
            self._stats[phase + "_max_s"] = 0.0

    def _init_layers(self):
        """The slot-batched cache tree this runtime decodes over.  Hook
        for subclasses with a different KV layout (the paged runtime
        swaps in a global page pool, ``repro.serve.paged``)."""
        return self._api.init_cache(
            self.cfg, self.max_slots, self.max_len)["layers"]

    @property
    def stats(self) -> Dict[str, Any]:
        """An operator's counters since the last :meth:`reset`, always on:

        * ``decode_steps``, ``prefill_calls``: decode steps and prefill
          groups dispatched;
        * ``occupancy``: mean busy slots per decode step / ``max_slots``;
        * ``tokens_out``: tokens of the requests collected;
        * ``ttft_s``: per request, submit to admission (to its first
          token with ``measure_ttft``), in seconds;
        * ``heal_events``, ``bands_reprogrammed``, ``recalibrations``,
          ``probe_losses``: the health loop's (with a ``manager``);
        * ``<phase>_s`` and ``<phase>_max_s`` for each of :data:`PHASES`:
          host seconds spent in the phase in all and in its longest call
          (``time.perf_counter``).  ``maintain``, ``admit``, ``decode``
          and ``collect`` are disjoint parts of :meth:`step`;
          ``prefill`` (one group's dispatch) lies inside ``admit``, as
          does a collect made during admission.  ``prefill`` and
          ``decode`` are dispatches of jitted programs; a dispatch
          returns before the device runs the program, but on a TPU not
          before the program dispatched ahead of it (it reads the slot
          state that one writes) has run;
        * ``device_syncs``: blocking reads of device state — each collect
          that read the slot state (a step on which a lane may finish;
          every step with EOS stopping on), each ``measure_ttft`` wait
          and each :meth:`stream`;
        * ``attn_blocks_read``, ``attn_blocks_cached``: with
          ``attn_backend="flash"``, the flash-decode chunks of each live
          lane at each decode step up to its fill, ``ceil(fill / block)``,
          and of its whole cache (0 with any other backend).  Their ratio
          is the share of the cache the kernel reads.  Counted from the
          host's bookkeeping, so exact while the budget is the only stop
          condition (an upper bound with EOS stopping on).
        """
        s = dict(self._stats)
        s["ttft_s"] = list(s["ttft_s"])      # snapshot, not the live list
        s["probe_losses"] = list(s["probe_losses"])
        steps = max(s["decode_steps"], 1)
        s["occupancy"] = s.pop("occupancy_sum") / (steps * self.max_slots)
        return s

    # -- request API -------------------------------------------------------

    def submit(self, prompt, *, max_new_tokens: int, uid=None):
        """Queue one request; returns its uid (auto-assigned if None)."""
        with jax.profiler.TraceAnnotation("serve.submit") as span:
            uid = self._enqueue(prompt, max_new_tokens, uid)
            span.set_metadata(uid=str(uid))
        return uid

    def _enqueue(self, prompt, max_new_tokens: int, uid):
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if prompt.min() < 0 or prompt.max() >= self.cfg.vocab:
            raise ValueError(
                f"prompt tokens must sit in [0, vocab={self.cfg.vocab}); "
                f"got range [{prompt.min()}, {prompt.max()}]")
        if prompt.size > self.buckets[-1]:
            raise ValueError(
                f"prompt length {prompt.size} exceeds the largest bucket "
                f"{self.buckets[-1]}; raise max_len/buckets")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if prompt.size + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds the per-slot KV capacity max_len={self.max_len}")
        if uid is None:
            # auto-assignment shares a namespace with caller-chosen uids:
            # skip over any that are already in flight
            while str(self._next_uid) in self._live_uids:
                self._next_uid += 1
            uid, self._next_uid = self._next_uid, self._next_uid + 1
        # keys fold from str(uid), so "7" and 7 would share a sampling
        # stream — and run() keys completions by uid
        if str(uid) in self._live_uids:
            raise ValueError(f"request uid {uid!r} is already in flight")
        self._live_uids.add(str(uid))
        self._queue.append(_Pending(uid, prompt, int(max_new_tokens),
                                    time.perf_counter(),
                                    queued_step=self._stats["decode_steps"]))
        return uid

    @property
    def idle(self) -> bool:
        return not self._queue and all(p is None for p in self._slots)

    def run(self) -> Dict[Any, np.ndarray]:
        """Drain the queue to completion; returns {uid: generated tokens}."""
        done: Dict[Any, np.ndarray] = {}
        while not self.idle:
            for c in self.step():
                done[c.uid] = c.tokens
        while self._heal_queue:      # finish healing that started late
            self._maintain()
        return done

    # -- scheduler ---------------------------------------------------------

    def step(self) -> List[Completion]:
        """One scheduler iteration: maintain -> admit -> decode -> collect."""
        t = self._stats["decode_steps"]
        with jax.profiler.TraceAnnotation("serve.step", step=t) as span:
            with self._phase("maintain"):
                self._maintain()
            with self._phase("admit"):
                self._admit()
            early, self._early = self._early, []
            # lanes past their budget (done_step <= t: retired at prefill,
            # or certainly finished) need collecting, not decoding — don't
            # burn a model step on them.  An EOS that fired early on a lane
            # with budget left is device-side knowledge; _collect (which
            # syncs every step when EOS is on) frees that slot one step
            # later.
            lanes = [p for p in self._slots
                     if p is not None and p.done_step > t]
            live = len(lanes)
            span.set_metadata(live=live)
            if live:
                with self._phase("decode"):
                    self._run_decode()
                self._stats["decode_steps"] += 1
                self._stats["occupancy_sum"] += live
                self._count_attn_blocks(lanes, t)
            return early + self._collect()

    def _count_attn_blocks(self, lanes: List[_Pending], t: int) -> None:
        """Add decode step ``t``'s flash-decode chunks to the counters.  A
        lane retiring at step ``done_step`` decodes its last token with
        ``prompt + max_new - 1`` positions filled, one fewer each step
        before."""
        blk = self._attn_block
        if blk is None:
            return
        for p in lanes:
            fill = p.prompt.size + p.max_new - (p.done_step - t)
            self._stats["attn_blocks_read"] += -(-fill // blk)
        self._stats["attn_blocks_cached"] += len(lanes) * -(-self.max_len
                                                            // blk)

    @contextlib.contextmanager
    def _phase(self, name: str, counted: bool = True, **args):
        """Span ``serve.<name>`` (one of :data:`PHASES`) with ``args``
        and, when ``counted``, the phase's host-time counters in
        :attr:`stats`.  With no trace running a span costs about a
        microsecond."""
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("serve." + name, **args) as span:
            yield span
        if counted:
            dt = time.perf_counter() - t
            self._stats[name + "_s"] += dt
            self._stats[name + "_max_s"] = max(self._stats[name + "_max_s"],
                                               dt)

    def stream(self) -> Tuple[np.ndarray, np.ndarray]:
        """``(tok, emitted)``: each slot's newest token and how many it has
        emitted, as two ``(max_slots,)`` arrays read off the device in one
        blocking read (span ``serve.read``, counted in ``device_syncs``) —
        what a streaming server sends after each :meth:`step`.  A free
        slot's entries are stale."""
        with jax.profiler.TraceAnnotation("serve.read"):
            self._stats["device_syncs"] += 1
            tok, emitted = jax.device_get((self._state.tok,
                                           self._state.emitted))
        return tok, emitted

    def _run_decode(self) -> None:
        """Dispatch one jitted decode step over the slot state.  Hook for
        subclasses that thread extra traced operands through the step
        (the paged runtime passes its block table)."""
        self._state = self._decode_fn(self._state, self.params, self.pack)

    def _maintain(self) -> None:
        """Device-state upkeep between decode steps (no-op without a
        manager).  Drains the heal queue ``bands_per_step`` targets per
        call through ``resilient_step`` (retry/backoff on transient
        faults), recalibrating once the queue empties; otherwise every
        ``check_every`` steps it re-ages the served pack and probes
        health, queueing a heal when the probe loss exceeds the policy
        threshold.  In-flight requests are untouched: the pack is a
        traced argument of the jitted step, so the swap never recompiles
        and never moves slot state."""
        m = self._manager
        if m is None:
            return
        hp = self._heal
        steps = self._stats["decode_steps"]
        t = self._clock.at(steps) if self._clock is not None else 1.0
        if self._heal_queue:
            for _ in range(min(hp.bands_per_step, len(self._heal_queue))):
                target = self._heal_queue.popleft()
                if target == HEAD_BAND:
                    resilient_step(m.reprogram_head, t_now=t,
                                   max_retries=hp.max_retries,
                                   backoff_s=hp.backoff_s)
                else:
                    resilient_step(m.reprogram_band, target, t_now=t,
                                   max_retries=hp.max_retries,
                                   backoff_s=hp.backoff_s)
                self._stats["bands_reprogrammed"] += 1
            self.pack = m.aged(t)
            if not self._heal_queue and hp.recalibrate:
                self.pack = m.recalibrate(self.pack)
                self._stats["recalibrations"] += 1
            return
        every = (hp.check_every if hp is not None
                 else (self._clock.update_every
                       if self._clock is not None else 0))
        if not every or (steps - self._last_health) < every:
            return
        self._last_health = steps
        if self._clock is not None:
            self.pack = m.aged(t)
        if hp is None:
            return
        loss = m.probe_loss(self.pack)
        self._stats["probe_losses"].append(loss)
        if loss > m.ref_loss * hp.loss_mult + hp.loss_add:
            self._stats["heal_events"] += 1
            if hp.reprogram:
                self._heal_queue.extend(m.heal_targets())
            elif hp.recalibrate:
                self.pack = m.recalibrate(self.pack)
                self._stats["recalibrations"] += 1

    def _admit(self) -> None:
        """Admit queued requests until slots or queue run dry.

        Lanes that retire *at prefill* — a 1-token generation budget, or
        an immediate EOS when stopping is on — release their slot (and,
        in the paged runtime, their KV pages) right here, and the loop
        re-admits into the freed capacity.  A bursty queue of short
        requests therefore drains within one scheduler step instead of
        each batch holding slots through a decode step it never needed.
        """
        while self._admit_batch():
            if not self._queue:
                return
            t = self._stats["decode_steps"]
            may_retire = any(p is not None and p.done_step <= t
                             for p in self._slots)
            # with EOS stopping on, a first-token EOS also retires a lane
            # at prefill — that is device-side knowledge, so attempt a
            # (syncing) collect whenever EOS is enabled
            if not (may_retire or self._eos_enabled):
                return
            done = self._collect(counted=False)
            if not done:
                return
            self._early.extend(done)

    def _admit_batch(self) -> bool:
        """Admit one batch of requests into free slots; True if any."""
        free = [i for i, p in enumerate(self._slots) if p is None]
        if not free or not self._queue:
            return False
        if self.gang and len(free) < self.max_slots:
            return False                # static batching: wait for a full drain
        if self._hold():
            return False
        take: List[_Pending] = []
        while self._queue and len(take) < len(free):
            if not self._reserve(self._queue[0]):
                break                   # backpressure: keep FIFO order intact
            take.append(self._queue.popleft())
        if not take:
            return False
        groups: Dict[Tuple, List[Tuple[_Pending, int]]] = {}
        if self.gang:
            # one shared bucket: pad the whole batch to its longest prompt
            bucket = self._bucket_for(max(r.prompt.size for r in take))
            groups[(bucket,)] = [(r, free.pop(0)) for r in take]
        else:
            for r in take:
                groups.setdefault(self._group_key(r), []).append(
                    (r, free.pop(0)))
        # ascending key order; the paged runtime relies on this (groups
        # sort by cached-prefix length, so a prefix donor's prefill is
        # dispatched before any same-batch borrower gathers its pages)
        for key in sorted(groups):
            items = groups[key]
            with self._phase("prefill", bucket=key[-1],
                             group=self._group_size(len(items)),
                             uids=" ".join(str(r.uid) for r, _ in items)):
                self._prefill_group(key, items)
        return True

    def _hold(self) -> bool:
        """Whether a lone queued request waits a step to share its
        admission with the successor of a lane that retires soon
        (``coalesce_steps``): not once it has waited ``coalesce_steps``
        steps, and only while a live lane retires within that many."""
        w = self.coalesce_steps
        if not w or self.gang or len(self._queue) != 1:
            return False
        t = self._stats["decode_steps"]
        if t - self._queue[0].queued_step >= w:
            return False
        return any(p is not None and 0 < p.done_step - t <= w
                   for p in self._slots)

    def _reserve(self, req: _Pending) -> bool:
        """Claim admission resources for the queue head (hook).  False
        leaves the request queued — the paged runtime returns False when
        the page pool cannot hold the request right now."""
        return True

    def _group_key(self, req: _Pending) -> Tuple:
        """Compile-group key for an admitted request; the last element
        is always the padded prompt bucket."""
        return (self._bucket_for(req.prompt.size),)

    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        raise AssertionError(n)         # unreachable: submit() validates

    def _group_size(self, n: int) -> int:
        """Rows of the prefill program that admits ``n`` requests."""
        return min(_pow2_at_least(n), self.max_slots)

    def _prefill_group(self, key: Tuple,
                       items: List[Tuple[_Pending, int]]) -> None:
        bucket = key[-1]
        g = self._group_size(len(items))
        prompts = np.zeros((g, bucket), np.int32)
        true_lens = np.ones((g,), np.int32)
        slots = np.full((g,), self.max_slots, np.int32)   # dummy -> dropped
        max_new = np.ones((g,), np.int32)
        keys = [jnp.zeros((2,), jnp.uint32)] * g
        for j, (req, slot) in enumerate(items):
            prompts[j, :req.prompt.size] = req.prompt
            true_lens[j] = req.prompt.size
            slots[j] = slot
            max_new[j] = req.max_new
            keys[j] = request_key(self._root_key, req.uid)
            self._slots[slot] = req
        fn = self._prefill_fns.get((bucket, g))
        if fn is None:
            fn = self._prefill_fns[(bucket, g)] = jax.jit(
                self._make_prefill_fn())
        self._state = fn(self._state, self.params, self.pack,
                         jnp.asarray(prompts),
                         jnp.asarray(true_lens), jnp.asarray(slots),
                         jnp.asarray(max_new), jnp.stack(keys))
        self._dispatched(items)

    def _dispatched(self, items: List[Tuple[_Pending, int]]) -> None:
        """Bookkeeping once a group's prefill is dispatched: count it and
        stamp each request's ``ttft_s`` and retiring step."""
        self._stats["prefill_calls"] += 1
        if self.measure_ttft:
            # first tokens exist only once the async dispatch lands —
            # without the block, ttft_s is submit->admission latency
            self._stats["device_syncs"] += 1
            jax.block_until_ready(self._state.tok)
        now = time.perf_counter()
        for req, _ in items:
            req.ttft_s = now - req.submit_t
            req.done_step = self._stats["decode_steps"] + req.max_new - 1
            self._stats["ttft_s"].append(req.ttft_s)

    def _collect(self, counted: bool = True) -> List[Completion]:
        """Retire the lanes that finished (span ``serve.collect``, args
        ``synced`` and the ``finished`` uids); ``counted`` False inside
        admission, whose time it is."""
        with self._phase("collect", counted) as span:
            syncs = self._stats["device_syncs"]
            done = self._retire()
            span.set_metadata(
                synced=self._stats["device_syncs"] - syncs,
                finished=" ".join(str(c.uid) for c in done))
        return done

    def _retire(self) -> List[Completion]:
        busy = [p for p in self._slots if p is not None]
        if not busy:
            return []
        if not self._eos_enabled:
            # the generation budget is the only stop condition, so finish
            # steps are host-predictable: skip the device sync entirely on
            # steps where no slot can retire (the steady-state fast path)
            t = self._stats["decode_steps"]
            if all(p.done_step > t for p in busy):
                return []
        self._stats["device_syncs"] += 1
        active = np.asarray(self._state.active)
        finished = [i for i, p in enumerate(self._slots)
                    if p is not None and not active[i]]
        if not finished:
            return []
        out = np.asarray(self._state.out)
        emitted = np.asarray(self._state.emitted)
        done = []
        for i in finished:
            req = self._slots[i]
            self._free_slot(i)
            self._live_uids.discard(str(req.uid))
            toks = out[i, :emitted[i]].astype(np.int32)
            self._stats["tokens_out"] += int(emitted[i])
            done.append(Completion(uid=req.uid, tokens=toks,
                                   prompt_len=int(req.prompt.size),
                                   ttft_s=req.ttft_s))
        return done

    def _free_slot(self, i: int) -> None:
        """Return slot ``i`` to the free list (hook: the paged runtime
        also releases the slot's page references and zeroes its
        block-table row here)."""
        self._slots[i] = None

    # -- jitted step bodies ------------------------------------------------

    def _make_decode_model(self):
        """The model half of the decode step: (state, params, pack,
        *extra) -> (last-token logits, new cache layers, new lengths).
        Hook — the paged runtime swaps in ``decode_step_paged`` over the
        page pool; the sampling/bookkeeping tail in ``_make_decode_fn``
        is shared.
        """
        cfg, api = self.cfg, self._api
        attn_backend = self.attn_backend

        def model(state: SlotState, params, pack):
            cache = {"layers": state.layers, "len": state.length}
            logits, cache = api.decode_step(
                cfg, params, state.tok[:, None], cache, pack=pack,
                attn_backend=attn_backend)
            return logits[:, -1], cache["layers"], cache["len"]

        return model

    def _make_decode_fn(self):
        sampler, eos = self.sampler, self._eos
        model = self._make_decode_model()

        # params and pack are traced ARGUMENTS, not closures: a healed/
        # aged pack (same treedef, new conductances) swaps in between
        # decode steps without recompiling the step, and no weight is
        # baked into the executable as a constant (at published widths
        # that would copy gigabytes into every compiled program)
        def decode(state: SlotState, params, pack, *extra) -> SlotState:
            logits, layers, length = model(state, params, pack, *extra)
            nxt, keys = sample_tokens(logits, state.key, sampler)
            act = state.active
            cap = state.out.shape[1]
            hit = (jnp.arange(cap)[None, :] == state.emitted[:, None]) \
                & act[:, None]
            out = jnp.where(hit, nxt[:, None], state.out)
            emitted = state.emitted + act.astype(state.emitted.dtype)
            done = act & ((emitted >= state.max_new) | (nxt == eos))
            return SlotState(
                layers=layers,
                length=jnp.where(act, length, state.length),
                tok=jnp.where(act, nxt, state.tok),
                active=act & ~done,
                emitted=emitted,
                max_new=state.max_new,
                out=out,
                key=jnp.where(act[:, None], keys, state.key),
            )

        return decode

    def _make_prefill_fn(self):
        cfg = self.cfg
        api, sampler, eos = self._api, self.sampler, self._eos

        def prefill(state: SlotState, params, pack, prompts, true_lens,
                    slots, max_new, keys) -> SlotState:
            logits, pcache = api.prefill_ragged(
                cfg, params, prompts, true_lens=true_lens, pack=pack)
            first, keys = sample_tokens(logits[:, -1], keys, sampler)
            slot_cache = api.cache_slot_insert(
                {"layers": state.layers, "len": state.length}, pcache, slots)
            cap = state.out.shape[1]
            row = jnp.zeros((slots.shape[0], cap), state.out.dtype)
            row = row.at[:, 0].set(first)
            # a 1-token budget (or immediate EOS) finishes at prefill
            live = (max_new > 1) & (first != eos)
            return SlotState(
                layers=slot_cache["layers"],
                length=slot_cache["len"],
                tok=state.tok.at[slots].set(first, mode="drop"),
                active=state.active.at[slots].set(live, mode="drop"),
                emitted=state.emitted.at[slots].set(1, mode="drop"),
                max_new=state.max_new.at[slots].set(max_new, mode="drop"),
                out=state.out.at[slots].set(row, mode="drop"),
                key=state.key.at[slots].set(keys, mode="drop"),
            )

        return prefill
