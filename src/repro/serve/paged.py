"""Paged-KV continuous batching with prefix sharing.

:class:`PagedServeRuntime` replaces :class:`~repro.serve.runtime.
ServeRuntime`'s dense per-slot KV buffers (``max_slots`` rows of
``max_len`` positions each, mostly empty) with a *paged* layout: one
global pool of fixed-size pages (``models.transformer.init_page_pool``)
plus a per-slot **block table** mapping each slot's logical positions to
pool pages.  Capacity is then pooled — a slot holds exactly
``ceil((prompt + max_new) / page_size)`` pages instead of a full
``max_len`` row — and identical prompt *prefixes* can share pages:

* the **page allocator** (``kvpool.PageAllocator``) refcounts pages;
  page 0 is the sink page retired lanes scatter into;
* the **radix cache** (``kvpool.RadixCache``) maps page-sized token
  chunks to pages holding their K/V.  At admission a request's prompt
  is matched against it; whole-page hits are *retained* and reused as
  the request's leading block-table entries, and only the remaining
  suffix runs through prefill (``transformer.prefill_cached``).  Shared
  pages are always full, hence immutable — extension writes land past
  the shared region in the extender's own pages, so sharing is
  copy-on-extend with no copying;
* decode is one jitted step over the whole slot batch, exactly like the
  dense runtime, with the block table passed as *traced* data — the
  allocator rewrites it every admission without recompiling
  (``tools/analyze.py --contracts`` pins the compile count).

**Exactness contract** (the reason the dense runtime stays around as
the differential oracle): with ``max_len % page_size == 0`` the gathered
paged view ``pool[ptab]`` has the same ``(B, max_len)`` geometry as a
dense slot row, runs through the *same* ``streaming_attention`` with the
same ``kv_len`` masking, and a cold prefill is literally the same
``prefill_ragged`` call — so the paged runtime emits **bit-identical
tokens** to the dense runtime, greedy or seeded sampling, digital or
analog pack (pinned token-for-token by ``tests/test_paged.py``).
Prefix hits stay on the contract because ``prefill_cached`` computes
the suffix over the cached K/V with the same masked-softmax math a cold
prefill would (pinned bitwise at the model layer), and cached pages by
construction hold the bitwise-identical K/V the cold path would have
recomputed.  ``backend="pallas"`` swaps the gather for the in-kernel
block-table gather (``kernels.paged``) — numerically equivalent flash
decode, not bit-identical to the gather path, so it is opt-in.

Analog invariant: every matmul — shared-prefix suffixes included —
still routes through the :class:`AnalogPack`; sharing skips
*recomputation* of identical results, never the analog path, and
programming/sampling key derivations are untouched.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.models.registry import get_model
from repro.serve.kvpool import (
    SINK_PAGE,
    PageAllocator,
    PagePoolExhausted,
    RadixCache,
    full_pages,
    pages_needed,
    shareable_prefix,
)
from repro.serve.runtime import (
    ServeRuntime,
    SlotState,
    _Pending,
    request_key,
    sample_tokens,
)


class PagedServeRuntime(ServeRuntime):
    """:class:`ServeRuntime` over a paged KV pool with prefix sharing.

    Additional parameters
    ---------------------
    page_size:    tokens per KV page.  ``max_len`` must be a multiple
                  (the geometry that makes the gathered paged view
                  bit-identical to a dense slot row — see the module
                  docstring).
    num_pages:    pool size, sink page included.  Default
                  ``1 + max_slots * (max_len / page_size)`` — capacity
                  parity with the dense runtime; shrink it to pool
                  capacity instead (requests then wait at admission
                  when the pool is full, FIFO order preserved).
    prefix_cache: keep completed prompts' full pages in the radix cache
                  so identical prefixes prefill once (on by default).
    backend:      ``"gather"`` (default) decodes over the jnp-gathered
                  view — the bit-exact configuration; ``"pallas"`` uses
                  the in-kernel block-table gather kernel.

    Everything else — sampler, analog pack / manager+clock+heal, EOS,
    TTFT measurement — behaves exactly as in the dense runtime.  Gang
    (static-batching) mode is dense-only: it exists as the servebench
    baseline and has no paged counterpart.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params: dict,
        *,
        page_size: int = 8,
        num_pages: Optional[int] = None,
        prefix_cache: bool = True,
        backend: str = "gather",
        max_slots: int = 8,
        max_len: int = 64,
        **kw,
    ):
        if kw.get("gang"):
            raise ValueError(
                "the paged runtime has no gang mode; use the dense "
                "ServeRuntime as the static-batching baseline")
        if kw.get("attn_backend", "stream") != "stream":
            # decode_step_paged has its own gather/pallas backends; the
            # flash-decode kernel reads the *dense* per-slot cache
            raise ValueError(
                "the paged runtime ignores attn_backend (its decode path "
                "is decode_step_paged); use backend='pallas' for the "
                "paged-attention kernel, or the dense ServeRuntime for "
                "flash decode")
        if backend not in ("gather", "pallas"):
            raise ValueError(f"unknown paged backend {backend!r}; "
                             "choose 'gather' or 'pallas'")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if max_len % page_size:
            raise ValueError(
                f"max_len={max_len} must be a multiple of "
                f"page_size={page_size}: equal geometry between the "
                f"gathered paged view and a dense slot row is what pins "
                f"paged decode bit-identical to the dense runtime")
        api = get_model(cfg)
        if (api.init_page_pool is None or api.prefill_cached is None
                or api.decode_step_paged is None):
            raise ValueError(
                f"family {cfg.family!r} has no paged-KV support (needs "
                f"ModelApi.init_page_pool + prefill_cached + "
                f"decode_step_paged)")
        self.page_size = int(page_size)
        self.backend = backend
        self._np = max_len // self.page_size      # block-table width
        self.num_pages = (1 + max_slots * self._np if num_pages is None
                          else int(num_pages))
        if self.num_pages < 1 + self._np:
            raise ValueError(
                f"num_pages={self.num_pages} cannot hold even one "
                f"full-length request ({self._np} pages + sink)")
        self._use_prefix_cache = bool(prefix_cache)
        super().__init__(cfg, params, max_slots=max_slots, max_len=max_len,
                         **kw)

    # -- state ------------------------------------------------------------

    def reset(self) -> None:
        self._alloc = PageAllocator(self.num_pages)
        self._radix = (RadixCache(self._alloc, self.page_size)
                       if self._use_prefix_cache else None)
        self._resv: Dict[str, Tuple[List[int], int]] = {}
        self._ptab = np.zeros((self.max_slots, self._np), np.int32)
        self._slot_pages: List[List[int]] = [[] for _ in range(self.max_slots)]
        super().reset()
        self._stats.update(prefix_hits=0, prefix_tokens_reused=0,
                           cache_evictions=0, admission_stalls=0)

    def _init_layers(self):
        return self._api.init_page_pool(self.cfg, self.num_pages,
                                        self.page_size)

    # -- admission ---------------------------------------------------------

    def _reserve(self, req: _Pending) -> bool:
        """Claim pages for the queue head: radix-match its prompt, retain
        the shared whole-page prefix, allocate the rest.  On exhaustion,
        evict LRU cache-only pages; if still short, leave the request
        queued (capacity frees as in-flight requests complete)."""
        ps = self.page_size
        plen = int(req.prompt.size)
        total = pages_needed(plen + req.max_new, ps)
        shared: List[int] = []
        ctx = 0
        if self._radix is not None:
            match = self._radix.match(req.prompt.tolist())
            ctx = shareable_prefix(len(match), plen, ps)
            shared = match[:ctx // ps]
            if shared:
                # take slot references before any eviction can release
                # the cache's own references on these pages
                self._alloc.retain(shared)
        n_new = total - len(shared)
        if n_new > self._alloc.free_pages and self._radix is not None:
            self._stats["cache_evictions"] += self._radix.evict(n_new)
        try:
            fresh = self._alloc.alloc(n_new)
        except PagePoolExhausted:
            if shared:
                self._alloc.release(shared)
            self._stats["admission_stalls"] += 1
            return False
        pages = shared + fresh
        if self._radix is not None:
            # register the prompt's full pages now: same-batch followers
            # match them and are grouped *after* this request (ascending
            # ctx), so their gathers read this prefill's pool writes
            self._radix.insert(req.prompt.tolist(),
                               pages[:full_pages(plen, ps)])
        if ctx:
            self._stats["prefix_hits"] += 1
            self._stats["prefix_tokens_reused"] += ctx
        self._resv[str(req.uid)] = (pages, ctx)
        return True

    def _group_key(self, req: _Pending) -> Tuple:
        pages, ctx = self._resv[str(req.uid)]
        return (ctx, self._bucket_for(req.prompt.size - ctx))

    def _free_slot(self, i: int) -> None:
        pages, self._slot_pages[i] = self._slot_pages[i], []
        if pages:
            self._alloc.release(pages)
        self._ptab[i, :] = SINK_PAGE
        super()._free_slot(i)

    # -- prefill -----------------------------------------------------------

    def _prefill_group(self, key: Tuple,
                       items: List[Tuple[_Pending, int]]) -> None:
        ctx, bucket = key
        g = self._group_size(len(items))
        ncp = ctx // self.page_size
        suffix = np.zeros((g, bucket), np.int32)
        true_lens = np.ones((g,), np.int32)
        slots = np.full((g,), self.max_slots, np.int32)   # dummy -> dropped
        max_new = np.ones((g,), np.int32)
        keys = [jnp.zeros((2,), jnp.uint32)] * g
        ctx_pages = np.zeros((g, ncp), np.int32)          # dummy -> sink
        ptabg = np.zeros((g, self._np), np.int32)
        for j, (req, slot) in enumerate(items):
            pages, rctx = self._resv.pop(str(req.uid))
            if rctx != ctx:
                raise RuntimeError(
                    f"admission group mixed cached-prefix depths: "
                    f"reserved ctx={rctx}, group ctx={ctx}")
            sfx = req.prompt[ctx:]
            suffix[j, :sfx.size] = sfx
            true_lens[j] = sfx.size
            slots[j] = slot
            max_new[j] = req.max_new
            keys[j] = request_key(self._root_key, req.uid)
            ctx_pages[j] = pages[:ncp]
            ptabg[j, :len(pages)] = pages
            self._slot_pages[slot] = pages
            self._ptab[slot, :] = SINK_PAGE
            self._ptab[slot, :len(pages)] = pages
            self._slots[slot] = req
        fnkey = (ctx, bucket, g)
        fn = self._prefill_fns.get(fnkey)
        if fn is None:
            fn = self._prefill_fns[fnkey] = jax.jit(
                self._make_paged_prefill_fn())
        self._state = fn(self._state, self.params, self.pack,
                         jnp.asarray(suffix),
                         jnp.asarray(true_lens), jnp.asarray(slots),
                         jnp.asarray(max_new), jnp.stack(keys),
                         jnp.asarray(ctx_pages), jnp.asarray(ptabg))
        self._dispatched(items)

    def _make_paged_prefill_fn(self):
        cfg = self.cfg
        api, sampler, eos = self._api, self.sampler, self._eos
        ps, npg = self.page_size, self._np

        def prefill(state: SlotState, params, pack, suffix, true_lens,
                    slots, max_new, keys, ctx_pages, ptabg) -> SlotState:
            g, s = suffix.shape
            ncp = ctx_pages.shape[1]
            ctx = ncp * ps
            pool = state.layers["attn"]
            if ncp == 0:
                # cold group: literally the dense runtime's prefill call
                # (the paged-vs-dense bitwise contract's cold half)
                logits, pcache = api.prefill_ragged(
                    cfg, params, suffix, true_lens=true_lens, pack=pack)
                kv = pcache["layers"]["attn"]
            else:
                # prefix hit: gather the shared pages into a contiguous
                # context, run only the suffix through the layers
                ctx_cache = {
                    name: pool[name][:, ctx_pages].reshape(
                        pool[name].shape[0], g, ctx,
                        *pool[name].shape[3:])
                    for name in ("k", "v")
                }
                logits, pcache = api.prefill_cached(
                    cfg, params, suffix, true_lens=true_lens,
                    ctx_lens=jnp.full((g,), ctx, jnp.int32),
                    ctx_cache=ctx_cache, pack=pack)
                # only the suffix region is new; shared pages are
                # immutable (always full) and already hold [0, ctx)
                kv = {name: a[:, :, ctx:ctx + s]
                      for name, a in pcache["layers"]["attn"].items()}
            # scatter the suffix K/V into each row's own pages; pad
            # positions (and every dummy-row position) go to the sink
            pos = ctx + jnp.arange(s)[None, :]                    # (1, S)
            valid = jnp.arange(s)[None, :] < true_lens[:, None]   # (G, S)
            pidx = jnp.broadcast_to(jnp.minimum(pos // ps, npg - 1), (g, s))
            pids = jnp.where(valid,
                             jnp.take_along_axis(ptabg, pidx, axis=1),
                             SINK_PAGE)
            offs = jnp.broadcast_to(pos % ps, (g, s))
            new_pool = {"attn": {
                name: pool[name].at[:, pids, offs].set(
                    kv[name].astype(pool[name].dtype))
                for name in ("k", "v")
            }}
            first, keys = sample_tokens(logits[:, -1], keys, sampler)
            cap = state.out.shape[1]
            row = jnp.zeros((g, cap), state.out.dtype).at[:, 0].set(first)
            # a 1-token budget (or immediate EOS) finishes at prefill
            live = (max_new > 1) & (first != eos)
            fill = ctx + true_lens
            return SlotState(
                layers=new_pool,
                length=state.length.at[slots].set(fill, mode="drop"),
                tok=state.tok.at[slots].set(first, mode="drop"),
                active=state.active.at[slots].set(live, mode="drop"),
                emitted=state.emitted.at[slots].set(1, mode="drop"),
                max_new=state.max_new.at[slots].set(max_new, mode="drop"),
                out=state.out.at[slots].set(row, mode="drop"),
                key=state.key.at[slots].set(keys, mode="drop"),
            )

        return prefill

    # -- decode ------------------------------------------------------------

    def _run_decode(self) -> None:
        # the block table is traced data: admissions rewrite it without
        # recompiling the step (repro.analysis contract "paged-decode")
        self._state = self._decode_fn(self._state, self.params, self.pack,
                                      jnp.asarray(self._ptab))

    def _make_decode_model(self):
        cfg, api = self.cfg, self._api
        backend = self.backend

        def model(state: SlotState, params, pack, ptab):
            cache = {"pool": state.layers, "ptab": ptab,
                     "len": state.length}
            logits, cache = api.decode_step_paged(
                cfg, params, state.tok[:, None], cache, pack=pack,
                backend=backend)
            return logits[:, -1], cache["pool"], cache["len"]

        return model

    # -- introspection -----------------------------------------------------

    @property
    def page_stats(self) -> Dict[str, Any]:
        """Live pool occupancy: free/used pages, cached pages, and the
        KV-token capacity actually reserved by resident requests."""
        return {
            "num_pages": self.num_pages,
            "free_pages": self._alloc.free_pages,
            "used_pages": self._alloc.used_pages,
            "pages_cached": (0 if self._radix is None
                             else self._radix.pages_cached),
            "resident_pages": sum(len(p) for p in self._slot_pages),
        }

    def check(self) -> None:
        """Cross-structure invariants (used by the differential tests):
        allocator/radix internal consistency, block tables referencing
        only live pages, and no page aliased across two slots."""
        self._alloc.check()
        if self._radix is not None:
            self._radix.check()
        holders: Dict[int, int] = {}
        for i, pages in enumerate(self._slot_pages):
            if (self._slots[i] is None) and pages:
                raise AssertionError(f"free slot {i} still owns pages")
            if len(set(pages)) != len(pages):
                raise AssertionError(f"slot {i} lists a page twice")
            for p in pages:
                if p == SINK_PAGE:
                    raise AssertionError(f"slot {i} owns the sink page")
                if self._alloc.refcount(p) < 1:
                    raise AssertionError(
                        f"slot {i} references dead page {p}")
                holders[p] = holders.get(p, 0) + 1
        for p, n in holders.items():
            # every holding slot owns one reference (sharing without a
            # matching refcount would be cross-slot aliasing: one slot's
            # free could yank pages out from under another)
            if self._alloc.refcount(p) < n:
                raise AssertionError(
                    f"page {p} held by {n} slots with only "
                    f"{self._alloc.refcount(p)} references")
