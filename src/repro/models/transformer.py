"""Decoder-only LM covering the dense / moe / vlm / ssm(rwkv) families.

Layers are scanned (stacked params, one traced block body) so 94-layer
configs lower to compact HLO.  The same block body serves training
(no cache), prefill (returns a cache) and decode (single-token cache
update) — the cache travels through the scan as per-layer xs/ys.

The analog execution path threads an :class:`AnalogPack` whose per-layer
conductance stacks are scanned alongside the parameters; see
``repro.serve.analog_engine`` for programming/calibration.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.config import ModelConfig
from repro.core.analog import (AnalogSpec, AnalogWeights, analog_matmul,
                               reads_stacked)
from repro.hw.profile import Profile, SiteSpecs
from repro.models import ssm as ssm_mod
from repro.models.attention import attention_block, init_attention
from repro.models.layers import AnalogCtx, dense, norm, rms_norm
from repro.models.mlp import init_mlp, init_moe, mlp_block, moe_block

GLOBAL_WINDOW = 1 << 30

NO_CAST = ("a_log", "dt_bias", "u", "w_base", "d_skip", "router")


def cast_params(params, dtype):
    """Cast float params to the compute dtype, keeping numerically
    sensitive leaves (decay logs, router) in fp32."""

    def f(path, x):
        name = str(path[-1].key) if hasattr(path[-1], "key") else ""
        if x.dtype == jnp.float32 and name not in NO_CAST:
            return x.astype(dtype)
        return x

    return jax.tree_util.tree_map_with_path(f, params)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class AnalogPack:
    """Layer-stacked analog weights + calibrated ranges for the LM.

    Heterogeneous profiles: ``profile`` is the site resolver the pack was
    programmed from; ``bands`` are its maximal contiguous layer bands
    (``((0, L),)`` for any profile without layer-band rules — the uniform
    fast path, one scan, bit-identical to the pre-profile program) and
    ``band_specs[i]`` the resolved (site, spec) map serving band ``i``.
    Each site keeps ONE layer-stacked conductance array regardless of
    banding (per-band specs must agree on array geometry —
    ``repro.hw.check_band_geometry``); the scan is split at band
    boundaries so each band runs under its own static specs.
    """

    profile: Profile = dataclasses.field(metadata=dict(static=True))
    bands: Tuple[Tuple[int, int], ...] = dataclasses.field(
        metadata=dict(static=True))
    band_specs: Tuple[SiteSpecs, ...] = dataclasses.field(
        metadata=dict(static=True))
    layer_weights: Dict[str, AnalogWeights]     # arrays stacked over L
    layer_lo: Dict[str, jax.Array]              # (L, S)
    layer_hi: Dict[str, jax.Array]
    layer_act: Dict[str, jax.Array]             # (L,)
    head: Optional[AnalogWeights] = None        # lm_head
    head_lo: Optional[jax.Array] = None
    head_hi: Optional[jax.Array] = None
    head_act: Optional[jax.Array] = None
    head_spec: Optional[AnalogSpec] = dataclasses.field(
        default=None, metadata=dict(static=True))
    collect: bool = dataclasses.field(default=False, metadata=dict(static=True))

    def site_spec(self, name: str) -> AnalogSpec:
        """The spec serving ``name`` (first band where it is analog).

        Array geometry (mapping, max_rows) is band-uniform per site, so
        any analog band answers geometry questions like ``mapping.sliced``.
        """
        if name == "head" and self.head_spec is not None:
            return self.head_spec
        for ss in self.band_specs:
            s = ss.get(name)
            if s is not None:
                return s
        raise KeyError(f"site {name!r} is not analog in any band of this pack")

    def age(self, t, key: jax.Array) -> "AnalogPack":
        """Deterministic device state of this pack at age ``t`` (units of
        the programming-reference time t0; ``t = 1`` is fresh).

        Applies each site's own drift/fault models
        (``repro.core.errors``) with keys folded from the same stable
        hook-name hashes as programming, so aging is replayable and
        band-structure-invariant; bit-identical to ``self`` at ``t = 1``
        or with aging disabled.  See ``repro.serve.analog_engine.age_pack``.
        """
        from repro.serve.analog_engine import age_pack

        return age_pack(self, t, key)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    """fp32 master parameters."""
    ks = jax.random.split(key, 8)
    d, l, v = cfg.d_model, cfg.n_layers, cfg.vocab
    dt = jnp.float32
    p: Dict[str, Any] = {
        "embed": jax.random.normal(ks[0], (v, d), dt) * d ** -0.5,
        "final_norm": {"scale": jnp.zeros((d,), dt)},
    }
    if cfg.norm == "layernorm":
        p["final_norm"] = {"scale": jnp.ones((d,), dt),
                           "bias": jnp.zeros((d,), dt)}
    if not cfg.tie_embeddings:
        p["lm_head"] = jax.random.normal(ks[1], (d, v), dt) * d ** -0.5

    layers: Dict[str, Any] = {}
    if cfg.rwkv:
        layers["rwkv"] = ssm_mod.init_rwkv(ks[2], cfg, l, dt)
        layers["norm1"] = _norm_init(cfg, l, dt)
        layers["norm2"] = _norm_init(cfg, l, dt)
    else:
        layers["attn"] = init_attention(ks[2], cfg, l, dt)
        layers["norm1"] = _norm_init(cfg, l, dt)
        layers["norm2"] = _norm_init(cfg, l, dt)
        if cfg.n_experts:
            layers["moe"] = init_moe(ks[3], cfg, l, dt)
            if cfg.dense_residual:
                layers["mlp"] = init_mlp(ks[4], d, cfg.d_ff, cfg.act, l, dt)
        else:
            layers["mlp"] = init_mlp(ks[4], d, cfg.d_ff, cfg.act, l, dt)
    p["layers"] = layers
    return p


def _norm_init(cfg: ModelConfig, l: int, dt) -> dict:
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((l, cfg.d_model), dt),
                "bias": jnp.zeros((l, cfg.d_model), dt)}
    return {"scale": jnp.zeros((l, cfg.d_model), dt)}


def layer_windows(cfg: ModelConfig) -> Optional[jax.Array]:
    """Per-layer attention window (gemma3's N local : 1 global pattern)."""
    if cfg.sliding_window is None:
        return None
    if cfg.local_global_ratio == 0:
        return jnp.full((cfg.n_layers,), cfg.sliding_window, jnp.int32)
    period = cfg.local_global_ratio + 1
    idx = jnp.arange(cfg.n_layers)
    is_global = (idx % period) == (period - 1)
    return jnp.where(is_global, GLOBAL_WINDOW, cfg.sliding_window)


# ---------------------------------------------------------------------------
# block body
# ---------------------------------------------------------------------------


def _block(
    cfg: ModelConfig,
    p_l: dict,
    x: jax.Array,
    *,
    positions,
    window,
    cache_l: Optional[dict],
    cache_len,
    actx: Optional[AnalogCtx],
    paged: Optional[dict] = None,
    attn_backend: str = "stream",
) -> Tuple[jax.Array, Optional[dict], dict]:
    aux: Dict[str, jax.Array] = {}
    if cfg.rwkv:
        st = cache_l["rwkv"] if cache_l is not None else None
        h, new_t = ssm_mod.rwkv_time_mix(
            p_l["rwkv"], norm(x, p_l["norm1"], cfg.norm), cfg,
            state=st, decode=cache_len is not None and st is not None,
            ctx=actx, aux=aux,
        )
        x = x + h
        h, new_c = ssm_mod.rwkv_channel_mix(
            p_l["rwkv"], norm(x, p_l["norm2"], cfg.norm),
            state=st, decode=cache_len is not None and st is not None,
            ctx=actx, aux=aux,
        )
        x = x + h
        new_cache = {"rwkv": {**new_t, **new_c}}
        return x, new_cache, aux

    h, new_kv = attention_block(
        p_l["attn"], norm(x, p_l["norm1"], cfg.norm), cfg,
        positions=positions, window=window,
        cache=cache_l["attn"] if cache_l is not None else None,
        cache_len=cache_len, ctx=actx, aux=aux, paged=paged,
        attn_backend=attn_backend,
    )
    x = x + h
    h2_in = norm(x, p_l["norm2"], cfg.norm)
    if cfg.n_experts:
        h, _ = moe_block(p_l["moe"], h2_in, cfg, ctx=actx, aux=aux)
        if cfg.dense_residual:
            h = h + mlp_block(p_l["mlp"], h2_in, cfg.act, actx, aux)
    else:
        h = mlp_block(p_l["mlp"], h2_in, cfg.act, actx, aux)
    x = x + h
    return x, {"attn": new_kv}, aux


def _stacked_sites(pack: AnalogPack) -> Dict[str, AnalogWeights]:
    """The sites whose layer stacks the fused kernel reads in place in
    every band that serves them analog (``core.analog.reads_stacked``):
    the layer scan hands these over whole, with the layer's index, and
    slices only the others."""
    out = {}
    for name, aw in pack.layer_weights.items():
        specs = [s for s in (ss.get(name) for ss in pack.band_specs)
                 if s is not None]
        if specs and all(reads_stacked(s, aw, collect=pack.collect)
                         for s in specs):
            out[name] = aw
    return out


def _make_actx(pack: Optional[AnalogPack], sliced, band: int,
               stacks: Dict[str, AnalogWeights],
               layer) -> Optional[AnalogCtx]:
    """Band-resolved per-layer context: only sites analog in this band
    are routed through the analog pipeline (the rest run digitally).
    A site in ``stacks`` gets its whole stack and the index ``layer``."""
    if pack is None:
        return None
    w, lo, hi, act = sliced
    w = {**w, **{n: dataclasses.replace(w[n], g_pos=aw.g_pos,
                                         g_neg=aw.g_neg, layer=layer)
                 for n, aw in stacks.items()}}
    ss = pack.band_specs[band]
    names = ss.names
    return AnalogCtx(
        specs=ss,
        weights={n: w[n] for n in names if n in w},
        lo={n: lo[n] for n in names if n in lo},
        hi={n: hi[n] for n in names if n in hi},
        act={n: act[n] for n in names if n in act},
        collect=pack.collect,
    )


def _stitch_aux(auxes, bands):
    """Concatenate per-band aux stacks back to full (L, ...) stacks.

    Bands may differ in which sites are analog (digital bands emit no
    ``adc/``/``act/`` entries); absent entries are zero-filled so every
    key yields one full-length stack (the filler rows belong to layers
    that never consult them)."""
    keys: list = []
    for a in auxes:
        for k in a:
            if k not in keys:
                keys.append(k)
    out = {}
    for k in keys:
        proto = next(a[k] for a in auxes if k in a)
        parts = []
        for (lo_b, hi_b), a in zip(bands, auxes):
            parts.append(a[k] if k in a else jnp.zeros(
                (hi_b - lo_b,) + proto.shape[1:], proto.dtype))
        out[k] = jnp.concatenate(parts, axis=0)
    return out


def _scan_layers(
    cfg: ModelConfig,
    params: dict,
    x: jax.Array,
    *,
    positions,
    cache: Optional[dict],
    cache_len,
    pack: Optional[AnalogPack],
    remat: bool,
    paged: Optional[dict] = None,
    attn_backend: str = "stream",
):
    windows = layer_windows(cfg)
    xs = {"p": params["layers"]}
    if windows is not None:
        xs["w"] = windows
    if cache is not None:
        xs["c"] = cache
    stacks = _stacked_sites(pack) if pack is not None else {}
    if pack is not None:
        # a stacked site's conductances stay out of the scanned slices
        weights = {n: dataclasses.replace(aw, g_pos=None, g_neg=None)
                   if n in stacks else aw
                   for n, aw in pack.layer_weights.items()}
        xs["a"] = (weights, pack.layer_lo, pack.layer_hi, pack.layer_act)
    if stacks:
        xs["l"] = jnp.arange(cfg.n_layers, dtype=jnp.int32)

    def band_scan(x, xs_band, band: int):
        def body(x, xs_l):
            actx = _make_actx(pack, xs_l.get("a"), band, stacks,
                              xs_l.get("l")) \
                if pack is not None else None
            window = xs_l.get("w")
            x, new_cache, aux = _block(
                cfg, xs_l["p"], x,
                positions=positions, window=window,
                cache_l=xs_l.get("c"), cache_len=cache_len, actx=actx,
                paged=paged, attn_backend=attn_backend,
            )
            return x, {"cache": new_cache, "aux": aux}

        if remat:
            body = jax.checkpoint(body)
        return lax.scan(body, x, xs_band)

    bands = pack.bands if pack is not None else ((0, cfg.n_layers),)
    if len(bands) == 1:
        # uniform profile (or digital run): one scan, exactly the
        # pre-profile lowering — the bit-identity fast path.
        x, ys = band_scan(x, xs, 0)
        return x, ys["cache"], ys["aux"]

    caches, auxes = [], []
    for b, (lo_b, hi_b) in enumerate(bands):
        xs_band = jax.tree.map(lambda a: a[lo_b:hi_b], xs)
        x, ys = band_scan(x, xs_band, b)
        caches.append(ys["cache"])
        auxes.append(ys["aux"])
    cache_out = jax.tree.map(lambda *p: jnp.concatenate(p, axis=0), *caches)
    return x, cache_out, _stitch_aux(auxes, bands)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------


def forward(
    cfg: ModelConfig,
    params: dict,
    tokens: jax.Array,                 # (B, S) int32
    *,
    prefix_embeds: Optional[jax.Array] = None,   # (B, P, d) vlm stub
    pack: Optional[AnalogPack] = None,
    remat: Optional[bool] = None,
) -> Tuple[jax.Array, dict]:
    """Training/eval forward: returns (logits, aux)."""
    dtype = jnp.dtype(cfg.dtype)
    cp = cast_params(params, dtype)
    x = _embed(cfg, cp, tokens, prefix_embeds, dtype)
    x = _maybe_seq_shard(x)
    positions = jnp.arange(tokens.shape[1])
    remat = cfg.remat if remat is None else remat
    x, _, aux = _scan_layers(
        cfg, cp, x, positions=positions, cache=None, cache_len=None,
        pack=pack, remat=remat,
    )
    if pack is not None and pack.collect:
        aux["final_hidden"] = norm(x, cp["final_norm"], cfg.norm)
    logits = _head(cfg, cp, x, pack)
    return logits, aux


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    l = cfg.n_layers
    if cfg.rwkv:
        st = ssm_mod.rwkv_state_init(cfg, batch, dtype)
        return {
            "layers": {"rwkv": jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (l,) + a.shape), st)},
            "len": jnp.zeros((), jnp.int32),
        }
    kv, hd = cfg.n_kv_heads, cfg.hd
    kvs = {
        "k": jnp.zeros((l, batch, max_len, kv, hd), dtype),
        "v": jnp.zeros((l, batch, max_len, kv, hd), dtype),
    }
    return {"layers": {"attn": kvs}, "len": jnp.zeros((), jnp.int32)}


def prefill(
    cfg: ModelConfig,
    params: dict,
    tokens: jax.Array,
    max_len: int,
    *,
    prefix_embeds: Optional[jax.Array] = None,
    pack: Optional[AnalogPack] = None,
) -> Tuple[jax.Array, dict]:
    """Process a prompt, returning (last-token logits, cache)."""
    b, s = tokens.shape
    dtype = jnp.dtype(cfg.dtype)
    cp = cast_params(params, dtype)
    x = _embed(cfg, cp, tokens, prefix_embeds, dtype)
    x = _maybe_seq_shard(x)
    positions = jnp.arange(s)
    x, new_cache, _ = _scan_layers(
        cfg, cp, x, positions=positions, cache=None, cache_len=None,
        pack=pack, remat=False,
    )
    logits = _head(cfg, cp, x[:, -1:], pack)
    if cfg.rwkv:
        cache = {"layers": new_cache, "len": jnp.asarray(s, jnp.int32)}
    else:
        kv = new_cache["attn"]
        pad = max_len - s
        kv = jax.tree.map(
            lambda a: jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))),
            kv,
        )
        cache = {"layers": {"attn": kv}, "len": jnp.asarray(s, jnp.int32)}
    return logits, cache


def decode_step(
    cfg: ModelConfig,
    params: dict,
    token: jax.Array,                  # (B, 1)
    cache: dict,
    *,
    pack: Optional[AnalogPack] = None,
    attn_backend: str = "stream",
) -> Tuple[jax.Array, dict]:
    """One decode step with a KV/state cache.

    ``cache["len"]`` may be a scalar (all rows at the same fill — the
    ``greedy_decode`` path) or a per-row ``(B,)`` vector (continuous
    batching: every slot at its own fill, see ``repro.serve.runtime``).

    ``attn_backend="stream"`` runs the online-softmax lax.scan attention;
    ``"flash"`` the flash-decode Pallas kernel over the dense slot cache
    (``kernels.ops.flash_attention_decode``, no sliding-window support);
    ``"flash_oracle"`` its bitwise jnp mirror.
    """
    if attn_backend not in ("stream", "flash", "flash_oracle"):
        raise ValueError(f"unknown attn_backend {attn_backend!r}")
    if attn_backend != "stream":
        if cfg.rwkv:
            raise ValueError("attn_backend applies to attention caches "
                             "only; rwkv has no KV cache")
        if cfg.sliding_window is not None:
            raise ValueError("the flash-decode kernel has no sliding-"
                             "window mask; use attn_backend='stream'")
    dtype = jnp.dtype(cfg.dtype)
    cp = cast_params(params, dtype)
    x = _embed(cfg, cp, token, None, dtype)
    t = cache["len"]
    positions = (t[:, None] if getattr(t, "ndim", 0) else t) \
        + jnp.arange(1)[None, :]
    x, new_cache, _ = _scan_layers(
        cfg, cp, x, positions=positions, cache=cache["layers"], cache_len=t,
        pack=pack, remat=False, attn_backend=attn_backend,
    )
    logits = _head(cfg, cp, x, pack)
    return logits, {"layers": new_cache, "len": t + 1}


def prefill_ragged(
    cfg: ModelConfig,
    params: dict,
    tokens: jax.Array,                 # (B, S_bucket) right-padded prompts
    *,
    true_lens: jax.Array,              # (B,) real prompt lengths
    prefix_embeds: Optional[jax.Array] = None,
    pack: Optional[AnalogPack] = None,
) -> Tuple[jax.Array, dict]:
    """Variable-length prefill for continuous batching.

    ``tokens`` is a right-padded prompt batch; ``true_lens`` gives each
    row's real length.  Returns per-row logits at position
    ``true_lens - 1`` (shape (B, 1, V)) and a cache whose ``len`` is the
    ``(B,)`` vector ``true_lens``.  Pad positions do hold K/V entries,
    but they sit at indices >= the row's fill: decode's ``kv_len`` mask
    never attends to them, and the slot's own decode tokens progressively
    overwrite them — so a padded row serves bit-identically to an
    unpadded one (causality: its last real token never sees the pads).
    """
    if cfg.rwkv:
        raise ValueError(
            "prefill_ragged does not support the rwkv family: the "
            "recurrent state folds right-pad tokens into every row; "
            "serve rwkv prompts at exact length via prefill() instead")
    b, s = tokens.shape
    dtype = jnp.dtype(cfg.dtype)
    cp = cast_params(params, dtype)
    x = _embed(cfg, cp, tokens, prefix_embeds, dtype)
    positions = jnp.arange(s)
    x, new_cache, _ = _scan_layers(
        cfg, cp, x, positions=positions, cache=None, cache_len=None,
        pack=pack, remat=False,
    )
    true_lens = jnp.asarray(true_lens, jnp.int32)
    last = jnp.take_along_axis(x, (true_lens - 1)[:, None, None], axis=1)
    logits = _head(cfg, cp, last, pack)
    return logits, {"layers": new_cache, "len": true_lens}


def init_page_pool(cfg: ModelConfig, num_pages: int, page_size: int) -> dict:
    """Global paged KV pool: ``num_pages`` fixed-size pages per layer.

    Page 0 is the *sink* page by convention (``repro.serve.kvpool``
    never hands it out): rows without a live allocation scatter their
    decode K/V there, and no live row's block table ever references it,
    so its garbage is unreachable through any ``kv_len`` mask.
    """
    if cfg.rwkv:
        raise ValueError("paged KV applies to attention caches only; "
                         "rwkv state is O(1) per slot already")
    dtype = jnp.dtype(cfg.dtype)
    kv, hd = cfg.n_kv_heads, cfg.hd
    shape = (cfg.n_layers, num_pages, page_size, kv, hd)
    return {"attn": {"k": jnp.zeros(shape, dtype),
                     "v": jnp.zeros(shape, dtype)}}


def prefill_cached(
    cfg: ModelConfig,
    params: dict,
    tokens: jax.Array,                 # (B, S_bucket) right-padded suffixes
    *,
    true_lens: jax.Array,              # (B,) real suffix lengths
    ctx_lens: jax.Array,               # (B,) valid prefix length per row
    ctx_cache: dict,                   # {"k","v"}: (L, B, C, KV, hd)
    pack: Optional[AnalogPack] = None,
) -> Tuple[jax.Array, dict]:
    """Ragged prefill of prompt *suffixes* over per-row cached prefixes.

    The prefix-sharing path: each row already owns ``ctx_lens[b]`` valid
    KV positions (gathered from the page pool by the caller) and only
    the remaining suffix tokens run through the layers.  Every matmul
    still routes through the ``AnalogPack`` exactly as a cold prefill
    would — sharing skips recomputation, never the analog path.

    Returns per-row logits at suffix position ``true_lens - 1`` (shape
    (B, 1, V)) and a cache whose K/V hold the context in ``[0, C)`` plus
    the suffix scattered at ``ctx_lens + [0, S)``; ``len`` is the total
    fill ``ctx_lens + true_lens``.  Positions beyond a row's fill are
    garbage exactly like ``prefill_ragged`` pads — unreachable through
    the decode ``kv_len`` mask.
    """
    if cfg.rwkv:
        raise ValueError("prefill_cached does not support the rwkv family")
    b, s = tokens.shape
    dtype = jnp.dtype(cfg.dtype)
    cp = cast_params(params, dtype)
    x = _embed(cfg, cp, tokens, None, dtype)
    ctx_lens = jnp.asarray(ctx_lens, jnp.int32)
    positions = ctx_lens[:, None] + jnp.arange(s)[None, :]
    # seq capacity C + S so every row's scatter at ctx_lens + [0, S)
    # stays in bounds (out-of-bounds scatter would clamp-corrupt)
    padded = jax.tree.map(
        lambda a: jnp.pad(a, ((0, 0), (0, 0), (0, s), (0, 0), (0, 0))),
        ctx_cache)
    x, new_cache, _ = _scan_layers(
        cfg, cp, x, positions=positions, cache={"attn": padded},
        cache_len=ctx_lens, pack=pack, remat=False,
    )
    true_lens = jnp.asarray(true_lens, jnp.int32)
    last = jnp.take_along_axis(x, (true_lens - 1)[:, None, None], axis=1)
    logits = _head(cfg, cp, last, pack)
    return logits, {"layers": new_cache, "len": ctx_lens + true_lens}


def decode_step_paged(
    cfg: ModelConfig,
    params: dict,
    token: jax.Array,                  # (B, 1)
    cache: dict,
    *,
    pack: Optional[AnalogPack] = None,
    backend: str = "gather",
) -> Tuple[jax.Array, dict]:
    """One decode step over the paged KV pool.

    ``cache`` is ``{"pool": init_page_pool(...), "ptab": (B, NP) int32,
    "len": (B,) int32}`` — the block table and lengths are *traced*
    data (the allocator changes them every step), the pool geometry is
    static, so the step compiles once.  ``backend="gather"`` runs the
    jnp gathered view through the same ``streaming_attention`` as the
    dense-slot decode (the bit-exactness oracle); ``"pallas"`` runs the
    in-kernel-gather flash-decode kernel (``kernels.ops.paged_attention``,
    no sliding-window support).
    """
    if backend not in ("gather", "pallas"):
        raise ValueError(f"unknown paged backend {backend!r}")
    if backend == "pallas" and cfg.sliding_window is not None:
        raise ValueError("the pallas paged-attention kernel has no "
                         "sliding-window mask; use backend='gather'")
    dtype = jnp.dtype(cfg.dtype)
    cp = cast_params(params, dtype)
    x = _embed(cfg, cp, token, None, dtype)
    t = jnp.asarray(cache["len"], jnp.int32)
    positions = t[:, None] + jnp.arange(1)[None, :]
    page_size = cache["pool"]["attn"]["k"].shape[2]
    x, new_pool, _ = _scan_layers(
        cfg, cp, x, positions=positions, cache=cache["pool"], cache_len=t,
        pack=pack, remat=False,
        paged={"ptab": cache["ptab"], "page_size": page_size,
               "backend": backend},
    )
    logits = _head(cfg, cp, x, pack)
    return logits, {"pool": new_pool, "ptab": cache["ptab"], "len": t + 1}


def cache_slot_insert(slot_cache: dict, new_cache: dict,
                      slots: jax.Array) -> dict:
    """Insert freshly-prefilled request rows into a running slot cache.

    Both caches are ``{"layers": ..., "len": ...}`` dicts; slot leaves
    are ``(L, max_slots, S_max, ...)``, new leaves ``(L, G, s, ...)``
    with ``s <= S_max`` (the seq axis is zero-padded up to the slot
    shape).  ``slots`` (G,) names the destination slot per row;
    out-of-range ids are dropped, which is how the runtime pads
    admission groups to fixed compile shapes (dummy rows get
    ``slots == max_slots``).
    """
    def insert(dst, src):
        src = src.astype(dst.dtype)
        pad = [(0, 0)] * src.ndim
        for ax in range(2, src.ndim):
            pad[ax] = (0, dst.shape[ax] - src.shape[ax])
        if any(p != (0, 0) for p in pad):
            src = jnp.pad(src, pad)
        return dst.at[:, slots].set(src, mode="drop")

    layers = jax.tree.map(insert, slot_cache["layers"], new_cache["layers"])
    length = slot_cache["len"].at[slots].set(
        jnp.asarray(new_cache["len"], slot_cache["len"].dtype), mode="drop")
    return {"layers": layers, "len": length}


def cache_slot_evict(slot_cache: dict, slots: jax.Array) -> dict:
    """Zero freed slot rows (hygiene only — the runtime's per-slot
    ``kv_len`` masking already makes evicted data unreachable)."""
    layers = jax.tree.map(
        lambda dst: dst.at[:, slots].set(
            jnp.zeros((), dst.dtype), mode="drop"),
        slot_cache["layers"])
    length = slot_cache["len"].at[slots].set(0, mode="drop")
    return {"layers": layers, "len": length}


def greedy_decode(
    cfg: ModelConfig,
    params: dict,
    prompts: jax.Array,                # (B, S) int32
    n_new: int,
    *,
    prefix_embeds: Optional[jax.Array] = None,
    pack: Optional[AnalogPack] = None,
) -> jax.Array:
    """Batched greedy generation: one prefill, then scanned decode steps.

    The decode loop is a ``lax.scan`` over :func:`decode_step` (cache as
    carry), so the whole multi-request serving path — analog pack
    included — lowers to a single compiled program.  Returns the
    (B, n_new) generated tokens.
    """
    if n_new < 1:
        raise ValueError(f"greedy_decode needs n_new >= 1, got {n_new}")
    b, s = prompts.shape
    # the first generated token comes from the prefill logits, so only
    # n_new - 1 decode steps (and cache slots) are needed
    logits, cache = prefill(cfg, params, prompts, s + n_new - 1,
                            prefix_embeds=prefix_embeds, pack=pack)
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)   # (B,)

    def body(carry, _):
        t, c = carry
        lg, c = decode_step(cfg, params, t[:, None], c, pack=pack)
        nxt = jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)
        return (nxt, c), nxt

    _, toks = lax.scan(body, (tok, cache), None, length=n_new - 1)
    return jnp.concatenate([tok[:, None], jnp.swapaxes(toks, 0, 1)], axis=1)


# ---------------------------------------------------------------------------


def _maybe_seq_shard(x):
    from repro.sharding.perf import FLAGS, constrain_bs

    if FLAGS.seq_parallel_attn and x.shape[1] > 1:
        return constrain_bs(x, seq=True)
    return x


def _embed(cfg, cp, tokens, prefix_embeds, dtype):
    x = cp["embed"][tokens].astype(dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, dtype)
    if prefix_embeds is not None:
        p = prefix_embeds.shape[1]
        x = lax.dynamic_update_slice(x, prefix_embeds.astype(dtype), (0, 0, 0))
        del p
    return x


def _head(cfg, cp, x, pack: Optional[AnalogPack]):
    x = norm(x, cp["final_norm"], cfg.norm)
    w = cp["embed"].T if cfg.tie_embeddings else cp["lm_head"]
    if pack is not None and pack.head is not None and not pack.collect:
        y = analog_matmul(x, pack.head, pack.head_spec, adc_lo=pack.head_lo,
                          adc_hi=pack.head_hi, act_hi=pack.head_act)
        return y.astype(jnp.float32)
    return (x @ w).astype(jnp.float32)
