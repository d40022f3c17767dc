"""Attention block: GQA/MQA, RoPE, optional QKV bias / per-head qk-norm /
sliding window, prefill + decode cache paths."""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models.layers import AnalogCtx, dense, rms_norm, rope, streaming_attention


def init_attention(key: jax.Array, cfg: ModelConfig, n_layers: int,
                   dtype) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    sc = d ** -0.5
    p = {
        "wq": jax.random.normal(ks[0], (n_layers, d, h * hd), dtype) * sc,
        "wk": jax.random.normal(ks[1], (n_layers, d, kv * hd), dtype) * sc,
        "wv": jax.random.normal(ks[2], (n_layers, d, kv * hd), dtype) * sc,
        "wo": jax.random.normal(ks[3], (n_layers, h * hd, d), dtype)
        * (h * hd) ** -0.5,
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((n_layers, h * hd), dtype)
        p["bk"] = jnp.zeros((n_layers, kv * hd), dtype)
        p["bv"] = jnp.zeros((n_layers, kv * hd), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((n_layers, hd), dtype)
        p["k_norm"] = jnp.zeros((n_layers, hd), dtype)
    return p


def attention_block(
    p: dict,                      # per-layer slice (no leading L axis)
    x: jax.Array,                 # (B, S, d)
    cfg: ModelConfig,
    *,
    positions: jax.Array,         # (S,) or (B, S) absolute positions
    window,                       # scalar (possibly traced): huge = global
    cache: Optional[dict] = None,  # {"k","v"}: (B, S_max, KV, hd)
    cache_len=None,               # dynamic current cache fill
    causal: bool = True,
    ctx: Optional[AnalogCtx] = None,
    aux: Optional[dict] = None,
    paged: Optional[dict] = None,  # {"ptab", "page_size", "backend"}
    attn_backend: str = "stream",  # dense decode: stream | flash | flash_oracle
) -> Tuple[jax.Array, Optional[dict]]:
    b, s, d = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd

    from repro.sharding.perf import FLAGS, constrain_bs

    seq_par = FLAGS.seq_parallel_attn and cache is None and s > 1

    q = dense(x, p["wq"], "wq", ctx, aux, bias=p.get("bq"))
    k = dense(x, p["wk"], "wk", ctx, aux, bias=p.get("bk"))
    v = dense(x, p["wv"], "wv", ctx, aux, bias=p.get("bv"))
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, kv, hd)
    v = v.reshape(b, s, kv, hd)

    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])

    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)

    if paged is not None:
        # paged decode: the cache is a global page pool (P, page, KV, hd)
        # and each row's page list is a block table.  The fresh token is
        # scattered into the row's current page; rows whose table entry
        # is unallocated (conventionally 0) write into the sink page,
        # whose contents are never reachable through any live row's
        # ``kv_len`` mask.
        if s != 1:
            raise ValueError("paged attention is a decode path (S == 1); "
                             "prefill goes through the dense cached path")
        ps_ = paged["page_size"]
        ptab = paged["ptab"]                              # (B, NP) int32
        pos = jnp.asarray(cache_len, jnp.int32)           # (B,)
        idx = jnp.clip(pos // ps_, 0, ptab.shape[1] - 1)
        pid = jnp.take_along_axis(ptab, idx[:, None], axis=1)[:, 0]
        off = pos % ps_
        pk = cache["k"].at[pid, off].set(k[:, 0])
        pv = cache["v"].at[pid, off].set(v[:, 0])
        if paged.get("backend", "gather") == "pallas":
            from repro.kernels.ops import paged_attention

            out = paged_attention(q[:, 0], pk, pv, ptab, pos + 1)[:, None]
        else:
            # jnp gather oracle: materialize the row-ordered view and run
            # the exact same streaming attention as the dense-slot decode
            # — with NP*page == max_len the two lower to the same program,
            # which is what pins the paged runtime bit-exact.
            np_ = ptab.shape[1]
            gk = pk[ptab].reshape(b, np_ * ps_, kv, hd)
            gv = pv[ptab].reshape(b, np_ * ps_, kv, hd)
            out = streaming_attention(
                q, gk, gv, q_offset=pos, causal=causal, window=window,
                kv_len=pos + 1,
            )
        out = out.reshape(b, s, h * hd)
        return dense(out, p["wo"], "wo", ctx, aux), {"k": pk, "v": pv}

    if cache is None:
        if seq_par:
            # context parallelism: queries stay sequence-sharded; K/V are
            # gathered over the model axis (cheap: kv_heads*hd << d).
            q = constrain_bs(q, seq=True)
            k = constrain_bs(k, seq=False)
            v = constrain_bs(v, seq=False)
        out = streaming_attention(
            q, k, v, q_offset=0, causal=causal, window=window,
        )
        if seq_par:
            out = constrain_bs(out, seq=True)
        new_cache = {"k": k, "v": v}
    else:
        # decode: insert the new token(s) at cache_len, attend over the cache
        if getattr(cache_len, "ndim", 0):
            # per-slot fills (continuous batching): each row writes its
            # token(s) at its own offset, as selects over the cache that
            # the compiler fuses into the layer's slice of the stacked
            # cache in the layout that has (a scatter would relayout the
            # cache on a TPU, twice a layer)
            off = (jnp.arange(cache["k"].shape[1])[None, :]
                   - cache_len[:, None])[:, :, None, None]
            ck, cv = cache["k"], cache["v"]
            for i in range(s):
                ck = jnp.where(off == i, k[:, i:i + 1].astype(ck.dtype), ck)
                cv = jnp.where(off == i, v[:, i:i + 1].astype(cv.dtype), cv)
        else:
            ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k,
                                                     cache_len, 1)
            cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v,
                                                     cache_len, 1)
        if attn_backend != "stream":
            # flash-decode Pallas kernel over the dense per-slot cache
            # (the dense sibling of the paged in-kernel-gather path).
            # Like that kernel it has no sliding-window mask; the caller
            # (decode_step) rejects windowed configs up front.
            if s != 1:
                raise ValueError("flash attention is a decode path "
                                 "(S == 1); prefill uses streaming")
            from repro.kernels.ops import flash_attention_decode

            fills = jnp.broadcast_to(
                jnp.asarray(cache_len + s, jnp.int32), (b,))
            be = "oracle" if attn_backend == "flash_oracle" else "kernel"
            out = flash_attention_decode(
                q[:, 0], ck, cv, fills, backend=be)[:, None]
        else:
            out = streaming_attention(
                q, ck, cv, q_offset=cache_len, causal=causal, window=window,
                kv_len=cache_len + s,
            )
        new_cache = {"k": ck, "v": cv}

    out = out.reshape(b, s, h * hd)
    return dense(out, p["wo"], "wo", ctx, aux), new_cache


def cross_attention_block(
    p: dict,
    x: jax.Array,                 # (B, S, d) decoder stream
    enc_kv: Tuple[jax.Array, jax.Array],   # precomputed (B, Senc, KV, hd) x2
    cfg: ModelConfig,
    *,
    ctx: Optional[AnalogCtx] = None,
    aux: Optional[dict] = None,
) -> jax.Array:
    """Whisper-style cross attention against cached encoder K/V."""
    b, s, d = x.shape
    h, hd = cfg.n_heads, cfg.hd
    q = dense(x, p["wq"], "xattn_wq", ctx, aux).reshape(b, s, h, hd)
    k, v = enc_kv
    out = streaming_attention(q, k, v, q_offset=0, causal=False, window=None)
    return dense(out.reshape(b, s, h * hd), p["wo"], "xattn_wo", ctx, aux)


def encode_cross_kv(p: dict, enc_out: jax.Array, cfg: ModelConfig) -> Tuple:
    """Project encoder output once into cross-attention K/V."""
    b, se, _ = enc_out.shape
    kv, hd = cfg.n_kv_heads, cfg.hd
    k = (enc_out @ p["wk"]).reshape(b, se, kv, hd)
    v = (enc_out @ p["wv"]).reshape(b, se, kv, hd)
    return k, v
