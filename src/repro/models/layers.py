"""Shared layers: norms, RoPE, dense projections (with the analog execution
hook), activations, and streaming attention.

Attention is implemented as an online-softmax scan over KV chunks (a
JAX-level flash attention): the (Sq, Skv) score matrix never materializes,
which is what makes the 32k-prefill and 500k-decode dry-run cells fit in
HBM.  On TPU this would be a Pallas kernel; attention is not the paper's
contribution, so the lax.scan formulation is the right altitude here (see
DESIGN.md) — XLA fuses the inner block well and the roofline accounting is
identical.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.analog import AnalogSpec, AnalogWeights, analog_matmul
from repro.hw.profile import SiteSpecs

# ---------------------------------------------------------------------------
# analog execution hook
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class AnalogCtx:
    """Per-layer analog execution context threaded through blocks.

    ``weights[name]`` is the :class:`AnalogWeights` for this layer (sliced
    out of the layer-stacked pack by the scan, or the whole stack and the
    layer's index where the fused kernel reads it in place), ``lo/hi[name]`` the
    calibrated per-slice ADC limits, ``act[name]`` the activation clip.
    ``specs`` carries the *site-resolved* spec per hook name (heterogeneous
    profiles: attention and MLP projections may sit on different hardware;
    sites absent from ``weights`` run digitally).  ``collect=True``
    bypasses the ADC and emits calibration stats into the block's aux
    dict instead.
    """

    specs: SiteSpecs = dataclasses.field(metadata=dict(static=True))
    weights: Dict[str, AnalogWeights]
    lo: Dict[str, jax.Array]
    hi: Dict[str, jax.Array]
    act: Dict[str, jax.Array]
    collect: bool = dataclasses.field(default=False, metadata=dict(static=True))


def dense(
    x: jax.Array,
    w: jax.Array,
    name: str,
    ctx: Optional[AnalogCtx],
    aux: Optional[dict] = None,
    *,
    bias: Optional[jax.Array] = None,
) -> jax.Array:
    """``x @ w`` — digitally, or through the analog pipeline when ``ctx``
    carries programmed conductances for ``name`` (executed under the
    site's own resolved :class:`AnalogSpec`)."""
    if ctx is None or name not in ctx.weights:
        y = x @ w
    else:
        aw = ctx.weights[name]
        spec = ctx.specs.spec_for(name)
        if ctx.collect:
            y, stats = analog_matmul(
                x, aw, spec, act_hi=ctx.act.get(name), collect=True
            )
            if aux is not None:
                aux[f"adc/{name}"] = stats
                from repro.core.quant import calibrate_act_range

                _, a_hi = calibrate_act_range(x, spec.input_bits)
                aux[f"act/{name}"] = a_hi
        else:
            y = analog_matmul(
                x,
                aw,
                spec,
                adc_lo=ctx.lo[name],
                adc_hi=ctx.hi[name],
                act_hi=ctx.act.get(name),
            )
        y = y.astype(x.dtype)
    if bias is not None:
        y = y + bias
    return y


# ---------------------------------------------------------------------------
# norms / embeddings / activations
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def norm(x: jax.Array, p: dict, kind: str) -> jax.Array:
    if kind == "layernorm":
        return layer_norm(x, p["scale"], p["bias"])
    return rms_norm(x, p["scale"])


def gelu(x: jax.Array) -> jax.Array:
    return jax.nn.gelu(x, approximate=True)


ACTIVATIONS = {
    "swiglu": jax.nn.silu,
    "geglu": gelu,
    "gelu": gelu,
}


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding.  x: (B, S, H, hd); positions: (B, S) or (S,).

    Formulated as ``x * cos + rotate_half(x) * sin`` with full-length
    (hd-sized) trig vectors and a roll-based rotate-half.  The textbook
    slice-into-halves + concatenate form is numerically identical but must
    not be used here: concatenating slices back together along the head_dim
    axis miscompiles in the XLA SPMD partitioner when that axis is
    model-sharded on a multi-axis mesh (within-head tensor parallelism —
    the 2x4 debug mesh shards wk's kv*hd=32 output dim across 4 devices),
    silently corrupting k and the training loss.  roll and elementwise ops
    partition correctly.
    """
    hd = x.shape[-1]
    half = hd // 2
    idx = jnp.arange(hd)
    freqs = theta ** (-(idx % half).astype(jnp.float32) / half)     # (hd,)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs      # (B, S, hd)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    sign = jnp.where(idx < half, -1.0, 1.0)
    rot = jnp.roll(x, half, axis=-1) * sign                     # [-x2, x1]
    return (x * cos + rot * sin).astype(x.dtype)


# ---------------------------------------------------------------------------
# streaming attention (online softmax over KV chunks)
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def streaming_attention(
    q: jax.Array,                # (B, Sq, H, hd)
    k: jax.Array,                # (B, Skv, KV, hd)
    v: jax.Array,                # (B, Skv, KV, hd)
    *,
    q_offset,                    # absolute position of q[0]: scalar or (B,)
    causal: bool = True,
    window: Optional[int] = None,
    kv_len=None,                 # dynamic valid KV length: scalar or (B,)
    chunk: int = 1024,
    scale: Optional[float] = None,
) -> jax.Array:
    """GQA attention with an online-softmax scan over KV chunks.

    ``q_offset`` / ``kv_len`` may be per-row ``(B,)`` vectors — the
    continuous-batching decode path, where every slot sits at its own
    cache fill.  The scalar path (shared offset) lowers to the exact same
    ops as before, so single-request serving is bit-identical.
    """
    b, sq, h, hd = q.shape
    _, skv, kv_heads, _ = k.shape
    g = h // kv_heads
    scale = scale if scale is not None else hd ** -0.5

    chunk = min(chunk, skv)
    n_chunks = -(-skv // chunk)
    pad = n_chunks * chunk - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(b, n_chunks, chunk, kv_heads, hd)
    vc = v.reshape(b, n_chunks, chunk, kv_heads, hd)
    kc = jnp.moveaxis(kc, 1, 0)          # (C, B, chunk, KV, hd)
    vc = jnp.moveaxis(vc, 1, 0)

    qg = q.reshape(b, sq, kv_heads, g, hd).astype(jnp.float32) * scale
    # (sq,) for a shared scalar offset, (B, sq) for per-row offsets
    q_pos = jnp.asarray(q_offset)[..., None] + jnp.arange(sq)

    def body(carry, inp):
        m, l, acc = carry
        j, k_j, v_j = inp
        k_pos = j * chunk + jnp.arange(chunk)
        s = jnp.einsum("bqkgd,bckd->bkgqc", qg, k_j.astype(jnp.float32))
        mask = jnp.ones(q_pos.shape + (chunk,), bool)   # (..., sq, chunk)
        if causal:
            mask &= k_pos <= q_pos[..., None]
        if window is not None:
            mask &= k_pos > q_pos[..., None] - window
        if kv_len is not None:
            mask &= k_pos < jnp.asarray(kv_len)[..., None, None]
        if pad:
            mask &= k_pos < skv
        # broadcast onto s: (b, KV, g, sq, chunk)
        mask = mask[None, None, None] if mask.ndim == 2 else mask[:, None, None]
        s = jnp.where(mask, s, NEG_INF)
        m_chunk = jnp.max(s, axis=-1)                        # (b,k,g,q)
        m_new = jnp.maximum(m, m_chunk)
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgqc,bckd->bkgqd", p, v_j.astype(jnp.float32)
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kv_heads, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kv_heads, g, sq), jnp.float32)
    a0 = jnp.zeros((b, kv_heads, g, sq, hd), jnp.float32)
    (m, l, acc), _ = lax.scan(
        body, (m0, l0, a0), (jnp.arange(n_chunks), kc, vc)
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]             # (b,k,g,q,hd)
    out = jnp.moveaxis(out, 3, 1).reshape(b, sq, h, hd)
    return out.astype(q.dtype)
