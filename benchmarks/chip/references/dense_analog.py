"""Plain reference of a dense decoder served on simulated analog arrays.

Written from the published description of the model (RMSNorm, rotary
attention with optional QKV bias and per-head q/k norm, SwiGLU MLP) and
of the analog design (the configuration's ``analog`` block: Design A of
the paper, differential cells, unsliced 8-bit weights, 8-bit inputs
accumulated in the analog domain, K split into partitions of at most
``max_rows`` rows, one calibrated 8-bit ADC per partition output).  It
imports nothing of the program under test and takes nothing it made:
weights, programming keys and calibration tokens come from the
benchmark, and the reference programs the conductances, calibrates the
ranges and runs the model itself, in plain ``jax.numpy``.

What it does, in the order the system does it:

* **programming** — per weight matrix, symmetric 7-bit magnitude codes
  (``max|w| / 127``), each sign on its own line of a differential pair,
  ``g = g_min + (1 - g_min) * code / 127`` with ``g_min = 1/on_off``,
  rows zero-padded to whole partitions, then a state-proportional
  programming error ``g + alpha * g * n``.  The normal draws ``n`` use
  the design's documented key schedule: a site's key is the programming
  key folded with the first four bytes of ``blake2s(site name)`` (top
  bit cleared), then with the layer index; it is split in three, the
  first draw for the positive line and the second for the negative.
* **calibration** — over the calibration tokens, two passes with no
  ADC: the first quantizes each site's input by its own absolute maximum
  and records the L1-optimal input clip (32 candidates from
  ``max/64`` to ``max`` on a log grid, each snapped to a 12-bit grid);
  the second applies those clips and records the 0.01% and 99.99%
  percentiles of the pre-ADC values.  The head is calibrated on the
  second pass's final normed hidden states.
* **serving** — inputs quantized to 8 bits by their clip, one dot per
  partition against ``g_pos - g_neg``, each output clipped to its range
  and rounded to 255 steps, partitions summed, rescaled.  Activations
  are kept in the configuration's ``dtype`` between sites, as the
  configuration states, each rounding made explicit so that no compiler
  folds it away; every matrix product is float32 at ``highest``
  precision.

``precision="high"`` runs the same chain with each float32 product
operand split into two bfloat16 parts (three passes), the precision one
step below the configuration's: the control that a comparison must
catch.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
COVERAGE = 0.9998
SITES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
SITE_PARENT = {"wq": "attn", "wk": "attn", "wv": "attn", "wo": "attn",
               "w_gate": "mlp", "w_up": "mlp", "w_down": "mlp"}


# ---------------------------------------------------------------------------
# arithmetic at the stated precision
# ---------------------------------------------------------------------------


def _split_bf16(a):
    hi = keep(a, jnp.bfloat16).astype(jnp.float32)
    lo = keep(a - hi, jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def mm(eq: str, a, b, precision: str):
    """float32 ``einsum`` at ``highest``, or three bfloat16 passes."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if precision == "highest":
        return jnp.einsum(eq, a, b, precision=HI)
    if precision == "bfloat16":
        ah, _ = _split_bf16(a)
        bh, _ = _split_bf16(b)
        return jnp.einsum(eq, ah, bh, precision=HI)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    ah, al = _split_bf16(a)
    bh, bl = _split_bf16(b)
    return (jnp.einsum(eq, ah, bh, precision=HI)
            + jnp.einsum(eq, ah, bl, precision=HI)
            + jnp.einsum(eq, al, bh, precision=HI))


# ---------------------------------------------------------------------------
# the analog design
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Design:
    """The analog design point, from the configuration's ``analog``."""

    qmax_w: int
    lmax: int
    g_min: float
    alpha: float
    qmax_x: int
    adc_levels: int
    max_rows: int

    @classmethod
    def of(cls, a: Dict[str, Any]) -> "Design":
        if not (a["scheme"] == "differential" and a["bits_per_cell"] is None
                and a["input_accum"] == "analog"
                and a["adc_style"] == "calibrated"
                and a["error"]["kind"] == "state_proportional"
                and float(a["r_hat"]) == 0.0):
            raise ValueError(f"the dense_analog reference covers "
                             f"differential, unsliced, analog-accumulation, "
                             f"calibrated-ADC designs without parasitics; "
                             f"got {a}")
        qmax_w = 2 ** (int(a["weight_bits"]) - 1) - 1
        return cls(qmax_w=qmax_w, lmax=qmax_w,            # 7-bit cells
                   g_min=1.0 / float(a["on_off_ratio"]),
                   alpha=float(a["error"]["alpha"]),
                   qmax_x=2 ** (int(a["input_bits"]) - 1) - 1,
                   adc_levels=2 ** int(a["adc_bits"]),
                   max_rows=int(a["max_rows"]))

    @property
    def gain(self) -> float:
        return self.lmax / (1.0 - self.g_min)

    def parts(self, k: int) -> Tuple[int, int]:
        p = max(1, -(-k // self.max_rows))
        return p, -(-k // p)


def site_key(key, name: str):
    h = hashlib.blake2s(name.encode(), digest_size=4).digest()
    return jax.random.fold_in(key, int.from_bytes(h, "big") & 0x7FFFFFFF)


def program(w, key, ds: Design):
    """(K, N) weights -> (g_pos, g_neg) of shape (P, rows, N), w_scale."""
    k, n = w.shape
    w = w.astype(jnp.float32)
    w_scale = jnp.maximum(jnp.max(jnp.abs(w)), 1e-12) / ds.qmax_w
    w_int = jnp.clip(jnp.round(w / w_scale), -ds.qmax_w, ds.qmax_w)
    pos = jnp.where(w_int > 0, jnp.abs(w_int), 0.0)
    neg = jnp.where(w_int < 0, jnp.abs(w_int), 0.0)
    p, rows = ds.parts(k)
    kp, kn, _ = jax.random.split(key, 3)

    def line(code, kk):
        g = ds.g_min + (1.0 - ds.g_min) * code / ds.lmax
        g = jnp.pad(g, ((0, p * rows - k), (0, 0))).reshape(1, p, rows, n)
        g = g + (ds.alpha * g) * jax.random.normal(kk, g.shape, jnp.float32)
        return g[0]

    return line(pos, kp), line(neg, kn), w_scale


def quantize(x, clip, ds: Design):
    """Signed 8-bit input codes and their scale."""
    scale = jnp.maximum(clip, 1e-12) / ds.qmax_x
    return jnp.clip(jnp.round(x / scale), -ds.qmax_x, ds.qmax_x), scale


def l1_clip(x, ds: Design):
    """L1-optimal clip of ``x`` over 32 log-spaced candidates."""
    flat = x.reshape(-1).astype(jnp.float32)
    absmax = jnp.maximum(jnp.max(jnp.abs(flat)), 1e-12)
    cands = absmax * jnp.exp(jnp.linspace(jnp.log(2.0 ** -6), 0.0, 32))

    def err(c):
        snapped = jnp.round(c / absmax * 4096.0) / 4096.0 * absmax
        q, s = quantize(flat, snapped, ds)
        return jnp.sum(jnp.abs(q * s - flat))

    return cands[jnp.argmin(jax.vmap(err)(cands))]


def pre_adc(x, g_pos, g_neg, clip, ds: Design, precision, *, split: bool):
    """Input codes (M, K) -> per-partition analog outputs (P, M, N)."""
    p, rows, _ = g_pos.shape
    xi, scale = quantize(x.astype(jnp.float32), clip, ds)
    xi = jnp.pad(xi, ((0, 0), (0, p * rows - xi.shape[1])))
    xp = xi.reshape(-1, p, rows)
    if split:        # the two lines read out separately, then subtracted
        return (mm("mpr,prn->pmn", xp, g_pos, precision)
                - mm("mpr,prn->pmn", xp, g_neg, precision)), scale
    return mm("mpr,prn->pmn", xp, g_pos - g_neg, precision), scale


def analog_site(x, site, clip, lo, hi, ds: Design, precision):
    """Served ``x @ W`` through the ADC: (M, K) -> (M, N) float32."""
    g_pos, g_neg, w_scale = site
    v, scale = pre_adc(x, g_pos, g_neg, clip, ds, precision, split=False)
    lsb = (hi - lo) / (ds.adc_levels - 1)
    code = jnp.clip(jnp.round((v - lo) / lsb), 0, ds.adc_levels - 1)
    return jnp.sum(lo + code * lsb, axis=0) * ds.gain * w_scale * scale


def collect_site(x, site, clip, ds: Design, precision):
    """Calibration pass of one site: output with no ADC, the L1 clip of
    its input, and the percentile range of its pre-ADC values."""
    g_pos, g_neg, w_scale = site
    dyn = clip is None
    use = jnp.max(jnp.abs(x.astype(jnp.float32))) if dyn else clip
    v, scale = pre_adc(x, g_pos, g_neg, use, ds, precision, split=True)
    y = jnp.sum(v * ds.gain, axis=0) * w_scale * scale
    tail = (1.0 - COVERAGE) / 2.0 * 100.0
    flat = v.reshape(-1)
    rng = (jnp.percentile(flat, tail), jnp.percentile(flat, 100.0 - tail))
    return y, l1_clip(x, ds), rng


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def keep(x, dtype):
    """``x`` rounded to ``dtype``, the activation type between sites.

    A 16-bit type is rounded by an explicit ``reduce_precision`` first:
    XLA may fold a float32 -> bfloat16 -> float32 round trip away
    (excess precision), and on a TPU it does so inside some fusions and
    not others, so a plain cast does not make every consumer see the
    rounded value."""
    dt = jnp.dtype(dtype)
    if dt.itemsize == 2:
        fi = jnp.finfo(dt)
        x = jax.lax.reduce_precision(x.astype(jnp.float32),
                                     exponent_bits=fi.nexp,
                                     mantissa_bits=fi.nmant)
    return x.astype(dt)


def rms_norm(x, scale, eps):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return keep(xf * (1.0 + scale.astype(jnp.float32)), x.dtype)


def rope(x, pos, theta):
    """x: (S, H, hd); rotate-half rotary embedding at positions ``pos``."""
    hd = x.shape[-1]
    half = hd // 2
    idx = jnp.arange(hd)
    freqs = theta ** (-(idx % half).astype(jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xf = x.astype(jnp.float32)
    rot = jnp.concatenate([-xf[..., half:], xf[..., :half]], axis=-1)
    return keep(xf * cos + rot * sin, x.dtype)


def attention(q, k, v, precision):
    """Causal attention of one sequence: q (S, H, hd), k/v (S, KV, hd)."""
    s, h, hd = q.shape
    kvh = k.shape[1]
    g = h // kvh
    qg = q.reshape(s, kvh, g, hd).astype(jnp.float32) * hd ** -0.5
    sc = mm("qkgd,pkd->kgqp", qg, k, precision)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    sc = jnp.where(causal, sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    out = mm("kgqp,pkd->qkgd", p, v, precision)
    return keep(out.reshape(s, h, hd), q.dtype)


@dataclasses.dataclass(frozen=True)
class Model:
    """Static description of the model and how the reference computes."""

    dtype: str
    h: int
    kv: int
    hd: int
    eps: float
    theta: float
    qk_norm: bool
    qkv_bias: bool
    ds: Design
    precision: str

    @classmethod
    def of(cls, c: Dict[str, Any], precision: str) -> "Model":
        """``precision``: ``highest`` as configured; ``high`` or
        ``bfloat16`` for the float32 products; ``float8`` for the
        activations (float8_e4m3fn in place of the configured dtype)."""
        dtype = c["dtype"]
        if precision == "float8":
            dtype, precision = "float8_e4m3fn", "highest"
        return cls(dtype=dtype, h=int(c["num_attention_heads"]),
                   kv=int(c["num_key_value_heads"]),
                   hd=int(c.get("head_dim") or c["hidden_size"]
                          // c["num_attention_heads"]),
                   eps=float(c["rms_norm_eps"]),
                   theta=float(c["rope_theta"]),
                   qk_norm=bool(c["architecture"]["qk_norm"]),
                   qkv_bias=bool(c["architecture"]["qkv_bias"]),
                   ds=Design.of(c["analog"]), precision=precision)

    @property
    def dt(self):
        return jnp.dtype(self.dtype)

    def add(self, a, b):
        """``a + b`` kept in the activation dtype."""
        return keep(a.astype(jnp.float32) + b.astype(jnp.float32), self.dt)

    def gated(self, g, u):
        sg = keep(jax.nn.silu(g.astype(jnp.float32)), self.dt)
        return keep(sg.astype(jnp.float32) * u.astype(jnp.float32), self.dt)

    def values(self, a, h_in, dense):
        """The attention values of normed rows, as the KV cache holds
        them: ``wv`` then its bias, in the activation dtype."""
        v = dense("wv", h_in)
        if self.qkv_bias:
            v = self.add(v, a["bv"])
        return v

    def block(self, p, x, dense):
        """One decoder layer over a (B, S, d) batch of whole sequences:
        projections on all B*S rows at once (``dense(site, rows) ->
        rows`` in dtype), attention per sequence."""
        b, s, d = x.shape
        pos = jnp.arange(s)
        a = p["attn"]
        h_in = rms_norm(x, p["norm1"]["scale"], self.eps).reshape(b * s, d)

        def proj(name, bias, heads):
            y = dense(name, h_in)
            if self.qkv_bias:
                y = self.add(y, a[bias])
            y = y.reshape(b, s, heads, self.hd)
            if self.qk_norm:
                y = rms_norm(y, a["q_norm" if name == "wq" else "k_norm"],
                             self.eps)
            return y

        q = proj("wq", "bq", self.h)
        k = proj("wk", "bk", self.kv)
        v = self.values(a, h_in, dense).reshape(b, s, self.kv, self.hd)
        q = jax.vmap(lambda t: rope(t, pos, self.theta))(q)
        k = jax.vmap(lambda t: rope(t, pos, self.theta))(k)
        o = jax.vmap(lambda q_, k_, v_: attention(q_, k_, v_, self.precision)
                     )(q, k, v).reshape(b * s, self.h * self.hd)
        x = self.add(x, dense("wo", o).reshape(b, s, d))
        h2 = rms_norm(x, p["norm2"]["scale"], self.eps).reshape(b * s, d)
        m = self.gated(dense("w_gate", h2), dense("w_up", h2))
        return self.add(x, dense("w_down", m).reshape(b, s, d))


def _layer_params(params, l: int):
    lp = params["layers"]
    return {"norm1": {"scale": lp["norm1"]["scale"][l]},
            "norm2": {"scale": lp["norm2"]["scale"][l]},
            "attn": {k: v[l] for k, v in lp["attn"].items()},
            "mlp": {k: v[l] for k, v in lp["mlp"].items()}}


@functools.partial(jax.jit, static_argnums=3)
def _program_layer(lp, key, l, m: Model):
    return {name: program(lp[SITE_PARENT[name]][name],
                          jax.random.fold_in(site_key(key, name), l), m.ds)
            for name in SITES}


@functools.partial(jax.jit, static_argnums=4)
def _calib_layer(lp, sites, x, clips, m: Model):
    """One layer of a calibration pass; ``clips=None`` quantizes each
    site's input by its own absolute maximum."""
    found_clip, found_rng = {}, {}

    def dense(name, rows):
        y, cl, rng = collect_site(
            rows, sites[name], None if clips is None else clips[name],
            m.ds, m.precision)
        found_clip[name], found_rng[name] = cl, rng
        return keep(y, m.dt)

    return m.block(lp, x, dense), found_clip, found_rng


@functools.partial(jax.jit, static_argnums=6)
def _serve_layer(lp, sites, x, clips, lo, hi, m: Model):
    def dense(name, rows):
        return keep(analog_site(rows, sites[name], clips[name], lo[name],
                                hi[name], m.ds, m.precision), m.dt)
    return m.block(lp, x, dense)


@functools.partial(jax.jit, static_argnums=6)
def _first_values(lp, sites, x, clips, lo, hi, m: Model):
    b, s, d = x.shape
    h_in = rms_norm(x, lp["norm1"]["scale"], m.eps).reshape(b * s, d)

    def dense(name, rows):
        return keep(analog_site(rows, sites[name], clips[name], lo[name],
                                hi[name], m.ds, m.precision), m.dt)

    return m.values(lp["attn"], h_in, dense).reshape(b, s, m.kv, m.hd)


@functools.partial(jax.jit, static_argnums=2)
def _head_program(w, key, m: Model):
    return program(w, site_key(key, "head"), m.ds)


@functools.partial(jax.jit, static_argnums=3)
def _head_calib(site, x, scale, m: Model):
    h = rms_norm(x, scale, m.eps)
    h = h.reshape(-1, h.shape[-1])
    clip = l1_clip(h, m.ds)
    v, _ = pre_adc(h, site[0], site[1], clip, m.ds, m.precision, split=True)
    tail = (1.0 - COVERAGE) / 2.0 * 100.0
    flat = v.reshape(-1)
    return clip, jnp.percentile(flat, tail), jnp.percentile(flat,
                                                            100.0 - tail)


@functools.partial(jax.jit, static_argnums=6)
def _head_serve(site, x, scale, clip, lo, hi, m: Model):
    h = rms_norm(x, scale, m.eps)
    return analog_site(h, site, clip, lo, hi, m.ds, m.precision)


def logits(c: Dict[str, Any], params, k_prog, calib_tokens: np.ndarray,
           sequences: List[np.ndarray], starts: List[int],
           precision: str = "highest", length: int = 0, rows: int = 0):
    """Reference logits of each sequence at positions ``start ..`` its
    end, as float32 numpy arrays, and the reference head's ADC code in
    logit units.  Runs layer by layer: one layer's conductances at a
    time are on the device, beside the weights.

    Every sequence is zero-padded to ``length`` tokens and the head runs
    on ``rows`` positions from each start, so that one compiled program
    serves every sequence; padding sits after every compared position,
    where causal attention never sees it."""
    m = Model.of(c, precision)
    length = max([length] + [len(s) for s in sequences])
    rows = max([rows] + [len(s) - st for s, st in zip(sequences, starts)])

    def embed(toks):
        return params["embed"][jnp.asarray(toks)].astype(m.dt)

    padded = [np.concatenate([s, np.zeros(length - len(s), np.int32)])
              for s in sequences]

    x1 = embed(calib_tokens)
    x2 = x1
    xs = [embed(s[None]) for s in padded]
    for l in range(int(c["num_hidden_layers"])):
        lp = _layer_params(params, l)
        sites = _program_layer(lp, k_prog, l, m)
        x1, clips, _ = _calib_layer(lp, sites, x1, None, m)
        x2, _, rngs = _calib_layer(lp, sites, x2, clips, m)
        lo = {n: r[0] for n, r in rngs.items()}
        hi = {n: r[1] for n, r in rngs.items()}
        xs = [_serve_layer(lp, sites, x, clips, lo, hi, m) for x in xs]
        del sites

    scale = params["final_norm"]["scale"]
    head = _head_program(params["lm_head"], k_prog, m)
    clip, lo, hi = _head_calib(head, x2, scale, m)
    out = []
    for x, s, start in zip(xs, sequences, starts):
        at = np.minimum(start + np.arange(rows), length - 1)
        lg = _head_serve(head, x[0, at], scale, clip, lo, hi, m)
        out.append(np.asarray(lg[:len(s) - start], np.float32))
    ds = m.ds
    code = float(ds.gain * head[2] * (clip / ds.qmax_x)
                 * (hi - lo) / (ds.adc_levels - 1))
    return out, code


def first_layer_values(c: Dict[str, Any], params, k_prog,
                       calib_tokens: np.ndarray,
                       sequences: List[np.ndarray],
                       precision: str = "highest", length: int = 0,
                       batch: int = 0):
    """The first layer's attention values of each sequence, as its KV
    cache would hold them (float32 numpy, ``(len, kv_heads, head_dim)``),
    and one ADC code of the ``wv`` site in their units.

    Nothing before the first layer's sites depends on earlier positions
    or layers, so two runs of the same design that compute alike give
    these values to the code; a product in lower precision moves one
    value in ten by a code.  Sequences are zero-padded to ``length``
    tokens and ``batch`` rows, so that one program serves a cell."""
    m = Model.of(c, precision)
    length = max([length] + [len(s) for s in sequences])
    batch = max(batch, len(sequences))
    lp = _layer_params(params, 0)
    sites = _program_layer(lp, k_prog, 0, m)
    x = params["embed"][jnp.asarray(calib_tokens)].astype(m.dt)
    _, clips, _ = _calib_layer(lp, sites, x, None, m)
    _, _, rngs = _calib_layer(lp, sites, x, clips, m)
    lo = {n: r[0] for n, r in rngs.items()}
    hi = {n: r[1] for n, r in rngs.items()}
    toks = np.zeros((batch, length), np.int32)
    for i, s in enumerate(sequences):
        toks[i, :len(s)] = s
    xs = params["embed"][jnp.asarray(toks)].astype(m.dt)
    v = np.asarray(_first_values(lp, sites, xs, clips, lo, hi, m),
                   np.float32)
    ds = m.ds
    code = float(ds.gain * sites["wv"][2] * (clips["wv"] / ds.qmax_x)
                 * (hi["wv"] - lo["wv"]) / (ds.adc_levels - 1))
    return [v[i, :len(s)] for i, s in enumerate(sequences)], code
