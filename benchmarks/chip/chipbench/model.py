"""A configuration file turned into what the program under test takes.

The configuration files use the published ``config.json`` key names, so
``reduced`` can name the keys a cell changed.  This module maps them
onto the program's ``ModelConfig`` and ``AnalogSpec``, and makes the
weights: on the device, in one jitted call, in the type they are served
in, from the seed.  The weights are the benchmark's, not the program's:
the plain reference reads the same arrays and nothing the program made.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

#: ``rms_norm`` epsilon the program applies; a configuration that states
#: another cannot be served as stated
PROGRAM_RMS_EPS = 1e-6


def head_dim(c: Dict[str, Any]) -> int:
    return int(c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"])


def model_config(c: Dict[str, Any]):
    """The program's ``ModelConfig`` for configuration file ``c``."""
    from repro.config import ModelConfig

    if c["hidden_act"] != "silu":
        raise ValueError(f"{c['name']}: hidden_act {c['hidden_act']!r}; "
                         "the dense reference serves silu (swiglu) only")
    if float(c["rms_norm_eps"]) != PROGRAM_RMS_EPS:
        raise ValueError(f"{c['name']}: rms_norm_eps {c['rms_norm_eps']} "
                         f"but the program's rms_norm uses {PROGRAM_RMS_EPS}")
    if c.get("tie_word_embeddings"):
        raise ValueError(f"{c['name']}: tied embeddings are not served here")
    arch = c["architecture"]
    return ModelConfig(
        name=c["name"], family="dense",
        n_layers=int(c["num_hidden_layers"]),
        d_model=int(c["hidden_size"]),
        n_heads=int(c["num_attention_heads"]),
        n_kv_heads=int(c["num_key_value_heads"]),
        head_dim=head_dim(c),
        d_ff=int(c["intermediate_size"]),
        vocab=int(c["vocab_size"]),
        act="swiglu", norm="rmsnorm",
        qkv_bias=bool(arch["qkv_bias"]), qk_norm=bool(arch["qk_norm"]),
        rope_theta=float(c["rope_theta"]),
        dtype=c["dtype"], remat=False,
    )


def analog_spec(a: Dict[str, Any]):
    """The program's ``AnalogSpec`` for the configuration's ``analog``
    block (the design point every site is programmed at)."""
    from repro.core import adc, analog, errors, mapping

    return analog.AnalogSpec(
        mapping=mapping.MappingConfig(
            scheme=a["scheme"], weight_bits=int(a["weight_bits"]),
            bits_per_cell=a["bits_per_cell"],
            on_off_ratio=float(a["on_off_ratio"])),
        adc=adc.ADCConfig(style=a["adc_style"], bits=int(a["adc_bits"])),
        error=errors.ErrorModel(kind=a["error"]["kind"],
                                alpha=float(a["error"]["alpha"])),
        input_bits=int(a["input_bits"]),
        input_accum=a["input_accum"],
        max_rows=int(a["max_rows"]),
        r_hat=float(a["r_hat"]),
        fused=a["fused"],
        compute_dtype=jnp.dtype(a.get("compute_dtype", "float32")),
    )


def param_shapes(c: Dict[str, Any]) -> List[Tuple[Tuple[str, ...], tuple,
                                                  float]]:
    """``(path, shape, std)`` of every weight, in the program's layout:
    per-layer leaves stacked over ``L``.  Norm scales are stored as the
    offset from 1 (``x * (1 + scale)``)."""
    d, ff, v = (int(c["hidden_size"]), int(c["intermediate_size"]),
                int(c["vocab_size"]))
    l = int(c["num_hidden_layers"])
    h, kv, hd = (int(c["num_attention_heads"]),
                 int(c["num_key_value_heads"]), head_dim(c))
    arch = c["architecture"]
    out = [
        (("embed",), (v, d), d ** -0.5),
        (("final_norm", "scale"), (d,), 0.1),
        (("lm_head",), (d, v), d ** -0.5),
        (("layers", "norm1", "scale"), (l, d), 0.1),
        (("layers", "norm2", "scale"), (l, d), 0.1),
        (("layers", "attn", "wq"), (l, d, h * hd), d ** -0.5),
        (("layers", "attn", "wk"), (l, d, kv * hd), d ** -0.5),
        (("layers", "attn", "wv"), (l, d, kv * hd), d ** -0.5),
        (("layers", "attn", "wo"), (l, h * hd, d), (h * hd) ** -0.5),
        (("layers", "mlp", "w_gate"), (l, d, ff), d ** -0.5),
        (("layers", "mlp", "w_up"), (l, d, ff), d ** -0.5),
        (("layers", "mlp", "w_down"), (l, ff, d), ff ** -0.5),
    ]
    if arch["qkv_bias"]:
        out += [(("layers", "attn", "bq"), (l, h * hd), 0.1),
                (("layers", "attn", "bk"), (l, kv * hd), 0.1),
                (("layers", "attn", "bv"), (l, kv * hd), 0.1)]
    if arch["qk_norm"]:
        out += [(("layers", "attn", "q_norm"), (l, hd), 0.1),
                (("layers", "attn", "k_norm"), (l, hd), 0.1)]
    return out


def make_params(c: Dict[str, Any], key: jax.Array) -> Dict[str, Any]:
    """Seeded normal weights in ``c["dtype"]``, made on the device in one
    jitted call."""
    shapes = param_shapes(c)
    dtype = jnp.dtype(c["dtype"])

    def build(key):
        tree: Dict[str, Any] = {}
        for i, (path, shape, std) in enumerate(shapes):
            leaf = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32) * std).astype(dtype)
            node = tree
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = leaf
        return tree

    return jax.jit(build)(key)


def site_weights(c: Dict[str, Any]) -> Dict[str, Tuple[int, int]]:
    """``(K, N)`` of every analog site of one layer, and of the head."""
    d, ff = int(c["hidden_size"]), int(c["intermediate_size"])
    h, kv, hd = (int(c["num_attention_heads"]),
                 int(c["num_key_value_heads"]), head_dim(c))
    return {"wq": (d, h * hd), "wk": (d, kv * hd), "wv": (d, kv * hd),
            "wo": (h * hd, d), "w_gate": (d, ff), "w_up": (d, ff),
            "w_down": (ff, d), "head": (d, int(c["vocab_size"]))}
