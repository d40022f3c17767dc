"""Operations and bytes each kernel's work needs, from shapes alone.

These count what the algorithm needs, not what today's implementation
happens to move:

* a fused analog MVM of ``M`` rows through a ``K x N`` site reads one
  float32 effective conductance per weight and slice (4 B), its float32
  inputs and outputs and the ADC ranges, and does ``2 M K N`` operations
  per slice and per input pass (one pass under analog accumulation, one
  per input bit under digital accumulation).  A kernel that reads both
  lines of the differential pair moves twice the conductance bytes
  counted here, so it cannot pass half of the bytes roofline;
* flash-decode attention over a fill of ``f`` positions reads the keys
  and values held there (in the cache's dtype) and does ``4 H hd f``
  operations per layer;
* the model's operations per token are 2 per analog-site weight, plus
  attention over the tokens before it.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Tuple

import jax.numpy as jnp

from chipbench.model import head_dim, site_weights

LAYER_SITES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def _passes(c: Dict[str, Any]) -> Tuple[int, int]:
    a = c["analog"]
    slices = 1 if a["bits_per_cell"] is None else -(
        -(int(a["weight_bits"]) - 1) // int(a["bits_per_cell"]))
    passes = 1 if a["input_accum"] == "analog" else int(a["input_bits"]) - 1
    return slices, passes


def fused_mvm(c: Dict[str, Any], m: int, k: int, n: int) -> Tuple[int, int]:
    """(operations, bytes) of one fused MVM call."""
    slices, passes = _passes(c)
    flops = 2 * m * k * n * slices * passes
    nbytes = 4 * k * n * slices + 4 * m * k + 4 * m * n + 8 * slices
    return flops, nbytes


def decode_mvms(c: Dict[str, Any], rows: int) -> Tuple[int, int]:
    """Every fused MVM of one decode step over ``rows`` slots."""
    sw = site_weights(c)
    layers = int(c["num_hidden_layers"])
    f = b = 0
    for s in LAYER_SITES:
        fs, bs = fused_mvm(c, rows, *sw[s])
        f, b = f + layers * fs, b + layers * bs
    fs, bs = fused_mvm(c, rows, *sw["head"])
    return f + fs, b + bs


def flash_decode(c: Dict[str, Any], fills: Iterable[int]) -> Tuple[int, int]:
    """(operations, bytes) of flash-decode attention over every layer for
    lanes at the given fills."""
    layers = int(c["num_hidden_layers"])
    h, kv, hd = (int(c["num_attention_heads"]),
                 int(c["num_key_value_heads"]), head_dim(c))
    kv_bytes = jnp.dtype(c["dtype"]).itemsize
    f = b = 0
    for fill in fills:
        f += layers * 4 * h * hd * fill
        b += layers * (2 * fill * kv * hd * kv_bytes + 2 * h * hd * 4)
    return f, b


def site_weight_count(c: Dict[str, Any]) -> Tuple[int, int]:
    """(weights of the layers' analog sites, weights of the head)."""
    sw = site_weights(c)
    layers = int(c["num_hidden_layers"])
    per_layer = sum(k * n for s, (k, n) in sw.items() if s != "head")
    k, n = sw["head"]
    return layers * per_layer, k * n


def model_flops_decode(c: Dict[str, Any], fills: Iterable[int]) -> int:
    """Model operations of one decoded token per lane at these fills."""
    layer_w, head_w = site_weight_count(c)
    fills = list(fills)
    attn, _ = flash_decode(c, fills)
    return 2 * (layer_w + head_w) * len(fills) + attn


def roofline_time(flops: float, nbytes: float, pk: Dict) -> float:
    """Least time the chip could take: bound by operations or by bytes."""
    return max(flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"])
