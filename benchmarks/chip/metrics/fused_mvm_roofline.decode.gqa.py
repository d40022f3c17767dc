"""``fused_mvm_roofline.decode`` on the grouped-query cell (``qwen3-14b.chat``), read as
``metrics/fused_mvm_roofline.decode.py`` reads it."""

from chipbench import spec

read = spec.metric_reader("fused_mvm_roofline.decode")
