"""``decode_glue_ms`` on the grouped-query cell (``qwen3-14b.chat``), read as
``metrics/decode_glue_ms.py`` reads it."""

from chipbench import spec

read = spec.metric_reader("decode_glue_ms")
