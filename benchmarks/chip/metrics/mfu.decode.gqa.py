"""``mfu.decode`` on the grouped-query cell (``qwen3-14b.chat``), read as
``metrics/mfu.decode.py`` reads it."""

from chipbench import spec

read = spec.metric_reader("mfu.decode")
