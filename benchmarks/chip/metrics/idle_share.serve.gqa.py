"""``idle_share.serve`` on the grouped-query cell (``qwen3-14b.chat``), read as
``metrics/idle_share.serve.py`` reads it."""

from chipbench import spec

read = spec.metric_reader("idle_share.serve")
