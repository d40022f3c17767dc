"""Operation and byte counts of each kernel against hand counts at
qwen1.5-4b's shapes and at Qwen3-14B's (GQA), and the peak table."""

import pytest

from chipbench import costs, peaks, spec


def _cfg(name):
    return spec.load_json(f"{spec.BENCH_DIR}/configs/{name}.json")


def _qwen3_14b():
    """Qwen3-14B's published widths (GQA, 40 query and 8 KV heads of
    128) on 2 layers with a quarter of the padded vocabulary, as one chip
    of a vocab-parallel, pipelined deployment would hold them."""
    return dict(_cfg("qwen1.5-4b"), hidden_size=5120,
                intermediate_size=17408, head_dim=128,
                num_attention_heads=40, num_key_value_heads=8,
                num_hidden_layers=2, vocab_size=38016)


def test_fused_mvm_counts_one_f32_conductance_per_weight():
    c = _cfg("qwen1.5-4b")
    # wq of qwen1.5-4b for 16 decode rows: 2*16*2560*2560 operations;
    # 4 B per weight, f32 rows in and out, one ADC range pair
    f, b = costs.fused_mvm(c, 16, 2560, 2560)
    assert f == 2 * 16 * 2560 * 2560
    assert b == 4 * 2560 * 2560 + 4 * 16 * 2560 + 4 * 16 * 2560 + 8


def test_decode_step_mvms_qwen15():
    c = _cfg("qwen1.5-4b")
    layer = 4 * 2560 * 2560 + 3 * 2560 * 6912          # q k v o, gate up down
    head = 2560 * 151936
    f, b = costs.decode_mvms(c, 16)
    assert f == 2 * 16 * (8 * layer + head)
    rows = 8 * (4 * (2560 + 2560) + 2 * (2560 + 6912) + (6912 + 2560)) \
        + (2560 + 151936)
    assert b == 4 * (8 * layer + head) + 4 * 16 * rows + 8 * (8 * 7 + 1)


def test_decode_step_mvms_qwen3_gqa_and_sliced_vocab():
    c = _qwen3_14b()
    layer = 2 * 5120 * 5120 + 2 * 5120 * 1024 + 3 * 5120 * 17408
    assert costs.site_weight_count(c) == (2 * layer, 5120 * 38016)
    f, _ = costs.decode_mvms(c, 16)
    assert f == 2 * 16 * (2 * layer + 5120 * 38016)


def test_flash_decode_reads_bf16_cache_of_the_fill():
    c = _qwen3_14b()
    f, b = costs.flash_decode(c, [100, 300])
    # 2 layers, 40 query heads of 128 over 8 KV heads
    assert f == 2 * 4 * 40 * 128 * 400
    assert b == 2 * (2 * 400 * 8 * 128 * 2) + 2 * 2 * (2 * 40 * 128 * 4)


def test_model_flops():
    c = _cfg("qwen1.5-4b")
    layer_w, head_w = costs.site_weight_count(c)
    assert costs.model_flops_decode(c, [10]) == (
        2 * (layer_w + head_w) + 8 * 4 * 20 * 128 * 10)


def test_roofline_time_takes_the_binding_bound():
    pk = peaks.peaks("TPU v5 lite")
    assert costs.roofline_time(197e12, 0, pk) == pytest.approx(1.0)
    assert costs.roofline_time(0, 819e9, pk) == pytest.approx(1.0)


def test_unknown_device_kind_is_refused():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
