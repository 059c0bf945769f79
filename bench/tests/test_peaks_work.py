"""The peak table and the implementation-independent work counts."""
from __future__ import annotations

import pytest

from bench import peaks, work

QWEN = {"hidden_size": 1024, "intermediate_size": 2816,
        "num_hidden_layers": 24, "num_attention_heads": 16,
        "num_key_value_heads": 16, "head_dim": 64, "vocab_size": 151936,
        "bloom_m": 30208, "bloom_k": 4}


@pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v5e"])
def test_v5e_peaks(kind):
    p = peaks.peak_for(kind)
    assert p.flops == 197e12 and p.hbm_bytes_s == 819e9
    assert "TPU v5e" in p.source


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
def test_unknown_device_is_an_error(kind):
    with pytest.raises(KeyError, match="no peak rates"):
        peaks.peak_for(kind)


def test_roofline_names_its_bound():
    p = peaks.peak_for("TPU v5e")
    t, bound = peaks.roofline_s(p, ops=197e12, nbytes=1.0)
    assert (t, bound) == (pytest.approx(1.0), "compute")
    t, bound = peaks.roofline_s(p, ops=1.0, nbytes=819e9)
    assert (t, bound) == (pytest.approx(1.0), "memory")


def test_decode_topk_counts_scale_with_rows():
    ops, nbytes = work.decode_topk(8, d=10_000_000, m=8192, k=2, topk=10)
    assert ops == 8 * (10_000_000 * 2 + 10_000_000)
    assert nbytes == 8 * (8192 * 4 + 10 * 8)
    assert work.decode_topk(0, d=10, m=4, k=2, topk=1) == (0.0, 0.0)


def test_retrieval_query_flops():
    f = work.retrieval_query_flops(d=100, m=16, k=2, hidden=[4, 8])
    assert f == 2 * (16 * 4 + 4 * 8 + 8 * 16) + 100 * 2


def test_lm_matmul_params_match_the_published_size():
    # 24 layers of attention and SwiGLU plus the 30,208-row tied head
    per_layer = 4 * 1024 * 1024 + 3 * 1024 * 2816
    assert work.lm_matmul_params(QWEN) == 24 * per_layer + 1024 * 30208


def test_lm_prefill_and_decode_flops():
    P = work.lm_matmul_params(QWEN)
    att = 4 * 16 * 64 * 24                  # per attended key
    rec = 151936 * 4
    assert work.lm_prefill_flops(QWEN, 3) == pytest.approx(
        2 * P * 3 + att * 6 + rec)
    assert work.lm_decode_flops(QWEN, 2, keys=10) == pytest.approx(
        2 * (2 * P + rec) + att * 10)
    lens = [128, 1024, 7]
    assert work.lm_prefill_flops_sum(
        QWEN, len(lens), sum(lens), sum(v * v for v in lens)) == \
        pytest.approx(sum(work.lm_prefill_flops(QWEN, v) for v in lens))
