"""Work counts and peaks against hand-worked shapes."""

import json

import pytest

from bench import workcounts as wc
from bench.harness import BENCH
from bench.peaks import peaks_for

# d=4, 2 layers, 2 heads of 2 over 1 KV head, ff 8, vocab 10, bf16
SMALL = wc.Dims(d=4, layers=2, heads=2, kv_heads=1, head_dim=2, ff=8,
                vocab=10, dtype_bytes=2)


def test_layer_params_and_kv_line():
    # q 4x4 + k 4x2 + v 4x2 + o 4x4 = 48; gated MLP 3 x 4 x 8 = 96
    assert SMALL.layer_matmul_params == 144
    # 2 (k, v) x 2 layers x 1 head x 2 dims x 2 bytes
    assert SMALL.kv_line_bytes == 16


def test_token_and_prefill_flops():
    # matmuls 2 x 2 x 144; attention 4 x 2 x 2 x 2 x keys; head 2 x 4 x 10
    assert wc.token_flops(SMALL, 3) == 576 + 96 + 80
    assert wc.decode_token_flops(SMALL, 3) == 752
    # three prompt tokens attend to 1 + 2 + 3 keys; head at the last only
    assert wc.prefill_flops(SMALL, 3) == 3 * 576 + 32 * 6 + 80
    # a prefix hit of 1 skips the first token's matmuls and its key
    assert wc.prefill_flops(SMALL, 3, skip=1) == 2 * 576 + 32 * 5 + 80
    assert wc.prefill_flops(SMALL, 3, skip=3) == 0


def test_paged_attention_work_and_tp_share():
    flops, nbytes = wc.paged_attention_work(SMALL, [3, 5])
    assert flops == 4 * 2 * 2 * 2 * 8
    # 8 live K/V lines of 16 bytes, q and o of 2 slots: 2 x 2 x 2 x 2 x 2
    assert nbytes == 8 * 16 + 2 * 32
    four = wc.Dims(d=8, layers=1, heads=4, kv_heads=2, head_dim=2, ff=8,
                   vocab=10, dtype_bytes=2)
    f1, b1 = wc.paged_attention_work(four, [10])
    f2, b2 = wc.paged_attention_work(four, [10], tp=2)
    assert (f2, b2) == (f1 / 2, b1 / 2)


def test_least_time_takes_the_binding_roof():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert wc.least_time_s(1000.0, 10.0, peaks) == 10.0
    assert wc.least_time_s(100.0, 100.0, peaks) == 10.0


def test_published_configs():
    cfg = json.loads((BENCH / "configs" / "qwen3-0.6b.json").read_text())
    d = wc.Dims.of(cfg)
    assert d.layers * d.layer_matmul_params == 440_401_920
    # Qwen/Qwen3-14B's published sizes
    d = wc.Dims(d=5120, layers=40, heads=40, kv_heads=8, head_dim=128,
                ff=17408, vocab=151936, dtype_bytes=2)
    assert d.layers * d.layer_matmul_params == 13_212_057_600


def test_peaks_are_keyed_by_device_kind():
    v5e = peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
