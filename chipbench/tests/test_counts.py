"""FLOP and byte counts against hand reckoning, and the peak table."""
import json

import pytest
from conftest import BENCH_DIR

from harness import counts


def conf(name):
    with open(BENCH_DIR / "configs" / f"{name}.json") as f:
        return json.load(f)


def test_stablelm_weights_and_cache():
    c = conf("stablelm-1.6b")
    # 24 x (4 x 2048^2 + 3 x 2048 x 5632) + 2048 x 100352
    assert counts.weight_elements(c) == 24 * (4 * 2048 ** 2
                                              + 3 * 2048 * 5632) \
        + 2048 * 100352 == 1_438_646_272
    # INT2 codes + 2-bit cluster ids: half a byte per element, plus
    # 3 scales and 3 zeros of 4 bytes for each of 24 x 7 + 1 matrices
    assert counts.weight_bytes(c) == 1_438_646_272 / 2 + 169 * 24
    # K and V: 32 heads x 64 codes + 32 heads x 4 chunks x (scale, zero)
    assert counts.kv_bytes_per_token(c) == 24 * 2 * (32 * 64 + 32 * 4 * 8) \
        == 147_456


def test_stablelm_decode_step_bytes():
    """16 slots about 700 positions deep: 0.72 GB of weights and 1.65 GB
    of live cache, as reckoned by hand."""
    c = conf("stablelm-1.6b")
    pos = [699] * 16
    b = counts.decode_step_bytes(c, pos)
    weights = 1_438_646_272 / 2 + 169 * 24
    kv = 16 * 700 * 147_456 + 16 * 147_456
    # embedding rows, and 49 LayerNorms of a gain and a bias
    small = 16 * 2048 * 2 + 49 * 2 * 2048 * 2
    assert b == weights + kv + small
    assert 0.719e9 < weights < 0.720e9
    assert 1.65e9 < kv < 1.654e9


def test_chatglm_cache_and_weights():
    c = conf("chatglm3-6b")
    # 7 of 28 layers; 2 KV heads of 128, 4 chunks of (scale, zero)
    assert counts.kv_bytes_per_token(c) == 7 * 2 * (2 * 128 + 2 * 4 * 8) \
        == 4480
    # 7 x (2 x 4096^2 + 2 x 4096 x 256 + 3 x 4096 x 13696) + 4096 x 65024
    assert counts.weight_elements(c) == 1_693_974_528


def test_flops():
    c = conf("stablelm-1.6b")
    W = counts.weight_elements(c)
    assert counts.token_flops(c, 100) == 2 * W + 4 * 24 * 32 * 64 * 100
    # a chunk of 3 tokens at 5: keys 6 + 7 + 8; head for one row only
    f = counts.chunk_flops(c, 5, 3)
    assert f == 2 * 3 * (W - 2048 * 100352) + 2 * 2048 * 100352 \
        + 4 * 24 * 32 * 64 * 21


def test_peaks():
    pk = counts.peaks("TPU v5 lite")
    assert (pk["bf16_flops"], pk["hbm_bytes_per_s"]) == (197e12, 819e9)
    assert "TPU v5e" in pk["source"]
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")
    assert counts.least_time(197e12, 0, pk) == 1.0
    assert counts.least_time(0, 819e9, pk) == 1.0
