"""costs/: operations and bytes of a decode step at the two
configurations' sizes, against hand figures (ISSUE 23, PERF.md §3)."""

import json
import os

import pytest

from benchmark import costs, weights
from benchmark.costs import decode


def _cfg(root, name):
    with open(os.path.join(root, "benchmark", "configs", f"{name}.json")) as f:
        return json.load(f)


def test_minitron_decode_step(root):
    cfg = _cfg(root, "minitron-4b")
    c = cfg["llama_config"]
    assert weights.num_params(cfg) == pytest.approx(4.19e9, rel=0.01)
    assert decode.cache_bytes_per_token(c) == 32 * 8 * 128 * 2 * 2  # 131 KB
    d = decode.decode_step(c, batch=16, context=2048)
    # all weights but the embedding table (a gather): 8.4 - 1.57 GB
    assert d["weight_bytes"] == pytest.approx(8.38e9 - 1.573e9, rel=0.01)
    assert d["cache_bytes"] == pytest.approx(4.29e9, rel=0.01)  # 16 x 2048 rows
    roof = costs.roofline_seconds(d["flops"], d["bytes"], "TPU v5 lite")
    assert roof["bound"] == "memory"
    assert roof["seconds"] == pytest.approx((6.81e9 + 4.29e9) / 819e9, rel=0.02)


def test_deepseek_decode_step(root):
    cfg = _cfg(root, "deepseek-v2-lite-9l")
    c = cfg["llama_config"]
    assert weights.num_params(cfg) == pytest.approx(5.18e9, rel=0.01)
    assert decode.cache_bytes_per_token(c) == 576 * 2 * 9  # 10.4 KB
    assert decode.expected_distinct_experts(64, 6, 16) == pytest.approx(50.8, abs=0.2)
    assert decode.expected_distinct_experts(64, 6, 1) == pytest.approx(6.0)
    full = decode.decode_step(c, batch=1e6, context=0)  # every expert hit
    assert full["weight_bytes"] - 1e6 * 2048 * 2 == pytest.approx(10.36e9 - 0.42e9, rel=0.01)
    d = decode.decode_step(c, batch=16, context=1024)
    assert d["weight_bytes"] < full["weight_bytes"] - 1e6 * 2048 * 2
    assert costs.roofline_seconds(d["flops"], d["bytes"], "TPU v5 lite")["bound"] == "memory"


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        costs.peaks("TPU v9 imaginary")
    assert costs.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
