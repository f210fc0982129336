"""costs/decode_scmoe_zero.py: operations and bytes of a decode step of
``longcat-flash-chat-4l-ep32`` at its published sizes, against figures
reckoned by hand (ISSUE 37)."""

import json
import os

import pytest

from benchmark import costs
from benchmark.costs import decode_scmoe_zero as D


def _llama_config(root):
    with open(os.path.join(root, "benchmark", "configs", "longcat-flash-chat-4l-ep32.json")) as f:
        return json.load(f)["llama_config"]


def test_longcat_decode_step(root):
    c = _llama_config(root)
    # one latent attention: q down and up, kv down and up, out
    attn = 6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256 + 64 * 128 * 6144
    assert D.attn_weights(c) == attn == 90_570_752
    # a double layer outside its experts, the head: read whatever the batch
    layer = 2 * (attn + 3 * 6144 * 12288) + 6144 * 768
    assert layer == 638_844_928
    fixed = 4 * layer + 16384 * 6144
    expert = 3 * 6144 * 2048
    # held experts some token picked: 16 (1 - (63/64)^batch), 3.56 of 16 at batch 16
    touched = lambda b: 16 * (1 - (63 / 64) ** b)
    assert touched(16) == pytest.approx(3.56, abs=0.01) and touched(1) == pytest.approx(0.25)
    one, full = D.decode_step(c, 1, 1000), D.decode_step(c, 16, 1000)
    assert one["weight_bytes"] == pytest.approx((fixed + 4 * 0.25 * expert + 6144) * 2, abs=1)
    assert full["weight_bytes"] == pytest.approx(
        (fixed + 4 * touched(16) * expert + 16 * 6144) * 2, abs=1
    )
    # about 6.4 GB of the 10.35 GB the chip holds: 12.4 of the 16 held
    # experts a layer go unpicked at 16 slots
    assert full["weight_bytes"] == pytest.approx(6.39e9, rel=0.005)
    # each of the 8 sublayers has a latent row of its own a token
    assert one["cache_bytes"] == 1000 * 8 * 576 * 2
    assert full["cache_bytes"] == 16 * 1000 * 8 * 576 * 2
    # a token multiplies with 12 * 16 / 768 = 0.25 held experts a layer;
    # 12 * 256 / 768 = 4 of its picks are identity experts, 2 h each
    flops = (
        2 * (fixed + 4 * 0.25 * expert)
        + 8 * 2 * 64 * (2 * 512 + 64) * 1000 + 4 * 4 * 2 * 6144
    )
    assert one["flops"] == pytest.approx(flops, abs=1)
    roof = costs.roofline_seconds(full["flops"], full["bytes"], "TPU v5 lite")
    assert roof["bound"] == "memory"
    assert roof["seconds"] == pytest.approx((6.39e9 + 0.147e9) / 819e9, rel=0.01)


def test_identity_experts_cost_no_bytes(root):
    """The same model with every router output a real expert of which
    16 are held reads the same bytes; without its identity experts the
    router is 512 wide and a token's 12 picks find more held experts."""
    c = _llama_config(root)
    wide = dict(c, n_experts=768, zero_experts=0)
    assert D.decode_step(wide, 16, 500)["bytes"] == D.decode_step(c, 16, 500)["bytes"]
    assert D.decode_step(wide, 16, 500)["flops"] < D.decode_step(c, 16, 500)["flops"]
    narrow = dict(c, zero_experts=0)
    assert D.decode_step(narrow, 16, 500)["weight_bytes"] > D.decode_step(c, 16, 500)["weight_bytes"]
