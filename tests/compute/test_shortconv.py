"""The gated short-convolution mixer (``models/shortconv.py``; LFM2's
``conv`` operator): its one function in the token form and the sequence
form against a padded ``lax.conv``, the tail a dead token leaves, and
parity with ``transformers``' ``Lfm2Model`` at a tiny size through
``convert_hf`` (a conv layer, an attention layer with q/k norms before a
half-split rope, the norms' order, the tied head).

Float32 against float32 at ``highest``: ``TIGHT`` is rounding order.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dstack_tpu.models import convert_hf, kda, llama, shortconv
from tests.shared import init_params

TIGHT = 2e-5
H, K = 24, 3
C = types.SimpleNamespace(hidden_size=H, conv_taps=K)


def _layer(seed=0):
    k = jax.random.split(jax.random.key(seed), 3)
    return {
        "conv_win": jax.random.normal(k[0], (H, 3 * H)) * H**-0.5,
        "conv_w": jax.random.normal(k[1], (K, H)) * K**-0.5,
        "wo": jax.random.normal(k[2], (H, H)) * H**-0.5,
    }


def _padded_conv(h, layer):
    """The operator as the model states it: a causal depthwise
    convolution of the whole padded sequence, no tail, no shifted adds."""
    bcz = jnp.einsum("bte,ed->btd", h, layer["conv_win"], precision="highest")
    gate_in, gate_out, z = jnp.split(bcz, 3, axis=-1)
    u = gate_in * z  # [B, T, H]
    c = jax.lax.conv_general_dilated(
        u.transpose(0, 2, 1), layer["conv_w"].T[:, None, :], (1,), [(K - 1, 0)],
        feature_group_count=H, precision="highest",
    ).transpose(0, 2, 1)
    return gate_out * c, u


@pytest.mark.parametrize("t", [1, 2, 7, 16])
def test_sequence_form_is_a_padded_convolution(t):
    layer = _layer()
    h = jax.random.normal(jax.random.key(t), (2, t, H))
    with jax.default_matmul_precision("highest"):
        y, tail = shortconv.mix(h, layer, C, *shortconv.zeros(C, 2, jnp.float32))
    want, u = _padded_conv(h, layer)
    assert float(jnp.abs(y - want).max()) < TIGHT
    # the tail after: the last K - 1 rows of (zeros, u)
    rows = jnp.concatenate([jnp.zeros((2, K - 1, H)), u], axis=1)[:, -(K - 1):]
    assert float(jnp.abs(tail - rows).max()) < TIGHT


@pytest.mark.parametrize("split", [1, 5, 11])
def test_token_form_and_chunks_are_the_sequence_form(split):
    """One token at a time from the tail, and two chunks the second of
    which starts from the first's tail, against the whole sequence."""
    layer = _layer(1)
    h = jax.random.normal(jax.random.key(7), (2, 12, H))
    with jax.default_matmul_precision("highest"):
        whole, end = shortconv.mix(h, layer, C, *shortconv.zeros(C, 2, jnp.float32))
        (tail,) = shortconv.zeros(C, 2, jnp.float32)
        steps = []
        for i in range(12):
            y, tail = shortconv.mix(h[:, i:i + 1], layer, C, tail)
            steps.append(y)
        a, mid = shortconv.mix(h[:, :split], layer, C, *shortconv.zeros(C, 2, jnp.float32))
        b, last = shortconv.mix(h[:, split:], layer, C, mid)
    assert float(jnp.abs(jnp.concatenate(steps, 1) - whole).max()) < TIGHT
    assert float(jnp.abs(tail - end).max()) < TIGHT
    assert float(jnp.abs(jnp.concatenate([a, b], 1) - whole).max()) < TIGHT
    assert float(jnp.abs(last - end).max()) < TIGHT


def test_a_dead_token_leaves_the_tail():
    """Padding behind a row's real tokens, a row without any (a pad
    row, a dead slot) and a count given outright: the tail holds the
    last REAL rows, and a row of none keeps the tail it came with."""
    layer = _layer(2)
    h = jax.random.normal(jax.random.key(3), (3, 9, H))
    tail0 = jax.random.normal(jax.random.key(4), (3, K - 1, H))
    counts = jnp.asarray([9, 4, 0])
    valid = jnp.arange(9)[None, :] < counts[:, None]
    y, tail = shortconv.mix(h, layer, C, tail0, valid)
    y2, tail2 = shortconv.mix(h, layer, C, tail0, valid, counts)
    assert np.array_equal(tail, tail2) and np.array_equal(y, y2)
    _, (u,) = shortconv.mix_parts(h, layer, C, tail0)
    assert np.array_equal(tail[0], u[0, 7:9])
    assert np.array_equal(tail[1], u[1, 2:4])  # of its 4 real rows, the last two
    assert np.array_equal(tail[2], tail0[2])
    # one real row: the tail's newer row moves up, the new row behind it
    _, one = shortconv.mix(h, layer, C, tail0, counts=jnp.asarray([1, 1, 1]))
    assert np.array_equal(one[:, 0], tail0[:, 1]) and np.array_equal(one[:, 1], u[:, 0])
    # a real row's output does not read what is padded behind it
    short, _ = shortconv.mix(h[:, :4], layer, C, tail0)
    assert float(jnp.abs(short[1] - y[1, :4]).max()) < 1e-6


def test_the_tail_is_kept_in_the_caches_dtype():
    """``u`` is rounded to the tail's dtype BEFORE the convolution reads
    it: a row convolved now and the same row read back from the tail
    are the same numbers, so bfloat16 steps are the bfloat16 sequence."""
    layer = jax.tree.map(lambda a: a.astype(jnp.bfloat16), _layer(5))
    h = jax.random.normal(jax.random.key(6), (1, 6, H)).astype(jnp.bfloat16)
    whole, end = shortconv.mix(h, layer, C, *shortconv.zeros(C, 1, jnp.bfloat16))
    (tail,) = shortconv.zeros(C, 1, jnp.bfloat16)
    steps = []
    for i in range(6):
        y, tail = shortconv.mix(h[:, i:i + 1], layer, C, tail)
        steps.append(y)
    assert tail.dtype == jnp.bfloat16 and whole.dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(jnp.concatenate(steps, 1), np.float32),
                          np.asarray(whole, np.float32))
    assert np.array_equal(np.asarray(tail, np.float32), np.asarray(end, np.float32))


def test_leaves_and_count():
    c = llama.CONFIGS["conv-tiny"]
    shapes = shortconv.leaf_shapes(c, 5)
    assert {k: v[0] for k, v in shapes.items()} == {
        "conv_win": (5, 128, 384), "conv_w": (5, 3, 128), "wo": (5, 128, 128),
    }
    assert shortconv.n_params(c) == 128 * 384 + 3 * 128 + 128 * 128
    p = init_params(c, 0)
    assert sum(a.size for a in jax.tree.leaves(p)) == c.num_params()
    assert set(p) == {"embed", "dense_layers", "layers", "conv_layers", "final_norm"}
    assert "wq" not in p["conv_layers"] and "q_norm" not in p["conv_layers"]
    assert p["layers"]["q_norm"].shape == (3, 32) and "conv_win" not in p["layers"]
    assert p["dense_layers"]["conv_w"].shape == (2, 3, 128)
    assert float(jnp.abs(p["conv_layers"]["conv_w"]).mean()) > 0.3  # taps at 1 / sqrt(3)
    # the same mixer is kda's convolution: one function
    assert shortconv.conv_rows is kda.conv_rows and shortconv.next_tail is kda.next_tail


# --- transformers' Lfm2 -----------------------------------------------------


@pytest.fixture(scope="module")
def hf_model():
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    if not hasattr(transformers, "Lfm2ForCausalLM"):
        pytest.skip("this transformers has no lfm2")
    torch.manual_seed(0)
    hf = transformers.Lfm2Config(
        vocab_size=256, hidden_size=64, intermediate_size=96, num_hidden_layers=5,
        num_attention_heads=4, num_key_value_heads=2, norm_eps=1e-5, conv_L_cache=3,
        layer_types=["conv", "conv", "full_attention", "conv", "full_attention"],
        block_auto_adjust_ff_dim=False,
    )
    model = transformers.Lfm2ForCausalLM(hf).eval()
    with torch.no_grad():  # norms off identity, taps of a size that shows
        for name, p in model.named_parameters():
            if "norm" in name:
                p.copy_(1 + 0.1 * torch.randn_like(p))
            if "conv.conv" in name:
                p.copy_(0.5 * torch.randn_like(p))
    return hf, model


def _ours(hf, model, toks):
    c = convert_hf.config_from_hf(hf.to_dict(), dtype=jnp.float32)
    params = jax.tree.map(
        jnp.asarray, convert_hf.convert_state_dict(model.state_dict(), c, "lfm2")
    )
    with jax.default_matmul_precision("highest"):
        return c, params, np.asarray(llama.forward(params, jnp.asarray(toks), c))


def test_parity_with_transformers_lfm2(hf_model):
    import torch

    hf, model = hf_model
    toks = np.random.default_rng(0).integers(0, 256, (2, 17))
    with torch.no_grad():
        ref = model(torch.tensor(toks)).logits.numpy()
    c, params, out = _ours(hf, model, toks)
    assert np.abs(out - ref).max() < TIGHT and np.abs(ref).max() > 0.5
    assert c.layer_types == ("conv", "conv", "full", "conv", "full")
    assert (c.qk_norm, c.tie_embeddings, c.conv_taps, c.head_dim) == (True, True, 3, 16)
    assert "lm_head" not in params and params["conv_layers"]["conv_w"].shape == (3, 3, 64)


@pytest.mark.parametrize("fault", ["taps_reversed", "gates_swapped", "norm_after_rope"])
def test_parity_tells_a_wrong_reading(hf_model, fault):
    """What the parity is for: the tap order, which third of the input
    projection gates what, and the q/k norm's place each move the
    logits far past ``TIGHT``."""
    import torch

    hf, model = hf_model
    toks = np.random.default_rng(1).integers(0, 256, (1, 12))
    with torch.no_grad():
        ref = model(torch.tensor(toks)).logits.numpy()
    c, params, _ = _ours(hf, model, toks)
    conv = dict(params["conv_layers"])
    if fault == "taps_reversed":
        conv["conv_w"] = conv["conv_w"][:, ::-1]
    elif fault == "gates_swapped":  # (B, C, z) read as (C, B, z)
        b, g, z = jnp.split(conv["conv_win"], 3, axis=-1)
        conv["conv_win"] = jnp.concatenate([g, b, z], axis=-1)
    else:  # the rope of an un-normed head, the norm behind it: not this model
        c = llama.dataclasses.replace(c, qk_norm=False, qk_l2_norm=True)
    with jax.default_matmul_precision("highest"):
        out = np.asarray(llama.forward({**params, "conv_layers": conv}, jnp.asarray(toks), c))
    assert np.abs(out - ref).max() > 100 * TIGHT


def test_config_from_lfm2_moe_keys():
    """The published ``lfm2_moe`` config (the catalog's row, its keys as
    they stand) → the fields the program runs; the expert block's
    checkpoint names are not known here and are refused by name."""
    hf = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "max_position_embeddings": 128000,
        "layer_types": ["conv", "conv", "full_attention", "conv"] * 10,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1536, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32, "num_dense_layers": 2,
        "num_experts": 64, "num_experts_per_tok": 4, "num_hidden_layers": 40,
        "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
    }
    c = convert_hf.config_from_hf(hf)
    assert (c.n_layers, c.first_k_dense, c.dense_intermediate) == (40, 2, 11776)
    assert (c.intermediate_size, c.n_experts, c.experts_per_token) == (1536, 64, 4)
    assert (c.router_score, c.router_bias, c.router_renorm, c.routed_scale) == (
        "sigmoid", True, True, 1.0,
    )
    assert (c.head_dim, c.n_kv_heads, c.rope_theta, c.norm_eps) == (64, 8, 1e6, 1e-5)
    assert c.layer_types.count("conv") == 30 and c.layer_types[2] == "full"
    assert c.qk_norm and c.tie_embeddings and c.conv_taps == 3
    assert c.capacity_factor == 16.0  # dropless
    plan = llama.layer_periods(c)
    assert [(r.key, r.hi - r.lo) for r in plan.head] == [("dense_layers", 2)]
    assert [(r.key, r.hi - r.lo) for r in plan.period] == [("layers", 1), ("conv_layers", 3)]
    assert plan.count == 9
    assert [(r.key, r.hi - r.lo) for r in plan.tail] == [("layers", 1), ("conv_layers", 1)]
    assert c.num_params() > 23e9  # 24 B whole
    with pytest.raises(NotImplementedError, match="expert block"):
        convert_hf.convert_state_dict({}, c, "lfm2_moe")
    with pytest.raises(ValueError, match="conv_bias"):
        convert_hf.config_from_hf({**hf, "conv_bias": True})
