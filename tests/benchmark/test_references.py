"""Both plain references agree with the program's forward at a tiny
size, the check has teeth (a MoE prefill that drops tokens disagrees, a
lower precision is seen), and the benchmark's weights are the tree the
program expects."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import launch, weights
from benchmark.reference import check, dense, mla_moe

REFS = {"tiny-dense": dense, "tiny-mla-moe": mla_moe}


def _cfg(tiny, name):
    with open(os.path.join(tiny, "configs", f"{name}.json")) as f:
        return json.load(f)


def _program_logits(cfg, params, tokens, **override):
    from dstack_tpu.models import llama

    config = launch.build_llama_config({**cfg["llama_config"], **override})
    with jax.default_matmul_precision("highest"):
        out = llama.forward(params, jnp.asarray(tokens)[None], config)
    return np.asarray(out[0] if isinstance(out, tuple) else out)[0]


@pytest.mark.parametrize("name", sorted(REFS))
def test_weights_are_the_tree_the_program_expects(tiny, name):
    from dstack_tpu.models import llama

    cfg = _cfg(tiny, name)
    config = launch.build_llama_config(cfg["llama_config"])
    ours = weights.make_params(cfg["llama_config"], 2**31 + 5)
    theirs = llama.init_params(config, jax.random.key(0))
    shape = lambda t: jax.tree.map(lambda x: (x.shape, str(x.dtype)), t)
    assert shape(ours) == shape(theirs)
    assert weights.num_params(cfg["llama_config"]) == config.num_params()
    again = weights.make_params(cfg["llama_config"], 2**31 + 5)
    other = weights.make_params(cfg["llama_config"], 2**31 + 6)
    assert bool(jnp.array_equal(ours["embed"], again["embed"]))
    assert not bool(jnp.array_equal(ours["embed"], other["embed"]))


@pytest.mark.parametrize("name", sorted(REFS))
def test_reference_agrees_with_the_programs_forward(tiny, name):
    cfg, ref = _cfg(tiny, name), REFS[name]
    params = weights.make_params(cfg["llama_config"], 7)
    tokens = np.random.default_rng(0).integers(1, 512, 96)
    logits = _program_logits(cfg, params, tokens)
    hid = ref.hidden_states(cfg, params, tokens)
    best, best_id, vals = ref.head(cfg, params, hid, tokens[:, None])
    # float32 against float32 at `highest`: rounding order only
    assert np.abs(np.asarray(best) - logits.max(-1)).max() < 2e-5
    assert np.abs(np.asarray(vals)[:, 0] - logits[np.arange(96), tokens]).max() < 2e-5
    assert (np.asarray(best_id) == logits.argmax(-1)).mean() > 0.97


def test_a_moe_prefill_that_drops_tokens_disagrees(tiny):
    """The program's default capacity factor (1.25) drops the tokens an
    overfull expert cannot seat; the dropless reference must see it."""
    cfg = _cfg(tiny, "tiny-mla-moe")
    params = weights.make_params(cfg["llama_config"], 7)
    worst = 0.0
    for s in range(6):  # 32-token prompts: capacity 16 a chunk, mean load 12
        tokens = np.random.default_rng(s).integers(1, 512, 32)
        hid = mla_moe.hidden_states(cfg, params, tokens)
        best, _, _ = mla_moe.head(cfg, params, hid, tokens[:, None])
        dropless = _program_logits(cfg, params, tokens)
        dropping = _program_logits(cfg, params, tokens, capacity_factor=1.25)
        assert np.abs(np.asarray(best) - dropless.max(-1)).max() < 2e-5
        worst = max(worst, np.abs(np.asarray(best) - dropping.max(-1)).max())
    assert worst > 1e-3


@pytest.mark.parametrize("name", sorted(REFS))
def test_served_tokens_check_and_its_control(tiny, name):
    """Greedy tokens of the program's own forward have no gap; tokens
    altered where they are produced, and the int8 control, have one."""
    cfg = _cfg(tiny, name)
    seed = 2**31 + 11
    params = weights.make_params(cfg["llama_config"], seed)
    rng = np.random.default_rng(2)
    prompt = rng.integers(1, 512, 40).tolist()
    ids, toks = [], list(prompt)
    for _ in range(24):  # greedy decode by repeated full forwards
        nxt = int(_program_logits(cfg, params, np.asarray(toks))[-1].argmax())
        ids.append(nxt)
        toks.append(nxt)
    sound = check.run(cfg, seed, [{"rid": "a", "prompt_ids": prompt, "ids": ids}])
    assert sound["positions"] == 24 and sound["gap_max"] < 1e-5
    broken = [(t + 1) % 512 for t in ids]
    bad = check.run(cfg, seed, [{"rid": "a", "prompt_ids": prompt, "ids": broken}])
    assert bad["gap_max"] > 0.05 and bad["requests"][0]["agree"] < 0.2
    control = check.run(cfg, seed, [{"rid": "a", "prompt_ids": prompt, "ids": ids}], control="int8")
    assert control["control"] == "int8" and control["gap_max"] >= 0.0


def test_sampler_statistic_reads_the_temperature():
    """Tokens drawn at the stated temperature read 0; the same tokens
    held against another temperature, and greedy tokens, do not."""
    from benchmark.reference import common as C

    rng = np.random.default_rng(3)
    hidden = rng.standard_normal((2048, 32)).astype(np.float32)
    lm_head = jnp.asarray(rng.standard_normal((32, 1000)) * 0.25, jnp.float32)
    logits = hidden @ np.asarray(lm_head)
    p = np.exp(logits / 0.7 - (logits / 0.7).max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    drawn = np.array([rng.choice(1000, p=row) for row in p])

    def excess(ids, t):
        with jax.default_matmul_precision("highest"):
            got, mean = C.sampling_stats(hidden, lambda h: h, lm_head, ids, t, block=256)
        return float((np.asarray(got) - np.asarray(mean)).mean())

    assert abs(excess(drawn, 0.7)) < 0.15
    assert excess(drawn, 1.0) > 0.3  # drawn sharper than a stated 1.0
    assert excess(logits.argmax(-1), 0.7) > 2.0


@pytest.mark.parametrize("name", sorted(REFS))
def test_the_programs_own_int8_weights_are_seen_at_toy_size(tiny, name):
    """The control at a size a test holds: the program's forward over
    ``quantize_tree`` weights puts another token first at some of a few
    hundred positions, and the reference reads a gap there; over the
    weights as they are it reads none."""
    from dstack_tpu.models.quant import quantize_tree

    cfg, ref = _cfg(tiny, name), REFS[name]
    config = launch.build_llama_config(cfg["llama_config"])
    params = weights.make_params(cfg["llama_config"], 7)
    int8 = jax.tree.map(jnp.asarray, quantize_tree(params, config))
    worst = {"sound": 0.0, "int8": 0.0}
    for s in range(4):
        tokens = np.random.default_rng(s).integers(1, 512, 96)
        hid = ref.hidden_states(cfg, params, tokens)
        for label, tree in (("sound", params), ("int8", int8)):
            config_logits = _program_logits(cfg, tree, tokens)
            best, _, vals = ref.head(cfg, params, hid, config_logits.argmax(-1)[:, None])
            gap = float((np.asarray(best) - np.asarray(vals)[:, 0]).max())
            worst[label] = max(worst[label], gap)
    assert worst["sound"] < 1e-5 < 5e-5 < worst["int8"]
