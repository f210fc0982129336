"""The plain references agree with the program's forward at a tiny
size, the check has teeth (a MoE prefill that drops tokens disagrees, a
lower precision is seen), the benchmark's weights are the tree the
program expects, and trees, specs and step costs read what they read
before each architecture stated its own (the pins at the end of this
file). ``tiny-mla-lowq`` is the architecture that lives in files of its
own under ``data/arch`` (``test_new_architecture.py``)."""

import hashlib
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import launch, weights
from benchmark.costs import decode
from benchmark.reference import check, mla_moe

TINY = ("tiny-dense", "tiny-mla-moe")
ALL = TINY + ("tiny-mla-lowq",)


def _cfg(tiny, name, arch=None):
    """The configuration file ``name`` under ``tiny``, else under ``arch``."""
    path = os.path.join(tiny, "configs", f"{name}.json")
    if arch and not os.path.exists(path):
        path = os.path.join(arch, "configs", f"{name}.json")
    with open(path) as f:
        return json.load(f)


def _ref(cfg):
    return importlib.import_module(f"benchmark.reference.{cfg['reference']}")


def _program_logits(cfg, params, tokens, **override):
    from dstack_tpu.models import llama

    config = launch.build_llama_config({**cfg["llama_config"], **override})
    with jax.default_matmul_precision("highest"):
        out = llama.forward(params, jnp.asarray(tokens)[None], config)
    return np.asarray(out[0] if isinstance(out, tuple) else out)[0]


@pytest.mark.parametrize("name", ALL)
def test_weights_are_the_tree_the_program_expects(tiny, arch, name):
    from dstack_tpu.models import llama

    cfg = _cfg(tiny, name, arch)
    config = launch.build_llama_config(cfg["llama_config"])
    ours = weights.make_params(cfg, 2**31 + 5)
    theirs = llama.init_params(config, jax.random.key(0))
    shape = lambda t: jax.tree.map(lambda x: (x.shape, str(x.dtype)), t)
    assert shape(ours) == shape(theirs)
    assert weights.num_params(cfg) == config.num_params()
    again = weights.make_params(cfg, 2**31 + 5)
    other = weights.make_params(cfg, 2**31 + 6)
    assert bool(jnp.array_equal(ours["embed"], again["embed"]))
    assert not bool(jnp.array_equal(ours["embed"], other["embed"]))


@pytest.mark.parametrize("name", ALL)
def test_reference_agrees_with_the_programs_forward(tiny, arch, name):
    cfg = _cfg(tiny, name, arch)
    ref = _ref(cfg)
    params = weights.make_params(cfg, 7)
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
    params = weights.make_params(cfg, 7)
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


@pytest.mark.parametrize("name", TINY)
def test_served_tokens_check_and_its_control(tiny, name):
    """Greedy tokens of the program's own forward have no gap; tokens
    altered where they are produced, and the int8 control, have one."""
    cfg = _cfg(tiny, name)
    seed = 2**31 + 11
    params = weights.make_params(cfg, seed)
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


@pytest.mark.parametrize("name", TINY)
def test_the_programs_own_int8_weights_are_seen_at_toy_size(tiny, name):
    """The control at a size a test holds: the program's forward over
    ``quantize_tree`` weights puts another token first at some of a few
    hundred positions, and the reference reads a gap there; over the
    weights as they are it reads none."""
    from dstack_tpu.models.quant import quantize_tree

    cfg = _cfg(tiny, name)
    ref = _ref(cfg)
    config = launch.build_llama_config(cfg["llama_config"])
    params = weights.make_params(cfg, 7)
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


def test_nested_lists_become_tuples_at_every_depth(tiny):
    """A per-layer pattern of pairs is a list of lists in JSON; a
    ``LlamaConfig`` is hashed (it is a static argument of every jitted
    program), so no list may be left inside a field."""
    lc = _cfg(tiny, "tiny-mla-moe")["llama_config"]
    config = launch.build_llama_config({**lc, "router_groups": [[2, 1], [4, [2, 1]]]})
    assert config.router_groups == ((2, 1), (4, (2, 1)))
    assert config.rope_scaling == ("yarn", 40.0, 32.0, 1.0, 64.0, 1.0)
    hash(config)


# The pins (constants at the end of the file) were taken on PR 25's tree,
# where one function ``weights.leaf_shapes`` held every architecture,
# before any line moved. A leaf's values hang on its path, its shape,
# its scale and the seed alone.

PIN_SEED = 2**31 + 5


@pytest.mark.parametrize("name", TINY)
def test_every_leaf_is_bit_for_bit_what_it_was(tiny, name):
    params = weights.make_params(_cfg(tiny, name), PIN_SEED)
    got = {
        path: hashlib.sha256(np.asarray(leaf).tobytes()).hexdigest()
        for path, leaf in weights.flatten(params)
    }
    assert got == LEAF_SHA256[name]


@pytest.mark.parametrize("name", ["minitron-4b", "deepseek-v2-lite-9l"])
def test_published_size_spec_and_step_costs_are_what_they_were(root, name):
    """Device-free: the flattened spec (paths, shapes, scales) and
    ``num_params`` at the published sizes, and ``decode_step`` over a
    grid of batch and context, to the last digit."""
    cfg = _cfg(os.path.join(root, "benchmark"), name)
    spec = [(p, tuple(s), scale) for p, (s, scale) in weights.flatten(weights.leaf_spec(cfg))]
    assert spec == SPEC[name]
    assert weights.num_params(cfg) == NUM_PARAMS[name]
    for (batch, context), want in DECODE_STEP[name].items():
        d = decode.decode_step(cfg["llama_config"], batch, context)
        got = (d["flops"], d["bytes"], d["weight_bytes"], d["cache_bytes"])
        assert got == want, (batch, context)


LEAF_SHA256 = {
    "tiny-dense": {
        "embed":
            "813dbbc175b0ffcb3f02ef443768ee56459023def1493d378dd5a8b3b9e90fce",
        "final_norm":
            "076a27c79e5ace2a3d47f9dd2e83e4ff6ea8872b3c2218f66c92b89b55f36560",
        "layers/attn_norm":
            "5f70bf18a086007016e948b04aed3b82103a36bea41755b6cddfaf10ace3c6ef",
        "layers/mlp_norm":
            "5f70bf18a086007016e948b04aed3b82103a36bea41755b6cddfaf10ace3c6ef",
        "layers/w_down":
            "11c337eb30b4999924a36720cf8ba10ef0de114558c0cbb3d2a78de2541b10b9",
        "layers/w_up":
            "7425a51aaeae60a66f5babc1628cbb82cf9e09a63f3203ef1324d48ade53af06",
        "layers/wk":
            "2aa59009c5d4f0400657357f571f7fc91412c335a43bfd55e3b0997d01ed0fbf",
        "layers/wo":
            "4e2608460ef385b21adddd3cc1724fed548bc11f13b730e6d022fe25df210f35",
        "layers/wq":
            "dfed56cd77063eed37b0e8c0fdd36b5a5b9575e9fe547d3317df520066fea36c",
        "layers/wv":
            "d99db74d3e96dffc56c20fe5471c9097b92f160e744f38173ea54a98fabf4437",
        "lm_head":
            "980bd6b1dda597509ea239d5ebdeeea8de5463f53c185c2943d7804576d520ab",
    },
    "tiny-mla-moe": {
        "dense_layers/attn_norm":
            "2f20cd03c9cd392a406c56232b0ff93a15f6d6d7da79086bfa14f55d4a4031b0",
        "dense_layers/kv_a_norm":
            "b638277a8690e175a9137feff1e43c067f9faf4e2f600caf468fb05b0403b717",
        "dense_layers/mlp_norm":
            "2f20cd03c9cd392a406c56232b0ff93a15f6d6d7da79086bfa14f55d4a4031b0",
        "dense_layers/w_down":
            "235abd8a1d341781a44f7aa5f094b86756972bfc2bd88f1d0d78a1f25677ac71",
        "dense_layers/w_gate":
            "b2d04cc894396832147132653f624098e5eb61600df3c52e6d6d1d5c332a3469",
        "dense_layers/w_up":
            "b945e325668039c78e8b2524d20232f2bbfd3a589bce5486529da59c4fd3f74a",
        "dense_layers/wkv_a":
            "50b72647520db3a57d1ecbff1d398a38e1f01b3da13111979265edbce2a6b97d",
        "dense_layers/wkv_b":
            "d13d8524ba8cdcbfc7581c852c220f28828d0aad454d25c110c6d10bb7fd4027",
        "dense_layers/wo":
            "7276f7d89cfa6ef2908c45517b9250773a934882a2e70c246dd820f64e39a864",
        "dense_layers/wq":
            "c3946f47673e8a189174a3c5b0342ef3f97284e18b981f2c9aaea5dcb5d4549a",
        "embed":
            "813dbbc175b0ffcb3f02ef443768ee56459023def1493d378dd5a8b3b9e90fce",
        "final_norm":
            "2f20cd03c9cd392a406c56232b0ff93a15f6d6d7da79086bfa14f55d4a4031b0",
        "layers/attn_norm":
            "02722f124d0f1736a9dd7c4ddcd05630dcf16ee1ce3454e9a876005ce005d4ac",
        "layers/kv_a_norm":
            "2f20cd03c9cd392a406c56232b0ff93a15f6d6d7da79086bfa14f55d4a4031b0",
        "layers/mlp_norm":
            "02722f124d0f1736a9dd7c4ddcd05630dcf16ee1ce3454e9a876005ce005d4ac",
        "layers/w_down":
            "d0779d4d3574b5af0c18c4186120bd7ed652dda613b0eb3ac3d43e06988a5ae5",
        "layers/w_gate":
            "138d12ea2488732f9097c5ef113a9e198be72615a13e3ffd5d63a462ba4907b5",
        "layers/w_router":
            "58a871fdb66c6022d6a7559345a53465645fcd7ad0ca69cf5a61d5554d16f0d2",
        "layers/w_shared_down":
            "946f58f1273f3d10da6daf5097ccfb4e51ec090b6a21b47f5b8f9825cdd460a9",
        "layers/w_shared_gate":
            "040f535071e021b57c0331070cf5dbf495f20664f210028d5ddd75ef004179bd",
        "layers/w_shared_up":
            "8576be0d7ae16f8771286c11f10be7502cd4ad2c9acc3443ded76aaa8ae32c77",
        "layers/w_up":
            "09b52aa4a1a3ffa4615e76b628929e5d8dacacf461578c2c031979fa9602419e",
        "layers/wkv_a":
            "5f74d8fac02d605a654f16047d146447ea1ae2d4f6efd004a4cfa3df382fb33d",
        "layers/wkv_b":
            "a57287990fadce750a06b454e7f03d360cf48adfb382b61cf51f63e4c785ce9a",
        "layers/wo":
            "d3470a1d178c7c414d0fa23825db1554618983bc00e38b7ab42277b550f6e6d3",
        "layers/wq":
            "a84a0732942c2a00f2dcc40229d5b3c4f7775146296822b0caf21617adfdbc17",
        "lm_head":
            "980bd6b1dda597509ea239d5ebdeeea8de5463f53c185c2943d7804576d520ab",
    },
}
SPEC = {
    "minitron-4b": [
        ("embed", (256000, 3072), 0.02),
        ("final_norm", (2, 3072), None),
        ("layers/attn_norm", (32, 2, 3072), None),
        ("layers/mlp_norm", (32, 2, 3072), None),
        ("layers/w_down", (32, 9216, 3072), 0.0025),
        ("layers/w_up", (32, 3072, 9216), 0.02),
        ("layers/wk", (32, 3072, 1024), 0.02),
        ("layers/wo", (32, 3072, 3072), 0.0025),
        ("layers/wq", (32, 3072, 3072), 0.02),
        ("layers/wv", (32, 3072, 1024), 0.02),
        ("lm_head", (3072, 256000), 0.02),
    ],
    "deepseek-v2-lite-9l": [
        ("dense_layers/attn_norm", (1, 2048), None),
        ("dense_layers/kv_a_norm", (1, 512), None),
        ("dense_layers/mlp_norm", (1, 2048), None),
        ("dense_layers/w_down", (1, 10944, 2048), 0.0047140452079103175),
        ("dense_layers/w_gate", (1, 2048, 10944), 0.02),
        ("dense_layers/w_up", (1, 2048, 10944), 0.02),
        ("dense_layers/wkv_a", (1, 2048, 576), 0.02),
        ("dense_layers/wkv_b", (1, 512, 4096), 0.02),
        ("dense_layers/wo", (1, 2048, 2048), 0.0047140452079103175),
        ("dense_layers/wq", (1, 2048, 3072), 0.02),
        ("embed", (102400, 2048), 0.02),
        ("final_norm", (2048,), None),
        ("layers/attn_norm", (8, 2048), None),
        ("layers/kv_a_norm", (8, 512), None),
        ("layers/mlp_norm", (8, 2048), None),
        ("layers/w_down", (8, 64, 1408, 2048), 0.0047140452079103175),
        ("layers/w_gate", (8, 64, 2048, 1408), 0.02),
        ("layers/w_router", (8, 2048, 64), 0.02),
        ("layers/w_shared_down", (8, 2816, 2048), 0.0047140452079103175),
        ("layers/w_shared_gate", (8, 2048, 2816), 0.02),
        ("layers/w_shared_up", (8, 2048, 2816), 0.02),
        ("layers/w_up", (8, 64, 2048, 1408), 0.02),
        ("layers/wkv_a", (8, 2048, 576), 0.02),
        ("layers/wkv_b", (8, 512, 4096), 0.02),
        ("layers/wo", (8, 2048, 2048), 0.0047140452079103175),
        ("layers/wq", (8, 2048, 3072), 0.02),
        ("lm_head", (2048, 102400), 0.02),
    ],
}
NUM_PARAMS = {"minitron-4b": 4190509056, "deepseek-v2-lite-9l": 5179222528}
DECODE_STEP = {  # (batch, context): (flops, bytes, weight_bytes, cache_bytes)
    "minitron-4b": {
        (1, 256): (6908018688, 6840915968, 6807361536, 33554432),
        (1, 1024): (7210008576, 6941579264, 6807361536, 134217728),
        (1, 4096): (8417968128, 7344232448, 6807361536, 536870912),
        (5, 256): (34540093440, 6975158272, 6807386112, 167772160),
        (5, 1024): (36050042880, 7478474752, 6807386112, 671088640),
        (5, 4096): (42089840640, 9491740672, 6807386112, 2684354560),
        (16, 256): (110528299008, 7344324608, 6807453696, 536870912),
        (16, 1024): (115360137216, 8954937344, 6807453696, 2147483648),
        (16, 4096): (134687490048, 15397388288, 6807453696, 8589934592),
    },
    "deepseek-v2-lite-9l": {
        (1, 256): (1991245824, 1913688064.0, 1911033856.0, 2654208),
        (1, 1024): (2231894016, 1921650688.0, 1911033856.0, 10616832),
        (1, 4096): (3194486784, 1953501184.0, 1911033856.0, 42467328),
        (5, 256): (9956229120, 4537275800.0, 4524004760.0, 13271040),
        (5, 1024): (11159470080, 4577088920.0, 4524004760.0, 53084160),
        (5, 4096): (15972433920, 4736341400.0, 4524004760.0, 212336640),
        (16, 256): (31859933184, 8147786460.386246, 8105319132.386246, 42467328),
        (16, 1024): (35710304256, 8275188444.386246, 8105319132.386246, 169869312),
        (16, 4096): (51111788544, 8784796380.386246, 8105319132.386246, 679477248),
    },
}
