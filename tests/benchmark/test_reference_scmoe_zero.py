"""A latent model whose layer is TWO attention sublayers and two dense
FFNs with one expert branch across them, routed by a softmax router
with identity experts among its outputs and a chip's share of the real
ones (``benchmark/reference/mla_scmoe_zero.py``; the cell
``longcat-flash-chat-4l-ep32.agent``), at toy widths on the CPU: the
benchmark's weights are the tree the program expects, the program's
forward agrees with the plain reference's on logits, the shares of an
expert layer add up to the uncut layer with the identity experts' term
counted once, and each control fails. The four serving programs against
the same reference: ``tests/serve/test_scmoe_latent.py``.

Tolerances. Everything here is float32 against float32 at ``highest``
and nothing is discontinuous (a top-k of router scores can tie only by
accident), so the two sides differ by rounding order: ``TIGHT``. Every
control (the reference in W8A8 or on weights rounded to bfloat16, the
branch's gates renormalised or unscaled, the identity experts left out,
a rescale left out) moves the logits by ``FAULT`` or more, fifty times
that.
"""

import copy
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import launch, weights
from benchmark.reference import mla_scmoe_zero as R

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data", "scmoe_zero")
CELL, CONFIG = "longcat-flash-chat-4l-ep32.agent", "longcat-flash-chat-4l-ep32"
TIGHT, FAULT = 2e-5, 1e-3


def _cfg():
    with open(os.path.join(DATA, "configs", "tiny-scmoe-zero.json")) as f:
        return json.load(f)


def _real():
    with open(os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json")) as f:
        return json.load(f)


def _ref_logits(cfg, params, tokens, precision="f32"):
    hid = R.hidden_states(cfg, params, np.asarray(tokens), precision)
    h = R.final_norm(cfg, params)(hid)
    return np.asarray(
        jnp.matmul(h, params["lm_head"].astype(jnp.float32), precision="highest")
    )


def test_weights_are_the_tree_the_program_expects():
    from dstack_tpu.models import llama

    cfg = _cfg()
    config = launch.build_llama_config(cfg["llama_config"])
    ours = weights.make_params(cfg, 2**31 + 5)
    theirs = llama.init_params(config, jax.random.key(0))
    shape = lambda t: jax.tree.map(lambda x: (x.shape, str(x.dtype)), t)
    assert shape(ours) == shape(theirs)
    assert set(ours) == {"embed", "layers", "final_norm", "lm_head"}
    layers = ours["layers"]
    # a sublayer's own leaves in its sub-tree; the router and the held experts the layer's
    assert set(layers) == {"sub0", "sub1", "w_router", "router_bias", "w_gate", "w_up", "w_down"}
    for sub in (layers["sub0"], layers["sub1"]):
        assert sub["wq_a"].shape == (2, 64, 24) and sub["attn_norm"].shape == (2, 64)
        assert sub["w_gate"].shape == (2, 64, 96)  # its dense FFN
    assert not np.array_equal(np.asarray(layers["sub0"]["wo"]), np.asarray(layers["sub1"]["wo"]))
    assert layers["w_router"].shape == (2, 64, 16 + 8)  # 16 routed + 8 identity outputs
    assert layers["router_bias"].shape == (2, 24)
    assert layers["w_gate"].shape == (2, 4, 64, 32)  # the experts held, not the 16 routed over
    assert weights.num_params(cfg) == config.num_params()
    # the selection bias is drawn at the mean score, not at the tree's 0.02
    bias = np.asarray(layers["router_bias"], np.float32)
    assert 0.5 / 24 < bias.std() < 2.0 / 24


def test_the_cells_tree_is_the_programs_at_published_sizes():
    """Device-free, at the cell's sizes: shapes, dtypes and the count
    the issue reckoned: 4 double layers x (638.9 M outside the experts +
    16 x 37.75 M) + embedding and head = 5.17 B parameters."""
    from dstack_tpu.models import llama

    cfg = _real()
    config = launch.build_llama_config(cfg["llama_config"])
    theirs = llama.abstract_params(config)
    spec = dict(weights.flatten(weights.leaf_spec(cfg)))
    flat = {
        "/".join(str(k.key) for k in path): leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(theirs)[0]
    }
    assert set(spec) == set(flat)
    for path, (shape, _) in spec.items():
        assert tuple(shape) == flat[path].shape, path
        # the program's own init keeps the selection bias in float32; the
        # benchmark draws it in the served dtype, for both sides alike
        want = "float32" if path == "layers/router_bias" else "bfloat16"
        assert str(flat[path].dtype) == want, path
    layer = 2 * (90_572_800 + 3 * 6144 * 12288 + 2 * 6144) + 6144 * 768 + 768
    assert layer == 638_874_368
    want = 4 * (layer + 16 * 3 * 6144 * 2048) + 2 * 16384 * 6144 + 6144
    assert weights.num_params(cfg) == config.num_params() == want == 5_172_749_312
    for i in (0, 1):
        assert flat[f"layers/sub{i}/wq_b"].shape == (4, 1536, 64 * 192)
        assert flat[f"layers/sub{i}/wo"].shape == (4, 64 * 128, 6144)
        assert flat[f"layers/sub{i}/w_up"].shape == (4, 6144, 12288)
    assert flat["layers/w_router"].shape == (4, 6144, 768)
    assert flat["layers/w_gate"].shape == (4, 16, 6144, 2048)
    assert flat["lm_head"].shape == (6144, 16384)


def test_the_configuration_carries_the_catalogs_keys():
    """Every key of the published config at its published value but the
    three listed in ``reduced``."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog beside the guides here")
    with open(catalog) as f:
        row = next(json.loads(l) for l in f if '"LongCat-Flash-Chat"' in l)
    cfg = _real()
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            continue
        assert cfg[key] == value, key
    assert cfg["num_layers"] == 4 and cfg["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert cfg["experts_held"] == [0, 16] and cfg["published"]["n_routed_experts"] == 512
    lc = cfg["llama_config"]
    # the router's published width: 512 routed + 256 identity outputs, top-12
    assert (lc["n_experts"], lc["zero_experts"], lc["experts_per_token"]) == (512, 256, 12)
    assert lc["sublayers"] == 2 and lc["n_layers"] == 4
    assert cfg["serve_flags"] == ["--max-batch", "16", "--max-seq", "8192"]


def _forward(cfg, params, tokens):
    from dstack_tpu.models import llama

    config = launch.build_llama_config(cfg["llama_config"])
    with jax.default_matmul_precision("highest"):
        return np.asarray(llama.forward(params, jnp.asarray(tokens)[None], config))[0]


def test_reference_agrees_with_the_programs_forward():
    cfg = _cfg()
    params = weights.make_params(cfg, 7)
    tokens = np.random.default_rng(0).integers(1, 512, 96)
    assert np.abs(_forward(cfg, params, tokens) - _ref_logits(cfg, params, tokens)).max() < TIGHT


def _faulty(cfg, fault):
    cfg = copy.deepcopy(cfg)
    if fault == "identity_experts_left_out":
        cfg["zero_expert_num"] = 0  # their gates times the token never added
    elif fault == "gates_unscaled":
        cfg["routed_scaling_factor"] = 1
    elif fault == "q_rescale_left_out":
        cfg["mla_scale_q_lora"] = False
    elif fault == "kv_rescale_left_out":
        cfg["mla_scale_kv_lora"] = False
    elif fault == "one_more_pick":
        cfg["moe_topk"] += 1
    return cfg


@pytest.mark.parametrize("fault", [
    "int8", "bf16_weights", "identity_experts_left_out", "gates_unscaled",
    "q_rescale_left_out", "kv_rescale_left_out", "one_more_pick",
])
def test_a_control_fails(fault):
    """What the comparison is for: each of these readings of the model
    is farther from the program than ``FAULT``."""
    cfg = _cfg()
    params = weights.make_params(cfg, 7)
    tokens = np.random.default_rng(0).integers(1, 512, 96)
    logits = _forward(cfg, params, tokens)
    ref_params = params
    if fault == "bf16_weights":
        ref_params = jax.tree.map(
            lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params
        )
    ref = _ref_logits(
        _faulty(cfg, fault), ref_params, tokens, "int8" if fault == "int8" else "f32"
    )
    assert np.abs(logits - ref).max() > FAULT


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Four chips hold four experts each of a layer of sixteen, and each
    computes the identity experts for its own tokens. What the shares'
    held experts give, the identity experts' term counted once, is what
    the uncut reference gives for the whole layer: the cut ties to the
    model."""
    from dstack_tpu.models import moe

    lc = _cfg()["llama_config"]
    H, F = lc["hidden_size"], lc["intermediate_size"]
    E, Z, K = lc["n_experts"], lc["zero_experts"], lc["experts_per_token"]
    k = jax.random.split(jax.random.key(3), 6)
    draw = lambda key, *shape: jax.random.normal(key, shape, jnp.float32) * 0.05
    whole = {
        "w_router": draw(k[0], H, E + Z) * 4,
        "router_bias": jax.random.normal(k[1], (E + Z,), jnp.float32) / (E + Z),
        "w_gate": draw(k[2], E, H, F), "w_up": draw(k[3], E, H, F),
        "w_down": draw(k[4], E, F, H),
    }
    x = jax.random.normal(k[5], (2, 24, H), jnp.float32)
    routing = dict(zero=Z, top_k=K, scaling=lc["routed_scale"], precision="f32")
    with jax.default_matmul_precision("highest"):
        uncut = jnp.stack([R.moe(row, whole, held=(0, E), **routing) for row in x])
        total, held_picks, zero_picks, identity = jnp.zeros_like(x), 0, [], []
        for first in range(0, E, 4):
            share = {
                **whole,
                **{n: whole[n][first:first + 4] for n in ("w_gate", "w_up", "w_down")},
            }
            out, aux = moe.moe_mlp(
                x, share, E, K, lc["capacity_factor"], None, None,
                routed_scale=lc["routed_scale"], held=(first, 4), zero=Z,
                valid=jnp.ones(x.shape[:2], bool),
            )
            assert share["w_gate"].shape[0] == 4  # absent experts have no weights
            total, held_picks = total + out, held_picks + int(aux["held_picks"])
            zero_picks.append(int(aux["zero_picks"]))
            identity.append(aux["zero_gate"][..., None] * x)
            # and the reference's share is the program's
            mine = jnp.stack([R.moe(row, share, held=(first, 4), **routing) for row in x])
            assert np.abs(np.asarray(mine - out)).max() < TIGHT
    # every pick lands on one chip's held experts or on an identity expert
    assert len(set(zero_picks)) == 1 and 0 < zero_picks[0] < 2 * 24 * K
    assert held_picks + zero_picks[0] == 2 * 24 * K
    # every chip computed the same identity term: counted once, not four times
    assert np.abs(np.asarray(total - 3 * identity[0] - uncut)).max() < TIGHT
    assert np.abs(np.asarray(identity[0])).max() > FAULT  # and it is no small term


def test_the_reference_imports_nothing_of_the_program():
    import ast

    path = os.path.join(ROOT, "benchmark", "reference", "mla_scmoe_zero.py")
    with open(path) as f:
        src = f.read()
    names = set()
    for node in ast.walk(ast.parse(src)):
        if isinstance(node, ast.Import):
            names |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add(("." * node.level) + (node.module or ""))
    assert not any("dstack_tpu" in n for n in names), names
    assert names <= {"math", "functools", "jax", "jax.numpy", ".", ".mla_moe"}
    assert src.count('default_matmul_precision("highest")') == 2  # hidden_states and head


def test_the_benchmark_validates_with_the_new_cell():
    """By name, not by place: a later cell goes after this one."""
    from benchmark import validate

    assert validate.validate(ROOT) == []
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "agent", 1)
    config = next(c for c in b["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == _real()["reduced"]
    listed = {m["name"]: m.get("workloads") for m in b["per_layer"]}
    assert listed["moe_zero_picks_share"] == listed["scmoe_decode_keys_read_share"] == [CELL]
    tps = next(m for m in b["end_to_end"] if m["name"] == "out_tokens_per_s")
    assert CELL in tps["workloads"]
    with open(os.path.join(ROOT, "benchmark", "workloads", CELL + ".json")) as f:
        mix = json.load(f)["traffic"]
    assert mix == {
        "loop": "closed", "clients": 16, "prompt_tokens": [256, 2048],
        "prompt_dist": "loguniform", "output_tokens": [256, 768], "temperature": 0.0,
        "ramp_s": 16.0, "lengths": "stratified", "stratify_block": 8,
    }


def test_the_new_metrics_read_in_the_new_cell_and_in_no_other():
    import sys

    sys.path.insert(0, ROOT)
    from benchmark import run

    names = {"moe_zero_picks_share", "scmoe_decode_keys_read_share"}
    wdir = os.path.join(ROOT, "benchmark", "workloads")
    for fn in sorted(os.listdir(wdir)):
        with open(os.path.join(wdir, fn)) as f:
            workload = json.load(f)
        got = names & set(run.load_metric_defs(workload))
        assert got == (names if workload["name"] == CELL else set()), fn
        if workload["name"] == CELL:
            assert "decode_roofline" in run.load_metric_defs(workload)


def test_rehearsal_serves_double_layers_through_the_normal_path():
    """The whole path at toy sizes on the CPU: ``run.py`` → the real
    server entry point, scheduler, warm-up and HTTP → the reference
    child; the line is well-formed, ``correct``, nothing compiled inside
    the window."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu", TF_CPP_MIN_LOG_LEVEL="3")
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "tiny-scmoe-zero.agent",
         "--seed", str(2**31 + 23), "--seconds", "4", "--trace", "1", "--platform", "cpu",
         "--bench-dir", DATA],
        capture_output=True, text=True, cwd=ROOT, env=env, timeout=900,
    )
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["attempted"] > 0 and line["failed"] == 0
    assert line["compiles_in_window"] == 0
    compared = [l for l in out.stdout.splitlines() if l.startswith("compared ")]
    assert len(compared) == 3 and all(l.endswith(" ok") for l in compared)
