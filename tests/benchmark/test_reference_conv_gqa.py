"""A grouped-query model most of whose layers are gated
short-convolution layers (a two-row tail a slot, no keys and values)
with sigmoid routing of which a chip holds an eighth
(``benchmark/reference/conv_gqa_moe.py``; the cell
``lfm2-24b-a2b-ep8.rag``), at toy widths on the CPU: the benchmark's
weights are the tree the program expects, the program's forward agrees
with the plain reference's on logits, the chips' shares of an expert
layer add up to the uncut layer, each control fails, and the costs
module counts what the issue reckoned. The serving programs against
the program's forward: ``tests/serve/test_conv_state.py``.

Tolerances. Everything here is float32 against float32 at ``highest``
and nothing is discontinuous (a top-k of router scores can tie only by
accident), so the two sides differ by rounding order and by the
renormalisation's epsilon (the model's 1e-6 in the reference, 1e-20 in
the program: 5e-7 relative on a gate): ``TIGHT``. Every control moves
the logits by ``FAULT`` or more, fifty times that.
"""

import copy
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import costs, launch, weights
from benchmark.costs import decode_conv_gqa as D
from benchmark.reference import conv_gqa_moe as R

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data", "conv_gqa")
CELL, CONFIG = "lfm2-24b-a2b-ep8.rag", "lfm2-24b-a2b-ep8"
TIGHT, FAULT = 2e-5, 1e-3
REDUCED = ["num_experts", "vocab_size"]
NEW_METRICS = {
    "conv_tail_cache_share", "conv_gqa_keys_read_share",
    "conv_moe_experts_read_share", "conv_moe_held_picks_per_token",
}


def _cfg():
    with open(os.path.join(DATA, "configs", "tiny-conv-gqa-moe.json")) as f:
        return json.load(f)


def _real():
    with open(os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json")) as f:
        return json.load(f)


def _ref_logits(cfg, params, tokens, precision="f32"):
    hid = R.hidden_states(cfg, params, np.asarray(tokens), precision)
    h = R.final_norm(cfg, params)(hid)
    # tied: the head is the embedding's transpose
    return np.asarray(
        jnp.matmul(h, params["embed"].T.astype(jnp.float32), precision="highest")
    )


def test_weights_are_the_tree_the_program_expects():
    from dstack_tpu.models import llama

    cfg = _cfg()
    config = launch.build_llama_config(cfg["llama_config"])
    ours = weights.make_params(cfg, 2**31 + 5)
    theirs = llama.init_params(config, jax.random.key(0))
    shape = lambda t: jax.tree.map(lambda x: (x.shape, str(x.dtype)), t)
    assert shape(ours) == shape(theirs)
    assert set(ours) == {"embed", "dense_layers", "conv_layers", "layers", "final_norm"}
    conv, full, pre = ours["conv_layers"], ours["layers"], ours["dense_layers"]
    # an operator's leaves in the attention's place, the expert layer's under both
    assert conv["conv_win"].shape == (7, 64, 192) and conv["conv_w"].shape == (7, 3, 64)
    assert conv["wo"].shape == (7, 64, 64) and full["wo"].shape == (3, 64, 64)
    assert "wq" not in conv and "q_norm" not in conv and "conv_win" not in full
    assert full["q_norm"].shape == full["k_norm"].shape == (3, 16)
    assert pre["conv_win"].shape == (2, 64, 192) and pre["w_gate"].shape == (2, 64, 96)
    for stack in (conv, full):
        assert stack["w_router"].shape[1:] == (64, 8)  # the router's whole width
        assert stack["router_bias"].shape[1:] == (8,)
        assert stack["w_gate"].shape[1:] == (4, 64, 32)  # four of eight held
    assert weights.num_params(cfg) == config.num_params()
    assert float(jnp.abs(conv["conv_w"]).mean()) > 0.3  # taps at 1 / sqrt(3), no 0.02
    assert float(jnp.abs(conv["router_bias"]).mean()) > 0.005  # a bias that bites


def test_the_cells_tree_is_the_programs_at_published_sizes():
    """Device-free, at the cell's sizes: shapes, dtypes and the count
    the issue reckoned: two dense conv layers, 28 conv and 10 attention
    expert layers of 8 held experts, embedding = head at 1/8 of the
    vocabulary = 3,643,893,376."""
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
        want = "float32" if path.endswith("router_bias") else "bfloat16"
        assert str(flat[path].dtype) == want, path
    H = 2048
    conv = H * 3 * H + 3 * H + H * H + 2 * H  # operator and both norms
    assert conv == 16_787_456
    attn = 2 * H * 32 * 64 + 2 * H * 8 * 64 + 2 * 64 + 2 * H
    assert attn == 10_489_984
    router, experts = H * 64 + 64, 8 * 3 * H * 1536
    assert (router, experts) == (131_136, 75_497_472)
    want = (
        2 * (conv + 3 * H * 11776) + 28 * (conv + router + experts)
        + 10 * (attn + router + experts) + 8192 * H + H
    )
    assert 2 * (conv + 3 * H * 11776) == 178_278_400
    assert 28 * (conv + router + experts) == 2_587_649_792
    assert 10 * (attn + router + experts) == 861_185_920
    assert weights.num_params(cfg) == config.num_params() == want == 3_643_893_376
    assert flat["conv_layers/conv_win"].shape == (28, H, 3 * H)
    assert flat["conv_layers/w_gate"].shape == (28, 8, H, 1536)
    assert flat["layers/wq"].shape == (10, H, H) and flat["layers/wk"].shape == (10, H, 512)
    assert flat["layers/w_router"].shape == (10, H, 64)
    assert flat["dense_layers/w_up"].shape == (2, H, 11776)
    assert flat["embed"].shape == (8192, H) and "lm_head" not in flat
    # the cache: keys and values for the ten full layers only, a two-row tail for thirty
    from dstack_tpu.serve import engine as E

    shapes = E._cache_shapes(config, 16, 8192, 256)
    assert shapes["k"] == shapes["v"] == (10, 16, 8, 8192, 64)
    assert shapes["conv"] == (30, 16, 2, H) and "state" not in shapes
    size = lambda n: int(np.prod(shapes[n])) * 2
    assert size("k") + size("v") == 2_684_354_560 and size("conv") == 3_932_160
    assert size("conv") / (size("k") + size("v") + size("conv")) < 0.002  # well under 1 %


def test_the_configuration_carries_the_catalogs_keys():
    """Every key of the published config at its published value but the
    two listed in ``reduced``: the depth, the layer kinds and the dense
    prelude are as published."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog beside the guides here")
    with open(catalog) as f:
        row = next(json.loads(l) for l in f if '"LFM2-24B-A2B"' in l)
    cfg = _real()
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == REDUCED
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            continue
        assert cfg[key] == value, key
    assert cfg["num_hidden_layers"] == 40 and cfg["num_dense_layers"] == 2
    assert cfg["layer_types"] == ["conv", "conv", "full_attention", "conv"] * 10
    assert cfg["vocab_size"] * 8 == row["config"]["vocab_size"] == cfg["published"]["vocab_size"]
    assert cfg["num_experts"] * 8 == row["config"]["num_experts"] == cfg["published"]["num_experts"]
    assert cfg["experts_held"] == [0, 8]
    lc = cfg["llama_config"]
    assert (lc["n_experts"], lc["experts_per_token"], lc["capacity_factor"]) == (64, 4, 16.0)
    assert lc["layer_types"] == [
        "full" if t == "full_attention" else "conv" for t in cfg["layer_types"]
    ]
    assert (lc["n_layers"], lc["first_k_dense"], lc["head_dim"]) == (40, 2, 64)
    assert lc["conv_taps"] == cfg["conv_L_cache"] == 3 and lc["tie_embeddings"] is True
    assert cfg["serve_flags"] == ["--max-batch", "16", "--max-seq", "8192"]
    assert (cfg["reference"], cfg["costs"]) == ("conv_gqa_moe", "decode_conv_gqa")
    for reading in ("head_dim", "tie_word_embeddings", "conv_layer", "full_layer",
                    "norms", "routing", "renorm_epsilon", "weights"):
        assert reading in cfg["assumed"], reading
    assert "8 chips share each layer" in cfg["deployment"]


def _forward(cfg, params, tokens):
    from dstack_tpu.models import llama

    config = launch.build_llama_config(cfg["llama_config"])
    with jax.default_matmul_precision("highest"):
        return np.asarray(llama.forward(params, jnp.asarray(tokens)[None], config))[0]


def test_reference_agrees_with_the_programs_forward():
    """A padded convolution of the whole sequence against the program's
    shifted adds from a tail of zeros; q/k norms, half-split rope, the
    biased selection, the held share, the tied head: 96 tokens."""
    cfg = _cfg()
    params = weights.make_params(cfg, 7)
    tokens = np.random.default_rng(0).integers(1, 512, 96)
    assert np.abs(_forward(cfg, params, tokens) - _ref_logits(cfg, params, tokens)).max() < TIGHT


def _faulty(cfg, params, fault):
    cfg, params = copy.deepcopy(cfg), dict(params)
    if fault == "no_selection_bias":  # the picks by the scores alone
        cfg["use_expert_bias"] = False
    elif fault == "a_dropped_tap":
        for stack in ("dense_layers", "conv_layers"):
            p = dict(params[stack])
            p["conv_w"] = p["conv_w"].at[:, 0].set(0.0)  # the oldest row unread
            params[stack] = p
    elif fault == "one_more_pick":
        cfg["num_experts_per_tok"] += 1
    elif fault == "gates_not_normed":
        cfg["norm_topk_prob"] = False
    elif fault == "attention_where_conv":
        kinds = cfg["layer_types"]
        kinds[2], kinds[3] = kinds[3], kinds[2]
    elif fault == "no_qk_norm":
        p = dict(params["layers"])
        p["q_norm"] = p["q_norm"] * 1.5  # a norm's weight that is not the program's
        params["layers"] = p
    elif fault == "bf16_weights":
        params = jax.tree.map(lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params)
    return cfg, params


@pytest.mark.parametrize("fault", [
    "int8", "bf16_weights", "a_dropped_tap", "no_selection_bias", "one_more_pick",
    "gates_not_normed", "no_qk_norm",
])
def test_a_control_fails(fault):
    """What the comparison is for: each of these readings of the model
    is farther from the program than ``FAULT``."""
    cfg = _cfg()
    params = weights.make_params(cfg, 7)
    tokens = np.random.default_rng(0).integers(1, 512, 96)
    logits = _forward(cfg, params, tokens)
    ref_cfg, ref_params = _faulty(cfg, params, fault)
    ref = _ref_logits(ref_cfg, ref_params, tokens, "int8" if fault == "int8" else "f32")
    assert np.abs(logits - ref).max() > FAULT


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Eight chips hold eight experts each of a layer of 64, top-4 by a
    biased selection, no shared expert. What the eight shares' held
    experts give adds up to what the uncut reference gives for the
    whole layer; every pick lands on exactly one chip; and each share of
    the reference is the program's share."""
    from dstack_tpu.models import moe

    H, F, E, PER, K = 64, 32, 64, 8, 4
    k = jax.random.split(jax.random.key(3), 6)
    draw = lambda key, *shape: jax.random.normal(key, shape, jnp.float32) * 0.05
    whole = {
        "w_router": draw(k[0], H, E) * 4, "router_bias": draw(k[1], E),
        "w_gate": draw(k[2], E, H, F), "w_up": draw(k[3], E, H, F),
        "w_down": draw(k[4], E, F, H),
    }
    x = jax.random.normal(k[5], (2, 24, H), jnp.float32)
    routing = dict(top_k=K, bias=True, renorm=True, scaling=1.0, precision="f32")
    with jax.default_matmul_precision("highest"):
        uncut = jnp.stack([R.moe(row, whole, held=(0, E), **routing) for row in x])
        total, held_picks = jnp.zeros_like(x), 0
        for first in range(0, E, PER):
            share = {
                **whole,
                **{n: whole[n][first:first + PER] for n in ("w_gate", "w_up", "w_down")},
            }
            out, aux = moe.moe_mlp(
                x, share, E, K, E / K, None, None, renorm=True, score="sigmoid",
                routed_scale=1.0, held=(first, PER), valid=jnp.ones(x.shape[:2], bool),
            )
            total, held_picks = total + out, held_picks + int(aux["held_picks"])
            mine = jnp.stack([R.moe(row, share, held=(first, PER), **routing) for row in x])
            assert np.abs(np.asarray(mine - out)).max() < TIGHT
    assert held_picks == 2 * 24 * K  # every pick on one chip
    assert np.abs(np.asarray(total - uncut)).max() < TIGHT
    assert np.abs(np.asarray(uncut)).max() > FAULT  # and it is no small term
    # the bias moved the selection (else the control above would be idle)
    plain = jnp.stack([
        R.moe(row, whole, held=(0, E), **{**routing, "bias": False}) for row in x
    ])
    assert np.abs(np.asarray(plain - uncut)).max() > FAULT
    # and the selection ONLY: gates of s + b over the same picks are another layer
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(x @ whole["w_router"])
        top_b, top_i = jax.lax.top_k(s + whole["router_bias"], K)
        gates = top_b / (top_b.sum(-1, keepdims=True) + 1e-6)
        biased = sum(
            gates[..., j, None] * jnp.stack([
                R.swiglu(row, whole["w_gate"][e], whole["w_up"][e], whole["w_down"][e], "f32")
                for row, e in zip(x.reshape(-1, 1, H), top_i[..., j].reshape(-1))
            ]).reshape(x.shape)
            for j in range(K)
        )
    assert np.abs(np.asarray(biased - uncut)).max() > FAULT


def test_decode_step_costs_at_the_cells_shapes():
    """The bytes and operations of a token step as ISSUE 44 reckoned
    them: about 5.2 GB of weights at 16 slots, of it the 30 operators
    1.0 GB and the picked held experts 3.7 GB (5.15 of 8 a layer), plus
    the ten full layers' rows at the context and two tail rows a conv
    layer; nothing of a conv layer grows with the context."""
    c = _real()["llama_config"]
    H = 2048
    conv = H * 3 * H + 3 * H + H * H
    attn = 2 * H * H + 2 * H * 512
    assert D.conv_weights(c) == conv == 16_783_360
    assert D.attn_weights(c) == attn == 10_485_760
    assert 30 * conv * 2 == pytest.approx(1.0e9, rel=0.01)
    tail = 30 * 16 * 2 * 2 * H * 2  # read and written
    assert D.tail_bytes(c, 16) == tail == 7_864_320
    fixed = 30 * conv + 10 * attn + 2 * 3 * H * 11776 + 38 * H * 64 + 8192 * H
    expert = 3 * H * 1536
    touched = lambda b: 8 * (1 - (60 / 64) ** b)  # of the 8 held, under top-4 of 64
    assert touched(16) == pytest.approx(5.15, abs=0.01) and touched(1) == pytest.approx(0.5)
    assert 38 * touched(16) * expert * 2 == pytest.approx(3.7e9, rel=0.01)
    one, full = D.decode_step(c, 1, 2000), D.decode_step(c, 16, 2000)
    assert one["weight_bytes"] == pytest.approx((fixed + 38 * 0.5 * expert + H) * 2, abs=1)
    assert full["weight_bytes"] == pytest.approx(
        (fixed + 38 * touched(16) * expert + 16 * H) * 2, abs=1
    )
    assert full["weight_bytes"] == pytest.approx(5.2e9, rel=0.02)
    kv_row = 10 * 2 * 8 * 64 * 2  # a token's keys and values over the ten full layers
    assert full["cache_bytes"] == 16 * 2000 * kv_row + tail
    assert one["cache_bytes"] == 2000 * kv_row + tail // 16
    flops = 2 * (fixed + 38 * 0.5 * expert) + 10 * 4 * 32 * 64 * 2000 + 30 * 2 * 5 * H
    assert one["flops"] == pytest.approx(flops, abs=1)
    roof = costs.roofline_seconds(full["flops"], full["bytes"], "TPU v5 lite")
    assert roof["bound"] == "memory" and roof["seconds"] == pytest.approx(7.2e-3, rel=0.03)
    # the tails do not grow with the context; the ten full layers' rows do
    far = D.decode_step(c, 16, 3300)
    assert far["bytes"] - full["bytes"] == 16 * 1300 * kv_row
    # a step that read all 8192 reserved rows of every slot moves 2.68 GB for them
    assert 16 * 8192 * kv_row == 2_684_354_560


def test_the_reference_imports_nothing_of_the_program():
    import ast

    path = os.path.join(ROOT, "benchmark", "reference", "conv_gqa_moe.py")
    with open(path) as f:
        src = f.read()
    names = set()
    for node in ast.walk(ast.parse(src)):
        if isinstance(node, ast.Import):
            names |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add(("." * node.level) + (node.module or ""))
    assert not any("dstack_tpu" in n for n in names), names
    assert names <= {"math", "functools", "jax", "jax.numpy", ".", ".dense", ".mla_moe"}
    assert src.count('default_matmul_precision("highest")') == 2  # hidden_states and head
    assert "conv_general_dilated" in src  # the convolution: of the whole padded sequence
    assert "RENORM_EPS = 1e-6" in src  # the model's, not the program's


def test_the_benchmark_validates_with_the_new_cell():
    """Entries looked up by name, not by place: a later cell goes after
    this one."""
    from benchmark import validate

    assert validate.validate(ROOT) == []
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "rag", 1)
    config = next(c for c in b["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == _real()["reduced"] == REDUCED
    assert config["file"] == f"benchmark/configs/{CONFIG}.json"
    listed = {m["name"]: m.get("workloads") for m in b["per_layer"]}
    for name in NEW_METRICS:
        assert listed[name] == [CELL], name
    tps = next(m for m in b["end_to_end"] if m["name"] == "out_tokens_per_s")
    assert CELL in tps["workloads"]
    with open(os.path.join(ROOT, "benchmark", "workloads", CELL + ".json")) as f:
        workload = json.load(f)
    assert workload["traffic"] == {
        "loop": "closed", "clients": 16, "prompt_tokens": [512, 4096],
        "prompt_dist": "loguniform", "output_tokens": [384, 1152], "temperature": 0.0,
        "ramp_s": 16.0, "lengths": "stratified", "stratify_block": 8,
    }
    assert set(workload["end_to_end"]) == {"itl_p95_ms", "out_tokens_per_s", "setup_s"}
    assert (workload["trace_s"], workload["drain_s"], workload["check"]["requests"]) == (4.0, 1.0, 3)


def test_the_new_metrics_read_in_the_new_cell_and_in_no_other():
    import sys

    sys.path.insert(0, ROOT)
    from benchmark import run

    wdir = os.path.join(ROOT, "benchmark", "workloads")
    for fn in sorted(os.listdir(wdir)):
        with open(os.path.join(wdir, fn)) as f:
            workload = json.load(f)
        got = NEW_METRICS & set(run.load_metric_defs(workload))
        assert got == (NEW_METRICS if workload["name"] == CELL else set()), fn
        if workload["name"] == CELL:
            assert "decode_roofline" in run.load_metric_defs(workload)
    mdir = os.path.join(ROOT, "benchmark", "metrics")
    for name in NEW_METRICS:
        with open(os.path.join(mdir, name + ".json")) as f:
            m = json.load(f)
        assert m["cells"] == [CELL] and m["reader"] in ("prom_value", "prom_ratio")


def test_rehearsal_serves_conv_layers_through_the_normal_path():
    """The whole path at toy sizes on the CPU: ``run.py`` → the real
    server entry point, scheduler, warm-up and HTTP → the reference
    child; the line is well-formed, ``correct``, nothing compiled inside
    the window."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu", TF_CPP_MIN_LOG_LEVEL="3")
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "tiny-conv-gqa-moe.rag",
         "--seed", str(2**31 + 29), "--seconds", "4", "--trace", "1", "--platform", "cpu",
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
