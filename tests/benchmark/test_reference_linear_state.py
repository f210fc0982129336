"""A latent model most of whose layers are linear-attention layers (a
gated delta rule with a decay a channel behind a causal convolution,
a recurrent state a slot) with group-limited routing of which a chip
holds one group (``benchmark/reference/kda_mla_moe.py``; the cell
``ling-3.0-flash-vl-13l-ep8.thinking``), at toy widths on the CPU: the
benchmark's weights are the tree the program expects, the program's
forward agrees with the plain reference's on logits, the chips' shares
of an expert layer add up to the uncut layer with the shared expert
counted once, each control fails, and the costs module counts what the
issue reckoned. The serving programs against the program's forward:
``tests/serve/test_linear_state.py``.

Tolerances. Everything here is float32 against float32 at ``highest``
and nothing is discontinuous (a top-k of router scores can tie only by
accident), so the two sides differ by rounding order: ``TIGHT``. Every
control moves the logits by ``FAULT`` or more, fifty times that.
"""

import copy
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import costs, launch, weights
from benchmark.costs import decode_linear_state as D
from benchmark.reference import kda_mla_moe as R

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data", "linear_state")
CELL, CONFIG = "ling-3.0-flash-vl-13l-ep8.thinking", "ling-3.0-flash-vl-13l-ep8"
TIGHT, FAULT = 2e-5, 1e-3
REDUCED = [
    "num_hidden_layers", "first_k_dense_replace", "num_experts", "vocab_size",
    "expert_swiglu_limit_list", "share_expert_swiglu_limit_list",
]


def _cfg():
    with open(os.path.join(DATA, "configs", "tiny-kda-mla-moe.json")) as f:
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
    assert set(ours) == {
        "embed", "dense_layers", "linear_layers", "layers", "final_norm", "lm_head"
    }
    lin, full, pre = ours["linear_layers"], ours["layers"], ours["dense_layers"]
    # a mixer's leaves in the attention's place, the expert layer's under both
    assert lin["lin_wqkv"].shape == (4, 64, 3 * 64) and lin["lin_conv"].shape == (4, 4, 192)
    assert lin["lin_a_log"].shape == (4, 4) and lin["lin_dt_bias"].shape == (4, 64)
    assert "wq" not in lin and "lin_wqkv" not in full and full["w_og"].shape == (2, 64, 4)
    assert pre["lin_wqkv"].shape == (1, 64, 192) and pre["w_gate"].shape == (1, 64, 96)
    for stack in (lin, full):
        assert stack["w_router"].shape[1:] == (64, 8)  # the router's whole width
        assert stack["w_gate"].shape[1:] == (2, 64, 32)  # one group of two held
    assert weights.num_params(cfg) == config.num_params()
    assert float(jnp.abs(lin["lin_conv"]).mean()) > 0.2  # taps at 1 / sqrt(4), no 0.02


def test_the_cells_tree_is_the_programs_at_published_sizes():
    """Device-free, at the cell's sizes: shapes, dtypes and the count
    the issue reckoned: layer 0 (110.2 M), ten linear and two latent
    expert layers of 384.7 M each beside their mixers (63.05 M | 31.97
    M), embedding and head at 1/8 of the vocabulary = 5.52 B."""
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
    f32 = ("router_bias", "lin_a_log", "lin_dt_bias")
    for path, (shape, _) in spec.items():
        assert tuple(shape) == flat[path].shape, path
        # the program's own init keeps these in float32; the benchmark
        # draws them in the served dtype, for both sides alike
        want = "float32" if path.split("/")[-1] in f32 else "bfloat16"
        assert str(flat[path].dtype) == want, path
    H, P = 2560, 32 * 128
    linear = 3 * H * P + 3 * H * P + H * 32 + 4 * 3 * P + 32 + P + 128 + H
    assert linear == 63_052_448
    latent = H * 32 * 192 + H * 576 + 512 + 512 * 32 * 256 + 32 * 128 * H + H * 32 + H
    assert latent == 31_968_256
    experts = 64 * 3 * H * 768 + 3 * H * 768 + H * 512 + 512 + H
    assert experts == 384_699_392
    want = (
        (linear + 3 * H * 6144 + H) + 10 * (linear + experts) + 2 * (latent + experts)
        + 2 * 19648 * H + H
    )
    assert weights.num_params(cfg) == config.num_params() == want == 5_521_694_944
    assert flat["linear_layers/lin_wqkv"].shape == (10, H, 3 * P)
    assert flat["linear_layers/w_gate"].shape == (10, 64, H, 768)
    assert flat["layers/wq"].shape == (2, H, 32 * 192)
    assert flat["layers/w_router"].shape == (2, H, 512)
    assert flat["dense_layers/w_up"].shape == (1, H, 6144)
    assert flat["lm_head"].shape == (H, 19648)


def test_the_configuration_carries_the_catalogs_keys():
    """Every key of the published config at its published value but the
    six listed in ``reduced``; the layers kept are written out."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog beside the guides here")
    with open(catalog) as f:
        row = next(json.loads(l) for l in f if '"Ling-3.0-flash-VL"' in l)
    cfg = _real()
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == REDUCED
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            continue
        assert cfg[key] == value, key
    assert cfg["num_hidden_layers"] == 13 and cfg["first_k_dense_replace"] == 1
    assert cfg["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert cfg["num_experts"] * cfg["n_group"] == row["config"]["num_experts"] == 512
    assert cfg["expert_swiglu_limit_list"] == cfg["share_expert_swiglu_limit_list"] == [0] * 13
    # published layer 0, then 6-17: (i + 1) % layer_group_size == 0 is latent
    kept = [0] + list(range(6, 18))
    assert cfg["layer_types"] == [
        "full_attention" if (i + 1) % cfg["layer_group_size"] == 0 else "linear_attention"
        for i in kept
    ]
    assert cfg["experts_held"] == [0, 64] and cfg["published"]["num_experts"] == 512
    lc = cfg["llama_config"]
    assert (lc["n_experts"], lc["experts_per_token"], lc["router_groups"]) == (512, 8, [8, 4])
    assert lc["layer_types"] == [t.split("_")[0] for t in cfg["layer_types"]]
    assert lc["linear_gate_floor"] == cfg["kda_lower_bound"] == -5
    assert cfg["serve_flags"] == ["--max-batch", "16", "--max-seq", "8192"]
    for reading in ("layer_types", "linear_layer", "decay_gate", "linear_output",
                    "gated_attention_proj_granularity_type", "latent_layer", "routing",
                    "left_out"):
        assert reading in cfg["assumed"], reading


def _forward(cfg, params, tokens):
    from dstack_tpu.models import llama

    config = launch.build_llama_config(cfg["llama_config"])
    with jax.default_matmul_precision("highest"):
        return np.asarray(llama.forward(params, jnp.asarray(tokens)[None], config))[0]


def _slow(params):
    """The gates shifted so that a state remembers dozens of tokens."""
    out = dict(params)
    for stack in ("dense_layers", "linear_layers"):
        out[stack] = {**params[stack], "lin_dt_bias": params[stack]["lin_dt_bias"] - 3.0}
    return out


@pytest.mark.parametrize("decay", ["as_drawn", "slow"])
def test_reference_agrees_with_the_programs_forward(decay):
    """A scan over tokens against the program's blocks of 16 in closed
    form, 96 tokens = six blocks; at the drawn gates and at slow ones."""
    cfg = _cfg()
    params = weights.make_params(cfg, 7)
    if decay == "slow":
        params = _slow(params)
    tokens = np.random.default_rng(0).integers(1, 512, 96)
    assert np.abs(_forward(cfg, params, tokens) - _ref_logits(cfg, params, tokens)).max() < TIGHT


def _faulty(cfg, fault):
    cfg = copy.deepcopy(cfg)
    if fault == "gates_unscaled":
        cfg["routed_scaling_factor"] = 1
    elif fault == "no_group_limit":
        cfg["topk_group"] = cfg["n_group"]
    elif fault == "one_more_pick":
        cfg["num_experts_per_tok"] += 1
    elif fault == "another_floor":
        cfg["kda_lower_bound"] = -4
    elif fault == "a_shorter_convolution":
        cfg["short_conv_kernel_size"] = 3
    elif fault == "latent_where_linear":
        cfg["layer_types"][3], cfg["layer_types"][4] = cfg["layer_types"][4], cfg["layer_types"][3]
    return cfg


@pytest.mark.parametrize("fault", [
    "int8", "bf16_weights", "gates_unscaled", "no_group_limit", "one_more_pick",
    "another_floor",
])
def test_a_control_fails(fault):
    """What the comparison is for: each of these readings of the model
    is farther from the program than ``FAULT``."""
    cfg = _cfg()
    params = _slow(weights.make_params(cfg, 7))
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
    """Eight chips hold one expert group each of a layer of 8 x 4
    experts, and each computes the shared expert for its own tokens.
    What the shares' held experts give, the shared expert counted once,
    is what the uncut reference gives for the whole layer; every pick
    lands on exactly one chip, on one of the token's 4 eligible groups;
    and a chip is sent the tokens that have its group eligible: half."""
    from dstack_tpu.models import moe

    H, F, G, PER, K = 64, 32, 8, 4, 8
    E = G * PER
    k = jax.random.split(jax.random.key(3), 9)
    draw = lambda key, *shape: jax.random.normal(key, shape, jnp.float32) * 0.05
    whole = {
        "w_router": draw(k[0], H, E) * 4, "router_bias": draw(k[1], E),
        "w_gate": draw(k[2], E, H, F), "w_up": draw(k[3], E, H, F),
        "w_down": draw(k[4], E, F, H),
        "w_shared_gate": draw(k[5], H, F), "w_shared_up": draw(k[6], H, F),
        "w_shared_down": draw(k[7], F, H),
    }
    x = jax.random.normal(k[8], (2, 24, H), jnp.float32)
    routing = dict(groups=(G, 4), top_k=K, scaling=2.5, norm=True, precision="f32")
    with jax.default_matmul_precision("highest"):
        uncut = jnp.stack([R.moe(row, whole, held=(0, E), **routing) for row in x])
        shared = jnp.stack([
            R.swiglu(row, whole["w_shared_gate"], whole["w_shared_up"],
                     whole["w_shared_down"], "f32") for row in x
        ])
        total, held_picks, hits = jnp.zeros_like(x), 0, []
        for first in range(0, E, PER):
            share = {
                **whole,
                **{n: whole[n][first:first + PER] for n in ("w_gate", "w_up", "w_down")},
            }
            out, aux = moe.moe_mlp(
                x, share, E, K, E / K, None, None, renorm=True, score="sigmoid",
                groups=(G, 4), routed_scale=2.5, held=(first, PER),
                valid=jnp.ones(x.shape[:2], bool),
            )
            total, held_picks = total + out, held_picks + int(aux["held_picks"])
            hits.append(int(aux["group_hit"]))
            # and the reference's share is the program's
            mine = jnp.stack([R.moe(row, share, held=(first, PER), **routing) for row in x])
            assert np.abs(np.asarray(mine - out)).max() < TIGHT
    assert held_picks == 2 * 24 * K  # every pick on one chip's group
    assert sum(hits) == 2 * 24 * 4 and max(hits) < 2 * 24  # 4 eligible groups a token
    # every chip computed the same shared expert: counted once, not eight times
    assert np.abs(np.asarray(total - (G - 1) * shared - uncut)).max() < TIGHT
    assert np.abs(np.asarray(shared)).max() > FAULT  # and it is no small term


def test_decode_step_costs_at_the_cells_shapes():
    """The bytes and operations of a token step as ISSUE 42 reckoned
    them: at 16 slots and 2k of context 4.7 GB, 45 % the linear layers'
    and 46 % the experts', 5.8 ms at 819 GB/s."""
    c = _real()["llama_config"]
    H, P = 2560, 4096
    linear = 6 * H * P + H * 32 + 4 * 3 * P + 32 + P
    latent = H * 32 * 192 + H * 576 + 512 * 32 * 256 + P * H + H * 32
    assert D.linear_weights(c) == linear == 63_049_760
    assert D.latent_weights(c) == latent == 31_965_184
    # a state read and written in float32, a tail in bf16: 67 MB a layer at 16 slots
    state = 16 * 2 * (32 * 128 * 128 * 4 + 3 * 3 * P * 2)
    assert D.state_bytes(c, 16) == 11 * state and state == pytest.approx(67.1e6 + 2.4e6, rel=0.01)
    fixed = (
        11 * linear + 2 * latent + 3 * H * 6144 + 12 * (H * 512 + 3 * H * 768)
        + 19648 * H
    )
    expert = 3 * H * 768
    touched = lambda b: 64 * (1 - (63 / 64) ** b)  # of the 64 held, under top-8 of 512
    assert touched(16) == pytest.approx(14.3, abs=0.05) and touched(1) == pytest.approx(1.0)
    one, full = D.decode_step(c, 1, 2000), D.decode_step(c, 16, 2000)
    assert one["weight_bytes"] == pytest.approx((fixed + 12 * 1.0 * expert + H) * 2, abs=1)
    assert full["weight_bytes"] == pytest.approx(
        (fixed + 12 * touched(16) * expert + 16 * H) * 2, abs=1
    )
    assert full["cache_bytes"] == 16 * 2000 * 2 * 576 * 2 + 11 * state
    assert one["cache_bytes"] == 2000 * 2 * 576 * 2 + 11 * state // 16
    assert full["bytes"] == pytest.approx(4.7e9, rel=0.02)
    lin_share = (11 * linear * 2 + 11 * state) / full["bytes"]
    exp_share = 12 * (touched(16) * expert + 3 * H * 768 + H * 512) * 2 / full["bytes"]
    assert lin_share == pytest.approx(0.45, abs=0.02) and exp_share == pytest.approx(0.46, abs=0.02)
    flops = 2 * (fixed + 12 * expert) + 2 * 2 * 32 * (2 * 512 + 64) * 2000 + 11 * 32 * 7 * 128 * 128
    assert one["flops"] == pytest.approx(flops, abs=1)
    roof = costs.roofline_seconds(full["flops"], full["bytes"], "TPU v5 lite")
    assert roof["bound"] == "memory" and roof["seconds"] == pytest.approx(5.8e-3, rel=0.03)
    # the state does not grow with the context; the two latent layers' rows do
    far = D.decode_step(c, 16, 3300)
    assert far["bytes"] - full["bytes"] == 16 * 1300 * 2 * 576 * 2


def test_the_reference_imports_nothing_of_the_program():
    import ast

    path = os.path.join(ROOT, "benchmark", "reference", "kda_mla_moe.py")
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
    assert "lax.scan(one, jnp.zeros((nh, d, d)" in src  # the recurrence: a scan over tokens


def test_the_benchmark_validates_with_the_new_cell():
    """By name, not by place: a later cell goes after this one."""
    from benchmark import validate

    assert validate.validate(ROOT) == []
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "thinking", 1)
    config = next(c for c in b["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == _real()["reduced"] == REDUCED
    listed = {m["name"]: m.get("workloads") for m in b["per_layer"]}
    assert listed["state_cache_share"] == listed["moe_group_hit_share"] == [CELL]
    tps = next(m for m in b["end_to_end"] if m["name"] == "out_tokens_per_s")
    assert CELL in tps["workloads"]
    with open(os.path.join(ROOT, "benchmark", "workloads", CELL + ".json")) as f:
        mix = json.load(f)["traffic"]
    assert mix == {
        "loop": "closed", "clients": 16, "prompt_tokens": [256, 1024],
        "prompt_dist": "loguniform", "output_tokens": [768, 2304], "temperature": 0.0,
        "ramp_s": 16.0, "lengths": "stratified", "stratify_block": 8,
    }


def test_the_new_metrics_read_in_the_new_cell_and_in_no_other():
    import sys

    sys.path.insert(0, ROOT)
    from benchmark import run

    names = {"state_cache_share", "moe_group_hit_share"}
    wdir = os.path.join(ROOT, "benchmark", "workloads")
    for fn in sorted(os.listdir(wdir)):
        with open(os.path.join(wdir, fn)) as f:
            workload = json.load(f)
        got = names & set(run.load_metric_defs(workload))
        assert got == (names if workload["name"] == CELL else set()), fn
        if workload["name"] == CELL:
            assert "decode_roofline" in run.load_metric_defs(workload)


def test_rehearsal_serves_linear_layers_through_the_normal_path():
    """The whole path at toy sizes on the CPU: ``run.py`` → the real
    server entry point, scheduler, warm-up and HTTP → the reference
    child; the line is well-formed, ``correct``, nothing compiled inside
    the window."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu", TF_CPP_MIN_LOG_LEVEL="3")
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "tiny-kda-mla-moe.thinking",
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
