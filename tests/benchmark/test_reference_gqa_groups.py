"""A grouped-query model of layer GROUPS (full layers of 4 query heads
and window layers of 6 over 2 KV heads, yarn on half a head beside a
plain rope on the whole of it, per-head gates, a dense first layer, a
chip's share of sigmoid-routed experts) at toy widths on the CPU: the
benchmark's weights are the tree the program expects, the program's
forward and its serving programs (serial chunk, packed wave, decode
step, macro-step, verify step, all through the two caches) agree with
the plain reference's full forward on logits, the shares of an expert
layer add up to the uncut layer, and each control fails.

Tolerances. Everything here is float32 against float32 at ``highest``
and nothing is discontinuous (a top-k of router scores can tie only by
accident), so the two sides differ by rounding order: ``TIGHT``. Every
control (the reference in W8A8 or on weights rounded to bfloat16, a
window off by one, the gate left out, the rope on the whole head of a
full layer) moves the logits by ``FAULT`` or more, fifty times that.
"""

import copy
import json
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import launch, weights
from benchmark.reference import gqa_groups_moe as R

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data", "gqa_groups")
TIGHT, FAULT = 2e-5, 1e-3

B, TMAX, CHUNK = 4, 96, 16  # a ring of 32 rows (window 8 + a chunk) that wraps


def _cfg():
    with open(os.path.join(DATA, "configs", "tiny-gqa-groups.json")) as f:
        return json.load(f)


def _real():
    with open(os.path.join(ROOT, "benchmark", "configs", "laguna-s-2.1-13l-ep8.json")) as f:
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
    assert set(ours) == {"embed", "dense_layers", "layers", "window_layers", "final_norm", "lm_head"}
    assert ours["layers"]["w_gate"].shape[:2] == (2, 4)  # the experts held, not the 16 routed over
    assert ours["layers"]["w_router"].shape == (2, 64, 16)
    # the window layers' projections and gate at their own head count
    assert ours["layers"]["wq"].shape == (2, 64, 4 * 16)
    assert ours["window_layers"]["wq"].shape == (6, 64, 6 * 16)
    assert ours["window_layers"]["wo"].shape == (6, 6 * 16, 64)
    assert ours["window_layers"]["w_og"].shape == (6, 64, 6)
    assert ours["window_layers"]["wk"].shape == ours["layers"]["wk"].shape[:0] + (6, 64, 2 * 16)
    assert weights.num_params(cfg) == config.num_params()


def test_the_cells_tree_is_the_programs_at_published_sizes():
    """Device-free, at the cell's sizes: shapes, dtypes and the count
    the issue reckoned, 4,681,933,824 parameters = 9.36 GB."""
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
        assert tuple(shape) == flat[path].shape and flat[path].dtype == jnp.bfloat16, path
    assert weights.num_params(cfg) == config.num_params() == 4_681_933_824
    assert flat["window_layers/wq"].shape == (9, 3072, 72 * 128)
    assert flat["layers/wq"].shape == (3, 3072, 48 * 128)
    assert flat["layers/w_gate"].shape == (3, 32, 3072, 1024)
    assert flat["dense_layers/w_up"].shape == (1, 3072, 12288)
    assert flat["lm_head"].shape == (3072, 12544)


def test_the_configuration_carries_the_catalogs_keys():
    """Every key of the published config at its published value but the
    ones listed in ``reduced``; the per-layer lists are their first 13."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog beside the guides here")
    with open(catalog) as f:
        row = next(json.loads(l) for l in f if '"Laguna-S-2.1"' in l)
    cfg = _real()
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            continue
        assert cfg[key] == value, key
    for key in ("layer_types", "mlp_layer_types", "gating_types", "num_attention_heads_per_layer"):
        assert cfg[key] == row["config"][key][:13] and key in cfg["reduced"]
    assert cfg["num_hidden_layers"] == 13 and cfg["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert cfg["experts_held"] == [0, 32] and cfg["published"]["num_experts"] == 256
    lc = cfg["llama_config"]
    assert lc["n_experts"] == 256 and lc["experts_per_token"] == 10  # the router's published width
    assert lc["layer_types"] == ["full"] + ["window", "window", "window", "full"] * 3


def test_reference_agrees_with_the_programs_forward():
    from dstack_tpu.models import llama

    cfg = _cfg()
    config = launch.build_llama_config(cfg["llama_config"])
    params = weights.make_params(cfg, 7)
    tokens = np.random.default_rng(0).integers(1, 512, 96)
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(llama.forward(params, jnp.asarray(tokens)[None], config))[0]
    assert np.abs(logits - _ref_logits(cfg, params, tokens)).max() < TIGHT


def _faulty(cfg, fault):
    cfg = copy.deepcopy(cfg)
    if fault == "window_off_by_one":
        cfg["sliding_window"] += 1
    elif fault == "gate_left_out":
        cfg["gating_types"] = ["none"] * len(cfg["gating_types"])
    elif fault == "rope_on_the_whole_head_of_a_full_layer":
        cfg["rope_parameters"]["full_attention"]["partial_rotary_factor"] = 1.0
    elif fault == "yarn_left_out":
        cfg["rope_parameters"]["full_attention"]["rope_type"] = "default"
    elif fault == "picks_not_renormed":
        cfg["norm_topk_prob"] = False
    return cfg


@pytest.mark.parametrize("fault", [
    "int8", "bf16_weights", "window_off_by_one", "gate_left_out",
    "rope_on_the_whole_head_of_a_full_layer", "yarn_left_out", "picks_not_renormed",
])
def test_a_control_fails(fault):
    """What the comparison is for: each of these readings of the model
    is farther from the program than ``FAULT``."""
    from dstack_tpu.models import llama

    cfg = _cfg()
    config = launch.build_llama_config(cfg["llama_config"])
    params = weights.make_params(cfg, 7)
    tokens = np.random.default_rng(0).integers(1, 512, 96)
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(llama.forward(params, jnp.asarray(tokens)[None], config))[0]
    ref_params = params
    if fault == "bf16_weights":
        ref_params = jax.tree.map(
            lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params
        )
    ref = _ref_logits(
        _faulty(cfg, fault), ref_params, tokens, "int8" if fault == "int8" else "f32"
    )
    moved = np.abs(logits - ref).max(-1)
    # positions inside the window see no window; the first has no rope
    assert moved[16:].max() > FAULT, moved.max()


class _Served:
    """The engine's programs on one cache, driven by hand so that each
    program's logits can be read."""

    def __init__(self, cfg):
        from dstack_tpu.serve import engine as E

        self.E, self.cfg = E, cfg
        self.c = launch.build_llama_config(cfg["llama_config"])
        self.params = weights.make_params(cfg, 7)
        self.cache = E.init_cache(self.c, B, TMAX, chunk=CHUNK)
        self.decode = jax.jit(partial(E.decode_step, config=self.c))

    def serial(self, prompt, slot):
        for start in range(0, len(prompt), CHUNK):
            chunk = prompt[start:start + CHUNK]
            fn = jax.jit(partial(self.E.prefill_chunk_step, config=self.c, start=start))
            logits, self.cache = fn(
                self.params, self.cache,
                jnp.asarray([chunk + [0] * (CHUNK - len(chunk))], jnp.int32),
                jnp.asarray(slot, jnp.int32), jnp.asarray(len(chunk) - 1, jnp.int32),
            )
        return np.asarray(logits[0])

    def packed(self, prompts: dict):
        """Every prompt a chunk a wave, rows at unequal starts once the
        shorter prompts are through → {slot: last logits}."""
        fn = jax.jit(partial(self.E.prefill_packed_step, config=self.c))
        at, out = {s: 0 for s in prompts}, {}
        while at:
            slots = sorted(at)
            rows = [prompts[s][at[s]:at[s] + CHUNK] for s in slots]
            pad = [slots[0]] * (2 - len(slots))  # G = 2: a pad row where one is left
            logits, self.cache = fn(
                self.params, self.cache,
                jnp.asarray([r + [0] * (CHUNK - len(r)) for r in rows] + [[0] * CHUNK] * len(pad), jnp.int32),
                jnp.asarray(slots + pad, jnp.int32),
                jnp.asarray([at[s] for s in slots] + [0] * len(pad), jnp.int32),
                jnp.asarray([len(r) - 1 for r in rows] + [-1] * len(pad), jnp.int32),
            )
            for i, s in enumerate(slots):
                at[s] += CHUNK
                if at[s] >= len(prompts[s]):
                    out[s] = np.asarray(logits[i])
                    del at[s]
        return out

    def step(self, tokens: dict, positions: dict):
        tok, pos = np.zeros(B, np.int32), np.zeros(B, np.int32)
        live = np.zeros(B, bool)
        for s in tokens:
            tok[s], pos[s], live[s] = tokens[s], positions[s], True
        logits, self.cache = self.decode(
            self.params, self.cache, jnp.asarray(tok), jnp.asarray(pos),
            write_mask=jnp.asarray(live),
        )
        return np.asarray(logits)


@pytest.fixture(params=["at_once", "in_blocks"])
def scores(request, monkeypatch):
    """Both forms of the masked grouped-query attention of a prefill
    chunk: all scores at once (what a ring takes), and rows one after
    the other with their keys in blocks under a running softmax (what a
    full layer's row takes at the published sizes)."""
    if request.param == "in_blocks":
        from dstack_tpu.serve import engine as E

        monkeypatch.setattr(E, "_SCORE_BYTES", 0)
    return request.param


def test_serial_prefill_then_decode_through_the_ring(scores):
    """40 prompt tokens in three chunks, then 40 greedy tokens a step at
    a time: 80 positions, past the window 8, in a ring of 32 rows that
    wraps twice, beside full layers' rows of 96."""
    cfg = _cfg()
    sv = _Served(cfg)
    assert sv.cache["win_k"].shape == sv.cache["win_v"].shape == (6, B, 2, 32, 16)
    assert sv.cache["k"].shape == sv.cache["v"].shape == (3, B, 2, TMAX, 16)
    prompt = np.random.default_rng(0).integers(1, 512, 40).tolist()
    with jax.default_matmul_precision("highest"):
        got = [sv.serial(prompt, slot=2)]
        toks = list(prompt)
        for _ in range(40):
            toks.append(int(got[-1].argmax()))
            got.append(sv.step({2: toks[-1]}, {2: len(toks) - 1})[2])
    ref = _ref_logits(cfg, sv.params, toks)
    assert max(np.abs(g - ref[39 + i]).max() for i, g in enumerate(got)) < TIGHT
    picks, routed = np.asarray(sv.cache["moe_stats"])
    assert routed == 80 * 8  # every real token, every expert layer; the padding not
    assert 0 < picks < routed * 3


def test_packed_wave_macro_step_and_verify_step(scores):
    """A prompt shorter than the window and one longer than the ring in
    one wave, then the macro-step and the verify step over both."""
    cfg = _cfg()
    sv = _Served(cfg)
    E, c = sv.E, sv.c
    rng = np.random.default_rng(1)
    prompts = {1: rng.integers(1, 512, 5).tolist(), 3: rng.integers(1, 512, 52).tolist()}
    with jax.default_matmul_precision("highest"):
        first = sv.packed(prompts)
        diffs = []
        seqs = {s: list(p) for s, p in prompts.items()}
        for s in seqs:
            diffs.append(np.abs(first[s] - _ref_logits(cfg, sv.params, seqs[s])[-1]).max())
            seqs[s].append(int(first[s].argmax()))
        # macro-step: 8 greedy tokens a slot in one program
        loop = jax.jit(partial(E.decode_loop, config=c, steps=8, max_seq=TMAX))
        live = np.zeros(B, bool)
        live[[1, 3]] = True
        tok, pos = np.zeros(B, np.int32), np.zeros(B, np.int32)
        for s in seqs:
            tok[s], pos[s] = seqs[s][-1], len(seqs[s]) - 1
        emitted, sv.cache, *_ = loop(
            sv.params, sv.cache, jnp.asarray(tok), jnp.asarray(pos),
            jnp.full((B,), 30, jnp.int32), jnp.asarray(live), jnp.full((B,), -1, jnp.int32),
        )
        emitted = np.asarray(emitted)
        for s in seqs:
            seqs[s] += emitted[:, s].tolist()
        assert (emitted[:, [0, 2]] == -1).all()
        # verify step: the last token and three drafts a slot
        sdraft = 4
        grid = np.zeros((B, sdraft), np.int32)
        drafts = {s: rng.integers(1, 512, sdraft - 1).tolist() for s in seqs}
        for s in seqs:
            grid[s] = [seqs[s][-1]] + drafts[s]
            pos[s] = len(seqs[s]) - 1
        vlogits, sv.cache = jax.jit(partial(E.verify_step, config=c))(
            sv.params, sv.cache, jnp.asarray(grid), jnp.asarray(pos),
            write_mask=jnp.asarray(live),
        )
        vlogits = np.asarray(vlogits)
    for s in seqs:
        ref = _ref_logits(cfg, sv.params, seqs[s] + drafts[s])
        n = len(prompts[s])
        # the macro-step's tokens are the reference's greedy tokens
        for j in range(8):
            at = n + j
            assert ref[at].max() - ref[at][seqs[s][at + 1]] <= TIGHT
        at = len(seqs[s]) - 1
        diffs += [np.abs(vlogits[s, j] - ref[at + j]).max() for j in range(sdraft)]
    assert max(diffs) < TIGHT


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Four chips hold four experts each of a layer of sixteen. What the
    shares give, the shared expert counted once, is what the uncut
    reference gives for the whole layer."""
    from dstack_tpu.models import moe
    from benchmark.reference.mla_moe import swiglu

    cfg = _cfg()
    lc = cfg["llama_config"]
    H, F, E = lc["hidden_size"], lc["intermediate_size"], lc["n_experts"]
    k = jax.random.split(jax.random.key(3), 8)
    draw = lambda key, *shape: jax.random.normal(key, shape, jnp.float32) * 0.05
    whole = {
        "w_router": draw(k[0], H, E),
        "w_gate": draw(k[2], E, H, F), "w_up": draw(k[3], E, H, F),
        "w_down": draw(k[4], E, F, H), "w_shared_gate": draw(k[5], H, F),
        "w_shared_up": draw(k[6], H, F), "w_shared_down": draw(k[7], F, H),
    }
    x = jax.random.normal(k[1], (2, 24, H), jnp.float32)
    routing = dict(top_k=lc["experts_per_token"], renorm=True, scaling=lc["routed_scale"])
    with jax.default_matmul_precision("highest"):
        uncut = jnp.stack([
            R.moe(row, whole, held=(0, E), precision="f32", **routing) for row in x
        ])
        shared = jnp.stack([
            swiglu(row, whole["w_shared_gate"], whole["w_shared_up"], whole["w_shared_down"], "f32")
            for row in x
        ])
        total, picks = jnp.zeros_like(x), 0
        for first in range(0, E, 4):
            share = {
                **whole,
                **{n: whole[n][first:first + 4] for n in ("w_gate", "w_up", "w_down")},
            }
            out, aux = moe.moe_mlp(
                x, share, E, lc["experts_per_token"], lc["capacity_factor"], None, None,
                renorm=True, score="sigmoid", routed_scale=lc["routed_scale"],
                held=(first, 4),
            )
            assert share["w_gate"].shape[0] == 4  # absent experts have no weights
            total, picks = total + out, picks + int(aux["held_picks"])
            # and the reference's share is the program's
            mine = jnp.stack([
                R.moe(row, share, held=(first, 4), precision="f32", **routing) for row in x
            ])
            assert np.abs(np.asarray(mine - out)).max() < TIGHT
    assert picks == 2 * 24 * lc["experts_per_token"]  # every pick lands on one chip
    assert np.abs(np.asarray(total - 3 * shared - uncut)).max() < TIGHT


def test_the_reference_imports_nothing_of_the_program():
    import ast

    with open(os.path.join(ROOT, "benchmark", "reference", "gqa_groups_moe.py")) as f:
        tree = ast.parse(f.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add(("." * node.level) + (node.module or ""))
    assert not any("dstack_tpu" in n for n in names), names
    assert names <= {"math", "functools", "jax", "jax.numpy", ".", ".dense", ".mla_moe"}
    src = open(os.path.join(ROOT, "benchmark", "reference", "gqa_groups_moe.py")).read()
    assert src.count('default_matmul_precision("highest")') == 2  # hidden_states and head


def test_yarn_tables_are_transformers_formula_on_the_rotated_half():
    """``rope_tables`` against the closed form at the published
    numbers: 64 rotated dims of 128, factor 128 over 8192."""
    rope = _real()["rope_parameters"]["full_attention"]
    cos, sin = R.rope_tables(rope, 128, 4)
    assert cos.shape == sin.shape == (4, 32)
    import math

    dim, base = 64, 500000.0
    corr = lambda n: dim * math.log(8192 / (n * 2 * math.pi)) / (2 * math.log(base))
    low, high = math.floor(corr(32)), math.ceil(corr(1))
    assert (low, high) == (9, 18)
    want = []
    for i in range(32):
        f = base ** (-2 * i / dim)
        ramp = min(1.0, max(0.0, (i - low) / (high - low)))
        want.append(f / 128 * ramp + f * (1 - ramp))
    got = np.arctan2(np.asarray(sin[1]), np.asarray(cos[1]))  # position 1: the angles
    assert np.allclose(got, want, rtol=1e-5, atol=0)
    assert abs(float(cos[0, 0]) - rope["attention_factor"]) < 1e-6  # cos(0) x the factor
    # the window layers: the whole head, theta 1e4, no scaling
    cos_w, _ = R.rope_tables(_real()["rope_parameters"]["sliding_attention"], 128, 2)
    assert cos_w.shape == (2, 64) and abs(float(cos_w[1, 1]) - math.cos(1e4 ** (-2 / 128))) < 1e-6


def test_step_costs_are_what_was_reckoned_by_hand():
    """``costs/decode_gqa_groups.py`` at the published sizes."""
    from benchmark.costs import decode_gqa_groups as D

    c = _real()["llama_config"]
    row = 2 * 8 * 128 * 2  # one token's keys and values of a layer, bf16
    # a slot at context 300 (inside the window): 300 rows of all 13 layers
    assert D.decode_step(c, 1, 300)["cache_bytes"] == 13 * 300 * row
    # past the window a window layer reads 512 rows, a full layer all of them
    assert D.decode_step(c, 1, 5000)["cache_bytes"] == (4 * 5000 + 9 * 512) * row
    assert D.decode_step(c, 16, 5000)["cache_bytes"] == 16 * (4 * 5000 + 9 * 512) * row
    # experts touched a layer: 32 (1 - (246/256)^batch), 15.1 of 32 at batch 16
    touched = lambda b: 32 * (1 - (246 / 256) ** b)
    assert abs(touched(16) - 15.09) < 0.01
    expert = 3 * 3072 * 1024 * 2
    grow = D.decode_step(c, 16, 300)["weight_bytes"] - D.decode_step(c, 1, 300)["weight_bytes"]
    assert abs(grow - (12 * (touched(16) - touched(1)) * expert + 15 * 3072 * 2)) < 1
    # what every step reads whatever the batch: attention of both shapes
    # with its gates, the dense layer, routers, shared experts, the head
    full = 2 * 3072 * 48 * 128 + 2 * 3072 * 8 * 128 + 3072 * 48
    win = 2 * 3072 * 72 * 128 + 2 * 3072 * 8 * 128 + 3072 * 72
    assert (full, win) == (44_187_648, 63_135_744)
    fixed = (
        4 * full + 9 * win + 3 * 3072 * 12288 + 12 * (3072 * 256 + 3 * 3072 * 1024)
        + 12544 * 3072
    )
    one = D.decode_step(c, 1, 300)
    assert abs(one["weight_bytes"] - (fixed + 12 * touched(1) * 3 * 3072 * 1024 + 3072) * 2) < 1
    # a token multiplies with 10 * 32 / 256 = 1.25 held experts a layer
    flops = 2 * (fixed + 12 * 1.25 * 3 * 3072 * 1024) + 4 * 128 * 300 * (4 * 48 + 9 * 72)
    assert abs(one["flops"] - flops) < 1


def test_the_benchmark_validates_with_the_new_cell():
    from benchmark import validate

    assert validate.validate(ROOT) == []
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    cell = "laguna-s-2.1-13l-ep8.mixed"
    assert b["workloads"][-1]["name"] == cell and b["configs"][-1]["name"] == "laguna-s-2.1-13l-ep8"
    listed = {m["name"]: m.get("workloads") for m in b["per_layer"]}
    assert listed["attn_window_keys_share"] == listed["kv_window_pool_share"] == [cell]
    tps = next(m for m in b["end_to_end"] if m["name"] == "out_tokens_per_s")
    assert tps["workloads"][-1] == cell


def test_rehearsal_serves_layer_groups_through_the_normal_path():
    """The whole path at toy sizes on the CPU: ``run.py`` → the real
    server entry point, scheduler, warm-up and HTTP → the reference
    child; the line is well-formed, ``correct``, nothing compiled inside
    the window, and the new per-layer metrics are in it."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu", TF_CPP_MIN_LOG_LEVEL="3")
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "tiny-gqa-groups.mixed",
         "--seed", str(2**31 + 17), "--seconds", "4", "--trace", "1", "--platform", "cpu",
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
