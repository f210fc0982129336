"""A decoder-hybrid-decoder of selective state-space layers,
differential window and full attention, gated memory units and cross
layers over ONE K/V leaf (``benchmark/reference/ssm_diff_yoco.py``; the
cell ``phi-4-mini-flash-reasoning.longgen``), at toy widths on the CPU:
the benchmark's weights are the tree the program expects, the program's
forward and its serving programs (prefill, then decode through states,
rings and the one K/V leaf) agree with the plain reference's full
forward on logits, each control fails, and the costs module counts what
the issue reckoned. Every serving program case by case, against the
program's forward, with a long memory: ``tests/serve/test_ssm_state.py``.

Tolerances. Everything here is float32 against float32 at ``highest``
and nothing is discontinuous, so the two sides (the program's packed
pairs and token-at-a-time scans, the reference's two softmaxes a pair
and one scan over the sequence) differ by rounding order: ``TIGHT``.
Every control (one softmax in place of two, a state in bfloat16, a
window one key wide of the model's, the int8 control) moves the logits
by ``FAULT`` or more.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import costs, launch, weights
from benchmark.costs import decode_ssm_yoco as D
from benchmark.reference import ssm_diff_yoco as R

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DATA = os.path.join(HERE, "data", "ssm_yoco")
CELL, CONFIG = "phi-4-mini-flash-reasoning.longgen", "phi-4-mini-flash-reasoning"
TIGHT, FAULT = 2e-5, 2e-4
NEW_METRICS = {
    "ssm_state_cache_share", "yoco_keys_read_share", "ssm_window_keys_share",
    "prefill_upper_rows_share",
}
STACKS = {"mamba_layers", "window_layers", "layers", "gmu_layers", "cross_layers"}


def _cfg():
    with open(os.path.join(DATA, "configs", "tiny-ssm-yoco.json")) as f:
        return json.load(f)


def _real():
    with open(os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json")) as f:
        return json.load(f)


def _ref_logits(cfg, params, tokens, precision="f32"):
    hid = R.hidden_states(cfg, params, np.asarray(tokens), precision)
    h = R.final_norm(cfg, params)(hid)
    return np.asarray(
        jnp.matmul(h, params["embed"].T.astype(jnp.float32), precision="highest")
    )


def _params(cfg, seed=7):
    """The seeded tree with what a std-0.02 draw leaves too small to
    show: B and C of a size at which the state's part of the scan's
    output weighs what the skip's does, a slow decay, lambdas of size."""
    p = weights.make_params(cfg, seed)
    ssm = dict(p["mamba_layers"])
    ssm["ssm_wx"], ssm["ssm_win"] = 40.0 * ssm["ssm_wx"], 4.0 * ssm["ssm_win"]
    ssm["ssm_dt_b"] = ssm["ssm_dt_b"] - 3.0
    ssm["ssm_d"] = ssm["ssm_d"] + 1.0
    out = {**p, "mamba_layers": ssm}
    for stack in ("layers", "window_layers", "cross_layers"):
        out[stack] = {**p[stack], "diff_lam": 15.0 * p[stack]["diff_lam"]}
    return out


def test_weights_are_the_tree_the_program_expects():
    from dstack_tpu.models import llama

    cfg = _cfg()
    config = launch.build_llama_config(cfg["llama_config"])
    ours = weights.make_params(cfg, 2**31 + 5)
    theirs = llama.init_params(config, jax.random.key(0))
    shape = lambda t: jax.tree.map(lambda x: (x.shape, str(x.dtype)), t)
    assert shape(ours) == shape(theirs)
    assert set(ours) == {"embed", "final_norm"} | STACKS
    ssm, full, cross, gmu = (ours[k] for k in ("mamba_layers", "layers", "cross_layers", "gmu_layers"))
    assert ssm["ssm_win"].shape == (4, 64, 256) and ssm["ssm_a_log"].shape == (4, 128, 16)
    assert ssm["ssm_wx"].shape == (4, 128, 4 + 32) and ssm["wo"].shape == (4, 128, 64)
    assert full["wk"].shape == (1, 64, 32) and "wk" not in cross and "bv" not in cross
    assert cross["wq"].shape == (2, 64, 64) and cross["bo"].shape == (2, 64)
    assert full["diff_lam"].shape == (1, 4, 8) and full["diff_norm"].shape == (1, 16)
    assert gmu["gmu_w1"].shape == (2, 64, 128) and set(gmu) == {
        "attn_norm", "mlp_norm", "w_gate", "w_up", "w_down", "gmu_w1", "wo"}
    assert ours["final_norm"].shape == (2, 64)  # (w - 1, b)
    assert weights.num_params(cfg) == config.num_params()
    assert float(jnp.abs(ssm["ssm_conv"]).mean()) > 0.3  # taps at 1 / sqrt(4), no 0.02
    assert float(jnp.abs(full["bq"]).mean()) > 0.005  # biases that bite
    assert float(jnp.abs(full["diff_norm"]).max()) == 0  # w - 1: identity


def test_the_cells_tree_is_the_programs_at_published_sizes():
    """Device-free, at the cell's sizes: shapes, dtypes and the count
    the issue reckoned, part by part: 3.85 B parameters, nothing cut."""
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
        assert str(flat[path].dtype) == "bfloat16", path
    H, DI, V = 2560, 5120, 200064
    norms = 2 * 2 * H  # two LayerNorms of weight and bias a layer
    mamba = H * 2 * DI + 5 * DI + DI * 192 + 161 * DI + DI * 16 + DI + DI * H
    assert mamba == 41_241_600  # "41.2 M"
    attn = 2 * H * H + 2 * H * 1280 + (H + 2 * 1280) + H + 6 * 64
    cross = 2 * H * H + 2 * H + 6 * 64
    gmu = 2 * H * DI
    mlp = 3 * H * 10240
    assert (attn, cross, gmu, mlp) == (19_668_864, 13_112_704, 26_214_400, 78_643_200)
    want = (
        9 * mamba + 9 * attn + 7 * cross + 7 * gmu + 32 * (mlp + norms)
        + V * H + 2 * H
    )
    assert want == config.num_params() == weights.num_params(cfg) == 3_852_562_944
    assert want * 2 == pytest.approx(7.70e9, rel=0.002)


def test_the_configuration_carries_the_catalogs_keys():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog beside the guides here")
    with open(catalog) as f:
        row = next(json.loads(l) for l in f if '"Phi-4-mini-flash-reasoning"' in l)
    cfg = _real()
    assert cfg["source"] == row["source_url"] and cfg["reduced"] == []
    for key, value in row["config"].items():
        assert cfg[key] == value, key
    lc = cfg["llama_config"]
    assert lc["layer_types"] == R.layer_kinds(32) == (
        ["mamba", "window"] * 8 + ["mamba", "full"] + ["gmu", "cross"] * 7
    )
    assert (lc["n_layers"], lc["n_heads"], lc["n_kv_heads"], lc["head_dim"]) == (32, 40, 20, 64)
    assert (lc["vocab_size"], lc["hidden_size"], lc["intermediate_size"]) == (200064, 2560, 10240)
    assert (lc["ssm_state"], lc["ssm_conv"], lc["ssm_expand"], lc["ssm_dt_rank"]) == (16, 4, 2, 160)
    assert lc["partial_rotary"] == 0.0 and lc["diff_attn"] and lc["norm_type"] == "layernorm1p"
    assert cfg["serve_flags"] == ["--max-batch", "32", "--max-seq", "8192"]
    assert (cfg["reference"], cfg["costs"]) == ("ssm_diff_yoco", "decode_ssm_yoco")
    for reading in ("head_dim", "layer_map", "mamba", "what_M_is", "gmu", "attention_biases",
                    "head_pairing", "lambda", "sub_norm", "no_rotary", "window_convention",
                    "norms_and_mlp", "weights"):
        assert reading in cfg["assumed"], reading
    assert "one chip a replica, the whole model" in cfg["deployment"]


def _forward(cfg, params, tokens):
    from dstack_tpu.models import llama

    config = launch.build_llama_config(cfg["llama_config"])
    with jax.default_matmul_precision("highest"):
        return np.asarray(llama.forward(params, jnp.asarray(tokens)[None], config))[0]


def test_reference_agrees_with_the_programs_forward():
    """Two softmaxes a pair over the whole sequence against the packed
    pairs through one grouped-query attention; one scan over the tokens
    against the mixer from a past of zeros; 96 tokens, four windows long."""
    cfg = _cfg()
    params = _params(cfg)
    tokens = np.random.default_rng(0).integers(1, 512, 96)
    assert np.abs(_ref_logits(cfg, params, tokens) - _forward(cfg, params, tokens)).max() < TIGHT


class _Engine:
    """The serving programs on one cache, two slots at a time."""

    def __init__(self, cfg, params):
        from dstack_tpu.serve import engine as E

        self.E, self.params = E, params
        self.c = launch.build_llama_config(cfg["llama_config"])
        self.cache = E.init_cache(self.c, 4, 128, chunk=16)

    def prefill(self, prompts: dict, g=2):
        E = self.E
        fn = jax.jit(lambda p, c, t, s, st, li: E.prefill_packed_step(p, c, t, s, st, li, self.c))
        at, out = {s: 0 for s in prompts}, {}
        while at:
            slots = sorted(at)[:g]
            rows = [prompts[s][at[s]:at[s] + 16] for s in slots]
            pad = g - len(slots)
            logits, self.cache = fn(
                self.params, self.cache,
                jnp.asarray([r + [0] * (16 - len(r)) for r in rows] + [[0] * 16] * pad, jnp.int32),
                jnp.asarray(slots + [0] * pad, jnp.int32),
                jnp.asarray([at[s] for s in slots] + [0] * pad, jnp.int32),
                jnp.asarray([len(r) - 1 for r in rows] + [-1] * pad, jnp.int32),
            )
            for i, s in enumerate(slots):
                at[s] += 16
                if at[s] >= len(prompts[s]):
                    out[s] = np.asarray(logits[i])
                    del at[s]
        return out

    def arrays(self, tokens: dict, positions: dict):
        tok, pos, live = np.zeros(4, np.int32), np.zeros(4, np.int32), np.zeros(4, bool)
        for s in tokens:
            tok[s], pos[s], live[s] = tokens[s], positions[s], True
        return jnp.asarray(tok), jnp.asarray(pos), jnp.asarray(live)


def test_prefill_then_decode_through_every_program_agrees_with_the_reference():
    """Two slots of different lengths through the packed wave (one of
    them over three chunks), a serial chunk into a REUSED slot, then
    decode steps, a macro-step of eight and a verify step whose second
    draft falls: every logit read is the reference's full forward's over
    the text so far, and what a rejected draft would have left shows in
    none of them."""
    cfg = _cfg()
    params = _params(cfg)
    sv = _Engine(cfg, params)
    E, c = sv.E, sv.c
    rng = np.random.default_rng(3)
    seq = {0: rng.integers(1, 512, 41).tolist(), 2: rng.integers(1, 512, 13).tolist()}
    ref = lambda toks: _ref_logits(cfg, params, toks)
    first = sv.prefill(seq)
    for s in seq:
        assert np.abs(first[s] - ref(seq[s])[-1]).max() < TIGHT, s
        seq[s].append(int(first[s].argmax()))
    # slot 2 served: a new request in it, a serial chunk from position 0
    seq[2] = rng.integers(1, 512, 9).tolist()
    chunk = jax.jit(lambda p, ca, t, s, li: E.prefill_chunk_step(p, ca, t, s, li, c, start=0))
    logits, sv.cache = chunk(
        params, sv.cache, jnp.asarray([seq[2] + [0] * 7], jnp.int32),
        jnp.asarray(2, jnp.int32), jnp.asarray(8, jnp.int32),
    )
    assert np.abs(np.asarray(logits[0]) - ref(seq[2])[-1]).max() < TIGHT
    seq[2].append(int(np.asarray(logits[0]).argmax()))
    step = jax.jit(lambda p, ca, t, pos, m: E.decode_step(p, ca, t, pos, c, write_mask=m))
    for _ in range(3):
        logits, sv.cache = step(params, sv.cache, *sv.arrays(
            {s: t[-1] for s, t in seq.items()}, {s: len(t) - 1 for s, t in seq.items()}))
        for s, t in seq.items():
            assert np.abs(np.asarray(logits[s]) - ref(t)[-1]).max() < TIGHT, s
            t.append(int(np.asarray(logits[s]).argmax()))
    loop = jax.jit(lambda p, ca, t, pos, rem, act, eos: E.decode_loop(
        p, ca, t, pos, rem, act, eos, c, steps=8, max_seq=128))
    tok, pos, act = sv.arrays({s: t[-1] for s, t in seq.items()}, {s: len(t) - 1 for s, t in seq.items()})
    toks, sv.cache, *_ = loop(
        params, sv.cache, tok, pos, jnp.full((4,), 50, jnp.int32), act, jnp.full((4,), -1, jnp.int32))
    for s, t in seq.items():
        for i in range(8):  # a near-tie may part two float32 runs: the pick is within rounding of the best
            want = ref(t)[-1]
            assert want[int(toks[i, s])] > want.max() - 10 * TIGHT
            t.append(int(toks[i, s]))
    # a verify step: slot 0 drafts four, the second is wrong; slot 2 drafts none
    truth = list(seq[0])
    for _ in range(4):
        truth.append(int(ref(truth)[-1].argmax()))
    draft = truth[len(seq[0]):]
    draft[1] = (draft[1] + 1) % 512
    rows = np.zeros((4, 5), np.int32)
    rows[0], rows[2, 0] = [seq[0][-1]] + draft, seq[2][-1]
    _, pos, live = sv.arrays({s: t[-1] for s, t in seq.items()}, {s: len(t) - 1 for s, t in seq.items()})
    verify = jax.jit(lambda p, ca, t, pos, m, d: E.verify_step(p, ca, t, pos, c, m, draft_len=d))
    logits, sv.cache = verify(
        params, sv.cache, jnp.asarray(rows), pos, live, jnp.asarray([4, 0, 0, 0], jnp.int32))
    assert np.abs(np.asarray(logits[0]) - ref(seq[0][:-1] + rows[0].tolist())[-5:]).max() < TIGHT
    preds = np.asarray(logits).argmax(-1)
    seq[0] += [draft[0], int(preds[0, 1])]  # one stands, then the model's own pick
    seq[2] += [int(preds[2, 0])]
    logits, sv.cache = step(params, sv.cache, *sv.arrays(
        {s: t[-1] for s, t in seq.items()}, {s: len(t) - 1 for s, t in seq.items()}))
    for s, t in seq.items():
        assert np.abs(np.asarray(logits[s]) - ref(t)[-1]).max() < TIGHT, s


@pytest.mark.parametrize("fault", ["one_softmax", "bf16_state", "window", "int8"])
def test_a_control_fails(fault, monkeypatch):
    """What ``TIGHT`` is for: each of these moves the logits by
    ``FAULT`` or more, ten times it."""
    from dstack_tpu.models import llama, mamba

    cfg = _cfg()
    params = _params(cfg)
    tokens = np.random.default_rng(1).integers(1, 512, 64)
    want = _ref_logits(cfg, params, tokens)
    if fault == "int8":
        got = _ref_logits(cfg, params, tokens, "int8")
    elif fault == "window":  # the other convention: the query's own key beside the 24
        got = _ref_logits({**cfg, "sliding_window": cfg["sliding_window"] + 1}, params, tokens)
    else:
        if fault == "one_softmax":
            two = llama.diff_combine
            monkeypatch.setattr(llama, "diff_combine", lambda o, layer, c, lam0: two(
                o.reshape(o.shape[:2] + (c.n_heads // 2, 2, -1)).at[..., 1, :].set(0).reshape(o.shape),
                layer, c, lam0))
        else:
            scan = mamba.scan

            def rounded(x, dt, b_in, c_out, a, state):  # a token at a time, the state in bfloat16
                out = []
                for t in range(x.shape[1]):
                    m, state = scan(x[:, t:t + 1], dt[:, t:t + 1], b_in[:, t:t + 1], c_out[:, t:t + 1], a, state)
                    state = state.astype(jnp.bfloat16).astype(jnp.float32)
                    out.append(m)
                return jnp.concatenate(out, axis=1), state

            monkeypatch.setattr(mamba, "scan", rounded)
        got = _forward(cfg, params, tokens)
    assert np.abs(got - want).max() > FAULT, fault


def test_decode_step_costs_at_the_cells_shapes():
    """The bytes of a token step as ISSUE 48 reckoned them, by hand: at
    32 slots of ~3k tokens about 12.5 GB: MLPs 5.0, the head 1.0, the
    new mixers' weights 1.65, the one K/V leaf read by eight layers 3.9,
    rings 0.67, states read and written 0.2."""
    c = _real()["llama_config"]
    H, DI = 2560, 5120
    mamba = H * 2 * DI + 5 * DI + DI * 192 + 161 * DI + DI * 16 + DI + DI * H
    attn = 2 * H * H + 2 * H * 1280 + 2 * H + 2 * 1280 + 6 * 64
    cross = 2 * H * H + 2 * H + 6 * 64
    assert D.mamba_weights(c) == mamba and D.attn_weights(c) == attn
    assert D.attn_weights(c, cross=True) == cross
    mixers = 9 * mamba + 9 * attn + 7 * cross + 7 * 2 * H * DI
    assert mixers * 2 == pytest.approx(1.65e9, rel=0.01)
    mlps, head = 32 * 3 * H * 10240, 200064 * H
    assert mlps * 2 == pytest.approx(5.0e9, rel=0.01) and head * 2 == pytest.approx(1.0e9, rel=0.03)
    step = D.decode_step(c, 32, 3000)
    assert step["weight_bytes"] == (mixers + mlps + head + 32 * H) * 2
    kv_row = 2 * 1280 * 2  # a token's keys and values of ONE layer: 5,120 B
    assert kv_row == 5120
    leaf, rings = 32 * 3000 * kv_row * 8, 32 * 512 * kv_row * 8
    states = 9 * 32 * 2 * (DI * 16 * 4 + 3 * DI * 2)
    assert D.state_bytes(c, 32) == states
    assert leaf == pytest.approx(3.9e9, rel=0.01) and rings == pytest.approx(0.67e9, rel=0.01)
    assert states == pytest.approx(0.2e9, rel=0.04)
    assert step["cache_bytes"] == leaf + rings + states
    assert step["bytes"] == pytest.approx(12.5e9, rel=0.01)
    roof = costs.roofline_seconds(step["flops"], step["bytes"], "TPU v5 lite")
    assert roof["bound"] == "memory" and roof["seconds"] == pytest.approx(15.3e-3, rel=0.02)
    # a short context lies inside the ring; the states do not grow with it
    short = D.decode_step(c, 32, 200)
    assert short["cache_bytes"] == 32 * 200 * kv_row * 16 + states
    far = D.decode_step(c, 32, 4000)
    assert far["bytes"] - step["bytes"] == 32 * 1000 * kv_row * 8  # the eight readers alone
    one = D.decode_step(c, 1, 3000)
    flops = 2 * (mixers + mlps + head) + 6 * 40 * 64 * (8 * 3000 + 8 * 512) + 9 * 8 * DI * 16
    assert one["flops"] == pytest.approx(flops, abs=1)


def test_the_reference_imports_nothing_of_the_program():
    import ast

    path = os.path.join(ROOT, "benchmark", "reference", "ssm_diff_yoco.py")
    with open(path) as f:
        src = f.read()
    names = set()
    for node in ast.walk(ast.parse(src)):
        if isinstance(node, ast.Import):
            names |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add(("." * node.level) + (node.module or ""))
    assert not any("dstack_tpu" in n for n in names), names
    assert names <= {"math", "functools", "jax", "jax.numpy", ".", ".conv_gqa_moe", ".mla_moe"}
    assert src.count('default_matmul_precision("highest")') == 2  # hidden_states and head
    assert "jax.lax.scan" in src  # the recurrence: over the tokens, in order
    assert src.count("jax.nn.softmax") == 1 and "one(qa, k1), one(qb, k2)" in src  # twice a pair


def test_the_benchmark_validates_with_the_new_cell():
    """Entries looked up by name, not by place: a later cell goes after
    this one."""
    from benchmark import validate

    assert validate.validate(ROOT) == []
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "longgen", 1)
    config = next(c for c in b["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == _real()["reduced"] == []
    assert config["file"] == f"benchmark/configs/{CONFIG}.json"
    listed = {m["name"]: m.get("workloads") for m in b["per_layer"]}
    for name in NEW_METRICS:
        assert listed[name] == [CELL], name
    tps = next(m for m in b["end_to_end"] if m["name"] == "out_tokens_per_s")
    assert CELL in tps["workloads"]
    with open(os.path.join(ROOT, "benchmark", "workloads", CELL + ".json")) as f:
        workload = json.load(f)
    assert workload["traffic"] == {
        "loop": "closed", "clients": 32, "prompt_tokens": [1024, 4096],
        "prompt_dist": "loguniform", "output_tokens": [768, 2304], "temperature": 0.0,
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


def test_rehearsal_serves_the_model_through_the_normal_path():
    """The whole path at toy sizes on the CPU: ``run.py`` → the real
    server entry point, scheduler, warm-up and HTTP → the reference
    child; the line is well-formed, ``correct``, carries the four new
    metrics, and nothing compiled inside the window."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu", TF_CPP_MIN_LOG_LEVEL="3")
    out = subprocess.run(
        # (the toy cell carries the cell's own name: its metrics list it)
        [sys.executable, "benchmark/run.py", "--workload", CELL,
         "--seed", str(2**31 + 29), "--seconds", "4", "--trace", "1", "--platform", "cpu",
         "--bench-dir", DATA],
        capture_output=True, text=True, cwd=ROOT, env=env, timeout=900,
    )
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["attempted"] > 0 and line["failed"] == 0
    assert line["compiles_in_window"] == 0
    metrics = line["metrics"]
    for name in NEW_METRICS:
        assert name in metrics, (name, sorted(metrics))
    assert metrics["prefill_upper_rows_share"]["value"] == pytest.approx(100.0)
    assert 0 < metrics["ssm_state_cache_share"]["value"] < 100
    assert metrics["yoco_keys_read_share"]["value"] == pytest.approx(100.0)  # the einsum, on the CPU
    assert 0 < metrics["ssm_window_keys_share"]["value"] <= 100
    compared = [l for l in out.stdout.splitlines() if l.startswith("compared ")]
    assert len(compared) == 3 and all(l.endswith(" ok") for l in compared)
