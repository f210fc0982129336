"""A latent model whose layer is two attention sublayers and two dense
FFNs with the expert branch across them, routed by a softmax router
with identity experts among its outputs (``sublayers`` = 2,
``zero_experts``), at toy widths on the CPU: the four serving programs
(serial chunk, packed wave, decode step, macro-step, verify step, all
through a cache of a row a sublayer) agree with the plain reference's
full forward on logits, the router does what the model's router does,
and the engine counts what the benchmark reads.

Tolerances. Everything is float32 against float32 at ``highest`` and
nothing is discontinuous (a top-k of router scores can tie only by
accident), so the two sides differ by rounding order: ``TIGHT``, the
2e-5 of the other architectures' comparisons. The same comparison on
weights rounded to bfloat16 moves the logits by ``FAULT`` or more, fifty
times that (``test_bf16_weights_fail_the_same_comparison``).
"""

import dataclasses
import json
import os
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import launch, weights
from benchmark.reference import mla_scmoe_zero as R
from tests.shared import init_params, jitted

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(ROOT, "tests", "benchmark", "data", "scmoe_zero")
TIGHT, FAULT = 2e-5, 1e-3
B, TMAX, CHUNK = 4, 96, 16


def _cfg():
    with open(os.path.join(DATA, "configs", "tiny-scmoe-zero.json")) as f:
        return json.load(f)


@lru_cache(maxsize=None)
def _params():
    return weights.make_params(_cfg(), 7)


def _ref_logits(cfg, params, tokens):
    hid = R.hidden_states(cfg, params, np.asarray(tokens))
    h = R.final_norm(cfg, params)(hid)
    return np.asarray(
        jnp.matmul(h, params["lm_head"].astype(jnp.float32), precision="highest")
    )


class _Served:
    """The engine's programs on one cache, driven by hand so that each
    program's logits can be read."""

    def __init__(self, cfg, params=None, scores=None):
        from dstack_tpu.serve import engine as E

        self.E, self.cfg = E, cfg
        self.c = launch.build_llama_config(cfg["llama_config"])
        self.params = _params() if params is None else params
        self.cache = E.init_cache(self.c, B, TMAX, chunk=CHUNK)
        # a program traced under the `scores` fixture's patch is its own
        self.jitted = lambda fn, **static: jitted(fn, scores, config=self.c, **static)
        self.decode = self.jitted(E.decode_step)

    def serial(self, prompt, slot):
        for start in range(0, len(prompt), CHUNK):
            chunk = prompt[start:start + CHUNK]
            fn = self.jitted(self.E.prefill_chunk_step, start=start)
            logits, self.cache = fn(
                self.params, self.cache,
                jnp.asarray([chunk + [0] * (CHUNK - len(chunk))], jnp.int32),
                jnp.asarray(slot, jnp.int32), jnp.asarray(len(chunk) - 1, jnp.int32),
            )
        return np.asarray(logits[0])

    def packed(self, prompts: dict):
        """Every prompt a chunk a wave, rows at unequal starts once the
        shorter prompts are through → {slot: last logits}."""
        fn = self.jitted(self.E.prefill_packed_step)
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


def _serial_then_decode(sv, n_prompt=40, n_steps=24):
    prompt = np.random.default_rng(0).integers(1, 512, n_prompt).tolist()
    with jax.default_matmul_precision("highest"):
        got = [sv.serial(prompt, slot=2)]
        toks = list(prompt)
        for _ in range(n_steps):
            toks.append(int(got[-1].argmax()))
            got.append(sv.step({2: toks[-1]}, {2: len(toks) - 1})[2])
    return toks, got


def test_serial_prefill_then_decode_through_a_row_a_sublayer():
    """40 prompt tokens in three chunks, then 24 greedy tokens a step at
    a time, each of the 2 x 2 sublayers writing and reading its own row
    of ``ckv``; the routing counts hold every real token once a layer."""
    cfg = _cfg()
    sv = _Served(cfg)
    assert sv.cache["ckv"].shape == (2 * 2, B, TMAX, 32 + 8)
    assert sv.cache["moe_stats"].shape == (3,) and sv.cache["moe_reads"].shape == (2,)
    toks, got = _serial_then_decode(sv)
    ref = _ref_logits(cfg, sv.params, toks)
    assert max(np.abs(g - ref[39 + i]).max() for i, g in enumerate(got)) < TIGHT
    held, routed, zero = np.asarray(sv.cache["moe_stats"]).tolist()
    assert routed == 64 * 2  # every real token, each of the two expert layers; the padding not
    assert 0 < held and 0 < zero and held + zero <= routed * 3
    # every sublayer's row holds the 64 positions and nothing past them
    rows = np.asarray(sv.cache["ckv"])[:, 2]
    assert (np.abs(rows[:, :64]).sum(-1) > 0).all() and not rows[:, 64:].any()
    assert len({rows[i, :64].tobytes() for i in range(4)}) == 4  # four different rows


def test_bf16_weights_fail_the_same_comparison():
    """What the tolerance is for: the reference on the weights as given
    against the programs on weights rounded to bfloat16."""
    cfg = _cfg()
    params = _params()
    rounded = jax.tree.map(lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params)
    sv = _Served(cfg, rounded)
    toks, got = _serial_then_decode(sv, n_steps=4)
    ref = _ref_logits(cfg, params, toks)
    assert max(np.abs(g - ref[39 + i]).max() for i, g in enumerate(got)) > FAULT


@pytest.fixture(params=["at_once", "in_blocks"])
def scores(request, monkeypatch):
    """Both forms of a packed wave's causal latent attention: all scores
    of all rows at once (what fits half a GB), and rows one after the
    other with their keys in blocks under a running softmax (what a
    wave of 64 heads x 256 queries against 8192 keys takes at the
    published sizes: 1.07 GB of f32 scores a row)."""
    if request.param == "in_blocks":
        from dstack_tpu.serve import engine as E

        monkeypatch.setattr(E, "_SCORE_BYTES", 0)
    return request.param


def test_packed_wave_macro_step_and_verify_step(scores):
    """Two prompts of unequal length in one wave, then the macro-step (8
    tokens = eight steps') and the verify step over both."""
    cfg = _cfg()
    sv = _Served(cfg, scores=scores)
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
        stats0 = np.asarray(sv.cache["moe_stats"])
        assert stats0[1] == (5 + 52) * 2
        loop = sv.jitted(E.decode_loop, steps=8, max_seq=TMAX)
        live = np.zeros(B, bool)
        live[[1, 3]] = True
        tok, pos = np.zeros(B, np.int32), np.zeros(B, np.int32)
        for s in seqs:
            tok[s], pos[s] = seqs[s][-1], len(seqs[s]) - 1
        # the same eight tokens a step at a time, on a copy of the cache
        by_step, cache0 = {s: [] for s in seqs}, sv.cache
        t1, p1 = dict((s, seqs[s][-1]) for s in seqs), dict((s, len(seqs[s]) - 1) for s in seqs)
        for _ in range(8):
            lg = sv.step(t1, p1)
            for s in seqs:
                t1[s], p1[s] = int(lg[s].argmax()), p1[s] + 1
                by_step[s].append(t1[s])
        sv.cache = cache0
        emitted, sv.cache, *_ = loop(
            sv.params, sv.cache, jnp.asarray(tok), jnp.asarray(pos),
            jnp.full((B,), 30, jnp.int32), jnp.asarray(live), jnp.full((B,), -1, jnp.int32),
        )
        emitted = np.asarray(emitted)
        for s in seqs:
            assert emitted[:, s].tolist() == by_step[s]
            seqs[s] += emitted[:, s].tolist()
        assert (emitted[:, [0, 2]] == -1).all()
        assert np.asarray(sv.cache["moe_stats"])[1] == stats0[1] + 2 * 8 * 2
        sdraft = 4
        grid = np.zeros((B, sdraft), np.int32)
        drafts = {s: rng.integers(1, 512, sdraft - 1).tolist() for s in seqs}
        for s in seqs:
            grid[s] = [seqs[s][-1]] + drafts[s]
            pos[s] = len(seqs[s]) - 1
        vlogits, sv.cache = sv.jitted(E.verify_step)(
            sv.params, sv.cache, jnp.asarray(grid), jnp.asarray(pos),
            write_mask=jnp.asarray(live),
        )
        vlogits = np.asarray(vlogits)
    for s in seqs:
        ref = _ref_logits(cfg, sv.params, seqs[s] + drafts[s])
        n = len(prompts[s])
        for j in range(8):  # the macro-step's tokens are the reference's greedy tokens
            at = n + j
            assert ref[at].max() - ref[at][seqs[s][at + 1]] <= TIGHT
        at = len(seqs[s]) - 1
        diffs += [np.abs(vlogits[s, j] - ref[at + j]).max() for j in range(sdraft)]
    assert max(diffs) < TIGHT


def test_a_prefix_copy_takes_every_sublayers_row():
    """``copy_cache_prefix`` over the 2 x n_layers rows: a slot that
    took another's first 32 positions decodes as if it had prefilled."""
    cfg = _cfg()
    sv = _Served(cfg)
    prompt = np.random.default_rng(3).integers(1, 512, 40).tolist()
    with jax.default_matmul_precision("highest"):
        sv.serial(prompt[:32], slot=0)
        sv.cache = jitted(sv.E.copy_cache_prefix, p=32)(
            sv.cache, jnp.asarray(0, jnp.int32), jnp.asarray(3, jnp.int32)
        )
        fn = sv.jitted(sv.E.prefill_chunk_step, start=32)
        logits, sv.cache = fn(
            sv.params, sv.cache, jnp.asarray([prompt[32:] + [0] * 8], jnp.int32),
            jnp.asarray(3, jnp.int32), jnp.asarray(7, jnp.int32),
        )
    ref = _ref_logits(cfg, sv.params, prompt)
    assert np.abs(np.asarray(logits[0]) - ref[-1]).max() < TIGHT
    rows = np.asarray(sv.cache["ckv"])
    assert np.array_equal(rows[:, 3, :32], rows[:, 0, :32])
    assert sv.cache["moe_stats"].shape == (3,)  # the counts ride along untouched


# --------------------------------------------------------------------------
# the router


def _router_case(case):
    """→ (x, w_router, bias, kwargs of ``moe.router``) at 8 real + 4
    identity outputs, top-3, gates x 6."""
    k = jax.random.split(jax.random.key(5), 3)
    x = jax.random.normal(k[0], (2, 10, 16), jnp.float32)
    w = jax.random.normal(k[1], (16, 12), jnp.float32) * 0.3
    bias = jnp.zeros((12,), jnp.float32)
    if case == "every_pick_real":
        bias = bias.at[8:].set(-1.0)
    elif case == "every_pick_zero":
        bias = bias.at[8:].set(1.0)
    elif case in ("a_bias_moves_the_selection_not_the_gates", "gates_not_renormalised"):
        bias = jax.random.normal(k[2], (12,), jnp.float32) * 0.2
    return x, w, bias


@pytest.mark.parametrize("case", [
    "every_pick_real", "every_pick_zero", "a_bias_moves_the_selection_not_the_gates",
    "gates_not_renormalised", "a_zero_pick_takes_no_capacity_slot",
])
def test_router_with_identity_experts(case):
    from dstack_tpu.models import moe

    x, w, bias = _router_case(case)
    top_k, zero, scale, cap = 3, 4, 6.0, 10
    with jax.default_matmul_precision("highest"):
        dispatch, combine, aux = moe.router(
            x, w, 8, top_k, cap, bias=bias, routed_scale=scale, zero=zero,
            valid=jnp.ones(x.shape[:2], bool),
        )
        probs = jax.nn.softmax(jnp.einsum("bth,he->bte", x, w), axis=-1)
    _, picked = jax.lax.top_k(probs + bias, top_k)
    gates = jnp.take_along_axis(probs, picked, -1) * scale
    is_zero = np.asarray(picked >= 8)
    # dispatch and combine are over the 8 experts that have weights
    assert dispatch.shape == combine.shape == (2, 10, 8, cap)
    # exact: every gate is one product, every token one slot an expert
    real_gate = np.asarray(combine.sum((2, 3)))
    np.testing.assert_allclose(
        real_gate, np.where(is_zero, 0, np.asarray(gates)).sum(-1), rtol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(aux["zero_gate"]), np.where(is_zero, np.asarray(gates), 0).sum(-1),
        rtol=1e-6,
    )
    assert int(aux["zero_picks"]) == is_zero.sum()
    assert float(dispatch.sum()) == (~is_zero).sum()  # a slot a real pick, none a zero pick
    if case == "every_pick_real":
        assert not is_zero.any() and float(jnp.abs(aux["zero_gate"]).max()) == 0.0
    if case == "every_pick_zero":
        assert is_zero.all() and float(jnp.abs(dispatch).max()) == 0.0
        layer = {
            "w_router": w, "router_bias": bias,
            "w_gate": jnp.ones((8, 16, 4)), "w_up": jnp.ones((8, 16, 4)),
            "w_down": jnp.ones((8, 4, 16)),
        }
        with jax.default_matmul_precision("highest"):
            out, _ = moe.moe_mlp(
                x, layer, 8, top_k, 8.0, None, None, routed_scale=scale, zero=zero
            )
        # exactly 6 · Σp · h: no expert ran
        want = np.asarray(gates).sum(-1)[..., None] * np.asarray(x)
        assert np.array_equal(np.asarray(out), want.astype(np.float32))
    if case == "a_bias_moves_the_selection_not_the_gates":
        _, unbiased = jax.lax.top_k(probs, top_k)
        assert not np.array_equal(np.sort(picked, -1), np.sort(unbiased, -1))
        # the gates are the scores at the picked outputs, the bias not in them
        total = real_gate + np.asarray(aux["zero_gate"])
        np.testing.assert_allclose(total, np.asarray(gates).sum(-1), rtol=1e-6)
    if case == "gates_not_renormalised":
        total = real_gate + np.asarray(aux["zero_gate"])
        assert (total < scale * 0.9).all()  # renormalised they would sum to 6


# --------------------------------------------------------------------------
# the engine: what the benchmark's counters read


def test_engine_serves_it_and_counts_sublayers_and_zero_picks():
    from dstack_tpu.models import llama
    from dstack_tpu.serve.engine import GenParams, InferenceEngine

    c = dataclasses.replace(llama.CONFIGS["scmoe-tiny"], experts_held=(0, 4))
    eng = InferenceEngine(
        c, init_params(c, 0), max_batch=2, max_seq=128,
        prefill_chunk=16,
    )
    fam = lambda n: eng.metrics.family(n).value()
    assert fam("dtpu_serve_moe_picks_zero_total") == fam("dtpu_serve_moe_picks_total") == 0
    assert eng.cache["ckv"].shape[0] == 2 * c.n_layers == eng._full_layers
    prompt = [(7 * i) % 500 + 1 for i in range(21)]
    out = eng.generate(prompt, GenParams(max_new_tokens=12))
    assert len(out) == 12
    routed = fam("dtpu_serve_moe_tokens_routed_total")
    assert fam("dtpu_serve_moe_picks_total") == routed * c.experts_per_token
    assert 0 < fam("dtpu_serve_moe_picks_zero_total") < fam("dtpu_serve_moe_picks_total")
    # decode reads a block of the live keys a SUBLAYER, of the rows 2 x n_layers reserve
    read, reserved = (
        fam("dtpu_serve_decode_keys_read_total"), fam("dtpu_serve_decode_keys_reserved_total")
    )
    assert reserved > 0 and reserved % (128 * 2 * 2 * c.n_layers) == 0
    assert read == reserved  # 128 rows are one block
    # the same tokens as the training path's forward, greedy
    toks = list(prompt)
    with jax.default_matmul_precision("highest"):
        for _ in range(3):
            logits = jitted(llama.forward, config=c)(eng.params, jnp.asarray(toks)[None])
            toks.append(int(np.asarray(logits)[0, -1].argmax()))
    assert toks[len(prompt):] == out[:3]
