"""A latent model with linear-attention layers (``layer_types`` kind
``"linear"``, ``models/kda.py``) at ``linear-tiny``, float32 on the CPU:
a slot's past in such a layer is a recurrent state and a convolution
tail beside the latent rows, and every serving program has to carry it.

What is held here: the chunkwise form of the recurrence is the
token-by-token one; prefill then decode through every serving program
(serial chunk, packed wave with padded rows and a pad row, decode step,
macro-step, verify step with a draft rejected mid-way) gives the full
forward's logits; a reused slot serves its second request from zeros; a
prompt that shares a prefix with a registered slot is served whole (the
refusal: no state is kept at a prefix's end); the counters the
benchmark reads.

The decays of seeded weights are fast (a state forgets in a few
tokens): the tests shift ``lin_dt_bias`` so that a state remembers
dozens of tokens and a wrong one shows.
"""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dstack_tpu.models import kda, llama
from dstack_tpu.serve import engine as E
from tests.shared import init_params, jitted

C = llama.CONFIGS["linear-tiny"]
TIGHT = 2e-5
B, TMAX, CHUNK = 4, 96, 16
N_LIN = C.layer_types.count("linear")


@pytest.fixture(scope="module")
def params():
    p = init_params(C, 11)
    for stack in ("dense_layers", "linear_layers"):
        p[stack]["lin_dt_bias"] = p[stack]["lin_dt_bias"] - 3.0  # slow decays
    return p


@jax.jit
def _forward_padded(params, tokens):
    with jax.default_matmul_precision("highest"):
        return llama.forward(params, tokens, C)[0]


def _forward(params, tokens):
    """The full forward's logits [len(tokens), V]: one program at TMAX
    tokens (causal: what is padded behind moves nothing before it)."""
    padded = jnp.asarray([list(tokens) + [0] * (TMAX - len(tokens))], jnp.int32)
    return np.asarray(_forward_padded(params, padded))[: len(tokens)]


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(1, C.vocab_size, n).tolist()


class _Served:
    """The engine's programs on one cache, driven by hand."""

    def __init__(self, params):
        self.params = params
        self.cache = E.init_cache(C, B, TMAX, chunk=CHUNK)
        self.decode = jitted(E.decode_step, config=C)

    def serial(self, prompt, slot):
        for start in range(0, len(prompt), CHUNK):
            chunk = prompt[start:start + CHUNK]
            fn = jitted(E.prefill_chunk_step, config=C, start=start)
            logits, self.cache = fn(
                self.params, self.cache,
                jnp.asarray([chunk + [0] * (CHUNK - len(chunk))], jnp.int32),
                jnp.asarray(slot, jnp.int32), jnp.asarray(len(chunk) - 1, jnp.int32),
            )
        return np.asarray(logits[0])

    def packed(self, prompts: dict, g=4):
        """A chunk of every prompt a wave of ``g`` rows: rows at unequal
        starts once the shorter prompts are through, pad rows (slot 0,
        start 0, ``last_ix`` -1, as the engine makes them) behind."""
        fn = jitted(E.prefill_packed_step, config=C)
        at, out = {s: 0 for s in prompts}, {}
        while at:
            slots = sorted(at)
            rows = [prompts[s][at[s]:at[s] + CHUNK] for s in slots]
            pad = g - len(slots)
            logits, self.cache = fn(
                self.params, self.cache,
                jnp.asarray([r + [0] * (CHUNK - len(r)) for r in rows] + [[0] * CHUNK] * pad, jnp.int32),
                jnp.asarray(slots + [0] * pad, jnp.int32),
                jnp.asarray([at[s] for s in slots] + [0] * pad, jnp.int32),
                jnp.asarray([len(r) - 1 for r in rows] + [-1] * pad, jnp.int32),
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


# --- the recurrence's two forms -------------------------------------------


@partial(jax.jit, static_argnums=(0, 1, 2))  # one program a case, not six draws
def _rule_inputs(t, seed, floor_share):
    ks = jax.random.split(jax.random.key(seed), 6)
    nh, d = 3, 16
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (2, t, nh, d))) * d**-0.5
    k = unit(jax.random.normal(ks[1], (2, t, nh, d)))
    v = jax.random.normal(ks[2], (2, t, nh, d))
    g = -5.0 * floor_share * jax.nn.sigmoid(2 * jax.random.normal(ks[3], (2, t, nh, d)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (2, t, nh)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (2, nh, d, d))


@pytest.mark.parametrize("t", [5, 16, 17, 53, 64])
@pytest.mark.parametrize("floor_share", [0.02, 1.0])
def test_chunkwise_is_token_by_token(t, floor_share):
    """Blocks of 16 solved in closed form, the state carried over the
    block edges (and a last block padded), against one token at a time;
    slow decays (a state that remembers) and the gate's whole range."""
    q, k, v, g, beta, s0 = _rule_inputs(t, t, floor_share)
    s, outs = s0, []
    for i in range(t):
        o, s = kda.token_rule(q[:, i], k[:, i], v[:, i], g[:, i], beta[:, i], s)
        outs.append(o)
    o2, s2 = jitted(kda.chunk_rule)(q, k, v, g, beta, s0)
    assert float(jnp.abs(jnp.stack(outs, 1) - o2).max()) < 1e-5
    assert float(jnp.abs(s - s2).max()) < 1e-5


def test_a_dead_token_leaves_state_and_tail():
    """beta = 0 and g = 0 (how padding is kept out): the state is the
    one before, and the tail holds the last REAL rows."""
    q, k, v, g, beta, s0 = _rule_inputs(20, 3, 1.0)
    real = jnp.arange(20)[None, :] < jnp.asarray([13, 20])[:, None]
    gm, bm = jnp.where(real[..., None, None], g, 0.0), jnp.where(real[..., None], beta, 0.0)
    _, s_all = kda.chunk_rule(q, k, v, gm, bm, s0)
    _, s_13 = kda.chunk_rule(q[:, :13], k[:, :13], v[:, :13], g[:, :13], beta[:, :13], s0)
    assert float(jnp.abs(s_all[0] - s_13[0]).max()) < 1e-6
    pre = jnp.arange(2 * 20 * 4, dtype=jnp.float32).reshape(2, 20, 4)
    tail = kda.next_tail(pre, -jnp.ones((2, 3, 4)), jnp.asarray([13, 1]))
    assert np.array_equal(tail[0], pre[0, 10:13])
    assert np.array_equal(tail[1, :2], -np.ones((2, 4))) and np.array_equal(tail[1, 2], pre[1, 0])


# --- the serving programs -------------------------------------------------


def test_cache_holds_a_state_and_a_tail_a_slot():
    cache = E.init_cache(C, B, TMAX, chunk=CHUNK)
    nh, d = C.n_heads, C.linear_head_dim
    assert cache["state"].shape == (N_LIN, B, nh, d, d) and cache["state"].dtype == jnp.float32
    assert cache["conv"].shape == (N_LIN, B, C.linear_conv - 1, 3 * nh * d)
    assert cache["ckv"].shape[0] == C.n_layers - N_LIN  # rows for the latent layers alone
    # held picks, routed, group hits | read, held
    assert cache["moe_stats"].shape == (3,) and cache["moe_reads"].shape == (2,)


def test_serial_prefill_then_decode(params):
    """40 prompt tokens in three chunks (the last padded), then 24
    greedy tokens a step at a time in slot 2, beside a cache whose
    other slots hold another request's state."""
    sv = _Served(params)
    sv.serial(_prompt(21, 5), slot=1)
    before = np.asarray(sv.cache["state"])[:, 1].copy()
    prompt = _prompt(40)
    got, toks = [sv.serial(prompt, slot=2)], list(prompt)
    for _ in range(24):
        toks.append(int(got[-1].argmax()))
        got.append(sv.step({2: toks[-1]}, {2: len(toks) - 1})[2])
    ref = _forward(params, toks)
    assert max(np.abs(g - ref[39 + i]).max() for i, g in enumerate(got)) < TIGHT
    assert np.array_equal(np.asarray(sv.cache["state"])[:, 1], before)  # a dead slot's stays
    routed = int(sv.cache["moe_stats"][1])
    assert routed == (21 + 64) * (C.n_layers - 1)  # every real token an expert layer, no padding


def test_packed_wave_with_padded_rows_and_a_pad_row(params):
    """Three prompts of unequal length from position 0 in waves of four
    rows: the fourth is a pad row that carries slot 0 like the real row
    before it, and once the short prompts are through two more."""
    sv = _Served(params)
    prompts = {0: _prompt(45, 1), 1: _prompt(9, 2), 3: _prompt(30, 3)}
    got = sv.packed(prompts)
    for s, p in prompts.items():
        assert np.abs(got[s] - _forward(params, p)[-1]).max() < TIGHT, s
    # and the states left behind decode on
    toks = {s: list(p) + [int(got[s].argmax())] for s, p in prompts.items()}
    logits = sv.step({s: t[-1] for s, t in toks.items()}, {s: len(t) - 1 for s, t in toks.items()})
    for s, t in toks.items():
        assert np.abs(logits[s] - _forward(params, t)[-1]).max() < TIGHT, s


def test_macro_step_carries_the_state_over_its_tokens(params):
    sv = _Served(params)
    prompts = {0: _prompt(20, 7), 2: _prompt(33, 8)}
    first = sv.packed(prompts, g=2)
    tok, pos = np.zeros(B, np.int32), np.zeros(B, np.int32)
    act = np.zeros(B, bool)
    for s, p in prompts.items():
        tok[s], pos[s], act[s] = int(first[s].argmax()), len(p), True
    loop = jitted(E.decode_loop, config=C, steps=8, max_seq=TMAX)
    toks, sv.cache, *_ = loop(
        params, sv.cache, jnp.asarray(tok), jnp.asarray(pos),
        jnp.full((B,), 50, jnp.int32), jnp.asarray(act), jnp.full((B,), -1, jnp.int32),
    )
    toks = np.asarray(toks)
    for s, p in prompts.items():
        seq = list(p) + [int(tok[s])]
        for i in range(8):  # each emitted token is the full forward's greedy pick
            want = _forward(params, seq)[-1]
            assert int(want.argmax()) == toks[i, s]
            seq.append(int(toks[i, s]))
        # the state after the loop serves the next token too
        nxt = sv.step({s: seq[-1]}, {s: len(seq) - 1})[s]
        assert np.abs(nxt - _forward(params, seq)[-1]).max() < TIGHT


@pytest.mark.parametrize("stand", [0, 2, 4])
def test_a_rejected_draft_has_not_moved_the_state(params, stand):
    """A verify step of 1 + 4 positions whose drafts agree with the
    model's own greedy picks up to ``stand`` and then do not: its logits
    are the full forward's over the drafted text, and after it the
    slot's state and tail are those of the tokens that stand (decoding
    on from them gives the full forward's logits), a slot without
    drafts advances by its one token, a dead slot not at all."""
    sv = _Served(params)
    prompts = {1: _prompt(27, 4), 2: _prompt(18, 6)}
    first = sv.packed(prompts, g=2)
    seq = {s: list(p) + [int(first[s].argmax())] for s, p in prompts.items()}
    # slot 1's true continuation, greedy by the full forward
    truth = list(seq[1])
    for _ in range(5):
        truth.append(int(_forward(params, truth)[-1].argmax()))
    draft = truth[len(seq[1]):len(seq[1]) + 4]
    if stand < 4:
        draft[stand] = (draft[stand] + 1) % C.vocab_size  # rejected here
    rows = np.zeros((B, 5), np.int32)
    rows[1] = [seq[1][-1]] + draft
    rows[2, 0] = seq[2][-1]  # no draft: zeros behind its last token
    pos = np.zeros(B, np.int32)
    pos[1], pos[2] = len(seq[1]) - 1, len(seq[2]) - 1
    live = np.asarray([False, True, True, False])
    dead_before = np.asarray(sv.cache["state"])[:, 0].copy()
    verify = jitted(E.verify_step, config=C)
    logits, sv.cache = verify(
        params, sv.cache, jnp.asarray(rows), jnp.asarray(pos),
        write_mask=jnp.asarray(live), draft_len=jnp.asarray([0, 4, 0, 0], jnp.int32),
    )
    assert set(sv.cache) == set(E.init_cache(C, B, TMAX, chunk=CHUNK))
    ref = _forward(params, seq[1][:-1] + rows[1].tolist())
    assert np.abs(np.asarray(logits[1]) - ref[-5:]).max() < TIGHT
    preds = np.asarray(logits).argmax(-1)
    agree = [int(preds[1, j]) == draft[j] for j in range(4)]
    assert all(agree[:stand]) and not any(agree[stand:stand + 1])
    # what stands: the last token, the agreed drafts; then the model's own pick
    seq[1] = seq[1] + draft[:stand] + [int(preds[1, stand])]
    seq[2] = seq[2] + [int(preds[2, 0])]
    nxt = sv.step({s: t[-1] for s, t in seq.items()}, {s: len(t) - 1 for s, t in seq.items()})
    for s, t in seq.items():
        assert np.abs(nxt[s] - _forward(params, t)[-1]).max() < TIGHT, s
    assert np.array_equal(np.asarray(sv.cache["state"])[:, 0], dead_before)


# --- the engine: slots, prefixes, counters --------------------------------


def _engine(params, **kw):
    return E.InferenceEngine(
        C, params, max_batch=B, max_seq=TMAX, prefill_chunk=CHUNK, **kw
    )


def _greedy(params, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(_forward(params, seq)[-1].argmax()))
    return seq[len(prompt):]


def test_a_reused_slot_serves_its_second_request_from_zeros(params):
    """One slot, two requests one after the other: the second's tokens
    are its own (a state left over from the first would bend them), and
    each start is counted."""
    eng = _engine(params, spec_draft=0)
    gen = lambda: E.GenParams(max_new_tokens=10)
    a, b = _prompt(37, 21), _prompt(22, 22)
    assert eng.generate(a, gen()) == _greedy(params, a, 10)
    assert eng.free_slots()[0] == 0
    assert eng.generate(b, gen()) == _greedy(params, b, 10)
    fam = lambda n: eng.metrics.family(n).value()
    assert fam("dtpu_serve_state_resets_total") == 2


def test_a_shared_prefix_is_served_whole(params):
    """A second prompt that shares two chunks with a registered slot's:
    no state exists at the shared length, so no source is offered, the
    prompt is prefilled whole, its tokens are right and the prefix
    counters stay 0."""
    eng = _engine(params, spec_draft=0)
    assert eng.prefix_cache is False
    head = _prompt(2 * CHUNK, 31)
    a, b = head + _prompt(5, 32), head + _prompt(9, 33)
    assert eng.generate(a, E.GenParams(max_new_tokens=6)) == _greedy(params, a, 6)
    assert eng.generate(b, E.GenParams(max_new_tokens=6)) == _greedy(params, b, 6)
    fam = lambda n: eng.metrics.family(n).value()
    assert fam("dtpu_serve_prefix_hits_total") == 0
    assert fam("dtpu_serve_prefix_tokens_reused_total") == 0
    eng.warm_prefix_copies()  # compiles nothing for such a model
    assert not eng._copy_fns


def test_copying_a_prefix_of_a_state_is_an_error():
    """``copy_cache_prefix`` names the leaves it can copy by token axis;
    a leaf without one is refused where the program is built, by name."""
    cache = E.init_cache(C, B, TMAX, chunk=CHUNK)
    with pytest.raises(ValueError, match="'state'|'conv'"):
        jax.eval_shape(lambda c: E.copy_cache_prefix(c, 0, 1, p=CHUNK), cache)


def test_the_engine_drafts_and_keeps_its_states(params):
    """With drafting on (the default) a prompt that repeats itself makes
    the engine verify drafts, some of which fall: the tokens are the
    greedy ones all the same."""
    eng = _engine(params)  # spec_draft 4
    unit = _prompt(6, 41)
    prompt = unit * 5
    want = _greedy(params, prompt, 24)
    assert eng.generate(prompt, E.GenParams(max_new_tokens=24)) == want


def test_counters_and_gauge(params):
    eng = _engine(params, spec_draft=0)
    fam = lambda n: eng.metrics.family(n).value()
    size = {n: a.size * a.dtype.itemsize for n, a in eng.cache.items() if n not in E._COUNTS}
    want = 100.0 * (size["state"] + size["conv"]) / sum(size.values())
    assert fam("dtpu_serve_state_cache_percent") == pytest.approx(want)
    assert fam("dtpu_serve_kv_cache_bytes") == sum(size.values())
    assert fam("dtpu_serve_moe_tokens_group_hit_total") == 0
    eng.generate(_prompt(30, 51), E.GenParams(max_new_tokens=9))
    routed = fam("dtpu_serve_moe_tokens_routed_total")
    hit = fam("dtpu_serve_moe_tokens_group_hit_total")
    picks = fam("dtpu_serve_moe_picks_held_total")
    assert routed == (30 + 8) * (C.n_layers - 1)
    # a pick lands here only where the held group is eligible; 2 of 4 groups are
    assert 0 < hit < routed and picks <= hit * C.experts_per_token


def test_group_hits_are_the_tokens_with_the_held_group_eligible(params):
    """``aux["group_hit"]`` against the selection written out: the held
    experts (2, 2) are group 1 of 4; a token counts if group 1 is among
    its two best groups by the sum of their top-2 biased scores."""
    from dstack_tpu.models import moe

    layer = jax.tree.map(lambda a: a[0], params["linear_layers"])
    layer = {**layer, "router_bias": 0.05 * jax.random.normal(jax.random.key(2), (8,))}
    x = jax.random.normal(jax.random.key(1), (2, 24, C.hidden_size))
    valid = jnp.arange(24)[None, :] < jnp.asarray([24, 10])[:, None]
    _, aux = moe.moe_mlp(
        x, layer, 8, 2, 4.0, None, None, renorm=True, score="sigmoid",
        groups=(4, 2), routed_scale=2.5, held=(2, 2), valid=valid,
    )
    s = jax.nn.sigmoid(x @ layer["w_router"]) + layer["router_bias"]
    by_group = np.sort(np.asarray(s).reshape(2, 24, 4, 2), -1).sum(-1)
    best2 = np.argsort(-by_group, -1)[..., :2]
    want = ((best2 == 1).any(-1) & np.asarray(valid)).sum()
    assert int(aux["group_hit"]) == want and 0 < want < 34


def test_a_chip_holds_whole_groups():
    with pytest.raises(ValueError, match="whole number of the router's groups"):
        dataclasses.replace(C, experts_held=(1, 2))
    with pytest.raises(ValueError, match="prelude"):
        dataclasses.replace(C, layer_types=("window",) + C.layer_types[1:])
