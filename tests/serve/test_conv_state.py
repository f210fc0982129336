"""A grouped-query model with gated short-convolution layers
(``layer_types`` kind ``"conv"``, ``models/shortconv.py``) at
``conv-tiny``, float32 on the CPU: a slot's past in such a layer is the
last two rows of the convolution's input, beside the keys and values
that the full layers ALONE hold, and every serving program has to
carry it.

What is held here: the cache's leaves; prefill then decode through
every serving program (serial chunks, packed waves with padded rows and
a pad row, decode step, macro-step, verify step with a draft rejected
mid-way) gives the full forward's logits; a reused slot serves its
second request from zeros; a prompt that shares a prefix with a
registered slot is served whole; the counters the benchmark reads; and
every program's text holds one scan body a period.

Tolerance. Float32 against float32 at ``highest``, nothing
discontinuous: the two sides differ by rounding order, ``TIGHT``. A
tail that was wrong by one row moves the logits by a hundred times
that (``test_a_wrong_tail_shows``).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dstack_tpu.models import llama
from dstack_tpu.serve import engine as E
from tests.shared import init_params, jitted

C = llama.CONFIGS["conv-tiny"]
TIGHT = 2e-5
B, TMAX, CHUNK = 4, 96, 16
N_CONV, N_FULL = C.layer_types.count("conv"), C.layer_types.count("full")
N_MOE = C.n_layers - C.first_k_dense


@pytest.fixture(scope="module")
def params():
    p = init_params(C, 11)
    # a selection bias that bites, as the benchmark draws it
    for stack in ("layers", "conv_layers"):
        bias = p[stack]["router_bias"]
        p[stack]["router_bias"] = 0.02 * jax.random.normal(jax.random.key(3), bias.shape)
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


# --- the serving programs -------------------------------------------------


def test_cache_holds_keys_for_full_layers_only_and_a_tail_a_slot():
    cache = E.init_cache(C, B, TMAX, chunk=CHUNK)
    kv = (N_FULL, B, C.n_kv_heads, TMAX, C.head_dim)
    assert cache["k"].shape == cache["v"].shape == kv and N_FULL == 3
    assert cache["conv"].shape == (N_CONV, B, C.conv_taps - 1, C.hidden_size)
    assert cache["conv"].dtype == C.dtype and N_CONV == 9
    assert "state" not in cache and "win_k" not in cache
    # held picks, routed | read, held
    assert cache["moe_stats"].shape == (2,) and cache["moe_reads"].shape == (2,)
    # a run's row in its kind's buffers: the conv prelude first among the tails
    rows = [(r.kind, llama.run_row(C, r), r.hi - r.lo) for r in llama.layer_runs(C)]
    assert rows == [
        ("conv", 0, 2), ("full", 0, 1), ("conv", 2, 3), ("full", 1, 1),
        ("conv", 5, 3), ("full", 2, 1), ("conv", 8, 1),
    ]


def test_serial_prefill_over_chunks_then_decode(params):
    """40 prompt tokens in three chunks (the second starts from the
    first's tail, the last is padded), then 24 greedy tokens a step at
    a time in slot 2, beside a cache whose other slots hold another
    request's tails."""
    sv = _Served(params)
    sv.serial(_prompt(21, 5), slot=1)
    before = np.asarray(sv.cache["conv"])[:, 1].copy()
    assert np.abs(before).max() > 0
    prompt = _prompt(40)
    got, toks = [sv.serial(prompt, slot=2)], list(prompt)
    for _ in range(24):
        toks.append(int(got[-1].argmax()))
        got.append(sv.step({2: toks[-1]}, {2: len(toks) - 1})[2])
    ref = _forward(params, toks)
    assert max(np.abs(g - ref[39 + i]).max() for i, g in enumerate(got)) < TIGHT
    assert np.array_equal(np.asarray(sv.cache["conv"])[:, 1], before)  # a dead slot's stays
    routed = int(sv.cache["moe_stats"][1])
    assert routed == (21 + 64) * N_MOE  # every real token an expert layer, no padding


def test_a_wrong_tail_shows(params):
    """What ``TIGHT`` is for: a slot decoded from a tail one row out of
    date is a hundred times farther from the forward than rounding."""
    sv = _Served(params)
    prompt = _prompt(30, 9)
    first = sv.serial(prompt, slot=0)
    toks = prompt + [int(first.argmax())]
    sv.cache["conv"] = jnp.roll(sv.cache["conv"], 1, axis=2)  # the two rows swapped
    got = sv.step({0: toks[-1]}, {0: len(toks) - 1})[0]
    assert np.abs(got - _forward(params, toks)[-1]).max() > 100 * TIGHT


def test_packed_wave_with_padded_rows_and_a_pad_row(params):
    """Three prompts of unequal length from position 0 in waves of four
    rows: each row ends on its own tail; the fourth is a pad row that
    carries slot 0 like the real row before it, and once the short
    prompts are through two more."""
    sv = _Served(params)
    prompts = {0: _prompt(45, 1), 1: _prompt(9, 2), 3: _prompt(30, 3)}
    got = sv.packed(prompts)
    for s, p in prompts.items():
        assert np.abs(got[s] - _forward(params, p)[-1]).max() < TIGHT, s
    # and the tails left behind decode on
    toks = {s: list(p) + [int(got[s].argmax())] for s, p in prompts.items()}
    logits = sv.step({s: t[-1] for s, t in toks.items()}, {s: len(t) - 1 for s, t in toks.items()})
    for s, t in toks.items():
        assert np.abs(logits[s] - _forward(params, t)[-1]).max() < TIGHT, s


def test_macro_step_carries_the_tail_over_its_tokens(params):
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
        # the tail after the loop serves the next token too
        nxt = sv.step({s: seq[-1]}, {s: len(seq) - 1})[s]
        assert np.abs(nxt - _forward(params, seq)[-1]).max() < TIGHT


@pytest.mark.parametrize("stand", [0, 2, 4])
def test_a_rejected_draft_has_not_moved_the_tail(params, stand):
    """A verify step of 1 + 4 positions whose drafts agree with the
    model's own greedy picks up to ``stand`` and then do not: its logits
    are the full forward's over the drafted text, and after it the
    slot's tails are those of the tokens that stand (decoding on from
    them gives the full forward's logits), a slot without drafts
    advances by its one token, a dead slot not at all."""
    sv = _Served(params)
    prompts = {1: _prompt(27, 4), 2: _prompt(18, 6)}
    first = sv.packed(prompts, g=2)
    seq = {s: list(p) + [int(first[s].argmax())] for s, p in prompts.items()}
    truth = list(seq[1])  # slot 1's true continuation, greedy by the full forward
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
    dead_before = np.asarray(sv.cache["conv"])[:, 0].copy()
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
    assert np.array_equal(np.asarray(sv.cache["conv"])[:, 0], dead_before)


# --- one scan body a period -----------------------------------------------


def _lowered(name):
    from tests.serve.test_program_pins import _lower

    return _lower(C, name, B, TMAX, chunk=CHUNK, S=3).as_text()


@pytest.mark.parametrize("name", [
    "decode_step", "decode_loop", "verify_step", "prefill_chunk_step@16",
    "prefill_packed_step@2",
])
def test_a_programs_text_has_one_scan_body_a_period(name):
    """Twelve layers = a prelude of two conv layers, two periods of
    (full, conv x 3) and (full, conv): the conv operator stands in the
    text four times (prelude, period, the tail's, and none a layer), the
    attention twice, however many periods the model has: a model of
    three periods more lowers to a text of the same length."""
    text = _lowered(name)
    deeper = dataclasses.replace(
        C, n_layers=C.n_layers + 12, layer_types=C.layer_types[:2]
        + C.layer_types[2:6] * 5 + C.layer_types[-2:],
    )
    from tests.serve.test_program_pins import _lower

    text_deeper = _lower(deeper, name, B, TMAX, chunk=CHUNK, S=3).as_text()
    assert len(text.splitlines()) == len(text_deeper.splitlines())
    # the operator's input projection [H, 3H]: once a scan body that holds it
    win = f"tensor<{C.hidden_size}x{3 * C.hidden_size}xf32>"
    bodies = sum(1 for line in text.splitlines() if "dot_general" in line and win in line)
    assert bodies == 3  # prelude, period, tail


@pytest.mark.parametrize("name", [
    "decode_step", "decode_loop", "verify_step", "prefill_chunk_step@16",
    "prefill_packed_step@2",
])
def test_the_operator_and_its_tail_are_named_in_every_program(name):
    """A capture finds the operator under ``dtpu.conv`` and the tail's
    update under ``dtpu.conv.tail``, beside the full layers'
    ``dtpu.attn_full`` and the held experts' ``dtpu.moe_held``."""
    from tests.serve.test_program_pins import _lower

    text = _lower(C, name, B, TMAX, chunk=CHUNK, S=3).as_text(debug_info=True)
    for scope in ("dtpu.conv/", "dtpu.conv.tail", "dtpu.attn_full", "dtpu.moe_held"):
        assert scope in text, scope


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
    are its own (a tail left over from the first would bend its first
    two positions), and each start is counted."""
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
    no tail exists at the shared length, so no source is offered, the
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
    cache = E.init_cache(C, B, TMAX, chunk=CHUNK)
    with pytest.raises(ValueError, match="'conv'"):
        jax.eval_shape(lambda c: E.copy_cache_prefix(c, 0, 1, p=CHUNK), cache)


def test_the_engine_drafts_and_keeps_its_tails(params):
    """With drafting on (the default) a prompt that repeats itself makes
    the engine verify drafts, some of which fall: every token it serves
    is the full forward's greedy pick over the text served so far (a
    run of tokens is not compared with a run made apart: one near-tie
    would part them for good)."""
    eng = _engine(params)  # spec_draft 4
    unit = _prompt(6, 41)
    prompt = unit * 5
    out = eng.generate(prompt, E.GenParams(max_new_tokens=24))
    assert eng._spec_tries[0] > 0  # drafts were verified
    seq = list(prompt)
    for tok in out:
        logits = _forward(params, seq)[-1]
        assert logits[tok] > logits.max() - 100 * TIGHT, len(seq)
        seq.append(tok)


def test_counters_and_gauge(params):
    eng = _engine(params, spec_draft=0)
    fam = lambda n: eng.metrics.family(n).value()
    size = {n: a.size * a.dtype.itemsize for n, a in eng.cache.items() if n not in E._COUNTS}
    want = 100.0 * size["conv"] / sum(size.values())
    assert fam("dtpu_serve_state_cache_percent") == pytest.approx(want)
    assert fam("dtpu_serve_kv_cache_bytes") == sum(size.values())
    eng.generate(_prompt(30, 51), E.GenParams(max_new_tokens=9))
    routed = fam("dtpu_serve_moe_tokens_routed_total")
    assert routed == (30 + 8) * N_MOE
    # half of the router's width is held: about half of the 2 picks a token land here
    picks = fam("dtpu_serve_moe_picks_held_total")
    assert 0.25 * 2 * routed < picks < 0.75 * 2 * routed
    read, held = fam("dtpu_serve_moe_experts_read_total"), fam("dtpu_serve_moe_experts_held_total")
    assert 0 < read <= held and held % C.experts_held[1] == 0
    # the full layers' decode reads every reserved row (the einsum), 3 layers
    keys = fam("dtpu_serve_decode_keys_read_total")
    assert keys == fam("dtpu_serve_decode_keys_reserved_total") == 8 * TMAX * B * N_FULL


def test_conv_layers_stand_beside_grouped_query_attention_alone():
    with pytest.raises(ValueError, match="beside grouped-query attention"):
        dataclasses.replace(C, kv_lora_rank=8)
    with pytest.raises(ValueError, match="two taps or more"):
        dataclasses.replace(C, conv_taps=1)
    with pytest.raises(ValueError, match="prelude"):
        dataclasses.replace(C, layer_types=("conv", "full") + C.layer_types[2:])
