"""A grouped-query model of state-space layers (``layer_types`` kind
``"mamba"``, ``models/mamba.py``), differential window and full
attention, and an upper half whose layers keep nothing (``"gmu"``,
``"cross"``) at ``ssm-tiny``, float32 on the CPU: a slot's past is a
float32 state and a three-row tail a mamba layer, a ring a window layer
and ONE layer's keys and values, which the cross layers read too; a gmu
layer reads the latest mamba layer's scan output of the same position,
which every serving program carries through its layer walk.

What is held here: the cache's leaves; prefill then decode through
every serving program (serial chunks, packed waves with padded rows and
a pad row, decode step, macro-step, verify step with a draft rejected
mid-way) gives the full forward's logits; a reused slot serves its
second request from zeros; the counters the benchmark reads; and every
program's text holds one scan body a run of a period, the upper half's
too.

Tolerance. Float32 against float32 at ``highest``, nothing
discontinuous: the two sides differ by rounding order, ``TIGHT``. The
draws have a LONG memory (``A_log = log(1..16)``, a step bias near -4:
a state forgets over a hundred tokens, not three), so a state that was
wrong sixty tokens ago still shows; a state rounded to bfloat16, a tail
one row out of date, a cross layer fed its own layer's zeros, one
softmax in place of two each move the logits by far more than
``TIGHT`` (the ``..._shows`` cases).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dstack_tpu.models import llama
from dstack_tpu.serve import engine as E
from tests.shared import init_params, jitted

C = llama.CONFIGS["ssm-tiny"]
TIGHT = 2e-5
B, TMAX, CHUNK = 4, 96, 16
N_SSM, N_WIN = C.layer_types.count("mamba"), C.layer_types.count("window")
N_UP = C.layer_types.count("gmu")  # and as many cross layers
AC = C.attend_config


def long_memory(p):
    """``p`` with the mamba layers' decay and step as a checkpoint has
    them: A = -(1..N) a channel, a step of softplus(-4 + .) = 0.02; and
    biases, lambdas and the sub-norm's weight that bite."""
    ssm = dict(p["mamba_layers"])
    n_state = ssm["ssm_a_log"].shape[-1]
    ssm["ssm_a_log"] = jnp.broadcast_to(
        jnp.log(jnp.arange(1, n_state + 1, dtype=jnp.float32)), ssm["ssm_a_log"].shape
    )
    ssm["ssm_dt_b"] = -4.0 + 0.5 * jax.random.normal(jax.random.key(5), ssm["ssm_dt_b"].shape)
    ssm["ssm_conv_b"] = 0.1 * jax.random.normal(jax.random.key(6), ssm["ssm_conv_b"].shape)
    ssm["ssm_d"] = 1.0 + ssm["ssm_d"]
    # B and C of a size at which the state's part of the scan's output
    # weighs what the skip's does (a std-0.02 draw leaves it a thousandth)
    ssm["ssm_win"], ssm["ssm_wx"] = 4.0 * ssm["ssm_win"], 40.0 * ssm["ssm_wx"]
    out = {**p, "mamba_layers": ssm}
    for i, stack in enumerate(("layers", "window_layers", "cross_layers")):
        st = dict(p[stack])
        for j, name in enumerate(("bq", "bk", "bv", "bo", "diff_norm")):
            if name in st:
                st[name] = 0.1 * jax.random.normal(jax.random.key(10 * i + j), st[name].shape)
        st["diff_lam"] = 0.3 * jax.random.normal(jax.random.key(40 + i), st["diff_lam"].shape)
        out[stack] = st
    return out


@pytest.fixture(scope="module")
def params():
    return long_memory(init_params(C, 11))


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


def test_cache_holds_one_layers_keys_eight_rings_and_a_state_a_mamba_layer():
    cache = E.init_cache(C, B, TMAX, chunk=CHUNK)
    # a KV pair side by side is one head: 2 heads of 32 for 4 of 16
    assert cache["k"].shape == cache["v"].shape == (1, B, 2, TMAX, 32)
    ring = E.ring_rows(C, TMAX, CHUNK)
    assert cache["win_k"].shape == cache["win_v"].shape == (N_WIN, B, 2, ring, 32)
    assert ring == 48 and N_WIN == 3
    assert cache["state"].shape == (N_SSM, B, C.ssm_state, C.ssm_inner)
    assert cache["state"].dtype == jnp.float32 and N_SSM == 4
    assert cache["conv"].shape == (N_SSM, B, C.ssm_conv - 1, C.ssm_inner)
    assert set(cache) == {"k", "v", "win_k", "win_v", "state", "conv"}
    rows = [(r.kind, llama.run_row(C, r)) for r in llama.layer_runs(C)]
    assert rows == [
        ("mamba", 0), ("window", 0), ("mamba", 1), ("window", 1), ("mamba", 2),
        ("window", 2), ("mamba", 3), ("full", 0), ("gmu", 0), ("cross", 0),
        ("gmu", 1), ("cross", 1),
    ]


def test_serial_prefill_over_chunks_then_decode(params):
    """40 prompt tokens in three chunks (the second starts from the
    first's state and tail, the last is padded), then 24 greedy tokens a
    step at a time in slot 2 (past the window of 24: the ring wraps),
    beside a cache whose other slots hold another request's."""
    sv = _Served(params)
    sv.serial(_prompt(21, 5), slot=1)
    before = {n: np.asarray(sv.cache[n])[:, 1].copy() for n in ("state", "conv")}
    assert all(np.abs(a).max() > 0 for a in before.values())
    prompt = _prompt(40)
    got, toks = [sv.serial(prompt, slot=2)], list(prompt)
    for _ in range(24):
        toks.append(int(got[-1].argmax()))
        got.append(sv.step({2: toks[-1]}, {2: len(toks) - 1})[2])
    ref = _forward(params, toks)
    assert max(np.abs(g - ref[39 + i]).max() for i, g in enumerate(got)) < TIGHT
    for n, a in before.items():  # a dead slot's stays
        assert np.array_equal(np.asarray(sv.cache[n])[:, 1], a)


def _next_logits(sv, prompt, slot=0):
    first = sv.serial(prompt, slot=slot)
    return prompt + [int(first.argmax())]


def test_a_wrong_tail_shows(params):
    sv = _Served(params)
    toks = _next_logits(sv, _prompt(30, 9))
    sv.cache["conv"] = jnp.roll(sv.cache["conv"], 1, axis=2)
    got = sv.step({0: toks[-1]}, {0: len(toks) - 1})[0]
    assert np.abs(got - _forward(params, toks)[-1]).max() > 100 * TIGHT


def test_a_state_in_bfloat16_shows(params):
    """What ``TIGHT`` is for: the state rounded to bfloat16 once is far
    outside it, and so is a state that missed the prompt's FIRST chunk
    (sixty tokens back: the long memory)."""
    sv = _Served(params)
    toks = _next_logits(sv, _prompt(30, 9))
    keep = sv.cache["state"]
    sv.cache["state"] = keep.astype(jnp.bfloat16).astype(jnp.float32)
    got = sv.step({0: toks[-1]}, {0: len(toks) - 1})[0]
    assert np.abs(got - _forward(params, toks)[-1]).max() > 20 * TIGHT
    sv = _Served(params)
    prompt = _prompt(64, 10)
    sv.serial(prompt[:16], slot=0)
    sv.cache["state"] = jnp.zeros_like(sv.cache["state"])  # the first chunk's, lost
    for start in (16, 32, 48):
        fn = jitted(E.prefill_chunk_step, config=C, start=start)
        logits, sv.cache = fn(
            params, sv.cache, jnp.asarray([prompt[start:start + 16]], jnp.int32),
            jnp.asarray(0, jnp.int32), jnp.asarray(15, jnp.int32),
        )
    assert np.abs(np.asarray(logits[0]) - _forward(params, prompt)[-1]).max() > 20 * TIGHT


def test_one_softmax_in_place_of_two_shows(params, monkeypatch):
    two = llama.diff_combine
    first = lambda o, layer, c, lam0: two(
        o.reshape(o.shape[:2] + (c.n_heads // 2, 2, -1)).at[..., 1, :].set(0).reshape(o.shape),
        layer, c, lam0,
    )
    sv = _Served(params)
    toks = _next_logits(sv, _prompt(30, 9))
    monkeypatch.setattr(E.llama, "diff_combine", first)
    step = jax.jit(lambda p, c, t, pos, m: E.decode_step(p, c, t, pos, C, write_mask=m))
    tok, pos, live = np.zeros(B, np.int32), np.zeros(B, np.int32), np.zeros(B, bool)
    tok[0], pos[0], live[0] = toks[-1], len(toks) - 1, True
    got = np.asarray(step(params, sv.cache, jnp.asarray(tok), jnp.asarray(pos), jnp.asarray(live))[0])[0]
    assert np.abs(got - _forward(params, toks)[-1]).max() > 100 * TIGHT


def test_a_cross_layer_reads_the_full_layers_rows_and_a_gmu_layer_the_scan(params):
    """Layer 7's K/V moved, every cross layer's output moves (and so do
    the logits); the last mamba layer's weights moved, the gmu layers'."""
    sv = _Served(params)
    toks = _next_logits(sv, _prompt(30, 9))
    want = sv.step({0: toks[-1]}, {0: len(toks) - 1})[0]
    sv2 = _Served(params)
    _next_logits(sv2, _prompt(30, 9))
    sv2.cache["v"] = sv2.cache["v"] * 1.5
    assert np.abs(sv2.step({0: toks[-1]}, {0: len(toks) - 1})[0] - want).max() > 10 * TIGHT
    # the last mamba layer's own way into the residual stream cut (its
    # W_out zeroed), its scan output reaches the logits through the gmu
    # layers alone: its skip weight moved, they move; the gmu layers' W_2
    # zeroed too, nothing does
    def with_(p, stack, name, fn):
        return {**p, stack: {**p[stack], name: fn(p[stack][name])}}

    base = with_(params, "mamba_layers", "wo", lambda w: w.at[-1].set(0))
    moved = with_(base, "mamba_layers", "ssm_d", lambda d: d.at[-1].mul(1.5))
    h = lambda p: np.asarray(_hidden(p, jnp.asarray([toks + [0] * (TMAX - len(toks))], jnp.int32)))
    assert np.abs(h(moved) - h(base)).max() > 100 * TIGHT
    cut = lambda p: with_(p, "gmu_layers", "wo", lambda w: 0 * w)
    assert np.abs(h(cut(moved)) - h(cut(base))).max() == 0


@jax.jit
def _hidden(params, tokens):
    with jax.default_matmul_precision("highest"):
        return llama.forward(params, tokens, C, return_hidden=True)[0]


def test_packed_wave_with_padded_rows_and_a_pad_row(params):
    sv = _Served(params)
    prompts = {0: _prompt(45, 1), 1: _prompt(9, 2), 3: _prompt(30, 3)}
    got = sv.packed(prompts)
    for s, p in prompts.items():
        assert np.abs(got[s] - _forward(params, p)[-1]).max() < TIGHT, s
    toks = {s: list(p) + [int(got[s].argmax())] for s, p in prompts.items()}
    logits = sv.step({s: t[-1] for s, t in toks.items()}, {s: len(t) - 1 for s, t in toks.items()})
    for s, t in toks.items():
        assert np.abs(logits[s] - _forward(params, t)[-1]).max() < TIGHT, s


def test_macro_step_carries_state_tail_and_ring_over_its_tokens(params):
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
        for i in range(8):
            want = _forward(params, seq)[-1]
            assert int(want.argmax()) == toks[i, s]
            seq.append(int(toks[i, s]))
        nxt = sv.step({s: seq[-1]}, {s: len(seq) - 1})[s]
        assert np.abs(nxt - _forward(params, seq)[-1]).max() < TIGHT


@pytest.mark.parametrize("stand", [0, 2, 4])
def test_a_rejected_draft_has_not_moved_state_or_tail(params, stand):
    sv = _Served(params)
    prompts = {1: _prompt(27, 4), 2: _prompt(18, 6)}
    first = sv.packed(prompts, g=2)
    seq = {s: list(p) + [int(first[s].argmax())] for s, p in prompts.items()}
    truth = list(seq[1])
    for _ in range(5):
        truth.append(int(_forward(params, truth)[-1].argmax()))
    draft = truth[len(seq[1]):len(seq[1]) + 4]
    if stand < 4:
        draft[stand] = (draft[stand] + 1) % C.vocab_size
    rows = np.zeros((B, 5), np.int32)
    rows[1] = [seq[1][-1]] + draft
    rows[2, 0] = seq[2][-1]
    pos = np.zeros(B, np.int32)
    pos[1], pos[2] = len(seq[1]) - 1, len(seq[2]) - 1
    live = np.asarray([False, True, True, False])
    dead = {n: np.asarray(sv.cache[n])[:, 0].copy() for n in ("state", "conv")}
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
    seq[1] = seq[1] + draft[:stand] + [int(preds[1, stand])]
    seq[2] = seq[2] + [int(preds[2, 0])]
    nxt = sv.step({s: t[-1] for s, t in seq.items()}, {s: len(t) - 1 for s, t in seq.items()})
    for s, t in seq.items():
        assert np.abs(nxt[s] - _forward(params, t)[-1]).max() < TIGHT, s
    for n, a in dead.items():
        assert np.array_equal(np.asarray(sv.cache[n])[:, 0], a)


# --- one scan body a run of a period --------------------------------------

PROGRAMS = [
    "decode_step", "decode_loop", "verify_step", "prefill_chunk_step@16",
    "prefill_packed_step@2",
]


@pytest.mark.parametrize("name", PROGRAMS)
def test_a_programs_text_does_not_grow_with_either_half(name):
    """Twelve layers = (mamba, window) x 3, then mamba and full, then
    (gmu, cross) x 2: two folded segments (``llama.layer_segments``). A
    model of two periods more in EACH half lowers to a text of the same
    length; the mamba mixer stands in it twice (the lower period's, the
    run before the full layer), the gmu once."""
    from tests.serve.test_program_pins import _lower

    text = _lower(C, name, B, TMAX, chunk=CHUNK, S=3).as_text()
    deeper = dataclasses.replace(
        C, n_layers=C.n_layers + 8,
        layer_types=("mamba", "window") * 5 + ("mamba", "full") + ("gmu", "cross") * 4,
    )
    text_deeper = _lower(deeper, name, B, TMAX, chunk=CHUNK, S=3).as_text()
    assert len(text.splitlines()) == len(text_deeper.splitlines())
    lines = [l for l in text.splitlines() if "dot_general" in l]
    wx = f"tensor<{C.ssm_inner}x{C.ssm_rank + 2 * C.ssm_state}xf32>"
    assert sum(wx in l for l in lines) == 2
    w1 = f"tensor<{C.hidden_size}x{C.ssm_inner}xf32>"
    assert sum(w1 in l for l in lines) == 1


@pytest.mark.parametrize("name", PROGRAMS)
def test_the_new_layers_are_named_in_every_program(name):
    from tests.serve.test_program_pins import _lower

    text = _lower(C, name, B, TMAX, chunk=CHUNK, S=3).as_text(debug_info=True)
    for scope in (
        "dtpu.ssm/", "dtpu.ssm.scan", "dtpu.gmu", "dtpu.diff_attn",
        "dtpu.cross_attn", "dtpu.attn_window", "dtpu.attn_full",
    ):
        assert scope in text, scope


def test_two_patterns_fold_into_two_segments():
    segs = llama.layer_segments(C)
    shape = lambda rs: [(r.key, r.lo, r.hi) for r in rs]
    assert [(shape(s.head), shape(s.period), s.count, shape(s.tail)) for s in segs] == [
        ([], [("mamba_layers", 0, 1), ("window_layers", 0, 1)], 3, []),
        ([("mamba_layers", 3, 4), ("layers", 0, 1)],
         [("gmu_layers", 0, 1), ("cross_layers", 0, 1)], 2, []),
    ]
    # a model of one pattern is one segment, its tail as it was
    for name in ("conv-tiny", "linear-tiny"):
        one = llama.CONFIGS[name]
        assert llama.layer_segments(one) == [llama.layer_periods(one)]


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
    eng = _engine(params, spec_draft=0)
    gen = lambda: E.GenParams(max_new_tokens=10)
    a, b = _prompt(37, 21), _prompt(22, 22)
    assert eng.generate(a, gen()) == _greedy(params, a, 10)
    assert eng.free_slots()[0] == 0
    assert eng.generate(b, gen()) == _greedy(params, b, 10)
    fam = lambda n: eng.metrics.family(n).value()
    assert fam("dtpu_serve_state_resets_total") == 2
    assert eng.prefix_cache is False


def test_the_engine_verifies_drafts_and_keeps_its_states(params):
    """A draft handed to the engine's own verify path (a random model
    seldom repeats a bigram, so none is found in its history): two of
    four stand, the third is the model's own pick, and every token it
    serves after that, by macro-steps, is the full forward's greedy
    pick over the text served so far."""
    eng = _engine(params)  # spec_draft 4
    prompt = _prompt(20, 41)
    slot, tok = eng.add_request(prompt, E.GenParams(max_new_tokens=30))
    truth = _greedy(params, prompt + [tok], 4)
    draft = truth[:2] + [(truth[2] + 1) % C.vocab_size, truth[3]]
    eng._last_step_phase = "spec"
    out = eng._spec_step([slot], {slot: draft})
    assert out[slot] == truth[:3]
    seq = prompt + [tok] + out[slot]
    while eng.active[slot]:
        for t in eng.step().get(slot, []):
            logits = _forward(params, seq)[-1]
            assert logits[t] > logits.max() - 100 * TIGHT, len(seq)
            seq.append(t)
    assert len(seq) == 20 + 30


def test_counters_and_gauge(params):
    eng = _engine(params, spec_draft=0)
    fam = lambda n: eng.metrics.family(n).value()
    size = {n: a.size * a.dtype.itemsize for n, a in eng.cache.items()}
    want = 100.0 * (size["state"] + size["conv"]) / sum(size.values())
    assert fam("dtpu_serve_state_cache_percent") == pytest.approx(want)
    assert fam("dtpu_serve_kv_cache_bytes") == sum(size.values())
    eng.generate(_prompt(30, 51), E.GenParams(max_new_tokens=9))
    # every prompt position through both halves, today
    assert fam("dtpu_serve_prefill_lower_rows_total") == 30
    assert fam("dtpu_serve_prefill_upper_rows_total") == 30
    # the one K/V leaf, read once a READING layer: the full layer and two
    # cross layers (the einsum: every reserved row)
    keys = fam("dtpu_serve_decode_keys_read_total")
    assert keys == fam("dtpu_serve_decode_keys_reserved_total") == 8 * TMAX * B * (1 + N_UP)
    # three rings: a token at context n sees min(n, 24) of its n keys
    ctx = range(31, 39)
    assert fam("dtpu_serve_window_keys_visible_total") == N_WIN * sum(min(n, 24) for n in ctx)
    assert fam("dtpu_serve_window_keys_in_context_total") == N_WIN * sum(ctx)


def test_what_the_new_kinds_stand_beside():
    with pytest.raises(ValueError, match="beside grouped-query attention"):
        dataclasses.replace(C, kv_lora_rank=8, diff_attn=False, layer_types=("mamba",) * 12)
    with pytest.raises(ValueError, match="one kind of layer that holds"):
        dataclasses.replace(C, layer_types=("mamba", "conv") + C.layer_types[2:])
    with pytest.raises(ValueError, match="reads the scan of a mamba layer"):
        dataclasses.replace(C, layer_types=("gmu",) + C.layer_types[1:])
    with pytest.raises(ValueError, match="one full"):
        dataclasses.replace(C, layer_types=C.layer_types[:6] + ("mamba", "cross") + C.layer_types[8:])
    with pytest.raises(ValueError, match="no rotary"):
        dataclasses.replace(C, partial_rotary=1.0)
