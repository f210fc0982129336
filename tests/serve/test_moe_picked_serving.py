"""The serving programs of a model that holds a share of its experts, at
a shape where a decode call reads only the picked experts
(``moe.reads_picked_experts``): the macro-step equals its token steps,
both forms of the expert FFNs serve the same logits, and the engine
publishes how many of the held experts the routed layer calls read.

Toy widths, a router wide enough (60 experts + 4 identity ones, top-2,
8 held) that 4 slots and a verify grid of 4 x 5 expect most experts
unpicked; ``scmoe-tiny``'s layer otherwise (two latent sublayers, the
experts across them), so the loop sits where the agent cell's does. The
rule weighs an expert's bytes against a loop trip's fixed cost
(``moe.TRIP_BYTES``), which a toy expert is far under: every test here
runs with that cost scaled to the toy expert, a quarter of its bytes as
it is of a cell's, so that the rule breaks even at a share of 0.8.
"""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dstack_tpu.models import llama, moe
from dstack_tpu.serve import engine as E
from tests.shared import init_params, jitted

B, TMAX, CHUNK = 4, 64, 16
HELD = 8
C = dataclasses.replace(
    llama.CONFIGS["scmoe-tiny"], n_experts=60, zero_experts=4,
    experts_per_token=2, experts_held=(0, HELD), capacity_factor=32.0,
)
LAYERS = C.n_layers  # an expert branch a layer


@pytest.fixture(scope="module")
def params():
    return init_params(C, 3)


@pytest.fixture(autouse=True)
def _toy_trip(params, monkeypatch):
    layer = params["layers"]
    expert_bytes = sum(layer[w][0, 0].nbytes for w in moe.EXPERT_STACKS)
    monkeypatch.setattr(moe, "TRIP_BYTES", expert_bytes // 4)


def _takes_picked(b, t):
    layer = jax.eval_shape(lambda: llama.init_params(C, jax.random.key(0)))["layers"]
    return moe.reads_picked_experts(
        layer, b, t, C.n_experts, C.experts_per_token, C.capacity_factor, None
    )


def test_the_shapes_engage_the_picked_form():
    assert _takes_picked(B, 1) and _takes_picked(B, 5)  # decode, verify
    assert _takes_picked(1, CHUNK)  # a 16-token chunk: dropless, 16 tokens
    assert not _takes_picked(2, 64)  # 128 tokens: nearly every expert picked


def _prefilled(params, rng, lengths):
    cache = E.init_cache(C, B, TMAX, chunk=CHUNK)
    last = []
    for slot, n in enumerate(lengths):
        toks = rng.integers(1, C.vocab_size, n).tolist()
        # traced under `_toy_trip`'s patch, like every program of this file
        fn = jitted(E.prefill_chunk_step, "toy-trip", config=C, start=0)
        logits, cache = fn(
            params, cache, jnp.asarray([toks + [0] * (CHUNK - n)], jnp.int32),
            jnp.asarray(slot, jnp.int32), jnp.asarray(n - 1, jnp.int32),
        )
        last.append(int(np.asarray(logits[0]).argmax()))
    return cache, last


def _reads(cache):
    return np.asarray(cache["moe_reads"]).tolist()


def test_decode_loop_of_eight_is_eight_decode_steps(params):
    lengths = [5, 16, 9, 12]
    cache0, tok0 = _prefilled(params, np.random.default_rng(0), lengths)
    # the chunks took the picked form too: fewer experts read than held
    read0, held0 = _reads(cache0)
    assert held0 == len(lengths) * LAYERS * HELD and 0 < read0 < held0
    tok, pos = jnp.asarray(tok0, jnp.int32), jnp.asarray(lengths, jnp.int32)
    act = jnp.asarray([True, True, False, True])  # a dead slot rides along
    loop = jitted(E.decode_loop, "toy-trip", config=C, steps=8, max_seq=TMAX)
    emitted, cache_l, *_ = loop(
        params, cache0, tok, pos, jnp.full((B,), 64, jnp.int32), act,
        jnp.full((B,), -1, jnp.int32),
    )
    step = jitted(E.decode_step, "toy-trip", config=C)
    cache_s, t, p, want = cache0, tok, pos, []
    for _ in range(8):
        logits, cache_s = step(params, cache_s, t, p, write_mask=act)
        t = jnp.where(act, jnp.argmax(logits, -1).astype(jnp.int32), t)
        p = jnp.where(act, p + 1, p)
        want.append(np.asarray(t))
    live = np.asarray(act)
    np.testing.assert_array_equal(np.asarray(emitted)[:, live], np.stack(want)[:, live])
    for name in cache_l:
        np.testing.assert_allclose(
            np.asarray(cache_l[name]), np.asarray(cache_s[name]), rtol=1e-5, atol=1e-5,
            err_msg=name,
        )
    read, held = _reads(cache_l)
    assert held - held0 == 8 * LAYERS * HELD
    # three live tokens a call pick at most six experts, of which few are held
    assert 0 < read - read0 <= 8 * LAYERS * min(HELD, 3 * C.experts_per_token)


@pytest.mark.parametrize("program", ["decode_step", "verify_step", "prefill_chunk_step"])
def test_both_forms_serve_the_same_logits_and_count_what_they_read(
    program, params, monkeypatch
):
    cache, tok0 = _prefilled(params, np.random.default_rng(1), [7, 16, 11, 3])
    pos = jnp.asarray([7, 16, 11, 3], jnp.int32)
    mask = jnp.asarray([True, False, True, True])
    if program == "decode_step":
        run = lambda: jax.jit(partial(E.decode_step, config=C))(
            params, cache, jnp.asarray(tok0, jnp.int32), pos, write_mask=mask
        )
    elif program == "verify_step":
        grid = np.random.default_rng(2).integers(1, C.vocab_size, (B, 5))
        run = lambda: jax.jit(partial(E.verify_step, config=C))(
            params, cache, jnp.asarray(grid, jnp.int32), pos, write_mask=mask
        )
    else:
        toks = np.random.default_rng(2).integers(1, C.vocab_size, (1, CHUNK))
        run = lambda: jax.jit(partial(E.prefill_chunk_step, config=C, start=16))(
            params, cache, jnp.asarray(toks, jnp.int32), jnp.asarray(1, jnp.int32),
            jnp.asarray(CHUNK - 1, jnp.int32),
        )
    before = np.asarray(cache["moe_reads"])
    with jax.default_matmul_precision("highest"):
        logits, after = run()
        monkeypatch.setattr(moe, "TRIP_BYTES", float("inf"))  # the capacity form
        want, after_cap = run()
    live = np.asarray(mask) if program != "prefill_chunk_step" else slice(None)
    np.testing.assert_allclose(
        np.asarray(logits)[live], np.asarray(want)[live], rtol=2e-5, atol=2e-5
    )
    read, held = (np.asarray(after["moe_reads"]) - before).tolist()
    read_cap, held_cap = (np.asarray(after_cap["moe_reads"]) - before).tolist()
    assert held == held_cap == read_cap == LAYERS * HELD  # count a routed layer call
    assert 0 <= read < held  # n a call: the distinct picked experts
    np.testing.assert_array_equal(after["moe_stats"], after_cap["moe_stats"])


def test_engine_publishes_the_experts_read_and_held(params):
    from dstack_tpu.serve.engine import GenParams, InferenceEngine

    eng = InferenceEngine(
        C, params, max_batch=B, max_seq=TMAX, prefill_chunk=CHUNK, spec_draft=4,
    )
    fam = lambda n: eng.metrics.family(n).value()
    text = eng.metrics.render()
    for name in ("dtpu_serve_moe_experts_read_total", "dtpu_serve_moe_experts_held_total"):
        assert fam(name) == 0 and f"{name} 0" in text  # exported from boot
    prompt = [(11 * i) % 500 + 1 for i in range(21)]  # two chunks
    out = eng.generate(prompt, GenParams(max_new_tokens=12))
    assert len(out) == 12
    read, held = (
        fam("dtpu_serve_moe_experts_read_total"), fam("dtpu_serve_moe_experts_held_total")
    )
    # every routed layer call of prefill, decode and verify holds 8 experts
    assert held > 0 and held % (LAYERS * HELD) == 0
    assert held >= (2 + 3) * LAYERS * HELD  # two chunks, and 12 tokens take ≥ 3 calls
    assert 0 < read < held
    assert (read, held) == tuple(np.asarray(eng.cache["moe_reads"]).tolist())
    # a model that holds every expert counts none
    whole = dataclasses.replace(C, experts_held=())
    assert "moe_reads" not in E.init_cache(whole, B, TMAX, chunk=CHUNK)
