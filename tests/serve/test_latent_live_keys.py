"""The latent decode reads the keys its live slots hold, not ``max_seq``.

``engine._attend_live`` (what ``decode_step``, ``decode_loop`` and
``verify_step`` compile to for a latent model with no window and no
indexer) goes by a layer's keys in blocks of 512 under a running
softmax, for as many blocks as hold the longest context among the
step's LIVE slots. Here, on the CPU in float32 at tiny widths: it is
the whole-row softmax it replaced, it reads no key past its bound (the
rows past it are NaN in every case), a dead slot's stale position does
not stretch the bound, and the engine's two counters say what was read.
What the TPU compiler makes of the block loop is
``tests/compute/test_tpu_compile.py``'s to check.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dstack_tpu.models import llama
from dstack_tpu.serve import engine as eng
from dstack_tpu.serve.engine import GenParams, InferenceEngine
from tests.shared import init_params

TMAX, KB = 2048, 512
LAYERS, LI, HEADS = 2, 1, 3
GC = dataclasses.replace(llama.MLA_TINY, kv_lora_rank=16, qk_rope_head_dim=8)
WIDTH = GC.kv_lora_rank + GC.qk_rope_head_dim

# case → (positions a slot, write_mask a slot); S rides a second parameter
LIVE = {
    "length-1": ([0, 0, 0], [True] * 3),
    "length-511": ([510] * 3, [True] * 3),
    "length-512": ([511] * 3, [True] * 3),
    "length-513": ([512] * 3, [True] * 3),
    "length-max_seq-1": ([TMAX - 2] * 3, [True] * 3),
    "ragged": ([3, 700, 1200], [True] * 3),
    "dead-slot-stale-at-max_seq-1": ([40, TMAX - 1, 300], [True, False, True]),
    "every-slot-dead": ([900, TMAX - 1, 5], [False] * 3),
}


def _whole_row(q_abs, row, positions, gc):
    """The form the programs had: scores over the whole row, one softmax."""
    s = q_abs.shape[2]
    sc = jnp.einsum(
        "bhsr,btr->bhst", q_abs, row, preferred_element_type=jnp.float32
    ) * gc.attention_scale
    qpos = (positions[:, None] + jnp.arange(s)[None, :])[:, None, :, None]
    sc = jnp.where(jnp.arange(row.shape[1])[None, None, None, :] <= qpos, sc, eng.NEG_INF)
    return jnp.einsum(
        "bhst,btr->bhsr", jax.nn.softmax(sc, axis=-1), row[..., : gc.kv_lora_rank]
    )


@pytest.mark.parametrize("s", [1, 5], ids=["S1", "S5"])
@pytest.mark.parametrize("case", sorted(LIVE))
def test_attend_live_is_the_whole_row_softmax(case, s):
    positions, live = (jnp.asarray(a) for a in LIVE[case])
    b = positions.shape[0]
    rng = np.random.default_rng(3)
    ckv = jnp.asarray(rng.normal(size=(LAYERS, b, TMAX, WIDTH)), jnp.float32)
    q_abs = jnp.asarray(rng.normal(size=(b, HEADS, s, WIDTH)), jnp.float32)
    blocks, kb = eng._live_key_blocks(positions, live, s, TMAX)
    longest = max([p + s for p, m in zip(*LIVE[case]) if m], default=0)
    assert kb == KB
    assert int(blocks) == min(max(-(-longest // KB), 1), TMAX // KB)
    # nothing past the bound is read: those rows, and every other layer, are NaN
    poisoned = ckv.at[:, :, int(blocks) * KB :].set(jnp.nan).at[1 - LI].set(jnp.nan)
    got = jax.jit(eng._attend_live, static_argnums=5)(
        q_abs, poisoned, LI, positions, live, GC
    )
    assert got.shape == (b, HEADS, s, GC.kv_lora_rank)
    assert bool(jnp.isfinite(got).all())  # the dead slots' rows too
    want = _whole_row(q_abs, ckv[LI], positions, GC)
    alive = np.asarray(live)
    np.testing.assert_allclose(
        np.asarray(got)[alive], np.asarray(want)[alive], rtol=1e-5, atol=1e-5
    )


@pytest.fixture(scope="module")
def tiny():
    config = dataclasses.replace(llama.MLA_TINY, max_seq_len=1024)
    params = init_params(config, 2)
    rng = np.random.default_rng(9)
    cache = eng.init_cache(config, 3, 1024)
    # whatever wrote them, the rows a slot holds are its context
    cache["ckv"] = jnp.asarray(rng.normal(size=cache["ckv"].shape), cache["ckv"].dtype)
    return config, params, cache


def test_decode_loop_carries_a_slot_across_a_block_edge(tiny):
    """Slot 0 goes 508 → 516 inside one call: from its fifth token on
    the batch reads a second block. The eight tokens are eight
    ``decode_step``s'."""
    config, params, cache = tiny
    tok = jnp.asarray([7, 11, 13], jnp.int32)
    pos = jnp.asarray([508, 20, 1000], jnp.int32)
    rem = jnp.asarray([30, 5, 30], jnp.int32)  # slot 1 runs out mid-call
    act = jnp.asarray([True, True, False])  # slot 2: dead, stale past the edge
    eos = jnp.full((3,), -1, jnp.int32)
    toks, loop_cache, *_ = jax.jit(
        lambda p, c, *a: eng.decode_loop(p, c, *a, config, steps=8, max_seq=1024)
    )(params, dict(cache), tok, pos, rem, act, eos)

    step = jax.jit(lambda p, c, t, ps, m: eng.decode_step(p, c, t, ps, config, m))
    want, c2 = [], dict(cache)
    for k in range(8):
        assert int(eng._live_key_blocks(pos, act, 1, 1024)[0]) == (1 if k < 4 else 2)
        logits, c2 = step(params, c2, tok, pos, act)
        new = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        was = act
        tok, pos, rem, act = eng.advance_decode_state(
            tok, pos, rem, act, eos, new, max_seq=1024
        )
        want.append(np.where(np.asarray(was), np.asarray(tok), -1))
    np.testing.assert_array_equal(np.asarray(toks), np.stack(want))
    np.testing.assert_array_equal(np.asarray(toks)[:, 2], -1)
    assert (np.asarray(toks)[:5, 1] >= 0).all() and (np.asarray(toks)[5:, 1] == -1).all()
    np.testing.assert_allclose(
        np.asarray(loop_cache["ckv"]), np.asarray(c2["ckv"]), rtol=1e-6, atol=1e-6
    )


def test_engine_counts_the_key_rows_read_and_reserved():
    """Both series exist from boot; a latent engine counts whole blocks
    up to the longest live context a token step, a dense one whole rows."""
    config = dataclasses.replace(llama.MLA_TINY, max_seq_len=2048)
    params = init_params(config, 2)
    e = InferenceEngine(
        config, params, max_batch=2, max_seq=2048, prefill_chunk=256, spec_draft=0,
    )
    value = lambda n: e.metrics.family(n).value()
    text = e.metrics.render()
    assert "dtpu_serve_decode_keys_read_total 0\n" in text
    assert "dtpu_serve_decode_keys_reserved_total 0\n" in text
    assert e._key_block == KB and e._full_layers == 3
    # 506 prompt tokens, then 10 decoded: the first comes of the prefill,
    # nine of decode steps whose token sees 507 … 515 keys, itself included
    e.generate(list(range(1, 507)), GenParams(max_new_tokens=10))
    steps = value("dtpu_serve_decode_keys_reserved_total") / (2 * 2048 * 3)
    assert steps == 9
    blocks = sum(-(-(ctx + 1) // KB) for ctx in range(506, 515))
    assert blocks == 9 + 3  # the last three steps read a second block
    assert value("dtpu_serve_decode_keys_read_total") == 2 * KB * 3 * blocks
    # a verify call of S = 5 rows a slot: slot 0 stood at 508 before it
    # emitted two tokens, and 508 + 5 keys take a second block
    e.spec_draft, e._last_step_phase, e.lengths = 4, "spec", [510, 100]
    before = value("dtpu_serve_decode_keys_read_total")
    e._count_decode_keys({0: [1, 2], 1: [3]})
    assert value("dtpu_serve_decode_keys_read_total") - before == 2 * KB * 3 * 2

    dense = InferenceEngine(
        llama.LLAMA_TINY, init_params(llama.LLAMA_TINY, 1),
        max_batch=2, max_seq=64, spec_draft=0,
    )
    assert dense._key_block == 0
    dense.generate([5, 6, 7], GenParams(max_new_tokens=4))
    read = dense.metrics.family("dtpu_serve_decode_keys_read_total").value()
    assert read == dense.metrics.family("dtpu_serve_decode_keys_reserved_total").value()
    assert read == 3 * 2 * 64 * llama.LLAMA_TINY.n_layers
