"""The grouped-query family's cache of layer GROUPS: full layers' K/V at
``max_seq`` rows beside window layers' K/V in a ring sized by the
window, written in place by every serving program and walked by periods.
Toy widths on the CPU, the program held against its own training-side
forward (``llama.forward``, no cache); the plain reference is held
against both in ``tests/benchmark/test_reference_gqa_groups.py``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dstack_tpu.models import llama
from dstack_tpu.serve import engine as E
from dstack_tpu.serve.engine import GenParams, InferenceEngine
from tests.shared import init_params, jitted

TIGHT = 2e-5
B, TMAX, CHUNK = 4, 96, 16  # a ring of 32 rows (window 8 - 1 + a chunk, in tiles)

KINDS = ("full",) + ("window", "window", "window", "full") * 2
TINY = llama.LlamaConfig(
    vocab_size=512, hidden_size=64, n_layers=9, n_heads=4, n_kv_heads=2,
    head_dim=16, intermediate_size=32, rope_theta=500000.0,
    rope_local_theta=10000.0, norm_eps=1e-6, max_seq_len=256,
    dtype=jnp.float32, remat=False, partial_rotary=0.5, swa_partial_rotary=1.0,
    rope_scaling=("yarn", 8.0, 32.0, 1.0, 16.0, 1.2), layer_types=KINDS,
    sliding_window=8, swa_n_heads=6, attn_gate=True, n_experts=16,
    experts_per_token=3, experts_held=(4, 4), capacity_factor=16 / 3,
    router_score="sigmoid", router_renorm=True, routed_scale=2.5,
    moe_shared_expert=True, moe_shared_intermediate=32, first_k_dense=1,
    dense_intermediate=96,
)
#: the same layers with nothing to repeat (one period and a tail of runs)
ODD = dataclasses.replace(
    TINY, n_layers=6, layer_types=("full", "window", "full", "full", "window", "window")
)
#: every expert held, no prelude: no counters ride the cache
WHOLE = dataclasses.replace(
    TINY, n_layers=4, layer_types=("window", "full") * 2, first_k_dense=0,
    n_experts=0, experts_held=(), moe_shared_expert=False, intermediate_size=96,
)


@pytest.fixture(scope="module", params=["periods", "odd", "whole"])
def model(request):
    c = {"periods": TINY, "odd": ODD, "whole": WHOLE}[request.param]
    return c, init_params(c, 1)


def _forward(c, params, tokens):
    return np.asarray(jitted(llama.forward, config=c)(params, jnp.asarray(tokens)[None]))[0]


def _packed(c, params, cache, prompts: dict, g: int = 2):
    fn = jitted(E.prefill_packed_step, config=c)
    at, out = {s: 0 for s in prompts}, {}
    while at:
        slots = sorted(at)
        rows = [prompts[s][at[s]:at[s] + CHUNK] for s in slots]
        pad = [slots[0]] * (g - len(slots))
        logits, cache = fn(
            params, cache,
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
    return out, cache


def test_two_buffers_a_kind_and_the_ring_is_sized_by_the_window():
    small, large = (
        jax.eval_shape(lambda t=t: E.init_cache(TINY, B, t, chunk=CHUNK)) for t in (64, 96)
    )
    assert set(small) == {"k", "v", "win_k", "win_v", "moe_stats", "moe_reads"}
    assert small["win_k"].shape == large["win_k"].shape == (6, B, 2, 32, 16)
    assert small["k"].shape == (3, B, 2, 64, 16) and large["k"].shape == (3, B, 2, 96, 16)
    assert small["moe_stats"].shape == (2,) and small["moe_stats"].dtype == jnp.int32
    assert small["moe_reads"].shape == (2,) and small["moe_reads"].dtype == jnp.int32
    assert set(jax.eval_shape(lambda: E.init_cache(WHOLE, B, 64, chunk=CHUNK))) == {
        "k", "v", "win_k", "win_v"
    }
    # one rule for both families: window - 1 + a chunk, in whole tiles, capped by max_seq
    mla = dataclasses.replace(
        llama.MLA_TINY, n_layers=4, layer_types=("full", "full", "window", "window"),
        sliding_window=8, swa_n_heads=2, swa_kv_lora_rank=48, swa_qk_nope_head_dim=24,
        swa_v_head_dim=16,
    )
    for c, name, t_ax in ((TINY, "win_k", 3), (mla, "win", 2)):
        for max_seq, want in ((16, 16), (64, 32), (4096, 32)):
            shapes = E._cache_shapes(c, B, max_seq, CHUNK)
            assert shapes[name][t_ax] == want == E.ring_rows(c, max_seq, CHUNK)
    with pytest.raises(ValueError):
        E.init_cache(TINY, B, 64, kv_quant="int8")
    # a model of one kind of layer keeps the cache it had
    assert E._cache_shapes(llama.LLAMA_TINY, B, 64, CHUNK) == {
        "k": (2, B, 2, 64, 32), "v": (2, B, 2, 64, 32)
    }
    assert set(E._cache_shapes(llama.LLAMA_TINY, B, 64, CHUNK, "int8")) == {"k", "v", "k_s", "v_s"}


def test_prefill_then_decode_past_a_wrap_is_the_forward(model):
    """A prompt shorter than the window and one longer than the ring in
    one wave; then 44 steps of both slots: the long one's ring wraps
    (52 + 44 = 96 > 32), the short one crosses the window."""
    c, params = model
    cache = E.init_cache(c, B, TMAX, chunk=CHUNK)
    rng = np.random.default_rng(1)
    seqs = {1: rng.integers(1, 512, 5).tolist(), 3: rng.integers(1, 512, 50).tolist()}
    decode = jitted(E.decode_step, config=c)
    with jax.default_matmul_precision("highest"):
        first, cache = _packed(c, params, cache, seqs)
        got = {s: [first[s]] for s in seqs}
        for _ in range(44):
            tok, pos = np.zeros(B, np.int32), np.zeros(B, np.int32)
            live = np.zeros(B, bool)
            for s in seqs:
                seqs[s].append(int(got[s][-1].argmax()))
                tok[s], pos[s], live[s] = seqs[s][-1], len(seqs[s]) - 1, True
            logits, cache = decode(
                params, cache, jnp.asarray(tok), jnp.asarray(pos), write_mask=jnp.asarray(live)
            )
            for s in seqs:
                got[s].append(np.asarray(logits[s]))
        for s in seqs:
            ref = _forward(c, params, seqs[s])
            n = len(seqs[s]) - 44
            assert max(np.abs(g - ref[n - 1 + i]).max() for i, g in enumerate(got[s])) < TIGHT
    if "moe_stats" in cache:
        n_moe = c.n_layers - c.first_k_dense
        assert int(cache["moe_stats"][1]) == n_moe * (5 + 50 + 2 * 44)


def test_macro_step_and_verify_step_agree_with_the_decode_step(model):
    c, params = model
    rng = np.random.default_rng(2)
    prompts = {0: rng.integers(1, 512, 30).tolist(), 2: rng.integers(1, 512, 7).tolist()}
    live = np.zeros(B, bool)
    live[[0, 2]] = True
    with jax.default_matmul_precision("highest"):
        first, cache = _packed(c, params, E.init_cache(c, B, TMAX, chunk=CHUNK), prompts)
        tok, pos = np.zeros(B, np.int32), np.zeros(B, np.int32)
        for s in prompts:
            tok[s], pos[s] = int(first[s].argmax()), len(prompts[s])
        # eight tokens by the decode step, a token at a time
        decode = jitted(E.decode_step, config=c)
        one, t1, p1, steps = jax.tree.map(jnp.copy, cache), tok.copy(), pos.copy(), []
        for _ in range(8):
            logits, one = decode(
                params, one, jnp.asarray(t1), jnp.asarray(p1), write_mask=jnp.asarray(live)
            )
            t1 = np.where(live, np.asarray(logits.argmax(-1)), 0).astype(np.int32)
            p1 = p1 + live
            steps.append(t1.copy())
        # the same eight in one program
        loop = jitted(E.decode_loop, config=c, steps=8, max_seq=TMAX)
        emitted, looped, *_ = loop(
            params, jax.tree.map(jnp.copy, cache), jnp.asarray(tok), jnp.asarray(pos),
            jnp.full((B,), 30, jnp.int32), jnp.asarray(live), jnp.full((B,), -1, jnp.int32),
        )
        emitted = np.asarray(emitted)
        for j in range(8):
            assert (emitted[j][live] == steps[j][live]).all()
        assert (emitted[:, ~live] == -1).all()
        for name in one:
            assert np.abs(np.asarray(one[name] - looped[name], np.float64)).max() < TIGHT
        # and as a verify step over the first four of them
        grid = np.zeros((B, 4), np.int32)
        grid[:, 0] = tok
        for j in range(3):
            grid[:, j + 1] = steps[j]
        vlogits, _ = jitted(E.verify_step, config=c)(
            params, cache, jnp.asarray(grid), jnp.asarray(pos), write_mask=jnp.asarray(live)
        )
        picked = np.asarray(vlogits.argmax(-1))
        for j in range(4):
            assert (picked[live, j] == steps[j][live]).all()


def test_a_dead_slot_keeps_its_bytes_in_both_buffers():
    """A masked row writes nothing: not into the full layers' rows, not
    into the ring (decode, verify and a wave's pad row alike)."""
    c = TINY
    params = init_params(c, 1)
    rng = np.random.default_rng(3)
    cache = {
        n: jnp.asarray(rng.normal(size=a.shape), a.dtype) if a.ndim > 1 else a
        for n, a in E.init_cache(c, B, TMAX, chunk=CHUNK).items()
    }
    live = jnp.asarray([True, False, True, False])
    pos = jnp.asarray([40, 41, 3, 33], jnp.int32)
    tok = jnp.asarray([5, 6, 7, 8], jnp.int32)
    after, _ = jitted(E.decode_step, config=c)(params, cache, tok, pos, write_mask=live)[::-1]
    very, _ = jitted(E.verify_step, config=c)(
        params, cache, jnp.tile(tok[:, None], (1, 3)), pos, write_mask=live
    )[::-1]
    _, waved = _packed(c, params, cache, {2: rng.integers(1, 512, 9).tolist()})
    for name in ("k", "v", "win_k", "win_v"):
        for new in (after, very):
            assert (np.asarray(new[name][:, [1, 3]]) == np.asarray(cache[name][:, [1, 3]])).all()
            assert (np.asarray(new[name][:, 0]) != np.asarray(cache[name][:, 0])).any()
        # the wave wrote slot 2 (and its pad row nothing)
        assert (np.asarray(waved[name][:, [0, 1, 3]]) == np.asarray(cache[name][:, [0, 1, 3]])).all()
    # the live slot's token went to row 40 of a full layer, to row 40 % 32 of the ring
    assert (np.asarray(after["k"][:, 0, :, 40]) != np.asarray(cache["k"][:, 0, :, 40])).all()
    assert (np.asarray(after["win_k"][:, 0, :, 8]) != np.asarray(cache["win_k"][:, 0, :, 8])).all()
    assert (np.asarray(after["win_k"][:, 0, :, 9]) == np.asarray(cache["win_k"][:, 0, :, 9])).all()


def test_the_engine_counts_and_reuses_a_prefix_while_the_ring_holds_it():
    """Through ``InferenceEngine``: the window counters follow from
    positions, the gauges from the buffers, the routing counts reach the
    registry with the step's tokens, and a prompt that shares a
    chunk-aligned prefix with a slot whose ring still holds it decodes
    what it decodes without the reuse; a slot that has run on past the
    ring is not offered as a source."""
    c = TINY
    params = init_params(c, 1)
    rng = np.random.default_rng(5)
    shared = rng.integers(1, 512, 32).tolist()
    first = shared + rng.integers(1, 512, 7).tolist()
    second = shared + rng.integers(1, 512, 11).tolist()

    def engine(prefix_cache):
        return InferenceEngine(
            c, params, max_batch=B, max_seq=TMAX, prefill_chunk=CHUNK,
            prefix_cache=prefix_cache, spec_draft=0,
        )

    plain = engine(False)
    assert plain._packed_only and plain._ring_rows == 32
    want = plain.generate(list(second), GenParams(max_new_tokens=6))
    eng = engine(True)
    eng.generate(list(first), GenParams(max_new_tokens=4))  # 43 tokens: ring 32, window 8
    got = eng.generate(list(second), GenParams(max_new_tokens=6))
    assert eng.prefix_hits == 1 and got == want
    value = lambda n: eng.metrics.family(n).value()
    routed = value("dtpu_serve_moe_tokens_routed_total")
    # prompt tokens prefilled (the reused 32 not again) + decoded tokens, x 8 expert layers
    assert routed == 8 * ((39 + 3) + (11 + 5))
    assert 0 < value("dtpu_serve_moe_picks_held_total") < 3 * routed
    # decoded tokens: 3 at contexts 40-42, 5 at 44-48; six window layers see 8 keys of them
    assert value("dtpu_serve_window_keys_visible_total") == 6 * 8 * (3 + 5)
    assert value("dtpu_serve_window_keys_in_context_total") == 6 * (
        sum(range(40, 43)) + sum(range(44, 49))
    )
    ring = 6 * B * 2 * 32 * 16 * 2 * 4  # layers, slots, KV heads, rows, head_dim, k and v, f32
    full = 3 * B * 2 * TMAX * 16 * 2 * 4
    assert value("dtpu_serve_kv_cache_bytes") == ring + full
    assert value("dtpu_serve_kv_window_pool_percent") == pytest.approx(100 * ring / (ring + full))
    # a source that has decoded past what its ring keeps is passed over
    far = engine(True)
    far.generate(list(first), GenParams(max_new_tokens=40))  # 79 tokens > 32 + 32 - 8 + 1
    assert far.generate(list(second), GenParams(max_new_tokens=6)) == want
    assert far.prefix_hits == 0
    # a model of one kind of layer: the window's series stay at nothing
    dense = InferenceEngine(
        llama.LLAMA_TINY, init_params(llama.LLAMA_TINY, 0),
        max_batch=2, max_seq=64,
    )
    assert dense.metrics.family("dtpu_serve_kv_window_pool_percent").value() == 0
    assert dense._ring_rows == 0 and not dense._packed_only


def test_speculative_and_macro_steps_through_the_engine_decode_the_same():
    """The engine's three decode paths (per-token, macro-step, verify
    with n-gram drafts) over the two caches give one greedy stream."""
    c = TINY
    params = init_params(c, 1)
    prompt = ([7, 8, 9, 10] * 6)[:22]

    def run(**kw):
        eng = InferenceEngine(
            c, params, max_batch=B, max_seq=TMAX, prefill_chunk=CHUNK, **kw
        )
        return eng.generate(list(prompt), GenParams(max_new_tokens=40))

    plain = run(spec_draft=0, turbo_steps=0)
    assert run(spec_draft=0, turbo_steps=8) == plain
    assert run(spec_draft=3, turbo_steps=0) == plain
