"""The decode programs write the donated KV cache in place.

``decode_step`` / ``decode_loop`` / ``verify_step`` carry the STACKED
cache through the layer scan and each layer writes its new rows as a
tile-aligned block (``engine._cwrite_rows``) instead of handing a
layer's slice through ``lax.scan`` as xs → ys. What the TPU compiler
makes of that is ``tests/compute/test_tpu_compile.py``'s to check; here,
on the CPU at small widths: the logits are the full forward's, the
cache holds what the scatter form (``.at[].set(mode="drop")``, the
write of the tree before PR 25) would have put there, and nothing else
moved.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dstack_tpu.models import llama
from dstack_tpu.serve import engine as eng
from tests.shared import init_params, jitted

TMAX = 40  # not a multiple of any tile: the last block is a clamped one
PROMPTS = [[5, 99, 321, 7, 250], [41, 18, 3, 77, 400, 10, 20, 30, 40], [9] * 17]
N_NEW = 9  # decoded tokens a slot: slot 2 crosses 17 → 26, over a tile's edge

CASES = {
    "dense-gqa": (llama.LLAMA_TINY, None),
    "window-sinks-softcap": (
        dataclasses.replace(
            llama.LLAMA_TINY, sliding_window=8, sliding_pattern=2,
            attn_sinks=True, attn_softcap=30.0,
        ),
        None,
    ),
    "int8-kv": (llama.LLAMA_TINY, "int8"),
    "mla-moe-first-k-dense": (llama.MLA_TINY, None),
}


@pytest.fixture(scope="module", params=sorted(CASES))
def model(request):
    config, kv_quant = CASES[request.param]
    assert request.param != "mla-moe-first-k-dense" or (
        config.mla and config.n_experts and config.first_k_dense == 1
    )
    params = init_params(config, 7)
    rng = np.random.default_rng(11)
    seqs = [
        p + [int(t) for t in rng.integers(1, config.vocab_size, N_NEW + 1)]
        for p in PROMPTS
    ]
    return config, kv_quant, params, seqs


def _prefilled(config, kv_quant, params, seqs, lengths):
    """A cache whose slot ``b`` holds ``seqs[b][:lengths[b]]``, written
    by the prefill program (which this PR does not touch)."""
    cache = eng.init_cache(config, len(seqs), TMAX, kv_quant=kv_quant)
    for b, (seq, n) in enumerate(zip(seqs, lengths)):
        toks = jnp.asarray([seq[:n] + [0] * (32 - n)], jnp.int32)
        _, cache = jitted(eng.prefill, config=config)(
            params, toks, jnp.asarray([n], jnp.int32),
            jnp.asarray(b, jnp.int32), cache=cache,
        )
    return cache


def _dequant(cache):
    """Cache leaves as float arrays (int8 pairs dequantized)."""
    if "k_s" in cache:
        return {
            n: np.asarray(eng.kv_dequant(cache[n], cache[n + "_s"], jnp.float32))
            for n in ("k", "v")
        }
    return {n: np.asarray(a, np.float32) for n, a in cache.items()}


def _token_axis(cache_leaf_name):
    return 2 if cache_leaf_name == "ckv" else 3  # [L,B,T,R] | [L,B,H,T(,D)]


def _decode(config, params, cache, seqs, starts, steps, mask):
    """Teacher-forced ``decode_step`` calls → (logits of every step, cache)."""
    out = []
    for i in range(steps):
        toks = jnp.asarray([s[p + i] for s, p in zip(seqs, starts)], jnp.int32)
        pos = jnp.asarray([p + i for p in starts], jnp.int32)
        logits, cache = jitted(eng.decode_step, config=config)(
            params, cache, toks, pos, write_mask=jnp.asarray(mask)
        )
        out.append(np.asarray(logits))
    return out, cache


def test_inplace_decode_matches_forward_and_prefill_cache(model):
    config, kv_quant, params, seqs = model
    starts = [len(p) for p in PROMPTS]
    cache = _prefilled(config, kv_quant, params, seqs, starts)
    before = _dequant(cache)
    logits, cache = _decode(
        config, params, cache, seqs, starts, N_NEW, [True] * 3
    )
    tol = 0.05 if kv_quant else 2e-3
    for b, seq in enumerate(seqs):
        for i in (0, N_NEW // 2, N_NEW - 1):
            n = starts[b] + i + 1  # causal: what is padded behind moves nothing
            full = jitted(llama.forward, config=config)(
                params, jnp.asarray([seq[:n] + [0] * (32 - n)], jnp.int32)
            )
            ref = np.asarray(full[0, n - 1])
            assert np.abs(logits[i][b] - ref).max() < tol * max(
                np.abs(ref).max(), 1.0
            ), (b, i)
    # the rows decode wrote are the rows one prefill of the whole
    # sequence writes, and the rows past them are as they were
    want = _dequant(
        _prefilled(config, kv_quant, params, seqs, [p + N_NEW for p in starts])
    )
    got = _dequant(cache)
    for name in got:
        ax = _token_axis(name)
        for b, p in enumerate(starts):
            g = np.take(got[name][:, b], range(p + N_NEW), axis=ax - 1)
            w = np.take(want[name][:, b], range(p + N_NEW), axis=ax - 1)
            scale = max(np.abs(w).max(), 1.0)
            assert np.abs(g - w).max() < (0.03 if kv_quant else 1e-4) * scale
            rest = range(p + N_NEW, TMAX)
            assert np.array_equal(
                np.take(got[name][:, b], rest, axis=ax - 1),
                np.take(before[name][:, b], rest, axis=ax - 1),
            ), (name, b)


def test_masked_rows_keep_their_bytes_in_every_layer(model):
    """A finished slot, or one mid-prefill, must not be scribbled on:
    its block is read, left as it is and written back."""
    config, kv_quant, params, seqs = model
    starts = [len(p) for p in PROMPTS]
    before = _prefilled(config, kv_quant, params, seqs, starts)
    keep = jax.tree.map(np.asarray, before)
    _, after = _decode(
        config, params, before, seqs, starts, 3, [True, False, True]
    )
    for name, leaf in after.items():
        leaf = np.asarray(leaf)
        assert leaf.dtype == keep[name].dtype
        for layer in range(leaf.shape[0]):
            assert leaf[layer, 1].tobytes() == keep[name][layer, 1].tobytes()
        assert not np.array_equal(leaf[:, 0], keep[name][:, 0])  # slot 0 wrote


def test_write_at_tmax_is_dropped(model):
    """``position == Tmax`` (a slot that ran to the cache's end) has no
    row to write: every byte stays, in every slot's last block too."""
    config, kv_quant, params, seqs = model
    starts = [len(p) for p in PROMPTS]
    before = _prefilled(config, kv_quant, params, seqs, starts)
    keep = jax.tree.map(np.asarray, before)
    toks = jnp.asarray([s[0] for s in seqs], jnp.int32)
    pos = jnp.full((3,), TMAX, jnp.int32)
    _, after = jitted(eng.decode_step, config=config)(
        params, before, toks, pos, write_mask=jnp.ones((3,), bool)
    )
    for name, leaf in after.items():
        assert np.asarray(leaf).tobytes() == keep[name].tobytes()


def test_decode_loop_equals_n_decode_steps(model):
    config, kv_quant, params, seqs = model
    starts = [len(p) for p in PROMPTS]
    cache = _prefilled(config, kv_quant, params, seqs, starts)
    tok = jnp.asarray([s[p] for s, p in zip(seqs, starts)], jnp.int32)
    pos = jnp.asarray(starts, jnp.int32)
    rem = jnp.asarray([50, 2, 50], jnp.int32)  # slot 1 stops after 2 tokens
    act = jnp.ones((3,), bool)
    eos = jnp.full((3,), -1, jnp.int32)
    n = 5
    emitted, loop_cache, *_ = jitted(
        eng.decode_loop, config=config, steps=n, max_seq=TMAX
    )(params, cache, tok, pos, rem, act, eos)
    step_cache = _prefilled(config, kv_quant, params, seqs, starts)
    want = []
    for _ in range(n):
        logits, step_cache = jitted(eng.decode_step, config=config)(
            params, step_cache, tok, pos, write_mask=act
        )
        new = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        was = act
        tok, pos, rem, act = jitted(eng.advance_decode_state, max_seq=TMAX)(
            tok, pos, rem, act, eos, new
        )
        want.append(np.where(np.asarray(was), np.asarray(tok), -1))
    assert np.array_equal(np.asarray(emitted), np.stack(want))
    assert (np.asarray(emitted)[2:, 1] == -1).all()  # its budget was 2
    for name in loop_cache:
        np.testing.assert_allclose(
            np.asarray(loop_cache[name], np.float32),
            np.asarray(step_cache[name], np.float32), atol=1e-5,
        )


def test_verify_step_leaves_the_rows_of_s_decode_steps(model):
    config, kv_quant, params, seqs = model
    starts = [len(p) for p in PROMPTS]
    s = 5  # slot 2 writes rows 17..21: two blocks of a float32 tile of 8
    mask = [True, False, True]
    toks = jnp.asarray(
        [seq[p : p + s] for seq, p in zip(seqs, starts)], jnp.int32
    )
    v_logits, v_cache = jitted(eng.verify_step, config=config)(
        params, _prefilled(config, kv_quant, params, seqs, starts), toks,
        jnp.asarray(starts, jnp.int32), write_mask=jnp.asarray(mask),
    )
    d_logits, d_cache = _decode(
        config, params, _prefilled(config, kv_quant, params, seqs, starts),
        seqs, starts, s, mask,
    )
    tol = 0.05 if kv_quant else 2e-3
    for i in range(s):
        for b in (0, 2):
            ref = d_logits[i][b]
            assert np.abs(np.asarray(v_logits)[b, i] - ref).max() < tol * max(
                np.abs(ref).max(), 1.0
            )
    got, want = _dequant(v_cache), _dequant(d_cache)
    for name in got:
        scale = max(np.abs(want[name]).max(), 1.0)
        assert np.abs(got[name] - want[name]).max() < (
            0.03 if kv_quant else 1e-4
        ) * scale
    keep = _prefilled(config, kv_quant, params, seqs, starts)
    for name, leaf in v_cache.items():  # the masked slot: untouched bytes
        assert (
            np.asarray(leaf)[:, 1].tobytes()
            == np.asarray(keep[name])[:, 1].tobytes()
        )


@pytest.mark.parametrize("rows", [1, 5, 16])
@pytest.mark.parametrize("leaf", ["values", "int8-pair", "latent"])
@pytest.mark.parametrize("span", ["one-layer", "all-layers"])
def test_block_write_is_the_scatter_write(span, leaf, rows):
    """``_cwrite_rows`` on the stacked buffer against the per-layer
    scatter it replaced (out-of-range index → dropped), bit
    for bit, for one layer inside a scan (verify, the latent) and for
    all layers after it (decode): every position class in one batch (a
    tile's first and last row, the clamped last block, the end of the
    cache and past it, a masked row)."""
    layers, heads, dim, tmax = 3, 2, 8, 44
    pos = np.array([0, 7, 8, 15, 30, tmax - rows, tmax - 1, tmax, tmax + 3, 20])
    mask = np.array([True] * 9 + [False])
    b = len(pos)
    rng = np.random.default_rng(rows)
    grid = pos[:, None] + np.arange(rows)[None, :]
    write_pos = jnp.asarray(np.where(mask[:, None], grid, tmax))
    batch_ix = jnp.arange(b)
    first, n = (1, 1) if span == "one-layer" else (0, layers)
    if leaf == "latent":
        buf = jnp.asarray(rng.normal(size=(layers, b, tmax, dim)), jnp.float32)
        new = jnp.asarray(rng.normal(size=(n, b, rows, dim)), jnp.float32)
        scatter = lambda layer, rows_: layer.at[
            batch_ix[:, None], write_pos
        ].set(rows_, mode="drop")
        axis = 0
    else:
        shape = (layers, b, heads, tmax, dim)
        new = jnp.asarray(rng.normal(size=(n, b, heads, rows, dim)), jnp.float32)
        if leaf == "values":
            buf = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
            new = new.astype(jnp.bfloat16)
        else:
            buf = (
                jnp.asarray(rng.integers(-127, 128, shape), jnp.int8),
                jnp.asarray(rng.uniform(0.01, 1.0, shape[:-1]), jnp.float32),
            )
        def scatter(layer, rows_):  # leaf-wise: values, or the (int8, scale) pair
            return jax.tree.map(
                lambda a, n: a.at[batch_ix[:, None], :, write_pos].set(
                    jnp.moveaxis(n, 2, 1), mode="drop"
                ),
                layer, eng._cstored(rows_, layer),
            )

        axis = 1
    got = eng._cwrite_rows(
        buf, first, jnp.asarray(pos), jnp.asarray(mask),
        eng._cstored(new, buf), axis=axis,
    )
    pick = lambda tree, i: jax.tree.map(lambda a: a[i], tree)
    for layer in range(layers):
        want = pick(buf, layer)
        if first <= layer < first + n:
            want = scatter(want, new[layer - first])
        for g, w in zip(jax.tree.leaves(pick(got, layer)), jax.tree.leaves(want)):
            assert np.asarray(g).tobytes() == np.asarray(w).tobytes(), layer
