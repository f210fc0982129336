"""A dense grouped-query decode step reads the keys each live slot
holds, not every reserved row of every slot.

Where ``ops/flash_decode.reads_live_keys`` holds, ``engine._decode_layer``
attends through ``ops/flash_decode``: the stacked cache leaf read in
place, each slot's key blocks up to its length, none for a slot that may
not write, the token's own key beside them. Here, on the CPU in float32
at tiny widths with the kernel in interpret mode (asked for by
``decode_kernel="flash"``, the tests' way in: by itself the program
takes the kernel on the TPU only): it computes what the masked einsum
computes, logits and the whole cache, with slots at every edge of a
block in one batch and dead rows among them; and the engine's two
counters say what was read, slot by slot. The kernel has a block form
a way the leaf lies (``flash_decode.tokens_on_lanes``): the models here
are head_dim 64 (a block is [head, keys]: the rag cell's form, PR 45)
but ``gqa-128`` (a block is [keys, head]: chat's and mixed's form).
What the TPU compiler makes of either is
``tests/compute/test_tpu_compile.py``'s to check.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dstack_tpu.models import llama
from dstack_tpu.ops import flash_decode as fd
from dstack_tpu.serve import engine as eng
from dstack_tpu.serve.engine import GenParams, InferenceEngine
from tests.shared import init_params

TMAX, KB = 256, 128
DENSE = dataclasses.replace(
    llama.LLAMA_TINY_64, n_heads=4, n_kv_heads=2, hidden_size=256,
    intermediate_size=256,
)
# case → (config, kv_quant)
MODELS = {
    "gqa": (DENSE, None),
    "gqa-128": (dataclasses.replace(DENSE, head_dim=128), None),
    "window-softcap": (
        dataclasses.replace(
            DENSE, sliding_window=32, sliding_pattern=2, attn_softcap=30.0
        ),
        None,
    ),
    "sinks": (dataclasses.replace(DENSE, attn_sinks=True), None),
    "int8-kv": (DENSE, "int8"),
    # a model of groups: the full layer's row buffer through the kernel,
    # the window layer's ring through the einsum
    "groups": (
        dataclasses.replace(
            DENSE, layer_types=("full", "window"), sliding_window=8, swa_n_heads=6,
        ),
        None,
    ),
    # the rag cell's family: conv layers (a tail a slot, no rows) beside
    # full layers that alone hold K/V, walked by periods, held experts
    "conv-groups": (
        dataclasses.replace(
            llama.CONV_TINY, head_dim=64, n_layers=4,
            layer_types=("conv", "conv", "full", "conv"),
        ),
        None,
    ),
}
# a slot a length: empty and dead, dead with a stale position, one key,
# a block edge - 1, a block edge, the row's end
POSITIONS = [0, 200, 1, KB - 1, KB, TMAX - 2]
LIVE = [False, False, True, True, True, True]


def _block_of_128(monkeypatch, config, kv_quant=None):
    """The block rule at these widths gives the whole row; a step of
    128 keys' bytes puts the lengths above on both sides of an edge."""
    itemsize = 1 if kv_quant else jnp.dtype(config.dtype).itemsize
    monkeypatch.setattr(
        fd, "BLOCK_BYTES", KB * 2 * config.n_kv_heads * config.head_dim * itemsize
    )
    assert fd.block_keys(config.n_kv_heads, config.head_dim, TMAX, itemsize) == KB


def _state(case):
    config, kv_quant = MODELS[case]
    params = init_params(config, 4)
    rng = np.random.default_rng(11)
    cache = eng.init_cache(config, len(LIVE), TMAX, kv_quant=kv_quant, chunk=16)

    def fill(name, a):
        # whatever wrote them, the rows a slot holds are its context
        if a.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, size=a.shape), jnp.int8)
        if name.endswith("_s"):
            return jnp.asarray(rng.uniform(0.002, 0.02, size=a.shape), a.dtype)
        return jnp.asarray(rng.normal(size=a.shape), a.dtype)

    cache = {n: fill(n, a) for n, a in cache.items()}
    tokens = jnp.asarray(rng.integers(1, config.vocab_size, size=len(LIVE)), jnp.int32)
    return config, params, cache, tokens


def _close(got: dict, want: dict):
    assert sorted(got) == sorted(want)
    for name in want:
        np.testing.assert_allclose(
            np.asarray(got[name], np.float32), np.asarray(want[name], np.float32),
            rtol=2e-5, atol=2e-5, err_msg=name,
        )


@pytest.mark.parametrize("program", ["decode_step", "decode_loop"])
@pytest.mark.parametrize("case", sorted(MODELS))
def test_kernel_path_is_the_einsum_path(case, program, monkeypatch):
    _block_of_128(monkeypatch, *MODELS[case])
    config, params, cache, tokens = _state(case)
    pos, live = jnp.asarray(POSITIONS, jnp.int32), jnp.asarray(LIVE)
    alive = np.asarray(LIVE)
    out = {}
    for kernel in ("einsum", "flash"):
        if program == "decode_step":
            logits, after = jax.jit(
                lambda p, c, t, ps, m: eng.decode_step(
                    p, c, t, ps, config, m, decode_kernel=kernel
                )
            )(params, dict(cache), tokens, pos, live)
            out[kernel] = (np.asarray(logits)[alive], after)
        else:
            rem = jnp.asarray([9, 9, 9, 2, 9, 9], jnp.int32)  # slot 3 runs out mid-call
            toks, after, *state = jax.jit(
                lambda p, c, *a: eng.decode_loop(
                    p, c, *a, config, steps=3, max_seq=TMAX, decode_kernel=kernel
                )
            )(params, dict(cache), tokens, pos, rem, live, jnp.full((len(LIVE),), -1, jnp.int32))
            out[kernel] = (np.stack([np.asarray(a) for a in [*toks, *state]]), after)
    (got, got_cache), (want, want_cache) = out["flash"], out["einsum"]
    if program == "decode_step":
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    else:
        np.testing.assert_array_equal(got, want)
        assert (got[:3, :2] == -1).all()  # the dead slots emit nothing
        assert (got[:2, 3] >= 0).all() and got[2, 3] == -1  # out of budget after two
        assert (got[:3, 4] >= 0).all()  # over the block's edge and on
    _close(got_cache, want_cache)
    # a slot that may not write keeps every byte of its rows
    for name, a in got_cache.items():
        if a.ndim > 2:
            np.testing.assert_array_equal(np.asarray(a)[:, :2], np.asarray(cache[name])[:, :2])


def test_the_rule_takes_the_kernel_for_what_it_can_see(monkeypatch):
    """By itself: on the TPU, a grouped-query layer over a plain row
    buffer, its head filling the lanes (128) or leaving the leaf with
    its tokens there (64: the kernel's other block form, PR 45); never
    a ring, a latent, Llama4's chunks, a head that is no half of the
    lanes; a caller's word goes first."""
    wide = MODELS["gqa-128"][0]
    assert not fd.reads_live_keys(wide, 1536)  # the CPU: the einsum
    assert not fd.reads_live_keys(DENSE, 1536)
    assert fd.reads_live_keys(DENSE, 256, decode_kernel="flash")  # asked for
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert fd.reads_live_keys(wide, 1536)
    assert fd.reads_live_keys(dataclasses.replace(MODELS["groups"][0], head_dim=128), 8192)
    assert not fd.reads_live_keys(wide, 1536, quantized=True)  # an int8 pair, either width
    assert not fd.reads_live_keys(DENSE, 1536, quantized=True)
    assert fd.reads_live_keys(DENSE, 256, quantized=True, decode_kernel="flash")
    # head_dim 64: the plain family, layer groups, beside conv layers
    assert fd.reads_live_keys(DENSE, 1536) and fd.reads_live_keys(llama.LLAMA_32_1B, 2048)
    assert fd.reads_live_keys(MODELS["groups"][0], 8192)
    assert fd.reads_live_keys(MODELS["conv-groups"][0], 8192)
    assert not fd.reads_live_keys(MODELS["conv-groups"][0], 768, ring=True)
    assert not fd.reads_live_keys(llama.LLAMA_TINY, 1536)  # head_dim 32
    assert fd.tokens_on_lanes(64) and fd.tokens_on_lanes(576) and not fd.tokens_on_lanes(128)
    assert not fd.reads_live_keys(wide, 1536, decode_kernel="einsum")
    assert not fd.reads_live_keys(wide, 768, ring=True)
    assert not fd.reads_live_keys(wide, 1500)  # rows the blocks do not divide
    assert not fd.reads_live_keys(dataclasses.replace(wide, attention_chunk_size=64), 1536)
    assert not fd.reads_live_keys(llama.MLA_TINY, 1536)
    assert not fd.reads_live_keys(wide, 256, ring=True, decode_kernel="flash")
    # one block rule: about 2 MiB of K and V a grid step, a divisor of the row
    assert fd.block_keys(8, 128, 1536) == 512 and fd.block_keys(8, 128, 8192) == 512
    assert fd.block_keys(8, 128, 1536, itemsize=1) == 768
    assert fd.block_keys(2, 128, 1536) == 1536 and fd.block_keys(8, 128, 640) == 128
    assert fd.block_keys(8, 64, 8192) == 1024  # the rag cell's: the same bytes a step


@pytest.mark.parametrize("head_dim", [64, 128])
def test_engine_counts_each_slots_own_blocks(head_dim, monkeypatch):
    """One long and three short live slots and twelve empty ones: a
    token step reads the sum of the four's own blocks, nothing of the
    twelve, where a whole-row program reads sixteen rows: the same
    count in either block form of the kernel."""
    config = dataclasses.replace(DENSE, head_dim=head_dim)
    _block_of_128(monkeypatch, config)
    params = init_params(config, 1)
    e = InferenceEngine(
        config, params, max_batch=16, max_seq=TMAX, spec_draft=0, turbo_steps=0,
        decode_kernel="flash",
    )
    assert e._slot_keys and e._key_block == KB and e._full_layers == 2
    prompts = [list(range(1, 201)), [5, 6, 7], [8] * 10, [9] * 128]
    slots = [e.add_request(p, GenParams(max_new_tokens=4))[0] for p in prompts]
    value = lambda n: e.metrics.family(n).value()
    out = e.step()
    assert sorted(out) == sorted(slots) and all(len(t) == 1 for t in out.values())
    # the step's token found the prompt in the cache: 200 keys take two
    # blocks; 3, 10 and 128 keys one each
    assert value("dtpu_serve_decode_keys_read_total") == (2 + 1 + 1 + 1) * KB * 2
    assert value("dtpu_serve_decode_keys_reserved_total") == 16 * TMAX * 2
    # a macro-step of 3 tokens carries the fourth slot over the edge:
    # 129, 130, 131 keys are two blocks each
    before = value("dtpu_serve_decode_keys_read_total")
    e._last_step_phase, e.lengths[slots[3]] = "turbo", 132
    e._count_decode_keys({slots[3]: [1, 2, 3], slots[1]: [4]})
    assert value("dtpu_serve_decode_keys_read_total") - before == (3 * 2 + 1) * KB * 2
    # the einsum reads every reserved row
    whole = InferenceEngine(config, params, max_batch=16, max_seq=TMAX, spec_draft=0)
    assert not whole._slot_keys and whole._key_block == 0
