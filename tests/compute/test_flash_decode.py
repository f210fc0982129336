"""flash_decode parity vs the engine's einsum decode attention.

The kernel must reproduce serve/engine.py::decode_step's masked-einsum
attention exactly (same masks, same softmax, same GQA regrouping) for
every feature combination it claims: ragged positions, int8 KV with
per-token scales, traced sliding windows, softcap, sinks. Interpret
mode on CPU — the kernel itself is the unit under test, in both of its
block forms: the kernel takes the form by the head's width (``HEAD_DIM``:
128 fills the lanes and a block is [keys, head]; 64 leaves the cache
leaf with its tokens on the lanes and a block is [head, keys]).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dstack_tpu.ops.flash_decode import flash_decode, flash_decode_supported
from dstack_tpu.serve.engine import kv_quantize
from tests.shared import init_params

NEG_INF = -1e30
HEAD_DIM = [64, 128]


def _ref_decode_attention(
    qg, kf, vf, positions, scale, window=0, softcap=0.0, sinks=None
):
    """decode_step's einsum attention, verbatim semantics."""
    s = jnp.einsum(
        "bhgd,bhkd->bhgk", qg, kf, preferred_element_type=jnp.float32
    ) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    kj = jnp.arange(kf.shape[2])[None, None, None, :]
    pos = positions[:, None, None, None]
    mask = kj <= pos
    mask = jnp.logical_and(
        mask, jnp.logical_or(window == 0, pos - kj < window)
    )
    s = jnp.where(mask, s, NEG_INF)
    if sinks is not None:
        from dstack_tpu.ops.attention import sink_softmax

        p = sink_softmax(s, sinks[None, :, :, None].astype(jnp.float32))
    else:
        p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhgk,bhkd->bhgd", p.astype(vf.dtype), vf)


def _rand(key, b=2, hkv=2, g=4, t=256, d=64, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, hkv, g, d), dtype)
    k = jax.random.normal(kk, (b, hkv, t, d), dtype)
    v = jax.random.normal(kv, (b, hkv, t, d), dtype)
    return q, k, v


@pytest.mark.parametrize("d", HEAD_DIM)
class TestFlashDecodeParity:
    def test_ragged_positions(self, d):
        q, k, v = _rand(jax.random.key(0), d=d)
        # mixed lengths incl. a fresh slot (pos 0) and a full row
        positions = jnp.asarray([3, 255], jnp.int32)
        out = flash_decode(
            q, k, v, positions, scale=0.125, block_k=128, interpret=True
        )
        ref = _ref_decode_attention(q, k, v, positions, 0.125)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_window_and_softcap(self, d):
        q, k, v = _rand(jax.random.key(1), d=d)
        positions = jnp.asarray([129, 200], jnp.int32)
        win = jnp.asarray(64, jnp.int32)  # traced, like the layer scan
        out = flash_decode(
            q, k, v, positions, scale=0.125, window=win, softcap=30.0,
            block_k=128, interpret=True,
        )
        ref = _ref_decode_attention(
            q, k, v, positions, 0.125, window=64, softcap=30.0
        )
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_window_zero_matches_full(self, d):
        q, k, v = _rand(jax.random.key(2), d=d)
        positions = jnp.asarray([100, 250], jnp.int32)
        out = flash_decode(
            q, k, v, positions, scale=0.125,
            window=jnp.asarray(0, jnp.int32), block_k=128, interpret=True,
        )
        ref = _ref_decode_attention(q, k, v, positions, 0.125)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_int8_kv(self, d):
        q, k, v = _rand(jax.random.key(3), d=d)
        kq8, ks = kv_quantize(k)
        vq8, vs = kv_quantize(v)
        positions = jnp.asarray([17, 255], jnp.int32)
        out = flash_decode(
            q, kq8, vq8, positions, scale=0.125,
            k_scale=ks, v_scale=vs, block_k=128, interpret=True,
        )
        # reference dequantizes exactly like engine._cfull
        from dstack_tpu.serve.engine import kv_dequant

        ref = _ref_decode_attention(
            q, kv_dequant(kq8, ks, q.dtype), kv_dequant(vq8, vs, q.dtype),
            positions, 0.125,
        )
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_sinks(self, d):
        q, k, v = _rand(jax.random.key(4), d=d)
        positions = jnp.asarray([63, 128], jnp.int32)
        sinks = jax.random.normal(jax.random.key(5), (2, 4), jnp.float32)
        out = flash_decode(
            q, k, v, positions, scale=0.125, sinks=sinks,
            block_k=128, interpret=True,
        )
        ref = _ref_decode_attention(
            q, k, v, positions, 0.125, sinks=sinks
        )
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_mha_group_of_one(self, d):
        q, k, v = _rand(jax.random.key(6), hkv=4, g=1, d=d)
        positions = jnp.asarray([0, 200], jnp.int32)
        out = flash_decode(
            q, k, v, positions, scale=0.125, block_k=128, interpret=True
        )
        ref = _ref_decode_attention(q, k, v, positions, 0.125)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_bf16_inputs(self, d):
        q, k, v = _rand(jax.random.key(7), d=d, dtype=jnp.bfloat16)
        positions = jnp.asarray([50, 180], jnp.int32)
        out = flash_decode(
            q, k, v, positions, scale=0.125, block_k=128, interpret=True
        )
        ref = _ref_decode_attention(q, k, v, positions, 0.125)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=2e-2, rtol=2e-2,
        )


# what the engine's decode scan hands the kernel since PR 43: the
# STACKED leaf read in place at a traced layer row, the token's own key
# beside the cache (which is masked at kj < position), length 0 for a
# slot that is not live; since PR 45 at both widths, the narrow one
# with the rag cell's grouping (4 query heads a KV head).
# case → (kwargs, int8)
STACKED = {
    "plain": ({}, False),
    "window-softcap": ({"window": 48, "softcap": 30.0}, False),
    "sinks": ({"sinks": True}, False),
    "int8": ({}, True),
    "int8-window-sinks": ({"window": 200, "sinks": True}, True),
}


class TestStackedLeafNewRow:
    @pytest.mark.parametrize("d", HEAD_DIM)
    @pytest.mark.parametrize("case", sorted(STACKED))
    def test_reads_its_layer_and_its_slots_blocks(self, case, d):
        from dstack_tpu.serve.engine import kv_dequant

        kw, int8 = STACKED[case]
        L, li, b, hkv, g, t, bk = 3, 1, 6, 2, 4 if d == 64 else 3, 384, 128
        ks = jax.random.split(jax.random.key(9), 6)
        q = jax.random.normal(ks[0], (b, hkv, g, d), jnp.float32)
        k = jax.random.normal(ks[1], (L, b, hkv, t, d), jnp.float32)
        v = jax.random.normal(ks[2], (L, b, hkv, t, d), jnp.float32)
        k_new = jax.random.normal(ks[3], (b, hkv, 1, d), jnp.float32)
        v_new = jax.random.normal(ks[4], (b, hkv, 1, d), jnp.float32)
        # not live (length 0), one key, a block's edge and either side
        # of it, the row's end
        positions = jnp.asarray([0, 1, bk - 1, bk, bk + 1, t - 1], jnp.int32)
        sinks = (
            jax.random.normal(ks[5], (hkv, g), jnp.float32) if kw.get("sinks") else None
        )
        opt = dict(
            window=jnp.asarray(kw.get("window", 0), jnp.int32),
            softcap=kw.get("softcap", 0.0), sinks=sinks,
        )
        if int8:
            (k, k_s), (v, v_s) = kv_quantize(k), kv_quantize(v)
            opt.update(k_scale=k_s, v_scale=v_s)
            kf, vf = kv_dequant(k, k_s, q.dtype), kv_dequant(v, v_s, q.dtype)
        else:
            kf, vf = k, v
        # the einsum's operand: the layer's slice with the new row selected in
        hit = (jnp.arange(t)[None, :] == positions[:, None])[:, None, :, None]
        ref = _ref_decode_attention(
            q, jnp.where(hit, k_new, kf[li]), jnp.where(hit, v_new, vf[li]),
            positions, 0.125, window=kw.get("window", 0),
            softcap=kw.get("softcap", 0.0), sinks=sinks,
        )
        if not int8:
            # nothing else is read: the other layers and every block past
            # a slot's last are NaN
            last = jnp.maximum(positions - 1, 0) // bk
            dead = (jnp.arange(t)[None, :] // bk > last[:, None])[:, None, :, None]
            k = jnp.where(dead, jnp.nan, k).at[0].set(jnp.nan).at[2].set(jnp.nan)
            v = jnp.where(dead, jnp.nan, v).at[0].set(jnp.nan).at[2].set(jnp.nan)
        out = jax.jit(
            lambda layer, kn, vn, *a: flash_decode(
                *a, scale=0.125, layer=layer, k_new=kn, v_new=vn, block_k=bk,
                interpret=True, **opt,
            )
        )(jnp.asarray(li), k_new, v_new, q, k, v, positions)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
        if sinks is None:  # a slot that holds nothing attends to its own key alone
            np.testing.assert_allclose(
                out[0], jnp.broadcast_to(v_new[0], out[0].shape), atol=1e-6
            )


class TestVerifyRows:
    @pytest.mark.parametrize("d", HEAD_DIM)
    def test_rows_per_slot_matches_per_row_masks(self, d):
        """rows_per_slot=S: row g*S+s attends to keys <= pos+s — the
        speculative-verify shape, checked against a per-row einsum."""
        S, g = 3, 2
        b, hkv, t = 2, 2, 256
        kq, kk, kv = jax.random.split(jax.random.key(8), 3)
        q = jax.random.normal(kq, (b, hkv, g * S, d), jnp.float32)
        k = jax.random.normal(kk, (b, hkv, t, d), jnp.float32)
        v = jax.random.normal(kv, (b, hkv, t, d), jnp.float32)
        positions = jnp.asarray([5, 130], jnp.int32)
        out = flash_decode(
            q, k, v, positions, scale=0.125, rows_per_slot=S,
            block_k=128, interpret=True,
        )
        # reference: einsum with per-row key limits
        s_ = jnp.einsum(
            "bhrd,bhkd->bhrk", q, k, preferred_element_type=jnp.float32
        ) * 0.125
        kj = jnp.arange(t)[None, None, None, :]
        roff = (jnp.arange(g * S) % S)[None, None, :, None]
        qpos = positions[:, None, None, None] + roff
        p = jax.nn.softmax(jnp.where(kj <= qpos, s_, NEG_INF), axis=-1)
        ref = jnp.einsum("bhrk,bhkd->bhrd", p.astype(v.dtype), v)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


class TestEngineParity:
    def _config(self):
        from dstack_tpu.models import llama

        # head_dim 64 (kernel-eligible), GQA 2:1, tiny everything else
        return llama.LLAMA_TINY_64

    @pytest.mark.parametrize("kv_quant", [None, "int8"])
    def test_greedy_tokens_identical(self, kv_quant):
        """Same prompts through the real engine (chunked prefill +
        turbo decode_loop) on both kernels → identical token ids."""
        from dstack_tpu.models import llama
        from dstack_tpu.serve.engine import GenParams, InferenceEngine

        config = self._config()
        params = init_params(config, 0)
        prompts = [
            list(range(1, 40)),
            list(range(7, 20)),  # ragged: different lengths
        ]
        outs = {}
        for kernel in ("einsum", "flash"):
            eng = InferenceEngine(
                config, params, max_batch=2, max_seq=256,
                turbo_steps=4, spec_draft=0, kv_quant=kv_quant,
                decode_kernel=kernel,
            )
            slots = [
                eng.add_request(p, GenParams(max_new_tokens=8))[0]
                for p in prompts
            ]
            got: dict = {s: [] for s in slots}
            while any(eng.active[s] for s in slots):
                for s, toks in eng.step().items():
                    got[s].extend(toks)
            outs[kernel] = [got[s] for s in slots]
        assert outs["flash"] == outs["einsum"]
        # random weights may hit EOS early — parity is the contract,
        # but every slot must actually have decoded something
        assert all(len(t) >= 1 for t in outs["flash"])

    @pytest.mark.parametrize("kv_quant", [None, "int8"])
    def test_tp_mesh_matches_einsum(self, kv_quant):
        """flash decode under shard_map on a tp=2 mesh (KV heads local
        per shard, no collectives) must reproduce the einsum mesh
        path's greedy stream exactly."""
        from dstack_tpu.models import llama
        from dstack_tpu.parallel.mesh import MeshConfig, make_mesh
        from dstack_tpu.serve.engine import GenParams, InferenceEngine

        # MHA 2 heads × 64: tp=2 leaves one KV head per shard
        config = llama.dataclasses.replace(
            llama.LLAMA_TINY_64, n_heads=2, n_kv_heads=2,
        )
        params = init_params(config, 0)
        mesh = make_mesh(MeshConfig(dp=1, fsdp=1, tp=2))
        prompt = [11, 22, 33, 44, 55]
        outs = {}
        for kernel in ("einsum", "flash"):
            eng = InferenceEngine(
                config, params, max_batch=2, max_seq=256, mesh=mesh,
                turbo_steps=4, spec_draft=0, kv_quant=kv_quant,
                decode_kernel=kernel,
            )
            outs[kernel] = eng.generate(prompt, GenParams(max_new_tokens=6))
        assert outs["flash"] == outs["einsum"]
        assert len(outs["flash"]) >= 1

    @pytest.mark.parametrize("kv_quant", [None, "int8"])
    def test_speculative_verify_parity(self, kv_quant):
        """spec_draft routes through verify_step: a repetitive prompt
        makes prompt-lookup drafts fire, so the flash verify path
        (rows_per_slot=S) must emit the einsum path's exact stream."""
        from dstack_tpu.models import llama
        from dstack_tpu.serve.engine import GenParams, InferenceEngine

        config = self._config()
        params = init_params(config, 0)
        phrase = [5, 9, 13, 17]
        prompt = (phrase * 12)[:40]  # repetition → drafts accepted
        outs = {}
        for kernel in ("einsum", "flash"):
            eng = InferenceEngine(
                config, params, max_batch=2, max_seq=256,
                turbo_steps=0, spec_draft=3, kv_quant=kv_quant,
                decode_kernel=kernel,
            )
            outs[kernel] = eng.generate(
                prompt, GenParams(max_new_tokens=10)
            )
        assert outs["flash"] == outs["einsum"]
        assert len(outs["flash"]) >= 1

    def test_speculative_verify_sinks_window_tp_mesh(self):
        """Speculative verify through the per-row window mask, the
        [Hkv, G*S] sink expansion, AND the verify shard_map specs at
        once — the branches the plain spec-parity test never enters."""
        from dstack_tpu.models import llama
        from dstack_tpu.parallel.mesh import MeshConfig, make_mesh
        from dstack_tpu.serve.engine import GenParams, InferenceEngine

        config = llama.dataclasses.replace(
            llama.LLAMA_TINY_64, n_heads=4, n_kv_heads=2,
            hidden_size=256, intermediate_size=512,
            attn_sinks=True, sliding_window=32, sliding_pattern=2,
        )
        params = init_params(config, 3)
        mesh = make_mesh(MeshConfig(dp=1, fsdp=1, tp=2))
        phrase = [5, 9, 13, 17]
        prompt = (phrase * 12)[:44]  # repetition → drafts fire
        outs = {}
        for kernel in ("einsum", "flash"):
            eng = InferenceEngine(
                config, params, max_batch=2, max_seq=256, mesh=mesh,
                turbo_steps=0, spec_draft=3, kv_quant="int8",
                decode_kernel=kernel,
            )
            outs[kernel] = eng.generate(
                prompt, GenParams(max_new_tokens=10)
            )
        assert outs["flash"] == outs["einsum"]
        assert len(outs["flash"]) >= 1

    @pytest.mark.parametrize("kv_quant", [None, "int8"])
    def test_tp_mesh_gqa_sinks_window(self, kv_quant):
        """The shard_map spec branches the plain test misses: GQA
        (grp 2 per KV head), sink logits (P('tp', None) sharding), and
        the traced per-layer sliding window (alternating 0/32 via
        sliding_pattern) — all under a tp=2 mesh, vs the einsum path."""
        from dstack_tpu.models import llama
        from dstack_tpu.parallel.mesh import MeshConfig, make_mesh
        from dstack_tpu.serve.engine import GenParams, InferenceEngine

        config = llama.dataclasses.replace(
            llama.LLAMA_TINY_64, n_heads=4, n_kv_heads=2,
            hidden_size=256, intermediate_size=512,
            attn_sinks=True, sliding_window=32, sliding_pattern=2,
        )
        params = init_params(config, 2)
        mesh = make_mesh(MeshConfig(dp=1, fsdp=1, tp=2))
        prompt = list(range(3, 50))  # long enough to engage the window
        outs = {}
        for kernel in ("einsum", "flash"):
            eng = InferenceEngine(
                config, params, max_batch=2, max_seq=256, mesh=mesh,
                turbo_steps=4, spec_draft=0, kv_quant=kv_quant,
                decode_kernel=kernel,
            )
            outs[kernel] = eng.generate(prompt, GenParams(max_new_tokens=6))
        assert outs["flash"] == outs["einsum"]
        assert len(outs["flash"]) >= 1

    def test_unsupported_config_raises(self):
        from dstack_tpu.models import llama
        from dstack_tpu.serve.engine import InferenceEngine

        config = llama.LLAMA_TINY  # head_dim 32
        params = init_params(config, 0)
        with pytest.raises(ValueError, match="flash"):
            InferenceEngine(
                config, params, max_batch=2, max_seq=256,
                decode_kernel="flash",
            )


class TestSupportGate:
    def test_gate(self):
        from dstack_tpu.models import llama

        c = llama.CONFIGS["llama-3.2-1b"]  # head_dim 64
        assert flash_decode_supported(c, 1024)
        assert not flash_decode_supported(c, 1000)  # T % 128
        # tiny test config (head_dim 32) stays on the einsum path
        assert not flash_decode_supported(llama.LLAMA_TINY, 1024)
        mla = llama.CONFIGS["deepseek-v2-lite"]
        assert not flash_decode_supported(mla, 1024)
