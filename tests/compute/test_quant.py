"""Weight-only int8 quantization: error bounds, forward parity, serving."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dstack_tpu.models import llama
from dstack_tpu.models.quant import (
    dequantize_weight,
    is_quantized,
    quant_param_specs,
    quantize_tree,
    quantize_weight,
)
from tests.shared import init_params


class TestQuantizeWeight:
    def test_roundtrip_error_bound(self):
        w = jax.random.normal(jax.random.key(0), (64, 32)) * 0.05
        q, s = quantize_weight(w)
        assert q.dtype == jnp.int8
        back = dequantize_weight(q, s, jnp.float32)
        # per-channel absmax: error ≤ scale/2 = absmax/254 per element
        bound = np.abs(np.asarray(w)).max(axis=0) / 254.0 + 1e-8
        err = np.abs(np.asarray(back) - np.asarray(w))
        assert (err <= bound[None, :] + 1e-7).all()

    def test_zero_column_safe(self):
        w = jnp.zeros((8, 4))
        q, s = quantize_weight(w)
        assert np.asarray(q).max() == 0
        assert np.isfinite(np.asarray(s)).all()

    def test_stacked_layers(self):
        w = jax.random.normal(jax.random.key(1), (3, 16, 8))
        q, s = quantize_weight(w)
        assert q.shape == (3, 16, 8) and s.shape == (3, 8)


class TestQuantizedForward:
    def test_logits_close_to_full_precision(self):
        config = llama.LLAMA_TINY
        params = init_params(config, 0)
        qparams = quantize_tree(params, config)
        assert is_quantized(qparams)
        tokens = jax.random.randint(jax.random.key(1), (2, 32), 0, config.vocab_size)
        full = llama.forward(params, tokens, config)
        quant = llama.forward(qparams, tokens, config)
        # int8 per-channel keeps logits within a fraction of their scale
        denom = np.abs(np.asarray(full)).max() + 1e-6
        rel = np.abs(np.asarray(quant) - np.asarray(full)).max() / denom
        assert rel < 0.05, f"relative logit error {rel:.3f}"

    def test_untied_lm_head_quantized(self):
        config = llama.dataclasses.replace(llama.LLAMA_TINY, tie_embeddings=False)
        params = init_params(config, 2)
        qparams = quantize_tree(params, config)
        assert "lm_head_q" in qparams and "lm_head" not in qparams
        tokens = jax.random.randint(jax.random.key(3), (1, 16), 0, config.vocab_size)
        full = llama.forward(params, tokens, config)
        quant = llama.forward(qparams, tokens, config)
        denom = np.abs(np.asarray(full)).max() + 1e-6
        assert np.abs(np.asarray(quant) - np.asarray(full)).max() / denom < 0.05

    def test_moe_expert_stacks_quantized(self):
        """MoE expert stacks [L, E, in, out] quantize per (expert,
        output channel); the router stays full precision and the
        dispatch/combine path consumes the int8 form."""
        config = llama.dataclasses.replace(
            llama.MOE_TINY, capacity_factor=float(llama.MOE_TINY.n_experts)
        )
        params = init_params(config, 0)
        qparams = quantize_tree(params, config)
        assert "w_gate_q" in qparams["layers"]
        assert qparams["layers"]["w_gate_s"].shape == (
            config.n_layers, config.n_experts, config.intermediate_size
        )
        assert "w_router" in qparams["layers"]  # router not quantized
        tokens = jax.random.randint(
            jax.random.key(1), (2, 16), 0, config.vocab_size
        )
        full = llama.forward(params, tokens, config)
        quant = llama.forward(qparams, tokens, config)
        denom = np.abs(np.asarray(full)).max() + 1e-6
        rel = np.abs(np.asarray(quant) - np.asarray(full)).max() / denom
        assert rel < 0.05, f"relative logit error {rel:.3f}"

    def test_shared_expert_quantized(self):
        """The fused shared expert (Llama4/DeepSeek layout) quantizes
        through _proj's int8 resolution like any dense projection."""
        config = llama.dataclasses.replace(
            llama.MOE_TINY, moe_shared_expert=True,
            moe_shared_intermediate=64,
            capacity_factor=float(llama.MOE_TINY.n_experts),
        )
        params = init_params(config, 0)
        qparams = quantize_tree(params, config)
        assert "w_shared_gate_q" in qparams["layers"]
        tokens = jax.random.randint(
            jax.random.key(1), (2, 16), 0, config.vocab_size
        )
        full = llama.forward(params, tokens, config)
        quant = llama.forward(qparams, tokens, config)
        denom = np.abs(np.asarray(full)).max() + 1e-6
        rel = np.abs(np.asarray(quant) - np.asarray(full)).max() / denom
        assert rel < 0.05, f"relative logit error {rel:.3f}"

    def test_moe_engine_decode(self):
        from dstack_tpu.serve.engine import GenParams, InferenceEngine

        config = llama.dataclasses.replace(
            llama.MOE_TINY, capacity_factor=float(llama.MOE_TINY.n_experts)
        )
        params = init_params(config, 0)
        qparams = quantize_tree(params, config)
        eng = InferenceEngine(
            config, qparams, max_batch=2, max_seq=64,
            spec_draft=0, turbo_steps=0,
        )
        out = eng.generate([3, 14, 15, 9], GenParams(max_new_tokens=5))
        assert len(out) == 5

    def test_mla_bench_path_still_refused(self):
        """The bench's random-tree generators stay non-MLA (the serving
        bench targets the llama family); the REAL quantize_tree now
        covers MLA — see TestMLAQuantization."""
        from dstack_tpu.models.quant import random_quantized_params

        with pytest.raises(ValueError, match="MLA"):
            random_quantized_params(llama.MLA_TINY)


class TestRandomQuantizedParams:
    """The numpy fast path must mirror the real init→quantize tree
    exactly — any layout drift must fail here, not at device_put."""

    def _assert_same_tree(self, config):
        from dstack_tpu.models.quant import random_quantized_params

        real = quantize_tree(
            init_params(config, 0), config
        )
        fast = random_quantized_params(config)
        rl = jax.tree_util.tree_leaves_with_path(real)
        fl = jax.tree_util.tree_leaves_with_path(fast)
        assert [p for p, _ in rl] == [p for p, _ in fl]
        for (path, a), (_, b) in zip(rl, fl):
            assert a.shape == b.shape, path
            assert jnp.asarray(a).dtype == jnp.asarray(b).dtype, path

    def test_matches_quantize_tree_structure(self):
        self._assert_same_tree(llama.LLAMA_TINY)

    def test_on_device_path_matches_numpy_path(self):
        """The jitted on-device generator (what the TPU serving bench
        uses — no bulk host→device copy) must emit the
        exact structure/shapes/dtypes of the numpy host path, and its
        tree must drive a forward pass."""
        from dstack_tpu.models.quant import (
            random_quantized_params,
            random_quantized_params_on_device,
        )

        config = llama.LLAMA_TINY
        host = random_quantized_params(config)
        dev = random_quantized_params_on_device(config)
        hl = jax.tree_util.tree_leaves_with_path(host)
        dl = jax.tree_util.tree_leaves_with_path(dev)
        assert [p for p, _ in hl] == [p for p, _ in dl]
        for (path, a), (_, b) in zip(hl, dl):
            assert a.shape == b.shape, path
            assert jnp.asarray(a).dtype == jnp.asarray(b).dtype, path
        assert is_quantized(dev)
        tokens = jax.random.randint(
            jax.random.key(1), (1, 8), 0, config.vocab_size
        )
        logits = llama.forward(dev, tokens, config)
        assert np.isfinite(np.asarray(logits)).all()

    def test_untied_head_and_forward_runs(self):
        from dstack_tpu.models.quant import random_quantized_params

        config = llama.dataclasses.replace(
            llama.LLAMA_TINY, tie_embeddings=False
        )
        self._assert_same_tree(config)
        qparams = jax.device_put(random_quantized_params(config))
        assert is_quantized(qparams)
        tokens = jax.random.randint(
            jax.random.key(1), (1, 8), 0, config.vocab_size
        )
        logits = llama.forward(qparams, tokens, config)
        assert np.isfinite(np.asarray(logits)).all()


class TestQuantizedServing:
    def test_engine_greedy_decode(self):
        from dstack_tpu.serve.engine import GenParams, InferenceEngine

        config = llama.LLAMA_TINY
        params = init_params(config, 0)
        qparams = quantize_tree(params, config)
        full_eng = InferenceEngine(config, params, max_batch=2, max_seq=64)
        q_eng = InferenceEngine(config, qparams, max_batch=2, max_seq=64)
        prompt = [3, 14, 15, 9, 2]
        a = full_eng.generate(prompt, GenParams(max_new_tokens=6))
        b = q_eng.generate(prompt, GenParams(max_new_tokens=6))
        # random tiny logits are closely spaced; just require a valid
        # stream and substantial agreement on the first tokens
        assert len(b) == len(a)
        assert b[0] == a[0]

    @pytest.mark.skipif(len(jax.devices()) < 2, reason="needs 2 devices")
    def test_tensor_parallel_sharded_quantized(self):
        from dstack_tpu.parallel.mesh import MeshConfig, make_mesh
        from dstack_tpu.serve.engine import GenParams, InferenceEngine

        config = llama.LLAMA_TINY
        params = init_params(config, 0)
        qparams = quantize_tree(params, config)
        mesh = make_mesh(
            MeshConfig(dp=1, fsdp=1, tp=2), devices=jax.devices()[:2]
        )
        eng = InferenceEngine(config, qparams, max_batch=2, max_seq=64, mesh=mesh)
        ref = InferenceEngine(config, params, max_batch=2, max_seq=64)
        prompt = [5, 6, 7, 8]
        a = ref.generate(prompt, GenParams(max_new_tokens=5))
        b = eng.generate(prompt, GenParams(max_new_tokens=5))
        assert len(b) == len(a) and b[0] == a[0]

    def test_spec_tree_matches_quantized_leaves(self):
        config = llama.dataclasses.replace(llama.LLAMA_TINY, tie_embeddings=False)
        params = init_params(config, 0)
        qparams = quantize_tree(params, config)
        specs = quant_param_specs(llama.param_specs(config))
        # identical tree structure → shardable leaf-for-leaf
        p_paths = {
            jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_leaves_with_path(qparams)
        }
        s_paths = {
            jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_leaves_with_path(
                specs, is_leaf=lambda x: isinstance(x, tuple)
            )
        }
        assert p_paths == s_paths


class TestMLAQuantization:
    """DeepSeek trees quantize their expert/FFN stacks + wo (the bytes)
    while latent attention projections stay full precision — previously
    MLA was refused entirely, serving V2/V3-family checkpoints bf16."""

    def test_mla_tree_quantizes_ffn_and_wo(self):
        from dstack_tpu.models.quant import quant_targets

        config = llama.MLA_TINY
        params = init_params(config, 0)
        qparams = quantize_tree(params, config)
        assert is_quantized(qparams)
        for stack in ("layers", "dense_layers"):
            keys = qparams[stack]
            assert "w_gate_q" in keys and "w_gate" not in keys
            assert "wo_q" in keys and "wo" not in keys
            # latent attention stays full precision
            for name in ("wq_a", "wq_b", "wkv_a", "wkv_b"):
                assert name in keys and name + "_q" not in keys, name
        assert "wo" in quant_targets(config)

    def test_mla_quantized_forward_close(self):
        config = llama.MLA_TINY
        params = init_params(config, 0)
        qparams = quantize_tree(params, config)
        tokens = jax.random.randint(
            jax.random.key(1), (2, 32), 0, config.vocab_size
        )
        full = llama.forward(params, tokens, config)
        quant = llama.forward(qparams, tokens, config)
        denom = np.abs(np.asarray(full)).max() + 1e-6
        rel = np.abs(np.asarray(quant) - np.asarray(full)).max() / denom
        assert rel < 0.05, f"relative logit error {rel:.3f}"

    def test_mla_quantized_serving_runs(self):
        from dstack_tpu.serve.engine import GenParams, InferenceEngine

        config = llama.MLA_TINY
        params = init_params(config, 0)
        qparams = quantize_tree(params, config)
        eng = InferenceEngine(config, qparams, max_batch=2, max_seq=128)
        out = eng.generate([7, 11, 13, 17], GenParams(max_new_tokens=5))
        assert len(out) >= 1 and all(isinstance(t, int) for t in out)

    def test_mla_quantized_tp_mesh_matches_single_device(self):
        """The V3 deployment shape: int8 MLA tree over a tp mesh. The
        config-aware quant specs must shard the partial tree so the
        greedy stream matches unsharded quantized serving exactly."""
        from dstack_tpu.parallel.mesh import MeshConfig, make_mesh
        from dstack_tpu.serve.engine import GenParams, InferenceEngine

        config = llama.MLA_TINY  # 4 q heads: tp=2 shards them
        params = init_params(config, 0)
        qparams = quantize_tree(params, config)
        prompt = [7, 11, 13, 17]
        ref = InferenceEngine(
            config, qparams, max_batch=2, max_seq=128
        ).generate(prompt, GenParams(max_new_tokens=5))
        mesh = make_mesh(MeshConfig(dp=1, fsdp=1, tp=2))
        eng = InferenceEngine(
            config, qparams, max_batch=2, max_seq=128, mesh=mesh
        )
        assert eng.generate(prompt, GenParams(max_new_tokens=5)) == ref

    def test_mla_spec_tree_matches_quantized_leaves(self):
        config = llama.MLA_TINY
        params = init_params(config, 0)
        qparams = quantize_tree(params, config)
        specs = quant_param_specs(llama.param_specs(config), config)
        p_paths = {
            jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_leaves_with_path(qparams)
        }
        s_paths = {
            jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_leaves_with_path(
                specs, is_leaf=lambda x: isinstance(x, tuple)
            )
        }
        assert p_paths == s_paths
