"""LoRA fine-tuning path (BASELINE target: Llama-3-8B LoRA on v5e-8),
exercised on the tiny config over the 8-device virtual mesh."""

import jax
import jax.numpy as jnp
import numpy as np

from dstack_tpu.models import llama
from dstack_tpu.parallel.mesh import MeshConfig, make_mesh
from dstack_tpu.train.lora import (
    LoRAConfig,
    init_lora_params,
    lora_param_specs,
    make_lora_train_step,
    merge_lora_params,
    sharded_lora_init,
)
from dstack_tpu.train.step import default_optimizer
from tests.shared import init_params

CFG = llama.LLAMA_TINY
LORA = LoRAConfig(rank=4, alpha=8.0)


def _batch(key, batch=4, seq=32):
    tokens = jax.random.randint(key, (batch, seq), 0, CFG.vocab_size)
    return {
        "tokens": tokens,
        "targets": jnp.roll(tokens, -1, axis=1),
        "mask": jnp.ones_like(tokens),
    }


class TestLoRAForward:
    def test_zero_init_is_identity(self):
        """B=0 at init → adapter output must equal the base model."""
        params = init_params(CFG, 0)
        lora = init_lora_params(CFG, LORA, jax.random.key(1))
        tokens = jax.random.randint(jax.random.key(2), (2, 16), 0, CFG.vocab_size)
        base = llama.forward(params, tokens, CFG)
        adapted = llama.forward(
            params, tokens, CFG, lora=lora, lora_scale=LORA.scale
        )
        np.testing.assert_allclose(base, adapted, atol=1e-6)

    def test_bypass_matches_merged_weights(self):
        """s·(x·A)·B bypass ≡ forward with W+s·A·B folded in."""
        params = init_params(CFG, 0)
        lora = init_lora_params(CFG, LORA, jax.random.key(1))
        # give B real values so the adapters actually do something
        lora = jax.tree.map(
            lambda x: jax.random.normal(jax.random.key(9), x.shape, x.dtype) * 0.02,
            lora,
        )
        tokens = jax.random.randint(jax.random.key(2), (2, 16), 0, CFG.vocab_size)
        adapted = llama.forward(params, tokens, CFG, lora=lora, lora_scale=LORA.scale)
        merged = merge_lora_params(params, lora, LORA)
        folded = llama.forward(merged, tokens, CFG)
        np.testing.assert_allclose(adapted, folded, atol=2e-2, rtol=2e-2)
        assert not np.allclose(
            adapted, llama.forward(params, tokens, CFG), atol=1e-4
        )

    def test_mlp_target_modules(self):
        lora_conf = LoRAConfig(rank=4, target_modules=("w_gate", "w_up", "w_down"))
        params = init_params(CFG, 0)
        lora = init_lora_params(CFG, lora_conf, jax.random.key(1))
        tokens = jnp.zeros((1, 8), jnp.int32)
        out = llama.forward(params, tokens, CFG, lora=lora, lora_scale=lora_conf.scale)
        assert out.shape == (1, 8, CFG.vocab_size)


class TestLoRATraining:
    def test_loss_decreases_and_base_frozen(self):
        mesh = make_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
        opt = default_optimizer(lr=5e-2, warmup=1, decay_steps=100)
        params, state, _ = sharded_lora_init(CFG, LORA, opt, mesh, seed=0)
        base_wq = np.asarray(jax.device_get(params["layers"]["wq"]))
        step = make_lora_train_step(CFG, LORA, opt, mesh)
        batch = _batch(jax.random.key(3))
        losses = []
        for _ in range(20):
            state, metrics = step(params, state, batch)
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0] * 0.95, losses
        # base params are untouched by LoRA training
        np.testing.assert_array_equal(
            base_wq, np.asarray(jax.device_get(params["layers"]["wq"]))
        )
        assert int(jax.device_get(state["step"])) == 20

    def test_adapters_sharded(self):
        mesh = make_mesh(MeshConfig(dp=1, fsdp=2, tp=4))
        opt = default_optimizer()
        _, state, _ = sharded_lora_init(CFG, LORA, opt, mesh, seed=0)
        a = state["lora"]["layers"]["wq_lora_a"]
        # A: [L, hidden(fsdp), r] — hidden dim sharded over fsdp
        assert a.addressable_shards[0].data.shape[1] == a.shape[1] // 2
        b = state["lora"]["layers"]["wq_lora_b"]
        # B: [L, r, q_dim(tp)] — out dim sharded over tp
        assert b.addressable_shards[0].data.shape[2] == b.shape[2] // 4

    def test_optimizer_state_only_for_adapters(self):
        """The HBM win: opt state leaf count matches the adapter tree,
        not the base param tree."""
        mesh = make_mesh(MeshConfig(dp=1, fsdp=1, tp=1), devices=jax.devices()[:1])
        opt = default_optimizer()
        _, state, _ = sharded_lora_init(CFG, LORA, opt, mesh, seed=0)
        lora_leaves = len(jax.tree.leaves(state["lora"]))
        n_base = len(jax.tree.leaves(llama.abstract_params(CFG)))
        adam_m_leaves = [
            leaf
            for leaf in jax.tree.leaves(state["opt_state"])
            if hasattr(leaf, "ndim") and leaf.ndim == 3
        ]
        assert lora_leaves == 8  # 4 target modules × (A, B)
        assert len(adam_m_leaves) < n_base * 2

    def test_spec_tree_matches(self):
        lora = init_lora_params(CFG, LORA, jax.random.key(0))
        specs = lora_param_specs(LORA)
        assert jax.tree.structure(
            jax.tree.map(lambda x: 0, lora)
        ) == jax.tree.structure(
            jax.tree.map(lambda x: 0, specs, is_leaf=lambda x: isinstance(x, tuple))
        )


class TestLoRAGradAccum:
    def test_accumulated_matches_full_batch(self):
        import optax

        from dstack_tpu.models import llama
        from dstack_tpu.parallel.mesh import MeshConfig, make_mesh
        from dstack_tpu.train.lora import (
            LoRAConfig,
            make_lora_train_step,
            sharded_lora_init,
        )

        config = llama.LLAMA_TINY
        mesh = make_mesh(MeshConfig(dp=1, fsdp=1, tp=1), devices=jax.devices()[:1])
        lc = LoRAConfig(rank=4, alpha=8.0)
        opt = optax.sgd(1e-2)
        tokens = jax.random.randint(jax.random.key(0), (4, 64), 0, config.vocab_size)
        batch = {
            "tokens": tokens,
            "targets": jnp.roll(tokens, -1, axis=1),
            "mask": jnp.ones_like(tokens),
        }
        p1, s1, _ = sharded_lora_init(config, lc, opt, mesh, seed=0)
        p2, s2, _ = sharded_lora_init(config, lc, opt, mesh, seed=0)
        full = make_lora_train_step(config, lc, opt, mesh)
        accum = make_lora_train_step(config, lc, opt, mesh, grad_accum=2)
        s1, m1 = full(p1, s1, batch)
        s2, m2 = accum(p2, s2, batch)
        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
        for a, b in zip(jax.tree.leaves(s1["lora"]), jax.tree.leaves(s2["lora"])):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=2e-3, atol=2e-6,
            )
