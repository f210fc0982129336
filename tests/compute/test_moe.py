"""MoE correctness: the capacity-bounded einsum dispatch (models/moe.py)
must agree with a dense run-every-expert reference when capacity is
ample, shard correctly over the ep axis, and train end-to-end."""

import jax
import jax.numpy as jnp
import numpy as np

from dstack_tpu.models import llama, moe
from dstack_tpu.parallel.mesh import MeshConfig, make_mesh
from dstack_tpu.parallel.sharding import default_rules
from dstack_tpu.train.step import default_optimizer, make_train_step, sharded_init
from tests.shared import init_params


def _moe_layer(key, h=16, f=32, e=4):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "w_router": jax.random.normal(k1, (h, e)) * 0.1,
        "w_gate": jax.random.normal(k2, (e, h, f)) * 0.1,
        "w_up": jax.random.normal(k3, (e, h, f)) * 0.1,
        "w_down": jax.random.normal(k4, (e, f, h)) * 0.1,
    }


class TestDispatch:
    def test_matches_dense_reference(self):
        """With capacity ≥ T no token is dropped, so the sparse dispatch
        must equal the dense weighted-mixture reference exactly."""
        layer = _moe_layer(jax.random.key(0))
        x = jax.random.normal(jax.random.key(1), (2, 8, 16))
        out, aux = moe.moe_mlp(
            x, layer, n_experts=4, experts_per_token=2, capacity_factor=4.0,
            mesh=None, rules=None,
        )
        ref = moe.moe_mlp_reference(x, layer, n_experts=4, experts_per_token=2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-5)
        assert np.isfinite(float(aux["balance"])) and float(aux["balance"]) >= 1.0 - 1e-5
        assert np.isfinite(float(aux["z"]))

    def test_capacity_drops_tokens(self):
        """Tiny capacity: dropped tokens contribute zero (residual carries
        them), so outputs differ from the dense reference but stay finite."""
        layer = _moe_layer(jax.random.key(0))
        x = jax.random.normal(jax.random.key(1), (1, 64, 16))
        out, _ = moe.moe_mlp(
            x, layer, n_experts=4, experts_per_token=2, capacity_factor=0.25,
            mesh=None, rules=None,
        )
        assert np.all(np.isfinite(np.asarray(out)))
        # some row must be exactly zero (a fully-dropped token)
        norms = np.linalg.norm(np.asarray(out[0]), axis=-1)
        assert (norms == 0).any()

    def test_unique_capacity_slots(self):
        """No two (token, choice) assignments may share an expert slot —
        the regression the cumsum offset guards against."""
        layer = _moe_layer(jax.random.key(2))
        x = jax.random.normal(jax.random.key(3), (1, 16, 16))
        cap = moe.expert_capacity(16, 4, 2, 4.0)
        dispatch, _, _ = moe.router(x, layer["w_router"], 4, 2, cap)
        # each (expert, slot) bucket holds at most one token
        per_slot = np.asarray(dispatch).sum(axis=1)  # [B, E, C]
        assert per_slot.max() <= 1.0 + 1e-6


class TestShardedMoE:
    def test_ep_sharded_matches_local(self):
        """ep=4 mesh: the all_to_all dispatch must be numerically
        identical to the unsharded path."""
        mesh = make_mesh(MeshConfig(dp=2, fsdp=1, ep=4, tp=1))
        rules = default_rules()
        layer = _moe_layer(jax.random.key(0))
        x = jax.random.normal(jax.random.key(1), (4, 16, 16))

        ref, _ = moe.moe_mlp(
            x, layer, n_experts=4, experts_per_token=2, capacity_factor=2.0,
            mesh=None, rules=None,
        )
        out, _ = jax.jit(
            lambda x, l: moe.moe_mlp(
                x, l, n_experts=4, experts_per_token=2, capacity_factor=2.0,
                mesh=mesh, rules=rules,
            )
        )(x, layer)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-5)


class TestMoELlama:
    def test_forward_and_aux(self):
        config = llama.MOE_TINY
        params = init_params(config, 0)
        tokens = jax.random.randint(jax.random.key(1), (2, 32), 0, config.vocab_size)
        logits, aux = llama.forward(params, tokens, config, return_aux=True)
        assert logits.shape == (2, 32, config.vocab_size)
        assert np.isfinite(np.asarray(logits)).all()
        assert float(aux) > 0  # router losses are live

    def test_train_step_moe_ep(self):
        """MoE train step on an ep=2 × fsdp=2 × dp=2 mesh: loss decreases,
        expert weights are ep-sharded."""
        mesh = make_mesh(MeshConfig(dp=2, fsdp=2, ep=2, tp=1))
        config = llama.MOE_TINY
        opt = default_optimizer(lr=1e-3)
        state, shardings = sharded_init(config, opt, mesh, seed=0)
        assert "ep" in str(shardings["params"]["layers"]["w_gate"].spec)
        step = make_train_step(config, opt, mesh)
        tokens = jax.random.randint(jax.random.key(1), (8, 32), 0, config.vocab_size)
        batch = {
            "tokens": tokens,
            "targets": jnp.roll(tokens, -1, axis=1),
            "mask": jnp.ones_like(tokens),
        }
        losses = []
        for _ in range(3):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
            assert np.isfinite(float(metrics["aux_loss"]))
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]

    def test_moe_pp_compose(self):
        """MoE layers inside the pipeline: pp=2 × ep=2 train step runs
        and the aux loss survives the bubble masking."""
        mesh = make_mesh(MeshConfig(dp=1, pp=2, fsdp=2, ep=2, tp=1))
        config = llama.MOE_TINY
        opt = default_optimizer(lr=1e-3)
        state, _ = sharded_init(config, opt, mesh, seed=0)
        step = make_train_step(config, opt, mesh, n_micro=2)
        tokens = jax.random.randint(jax.random.key(1), (4, 32), 0, config.vocab_size)
        batch = {
            "tokens": tokens,
            "targets": jnp.roll(tokens, -1, axis=1),
            "mask": jnp.ones_like(tokens),
        }
        state, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"]))
        assert float(metrics["aux_loss"]) > 0
