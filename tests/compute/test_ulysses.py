"""Ulysses (all-to-all) sequence parallelism vs dense references on the
8-virtual-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dstack_tpu.models import llama
from dstack_tpu.ops.attention import _xla_attention
from dstack_tpu.parallel.mesh import MeshConfig, make_mesh
from dstack_tpu.parallel.ulysses import ulysses_attention
from tests.shared import init_params

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs >= 4 virtual devices"
)


def _rand_qkv(key, b=1, h=4, hkv=4, t=64, d=16):
    k1, k2, k3 = jax.random.split(key, 3)
    return (
        jax.random.normal(k1, (b, h, t, d)),
        jax.random.normal(k2, (b, hkv, t, d)),
        jax.random.normal(k3, (b, hkv, t, d)),
    )


def _mesh(sp=4):
    return make_mesh(MeshConfig(dp=1, fsdp=1, sp=sp, tp=1))


class TestUlyssesAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, causal):
        mesh = _mesh()
        q, k, v = _rand_qkv(jax.random.key(0))
        ref = _xla_attention(q, k, v, causal=causal, scale=16**-0.5)
        out = ulysses_attention(q, k, v, mesh=mesh, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4
        )

    def test_gqa_kv_narrower_than_sp(self):
        """Hkv=2 < sp=4: KV expands to query width before the split."""
        mesh = _mesh()
        q, k, v = _rand_qkv(jax.random.key(1), h=8, hkv=2)
        ref = _xla_attention(q, k, v, causal=True, scale=16**-0.5)
        out = ulysses_attention(q, k, v, mesh=mesh, causal=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4
        )

    def test_gqa_kv_divisible_by_sp(self):
        """Hkv=4 == sp: KV stays at KV-head width through the a2a."""
        mesh = _mesh()
        q, k, v = _rand_qkv(jax.random.key(2), h=8, hkv=4)
        ref = _xla_attention(q, k, v, causal=True, scale=16**-0.5)
        out = ulysses_attention(q, k, v, mesh=mesh, causal=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4
        )

    def test_window_and_softcap(self):
        """Sliding window + softcap ride the local attention unchanged —
        the path the ring can't take through its pallas kernels."""
        mesh = _mesh()
        q, k, v = _rand_qkv(jax.random.key(3))
        ref = _xla_attention(
            q, k, v, causal=True, scale=16**-0.5, window=24, softcap=20.0
        )
        out = ulysses_attention(
            q, k, v, mesh=mesh, causal=True, window=24, softcap=20.0
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4
        )

    def test_grads_match_dense(self):
        mesh = _mesh()
        q, k, v = _rand_qkv(jax.random.key(4))

        def loss_u(q, k, v):
            return jnp.sum(ulysses_attention(q, k, v, mesh=mesh, causal=True) ** 2)

        def loss_d(q, k, v):
            return jnp.sum(_xla_attention(q, k, v, causal=True, scale=16**-0.5) ** 2)

        gu = jax.grad(loss_u, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss_d, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gu, gd):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
            )

    def test_heads_not_divisible_raises(self):
        mesh = _mesh()
        q, k, v = _rand_qkv(jax.random.key(5), h=6, hkv=6)
        with pytest.raises(ValueError, match="divisible"):
            ulysses_attention(q, k, v, mesh=mesh)


class TestUlyssesInModel:
    def test_forward_matches_ring_config(self):
        """Same model, sp=2 mesh: ulysses and ring configs agree with
        the single-device forward."""
        mesh = make_mesh(MeshConfig(dp=1, fsdp=2, sp=2, tp=2))
        config = llama.dataclasses.replace(llama.LLAMA_TINY, max_seq_len=128)
        params = init_params(config, 0)
        tokens = jax.random.randint(jax.random.key(1), (2, 128), 0, config.vocab_size)

        dense = llama.forward(params, tokens, config)
        ring = llama.forward(params, tokens, config, mesh=mesh)
        uly = llama.forward(
            params, tokens,
            llama.dataclasses.replace(config, seq_parallel="ulysses"),
            mesh=mesh,
        )
        np.testing.assert_allclose(
            np.asarray(ring), np.asarray(dense), rtol=2e-3, atol=2e-3
        )
        np.testing.assert_allclose(
            np.asarray(uly), np.asarray(dense), rtol=2e-3, atol=2e-3
        )

    def test_train_step_with_ulysses(self):
        """One optimization step end-to-end on an sp mesh."""
        from dstack_tpu.train.step import (
            default_optimizer,
            make_train_step,
            sharded_init,
        )

        mesh = make_mesh(MeshConfig(dp=1, fsdp=2, sp=2, tp=2))
        config = llama.dataclasses.replace(
            llama.LLAMA_TINY, max_seq_len=128, seq_parallel="ulysses"
        )
        opt = default_optimizer(lr=1e-2, warmup=1)
        state, _ = sharded_init(config, opt, mesh)
        step = make_train_step(config, opt, mesh)
        tokens = jax.random.randint(jax.random.key(2), (2, 128), 0, config.vocab_size)
        batch = {
            "tokens": tokens,
            "targets": jnp.roll(tokens, -1, axis=1),
            "mask": jnp.ones_like(tokens),
        }
        losses = []
        # the metric reports the PRE-update loss and warmup lr at step 0
        # is 0, so movement shows from the third step
        for _ in range(3):
            state, metrics = step(state, batch)
            losses.append(float(jax.device_get(metrics["loss"])))
        assert all(np.isfinite(l) for l in losses)
        assert losses[2] < losses[0]
