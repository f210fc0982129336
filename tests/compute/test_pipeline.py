"""Pipeline parallelism correctness on the 8-virtual-device CPU mesh:
the GPipe loop (parallel/pipeline.py) must be numerically identical to
the sequential layer stack, forward and backward, and compose with
fsdp/tp auto axes and the full train step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dstack_tpu.models import llama
from dstack_tpu.parallel.mesh import MeshConfig, make_mesh
from dstack_tpu.parallel.pipeline import (
    merge_stages,
    microbatch,
    pipeline_apply,
    split_stages,
    unmicrobatch,
)
from dstack_tpu.train.step import default_optimizer, make_train_step, sharded_init
from tests.shared import init_params


def _simple_stack(key, n_layers=4, h=16):
    return {"w": jax.random.normal(key, (n_layers, h, h)) * 0.1}


def _seq_apply(params, x):
    def body(x, layer):
        return jnp.tanh(x @ layer["w"]), None

    y, _ = jax.lax.scan(body, x, params)
    return y


def _stage_fn(stage_params, x, extras):
    def body(x, layer):
        return jnp.tanh(x @ layer["w"]), None

    y, _ = jax.lax.scan(body, x, stage_params)
    return y, jnp.zeros((), jnp.float32)


class TestPipelineApply:
    def test_matches_sequential(self):
        mesh = make_mesh(MeshConfig(pp=4, fsdp=2))
        params = _simple_stack(jax.random.key(0))
        x = jax.random.normal(jax.random.key(1), (8, 16))
        ref = _seq_apply(params, x)

        stage_params = split_stages(params, 4)
        x_mb = microbatch(x, 4)
        out_mb, aux = jax.jit(
            lambda sp, xm: pipeline_apply(_stage_fn, sp, xm, mesh=mesh)
        )(stage_params, x_mb)
        out = unmicrobatch(out_mb)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5)
        assert float(aux) == 0.0

    def test_grad_matches_sequential(self):
        mesh = make_mesh(MeshConfig(pp=4, fsdp=2))
        params = _simple_stack(jax.random.key(0))
        x = jax.random.normal(jax.random.key(1), (8, 16))

        def loss_seq(p):
            return jnp.sum(_seq_apply(p, x) ** 2)

        def loss_pipe(p):
            out_mb, _ = pipeline_apply(
                _stage_fn, split_stages(p, 4), microbatch(x, 4), mesh=mesh
            )
            return jnp.sum(unmicrobatch(out_mb) ** 2)

        g_ref = jax.grad(loss_seq)(params)
        g_pipe = jax.jit(jax.grad(loss_pipe))(params)
        np.testing.assert_allclose(
            np.asarray(g_pipe["w"]), np.asarray(g_ref["w"]), rtol=1e-4, atol=1e-6
        )

    def test_pp1_fallback(self):
        mesh = make_mesh(MeshConfig(pp=1, fsdp=1, tp=1))
        params = _simple_stack(jax.random.key(0))
        x = jax.random.normal(jax.random.key(1), (4, 16))
        out_mb, _ = pipeline_apply(
            _stage_fn, split_stages(params, 1), microbatch(x, 2), mesh=mesh
        )
        np.testing.assert_allclose(
            np.asarray(unmicrobatch(out_mb)),
            np.asarray(_seq_apply(params, x)),
            rtol=1e-5,
        )

    def test_split_merge_roundtrip(self):
        params = _simple_stack(jax.random.key(0))
        rt = merge_stages(split_stages(params, 2))
        np.testing.assert_array_equal(np.asarray(rt["w"]), np.asarray(params["w"]))

    def test_indivisible_raises(self):
        params = _simple_stack(jax.random.key(0), n_layers=3)
        with pytest.raises(ValueError):
            split_stages(params, 2)


class TestPipelinedLlama:
    def test_forward_matches(self):
        mesh = make_mesh(MeshConfig(pp=2, fsdp=2, tp=2))
        config = llama.LLAMA_TINY
        params = init_params(config, 0)
        tokens = jax.random.randint(jax.random.key(1), (4, 64), 0, config.vocab_size)
        ref = llama.forward(params, tokens, config)
        out = jax.jit(
            lambda p, t: llama.forward_pipelined(p, t, config, mesh=mesh, n_micro=2)
        )(params, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)

    def test_train_step_pp(self):
        """Full sharded train step on a pp=2 × fsdp=2 × tp=2 mesh; loss
        must decrease over a few steps, layers stage-sharded over pp."""
        mesh = make_mesh(MeshConfig(pp=2, fsdp=2, tp=2))
        config = llama.LLAMA_TINY
        opt = default_optimizer(lr=1e-3)
        state, shardings = sharded_init(config, opt, mesh, seed=0)
        # layer stacks are sharded over pp on the stacked dim
        assert "pp" in str(shardings["params"]["layers"]["wq"].spec)
        step = make_train_step(config, opt, mesh, n_micro=2)
        tokens = jax.random.randint(jax.random.key(1), (4, 64), 0, config.vocab_size)
        batch = {
            "tokens": tokens,
            "targets": jnp.roll(tokens, -1, axis=1),
            "mask": jnp.ones_like(tokens),
        }
        losses = []
        for _ in range(3):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]

    def test_train_step_pp_matches_dense(self):
        """The pp=2 train step and the plain 1-device-mesh train step
        must produce the same loss trajectory (same math, different
        schedule)."""
        config = llama.LLAMA_TINY
        opt = default_optimizer(lr=1e-3)
        tokens = jax.random.randint(jax.random.key(1), (4, 64), 0, config.vocab_size)
        batch = {
            "tokens": tokens,
            "targets": jnp.roll(tokens, -1, axis=1),
            "mask": jnp.ones_like(tokens),
        }

        def run(mesh, n_micro=None):
            state, _ = sharded_init(config, opt, mesh, seed=0)
            step = make_train_step(config, opt, mesh, n_micro=n_micro)
            out = []
            for _ in range(2):
                state, m = step(state, batch)
                out.append(float(m["loss"]))
            return out

        ref = run(make_mesh(MeshConfig(pp=1, fsdp=1, tp=1)))
        pp = run(make_mesh(MeshConfig(pp=2, fsdp=2, tp=2)), n_micro=2)
        np.testing.assert_allclose(pp, ref, rtol=1e-3)
