"""``LlamaConfig.sublayers`` / ``zero_experts`` on the training and
parity path (``llama.forward``): the tree, its logical axes, the
parameter count, what a configuration may combine, and gradients
through the expert branch that is read after the first attention and
added after the last dense FFN."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dstack_tpu.models import llama
from tests.shared import init_params

TINY = llama.CONFIGS["scmoe-tiny"]


def test_tree_axes_and_count():
    c = TINY
    params = init_params(c, 0)
    L, H = c.n_layers, c.hidden_size
    layers = params["layers"]
    assert {k for k in layers if k.startswith("sub")} == {"sub0", "sub1"}
    for sub in (layers["sub0"], layers["sub1"]):
        assert sub["attn_norm"].shape == sub["mlp_norm"].shape == (L, H)
        assert sub["wq_a"].shape == (L, H, c.q_lora_rank)
        assert sub["w_down"].shape == (L, c.dense_intermediate, H)  # its dense FFN
    # the router is as wide as the real and the identity experts together
    assert layers["w_router"].shape == (L, H, c.n_experts + c.zero_experts)
    assert layers["router_bias"].shape == (L, c.n_experts + c.zero_experts)
    assert layers["w_gate"].shape == (L, c.n_experts, H, c.intermediate_size)
    leaves = jax.tree.leaves(params)
    assert sum(a.size for a in leaves) == c.num_params()
    # the logical axes name every leaf, and no other
    specs = llama.param_specs(c)
    is_spec = lambda s: isinstance(s, tuple)
    assert jax.tree.structure(specs, is_leaf=is_spec) == jax.tree.structure(params)
    for spec, leaf in zip(jax.tree.leaves(specs, is_leaf=is_spec), leaves):
        assert len(spec) == leaf.ndim
    assert jax.tree.map(lambda a: (a.shape, a.dtype), llama.abstract_params(c)) == jax.tree.map(
        lambda a: (a.shape, a.dtype), params
    )
    # two sublayers' rows do not share a draw
    assert not np.array_equal(np.asarray(layers["sub0"]["wq_a"]), np.asarray(layers["sub1"]["wq_a"]))


@pytest.mark.parametrize("change", [
    dict(q_lora_rank=0, kv_lora_rank=0),  # no latent attention
    dict(dense_intermediate=0),
    dict(first_k_dense=1),
    dict(post_norms=True),
    dict(n_experts=0, zero_experts=0),
])
def test_what_sublayers_may_not_combine_with(change):
    with pytest.raises(ValueError):
        dataclasses.replace(TINY, **change)


def test_identity_experts_need_a_router():
    with pytest.raises(ValueError):
        dataclasses.replace(llama.LLAMA_TINY, zero_experts=4)


def test_gradients_reach_the_branch_and_both_sublayers():
    """The loss moves with the router, the experts and each sublayer's
    attention and dense FFN; an identity expert has nothing to train."""
    c = TINY
    params = init_params(c, 1)
    tokens = jax.random.randint(jax.random.key(2), (2, 16), 1, c.vocab_size)

    def loss(p):
        logits, aux = llama.forward(p, tokens, c, return_aux=True)
        return jnp.mean(jax.nn.logsumexp(logits, -1) - logits[..., 0]) + 0.0 * aux

    grads = jax.grad(loss)(params)["layers"]
    norm = lambda a: float(jnp.abs(a).sum())
    for leaf in ("w_router", "w_gate", "w_up", "w_down"):
        assert all(norm(grads[leaf][l]) > 0 for l in range(c.n_layers)), leaf
    for leaf in ("wq_a", "wkv_b", "wo", "w_gate", "w_down", "mlp_norm", "attn_norm"):
        for l in range(c.n_layers):
            for i in range(c.sublayers):
                assert norm(grads[f"sub{i}"][leaf][l]) > 0, (leaf, l, i)
    assert all(np.isfinite(np.asarray(g)).all() for g in jax.tree.leaves(grads))
