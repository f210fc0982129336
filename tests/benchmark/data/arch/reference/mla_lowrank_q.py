"""Plain reference for latent attention with a low-rank query
(DeepSeek-V3's ``q_lora_rank`` > 0) over ``mla_moe``'s experts: a test
fixture, the architecture that ``tests/benchmark/test_new_architecture.py``
adds to a copy of the benchmark as new files only.

It is ``mla_moe`` with the query projection replaced,
``q = RMSNorm(x @ wq_a) @ wq_b``: the rotary tables, the rebuilt keys
and values, the experts and the head are that module's own.
"""

from functools import partial

import jax
import jax.numpy as jnp

from . import common as C
from . import mla_moe as M


def leaf_shapes(c: dict) -> dict:
    """``mla_moe``'s tree with ``wq`` replaced by the three leaves of the
    low-rank query, in every group of layers."""
    tree = M.leaf_shapes(c)
    H, qr = c["hidden_size"], c["q_lora_rank"]
    for group in ("layers", "dense_layers"):
        if group in tree:
            (n, _, q), std = tree[group].pop("wq")
            tree[group].update({
                "wq_a": ((n, H, qr), std),
                "q_a_norm": ((n, qr), None),
                "wq_b": ((n, qr, q), std),
            })
    return tree


def attention(x, p, cos, sin, cfg, precision):
    t = x.shape[0]
    nh, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    q_a = C.rms_norm(C.matmul(x, p["wq_a"], precision), p["q_a_norm"], cfg["rms_norm_eps"])
    q = C.matmul(q_a, p["wq_b"], precision).reshape(t, nh, nope + rope)
    kv_a = C.matmul(x, p["wkv_a"], precision)
    latent = C.rms_norm(kv_a[:, :r], p["kv_a_norm"], cfg["rms_norm_eps"])
    k_pe = M.rotate_pairs(kv_a[:, None, r:], cos, sin)
    kv = C.matmul(latent, p["wkv_b"], precision).reshape(t, nh, nope + vd)
    q = jnp.concatenate([q[..., :nope], M.rotate_pairs(q[..., nope:], cos, sin)], -1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe, (t, nh, rope))], -1)
    a = C.causal_attention(q, k, kv[..., nope:], (nope + rope) ** -0.5)
    return C.matmul(a.reshape(t, nh * vd), p["wo"], precision)


@partial(jax.jit, static_argnames=("cfg_key", "dense", "precision"))
def _layer(x, p, cos, sin, *, cfg_key, dense, precision):
    cfg = dict(cfg_key)
    x = x + attention(
        C.rms_norm(x, p["attn_norm"], cfg["rms_norm_eps"]), p, cos, sin, cfg, precision
    )
    h = C.rms_norm(x, p["mlp_norm"], cfg["rms_norm_eps"])
    if dense:
        return x + M.swiglu(h, p["w_gate"], p["w_up"], p["w_down"], precision)
    return x + M.moe(h, p, cfg, precision)


def hidden_states(cfg, params, tokens, precision="f32"):
    """tokens [T] → final-layer residual stream [T, H] (float32)."""
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        x = params["embed"][tokens].astype(jnp.float32)
        cos, sin = M.rope_tables(cfg, tokens.shape[0])
        key = tuple((k, cfg[k]) for k in M._KEYS if k in cfg)
        k_dense = cfg.get("first_k_dense_replace", 0)
        for i in range(cfg["num_hidden_layers"]):
            dense = i < k_dense
            stack = params["dense_layers"] if dense else params["layers"]
            x = _layer(
                x, C.layer_slice(stack, i if dense else i - k_dense), cos, sin,
                cfg_key=key, dense=dense, precision=precision,
            )
        return x


final_norm = M.final_norm
head = M.head
