"""Plain reference for a dense Nemotron-style decoder (Minitron-4B).

Written from the published description (HF ``nemotron`` modeling):
LayerNorm1P, attention with grouped KV heads and rotary embedding over
the first ``partial_rotary_factor`` of each head (rotate-half within
that slice), an ungated MLP ``down(relu(up(x))**2)``, untied output
head. One sequence at a time, layer by layer, float32.

``cfg`` is the configuration file (HF keys); ``params`` the weight tree
that ``leaf_shapes`` states and ``benchmark/weights.py`` draws.
"""

import math
from functools import partial

import jax
import jax.numpy as jnp

from . import common as C


def leaf_shapes(c: dict) -> dict:
    """The weight tree this architecture reads, as the program's
    ``init_params`` lays it out: ``{path: (shape, scale)}``, nested;
    scale None = a norm leaf (identity init), else the std of the normal
    draw. ``c`` is the configuration file's ``llama_config`` group."""
    H, V, L = c["hidden_size"], c["vocab_size"], c["n_layers"]
    F = c["intermediate_size"]
    q = c["n_heads"] * c["head_dim"]
    kv = c["n_kv_heads"] * c["head_dim"]
    down = C.STD / math.sqrt(2 * L)

    def norm(*lead):  # LayerNorm1P: (scale-1, bias)
        return (lead + (2, H), None)

    return {
        "embed": ((V, H), C.STD),
        "layers": {
            "attn_norm": norm(L), "mlp_norm": norm(L),
            "wq": ((L, H, q), C.STD), "wk": ((L, H, kv), C.STD),
            "wv": ((L, H, kv), C.STD), "wo": ((L, q, H), down),
            "w_up": ((L, H, F), C.STD), "w_down": ((L, F, H), down),
        },
        "final_norm": norm(),
        "lm_head": ((H, V), C.STD),
    }


def rope_tables(cfg, t):
    rot = int(cfg["head_dim"] * cfg.get("partial_rotary_factor", 1.0))
    inv = 1.0 / (cfg["rope_theta"] ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)  # [T, rot/2]


def rotate_half(x, cos, sin):
    """x [T, H, D]; rotates the leading ``2*cos.shape[-1]`` dims."""
    rd = 2 * cos.shape[-1]
    xr, rest = x[..., :rd], x[..., rd:]
    x1, x2 = xr[..., : rd // 2], xr[..., rd // 2:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], axis=-1)


@partial(jax.jit, static_argnames=("nh", "nkv", "hd", "eps", "precision"))
def layer(x, p, cos, sin, *, nh, nkv, hd, eps, precision):
    t = x.shape[0]
    h = C.layer_norm_1p(x, p["attn_norm"], eps)
    q = C.matmul(h, p["wq"], precision).reshape(t, nh, hd)
    k = C.matmul(h, p["wk"], precision).reshape(t, nkv, hd)
    v = C.matmul(h, p["wv"], precision).reshape(t, nkv, hd)
    q, k = rotate_half(q, cos, sin), rotate_half(k, cos, sin)
    k = jnp.repeat(k, nh // nkv, axis=1)
    v = jnp.repeat(v, nh // nkv, axis=1)
    a = C.causal_attention(q, k, v, hd ** -0.5).reshape(t, nh * hd)
    x = x + C.matmul(a, p["wo"], precision)
    h = C.layer_norm_1p(x, p["mlp_norm"], eps)
    up = jnp.square(jax.nn.relu(C.matmul(h, p["w_up"], precision)))
    return x + C.matmul(up, p["w_down"], precision)


def hidden_states(cfg, params, tokens, precision="f32"):
    """tokens [T] → final-layer residual stream [T, H] (float32)."""
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        x = params["embed"][tokens].astype(jnp.float32)
        cos, sin = rope_tables(cfg, tokens.shape[0])
        for i in range(cfg["num_hidden_layers"]):
            x = layer(
                x, C.layer_slice(params["layers"], i), cos, sin,
                nh=cfg["num_attention_heads"], nkv=cfg["num_key_value_heads"],
                hd=cfg["head_dim"], eps=cfg["norm_eps"], precision=precision,
            )
        return x


def final_norm(cfg, params):
    return lambda h: C.layer_norm_1p(h, params["final_norm"], cfg["norm_eps"])


def head(cfg, params, hidden, ids, precision="f32"):
    with jax.default_matmul_precision("highest"):
        return C.head_stats(hidden, final_norm(cfg, params), params["lm_head"], ids, precision)
