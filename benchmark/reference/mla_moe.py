"""Plain reference for a DeepSeek-V2 style decoder (DeepSeek-V2-Lite).

Written from the published description (HF ``deepseek_v2`` modeling):
multi-head latent attention *without* weight absorption (full keys and
values are rebuilt from the 512-wide latent every time), rotary
embedding in interleaved complex pairs on the 64-wide rope slices with
YaRN frequencies, a dense SwiGLU first layer, then expert layers:
softmax router over all experts, greedy top-k, gates left unnormalised,
**dropless** (every token reaches each of its experts), plus the shared
experts as one always-on SwiGLU. One sequence at a time, layer by
layer, float32; the experts are visited one after the other.

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
    draw. ``c`` is the configuration file's ``llama_config`` group. The
    expert layers are one stacked group, the dense layers before them
    (``first_k_dense``) another."""
    H, V = c["hidden_size"], c["vocab_size"]
    n_layers, k_dense = c["n_layers"], c.get("first_k_dense", 0)
    L = n_layers - k_dense
    down = C.STD / math.sqrt(2 * n_layers)

    def norm(*lead):
        return (lead + (H,), None)

    def attn(n):
        r, rope = c["kv_lora_rank"], c["qk_rope_head_dim"]
        nope, vd, nh = c["qk_nope_head_dim"], c["v_head_dim"], c["n_heads"]
        return {
            "wq": ((n, H, nh * (nope + rope)), C.STD),
            "wkv_a": ((n, H, r + rope), C.STD),
            "kv_a_norm": ((n, r), None),
            "wkv_b": ((n, r, nh * (nope + vd)), C.STD),
            "wo": ((n, nh * vd, H), down),
        }

    F, E = c["intermediate_size"], c["n_experts"]
    FS = c.get("moe_shared_intermediate") or F
    tree = {
        "embed": ((V, H), C.STD),
        "layers": {
            "attn_norm": norm(L), "mlp_norm": norm(L), **attn(L),
            "w_router": ((L, H, E), C.STD),
            "w_gate": ((L, E, H, F), C.STD),
            "w_up": ((L, E, H, F), C.STD),
            "w_down": ((L, E, F, H), down),
            "w_shared_gate": ((L, H, FS), C.STD),
            "w_shared_up": ((L, H, FS), C.STD),
            "w_shared_down": ((L, FS, H), down),
        },
        "final_norm": norm(),
        "lm_head": ((H, V), C.STD),
    }
    if k_dense:
        FD = c.get("dense_intermediate") or F
        tree["dense_layers"] = {
            "attn_norm": norm(k_dense), "mlp_norm": norm(k_dense),
            **attn(k_dense),
            "w_gate": ((k_dense, H, FD), C.STD),
            "w_up": ((k_dense, H, FD), C.STD),
            "w_down": ((k_dense, FD, H), down),
        }
    return tree


def yarn_inv_freq(cfg):
    dim, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    inv = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    rs = cfg.get("rope_scaling")
    if not rs:
        return inv, 1.0
    factor, orig = rs["factor"], rs["original_max_position_embeddings"]

    def corr_dim(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    inv = inv / factor * ramp + inv * (1 - ramp)

    def mscale(scale, m):
        return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0

    att = mscale(factor, rs.get("mscale", 1)) / mscale(factor, rs.get("mscale_all_dim", 0))
    return inv, att


def rope_tables(cfg, t):
    inv, att = yarn_inv_freq(cfg)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang) * att, jnp.sin(ang) * att


def rotate_pairs(x, cos, sin):
    """x [T, H, D]: dims (2i, 2i+1) rotate as one complex number."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).reshape(x.shape)


def attention(x, p, cos, sin, cfg, precision):
    t = x.shape[0]
    nh, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    q = C.matmul(x, p["wq"], precision).reshape(t, nh, nope + rope)
    kv_a = C.matmul(x, p["wkv_a"], precision)
    latent = C.rms_norm(kv_a[:, :r], p["kv_a_norm"], cfg["rms_norm_eps"])
    k_pe = rotate_pairs(kv_a[:, None, r:], cos, sin)  # one shared head
    kv = C.matmul(latent, p["wkv_b"], precision).reshape(t, nh, nope + vd)
    q = jnp.concatenate([q[..., :nope], rotate_pairs(q[..., nope:], cos, sin)], -1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe, (t, nh, rope))], -1)
    a = C.causal_attention(q, k, kv[..., nope:], (nope + rope) ** -0.5)
    return C.matmul(a.reshape(t, nh * vd), p["wo"], precision)


def swiglu(h, gate, up, down, precision):
    g = jax.nn.silu(C.matmul(h, gate, precision)) * C.matmul(h, up, precision)
    return C.matmul(g, down, precision)


def moe(h, p, cfg, precision):
    probs = jax.nn.softmax(C.matmul(h, p["w_router"], precision), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob"):
        top_p = top_p / top_p.sum(-1, keepdims=True)
    top_p = top_p * cfg.get("routed_scaling_factor", 1)
    # gate of expert e for token t (0 where e is not among its top-k)
    gates = jnp.zeros_like(probs).at[jnp.arange(h.shape[0])[:, None], top_i].add(top_p)

    def one(acc, ew):
        wg, wu, wd, g = ew
        return acc + g[:, None] * swiglu(h, wg, wu, wd, precision), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(h), (p["w_gate"], p["w_up"], p["w_down"], gates.T)
    )
    shared = swiglu(h, p["w_shared_gate"], p["w_shared_up"], p["w_shared_down"], precision)
    return out + shared


@partial(jax.jit, static_argnames=("cfg_key", "dense", "precision"))
def _layer(x, p, cos, sin, *, cfg_key, dense, precision):
    cfg = dict(cfg_key)
    x = x + attention(
        C.rms_norm(x, p["attn_norm"], cfg["rms_norm_eps"]), p, cos, sin, cfg, precision
    )
    h = C.rms_norm(x, p["mlp_norm"], cfg["rms_norm_eps"])
    if dense:
        return x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"], precision)
    return x + moe(h, p, cfg, precision)


_KEYS = (
    "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "rms_norm_eps", "num_experts_per_tok", "norm_topk_prob",
    "routed_scaling_factor",
)


def hidden_states(cfg, params, tokens, precision="f32"):
    """tokens [T] → final-layer residual stream [T, H] (float32)."""
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        x = params["embed"][tokens].astype(jnp.float32)
        cos, sin = rope_tables(cfg, tokens.shape[0])
        key = tuple((k, cfg[k]) for k in _KEYS if k in cfg)
        k_dense = cfg.get("first_k_dense_replace", 0)
        for i in range(cfg["num_hidden_layers"]):
            dense = i < k_dense
            stack = params["dense_layers"] if dense else params["layers"]
            x = _layer(
                x, C.layer_slice(stack, i if dense else i - k_dense), cos, sin,
                cfg_key=key, dense=dense, precision=precision,
            )
        return x


def final_norm(cfg, params):
    return lambda h: C.rms_norm(h, params["final_norm"], cfg["rms_norm_eps"])


def head(cfg, params, hidden, ids, precision="f32"):
    with jax.default_matmul_precision("highest"):
        return C.head_stats(hidden, final_norm(cfg, params), params["lm_head"], ids, precision)
