"""Plain reference for a decoder of linear-attention layers (KDA: a gated
delta rule with a decay a channel behind a causal convolution) beside
latent-attention layers, with group-limited sigmoid routing over a
chip's share of the experts (the family of ``Ling-3.0-flash``;
arXiv:2510.26692 for the mixer).

Written from the published ``config.json`` and the papers' equations;
every reading that is not a key's plain meaning is listed under
``assumed`` in the configuration file. ``cfg["layer_types"]`` says which
layers are ``linear_attention`` and which ``full_attention``.

*Linear layer*, with ``h = RMSNorm(x)`` and P = heads x ``head_dim``:

    pre = h Wqkv                                   [T, 3P]
    y_t = sum_j conv[j] * pre_{t-K+1+j}            K shifted adds, causal
    q, k, v = split(silu(y));  q = l2(q) / sqrt(D);  k = l2(k)
    g = kda_lower_bound * sigmoid(exp(A_log) * (h Wg + dt_bias))
    beta = sigmoid(h Wb)                           one a head
    S' = Diag(exp(g_t)) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t                                S_0 = 0, a scan over tokens
    out = (RMSNorm_head(o_t) * sigmoid(h Wog)) Wo

*Latent layer*: DeepSeek-V2-Lite's MLA without absorption (``mla_moe``'s,
no query rank), rope in interleaved pairs at ``rope_theta``, plus a
head-wise sigmoid gate ``sigmoid(h W_og)`` on each head's output before
``wo``.

*FFN*: the first ``first_k_dense_replace`` layers a dense SwiGLU; after
them ``s = sigmoid(h W_r)``; selection on ``s + b``: ``n_group`` groups,
a group's score the sum of its two best, the ``topk_group`` best groups
eligible, the ``num_experts_per_tok`` best among them; gates ``s_e / sum
s`` times ``routed_scaling_factor``; the sum over the picked experts
HELD here (``experts_held``: first, count) plus one shared expert.
Dropless; nothing stands in for the other chips' experts.

One sequence at a time, layer by layer, float32 at ``highest``; queries
go by in blocks so that a few thousand tokens fit beside the weights.
"""

import math
from functools import partial

import jax
import jax.numpy as jnp

from . import common as C
from .mla_moe import rotate_pairs, swiglu


def _kinds(c: dict) -> list:
    return list(c["layer_types"])


def leaf_shapes(c: dict) -> dict:
    """The weight tree, as the program's ``init_params`` lays it out
    (``c``: the file's ``llama_config``): ``dense_layers`` (the prelude:
    a mixer and a dense FFN), ``linear_layers`` and ``layers`` (latent
    attention), each an expert layer's leaves under its mixer's. The
    selection bias and the gate's ``A_log`` / ``dt_bias`` are drawn like
    weights (std 0.02), the convolution at ``K**-0.5``."""
    H, V, n_layers, k_dense = c["hidden_size"], c["vocab_size"], c["n_layers"], c["first_k_dense"]
    kinds = _kinds(c)
    nh, d = c["n_heads"], c["linear_head_dim"]
    P, K = nh * d, c.get("linear_conv", 4)
    r, rope = c["kv_lora_rank"], c["qk_rope_head_dim"]
    nope, vd = c["qk_nope_head_dim"], c["v_head_dim"]
    F, E = c["intermediate_size"], c["n_experts"]
    EH = c["experts_held"][1] if c.get("experts_held") else E
    FS, FD = c["moe_shared_intermediate"], c["dense_intermediate"]
    down = C.STD / math.sqrt(2 * n_layers)

    def linear(n):
        return {
            "attn_norm": ((n, H), None),
            "lin_wqkv": ((n, H, 3 * P), C.STD),
            "lin_conv": ((n, K, 3 * P), K**-0.5),
            "lin_wg": ((n, H, P), C.STD),
            "lin_a_log": ((n, nh), C.STD), "lin_dt_bias": ((n, P), C.STD),
            "lin_wb": ((n, H, nh), C.STD), "lin_wog": ((n, H, P), C.STD),
            "lin_norm": ((n, d), None), "wo": ((n, P, H), down),
        }

    def latent(n):
        return {
            "attn_norm": ((n, H), None),
            "wq": ((n, H, nh * (nope + rope)), C.STD),
            "wkv_a": ((n, H, r + rope), C.STD), "kv_a_norm": ((n, r), None),
            "wkv_b": ((n, r, nh * (nope + vd)), C.STD),
            "w_og": ((n, H, nh), C.STD), "wo": ((n, nh * vd, H), down),
        }

    def experts(n):
        return {
            "mlp_norm": ((n, H), None), "w_router": ((n, H, E), C.STD),
            "router_bias": ((n, E), C.STD),
            "w_gate": ((n, EH, H, F), C.STD), "w_up": ((n, EH, H, F), C.STD),
            "w_down": ((n, EH, F, H), down),
            "w_shared_gate": ((n, H, FS), C.STD), "w_shared_up": ((n, H, FS), C.STD),
            "w_shared_down": ((n, FS, H), down),
        }

    assert set(kinds[:k_dense]) == {"linear"}, "the prelude is linear layers"
    n_lin = kinds[k_dense:].count("linear")
    return {
        "embed": ((V, H), C.STD), "final_norm": ((H,), None),
        "lm_head": ((H, V), C.STD),
        "dense_layers": {
            **linear(k_dense), "mlp_norm": ((k_dense, H), None),
            "w_gate": ((k_dense, H, FD), C.STD), "w_up": ((k_dense, H, FD), C.STD),
            "w_down": ((k_dense, FD, H), down),
        },
        "linear_layers": {**linear(n_lin), **experts(n_lin)},
        "layers": {
            **latent(n_layers - k_dense - n_lin), **experts(n_layers - k_dense - n_lin)
        },
    }


def rope_tables(dim, theta, t):
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def linear_mixer(x, p, cfg, precision):
    """``x [T, H]`` (normed) → one linear layer's mixer output [T, H]."""
    t = x.shape[0]
    nh, d = cfg["num_attention_heads"], cfg["head_dim"]
    P, K = nh * d, cfg["short_conv_kernel_size"]
    f32 = jnp.float32
    pre = C.matmul(x, p["lin_wqkv"], precision)
    rows = jnp.concatenate([jnp.zeros((K - 1, 3 * P), f32), pre])
    w = p["lin_conv"].astype(f32)
    y = jax.nn.silu(sum(rows[j:j + t] * w[j] for j in range(K)))  # K shifted adds
    q, k, v = (a.reshape(t, nh, d) for a in jnp.split(y, 3, axis=-1))
    q, k = _l2(q) * d**-0.5, _l2(k)
    z = C.matmul(x, p["lin_wg"], precision) + p["lin_dt_bias"].astype(f32)
    rate = jnp.exp(p["lin_a_log"].astype(f32))[:, None]
    g = cfg["kda_lower_bound"] * jax.nn.sigmoid(rate * z.reshape(t, nh, d))
    beta = jax.nn.sigmoid(C.matmul(x, p["lin_wb"], precision))  # [T, heads]

    def one(s, xs):  # s [heads, D(k), D(v)]
        q, k, v, g, b = xs
        s = s * jnp.exp(g)[..., None]
        u = b[:, None] * (v - jnp.sum(s * k[..., None], axis=-2))
        s = s + k[..., None] * u[:, None, :]
        return s, jnp.sum(s * q[..., None], axis=-2)

    _, o = jax.lax.scan(one, jnp.zeros((nh, d, d), f32), (q, k, v, g, beta))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg["rms_norm_eps"])
    o = o * p["lin_norm"].astype(f32)
    gate = jax.nn.sigmoid(C.matmul(x, p["lin_wog"], precision))
    return C.matmul(o.reshape(t, P) * gate, p["wo"], precision)


def _query_block(t, most=512):
    for b in range(min(most, t), 0, -1):
        if t % b == 0:
            return b


def attention(x, p, cos, sin, cfg, precision):
    """``x [T, H]`` (normed) → one latent layer's attention output."""
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
    v = kv[..., nope:]
    scale = (nope + rope) ** -0.5
    keys = jnp.arange(t)

    def block(args):
        qb, rows = args  # rows: the block's query positions
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=C.HIGHEST) * scale
        seen = keys[None, :] <= rows[:, None]
        pr = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", pr, v, precision=C.HIGHEST)

    b = _query_block(t)
    split = lambda a: a.reshape((t // b, b) + a.shape[1:])
    o = jax.lax.map(block, (split(q), split(keys))).reshape(t, nh, vd)
    gate = jax.nn.sigmoid(C.matmul(x, p["w_og"], precision))  # [T, heads]
    return C.matmul((o * gate[..., None]).reshape(t, nh * vd), p["wo"], precision)


def moe(h, p, *, held, groups, top_k, scaling, norm, precision, shared=True):
    """``h [T, H]`` → the partial sum of the experts held here (``held``:
    first, count) plus (``shared``) the shared expert. ``groups``:
    (n_group, topk_group)."""
    s = jax.nn.sigmoid(C.matmul(h, p["w_router"], precision))
    sel = s + p["router_bias"].astype(jnp.float32)
    n_group, topk_group = groups
    by_group = sel.reshape(h.shape[0], n_group, -1)
    score = jax.lax.top_k(by_group, 2)[0].sum(-1)  # a group's two best
    _, best = jax.lax.top_k(score, topk_group)
    eligible = jnp.zeros_like(score, bool).at[jnp.arange(h.shape[0])[:, None], best].set(True)
    sel = jnp.where(eligible[:, :, None], by_group, -jnp.inf).reshape(sel.shape)
    _, top_i = jax.lax.top_k(sel, top_k)
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    if norm:
        top_s = top_s / top_s.sum(-1, keepdims=True)
    top_s = top_s * scaling
    # gate of expert e for token t (0 where e is not among its top-k)
    gates = jnp.zeros_like(s).at[jnp.arange(h.shape[0])[:, None], top_i].add(top_s)
    here = gates[:, held[0]:held[0] + held[1]]

    def one(acc, ew):
        wg, wu, wd, g = ew
        return acc + g[:, None] * swiglu(h, wg, wu, wd, precision), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(h), (p["w_gate"], p["w_up"], p["w_down"], here.T)
    )
    if shared:
        out = out + swiglu(
            h, p["w_shared_gate"], p["w_shared_up"], p["w_shared_down"], precision
        )
    return out


@partial(jax.jit, static_argnames=("cfg_key", "kind", "dense", "held", "precision"))
def _layer(x, p, cos, sin, *, cfg_key, kind, dense, held, precision):
    cfg = dict(cfg_key)
    h = C.rms_norm(x, p["attn_norm"], cfg["rms_norm_eps"])
    if kind == "linear_attention":
        x = x + linear_mixer(h, p, cfg, precision)
    else:
        x = x + attention(h, p, cos, sin, cfg, precision)
    h = C.rms_norm(x, p["mlp_norm"], cfg["rms_norm_eps"])
    if dense:
        return x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"], precision)
    return x + moe(
        h, p, held=held, groups=(cfg["n_group"], cfg["topk_group"]),
        top_k=cfg["num_experts_per_tok"], scaling=cfg["routed_scaling_factor"],
        norm=cfg["norm_topk_prob"], precision=precision,
    )


_KEYS = (
    "num_attention_heads", "head_dim", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "rms_norm_eps", "short_conv_kernel_size",
    "kda_lower_bound", "n_group", "topk_group", "num_experts_per_tok",
    "routed_scaling_factor", "norm_topk_prob",
)


def hidden_states(cfg, params, tokens, precision="f32"):
    """tokens [T] → final-layer residual stream [T, H] (float32)."""
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        x = params["embed"][tokens].astype(jnp.float32)
        cos, sin = rope_tables(cfg["qk_rope_head_dim"], cfg["rope_theta"], tokens.shape[0])
        key = tuple((k, cfg[k]) for k in _KEYS)
        held = tuple(cfg["experts_held"])
        k_dense = cfg["first_k_dense_replace"]
        seen = {"linear_attention": 0, "full_attention": 0}
        for i, kind in enumerate(cfg["layer_types"]):
            if i < k_dense:
                stack, at = params["dense_layers"], i
            else:
                stack = params["linear_layers" if kind == "linear_attention" else "layers"]
                at, seen[kind] = seen[kind], seen[kind] + 1
            x = _layer(
                x, jax.tree.map(lambda a: a[at], stack), cos, sin, cfg_key=key,
                kind=kind, dense=i < k_dense, held=held, precision=precision,
            )
        return x


def final_norm(cfg, params):
    return lambda h: C.rms_norm(h, params["final_norm"], cfg["rms_norm_eps"])


def head(cfg, params, hidden, ids, precision="f32"):
    with jax.default_matmul_precision("highest"):
        return C.head_stats(hidden, final_norm(cfg, params), params["lm_head"], ids, precision)
