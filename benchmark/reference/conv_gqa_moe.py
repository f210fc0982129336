"""Plain reference for a decoder of gated short-convolution layers beside
grouped-query attention layers, with sigmoid routing over a chip's share
of the experts (the family of ``LFM2-24B-A2B``, ``model_type``
``lfm2_moe``).

Written from the published ``config.json`` and the family's public
modeling code (``transformers/models/lfm2``: ``Lfm2ShortConv``,
``Lfm2Attention``, ``Lfm2DecoderLayer``); every reading that is not a
key's plain meaning is listed under ``assumed`` in the configuration
file. ``cfg["layer_types"]`` says which layers are ``conv`` and which
``full_attention``. With ``h = RMSNorm(x; operator_norm)`` (plain
``w * x / rms(x)``, eps ``norm_eps``):

*conv layer*, K = ``conv_L_cache``:

    (B, C, z) = split3(h W_in)                     W_in [H, 3H], that order
    u = B * z
    c = causal depthwise convolution of the WHOLE padded sequence u with
        the taps w [K, H] (tap K-1 on the current row, zeros before the
        sequence), no bias (``conv_bias`` false), no activation
    out = (C * c) W_out

*full layer*: ``num_attention_heads`` query heads over
``num_key_value_heads`` KV heads of ``hidden_size /
num_attention_heads``; no biases; an RMSNorm with one weight vector of
the head's size on every q head and every k head BEFORE rope; rope over
the whole head in half-split (rotate-half) form at
``rope_parameters.rope_theta``, no scaling; scale 1 / sqrt(head),
causal; the output projection.

*both*: ``x = x + out``; ``x = x + FFN(RMSNorm(x; ffn_norm))``. FFN of
the first ``num_dense_layers`` layers: ``W2(silu(W1 h) * W3 h)`` at
``intermediate_size``. After them ``s = sigmoid(h W_r)`` over all
PUBLISHED experts (the router's width is its weight's), in float32; the
``num_experts_per_tok`` experts with the largest ``s + b``
(``use_expert_bias``: ``b`` shifts the selection only); gates ``s_e /
(sum of the picked s + 1e-6)`` (``norm_topk_prob``) times
``routed_scaling_factor``; the sum over the picked experts HELD here
(``experts_held``: first, count), each the same SwiGLU at
``moe_intermediate_size``. No shared expert. Dropless; nothing stands
in for the other chips' experts.

*last*: RMSNorm (``embedding_norm``), then the head, tied to the
embedding.

One sequence at a time, layer by layer, float32 at ``highest``; queries
go by in blocks so that a few thousand tokens fit beside the weights.
"""

import math
from functools import partial

import jax
import jax.numpy as jnp

from . import common as C
from .dense import rotate_half
from .mla_moe import swiglu

#: what the model adds to the picked scores' sum before it divides
RENORM_EPS = 1e-6


def leaf_shapes(c: dict) -> dict:
    """The weight tree, as the program's ``init_params`` lays it out
    (``c``: the file's ``llama_config``): ``dense_layers`` (the prelude:
    a conv operator and a dense FFN), ``conv_layers`` and ``layers``
    (grouped-query attention with its q/k norms), each an expert layer's
    leaves under its operator's; no ``lm_head`` (tied). The selection
    bias is drawn like a weight (std 0.02) so that it bites, the
    convolution's taps at ``K**-0.5``."""
    H, V, n_layers, k_dense = c["hidden_size"], c["vocab_size"], c["n_layers"], c["first_k_dense"]
    kinds = list(c["layer_types"])
    D, K = c["head_dim"], c.get("conv_taps", 3)
    q, kv = c["n_heads"] * D, c["n_kv_heads"] * D
    F, FD, E = c["intermediate_size"], c["dense_intermediate"], c["n_experts"]
    EH = c["experts_held"][1] if c.get("experts_held") else E
    down = C.STD / math.sqrt(2 * n_layers)

    def conv(n):
        return {
            "attn_norm": ((n, H), None), "conv_win": ((n, H, 3 * H), C.STD),
            "conv_w": ((n, K, H), K**-0.5), "wo": ((n, H, H), down),
        }

    def attn(n):
        return {
            "attn_norm": ((n, H), None), "wq": ((n, H, q), C.STD),
            "wk": ((n, H, kv), C.STD), "wv": ((n, H, kv), C.STD),
            "wo": ((n, q, H), down),
            "q_norm": ((n, D), None), "k_norm": ((n, D), None),
        }

    def experts(n):
        return {
            "mlp_norm": ((n, H), None), "w_router": ((n, H, E), C.STD),
            "router_bias": ((n, E), C.STD),
            "w_gate": ((n, EH, H, F), C.STD), "w_up": ((n, EH, H, F), C.STD),
            "w_down": ((n, EH, F, H), down),
        }

    if set(kinds[:k_dense]) != {"conv"}:
        raise ValueError("the dense layers lead and are conv layers")
    n_conv, n_full = kinds[k_dense:].count("conv"), kinds[k_dense:].count("full")
    return {
        "embed": ((V, H), C.STD), "final_norm": ((H,), None),
        "dense_layers": {
            **conv(k_dense), "mlp_norm": ((k_dense, H), None),
            "w_gate": ((k_dense, H, FD), C.STD), "w_up": ((k_dense, H, FD), C.STD),
            "w_down": ((k_dense, FD, H), down),
        },
        "conv_layers": {**conv(n_conv), **experts(n_conv)},
        "layers": {**attn(n_full), **experts(n_full)},
    }


def rope_tables(dim: int, theta: float, t: int):
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)  # [T, dim / 2]


def short_conv(x, p, precision):
    """``x [T, H]`` (normed) → one conv layer's operator output [T, H]:
    the convolution over the whole sequence, padded in front."""
    t, h = x.shape
    k = p["conv_w"].shape[0]
    gate_in, gate_out, z = jnp.split(C.matmul(x, p["conv_win"], precision), 3, axis=-1)
    u = gate_in * z  # [T, H]
    c = jax.lax.conv_general_dilated(
        u.T[None],  # [1, H, T]: a channel a hidden unit
        p["conv_w"].astype(jnp.float32).T[:, None, :],  # [H, 1, K]
        window_strides=(1,), padding=[(k - 1, 0)], feature_group_count=h,
        precision=C.HIGHEST,
    )[0].T
    return C.matmul(gate_out * c, p["wo"], precision)


def _query_block(t: int, most: int = 256) -> int:
    for b in range(min(most, t), 0, -1):
        if t % b == 0:
            return b


def attention(x, p, cos, sin, *, nh, nkv, eps, precision):
    """``x [T, H]`` (normed) → the attention sublayer's output."""
    t = x.shape[0]
    hd = p["q_norm"].shape[-1]
    q = C.rms_norm(C.matmul(x, p["wq"], precision).reshape(t, nh, hd), p["q_norm"], eps)
    k = C.rms_norm(C.matmul(x, p["wk"], precision).reshape(t, nkv, hd), p["k_norm"], eps)
    q, k = rotate_half(q, cos, sin), rotate_half(k, cos, sin)
    v = C.matmul(x, p["wv"], precision).reshape(t, nkv, hd)
    k = jnp.repeat(k, nh // nkv, axis=1)  # query head a reads KV head a // (nh / nkv)
    v = jnp.repeat(v, nh // nkv, axis=1)
    keys = jnp.arange(t)

    def block(args):
        qb, rows = args  # rows: the block's query positions
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=C.HIGHEST) * hd ** -0.5
        seen = keys[None, :] <= rows[:, None]
        pr = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", pr, v, precision=C.HIGHEST)

    b = _query_block(t)
    split = lambda a: a.reshape((t // b, b) + a.shape[1:])
    o = jax.lax.map(block, (split(q), split(keys))).reshape(t, nh * hd)
    return C.matmul(o, p["wo"], precision)


def moe(h, p, *, top_k, bias, renorm, scaling, held, precision):
    """``h [T, H]`` → the partial sum of the experts held here
    (``held``: first, count) among each token's ``top_k`` picks."""
    s = jax.nn.sigmoid(C.matmul(h, p["w_router"], precision))
    sel = s + p["router_bias"].astype(jnp.float32) if bias else s
    _, top_i = jax.lax.top_k(sel, top_k)
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    if renorm:
        top_s = top_s / (top_s.sum(-1, keepdims=True) + RENORM_EPS)
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
    return out


_KEYS = (
    "num_attention_heads", "num_key_value_heads", "norm_eps", "num_experts_per_tok",
    "use_expert_bias", "norm_topk_prob", "routed_scaling_factor",
)


@partial(jax.jit, static_argnames=("cfg_key", "kind", "dense", "held", "precision"))
def _layer(x, p, cos, sin, *, cfg_key, kind, dense, held, precision):
    cfg = dict(cfg_key)
    eps = cfg["norm_eps"]
    h = C.rms_norm(x, p["attn_norm"], eps)
    if kind == "conv":
        x = x + short_conv(h, p, precision)
    else:
        x = x + attention(
            h, p, cos, sin, nh=cfg["num_attention_heads"],
            nkv=cfg["num_key_value_heads"], eps=eps, precision=precision,
        )
    h = C.rms_norm(x, p["mlp_norm"], eps)
    if dense:
        return x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"], precision)
    return x + moe(
        h, p, top_k=cfg["num_experts_per_tok"], bias=bool(cfg["use_expert_bias"]),
        renorm=bool(cfg["norm_topk_prob"]), scaling=float(cfg["routed_scaling_factor"]),
        held=held, precision=precision,
    )


def hidden_states(cfg, params, tokens, precision="f32"):
    """tokens [T] → final-layer residual stream [T, H] (float32)."""
    if cfg.get("conv_bias"):
        raise ValueError("a bias on the convolution is not written here")
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        x = params["embed"][tokens].astype(jnp.float32)
        head_dim = cfg["hidden_size"] // cfg["num_attention_heads"]
        cos, sin = rope_tables(
            head_dim, float(cfg["rope_parameters"]["rope_theta"]), tokens.shape[0]
        )
        key = tuple((k, cfg[k]) for k in _KEYS)
        held = tuple(cfg["experts_held"])
        k_dense = cfg["num_dense_layers"]
        seen = {"conv": 0, "full_attention": 0}
        for i, kind in enumerate(cfg["layer_types"]):
            if i < k_dense:
                stack, at = params["dense_layers"], i
            else:
                stack = params["conv_layers" if kind == "conv" else "layers"]
                at, seen[kind] = seen[kind], seen[kind] + 1
            x = _layer(
                x, C.layer_slice(stack, at), cos, sin, cfg_key=key,
                kind="conv" if kind == "conv" else "full", dense=i < k_dense,
                held=held, precision=precision,
            )
        return x


def final_norm(cfg, params):
    return lambda h: C.rms_norm(h, params["final_norm"], cfg["norm_eps"])


def head(cfg, params, hidden, ids, precision="f32"):
    """The head is the embedding's transpose (tied)."""
    with jax.default_matmul_precision("highest"):
        return C.head_stats(hidden, final_norm(cfg, params), params["embed"].T, ids, precision)
