"""Plain reference for a decoder whose layer is TWO latent-attention
sublayers and two dense FFNs with one expert branch across them, routed
by a softmax router that has identity ("zero-computation") experts among
its outputs (the family of ``LongCat-Flash``; arXiv:2509.01322).

Written from the published ``config.json`` and the model's reference
implementation (``LongcatFlashDecoderLayer``); every inference is listed
under ``assumed`` in the configuration file. One published layer is

    for i in (0, 1):
        x = x + MLA_i(RMSNorm(x; g_attn_i))
        h = RMSNorm(x; g_mlp_i)
        if i == 0: s = MoE(h)                 # the shortcut: read here ...
        x = x + W_down_i (silu(W_gate_i h) * W_up_i h)
    x = x + s                                 # ... and added here

- ``MLA_i``: ``c_q = RMSNorm(h Wq_a) * sqrt(H / r_q)``, ``q = c_q Wq_b``;
  ``c_kv, k_pe = split(h Wkv_a)``, ``c_kv = RMSNorm(c_kv) * sqrt(H /
  r_kv)``, ``k_nope, v = c_kv Wkv_b``; rotary embedding in interleaved
  pairs on the rope slices of ``q`` and on the one shared ``k_pe``;
  scores ``q k / sqrt(nope + rope)``, causal. No absorption: keys and
  values are rebuilt from the latent for every token (the program
  attends in the latent).
- ``MoE(h)``: ``p = softmax(h W_r)`` over ``n + z`` outputs, the last
  ``z`` of them identity experts; the ``moe_topk`` largest of ``p + b``
  (``b`` biases the selection only); gates ``routed_scaling_factor *
  p_e``, **not** renormalised; the sum over the picked experts HELD here
  (``experts_held``: first, count) of ``g_e * SwiGLU_e(h)`` plus ``(sum
  of the gates that fell on identity experts) * h``. No shared expert.
  Dropless: every token reaches each of its held experts (the program
  seats them in capacity slots). Nothing stands in for the experts of
  the other chips.

One sequence at a time, layer by layer, float32 at ``highest``; queries
go by in blocks so that a few thousand tokens fit beside the weights,
and the held experts are visited one after the other.
"""

import math
from functools import partial

import jax
import jax.numpy as jnp

from . import common as C
from .mla_moe import rotate_pairs, swiglu


def leaf_shapes(c: dict) -> dict:
    """The weight tree, as the program's ``init_params`` lays it out for
    a layer of ``sublayers`` sublayers: one stack ``layers`` whose own
    leaves are the router and the held experts, ``[L, ...]``, and in it
    a sub-tree a sublayer, ``sub0``, ``sub1``: a plain dense layer's
    leaves ``[L, ...]`` (norms, the latent attention's projections, its
    dense FFN under ``w_gate`` / ``w_up`` / ``w_down``). The selection
    bias is drawn at the mean score ``1 / (n + z)``: at ``common.STD``
    it would be fifteen times the mean softmax score of the cell's
    router and pick the same experts for every token."""
    H, V, L, S = c["hidden_size"], c["vocab_size"], c["n_layers"], c["sublayers"]
    nh, rq, r = c["n_heads"], c["q_lora_rank"], c["kv_lora_rank"]
    nope, rope, vd = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    F, FD = c["intermediate_size"], c["dense_intermediate"]
    E = c["n_experts"] + c["zero_experts"]  # the router's width
    EH = c["experts_held"][1] if c.get("experts_held") else c["n_experts"]
    down = C.STD / math.sqrt(2 * L)
    sub = {
        "attn_norm": ((L, H), None), "mlp_norm": ((L, H), None),
        "wq_a": ((L, H, rq), C.STD), "q_a_norm": ((L, rq), None),
        "wq_b": ((L, rq, nh * (nope + rope)), C.STD),
        "wkv_a": ((L, H, r + rope), C.STD), "kv_a_norm": ((L, r), None),
        "wkv_b": ((L, r, nh * (nope + vd)), C.STD),
        "wo": ((L, nh * vd, H), down),
        "w_gate": ((L, H, FD), C.STD), "w_up": ((L, H, FD), C.STD),
        "w_down": ((L, FD, H), down),
    }
    return {
        "embed": ((V, H), C.STD), "final_norm": ((H,), None),
        "lm_head": ((H, V), C.STD),
        "layers": {
            **{f"sub{i}": dict(sub) for i in range(S)},
            "w_router": ((L, H, E), C.STD), "router_bias": ((L, E), 1.0 / E),
            "w_gate": ((L, EH, H, F), C.STD), "w_up": ((L, EH, H, F), C.STD),
            "w_down": ((L, EH, F, H), down),
        },
    }


def rope_tables(dim: int, base: float, t: int):
    inv = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def _query_block(t: int, most: int = 256) -> int:
    for b in range(min(most, t), 0, -1):
        if t % b == 0:
            return b


def attention(x, p, cos, sin, cfg, precision):
    """``x [T, H]`` (normed) → one latent-attention sublayer's output."""
    t, H = x.shape
    nh, rq, r = cfg["num_attention_heads"], cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    eps = cfg["rms_norm_eps"]
    c_q = C.rms_norm(C.matmul(x, p["wq_a"], precision), p["q_a_norm"], eps)
    if cfg["mla_scale_q_lora"]:
        c_q = c_q * math.sqrt(H / rq)
    kv_a = C.matmul(x, p["wkv_a"], precision)
    c_kv = C.rms_norm(kv_a[:, :r], p["kv_a_norm"], eps)
    if cfg["mla_scale_kv_lora"]:
        c_kv = c_kv * math.sqrt(H / r)
    q = C.matmul(c_q, p["wq_b"], precision).reshape(t, nh, nope + rope)
    q = jnp.concatenate([q[..., :nope], rotate_pairs(q[..., nope:], cos, sin)], -1)
    k_pe = rotate_pairs(kv_a[:, None, r:], cos, sin)  # one shared head, unscaled
    kv = C.matmul(c_kv, p["wkv_b"], precision).reshape(t, nh, nope + vd)
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
    o = jax.lax.map(block, (split(q), split(keys))).reshape(t, nh * vd)
    return C.matmul(o, p["wo"], precision)


def moe(h, p, *, held, zero, top_k, scaling, precision):
    """``h [T, H]`` → the partial sum of the experts held here (``held``:
    first, count) plus the identity experts' term. ``zero``: how many of
    the router's last outputs are identity experts."""
    probs = jax.nn.softmax(C.matmul(h, p["w_router"], precision), axis=-1)
    _, top_i = jax.lax.top_k(probs + p["router_bias"].astype(jnp.float32), top_k)
    top_p = jnp.take_along_axis(probs, top_i, axis=-1) * scaling  # not renormalised
    # gate of router output e for token t (0 where e is not among its top-k)
    gates = jnp.zeros_like(probs).at[jnp.arange(h.shape[0])[:, None], top_i].add(top_p)
    n_real = probs.shape[-1] - zero
    zero_gate = gates[:, n_real:].sum(-1)
    here = gates[:, held[0]:held[0] + held[1]]

    def one(acc, ew):
        wg, wu, wd, g = ew
        return acc + g[:, None] * swiglu(h, wg, wu, wd, precision), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(h), (p["w_gate"], p["w_up"], p["w_down"], here.T)
    )
    return out + zero_gate[:, None] * h


@partial(jax.jit, static_argnames=("cfg_key", "held", "precision"))
def _layer(x, p, cos, sin, *, cfg_key, held, precision):
    cfg = dict(cfg_key)
    eps = cfg["rms_norm_eps"]
    for i in range(sum(k.startswith("sub") for k in p)):
        sub = p[f"sub{i}"]
        x = x + attention(
            C.rms_norm(x, sub["attn_norm"], eps), sub, cos, sin, cfg, precision
        )
        h = C.rms_norm(x, sub["mlp_norm"], eps)
        if i == 0:
            branch = moe(
                h, p, held=held, zero=cfg["zero_expert_num"],
                top_k=cfg["moe_topk"], scaling=cfg["routed_scaling_factor"],
                precision=precision,
            )
        x = x + swiglu(h, sub["w_gate"], sub["w_up"], sub["w_down"], precision)
    return x + branch


_KEYS = (
    "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "rms_norm_eps", "mla_scale_q_lora",
    "mla_scale_kv_lora", "zero_expert_num", "moe_topk", "routed_scaling_factor",
)


def hidden_states(cfg, params, tokens, precision="f32"):
    """tokens [T] → final-layer residual stream [T, H] (float32)."""
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        x = params["embed"][tokens].astype(jnp.float32)
        cos, sin = rope_tables(cfg["qk_rope_head_dim"], cfg["rope_theta"], tokens.shape[0])
        key = tuple((k, cfg[k]) for k in _KEYS)
        held = tuple(cfg["experts_held"])
        for i in range(cfg["num_layers"]):
            x = _layer(
                x, jax.tree.map(lambda a: a[i], params["layers"]), cos, sin,
                cfg_key=key, held=held, precision=precision,
            )
        return x


def final_norm(cfg, params):
    return lambda h: C.rms_norm(h, params["final_norm"], cfg["rms_norm_eps"])


def head(cfg, params, hidden, ids, precision="f32"):
    with jax.default_matmul_precision("highest"):
        return C.head_stats(hidden, final_norm(cfg, params), params["lm_head"], ids, precision)
