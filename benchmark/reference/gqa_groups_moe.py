"""Plain reference for a grouped-query decoder of two kinds of layer
whose QUERY head count differs by kind, with per-head output gates, a
dense first layer and a chip's share of sigmoid-routed experts (the
family of ``Laguna-S-2.1``).

Written from the published ``config.json``; every reading of a key that
the config does not spell out is listed under ``assumed`` in the
configuration file. Layer ``l`` of kind ``t`` (``full_attention`` |
``sliding_attention``) has ``H_t = num_attention_heads_per_layer[l]``
query heads over ``num_key_value_heads`` KV heads of ``head_dim``:

- ``h = RMSNorm(x)``; ``q = h Wq`` [H_t, d], ``k = h Wk``, ``v = h Wv``
  [Hkv, d]; no bias, no q/k norm.
- rope by ``rope_parameters[t]``: the first ``partial_rotary_factor`` of
  each q and k head rotates (rotate-half inside that slice), the rest
  passes through. ``yarn``: the inverse frequencies of transformers'
  ``_compute_yarn_parameters`` over the rotated width (extrapolated
  below the correction range of ``beta_fast``, interpolated by
  ``factor`` above that of ``beta_slow``, a linear ramp between, the
  range truncated to whole dims), cos and sin times
  ``attention_factor``. ``default``: ``rope_theta`` and nothing else.
- scores ``q k / sqrt(d)``, softmax in float32 over the keys ``j`` that
  query ``i`` sees: ``0 <= i - j`` (full), ``0 <= i - j <
  sliding_window`` (sliding). Query head ``a`` reads KV head ``a //
  (H_t / Hkv)``.
- ``gating: per-head``: ``g = sigmoid(h Wg)`` [H_t], head ``a``'s output
  times ``g_a``; then ``Wo`` and the residual.
- ``h2 = RMSNorm(x)``. A ``dense`` layer: SwiGLU of
  ``intermediate_size``. A ``sparse`` layer: ``s = sigmoid(h2 Wr)`` over
  all published experts, the ``num_experts_per_tok`` largest, gates
  ``s_e / sum_picked s`` (``norm_topk_prob``) times
  ``moe_routed_scaling_factor``, on the experts' OUTPUT; only the
  experts held here (``experts_held``: first, count) are computed and
  summed, plus the shared expert. Dropless.

One sequence at a time, layer by layer, float32 at ``highest``; queries
go by in blocks, each against all its keys, so that 6.7k positions fit
beside the weights (72 heads x 256 x 6656 scores are half a GB).
"""

import math
from functools import partial

import jax
import jax.numpy as jnp

from . import common as C
from .dense import rotate_half
from .mla_moe import swiglu


def leaf_shapes(c: dict) -> dict:
    """The weight tree, as the program's ``init_params`` lays it out for
    layer groups: ``dense_layers`` (the dense first layers, full
    attention), ``layers`` (the full-attention expert layers) and
    ``window_layers`` (the sliding-attention expert layers, at their own
    query head count), each a stack of its own; expert leaves hold only
    the experts held here. ``c`` is the ``llama_config`` group."""
    H, V, n_layers = c["hidden_size"], c["vocab_size"], c["n_layers"]
    D, kv = c["head_dim"], c["n_kv_heads"] * c["head_dim"]
    k_dense = c.get("first_k_dense", 0)
    kinds = c["layer_types"][k_dense:]
    down = C.STD / math.sqrt(2 * n_layers)

    def attn(n, nh):
        return {
            "attn_norm": ((n, H), None),
            "wq": ((n, H, nh * D), C.STD), "wk": ((n, H, kv), C.STD),
            "wv": ((n, H, kv), C.STD), "wo": ((n, nh * D, H), down),
            "w_og": ((n, H, nh), C.STD),
        }

    F, E = c["intermediate_size"], c["n_experts"]
    EH = c["experts_held"][1] if c.get("experts_held") else E
    FS = c.get("moe_shared_intermediate") or F

    def experts(n):
        return {
            "mlp_norm": ((n, H), None), "w_router": ((n, H, E), C.STD),
            "w_gate": ((n, EH, H, F), C.STD), "w_up": ((n, EH, H, F), C.STD),
            "w_down": ((n, EH, F, H), down),
            "w_shared_gate": ((n, H, FS), C.STD),
            "w_shared_up": ((n, H, FS), C.STD),
            "w_shared_down": ((n, FS, H), down),
        }

    tree = {
        "embed": ((V, H), C.STD), "final_norm": ((H,), None),
        "lm_head": ((H, V), C.STD),
    }
    for key, kind, nh in (
        ("layers", "full", c["n_heads"]), ("window_layers", "window", c["swa_n_heads"])
    ):
        n = sum(1 for k in kinds if k == kind)
        if n:
            tree[key] = {**attn(n, nh), **experts(n)}
    if k_dense:
        FD = c.get("dense_intermediate") or F
        tree["dense_layers"] = {
            **attn(k_dense, c["n_heads"]), "mlp_norm": ((k_dense, H), None),
            "w_gate": ((k_dense, H, FD), C.STD),
            "w_up": ((k_dense, H, FD), C.STD),
            "w_down": ((k_dense, FD, H), down),
        }
    return tree


def rope_tables(rope: dict, head_dim: int, t: int):
    """cos, sin ``[T, rot / 2]`` of one kind of layer from its group of
    ``rope_parameters`` (``rot`` = the rotated width of a head)."""
    rot = int(head_dim * rope.get("partial_rotary_factor", 1.0))
    base = float(rope["rope_theta"])
    ix = jnp.arange(0, rot, 2, dtype=jnp.float32)
    inv = 1.0 / (base ** (ix / rot))
    scale = 1.0
    if rope["rope_type"] == "yarn":
        factor, orig = rope["factor"], rope["original_max_position_embeddings"]

        def dim_of(rotations):  # the dim whose wavelength fits that many in orig
            return rot * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(base))

        low = max(math.floor(dim_of(rope["beta_fast"])), 0)
        high = min(math.ceil(dim_of(rope["beta_slow"])), rot - 1)
        if low == high:
            high += 0.001
        ramp = jnp.clip((jnp.arange(rot // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
        inv = inv / factor * ramp + inv * (1 - ramp)
        scale = rope["attention_factor"]
    elif rope["rope_type"] != "default":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def _query_block(t: int, most: int = 256) -> int:
    for b in range(min(most, t), 0, -1):
        if t % b == 0:
            return b


def attention(x, p, cos, sin, *, nh, nkv, hd, window, gated, precision):
    """``x [T, H]`` (normed) → the attention sublayer's output."""
    t = x.shape[0]
    q = rotate_half(C.matmul(x, p["wq"], precision).reshape(t, nh, hd), cos, sin)
    k = rotate_half(C.matmul(x, p["wk"], precision).reshape(t, nkv, hd), cos, sin)
    v = C.matmul(x, p["wv"], precision).reshape(t, nkv, hd)
    k = jnp.repeat(k, nh // nkv, axis=1)  # query head a reads KV head a // (nh / nkv)
    v = jnp.repeat(v, nh // nkv, axis=1)
    keys = jnp.arange(t)

    def block(args):
        qb, rows = args  # rows: the block's query positions
        seen = keys[None, :] <= rows[:, None]
        if window:
            seen = seen & (rows[:, None] - keys[None, :] < window)
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=C.HIGHEST) * hd ** -0.5
        pr = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", pr, v, precision=C.HIGHEST)

    b = _query_block(t)
    split = lambda a: a.reshape((t // b, b) + a.shape[1:])
    o = jax.lax.map(block, (split(q), split(keys))).reshape(t, nh, hd)
    if gated:
        o = o * jax.nn.sigmoid(C.matmul(x, p["w_og"], precision))[..., None]
    return C.matmul(o.reshape(t, nh * hd), p["wo"], precision)


def moe(h, p, *, top_k, renorm, scaling, held, precision):
    """Sigmoid routing over all published experts; the sum over the
    experts held here (``held``: first, count) plus the shared expert."""
    s = jax.nn.sigmoid(C.matmul(h, p["w_router"], precision))
    top_s, top_i = jax.lax.top_k(s, top_k)
    if renorm:
        top_s = top_s / top_s.sum(-1, keepdims=True)
    top_s = top_s * scaling
    # gate of expert e for token t (0 where e is not among its top-k)
    gates = jnp.zeros_like(s).at[jnp.arange(h.shape[0])[:, None], top_i].add(top_s)
    gates = gates[:, held[0]:held[0] + held[1]]

    def one(acc, ew):
        wg, wu, wd, g = ew
        return acc + g[:, None] * swiglu(h, wg, wu, wd, precision), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(h), (p["w_gate"], p["w_up"], p["w_down"], gates.T)
    )
    return out + swiglu(
        h, p["w_shared_gate"], p["w_shared_up"], p["w_shared_down"], precision
    )


@partial(jax.jit, static_argnames=(
    "nh", "nkv", "hd", "window", "gated", "eps", "dense", "routing", "precision",
))
def _layer(x, p, cos, sin, *, nh, nkv, hd, window, gated, eps, dense, routing, precision):
    x = x + attention(
        C.rms_norm(x, p["attn_norm"], eps), p, cos, sin,
        nh=nh, nkv=nkv, hd=hd, window=window, gated=gated, precision=precision,
    )
    h = C.rms_norm(x, p["mlp_norm"], eps)
    if dense:
        return x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"], precision)
    top_k, renorm, scaling, held = routing
    return x + moe(
        h, p, top_k=top_k, renorm=renorm, scaling=scaling, held=held,
        precision=precision,
    )


def hidden_states(cfg, params, tokens, precision="f32"):
    """tokens [T] → final-layer residual stream [T, H] (float32)."""
    if cfg.get("moe_router_logit_softcapping") or cfg.get("moe_apply_router_weight_on_input"):
        raise ValueError("router soft-capping and gates on the input are not written here")
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        t = tokens.shape[0]
        x = params["embed"][tokens].astype(jnp.float32)
        hd = cfg["head_dim"]
        tables = {
            kind: rope_tables(rope, hd, t)
            for kind, rope in cfg["rope_parameters"].items()
        }
        routing = (
            cfg["num_experts_per_tok"], bool(cfg["norm_topk_prob"]),
            float(cfg["moe_routed_scaling_factor"]), tuple(cfg["experts_held"]),
        )
        at = {"dense_layers": 0, "layers": 0, "window_layers": 0}
        for i, kind in enumerate(cfg["layer_types"]):
            dense = cfg["mlp_layer_types"][i] == "dense"
            sliding = kind == "sliding_attention"
            if dense and (sliding or at["layers"] or at["window_layers"]):
                raise ValueError("dense layers lead and attend in full")
            group = "dense_layers" if dense else "window_layers" if sliding else "layers"
            cos, sin = tables[kind]
            x = _layer(
                x, C.layer_slice(params[group], at[group]), cos, sin,
                nh=cfg["num_attention_heads_per_layer"][i],
                nkv=cfg["num_key_value_heads"], hd=hd,
                window=cfg["sliding_window"] if sliding else 0,
                gated=cfg["gating_types"][i] == "per_head",
                eps=cfg["rms_norm_eps"], dense=dense, routing=routing,
                precision=precision,
            )
            at[group] += 1
        return x


def final_norm(cfg, params):
    return lambda h: C.rms_norm(h, params["final_norm"], cfg["rms_norm_eps"])


def head(cfg, params, hidden, ids, precision="f32"):
    with jax.default_matmul_precision("highest"):
        return C.head_stats(hidden, final_norm(cfg, params), params["lm_head"], ids, precision)
