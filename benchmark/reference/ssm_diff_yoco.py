"""Plain reference for a decoder-hybrid-decoder of selective state-space
layers, differential window and full attention, and an upper half whose
layers keep nothing (the family of ``Phi-4-mini-flash-reasoning``,
``model_type`` ``phi4flash``; the design is "SambaY", arXiv:2507.06607).

Written from the published ``config.json``, the paper, and the two
public modules whose mathematics the family shares
(``transformers/models/mamba``: ``MambaMixer.slow_forward``, the Mamba-1
recurrence; ``transformers/models/diffllama``: differential attention in
another head pairing). The model's own ``modeling_phi4flash.py`` is not
on this machine: every reading that is not a key's plain meaning is
listed under ``assumed`` in the configuration file.

*the layer map*, from ``num_hidden_layers`` L, ``mb_per_layer`` 2 and
``sliding_window`` (:func:`layer_kinds`): layer l < L/2 is ``mamba`` where
l is even and differential ``window`` attention where it is odd; layer
L/2 is ``mamba`` and its scan output M is kept; layer L/2 + 1 is
differential ``full`` attention, the model's only K/V; of the layers
after it the even ones are ``gmu`` (they read M) and the odd ones
``cross`` (queries alone, they read layer L/2 + 1's K/V).

*every layer*: ``x = x + mixer(LN(x)); x = x + MLP(LN(x))``, LN a
LayerNorm with weight and bias at ``layer_norm_eps`` (stored as
(w - 1, b): the tree's norm leaves are [2, H]); ``MLP(h) = W_down(silu(
W_gate h) * W_up h)`` (the published ``fc1`` is the two side by side),
no biases. No rotary anywhere.

*mamba* (d_inner = 2 H, N = 16, K = 4, R = ceil(H / 16)):

    (x, z) = split2(h W_in);  x_t = silu(sum_j w_j x_{t-K+1+j} + b)
    (d, B_t, C_t) = split(x_t W_x; R, N, N);  D_t = softplus(d W_dt + b_dt)
    S_t = exp(D_t (x) A) S_{t-1} + (D_t x_t) (x) B_t,  A = -exp(A_log)
    m_t = S_t C_t + D x_t;  out = (m_t silu(z_t)) W_out;  M = m (layer L/2)

*gmu*: ``out = (M_t silu(h_t W_1)) W_2``.

*differential attention*: query heads (2p, 2p + 1) = (q1, q2) of pair p,
K heads (2g, 2g + 1) = (k1, k2) and V heads (2g, 2g + 1) = (v1, v2) of KV
pair g = p // (pairs / KV pairs); ``a_i = softmax(q_i k_i^T / sqrt(D) +
mask) [v1 | v2]``; ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) +
lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3 l)``; ``o =
RMSNorm_2D(a1 - lambda a2; w) (1 - lambda_init)`` (the tree stores
w - 1), read back as heads 2p, 2p + 1; then the output projection with
its bias. A window layer sees key j from query i iff 0 <= i - j <
``sliding_window``.

*last*: LayerNorm, then the head, tied to the embedding.

One sequence at a time, layer by layer, float32 at ``highest``; the
recurrence a ``lax.scan`` over the tokens, attention as two softmaxes a
pair over the whole sequence, its queries in blocks.
"""

import math
from functools import partial

import jax
import jax.numpy as jnp

from . import common as C
from .conv_gqa_moe import _query_block
from .mla_moe import swiglu

#: the family's defaults for what ``config.json`` does not state
D_STATE, D_CONV, EXPAND = 16, 4, 2


def layer_kinds(n_layers: int, mb_per_layer: int = 2) -> list:
    """The kind of every layer, from the depth alone."""
    half = n_layers // 2
    kinds = []
    for l in range(n_layers):
        attends = l % mb_per_layer == mb_per_layer - 1
        if l < half:
            kinds.append("window" if attends else "mamba")
        elif l <= half + 1:
            kinds.append("full" if attends else "mamba")
        else:
            kinds.append("cross" if attends else "gmu")
    return kinds


def leaf_shapes(c: dict) -> dict:
    """The weight tree, as the program's ``init_params`` lays it out
    (``c``: the file's ``llama_config``): ``mamba_layers``,
    ``window_layers``, ``layers`` (the one full layer), ``gmu_layers``
    and ``cross_layers``, each a dense MLP's leaves under its mixer's.
    Biases, the lambdas, D and A_log are drawn like weights (std 0.02) so
    that they bite; the convolution's taps at ``K**-0.5``; norms (the
    sub-norm's w - 1 too) at identity."""
    H, V, n_layers = c["hidden_size"], c["vocab_size"], c["n_layers"]
    kinds = list(c["layer_types"])
    D, F = c["head_dim"], c["intermediate_size"]
    q, kv = c["n_heads"] * D, c["n_kv_heads"] * D
    di = c.get("ssm_expand", EXPAND) * H
    N, K = c.get("ssm_state", D_STATE), c.get("ssm_conv", D_CONV)
    R = c.get("ssm_dt_rank") or -(-H // 16)
    down = C.STD / math.sqrt(2 * n_layers)

    def mlp(n):
        return {
            "attn_norm": ((n, 2, H), None), "mlp_norm": ((n, 2, H), None),
            "w_gate": ((n, H, F), C.STD), "w_up": ((n, H, F), C.STD),
            "w_down": ((n, F, H), down),
        }

    def queries(n):
        return {
            "wq": ((n, H, q), C.STD), "bq": ((n, q), C.STD),
            "wo": ((n, q, H), down), "bo": ((n, H), C.STD),
            "diff_lam": ((n, 4, D), C.STD), "diff_norm": ((n, 2 * D), None),
        }

    def attn(n):
        return {
            **queries(n), "wk": ((n, H, kv), C.STD), "bk": ((n, kv), C.STD),
            "wv": ((n, H, kv), C.STD), "bv": ((n, kv), C.STD),
        }

    def mamba(n):
        return {
            "ssm_win": ((n, H, 2 * di), C.STD), "ssm_conv": ((n, K, di), K**-0.5),
            "ssm_conv_b": ((n, di), C.STD), "ssm_wx": ((n, di, R + 2 * N), C.STD),
            "ssm_wdt": ((n, R, di), C.STD), "ssm_dt_b": ((n, di), C.STD),
            "ssm_a_log": ((n, di, N), C.STD), "ssm_d": ((n, di), C.STD),
            "wo": ((n, di, H), down),
        }

    def gmu(n):
        return {"gmu_w1": ((n, H, di), C.STD), "wo": ((n, di, H), down)}

    count = kinds.count
    return {
        "embed": ((V, H), C.STD), "final_norm": ((2, H), None),
        "mamba_layers": {**mamba(count("mamba")), **mlp(count("mamba"))},
        "window_layers": {**attn(count("window")), **mlp(count("window"))},
        "layers": {**attn(count("full")), **mlp(count("full"))},
        "gmu_layers": {**gmu(count("gmu")), **mlp(count("gmu"))},
        "cross_layers": {**queries(count("cross")), **mlp(count("cross"))},
    }


def selective_scan(h, p, precision):
    """``h [T, H]`` (normed) → (the mamba mixer's output [T, H], its scan
    output m [T, d_inner] before the gate)."""
    f32 = lambda a: a.astype(jnp.float32)
    t = h.shape[0]
    k, di = p["ssm_conv"].shape
    n = p["ssm_a_log"].shape[-1]
    r = p["ssm_wdt"].shape[0]
    x, z = jnp.split(C.matmul(h, p["ssm_win"], precision), 2, axis=-1)
    rows = jnp.concatenate([jnp.zeros((k - 1, di), jnp.float32), x])
    x = sum(rows[j : j + t] * f32(p["ssm_conv"])[j] for j in range(k))
    x = jax.nn.silu(x + f32(p["ssm_conv_b"]))
    dbc = C.matmul(x, p["ssm_wx"], precision)
    dt = jax.nn.softplus(C.matmul(dbc[:, :r], p["ssm_wdt"], precision) + f32(p["ssm_dt_b"]))
    a = -jnp.exp(f32(p["ssm_a_log"]))

    def step(s, xs):
        x_t, dt_t, b_t, c_t = xs
        s = jnp.exp(dt_t[:, None] * a) * s + (dt_t * x_t)[:, None] * b_t[None, :]
        return s, s @ c_t

    _, sc = jax.lax.scan(
        step, jnp.zeros((di, n), jnp.float32),
        (x, dt, dbc[:, r : r + n], dbc[:, r + n :]),
    )
    m = sc + f32(p["ssm_d"]) * x
    return C.matmul(m * jax.nn.silu(z), p["wo"], precision), m


def diff_attention(h, p, kv, *, nh, nkv, window, lam0, eps, precision):
    """``h [T, H]`` (normed) → (the attention sublayer's output, (k, v)
    [T, nkv, D] as projected). ``kv``: another layer's keys and values (a
    cross layer's, which has none), else None."""
    t = h.shape[0]
    f32 = lambda a: a.astype(jnp.float32)
    hd = p["wq"].shape[-1] // nh
    q = (C.matmul(h, p["wq"], precision) + f32(p["bq"])).reshape(t, nh, hd)
    if kv is None:
        kv = tuple(
            (C.matmul(h, p[f"w{n}"], precision) + f32(p[f"b{n}"])).reshape(t, nkv, hd)
            for n in "kv"
        )
    k, v = kv
    pairs, per = nh // 2, (nh // 2) // (nkv // 2)  # pairs; pairs a KV pair
    q1, q2 = q[:, 0::2], q[:, 1::2]  # [T, pairs, D]
    k1 = jnp.repeat(k[:, 0::2], per, axis=1)
    k2 = jnp.repeat(k[:, 1::2], per, axis=1)
    vv = jnp.repeat(jnp.concatenate([v[:, 0::2], v[:, 1::2]], axis=-1), per, axis=1)
    keys = jnp.arange(t)

    def block(args):
        qa, qb, rows = args  # rows: the block's query positions
        age = rows[:, None] - keys[None, :]
        seen = (age >= 0) & ((age < window) if window else True)

        def one(qq, kk):
            sc = jnp.einsum("qhd,khd->hqk", qq, kk, precision=C.HIGHEST) * hd**-0.5
            pr = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
            return jnp.einsum("hqk,khd->qhd", pr, vv, precision=C.HIGHEST)

        return one(qa, k1), one(qb, k2)

    b = _query_block(t)
    split = lambda a: a.reshape((t // b, b) + a.shape[1:])
    a1, a2 = jax.lax.map(block, (split(q1), split(q2), split(keys)))
    a1, a2 = (a.reshape(t, pairs, 2 * hd) for a in (a1, a2))
    lq1, lk1, lq2, lk2 = f32(p["diff_lam"])
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lam0
    o = C.rms_norm(a1 - lam * a2, 1.0 + f32(p["diff_norm"]), eps) * (1.0 - lam0)
    return C.matmul(o.reshape(t, nh * hd), p["wo"], precision) + f32(p["bo"]), kv


@partial(jax.jit, static_argnames=("kind", "nh", "nkv", "window", "eps", "precision"))
def _layer(x, p, lam0, m, kv, *, kind, nh, nkv, window, eps, precision):
    """→ (x, m, kv): the residual stream after the layer, and what the
    layers further up read of it (a mamba layer's m, the full layer's
    keys and values), else what came in."""
    h = C.layer_norm_1p(x, p["attn_norm"], eps)
    if kind == "mamba":
        out, m = selective_scan(h, p, precision)
    elif kind == "gmu":
        gate = jax.nn.silu(C.matmul(h, p["gmu_w1"], precision))
        out = C.matmul(m * gate, p["wo"], precision)
    else:
        out, new = diff_attention(
            h, p, kv if kind == "cross" else None, nh=nh, nkv=nkv,
            window=window if kind == "window" else 0, lam0=lam0, eps=eps,
            precision=precision,
        )
        if kind == "full":
            kv = new
    x = x + out
    h = C.layer_norm_1p(x, p["mlp_norm"], eps)
    return x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"], precision), m, kv


_STACK = {
    "mamba": "mamba_layers", "window": "window_layers", "full": "layers",
    "gmu": "gmu_layers", "cross": "cross_layers",
}


def hidden_states(cfg, params, tokens, precision="f32"):
    """tokens [T] → final-layer residual stream [T, H] (float32)."""
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        x = params["embed"][tokens].astype(jnp.float32)
        t, h = x.shape
        nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        kinds = layer_kinds(cfg["num_hidden_layers"], cfg["mb_per_layer"])
        m = jnp.zeros((t, EXPAND * h), jnp.float32)
        kv = (jnp.zeros((t, nkv, h // nh), jnp.float32),) * 2
        seen = dict.fromkeys(_STACK, 0)
        for l, kind in enumerate(kinds):
            at, seen[kind] = seen[kind], seen[kind] + 1
            x, m, kv = _layer(
                x, C.layer_slice(params[_STACK[kind]], at),
                jnp.float32(0.8 - 0.6 * math.exp(-0.3 * l)), m, kv, kind=kind,
                nh=nh, nkv=nkv, window=int(cfg["sliding_window"]),
                eps=float(cfg["layer_norm_eps"]), precision=precision,
            )
        return x


def final_norm(cfg, params):
    return lambda h: C.layer_norm_1p(h, params["final_norm"], cfg["layer_norm_eps"])


def head(cfg, params, hidden, ids, precision="f32"):
    """The head is the embedding's transpose (tied)."""
    with jax.default_matmul_precision("highest"):
        return C.head_stats(hidden, final_norm(cfg, params), params["embed"].T, ids, precision)
