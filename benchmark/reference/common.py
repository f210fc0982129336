"""Shared pieces of the plain references: matmul by precision, norms,
rotary tables, causal attention and the blocked output head.

Everything is straightforward ``jax.numpy`` in float32 under
``default_matmul_precision("highest")`` (on a TPU a float32 matmul is
otherwise done in bfloat16 passes). Nothing here imports the program.

``precision`` selects how a *weight* matmul is computed:

- ``"f32"``: the reference proper.
- ``"int8"``: the control. Weights are rounded to int8 with one scale an
  output channel and activations to int8 with one scale a token (W8A8,
  the precision step below bfloat16 that the v5e's int8 peak invites).
  The products are exact in float32; only the rounding differs.
"""

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST

#: std of a seeded normal leaf in every ``leaf_shapes``; a projection back
#: into the residual stream takes ``STD / sqrt(2 * n_layers)``
STD = 0.02


def _q8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale), scale


def matmul(x, w, precision: str):
    """``x [..., K] @ w [K, N]`` → float32."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if precision == "f32":
        return jnp.matmul(x, w, precision=HIGHEST)
    if precision == "int8":
        xq, xs = _q8(x, -1)
        wq, ws = _q8(w, 0)
        return jnp.matmul(xq, wq, precision=HIGHEST) * xs * ws
    raise ValueError(f"unknown precision {precision!r}")


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w.astype(
        jnp.float32
    )


def layer_norm_1p(x, w2, eps):
    """Nemotron LayerNorm1P; ``w2 [2, H]`` holds (scale-1, bias)."""
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    w2 = w2.astype(jnp.float32)
    return (x - mu) * jax.lax.rsqrt(var + eps) * (1.0 + w2[0]) + w2[1]


def causal_attention(q, k, v, scale):
    """q, k ``[T, Hq, D]``, v ``[T, Hq, Dv]`` → ``[T, Hq, Dv]``; float32."""
    t = q.shape[0]
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * scale
    mask = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)


def layer_slice(stacked: dict, i: int) -> dict:
    return {k: v[i] for k, v in stacked.items()}


def head_stats(hidden, norm_fn, lm_head, ids, precision: str, block: int = 32768):
    """Final norm + output head over ``hidden [P, H]``, in vocabulary
    blocks → (best logit [P], its id [P], logits at ``ids [P, K]``)."""
    h = norm_fn(hidden)
    v = lm_head.shape[1]
    ids = jnp.asarray(ids, jnp.int32)

    @jax.jit
    def one(h, w, lo, ids):
        logits = matmul(h, w, precision)  # [P, blk]
        loc = ids - lo
        inside = (loc >= 0) & (loc < w.shape[1])
        got = jnp.take_along_axis(logits, jnp.clip(loc, 0, w.shape[1] - 1), axis=1)
        return (
            logits.max(-1), logits.argmax(-1).astype(jnp.int32) + lo,
            jnp.where(inside, got, -jnp.inf),
        )

    best = best_id = vals = None
    for lo in range(0, v, block):
        m, a, g = one(h, lm_head[:, lo:lo + block], jnp.int32(lo), ids)
        if best is None:
            best, best_id, vals = m, a, g
        else:
            take = m > best
            best_id = jnp.where(take, a, best_id)
            best = jnp.maximum(best, m)
            vals = jnp.maximum(vals, g)
    return best, best_id, vals


def sampling_stats(hidden, norm_fn, lm_head, ids, temperature: float, block: int = 32768):
    """For tokens ``ids [P]`` that a server says it sampled at
    ``temperature``: (logit of the token, mean logit under
    softmax(logits / temperature)), both ``[P]`` and divided by the
    temperature. Tokens drawn from that distribution have the same
    expectation as its mean logit, so the mean difference over many
    positions is 0 for a sound sampler, above 0 for one that samples
    sharper than stated (greedy at the limit) and below 0 for a flatter
    one. The vocabulary goes by in blocks with a running softmax."""
    h = norm_fn(hidden)
    ids = jnp.asarray(ids, jnp.int32)

    @jax.jit
    def one(h, w, lo, ids, m, s, u, got):
        z = matmul(h, w, "f32") / temperature  # [P, blk]
        m_new = jnp.maximum(m, z.max(-1))
        e = jnp.exp(z - m_new[:, None])
        keep = jnp.exp(m - m_new)
        loc = ids - lo
        inside = (loc >= 0) & (loc < w.shape[1])
        z_id = jnp.take_along_axis(z, jnp.clip(loc, 0, w.shape[1] - 1)[:, None], axis=1)[:, 0]
        return (
            m_new, s * keep + e.sum(-1), u * keep + (z * e).sum(-1),
            jnp.where(inside, z_id, got),
        )

    n = h.shape[0]
    m = jnp.full((n,), -jnp.inf, jnp.float32)
    s = u = got = jnp.zeros((n,), jnp.float32)
    for lo in range(0, lm_head.shape[1], block):
        m, s, u, got = one(h, lm_head[:, lo:lo + block], jnp.int32(lo), ids, m, s, u, got)
    return got, u / s
