"""Linear-attention mixer: a gated delta rule with a decay a channel
(KDA, arXiv:2510.26692) behind a causal depthwise convolution.

A layer of kind ``"linear"`` (``LlamaConfig.layer_types``) keeps no keys
and values. A sequence's whole past is a state ``S`` [heads, D(k), D(v)]
in float32 and the last ``linear_conv - 1`` rows of the projections the
convolution still reads (its "tail"). With ``h`` the normed hidden:

    q, k, v = split(silu(conv(h Wqkv)));  q = l2(q) / sqrt(D);  k = l2(k)
    g = floor * sigmoid(exp(A_log) * (h Wg + dt_bias))     in (floor, 0)
    beta = sigmoid(h Wb)                                    one a head
    S' = Diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T;   o_t = S_t^T q_t
    y = (RMSNorm_head(o_t) * sigmoid(h Wog)) Wo

THE recurrence has two forms here, and nothing else computes it:

- :func:`token_rule`: one token a sequence (decode, the macro-step);
- :func:`chunk_rule`: many tokens a sequence in blocks of :data:`BLOCK`,
  each block solved in closed form (a unit lower-triangular system of
  the block's size) and the state carried from block to block: prefill
  chunks, packed waves and the verify step. A block's cumulative
  log-decay is at most ``BLOCK * |floor|`` = 80 for the published floor
  of -5, which float32's exponent holds both ways (e^88): the bounded
  gate is what lets the block be factored as (k e^G)(k e^-G)^T.

A token with ``beta = 0`` and ``g = 0`` leaves the state as it was: that
is how padding, pad rows and rejected drafts are kept out of it
(:func:`mix`'s ``valid``).
"""

import math

import jax
import jax.numpy as jnp

#: tokens a block of the chunkwise form solves at once
BLOCK = 16

_HI = jax.lax.Precision.HIGHEST


def leaf_shapes(c, n: int) -> dict:
    """A stack of ``n`` linear mixers' leaves → ``{name: (shape, init)}``
    with init ``"normal"`` | ``"out"`` (the projection back into the
    residual stream) | ``"conv"`` | ``"ones"`` | ``"small"`` (float32,
    std 0.02: the gate's scale and bias). The one statement of the
    mixer's weight tree, for ``init_params``, ``param_specs`` and the
    parameter count."""
    h, nh, d = c.hidden_size, c.n_heads, c.linear_head_dim
    p = nh * d
    return {
        "lin_wqkv": ((n, h, 3 * p), "normal"),
        "lin_conv": ((n, c.linear_conv, 3 * p), "conv"),
        "lin_wg": ((n, h, p), "normal"),
        "lin_a_log": ((n, nh), "small"),
        "lin_dt_bias": ((n, p), "small"),
        "lin_wb": ((n, h, nh), "normal"),
        "lin_wog": ((n, h, p), "normal"),
        "lin_norm": ((n, d), "ones"),
        "wo": ((n, p, h), "out"),
    }


def n_params(c) -> int:
    """Parameters of one linear mixer (its pre-norm left out)."""
    return sum(math.prod(s[1:]) for s, _ in leaf_shapes(c, 1).values())


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def conv_rows(pre, tail, w):
    """Causal depthwise convolution: ``pre`` [B, T, P] the new rows,
    ``tail`` [B, K-1, P] the rows before them, ``w`` [K, P] (``w[-1]``
    multiplies the current row) → [B, T, P] float32, as K shifted
    adds."""
    k = w.shape[0]
    t = pre.shape[1]
    rows = jnp.concatenate([tail, pre], axis=1).astype(jnp.float32)
    w = w.astype(jnp.float32)
    return sum(rows[:, j : j + t] * w[j] for j in range(k))


def next_tail(pre, tail, n):
    """The tail after the first ``n`` [B] of ``pre``'s rows: the last
    K-1 rows of (tail, pre[:n])."""
    km1 = tail.shape[1]
    rows = jnp.concatenate([tail, pre], axis=1)  # [B, K-1+T, P]
    at = n[:, None] + jnp.arange(km1)[None, :]  # rows n .. n+K-2
    return jnp.take_along_axis(rows, at[:, :, None], axis=1)


def token_rule(q, k, v, g, beta, state):
    """One token a sequence: q, k, v, g [B, Hh, D] float32, beta
    [B, Hh], state [B, Hh, D(k), D(v)] float32 → (o [B, Hh, D], state).
    Multiplies and sums on the vector unit, exact in float32: a matmul
    would round the state to bfloat16 on the way in."""
    s = state * jnp.exp(g)[..., None]
    # the two reductions of the decayed state share its one read
    sk = jnp.sum(s * k[..., None], axis=-2)  # S'^T k
    sq = jnp.sum(s * q[..., None], axis=-2)  # S'^T q
    u = beta[..., None] * (v - sk)
    o = sq + jnp.sum(q * k, axis=-1, keepdims=True) * u
    return o, s + k[..., None] * u[..., None, :]


def _unit_lower_inverse(n_mat):
    """(I + N)^-1 for strictly lower triangular ``N`` [..., C, C]:
    (I - N)(I + N^2)(I + N^4)..., the series ends at N^C = 0."""
    c = n_mat.shape[-1]
    eye = jnp.eye(c, dtype=n_mat.dtype)
    r = eye - n_mat
    if c <= 2:
        return r
    pw = jnp.matmul(n_mat, n_mat, precision=_HI)
    for i in range(math.ceil(math.log2(c)) - 1):
        r = r + jnp.matmul(r, pw, precision=_HI)
        if i < math.ceil(math.log2(c)) - 2:
            pw = jnp.matmul(pw, pw, precision=_HI)
    return r


def chunk_rule(q, k, v, g, beta, state, block: int = BLOCK):
    """Many tokens a sequence: q, k, v, g [B, T, Hh, D] float32, beta
    [B, T, Hh], state [B, Hh, D, D] float32 → (o [B, T, Hh, D], state).

    Within a block, with ``G`` the running sum of ``g`` and ``S0`` the
    state before it:  U = (I + Diag(beta) A)^-1 Diag(beta) (V - (K e^G) S0)
    with A[i, j] = (k_i e^G_i) . (k_j e^-G_j) for j < i; O = (Q e^G) S0 +
    tril(B) U with B[i, j] = (q_i e^G_i) . (k_j e^-G_j); the state after
    it e^G_C S0 + (K e^(G_C - G))^T U. What does not read ``S0`` is
    computed for all blocks at once; a scan of T / block trips carries
    the state."""
    b, t, nh, d = q.shape
    cb = min(block, t)
    pad = -t % cb
    if pad:  # beta = 0 and g = 0: the state stays
        widen = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        q, k, v, g, beta = (widen(a) for a in (q, k, v, g, beta))
    nb = (t + pad) // cb
    # [NB, B, Hh, C, D]
    lay = lambda a: a.reshape(b, nb, cb, nh, d).transpose(1, 0, 3, 2, 4)
    q, k, v, g = (lay(a) for a in (q, k, v, g))
    beta = beta.reshape(b, nb, cb, nh).transpose(1, 0, 3, 2)  # [NB,B,Hh,C]
    gc = jnp.cumsum(g, axis=-2)
    kd, ki, qd = k * jnp.exp(gc), k * jnp.exp(-gc), q * jnp.exp(gc)
    g_end = gc[..., -1:, :]  # [NB,B,Hh,1,D]
    k_end = k * jnp.exp(g_end - gc)
    pair = lambda x, y: jnp.einsum("nbhid,nbhjd->nbhij", x, y, precision=_HI)
    lower = jnp.tril(jnp.ones((cb, cb), bool), -1)
    a = jnp.where(lower, pair(kd, ki), 0.0) * beta[..., :, None]
    solve = _unit_lower_inverse(a) * beta[..., None, :]  # (I + bA)^-1 Diag(b)
    bm = jnp.where(lower | jnp.eye(cb, dtype=bool), pair(qd, ki), 0.0)

    def one_block(s, xs):
        kd, qd, v, solve, bm, k_end, g_end = xs
        mm = lambda eq, x, y: jnp.einsum(eq, x, y, precision=_HI)
        u = mm("bhij,bhjv->bhiv", solve, v - mm("bhik,bhkv->bhiv", kd, s))
        o = mm("bhik,bhkv->bhiv", qd, s) + mm("bhij,bhjv->bhiv", bm, u)
        s = s * jnp.exp(g_end).swapaxes(-1, -2) + mm("bhik,bhiv->bhkv", k_end, u)
        return s, o

    state, o = jax.lax.scan(one_block, state, (kd, qd, v, solve, bm, k_end, g_end))
    o = o.transpose(1, 0, 3, 2, 4).reshape(b, nb * cb, nh, d)
    return o[:, :t], state


def gate_inputs(h, layer, c, tail, valid=None):
    """The recurrence's inputs of ``h`` [B, T, H] (the normed hidden) →
    (q, k, v, g [B, T, Hh, D] float32, beta [B, T, Hh] float32, pre
    [B, T, 3P] the projections' rows before the convolution, in the
    dtype the tail stores). ``tail`` [B, K-1, 3P]: the rows before
    them. A token ``valid`` [B, T] marks dead gets beta = 0 and g = 0."""
    b, t, _ = h.shape
    nh, d = c.n_heads, c.linear_head_dim
    f32 = jnp.float32
    proj = lambda w: jnp.einsum(
        "bte,ed->btd", h, layer[w].astype(h.dtype), preferred_element_type=f32
    )
    pre = proj("lin_wqkv").astype(tail.dtype)
    y = jax.nn.silu(conv_rows(pre, tail, layer["lin_conv"]))
    q, k, v = (a.reshape(b, t, nh, d) for a in jnp.split(y, 3, axis=-1))
    q, k = _l2(q) * d**-0.5, _l2(k)
    rate = jnp.exp(layer["lin_a_log"].astype(f32))[:, None]  # [Hh, 1]
    z = proj("lin_wg") + layer["lin_dt_bias"].astype(f32)
    g = c.linear_gate_floor * jax.nn.sigmoid(rate * z.reshape(b, t, nh, d))
    beta = jax.nn.sigmoid(proj("lin_wb"))
    if valid is not None:
        g = jnp.where(valid[..., None, None], g, 0.0)
        beta = jnp.where(valid[..., None], beta, 0.0)
    return q, k, v, g, beta, pre


def gated_out(o, h, layer, c):
    """The recurrence's output ``o`` [B, T, Hh, D] float32 → [B, T, P]
    in the model's dtype: a norm a head, the elementwise output gate.
    (``wo`` and the residual are the caller's, as for attention.)"""
    b, t = o.shape[:2]
    eps = c.norm_eps
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    o = o * layer["lin_norm"].astype(jnp.float32)
    gate = jax.nn.sigmoid(jnp.einsum(
        "bte,ed->btd", h, layer["lin_wog"].astype(h.dtype),
        preferred_element_type=jnp.float32,
    ))
    return (o.reshape(b, t, -1) * gate).astype(h.dtype)


def rule(q, k, v, g, beta, state):
    """The recurrence over [B, T, ...] inputs in the form its length
    asks for → (o [B, T, Hh, D], state)."""
    if q.shape[1] == 1:
        o, state = token_rule(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], state)
        return o[:, None], state
    return chunk_rule(q, k, v, g, beta, state)


def mix_parts(h, layer, c, state, tail, valid=None):
    """The mixer on ``h`` [B, T, H] from ``state`` and ``tail`` → (y
    [B, T, P] for ``wo``, the state after, the recurrence's inputs a
    position (k, v, g, beta, pre): what a caller that must not advance
    the state yet keeps of them)."""
    with jax.named_scope("dtpu.linear"):
        q, k, v, g, beta, pre = gate_inputs(h, layer, c, tail, valid)
        with jax.named_scope("dtpu.linear.state"):
            o, state = rule(q, k, v, g, beta, state)
        return gated_out(o, h, layer, c), state, (k, v, g, beta, pre)


def mix(h, layer, c, state, tail, valid=None, counts=None):
    """The mixer on ``h`` [B, T, H] from ``state`` [B, Hh, D, D] and
    ``tail`` [B, K-1, 3P] → (y [B, T, P] for ``wo``, state, tail).
    ``valid`` [B, T]: the real tokens (a prefix of each row), of which
    row b has ``counts[b]`` (None: all T). (:func:`mix_parts` and the
    tail, written out: the order of the traced operations is what the
    serving programs' pins hold.)"""
    with jax.named_scope("dtpu.linear"):
        q, k, v, g, beta, pre = gate_inputs(h, layer, c, tail, valid)
        with jax.named_scope("dtpu.linear.state"):
            o, state = rule(q, k, v, g, beta, state)
        if counts is None:
            counts = jnp.full((h.shape[0],), h.shape[1], jnp.int32)
        return gated_out(o, h, layer, c), state, next_tail(pre, tail, counts)


def zeros(c, batch: int, dtype) -> tuple:
    """(state, tail) of ``batch`` sequences that have seen nothing."""
    nh, d = c.n_heads, c.linear_head_dim
    return (
        jnp.zeros((batch, nh, d, d), jnp.float32),
        jnp.zeros((batch, c.linear_conv - 1, 3 * nh * d), dtype),
    )
