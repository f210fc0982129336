"""Selective state-space mixer (Mamba-1, arXiv:2312.00752): a diagonal
recurrence whose step size and input and output maps follow the token,
behind a causal depthwise convolution.

A layer of kind ``"mamba"`` (``LlamaConfig.layer_types``) keeps no keys
and values. A sequence's whole past is a state ``S`` [d_inner, N] in
float32 (N = ``ssm_state``; held TRANSPOSED, [N, d_inner], the channels
on the lanes: declared [d_inner, 16] the TPU compiler re-laid the whole
leaf out that way and back, a token step) and the last ``ssm_conv - 1``
rows of the convolution's input (its "tail"). With ``h`` the normed hidden,
d_inner = ``ssm_expand`` * hidden and R = ``ssm_rank``:

    (x, z) = split2(h W_in)                      W_in [H, 2 d_inner]
    x_t = silu(sum_j w_j * x_{t-K+1+j} + b)      causal depthwise, bias
    (d, B_t, C_t) = split(x_t W_x; R, N, N)      W_x [d_inner, R + 2N]
    D_t = softplus(d W_dt + b_dt)                W_dt [R, d_inner]
    A = -exp(A_log)                              [d_inner, N]
    S_t = exp(D_t (x) A) * S_{t-1} + (D_t * x_t) (x) B_t
    m_t = S_t C_t + D * x_t
    y_t = m_t * silu(z_t)                        then ``wo``, the caller's

``m`` (the scan's output before the gate) is handed out beside ``y``: a
gated memory unit further up the model reads it at the same position.
The step size, ``exp(D_t (x) A)`` and the state are float32; the state
is stored float32, as ``kda``'s.

THE recurrence is :func:`scan`: a ``lax.scan`` over the tokens in order
(one token a sequence in decode; a prefill chunk's, a wave's or a verify
step's many), the state [B, N, d_inner] its carry. Nothing is held a
token: a chunk of 256 costs the state twice and a row of ``m``, where an
associative scan would hold [T, d_inner, N] twice a row (84 MB each at
256 x 5120 x 16).

A token with ``D_t = 0`` leaves the state as it was (``exp(0) = 1``, and
nothing is added): that is how padding, pad rows, dead slots and
rejected drafts are kept out of it (:func:`mix`'s ``valid``); the tail
after a call is the last K - 1 rows of (tail, the row's first
``counts`` new rows), as ``shortconv``'s.
"""

import math

import jax
import jax.numpy as jnp

from dstack_tpu.models.kda import conv_rows, next_tail

#: tokens of the scan a loop trip holds (its body unrolled so many times)
UNROLL = 8


def leaf_shapes(c, n: int) -> dict:
    """A stack of ``n`` mixers' leaves → ``{name: (shape, init)}`` with
    init ``"normal"`` | ``"out"`` | ``"conv"`` | ``"zeros"`` as
    ``kda.leaf_shapes`` has them: the one statement of the mixer's
    weight tree. (A checkpoint's ``A_log`` is log(1..N) a channel and
    its ``dt`` bias near -4: a LONG memory. A seeded normal draw gives
    A = -1 and a step of softplus(0) = 0.69, a state that forgets in a
    few tokens: ``tests/compute/test_mamba.py`` draws the long one.)"""
    h, di, ns, r = c.hidden_size, c.ssm_inner, c.ssm_state, c.ssm_rank
    return {
        "ssm_win": ((n, h, 2 * di), "normal"),
        "ssm_conv": ((n, c.ssm_conv, di), "conv"),
        "ssm_conv_b": ((n, di), "zeros"),
        "ssm_wx": ((n, di, r + 2 * ns), "normal"),
        "ssm_wdt": ((n, r, di), "normal"),
        "ssm_dt_b": ((n, di), "normal"),
        "ssm_a_log": ((n, di, ns), "normal"),
        "ssm_d": ((n, di), "normal"),
        "wo": ((n, di, h), "out"),
    }


def n_params(c) -> int:
    """Parameters of one mixer (its pre-norm left out)."""
    return sum(math.prod(s[1:]) for s, _ in leaf_shapes(c, 1).values())


def scan(x, dt, b_in, c_out, a, state):
    """The recurrence over the tokens in order: ``x``, ``dt`` [B, T, di]
    float32, ``b_in``, ``c_out`` [B, T, N] float32, ``a`` [N, di],
    ``state`` [B, N, di] float32 → (S_t C_t [B, T, di], the state after).
    Multiplies and sums on the vector unit, exact in float32."""

    def one(s, xs):
        x_t, dt_t, b_t, c_t = xs  # [B, di], [B, di], [B, N], [B, N]
        s = jnp.exp(dt_t[:, None, :] * a) * s + (dt_t * x_t)[:, None, :] * b_t[..., None]
        return s, jnp.sum(s * c_t[..., None], axis=1)

    t = x.shape[1]
    if t == 1:
        state, m = one(state, (x[:, 0], dt[:, 0], b_in[:, 0], c_out[:, 0]))
        return m[:, None], state
    lead = lambda v: v.swapaxes(0, 1)
    state, m = jax.lax.scan(
        one, state, (lead(x), lead(dt), lead(b_in), lead(c_out)),
        unroll=min(t, UNROLL),
    )
    return lead(m), state


def scan_inputs(h, layer, c, tail, valid=None):
    """The recurrence's inputs of ``h`` [B, T, H] (the normed hidden) →
    (x, dt [B, T, di], B, C [B, T, N], all float32, z [B, T, di] the
    gate's input, pre [B, T, di] the convolution's new rows in the dtype
    the tail stores). ``tail`` [B, K-1, di]: the rows before them. A
    token ``valid`` [B, T] marks dead gets a step of 0."""
    f32 = jnp.float32
    di, ns, r = c.ssm_inner, c.ssm_state, c.ssm_rank
    proj = lambda v, w: jnp.einsum(
        "btk,kd->btd", v.astype(h.dtype), layer[w].astype(h.dtype),
        preferred_element_type=f32,
    )
    xz = proj(h, "ssm_win")
    pre, z = xz[..., :di].astype(tail.dtype), xz[..., di:]
    x = jax.nn.silu(
        conv_rows(pre, tail, layer["ssm_conv"]) + layer["ssm_conv_b"].astype(f32)
    )
    dbc = proj(x, "ssm_wx")
    dt = jax.nn.softplus(
        proj(dbc[..., :r], "ssm_wdt") + layer["ssm_dt_b"].astype(f32)
    )
    if valid is not None:
        dt = jnp.where(valid[..., None], dt, 0.0)
    return x, dt, dbc[..., r : r + ns], dbc[..., r + ns :], z, pre


def gated_out(sc, x, z, layer, dtype):
    """The scan's ``S_t C_t`` → (y [B, T, di] for ``wo``, m [B, T, di]
    the scan's output before the gate), both in ``dtype``."""
    m = sc + layer["ssm_d"].astype(jnp.float32) * x
    return (m * jax.nn.silu(z)).astype(dtype), m.astype(dtype)


def mix_parts(h, layer, c, state, tail, valid=None):
    """The mixer on ``h`` [B, T, H] from ``state`` and ``tail`` → (y
    [B, T, di] for ``wo``, m, the state after, the recurrence's inputs a
    position (x, dt, B, pre): what a caller that must not advance the
    state yet keeps of them)."""
    with jax.named_scope("dtpu.ssm"):
        x, dt, b_in, c_out, z, pre = scan_inputs(h, layer, c, tail, valid)
        a = -jnp.exp(layer["ssm_a_log"].astype(jnp.float32)).T
        with jax.named_scope("dtpu.ssm.scan"):
            sc, state = scan(x, dt, b_in, c_out, a, state)
        y, m = gated_out(sc, x, z, layer, h.dtype)
        return y, m, state, (x, dt, b_in, pre)


def mix(h, layer, c, state, tail, valid=None, counts=None):
    """The mixer on ``h`` [B, T, H] from ``state`` [B, N, di] and
    ``tail`` [B, K-1, di] → ((y [B, T, di] for ``wo``, m), state, tail).
    ``valid`` [B, T]: the real tokens (a prefix of each row), of which
    row b has ``counts[b]`` (both None: all T)."""
    y, m, state, (*_, pre) = mix_parts(h, layer, c, state, tail, valid)
    if counts is None:
        counts = (
            jnp.full((h.shape[0],), h.shape[1], jnp.int32) if valid is None
            else jnp.sum(valid, axis=1).astype(jnp.int32)
        )
    with jax.named_scope("dtpu.ssm.scan"):
        return (y, m), state, next_tail(pre, tail, counts)


def advance(state, tail, layer, inputs, n_tokens):
    """``state`` and ``tail`` after the first ``n_tokens`` [B] of the
    positions whose ``inputs`` (:func:`mix_parts`') were kept: the verify
    step's second half, once the count of drafts that stand is known."""
    x, dt, b_in, pre = inputs
    real = jnp.arange(x.shape[1])[None, :] < n_tokens[:, None]
    a = -jnp.exp(layer["ssm_a_log"].astype(jnp.float32)).T
    _, state = scan(
        x, jnp.where(real[..., None], dt, 0.0), b_in, jnp.zeros_like(b_in),
        a, state,
    )
    return state, next_tail(pre, tail, n_tokens)


def zeros(c, batch: int, dtype) -> tuple:
    """(state, tail) of ``batch`` sequences that have seen nothing."""
    return (
        jnp.zeros((batch, c.ssm_state, c.ssm_inner), jnp.float32),
        jnp.zeros((batch, c.ssm_conv - 1, c.ssm_inner), dtype),
    )
