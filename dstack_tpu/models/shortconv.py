"""Gated short-convolution mixer (the LFM2 family's ``conv`` operator):
a causal depthwise convolution over the HIDDEN itself, between two
elementwise gates.

A layer of kind ``"conv"`` (``LlamaConfig.layer_types``) keeps no keys
and values and no recurrent state. With ``h`` the normed hidden and
K = ``conv_taps``:

    (B, C, z) = split3(h W_in)           W_in [H, 3H], in that order
    u_t = B_t * z_t
    c_t = sum_j w_j * u_{t-K+1+j}        w [K, H], w_{K-1} on the current
                                         row, u before the sequence = 0,
                                         no activation
    y_t = C_t * c_t                      then ``wo`` [H, H], the caller's

A sequence's whole past on such a layer is the last K - 1 rows of ``u``
(its "tail"), whatever the context. THE operator is :func:`mix`, one
function of ``kda.conv_rows`` for one token a sequence (decode, the
macro-step) and for many (prefill chunks, packed waves, the verify
step, the training forward from a tail of zeros): products in float32,
``u`` rounded to the dtype the tail stores BEFORE the convolution reads
it, so that a row convolved now and a row read back from the tail later
are the same numbers.

A token past a row's ``counts`` (padding, a pad row, a dead slot, a
rejected draft) leaves the tail as it was: the tail after a call is the
last K - 1 rows of (tail, the row's first ``counts`` new rows).
"""

import math

import jax
import jax.numpy as jnp

from dstack_tpu.models.kda import conv_rows, next_tail


def leaf_shapes(c, n: int) -> dict:
    """A stack of ``n`` mixers' leaves → ``{name: (shape, init)}`` with
    init ``"normal"`` | ``"out"`` | ``"conv"`` as ``kda.leaf_shapes``
    has them: the one statement of the mixer's weight tree."""
    h = c.hidden_size
    return {
        "conv_win": ((n, h, 3 * h), "normal"),
        "conv_w": ((n, c.conv_taps, h), "conv"),
        "wo": ((n, h, h), "out"),
    }


def n_params(c) -> int:
    """Parameters of one mixer (its pre-norm left out)."""
    return sum(math.prod(s[1:]) for s, _ in leaf_shapes(c, 1).values())


def mix_parts(h, layer, c, tail):
    """The operator on ``h`` [B, T, H] from ``tail`` [B, K-1, H] → (y
    [B, T, H] for ``wo``, (``u`` [B, T, H],): the new rows as the tail
    stores them, what a caller that must not move the tail yet keeps)."""
    with jax.named_scope("dtpu.conv"):
        bcz = jnp.einsum(
            "bte,ed->btd", h, layer["conv_win"].astype(h.dtype),
            preferred_element_type=jnp.float32,
        )
        gate_in, gate_out, z = jnp.split(bcz, 3, axis=-1)
        u = (gate_in * z).astype(tail.dtype)
        y = gate_out * conv_rows(u, tail, layer["conv_w"])
        return y.astype(h.dtype), (u,)


def mix(h, layer, c, tail, valid=None, counts=None):
    """The operator on ``h`` [B, T, H] from ``tail`` [B, K-1, H] → (y
    [B, T, H] for ``wo``, the tail after). ``valid`` [B, T]: the real
    tokens (a prefix of each row), of which row b has ``counts[b]``
    (both None: all T)."""
    y, (u,) = mix_parts(h, layer, c, tail)
    if counts is None:
        counts = (
            jnp.full((h.shape[0],), h.shape[1], jnp.int32) if valid is None
            else jnp.sum(valid, axis=1).astype(jnp.int32)
        )
    with jax.named_scope("dtpu.conv.tail"):
        return y, next_tail(u, tail, counts)


def zeros(c, batch: int, dtype) -> tuple:
    """(tail,) of ``batch`` sequences that have seen nothing."""
    return (jnp.zeros((batch, c.conv_taps - 1, c.hidden_size), dtype),)
