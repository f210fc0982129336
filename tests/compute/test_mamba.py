"""The selective state-space mixer (``models/mamba.py``) at toy widths
on the CPU, float32: against ``transformers``' ``MambaMixer.
slow_forward`` (the same Mamba-1 recurrence, written apart); one token
at a time from a tail and a state against the whole sequence at once;
and what a chunk's edge, ``counts``, padding and a rejected draft leave
of state and tail.

The draws have a LONG memory, as a checkpoint has it: ``A_log =
log(1..16)`` a channel and a step bias near -4 (a step of 0.02: the
slowest channel forgets over fifty tokens). Under the benchmark's seeded
std-0.02 draw A = -1 and the step is softplus(0) = 0.69: a state forgets
in a few tokens and an error older than that shows nowhere.

Tolerance. float32 against float32, rounding order only: 1e-5.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dstack_tpu.models import llama, mamba

C = llama.LlamaConfig(
    vocab_size=64, hidden_size=64, n_layers=1, n_heads=2, n_kv_heads=2, head_dim=32,
    intermediate_size=64, dtype=jnp.float32, remat=False, ssm_state=16, ssm_conv=4,
    ssm_expand=2,
)
TIGHT = 1e-5
DI, N, K, R = C.ssm_inner, C.ssm_state, C.ssm_conv, C.ssm_rank


@pytest.fixture(scope="module")
def layer():
    rng = np.random.default_rng(0)
    f = lambda *shape, scale=1.0: jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)
    return {
        "ssm_win": f(64, 2 * DI, scale=0.1), "ssm_conv": f(K, DI, scale=0.5),
        "ssm_conv_b": f(DI, scale=0.1), "ssm_wx": f(DI, R + 2 * N, scale=0.2),
        "ssm_wdt": f(R, DI, scale=0.3), "ssm_dt_b": -4.0 + f(DI, scale=0.5),
        "ssm_a_log": jnp.broadcast_to(jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)), (DI, N)),
        "ssm_d": 1.0 + f(DI, scale=0.1), "wo": f(DI, 64, scale=0.1),
    }


def _h(b, t, seed=1):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(b, t, 64)), jnp.float32)


def _whole(layer, h):
    with jax.default_matmul_precision("highest"):
        (y, m), state, tail = mamba.mix(h, layer, C, *mamba.zeros(C, h.shape[0], h.dtype))
        return np.asarray(y @ layer["wo"]), np.asarray(m), state, tail


def test_the_sizes_are_the_familys():
    assert (DI, N, K, R) == (128, 16, 4, 4)  # expand 2, ceil(64 / 16)
    assert mamba.n_params(C) == sum(
        int(np.prod(s[1:])) for s, _ in mamba.leaf_shapes(C, 1).values()
    ) == 64 * 256 + 5 * 128 + 128 * 36 + 5 * 128 + 128 * 16 + 128 + 128 * 64
    state, tail = mamba.zeros(C, 3, jnp.float32)
    assert state.shape == (3, N, DI) and state.dtype == jnp.float32  # channels on the lanes
    assert tail.shape == (3, K - 1, DI)


def test_agrees_with_transformers_slow_forward(layer):
    torch = pytest.importorskip("torch")
    from transformers import MambaConfig
    from transformers.models.mamba.modeling_mamba import MambaMixer

    hf = MambaMixer(MambaConfig(
        hidden_size=64, state_size=N, conv_kernel=K, expand=2, time_step_rank=R,
        use_bias=False, use_conv_bias=True, hidden_act="silu", num_hidden_layers=1,
    ), layer_idx=0).eval()
    t = lambda a: torch.tensor(np.asarray(a))
    with torch.no_grad():
        hf.in_proj.weight.copy_(t(layer["ssm_win"]).T)
        hf.conv1d.weight.copy_(t(layer["ssm_conv"]).T[:, None, :])
        hf.conv1d.bias.copy_(t(layer["ssm_conv_b"]))
        hf.x_proj.weight.copy_(t(layer["ssm_wx"]).T)
        hf.dt_proj.weight.copy_(t(layer["ssm_wdt"]).T)
        hf.dt_proj.bias.copy_(t(layer["ssm_dt_b"]))
        hf.A_log.copy_(t(layer["ssm_a_log"]))
        hf.D.copy_(t(layer["ssm_d"]))
        hf.out_proj.weight.copy_(t(layer["wo"]).T)
        h = _h(2, 70)
        want = hf.slow_forward(t(h)).numpy()
    got, *_ = _whole(layer, h)
    assert np.abs(got - want).max() < TIGHT
    assert np.abs(want).max() > 0.1  # and it is no comparison of zeros


def test_a_token_at_a_time_is_the_whole_sequence_at_once(layer):
    h = _h(2, 70)
    want, m_want, state_want, tail_want = _whole(layer, h)
    state, tail = mamba.zeros(C, 2, h.dtype)
    step = jax.jit(lambda h1, s, tl: mamba.mix(h1, layer, C, s, tl))
    with jax.default_matmul_precision("highest"):
        for i in range(70):
            (y, m), state, tail = step(h[:, i:i + 1], state, tail)
            assert np.abs(np.asarray(y @ layer["wo"])[:, 0] - want[:, i]).max() < TIGHT, i
            assert np.abs(np.asarray(m)[:, 0] - m_want[:, i]).max() < TIGHT, i
    assert np.abs(np.asarray(state) - np.asarray(state_want)).max() < TIGHT
    assert np.array_equal(np.asarray(tail), np.asarray(tail_want))
    # the memory is long: the state still holds what the first ten tokens left
    (_, _), late, _ = mamba.mix(h[:, 10:], layer, C, *mamba.zeros(C, 2, h.dtype))
    assert np.abs(np.asarray(late) - np.asarray(state_want)).max() > 100 * TIGHT


@pytest.mark.parametrize("edges", [(16, 32, 48), (1, 2, 3, 69), (37,)])
def test_chunk_edges_carry_state_and_tail(layer, edges):
    """Chunks of any length, one of a single token and one of two (less
    than the convolution's three rows of tail) among them."""
    h = _h(2, 70, seed=2)
    want, _, state_want, tail_want = _whole(layer, h)
    state, tail = mamba.zeros(C, 2, h.dtype)
    got = []
    with jax.default_matmul_precision("highest"):
        for lo, hi in zip((0,) + edges, edges + (70,)):
            (y, _), state, tail = mamba.mix(h[:, lo:hi], layer, C, state, tail)
            got.append(np.asarray(y @ layer["wo"]))
    assert np.abs(np.concatenate(got, axis=1) - want).max() < TIGHT
    assert np.abs(np.asarray(state) - np.asarray(state_want)).max() < TIGHT
    assert np.array_equal(np.asarray(tail), np.asarray(tail_want))


def test_padding_counts_and_a_dead_row_leave_state_and_tail_as_the_equations_say(layer):
    """Row 0 has 9 real tokens of 16, row 1 all 16, row 2 none (a pad
    row, a dead slot): each row's state and tail are those of its real
    tokens alone, row 2's untouched, bit for bit."""
    h = _h(3, 16, seed=3)
    counts = jnp.asarray([9, 16, 0], jnp.int32)
    valid = jnp.arange(16)[None, :] < counts[:, None]
    s0 = jnp.asarray(np.random.default_rng(4).normal(size=(3, N, DI)), jnp.float32)
    t0 = jnp.asarray(np.random.default_rng(5).normal(size=(3, K - 1, DI)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        (y, _), state, tail = mamba.mix(h, layer, C, s0, t0, valid, counts)
        for row, n in ((0, 9), (1, 16)):
            (y1, _), s1, t1 = mamba.mix(h[row:row + 1, :n], layer, C, s0[row:row + 1], t0[row:row + 1])
            assert np.abs(np.asarray(y)[row, :n] - np.asarray(y1)[0]).max() < TIGHT
            assert np.abs(np.asarray(state)[row] - np.asarray(s1)[0]).max() < TIGHT
            assert np.array_equal(np.asarray(tail)[row], np.asarray(t1)[0])
    assert np.array_equal(np.asarray(state)[2], np.asarray(s0)[2])
    assert np.array_equal(np.asarray(tail)[2], np.asarray(t0)[2])
    # counts follow from valid where they are not given
    (_, _), state2, tail2 = mamba.mix(h, layer, C, s0, t0, valid)
    assert np.array_equal(np.asarray(state2), np.asarray(state))
    assert np.array_equal(np.asarray(tail2), np.asarray(tail))


@pytest.mark.parametrize("stand", [1, 3, 5])
def test_a_rejected_draft_has_not_moved_the_state(layer, stand):
    """The verify step's two halves: ``mix_parts`` over five positions
    leaves state and tail with the caller and hands out each position's
    inputs; ``advance`` by the ``stand`` that stand gives what ``mix``
    over those alone gives."""
    h = _h(2, 5, seed=6)
    s0 = jnp.asarray(np.random.default_rng(7).normal(size=(2, N, DI)), jnp.float32)
    t0 = jnp.asarray(np.random.default_rng(8).normal(size=(2, K - 1, DI)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        y, m, _, inputs = mamba.mix_parts(h, layer, C, s0, t0)
        n = jnp.asarray([stand, 5], jnp.int32)
        state, tail = mamba.advance(s0, t0, layer, inputs, n)
        for row, k in ((0, stand), (1, 5)):
            (y1, _), s1, t1 = mamba.mix(h[row:row + 1, :k], layer, C, s0[row:row + 1], t0[row:row + 1])
            assert np.abs(np.asarray(y)[row, :k] - np.asarray(y1)[0]).max() < TIGHT
            assert np.abs(np.asarray(state)[row] - np.asarray(s1)[0]).max() < TIGHT
            assert np.array_equal(np.asarray(tail)[row], np.asarray(t1)[0])


def test_a_state_in_bfloat16_is_outside_the_tolerance(layer):
    h = _h(2, 40, seed=9)
    want, *_ = _whole(layer, h)
    state, tail = mamba.zeros(C, 2, h.dtype)
    got = []
    with jax.default_matmul_precision("highest"):
        for i in range(40):
            (y, _), state, tail = mamba.mix(h[:, i:i + 1], layer, C, state, tail)
            state = state.astype(jnp.bfloat16).astype(jnp.float32)
            got.append(np.asarray(y @ layer["wo"]))
    assert np.abs(np.concatenate(got, axis=1) - want).max() > 20 * TIGHT
