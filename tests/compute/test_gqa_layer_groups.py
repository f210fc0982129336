"""Layer GROUPS in a grouped-query model (``models/llama.py``): full
layers and window layers whose QUERY head count differs over the same
KV heads, each kind a stack of its own, yarn on half a head beside a
plain rope on the whole of it, per-head gates, a dense first layer and
a chip's share of the experts. Toy widths on the CPU. The serving side
is ``tests/serve/test_dense_ring_cache.py``; the plain reference is
held against both in ``tests/benchmark/test_reference_gqa_groups.py``."""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dstack_tpu.models import llama
from tests.shared import init_params

TIGHT = 2e-5

KINDS = ("full",) + ("window", "window", "window", "full") * 3  # the cell's 13
BASE = dict(
    vocab_size=256, hidden_size=64, n_heads=4, n_kv_heads=2, head_dim=16,
    intermediate_size=32, rope_theta=500000.0, rope_local_theta=10000.0,
    norm_eps=1e-6, max_seq_len=256, dtype=jnp.float32, remat=False,
    partial_rotary=0.5, swa_partial_rotary=1.0,
    rope_scaling=("yarn", 8.0, 32.0, 1.0, 16.0, 1.2), sliding_window=8,
    swa_n_heads=6, attn_gate=True,
)
MOE = dict(
    n_experts=16, experts_per_token=3, experts_held=(4, 4),
    capacity_factor=16 / 3, router_score="sigmoid", router_renorm=True,
    routed_scale=2.5, moe_shared_expert=True, moe_shared_intermediate=32,
    first_k_dense=1, dense_intermediate=96,
)
TINY = llama.LlamaConfig(n_layers=13, layer_types=KINDS, **BASE, **MOE)
ODD = dataclasses.replace(
    TINY, n_layers=6, layer_types=("full", "window", "full", "full", "window", "window")
)
PLAIN = llama.LlamaConfig(  # every expert held, no prelude
    n_layers=4, layer_types=("window", "full") * 2, **{**BASE, "intermediate_size": 96}
)
MODELS = {"periods": TINY, "odd": ODD, "plain": PLAIN}


@pytest.mark.parametrize("fields", [
    dict(sliding_pattern=2),  # a pattern and a list say the same thing twice
    dict(nope_pattern=2),
    dict(layer_types=("window",) + KINDS[1:]),  # the dense first layer attends in full
    dict(layer_types=KINDS[:-1]),  # one kind a layer
    dict(layer_types=("full", "sliding") + KINDS[2:]),
    dict(swa_n_heads=5),  # whole groups of query heads a KV head
    dict(swa_n_heads=0),
    dict(sliding_window=0),
])
def test_a_config_that_cannot_be_walked_is_refused(fields):
    with pytest.raises(ValueError):
        dataclasses.replace(TINY, **fields)


def test_the_window_layers_shape_is_a_config_of_its_own():
    wc = TINY.window_config
    assert (wc.n_heads, wc.n_kv_heads, wc.head_dim) == (6, 2, 16)
    assert wc.q_dim == 96 and wc.o_dim == 96 and wc.kv_dim == TINY.kv_dim == 32
    # the whole head rotates at the local base, unscaled; a full layer's half under yarn
    assert (wc.rope_dim, wc.rope_theta, wc.rope_scaling) == (16, 10000.0, None)
    assert (TINY.rope_dim, TINY.rope_dim_local) == (8, 16)
    assert wc.layer_types == () and not wc.mla
    # without a local base the window layers keep the model's rope
    same = dataclasses.replace(TINY, rope_local_theta=0.0, swa_partial_rotary=0.0)
    assert same.window_config.rope_scaling == TINY.rope_scaling
    assert same.window_config.rope_dim == same.rope_dim_local == 8


def test_each_kind_rotates_with_its_own_table():
    pos = jnp.arange(40)
    (cos, sin), (cos_l, sin_l) = llama.dual_rope_freqs(TINY, pos)
    assert cos.shape == (40, 4) and cos_l.shape == (40, 8)
    assert llama.layer_rope(((cos, sin), (cos_l, sin_l)), TINY, 0)[0] is cos
    assert llama.layer_rope(((cos, sin), (cos_l, sin_l)), TINY, 8)[0] is cos_l
    # yarn's attention factor multiplies cos and sin (position 0: cos = factor)
    assert np.allclose(np.asarray(cos[0]), 1.2) and np.allclose(np.asarray(cos_l[0]), 1.0)
    assert np.allclose(np.asarray(cos**2 + sin**2), 1.2**2, atol=1e-5)
    # the first dim is above the correction range (kept), the last below it (divided by 8)
    base = 500000.0 ** (-np.arange(0, 8, 2) / 8)
    ang = np.arcsin(np.asarray(sin[1]) / 1.2)
    assert np.isclose(ang[0], base[0], rtol=1e-4) and np.isclose(ang[-1], base[-1] / 8, rtol=1e-4)
    # only the leading rope_dim dims of a head move
    x = jax.random.normal(jax.random.key(0), (1, 4, 40, 16))
    y = llama.apply_rope(x, cos, sin)
    assert (np.asarray(y[..., 8:]) == np.asarray(x[..., 8:])).all()
    assert not np.allclose(np.asarray(y[..., 1:, :8]), np.asarray(x[..., 1:, :8]))


def _flat(plan):
    """Every (stack, first, last) the periods walk, in order."""
    out = [(r.key, r.lo, r.hi) for r in plan.head]
    for i in range(plan.count):
        out += [
            (r.key, r.lo + i * plan.per[r.key], r.hi + i * plan.per[r.key])
            for r in plan.period
        ]
    return out + [(r.key, r.lo, r.hi) for r in plan.tail]


@pytest.mark.parametrize("kinds,head,period,count,tail", [
    (KINDS, 1, 2, 3, 0),  # the cell: a prelude and three periods of two runs, not seven runs
    (KINDS[:5], 1, 0, 0, 2),  # one period: nothing repeats
    (KINDS + ("window",), 1, 2, 3, 1),  # a run left over
    (("full",) + ("window", "full") * 5, 1, 2, 5, 0),
    (("full",) * 4, 1, 0, 0, 1),
    (("full", "window", "full", "full", "window", "window"), 1, 0, 0, 3),
])
def test_periods_fold_the_runs_and_lose_none(kinds, head, period, count, tail):
    c = dataclasses.replace(TINY, n_layers=len(kinds), layer_types=kinds)
    runs, plan = llama.layer_runs(c), llama.layer_periods(c)
    assert (len(plan.head), len(plan.period), plan.count, len(plan.tail)) == (
        head, period, count, tail
    )
    assert _flat(plan) == [(r.key, r.lo, r.hi) for r in runs]
    # the runs cover every layer once, in the order of layer_types
    walked = [
        "window" if r.key == "window_layers" else "full"
        for r in runs for _ in range(r.lo, r.hi)
    ]
    assert tuple(walked) == kinds
    assert all((r.window == 8) == (r.key == "window_layers") for r in runs)
    assert all(r.config.n_heads == (6 if r.window else 4) for r in runs)


@pytest.mark.parametrize("model", sorted(MODELS))
def test_the_tree_holds_a_stack_a_kind_and_counts_itself(model):
    c = MODELS[model]
    params = init_params(c, 0)
    n_win = c.layer_types.count("window")
    n_full = c.n_layers - n_win - c.first_k_dense
    assert params["window_layers"]["wq"].shape == (n_win, 64, 6 * 16)
    assert params["window_layers"]["wo"].shape == (n_win, 6 * 16, 64)
    assert params["window_layers"]["w_og"].shape == (n_win, 64, 6)
    assert params["layers"]["wq"].shape == (n_full, 64, 4 * 16)
    assert params["layers"]["w_og"].shape == (n_full, 64, 4)
    assert params["window_layers"]["wk"].shape == (n_win, 64, 2 * 16)
    if c.n_experts:
        assert params["window_layers"]["w_gate"].shape == (n_win, 4, 64, 32)  # held, of 16
        assert params["window_layers"]["w_router"].shape == (n_win, 64, 16)
        assert params["dense_layers"]["w_up"].shape == (1, 64, 96)
        assert params["dense_layers"]["w_og"].shape == (1, 64, 4)
    leaves = jax.tree.leaves(params)
    assert sum(a.size for a in leaves) == c.num_params()
    assert all(a.dtype == jnp.float32 for a in leaves)
    # the logical axes name every leaf, and no other
    specs = llama.param_specs(c)
    is_spec = lambda s: isinstance(s, tuple)
    assert jax.tree.structure(specs, is_leaf=is_spec) == jax.tree.structure(params)
    for spec, leaf in zip(jax.tree.leaves(specs, is_leaf=is_spec), leaves):
        assert len(spec) == leaf.ndim
    assert jax.tree.map(lambda a: (a.shape, a.dtype), llama.abstract_params(c)) == jax.tree.map(
        lambda a: (a.shape, a.dtype), params
    )


def test_a_list_of_full_layers_is_the_model_without_one():
    """``layer_types`` all ``full`` walks the stacks the plain model
    scans: the same weights give the same logits."""
    plain = dataclasses.replace(TINY, n_layers=4, layer_types=(), sliding_window=0)
    listed = dataclasses.replace(plain, layer_types=("full",) * 4)
    params = init_params(plain, 2)
    tokens = jax.random.randint(jax.random.key(3), (2, 24), 1, 256)
    with jax.default_matmul_precision("highest"):
        a = llama.forward(params, tokens, plain)
        b = llama.forward(params, tokens, listed)
    assert np.abs(np.asarray(a - b)).max() < TIGHT


def test_window_layers_of_the_full_shape_are_the_pattern_model():
    """Window layers with the full layers' head count and rotary share
    are what ``sliding_pattern`` describes (window, window, full; the
    local rope on window layers): the groups' two stacks, interleaved
    back into one, give the pattern model's logits."""
    kinds = ("window", "window", "full") * 2
    fields = {**BASE, "swa_n_heads": 4, "swa_partial_rotary": 0.0, "intermediate_size": 96}
    groups = llama.LlamaConfig(n_layers=6, layer_types=kinds, **fields)
    pattern = llama.LlamaConfig(n_layers=6, sliding_pattern=3, **fields)
    params = init_params(groups, 4)
    at = {"layers": 0, "window_layers": 0}
    order = []
    for kind in kinds:
        key = "window_layers" if kind == "window" else "layers"
        order.append((key, at[key]))
        at[key] += 1
    one = {
        **{k: v for k, v in params.items() if k not in at},
        "layers": {
            n: jnp.stack([params[key][n][i] for key, i in order])
            for n in params["layers"]
        },
    }
    tokens = jax.random.randint(jax.random.key(5), (2, 40), 1, 256)
    with jax.default_matmul_precision("highest"):
        a = llama.forward(params, tokens, groups)
        b = llama.forward(one, tokens, pattern)
    assert np.abs(np.asarray(a - b)).max() < TIGHT
    # and the window bites: past it the logits are not a wider window's
    wide = dataclasses.replace(groups, sliding_window=64)
    with jax.default_matmul_precision("highest"):
        c = llama.forward(params, tokens, wide)
    moved = np.abs(np.asarray(a - c)).max(-1)
    assert moved[:, :8].max() < TIGHT < 1e-3 < moved[:, 8:].max()


@pytest.mark.parametrize("model", sorted(MODELS))
def test_the_forward_trains(model):
    """The training-side forward differentiates through every stack:
    each group's projections, gates and experts get a gradient."""
    c = MODELS[model]
    params = init_params(c, 6)
    tokens = jax.random.randint(jax.random.key(7), (2, 20), 1, 256)

    def loss(p):
        logits, aux = llama.forward(p, tokens[:, :-1], c, return_aux=True)
        ll = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(ll, tokens[:, 1:, None], -1).mean() + aux

    value, grads = jax.value_and_grad(loss)(params)
    assert np.isfinite(float(value))
    for key in ("layers", "window_layers"):
        for name in ("wq", "wk", "wv", "wo", "w_og"):
            g = np.asarray(grads[key][name])
            assert np.isfinite(g).all() and (np.abs(g).max(axis=(1, 2)) > 0).all(), (key, name)
    if c.n_experts:
        assert (np.abs(np.asarray(grads["window_layers"]["w_router"])).max(axis=(1, 2)) > 0).all()
        assert np.abs(np.asarray(grads["dense_layers"]["w_up"])).max() > 0


def test_the_gate_scales_a_heads_output():
    """A gate driven shut silences the attention sublayer; left out,
    the logits move."""
    c = PLAIN
    params = init_params(c, 8)
    tokens = jax.random.randint(jax.random.key(9), (1, 16), 1, 256)
    shut = jax.tree.map(lambda a: a, params)
    for key in ("layers", "window_layers"):
        shut[key] = {**params[key], "w_og": jnp.zeros_like(params[key]["w_og"])}
        # sigmoid(0) = 1/2 a head: the sublayer at half strength = wo halved, ungated
    halved = {
        k: ({**v, "wo": v["wo"] * 0.5} if k in ("layers", "window_layers") else v)
        for k, v in params.items()
    }
    ungated = dataclasses.replace(c, attn_gate=False)
    with jax.default_matmul_precision("highest"):
        a = llama.forward(shut, tokens, c)
        b = llama.forward(halved, tokens, ungated)
        d = llama.forward(params, tokens, ungated)
        e = llama.forward(params, tokens, c)
    assert np.abs(np.asarray(a - b)).max() < TIGHT
    assert np.abs(np.asarray(d - e)).max() > 1e-3


def test_programs_do_not_grow_with_depth():
    """Lowered, the decode step of 25 layers (six periods) is the text
    of 13 (three) but for a loop bound and the stacks' sizes: the body
    is one period, scanned."""
    from dstack_tpu.serve import engine as E

    def text(n_periods, name):
        kinds = ("full",) + ("window", "window", "window", "full") * n_periods
        c = dataclasses.replace(TINY, n_layers=len(kinds), layer_types=kinds)
        params = llama.abstract_params(c)
        cache = jax.eval_shape(lambda: E.init_cache(c, 4, 64, chunk=16))
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
        if name == "decode_step":
            fn, args = partial(E.decode_step, config=c), (params, cache, i32(4), i32(4))
        else:
            fn = partial(E.prefill_packed_step, config=c)
            args = (params, cache, i32(2, 16), i32(2), i32(2), i32(2))
        return jax.jit(fn).lower(*args).as_text()

    for name in ("decode_step", "prefill_packed_step"):
        three, six = text(3, name), text(6, name)
        assert len(three.splitlines()) == len(six.splitlines())
        assert abs(len(six) - len(three)) < 0.01 * len(three)
        # and near a one-period cut's, whose two runs are walked one after the other
        one = text(1, name)
        assert len(three.splitlines()) < 1.35 * len(one.splitlines())
