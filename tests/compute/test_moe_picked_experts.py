"""``moe.moe_mlp``'s picked form (one loop trip a distinct picked expert,
no other expert's weights read) against its capacity form and against a
dense every-expert sum, over the router forms of three cells, and the
rule that chooses between the two forms.

Widths are toy; the routers are the cells' own widths (768 = 512 + 256
identity experts with 16 held; 256 with 32 held; 64, all held), because
the rule reads them. It reads an expert's bytes too, and a toy expert
(24 KB) is less than a loop trip's fixed cost: the picked form is had
with ``TRIP_BYTES`` at 0 (the loop wherever some expert is expected
unpicked), the capacity form from the same call with it at infinity:
what the rule decides is the only difference.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dstack_tpu.models import llama, moe
from dstack_tpu.parallel.sharding import default_rules

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
H, F = 64, 32

# name → the router's width (identity experts included), how many of its
# last outputs are identity experts, the experts held, top-k, whether it has
# a selection bias and a shared expert, moe_mlp's routing arguments
FORMS = {
    # longcat: softmax + selection bias, no renorm, x 6, identity experts
    "softmax-bias-zero-held": dict(
        width=768, zero=256, held=(0, 16), k=12, bias=True, shared=False,
        kw=dict(routed_scale=6.0),
    ),
    # dots3 / laguna: sigmoid scores + bias, renormed, scaled, one shared
    "sigmoid-renorm-scale-held": dict(
        width=256, zero=0, held=(0, 32), k=8, bias=True, shared=True,
        kw=dict(score="sigmoid", renorm=True, routed_scale=2.5),
    ),
    # deepseek-v2: plain softmax, every expert held
    "softmax-all-held": dict(
        width=64, zero=0, held=(), k=6, bias=False, shared=True, kw={},
    ),
}


def _layer(form, seed=0, dtype=jnp.float32):
    f = FORMS[form]
    count = f["held"][1] if f["held"] else f["width"] - f["zero"]
    ks = jax.random.split(jax.random.key(seed), 8)
    layer = {
        "w_router": jax.random.normal(ks[0], (H, f["width"]), jnp.float32) * 0.3,
        "w_gate": jax.random.normal(ks[1], (count, H, F), dtype) * 0.2,
        "w_up": jax.random.normal(ks[2], (count, H, F), dtype) * 0.2,
        "w_down": jax.random.normal(ks[3], (count, F, H), dtype) * 0.2,
    }
    if f["bias"]:
        layer["router_bias"] = jax.random.normal(ks[4], (f["width"],)) * 0.01
    if f["shared"]:
        layer["w_shared_gate"] = jax.random.normal(ks[5], (H, F), dtype) * 0.2
        layer["w_shared_up"] = jax.random.normal(ks[6], (H, F), dtype) * 0.2
        layer["w_shared_down"] = jax.random.normal(ks[7], (F, H), dtype) * 0.2
    return layer


def _x(b, t, seed=1, dtype=jnp.float32):
    return jax.random.normal(jax.random.key(seed), (b, t, H), dtype)


def _call(form, x, layer, valid=None, rules=None):
    f = FORMS[form]
    return moe.moe_mlp(
        x, layer, f["width"] - f["zero"], f["k"], f["width"] / f["k"],
        None, rules, held=f["held"], zero=f["zero"], valid=valid, **f["kw"],
    )


def _both(form, x, layer, monkeypatch, valid=None):
    """(picked form, capacity form) of one call, each (out, aux)."""
    f = FORMS[form]
    monkeypatch.setattr(moe, "TRIP_BYTES", 0)
    assert moe.reads_picked_experts(
        layer, *x.shape[:2], f["width"] - f["zero"], f["k"], f["width"] / f["k"], None
    ), "the case's shape must engage the picked form"
    picked = jax.jit(lambda: _call(form, x, layer, valid))()
    monkeypatch.setattr(moe, "TRIP_BYTES", float("inf"))
    capacity = jax.jit(lambda: _call(form, x, layer, valid))()
    monkeypatch.undo()
    return picked, capacity


def _picks(form, x, layer):
    """(expert_idx [B,T,k] over the router's width, gates [B,T,k])."""
    f = FORMS[form]
    _, _, idx, gates = moe.select(
        x, layer["w_router"], f["k"], bias=layer.get("router_bias"), **f["kw"]
    )
    return np.asarray(idx), np.asarray(gates)


def _dense_sum(form, x, layer):
    """Σ_j gate_j · FFN_{e_j}(x) with EVERY stacked expert computed for
    every token, float64 on the host: no capacity, no loop."""
    f = FORMS[form]
    first, count = f["held"] or (0, f["width"] - f["zero"])
    idx, gates = _picks(form, x, layer)
    xs = np.asarray(x, np.float64)
    w = {k: np.asarray(v, np.float64) for k, v in layer.items()}
    silu = lambda a: a / (1 + np.exp(-a))
    y = np.einsum(
        "ebtf,efh->ebth",
        silu(np.einsum("bth,ehf->ebtf", xs, w["w_gate"]))
        * np.einsum("bth,ehf->ebtf", xs, w["w_up"]),
        w["w_down"],
    )
    out = np.zeros_like(xs)
    for e in range(count):
        g = (gates * (idx == first + e)).sum(-1)
        out += g[..., None] * y[e]
    if f["zero"]:
        out += (gates * (idx >= f["width"] - f["zero"])).sum(-1)[..., None] * xs
    if f["shared"]:
        out += (silu(xs @ w["w_shared_gate"]) * (xs @ w["w_shared_up"])) @ w["w_shared_down"]
    return out


def _distinct_here(form, x, layer, valid=None):
    f = FORMS[form]
    first, count = f["held"] or (0, f["width"] - f["zero"])
    idx, _ = _picks(form, x, layer)
    if valid is not None:
        idx = idx[np.asarray(valid)]
    here = idx[(idx >= first) & (idx < first + count)]
    return len(set(here.tolist())), here.size


SHAPES = {  # form → {T: batch}: few enough tokens that some experts go unpicked
    "softmax-bias-zero-held": {1: 16, 5: 2},
    "sigmoid-renorm-scale-held": {1: 16, 5: 2},
    "softmax-all-held": {1: 2, 5: 1},
}


@pytest.mark.parametrize("t", [1, 5])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_picked_form_equals_the_capacity_form_and_a_dense_sum(form, t, monkeypatch):
    layer, x = _layer(form), _x(SHAPES[form][t], t)
    with jax.default_matmul_precision("highest"):
        (out, aux), (cap_out, cap_aux) = _both(form, x, layer, monkeypatch)
    np.testing.assert_allclose(out, cap_out, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(out, _dense_sum(form, x, layer), rtol=1e-5, atol=1e-5)
    for name in ("balance", "z", "zero_gate"):
        if name in aux:
            np.testing.assert_allclose(aux[name], cap_aux[name], rtol=1e-6)
    if FORMS[form]["held"]:
        distinct, landed = _distinct_here(form, x, layer)
        count = FORMS[form]["held"][1]
        assert int(aux["held_picks"]) == int(cap_aux["held_picks"]) == landed
        assert int(aux["experts_read"]) == distinct < count
        assert int(cap_aux["experts_read"]) == count
        assert int(aux["experts_held"]) == int(cap_aux["experts_held"]) == count
    else:
        assert "experts_read" not in aux  # counted for a chip's share only
    if FORMS[form]["zero"]:
        assert int(aux["zero_picks"]) == int(cap_aux["zero_picks"]) > 0


@pytest.mark.parametrize("form", sorted(FORMS))
def test_bfloat16_is_as_close_as_the_capacity_form(form, monkeypatch):
    """Against the float64 sum of the bf16-rounded weights and inputs the
    picked form (float32 accumulator, float32 gates) errs no more than
    the capacity form, whose combine rounds gates and sums to bf16."""
    layer = _layer(form, dtype=jnp.bfloat16)
    x = _x(SHAPES[form][1], 1, dtype=jnp.bfloat16)
    (out, _), (cap_out, _) = _both(form, x, layer, monkeypatch)
    assert out.dtype == jnp.bfloat16
    want = _dense_sum(form, x, layer)
    err = lambda got: np.abs(np.asarray(got, np.float64) - want).max()
    assert err(out) <= max(err(cap_out), 1e-6) * 1.25
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(cap_out, np.float32), rtol=5e-2, atol=5e-2
    )


def _biased(layer, form, favoured, by=50.0):
    """``layer`` with a selection bias that puts ``favoured`` first."""
    bias = np.zeros(FORMS[form]["width"], np.float32)
    bias[favoured] = by
    return {**layer, "router_bias": jnp.asarray(bias)}


def test_no_pick_lands_here(monkeypatch):
    """Every token's twelve picks fall on absent experts: zero trips,
    the identity experts' term alone."""
    form = "softmax-bias-zero-held"
    layer = _biased(_layer(form), form, slice(100, 112))  # absent, real
    x = _x(16, 1)
    (out, aux), (cap_out, _) = _both(form, x, layer, monkeypatch)
    assert int(aux["experts_read"]) == 0 == int(aux["held_picks"])
    np.testing.assert_allclose(out, cap_out, atol=1e-6)
    np.testing.assert_allclose(out, 0.0, atol=1e-6)  # no held pick, no identity pick
    layer = _biased(_layer(form), form, slice(600, 612))  # identity experts
    (out, aux), _ = _both(form, x, layer, monkeypatch)
    assert int(aux["experts_read"]) == 0 and int(aux["zero_picks"]) == 16 * 12
    np.testing.assert_allclose(
        out, np.asarray(aux["zero_gate"])[..., None] * np.asarray(x), rtol=1e-6
    )


def test_every_held_expert_picked(monkeypatch):
    form = "softmax-bias-zero-held"
    f = dict(FORMS[form], held=(0, 8))
    monkeypatch.setitem(FORMS, form, f)
    layer = _biased(_layer(form), form, slice(0, 8))
    x = _x(4, 1)
    with jax.default_matmul_precision("highest"):
        (out, aux), (cap_out, _) = _both(form, x, layer, monkeypatch)
    assert int(aux["experts_read"]) == 8 and int(aux["held_picks"]) == 4 * 8
    np.testing.assert_allclose(out, cap_out, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("t", [1, 5])
def test_dead_rows_read_nothing_and_count_nothing(t, monkeypatch):
    """A dead row's picks put no expert on the list and into no count;
    the live rows' outputs are the capacity form's."""
    form = "softmax-bias-zero-held"
    b = SHAPES[form][t]
    layer, x = _layer(form), _x(b, t)
    valid = np.zeros((b, t), bool)
    valid[0, : max(1, t - 2)] = True  # a row with padding behind it
    if b > 2:
        valid[5] = True
    with jax.default_matmul_precision("highest"):
        (out, aux), (cap_out, cap_aux) = _both(
            form, x, layer, monkeypatch, jnp.asarray(valid)
        )
    distinct, landed = _distinct_here(form, x, layer, valid)
    all_distinct, _ = _distinct_here(form, x, layer)
    assert int(aux["experts_read"]) == distinct < all_distinct
    assert int(aux["held_picks"]) == int(cap_aux["held_picks"]) == landed
    assert int(aux["zero_picks"]) == int(cap_aux["zero_picks"])
    np.testing.assert_allclose(
        np.asarray(out)[valid], np.asarray(cap_out)[valid], rtol=1e-5, atol=1e-5
    )
    none = jnp.zeros((b, t), bool)
    (_, aux), _ = _both(form, x, layer, monkeypatch, none)
    assert int(aux["experts_read"]) == 0 == int(aux["held_picks"])


@pytest.mark.parametrize("form", sorted(FORMS))
def test_unpicked_experts_weights_are_not_touched(form, monkeypatch):
    """NaN in every expert no token picked: the output is finite and the
    clean layer's (a read of any of them would poison every row)."""
    monkeypatch.setattr(moe, "TRIP_BYTES", 0)
    layer, x = _layer(form), _x(SHAPES[form][1], 1)
    f = FORMS[form]
    first, count = f["held"] or (0, f["width"] - f["zero"])
    idx, _ = _picks(form, x, layer)
    unpicked = np.setdiff1d(np.arange(count), idx.ravel() - first)
    assert 0 < unpicked.size < count
    poisoned = {
        **layer,
        **{w: layer[w].at[unpicked].set(jnp.nan) for w in moe.EXPERT_STACKS},
    }
    clean, _ = jax.jit(lambda: _call(form, x, layer))()
    got, _ = jax.jit(lambda: _call(form, x, poisoned))()
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(got, clean)
    monkeypatch.setattr(moe, "TRIP_BYTES", float("inf"))  # the capacity form reads them all
    assert not np.isfinite(np.asarray(jax.jit(lambda: _call(form, x, poisoned))()[0])).all()


def test_a_row_of_the_layer_stack_reads_the_same(monkeypatch):
    """The expert stacks handed as ``moe.Row(stack over layers, l)`` (how
    a layer scan hands them) give the layer's own output."""
    monkeypatch.setattr(moe, "TRIP_BYTES", 0)
    form = "sigmoid-renorm-scale-held"
    layers = [_layer(form, seed=s) for s in range(3)]
    x = _x(16, 1)
    for at in (0, 2):
        rows = {
            **layers[at],
            **{
                w: moe.Row(jnp.stack([l[w] for l in layers]), jnp.int32(at))
                for w in moe.EXPERT_STACKS
            },
        }
        want, _ = jax.jit(lambda: _call(form, x, layers[at]))()
        got, aux = jax.jit(lambda: _call(form, x, rows))()
        np.testing.assert_array_equal(got, want)
        assert int(aux["experts_held"]) == 32
    monkeypatch.setattr(moe, "TRIP_BYTES", float("inf"))  # a row in the capacity form is taken
    np.testing.assert_allclose(
        jax.jit(lambda: _call(form, x, rows))()[0], want, rtol=1e-5, atol=1e-5
    )


# ---------------------------------------------------------------------------
# the rule
# ---------------------------------------------------------------------------


def _cell_config(name):
    from benchmark import launch

    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return launch.build_llama_config(json.load(f)["llama_config"])


def _expert_layer_shapes(c):
    """An expert layer's leaves as shapes, from the model's own init."""
    layers = jax.eval_shape(lambda: llama.init_params(c, jax.random.key(0)))["layers"]
    return {k: v for k, v in layers.items() if not k.startswith("sub")}


def _takes_picked(c, b, t, rules=None):
    return moe.reads_picked_experts(
        _expert_layer_shapes(c), b, t, c.n_experts, c.experts_per_token,
        c.capacity_factor, rules,
        sigmoid_input=c.router_sigmoid_input, act=c.moe_act,
    )


# configuration → (expected share at 16 decode rows, picked form?; the same
# of a full verify grid, 16 x 5 tokens). Reasoning's 0.793 of 64 experts of
# 17.3 MB stands just over its break-even (0.773); agent's grid, 0.716 of 16
# experts of 75.5 MB, is the one grid under its own (0.937)
CELLS = {
    "longcat-flash-chat-4l-ep32": (0.223, True, 0.716, True),
    "ling-3.0-flash-vl-13l-ep8": (0.223, True, 0.716, False),
    "dots3-note-prev-5l-ep8": (0.398, True, 0.921, False),
    "laguna-s-2.1-13l-ep8": (0.471, True, 0.959, False),
    "lfm2-24b-a2b-ep8": (0.644, True, 0.994, False),
    "deepseek-v2-lite-9l": (0.793, False, 1.0, False),
}


def _expert_bytes(layer):
    """The three matrices of one expert of ``layer``'s stacks."""
    return sum(
        int(np.prod(layer[w].shape[-2:])) * layer[w].dtype.itemsize
        for w in moe.EXPERT_STACKS
    )


@pytest.mark.parametrize("name", sorted(CELLS) + ["minitron-4b"])
def test_the_rule_at_the_cells_decode_shapes(name):
    if name == "minitron-4b":  # dense: no router, nothing to choose
        assert not llama.MINITRON_4B.n_experts
        return
    c = (
        dataclasses.replace(llama.DEEPSEEK_V2_LITE, n_layers=9)
        if name == "deepseek-v2-lite-9l" else _cell_config(name)
    )
    share, picked, grid_share, grid_picked = CELLS[name]
    width = c.n_experts + c.zero_experts
    assert moe.picked_share(16, width, c.experts_per_token) == pytest.approx(share, abs=1e-3)
    assert moe.picked_share(80, width, c.experts_per_token) == pytest.approx(
        grid_share, abs=1e-3
    )
    # the bytes each form streams a call: the whole stack, or a trip an
    # expected distinct expert, each its expert and a trip's fixed cost
    layer = _expert_layer_shapes(c)
    count, one = layer["w_gate"].shape[-3], _expert_bytes(layer)
    assert (c.experts_held[1] if c.experts_held else c.n_experts) == count
    assert (share * count * (one + moe.TRIP_BYTES) < count * one) == picked
    assert (grid_share * count * (one + moe.TRIP_BYTES) < count * one) == grid_picked
    # decode_step and decode_loop route 16 x 1 tokens a layer call
    assert _takes_picked(c, 16, 1) == picked
    # a full verify grid
    assert _takes_picked(c, 16, 5) == grid_picked
    # a 256-token prefill chunk or wave row: the capacity form
    assert not _takes_picked(c, 1, 256) and not _takes_picked(c, 4, 256)
    # under sharding rules (training, ep): the dispatch einsums
    assert not _takes_picked(c, 16, 1, rules=default_rules())


def _rule_at(expert_bytes, tokens, width=64, k=4, count=8, dtype=jnp.bfloat16):
    """The rule at an expert of ``expert_bytes`` (three square-ish bf16
    matrices) and ``tokens`` x 1 tokens over a ``width``-wide top-``k``
    router, ``count`` experts held."""
    side = int(round((expert_bytes / 3 / jnp.dtype(dtype).itemsize) ** 0.5))
    sds = jax.ShapeDtypeStruct
    layer = {
        "w_router": sds((side, width), jnp.float32),
        "w_gate": sds((count, side, side), dtype),
        "w_up": sds((count, side, side), dtype),
        "w_down": sds((count, side, side), dtype),
    }
    return moe.reads_picked_experts(layer, tokens, 1, width, k, width / k, None)


@pytest.mark.parametrize("count", [8, 64])
@pytest.mark.parametrize("tokens", [1, 4, 16, 32])
def test_the_rule_follows_an_experts_bytes_and_the_share(tokens, count):
    """At a fixed share the loop is refused as an expert's bytes shrink
    toward a trip's fixed cost and taken as they grow past it; at a
    fixed expert it is taken as the share falls toward 0 and refused as
    it rises toward 1: monotone in both, whatever the stack's count
    (it cancels), and the break-even is where the bytes say."""
    share = moe.picked_share(tokens, 64, 4)
    sizes = [moe.TRIP_BYTES * 2.0**p for p in range(-8, 9)]
    taken = [_rule_at(b, tokens, count=count) for b in sizes]
    assert taken == sorted(taken)  # once taken, taken at every larger expert
    assert not taken[0]  # an expert of 1/256 of a trip's cost: never the loop
    # the break-even: share x (bytes + TRIP_BYTES) = bytes
    even = moe.TRIP_BYTES * share / (1 - share)
    for size, took in zip(sizes, taken):
        assert took == (size > even), (size, even)
    # at one expert (a cell's 18.9 MB) over more and more tokens
    by_tokens = [_rule_at(18.9e6, n, count=count) for n in (1, 2, 4, 8, 16, 32, 64, 256)]
    assert by_tokens == sorted(by_tokens, reverse=True)
    assert by_tokens[0] and not by_tokens[-1]


def test_rules_given_runs_the_capacity_form():
    form = "softmax-bias-zero-held"
    layer, x = _layer(form), _x(16, 1)
    out, aux = _call(form, x, layer, rules=default_rules())
    assert int(aux["experts_read"]) == int(aux["experts_held"]) == 16
    want, _ = _call(form, x, layer)
    np.testing.assert_allclose(out, want, rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize(
    "why", ["int8", "expert-biases", "oai_glu", "sigmoid_input", "capacity-drops"]
)
def test_forms_the_loop_does_not_cover_keep_the_capacity_form(why, monkeypatch):
    monkeypatch.setattr(moe, "TRIP_BYTES", 0)  # toy widths (the module's docstring)
    form = "softmax-bias-zero-held"
    layer = jax.eval_shape(lambda: _layer(form))
    args = dict(
        layer=layer, batch=16, seq_len=1, n_experts=512, experts_per_token=12,
        capacity_factor=64.0, rules=None,
    )
    assert moe.reads_picked_experts(**args)
    if why == "int8":
        for w in moe.EXPERT_STACKS:
            layer[w + "_q"], layer[w + "_s"] = layer.pop(w), None
    elif why == "expert-biases":
        layer["b_gate"] = layer["b_up_e"] = layer["b_down_e"] = None
    elif why == "oai_glu":
        args["act"] = "oai_glu"
    elif why == "sigmoid_input":
        args["sigmoid_input"] = True
    else:  # 16 tokens a row at capacity factor 1: a slot cap under T
        args.update(batch=1, seq_len=16, capacity_factor=1.0)
        assert moe.expert_capacity(16, 512, 12, 1.0) < 16
    assert not moe.reads_picked_experts(**args)
