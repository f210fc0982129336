"""The sampler does only what a live slot asked for.

``engine.sample`` runs its filters (min-p's softmax, the [B, V] sort,
the sorted softmax and cumsum) only on a call where some live row set
top-k, top-p or min-p. Held here against ``_sample_all_branches``, a
frozen copy of the body it had before (every branch computed on every
call, selected per row): tokens AND advanced key data bit-equal, filter
asked or not."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dstack_tpu.models import llama
from dstack_tpu.serve.engine import NEG_INF, GenParams, InferenceEngine, sample
from tests.shared import init_params


def _sample_all_branches(
    logits, key_data, temperature, top_p, top_k, rep_pen, counts, pres_pen,
    freq_pen, gen_counts, logit_bias=None, min_p=None,
):
    """``sample`` as it stood at PR 31 (d1c435c), kept verbatim."""
    v = logits.shape[-1]
    if logit_bias is not None:
        logits = logits + logit_bias
    seen = counts > 0
    pen = rep_pen[:, None]
    penalized = jnp.where(logits > 0, logits / pen, logits * pen)
    logits = jnp.where(seen & (pen != 1.0), penalized, logits)
    logits = logits - pres_pen[:, None] * (gen_counts > 0).astype(jnp.float32)
    logits = logits - freq_pen[:, None] * gen_counts.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1)
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    if min_p is not None:
        probs_mp = jax.nn.softmax(scaled, axis=-1)
        floor = min_p[:, None] * jnp.max(probs_mp, axis=-1, keepdims=True)
        scaled = jnp.where(
            (min_p[:, None] <= 0.0) | (probs_mp >= floor), scaled, NEG_INF
        )
    sorted_full = jnp.sort(scaled, axis=-1)[:, ::-1]
    kth_ix = jnp.clip(top_k - 1, 0, v - 1)
    kth = jnp.take_along_axis(sorted_full, kth_ix[:, None], axis=-1)
    scaled = jnp.where(
        (top_k[:, None] > 0) & (scaled < kth), NEG_INF, scaled
    )
    sorted_logits = jnp.where(
        (top_k[:, None] > 0)
        & (jnp.arange(v)[None, :] >= jnp.maximum(top_k, 1)[:, None]),
        NEG_INF,
        sorted_full,
    )
    sorted_probs = jax.nn.softmax(sorted_logits, axis=-1)
    cumulative = jnp.cumsum(sorted_probs, axis=-1)
    cutoff_ix = jnp.argmax(cumulative >= top_p[:, None], axis=-1)
    cutoff = jnp.take_along_axis(sorted_logits, cutoff_ix[:, None], axis=-1)
    masked = jnp.where(scaled >= cutoff, scaled, NEG_INF)
    masked = jnp.where(top_p[:, None] >= 1.0, scaled, masked)
    keys = jax.vmap(jax.random.wrap_key_data)(key_data)
    splits = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
    sampled = jax.vmap(jax.random.categorical)(splits[:, 1], masked)
    tokens = jnp.where(temperature <= 0.0, greedy, sampled)
    return tokens, jax.vmap(jax.random.key_data)(splits[:, 0])


V = 5000
_T4 = [0.7, 1.3, 0.2, 1.0]

# name → the call: per-row temperatures and filters, whether the rows
# carry penalties and a logit bias, whether min_p is passed at all, and
# the live mask (None = the first-token call, which passes none)
CASES = {
    "no_filter": dict(temps=_T4),
    "top_k_only": dict(temps=_T4, top_k=[40, 0, 3, 1]),
    "top_p_only": dict(temps=_T4, top_p=[0.9, 1.0, 0.5, 0.999]),
    "min_p_only": dict(temps=_T4, min_p=[0.05, 0.0, 0.5, 1.0]),
    "all_three_on_one_row": dict(
        temps=_T4, top_k=[0, 50, 0, 0], top_p=[1.0, 0.8, 1.0, 1.0],
        min_p=[0.0, 0.01, 0.0, 0.0],
    ),
    "mixed_batch_one_top_p": dict(temps=[0.7] * 8, top_p=[1.0] * 7 + [0.9]),
    "greedy_among_sampled": dict(temps=[0.7, 0.0, 1.1, 0.0]),
    "greedy_among_filtered": dict(
        temps=[0.7, 0.0, 1.1, 0.0], top_p=[0.9, 0.9, 1.0, 1.0], top_k=[0, 5, 0, 0],
    ),
    "penalties_and_bias": dict(temps=[0.7, 0.0, 1.1, 0.9], penalties=True),
    "penalties_and_bias_filtered": dict(
        temps=[0.7, 0.0, 1.1, 0.9], penalties=True, top_p=[0.95, 1.0, 1.0, 1.0],
        min_p=[0.0, 0.0, 0.02, 0.0],
    ),
    "no_min_p_argument": dict(temps=_T4, pass_min_p=False),
    "no_min_p_argument_top_k": dict(temps=_T4, pass_min_p=False, top_k=[0, 0, 7, 0]),
    "first_token_one_row": dict(temps=[0.7]),
    "first_token_one_row_greedy": dict(temps=[0.0]),
    "first_token_one_row_top_p": dict(temps=[0.7], top_p=[0.9]),
    "first_token_one_row_penalties": dict(temps=[0.7], penalties=True),
    "all_rows_live_mask": dict(temps=_T4, live=[True] * 4),
    "dead_slot_with_top_p": dict(
        temps=_T4, top_p=[1.0, 0.9, 1.0, 1.0], live=[True, False, True, True],
    ),
    "dead_slot_with_top_k_and_min_p": dict(
        temps=_T4, top_k=[0, 0, 0, 20], min_p=[0.0, 0.0, 0.0, 0.1],
        live=[True, True, False, False],
    ),
    "dead_and_live_slot_filtered": dict(
        temps=_T4, top_p=[0.9, 0.9, 1.0, 1.0], live=[True, False, True, True],
    ),
}


def _call(name: str):
    """A case's arguments → (positional arguments of both samplers,
    the new one's ``live``, whether a live row asked for a filter)."""
    case = CASES[name]
    temps = case["temps"]
    b = len(temps)
    rng = np.random.default_rng(sorted(CASES).index(name))
    # a peaked row block, so that top-p and min-p cut somewhere inside
    logits = jnp.asarray(rng.normal(size=(b, V)) * 3.0, jnp.float32)
    key_data = jnp.stack(
        [jax.random.key_data(jax.random.key(100 + i)) for i in range(b)]
    )
    f32 = lambda key, off: jnp.asarray(case.get(key, [off] * b), jnp.float32)  # noqa: E731
    top_p, min_p = f32("top_p", 1.0), f32("min_p", 0.0)
    top_k = jnp.asarray(case.get("top_k", [0] * b), jnp.int32)
    zeros, ones = jnp.zeros((b,), jnp.float32), jnp.ones((b,), jnp.float32)
    counts = gen_counts = jnp.zeros((b, V), jnp.int32)
    rep, pres, freq, bias = ones, zeros, zeros, jnp.zeros((b, V), jnp.float32)
    if case.get("penalties"):
        counts = jnp.asarray(rng.integers(0, 3, size=(b, V)), jnp.int32)
        gen_counts = jnp.minimum(counts, jnp.asarray(rng.integers(0, 3, size=(b, V)), jnp.int32))
        rep, pres, freq = ones * 1.2, ones * 0.3, ones * 0.1
        bias = jnp.asarray(rng.normal(size=(b, V)) * (rng.random((b, V)) < 0.01), jnp.float32)
    args = [
        logits, key_data, jnp.asarray(temps, jnp.float32), top_p, top_k, rep,
        counts, pres, freq, gen_counts, bias,
    ]
    if case.get("pass_min_p", True):
        args.append(min_p)
    live = case.get("live")
    alive = np.asarray(live if live is not None else [True] * b)
    asked = (np.asarray(top_k) > 0) | (np.asarray(top_p) < 1.0) | (np.asarray(min_p) > 0.0)
    return args, None if live is None else jnp.asarray(live), alive, bool((asked & alive).any())


@pytest.mark.parametrize("name", sorted(CASES))
def test_sample_matches_the_all_branches_body(name):
    args, live, alive, filtered = _call(name)
    want_toks, want_kd = jax.jit(_sample_all_branches)(*args)
    got_toks, got_kd = jax.jit(sample)(*args, live=live)
    np.testing.assert_array_equal(np.asarray(got_kd), np.asarray(want_kd))
    got_toks, want_toks = np.asarray(got_toks), np.asarray(want_toks)
    # a live row's token is the old body's, bit for bit
    np.testing.assert_array_equal(got_toks[alive], want_toks[alive])
    if filtered:
        # a filter asked: the old body ran, on the dead rows too
        np.testing.assert_array_equal(got_toks, want_toks)
    elif not alive.all():
        # nobody live asked: a dead row's own filter was not applied,
        # its token is the one the old body draws with the filter off
        off = list(args)
        off[3], off[4] = jnp.ones_like(off[3]), jnp.zeros_like(off[4])
        if len(off) == 12:
            off[11] = jnp.zeros_like(off[11])
        plain_toks, _ = jax.jit(_sample_all_branches)(*off)
        np.testing.assert_array_equal(got_toks, np.asarray(plain_toks))
        assert (got_toks != want_toks).any(), "the case's dead row never felt its filter"


def _primitives(jaxpr, inside_cond=False):
    """Every (primitive name, is it inside a conditional's branch) of a
    jaxpr, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, inside_cond
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub, inside_cond or eqn.primitive.name == "cond")


def test_the_sort_stands_only_inside_a_branch_of_the_conditional():
    args, live, _, _ = _call("dead_slot_with_top_p")
    jaxpr = jax.make_jaxpr(sample)(*args, live=live).jaxpr
    sorts = [inside for name, inside in _primitives(jaxpr) if name == "sort"]
    assert sorts and all(sorts)
    # min-p's softmax and the cumsum go with it: outside the
    # conditional nothing reduces over the vocabulary but the two
    # argmaxes (greedy, the categorical draw)
    outside = [name for name, inside in _primitives(jaxpr) if not inside]
    assert outside.count("cond") == 1
    assert "cumsum" not in outside and "reduce_sum" not in outside and "exp" not in outside
    # one branch of the two is the bypass: it holds no operation at all
    (cond,) = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    assert sorted(len(b.jaxpr.eqns) > 0 for b in cond.params["branches"]) == [False, True]
    # and the old body did sort at the top level, so the walk sees one
    old = jax.make_jaxpr(_sample_all_branches)(*args).jaxpr
    assert any(name == "sort" and not inside for name, inside in _primitives(old))


def _counts(eng):
    fam = eng.metrics.family
    return (
        fam("dtpu_serve_sample_calls_total").value(),
        fam("dtpu_serve_sample_filtered_calls_total").value(),
    )


def test_a_top_p_request_engages_the_filters_only_while_it_is_live():
    config = llama.LLAMA_TINY
    params = init_params(config, 0)
    prompts = [[5, 99, 321, 7], [10, 20, 30, 40, 50]]
    plain = lambda: [  # noqa: E731
        GenParams(max_new_tokens=14, temperature=0.9, seed=11),
        GenParams(max_new_tokens=14, temperature=1.2, seed=5),
    ]

    def drive(with_guest: bool):
        eng = InferenceEngine(config, params, max_batch=4, max_seq=64)
        # there from boot: a scrape exports 0, no call made yet
        assert "dtpu_serve_sample_filtered_calls_total 0\n" in eng.metrics.render()
        assert "dtpu_serve_sample_calls_total 0\n" in eng.metrics.render()
        streams, slots = [], []
        for p, g in zip(prompts, plain()):
            slot, tok = eng.add_request(p, g)
            slots.append(slot)
            streams.append([tok])
        seen = []  # (filtered calls the step added, was the guest live in it)

        def step(guest_live):
            before = _counts(eng)[1]
            out = eng.step()
            for s, st in zip(slots, streams):
                st.extend(out.get(s, []))
            seen.append((_counts(eng)[1] - before, guest_live))

        for _ in range(2):
            step(False)
        assert _counts(eng) == (2 + 2, 0)  # two first tokens, two steps
        if with_guest:
            guest, _ = eng.add_request(
                [400, 3, 77], GenParams(max_new_tokens=4, temperature=0.8, top_p=0.9, seed=3)
            )
            assert _counts(eng)[1] == 1  # its first token is a filtered call
            while eng.active[guest]:
                step(True)
            # finished, not yet released: it no longer counts, on the
            # host or (by the active mask) on the device
            step(False)
            eng.release(guest)
            assert eng.top_ps[guest] == 0.9  # the dead slot keeps its value
        while any(eng.active[s] for s in slots):
            step(False)
        return streams, seen, _counts(eng)

    with_streams, seen, (calls, filtered) = drive(True)
    without_streams, _, (calls_alone, filtered_alone) = drive(False)
    assert with_streams == without_streams
    assert filtered_alone == 0 and calls_alone == 2 + 13
    # first token + the three steps the guest was live in
    assert filtered == 1 + 3 and calls == calls_alone + 1
    assert all(grew == (1 if guest_live else 0) for grew, guest_live in seen)
