"""Traffic is a pure function of the seed. The open loop draws Poisson
arrivals and independent lengths from it; the closed loop gives every
seed the same work in another order."""

import json
import os

import pytest

from benchmark.traffic import generate

CHAT, REASONING = "minitron-4b.chat", "deepseek-v2-lite-9l.reasoning"


def _mix(root, name):
    with open(os.path.join(root, "benchmark", "workloads", f"{name}.json")) as f:
        return json.load(f)["traffic"]


@pytest.mark.parametrize("cell", [CHAT, REASONING])
def test_pure_function_of_the_seed(root, cell):
    mix = _mix(root, cell)
    a = generate.generate(mix, 1000, 2**31 + 7, 10.0)
    b = generate.generate(mix, 1000, 2**31 + 7, 10.0)
    c = generate.generate(mix, 1000, 2**31 + 8, 10.0)
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)
    assert json.dumps(a, sort_keys=True) != json.dumps(c, sort_keys=True)


def test_closed_loop_seeds_offer_the_same_work_in_another_order(root):
    mix = _mix(root, REASONING)
    assert mix["lengths"] == "stratified"
    plans = [generate.generate(mix, 1000, s, 10.0) for s in (3, 4)]
    sizes = [
        sorted((len(r["prompt_ids"]), r["max_tokens"], r["temperature"])[i] for r in p["requests"])
        for p in plans for i in range(3)
    ]
    assert sizes[0] == sizes[3] and sizes[1] == sizes[4] and sizes[2] == sizes[5]
    order = [[len(r["prompt_ids"]) for r in p["requests"]] for p in plans]
    assert order[0] != order[1]
    lo, hi = mix["prompt_tokens"]
    assert all(lo <= len(r["prompt_ids"]) <= hi for r in plans[0]["requests"])
    assert all(0 < t < 1000 for r in plans[0]["requests"] for t in r["prompt_ids"])
    # every stretch of a client's list spans the range: a block holds both halves
    block = plans[0]["requests"][: mix["stratify_block"]]
    mid = (mix["output_tokens"][0] + mix["output_tokens"][1]) / 2
    assert min(r["max_tokens"] for r in block) < mid < max(r["max_tokens"] for r in block)


def test_open_loop_is_a_poisson_process_from_the_seed(root):
    mix = dict(_mix(root, CHAT))
    assert mix["arrivals"] == "poisson" and mix["lengths"] == "iid"
    counts, gaps = [], []
    for seed in range(40):
        plan = generate.generate(mix, 1000, seed, 100.0)
        due = [r["due_s"] for r in plan["requests"]]
        assert due == sorted(due) and -mix["ramp_s"] <= due[0] and due[-1] < 100.0
        assert all((r["phase"] == "ramp") == (r["due_s"] < 0) for r in plan["requests"])
        counts.append(sum(1 for t in due if t >= 0))
        gaps += [b - a for a, b in zip(due, due[1:])]
    mean = sum(counts) / len(counts)
    var = sum((c - mean) ** 2 for c in counts) / (len(counts) - 1)
    expect = mix["rate_rps"] * 100.0
    assert abs(mean - expect) < 0.1 * expect
    assert 0.5 * expect < var < 2.0 * expect  # Poisson: the count's variance is its mean
    g = sum(gaps) / len(gaps)
    assert abs(g - 1 / mix["rate_rps"]) < 0.05 / mix["rate_rps"]
    # exponential gaps: the standard deviation equals the mean
    sd = (sum((x - g) ** 2 for x in gaps) / len(gaps)) ** 0.5
    assert 0.9 * g < sd < 1.1 * g


def test_open_loop_lengths_are_independent_draws_inside_their_bounds(root):
    mix = _mix(root, CHAT)
    plans = [generate.generate(mix, 1000, s, 60.0) for s in (5, 6)]
    sizes = [sorted(len(r["prompt_ids"]) for r in p["requests"]) for p in plans]
    assert sizes[0] != sizes[1]
    for p in plans:
        reqs = p["requests"]
        assert all(mix["prompt_tokens"][0] <= len(r["prompt_ids"]) <= mix["prompt_tokens"][1] for r in reqs)
        assert all(mix["output_tokens"][0] <= r["max_tokens"] <= mix["output_tokens"][1] for r in reqs)
        greedy = [i for i, r in enumerate(reqs) if r["temperature"] == 0.0]
        every = mix["greedy_every"]
        assert greedy == list(range(greedy[0], len(reqs), every)) and greedy[0] < every
        assert all(reqs[i]["seed"] is None for i in greedy)
        assert all(r["seed"] for i, r in enumerate(reqs) if i not in greedy)


def test_closed_loop_never_runs_dry(root):
    mix = _mix(root, REASONING)
    plan = generate.generate(mix, 1000, 5, 40.0)
    per = {}
    for r in plan["requests"]:
        per[r["client"]] = per.get(r["client"], 0) + 1
    assert len(per) == mix["clients"] and min(per.values()) >= 4
    assert len(plan["client_start_s"]) == mix["clients"]
    assert all(-mix["ramp_s"] <= s <= 0 for s in plan["client_start_s"])


@pytest.mark.parametrize("key,cell", [("arrivals", CHAT), ("lengths", CHAT), ("loop", REASONING)])
def test_a_mix_value_the_generator_does_not_know_is_an_error(root, key, cell):
    mix = dict(_mix(root, cell), **{key: "nonesuch"})
    with pytest.raises(ValueError):
        generate.generate(mix, 1000, 1, 5.0)
