"""Every engine jit site compiles under a stable program name: the
name of the function it wraps, which is what a profiler capture shows
on the device's ``XLA Modules`` line (``jit_<program>``) and what the
benchmark's per-layer metrics select by. The ``fn`` label of
``dtpu_serve_compiles_total`` is a different, older name for the same
site; PERF.md §3 has the table this test pins."""

import re

import pytest

from dstack_tpu.models import llama
from dstack_tpu.obs import flight
from dstack_tpu.serve.engine import GenParams, InferenceEngine
from tests.shared import init_params

# compile label (flight / dtpu_serve_compiles_total{fn}) → program
LABEL_PROGRAM = {
    "decode": "decode_step",
    "verify": "verify_step",
    "turbo": "decode_loop",
    "chunk": "prefill_chunk_step",
    "packed": "prefill_packed_step",
    "copy": "copy_cache_prefix",
    "advance_state": "advance_decode_state",
    "argmax": "argmax",
    "logprobs": "token_logprobs",
    "skip_key": "skip_key_data",
    # named before this table existed; `sampler_device_share` reads `sample`
    "sample": "sample",
    "mark_prompt": "_mark_prompt",
    "mark_seen": "_mark_seen",
}


@pytest.fixture(scope="module")
def lowered_names():
    """Drive a tiny engine through every jit site with
    ``flight.watch_jit`` replaced by a spy that lowers each site with
    the arguments of its first call → {label: {module names}}."""
    seen: dict = {}

    def spy(fn, name, registry=None, key=None, **kw):
        state = {"first": True}

        def call(*args, **kwargs):
            if state["first"]:
                state["first"] = False
                text = fn.lower(*args, **kwargs).as_text()
                seen.setdefault(name, set()).add(
                    re.match(r"module @(\S+)", text).group(1)
                )
            return fn(*args, **kwargs)

        return call

    mp = pytest.MonkeyPatch()
    mp.setattr(flight, "watch_jit", spy)
    try:
        config = llama.LLAMA_TINY
        params = init_params(config, 0)
        eng = InferenceEngine(
            config, params, max_batch=4, max_seq=128, prefill_chunk=16,
            spec_draft=0, turbo_steps=4,
        )

        def drain(*slots):
            while any(eng.active[s] for s in slots):
                eng.step()
            for s in slots:
                eng.release(s)

        base = [(7 * i) % 250 + 1 for i in range(40)]
        # sampled, resumed seed, logprobs: chunk, mark_prompt, skip_key,
        # sample, logprobs; its steps: decode, mark_seen, advance_state
        slot, _ = eng.add_request(base, GenParams(
            max_new_tokens=3, temperature=0.8, seed=7, seed_skip=2, logprobs=1,
        ))
        drain(slot)
        # same 32-token prefix again: copy; greedy alone: turbo
        slot, _ = eng.add_request(
            base[:32] + [3, 1, 4, 1, 5], GenParams(max_new_tokens=6)
        )
        drain(slot)
        # two prompts pending at once: packed
        slots = [
            eng.start_request([k + 1] * 20, GenParams(max_new_tokens=2))
            for k in range(2)
        ]
        pending = set(slots)
        while pending:
            pending -= set(eng.prefill_wave())
        drain(*slots)
        # greedy with a draft: verify + argmax
        eng.spec_draft = 3
        eng._find_draft = lambda slot: [1, 2, 3]
        slot, _ = eng.add_request([9, 8, 7, 6], GenParams(max_new_tokens=6))
        drain(slot)
    finally:
        mp.undo()
    return seen


@pytest.mark.parametrize("label", sorted(LABEL_PROGRAM))
def test_jit_site_lowers_under_its_program_name(lowered_names, label):
    assert lowered_names.get(label) == {f"jit_{LABEL_PROGRAM[label]}"}


def test_no_engine_jit_site_is_left_out(lowered_names):
    """A new jit site must get a row in ``LABEL_PROGRAM`` (and in
    PERF.md's table): an unnamed one would be ``jit__unknown`` again."""
    assert set(lowered_names) == set(LABEL_PROGRAM)
