"""Prefix-registry lifecycle: the engine-side contract the router's
affinity layer depends on (serving.md §10).

The router records "replica R holds the KV for prefix P" and routes
future turns there — a promise only as good as the registry's own
hygiene: a reassigned slot must drop its stale prompt (the KV rows
were overwritten), ``reset_prefix_cache`` must forget everything, and
a partial-overlap hit must copy ONLY the shared chunk-aligned prefix
(copying more would corrupt the continuation). These are pinned as
unit tests here, not just implied by the bench numbers.
"""

from dstack_tpu.models import llama
from dstack_tpu.serve.engine import GenParams, InferenceEngine
from tests.shared import init_params


def _run_to_completion(eng, slot):
    while eng.active[slot]:
        eng.step()
    eng.release(slot)


def _serve(eng, prompt, gen_len=2):
    slot, _ = eng.add_request(list(prompt), GenParams(max_new_tokens=gen_len))
    _run_to_completion(eng, slot)
    return slot


class TestPrefixRegistryLifecycle:
    def setup_method(self):
        self.config = llama.LLAMA_TINY
        self.params = init_params(self.config, 0)

    def _engine(self, batch=2, chunk=16, max_seq=256):
        return InferenceEngine(
            self.config, self.params, max_batch=batch, max_seq=max_seq,
            prefill_chunk=chunk,
        )

    def test_slot_overwrite_drops_stale_entry(self):
        """A slot reassigned to a new prompt must stop advertising the
        old one: the KV rows it pointed at no longer exist."""
        eng = self._engine(batch=2)
        C = eng.prefill_chunk
        a = [(i % 250) + 1 for i in range(2 * C + 3)]
        b = [((i * 7) % 250) + 1 for i in range(2 * C + 3)]
        slot_a = _serve(eng, a)
        assert eng._prefix_registry[slot_a] == a
        slot_b = _serve(eng, b)
        assert slot_b != slot_a  # free slots NOT in the registry go first
        # both slots now registered; a third admission must reuse one
        # and drop that slot's stale prompt in the same move
        c = [((i * 13) % 250) + 1 for i in range(2 * C + 3)]
        slot_c = _serve(eng, c)
        assert eng._prefix_registry[slot_c] == c
        registered = list(eng._prefix_registry.values())
        # exactly one of a/b survives; the overwritten one is gone
        assert registered.count(a) + registered.count(b) == 1
        # a request sharing the EVICTED prompt's prefix must find no
        # source (the rows it would copy were overwritten); a and b
        # diverge from token 0, so the survivor cannot match either
        evicted = a if a not in registered else b
        follow = evicted[: 2 * C] + [99, 98, 97]
        assert eng._find_prefix_source(follow) == (0, None)

    def test_reset_clears_registry(self):
        eng = self._engine()
        C = eng.prefill_chunk
        a = [(i % 250) + 1 for i in range(2 * C + 3)]
        _serve(eng, a)
        assert eng._prefix_registry
        eng.reset_prefix_cache()
        assert eng._prefix_registry == {}
        hits0 = eng.prefix_hits
        _serve(eng, a)  # identical prompt: would hit if not cleared
        assert eng.prefix_hits == hits0

    def test_partial_overlap_copies_only_shared_prefix(self):
        """A follow-up sharing 2 of 4 chunks must reuse exactly the
        2 shared chunk-aligned ones — and generate the same tokens a
        cold engine does (the copy is correct, not just counted)."""
        eng = self._engine(batch=2, chunk=16, max_seq=256)
        C = eng.prefill_chunk
        a = [(i % 250) + 1 for i in range(4 * C)]
        # shares exactly 2C + 5 tokens, then diverges: chunk-aligned
        # reuse must floor to 2C
        b = a[: 2 * C + 5] + [((i * 11) % 250) + 1 for i in range(2 * C - 5)]
        _serve(eng, a)
        reused0 = eng.prefix_tokens_reused
        hits0 = eng.prefix_hits
        slot_b, first_b = eng.add_request(b, GenParams(max_new_tokens=6))
        got = [first_b]
        while eng.active[slot_b]:
            got.extend(eng.step().get(slot_b, []))
        eng.release(slot_b)
        assert eng.prefix_hits == hits0 + 1
        assert eng.prefix_tokens_reused - reused0 == 2 * C
        # correctness: a cold engine (no cache to reuse) generates the
        # same continuation for b
        cold = InferenceEngine(
            self.config, self.params, max_batch=2, max_seq=256,
            prefill_chunk=C,
        )
        assert got == cold.generate(b, GenParams(max_new_tokens=6))

    def test_prefix_stats_reports_occupancy(self):
        """/health plumbing: prefix_stats mirrors the registry."""
        eng = self._engine(batch=4)
        C = eng.prefill_chunk
        stats = eng.prefix_stats()
        assert stats == {
            "prefix_hits": 0, "prefix_slots": 0,
            "prefix_occupancy": 0.0, "prefix_tokens": 0,
        }
        a = [(i % 250) + 1 for i in range(2 * C)]
        _serve(eng, a)
        stats = eng.prefix_stats()
        assert stats["prefix_slots"] == 1
        assert stats["prefix_occupancy"] == 0.25
        assert stats["prefix_tokens"] == len(a)
        eng.reset_prefix_cache()
        assert eng.prefix_stats()["prefix_slots"] == 0
