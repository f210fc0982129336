"""Inference engine: KV-cache decode must reproduce the full forward
exactly, slots batch continuously, and sampling behaves."""

import jax
import jax.numpy as jnp
import numpy as np

from dstack_tpu.models import llama
from dstack_tpu.serve.engine import GenParams, InferenceEngine, sample
from tests.shared import init_params, jitted


def _reference_greedy(params, config, prompt: list[int], n: int) -> list[int]:
    seq = list(prompt)
    out = []
    forward = jitted(llama.forward, config=config)
    for _ in range(n):
        # one program a 32 tokens (causal: padding behind moves nothing)
        padded = seq + [0] * (-len(seq) % 32)
        logits = forward(params, jnp.asarray([padded], jnp.int32))
        nxt = int(jnp.argmax(logits[0, len(seq) - 1]))
        out.append(nxt)
        seq.append(nxt)
    return out


class TestDecode:
    def setup_method(self):
        self.config = llama.LLAMA_TINY
        self.params = init_params(self.config, 0)

    def test_greedy_matches_full_forward(self):
        eng = InferenceEngine(self.config, self.params, max_batch=2, max_seq=64)
        prompt = [5, 99, 321, 7, 250, 41, 18]
        out = eng.generate(prompt, GenParams(max_new_tokens=8, temperature=0.0))
        assert out == _reference_greedy(self.params, self.config, prompt, 8)

    def test_continuous_batching_interleaves(self):
        """A request admitted mid-decode of another must not perturb
        either stream (per-slot cache isolation + masks). Turbo off:
        the scenario needs s1 still mid-stream when s2 joins, and a
        macro-step would finish s1's whole budget in one call
        (TestTurboDecode covers the macro-step path)."""
        eng = InferenceEngine(
            self.config, self.params, max_batch=4, max_seq=64, turbo_steps=0
        )
        p1 = [10, 20, 30, 40, 50]
        p2 = [400, 3, 77]
        ref1 = _reference_greedy(self.params, self.config, p1, 6)
        ref2 = _reference_greedy(self.params, self.config, p2, 6)

        s1, t1 = eng.add_request(p1, GenParams(max_new_tokens=6))
        got1 = [t1]
        # two solo steps, then p2 joins
        for _ in range(2):
            got1.extend(eng.step().get(s1, []))
        s2, t2 = eng.add_request(p2, GenParams(max_new_tokens=6))
        got2 = [t2]
        while eng.active[s1] or eng.active[s2]:
            out = eng.step()
            got1.extend(out.get(s1, []))
            got2.extend(out.get(s2, []))
        assert got1 == ref1
        assert got2 == ref2

    def test_slot_reuse_after_release(self):
        eng = InferenceEngine(self.config, self.params, max_batch=1, max_seq=64)
        p = [9, 8, 7]
        a = eng.generate(p, GenParams(max_new_tokens=4))
        b = eng.generate(p, GenParams(max_new_tokens=4))
        assert a == b  # stale cache from run 1 must not leak into run 2

    def test_eos_stops(self):
        eng = InferenceEngine(self.config, self.params, max_batch=1, max_seq=64)
        prompt = [5, 99, 321]
        ref = _reference_greedy(self.params, self.config, prompt, 1)
        out = eng.generate(
            prompt, GenParams(max_new_tokens=10, eos_id=ref[0])
        )
        assert out == ref  # first token is eos -> generation ends

    def test_prompt_bucketing_consistent(self):
        """Different prompt lengths land in different pad buckets but
        must produce identical continuations for identical content."""
        eng = InferenceEngine(self.config, self.params, max_batch=2, max_seq=128)
        p_short = [3, 14, 15]
        p_long = [3, 14, 15] * 7  # crosses the 16-bucket boundary
        assert eng.generate(p_short, GenParams(max_new_tokens=3)) == \
            _reference_greedy(self.params, self.config, p_short, 3)
        assert eng.generate(p_long, GenParams(max_new_tokens=3)) == \
            _reference_greedy(self.params, self.config, p_long, 3)


def _sample(
    logits, seeds, temps, top_ps, top_ks=None, rep_pens=None, seen=None,
    pres=None, freq=None,
):
    """Thin wrapper: per-row seeds → key_data; defaults for new knobs."""
    b, v = logits.shape
    kd = jnp.stack(
        [jax.random.key_data(jax.random.key(s)) for s in seeds]
    )
    counts = seen if seen is not None else jnp.zeros((b, v), jnp.int32)
    toks, _ = sample(
        logits, kd, jnp.asarray(temps), jnp.asarray(top_ps),
        jnp.asarray(top_ks if top_ks is not None else [0] * b, jnp.int32),
        jnp.asarray(rep_pens if rep_pens is not None else [1.0] * b, jnp.float32),
        counts,
        jnp.asarray(pres if pres is not None else [0.0] * b, jnp.float32),
        jnp.asarray(freq if freq is not None else [0.0] * b, jnp.float32),
        # unit tests treat the given counts as generated-only too
        counts,
    )
    return toks


class TestSampling:
    def test_greedy_at_zero_temperature(self):
        logits = jnp.asarray([[0.0, 5.0, 1.0], [2.0, 0.0, -1.0]], jnp.float32)
        out = _sample(logits, [0, 0], [0.0, 0.0], [1.0, 1.0])
        assert list(np.asarray(out)) == [1, 0]

    def test_top_p_narrow_nucleus_is_greedy(self):
        logits = jnp.asarray([[0.0, 5.0, 1.0]], jnp.float32)
        out = _sample(logits, [1], [1.0], [1e-6])
        assert int(out[0]) == 1

    def test_sampling_valid_and_varied(self):
        logits = jnp.zeros((1, 16), jnp.float32)  # uniform
        seen = set()
        for i in range(12):
            out = _sample(logits, [i], [1.0], [1.0])
            tok = int(out[0])
            assert 0 <= tok < 16
            seen.add(tok)
        assert len(seen) > 1  # actually sampling, not collapsing

    def test_top_k_restricts_support(self):
        logits = jnp.asarray([[0.0, 3.0, 2.0, 1.0, -1.0]] * 1, jnp.float32)
        for i in range(10):
            out = _sample(logits, [i], [5.0], [1.0], top_ks=[2])
            assert int(out[0]) in (1, 2)  # only the top-2 logits

    def test_presence_penalty_flips_argmax(self):
        logits = jnp.asarray([[0.0, 2.0, 1.9]], jnp.float32)
        counts = jnp.zeros((1, 3), jnp.int32).at[0, 1].set(1)
        out = _sample(logits, [0], [0.0], [1.0], seen=counts, pres=[0.5])
        assert int(out[0]) == 2  # 2.0 - 0.5 < 1.9
        out = _sample(logits, [0], [0.0], [1.0], seen=counts, pres=[0.05])
        assert int(out[0]) == 1  # small penalty: argmax unchanged

    def test_frequency_penalty_scales_with_count(self):
        logits = jnp.asarray([[0.0, 2.0, 1.9]], jnp.float32)
        once = jnp.zeros((1, 3), jnp.int32).at[0, 1].set(1)
        thrice = jnp.zeros((1, 3), jnp.int32).at[0, 1].set(3)
        # 0.05/occurrence: 1 hit keeps argmax, 3 hits flip it
        out = _sample(logits, [0], [0.0], [1.0], seen=once, freq=[0.05])
        assert int(out[0]) == 1
        out = _sample(logits, [0], [0.0], [1.0], seen=thrice, freq=[0.05])
        assert int(out[0]) == 2

    def test_repetition_penalty_flips_argmax(self):
        # token 1 leads, but was seen; a strong penalty hands the
        # argmax to unseen token 2
        logits = jnp.asarray([[0.0, 2.0, 1.9]], jnp.float32)
        seen = jnp.zeros((1, 3), jnp.int32).at[0, 1].set(1)
        out = _sample(
            logits, [0], [0.0], [1.0], rep_pens=[2.0], seen=seen
        )
        assert int(out[0]) == 2
        # penalty off: argmax stays at 1 even though seen
        out = _sample(logits, [0], [0.0], [1.0], rep_pens=[1.0], seen=seen)
        assert int(out[0]) == 1

    def test_seeded_streams_deterministic(self):
        logits = jnp.zeros((2, 32), jnp.float32)
        a = _sample(logits, [7, 9], [1.0, 1.0], [1.0, 1.0])
        b = _sample(logits, [7, 9], [1.0, 1.0], [1.0, 1.0])
        assert list(np.asarray(a)) == list(np.asarray(b))
        # a slot's stream depends only on its own key
        c = _sample(logits, [7, 123], [1.0, 1.0], [1.0, 1.0])
        assert int(a[0]) == int(c[0])


class TestTensorParallelServing:
    def test_tp_matches_single_device(self):
        """tp=2 sharded serving must reproduce the unsharded greedy
        stream exactly (params sharded over heads/mlp, cache over KV
        heads, psums inserted by GSPMD)."""
        from dstack_tpu.parallel.mesh import MeshConfig, make_mesh

        config = llama.LLAMA_TINY
        params = init_params(config, 0)
        prompt = [11, 22, 33, 44]
        ref = InferenceEngine(config, params, max_batch=2, max_seq=64).generate(
            prompt, GenParams(max_new_tokens=5)
        )
        mesh = make_mesh(MeshConfig(dp=1, fsdp=1, tp=2))
        eng = InferenceEngine(
            config, params, max_batch=2, max_seq=64, mesh=mesh
        )
        assert eng.generate(prompt, GenParams(max_new_tokens=5)) == ref

    def test_tp_indivisible_kv_heads_rejected(self):
        import pytest

        from dstack_tpu.parallel.mesh import MeshConfig, make_mesh

        config = llama.LLAMA_TINY  # 2 kv heads
        params = init_params(config, 0)
        mesh = make_mesh(MeshConfig(dp=1, fsdp=1, tp=4))
        with pytest.raises(ValueError):
            InferenceEngine(config, params, mesh=mesh)


class TestChunkedPrefill:
    """Long prompts prefill in fixed-size chunks; results must be
    identical to the one-shot path, and the scheduler-facing API must
    let decode interleave between chunks."""

    config = llama.LLAMA_TINY

    def setup_method(self):
        self.params = init_params(self.config, 0)

    def test_multi_chunk_matches_reference(self):
        # chunk=32, prompt 80 → 3 chunks (two full + padded tail)
        eng = InferenceEngine(
            self.config, self.params, max_batch=2, max_seq=256,
            prefill_chunk=32,
        )
        prompt = [(7 * i + 3) % self.config.vocab_size for i in range(80)]
        ref = _reference_greedy(self.params, self.config, prompt, 5)
        out = eng.generate(prompt, GenParams(max_new_tokens=5))
        assert out == ref

    def test_chunk_boundary_exact_multiple(self):
        eng = InferenceEngine(
            self.config, self.params, max_batch=1, max_seq=256,
            prefill_chunk=32,
        )
        prompt = [(5 * i + 1) % self.config.vocab_size for i in range(64)]
        ref = _reference_greedy(self.params, self.config, prompt, 4)
        assert eng.generate(prompt, GenParams(max_new_tokens=4)) == ref

    def test_decode_interleaves_between_chunks(self):
        """A running slot keeps decoding while another slot's long
        prompt prefills chunk by chunk."""
        eng = InferenceEngine(
            self.config, self.params, max_batch=2, max_seq=256,
            prefill_chunk=32,
        )
        p1 = [3, 14, 15]
        p2 = [(11 * i + 2) % self.config.vocab_size for i in range(96)]
        ref1 = _reference_greedy(self.params, self.config, p1, 8)
        ref2 = _reference_greedy(self.params, self.config, p2, 4)

        s1, t1 = eng.add_request(p1, GenParams(max_new_tokens=8))
        got1 = [t1]
        # start the long prompt; decode s1 between every chunk
        s2 = eng.start_request(p2, GenParams(max_new_tokens=4))
        assert s2 in eng.prefilling_slots()
        first2 = None
        got2 = []
        while first2 is None:
            first2 = eng.prefill_step(s2)
            out = eng.step()  # s1 advances during s2's prefill
            got1.extend(out.get(s1, []))
            got2.extend(out.get(s2, []))  # step right after activation
        got2 = [first2] + got2
        while eng.active[s1] or eng.active[s2]:
            out = eng.step()
            got1.extend(out.get(s1, []))
            got2.extend(out.get(s2, []))
        assert got1 == ref1
        assert got2 == ref2

    def test_release_during_prefill_frees_slot(self):
        eng = InferenceEngine(
            self.config, self.params, max_batch=1, max_seq=256,
            prefill_chunk=32,
        )
        p = [(3 * i) % self.config.vocab_size for i in range(96)]
        slot = eng.start_request(p, GenParams(max_new_tokens=4))
        assert eng.free_slots() == []
        assert eng.prefill_step(slot) is None  # first chunk only
        eng.release(slot)
        assert eng.free_slots() == [slot]
        # slot reusable and correct afterwards
        ref = _reference_greedy(self.params, self.config, [1, 2, 3], 3)
        assert eng.generate([1, 2, 3], GenParams(max_new_tokens=3)) == ref

    def test_max_seq_not_multiple_of_chunk(self):
        """The final chunk must clip at the cache row end, not clamp
        and shift the written K/V."""
        eng = InferenceEngine(
            self.config, self.params, max_batch=1, max_seq=200,
            prefill_chunk=64,
        )
        # prompt long enough that the last chunk would cross max_seq
        prompt = [(13 * i + 5) % self.config.vocab_size for i in range(190)]
        ref = _reference_greedy(self.params, self.config, prompt, 3)
        out = eng.generate(prompt, GenParams(max_new_tokens=3))
        assert out == ref


class TestSpeculativeDecoding:
    """Prompt-lookup speculation must be lossless for greedy decoding
    and actually accelerate repetitive text."""

    config = llama.LLAMA_TINY

    def setup_method(self):
        self.params = init_params(self.config, 0)

    def test_lossless_vs_disabled(self):
        prompt = [7, 8, 9, 10] * 6  # repetitive: drafts will fire
        on = InferenceEngine(
            self.config, self.params, max_batch=1, max_seq=128, spec_draft=4
        )
        off = InferenceEngine(
            self.config, self.params, max_batch=1, max_seq=128, spec_draft=0
        )
        g = GenParams(max_new_tokens=12)
        assert on.generate(prompt, g) == off.generate(prompt, GenParams(max_new_tokens=12))

    def test_emits_multiple_tokens_per_step_on_repetition(self):
        eng = InferenceEngine(
            self.config, self.params, max_batch=1, max_seq=128, spec_draft=4
        )
        prompt = [5, 6] * 10
        slot, _ = eng.add_request(prompt, GenParams(max_new_tokens=16))
        steps, tokens = 0, 0
        while eng.active[slot]:
            out = eng.step()
            steps += 1
            tokens += len(out.get(slot, []))
            assert steps < 50
        # a tiny random model may not repeat itself, but the history
        # n-grams from the prompt guarantee at least SOME drafted steps;
        # losslessness is covered above — here we check the machinery
        # emits exactly the budget across fewer-or-equal steps
        assert tokens == 15  # max_new_tokens - 1 (first came from prefill)
        assert steps <= tokens

    def test_sampled_requests_bypass_speculation(self):
        eng = InferenceEngine(
            self.config, self.params, max_batch=1, max_seq=128, spec_draft=4
        )
        prompt = [5, 6] * 8
        slot, _ = eng.add_request(
            prompt, GenParams(max_new_tokens=6, temperature=1.0, seed=3)
        )
        while eng.active[slot]:
            out = eng.step()
            for toks in out.values():
                assert len(toks) == 1  # plain path only
        eng.release(slot)

    def test_find_draft_matches_last_ngram(self):
        eng = InferenceEngine(
            self.config, self.params, max_batch=1, max_seq=64, spec_draft=3
        )
        eng._record_tokens(0, [1, 2, 3, 4, 5, 2, 3])
        # tail (2,3) previously at index 1; following tokens: 4,5,2
        assert eng._find_draft(0) == [4, 5, 2]
        eng.history[0] = []
        eng._ngram_ix[0] = {}
        eng._record_tokens(0, [9, 9, 1, 7])
        assert eng._find_draft(0) == []  # no earlier (1,7)


class TestTurboDecode:
    """Device-side decode macro-steps (decode_loop) must be invisible
    except for emission granularity: same tokens, same finish reasons,
    same per-slot bookkeeping as the per-step path."""

    config = llama.LLAMA_TINY

    def setup_method(self):
        self.params = init_params(self.config, 0)

    def _engine(self, turbo: int, **kw):
        kw.setdefault("max_batch", 2)
        kw.setdefault("max_seq", 64)
        return InferenceEngine(
            self.config, self.params, spec_draft=0, turbo_steps=turbo, **kw
        )

    def test_matches_per_step_path(self):
        prompt = [5, 99, 321, 7, 250]
        on = self._engine(8)
        off = self._engine(0)
        g = lambda: GenParams(max_new_tokens=13)  # noqa: E731
        assert on.generate(prompt, g()) == off.generate(prompt, g())

    def test_multi_token_emission_and_budget(self):
        eng = self._engine(4)
        slot, first = eng.add_request([3, 1, 4, 1, 5], GenParams(max_new_tokens=10))
        calls, got = 0, [first]
        while eng.active[slot]:
            out = eng.step()
            calls += 1
            got.extend(out.get(slot, []))
        # 9 post-prefill tokens over 4-step macro-steps: ≤ 3 dispatches
        assert calls <= 3
        assert len(got) == 10
        assert eng.finish_reason[slot] == "length"

    def test_eos_mid_macro_step(self):
        prompt = [5, 99, 321]
        ref = _reference_greedy(self.params, self.config, prompt, 4)
        eng = self._engine(8)
        slot, first = eng.add_request(
            prompt, GenParams(max_new_tokens=10, eos_id=ref[3])
        )
        got = [first]
        while eng.active[slot]:
            got.extend(eng.step().get(slot, []))
        # emission stops AT the eos token, exactly like _emit
        assert got == ref[:4]
        assert eng.finish_reason[slot] == "stop"
        # device stopped writing this row mid-loop: lengths match host
        # (the first token was sampled at prefill; 3 decode increments)
        assert eng.lengths[slot] == len(prompt) + 3

    def test_slots_finish_on_different_steps(self):
        eng = self._engine(8, max_batch=2)
        p1, p2 = [10, 20, 30], [400, 3, 77, 9]
        ref1 = _reference_greedy(self.params, self.config, p1, 3)
        ref2 = _reference_greedy(self.params, self.config, p2, 9)
        s1, t1 = eng.add_request(p1, GenParams(max_new_tokens=3))
        s2, t2 = eng.add_request(p2, GenParams(max_new_tokens=9))
        got1, got2 = [t1], [t2]
        while eng.active[s1] or eng.active[s2]:
            out = eng.step()
            got1.extend(out.get(s1, []))
            got2.extend(out.get(s2, []))
        # s1 exhausts its budget mid-macro-step; s2 decodes on (the
        # deactivated row must neither emit nor corrupt s2's stream)
        assert got1 == ref1
        assert got2 == ref2

    def test_pipelined_depth_matches_per_step(self):
        # turbo_depth chains macro-steps device-side with one fetch —
        # emission must stay byte-identical to the per-step path
        prompt = [5, 99, 321, 7, 250]
        on = self._engine(4, turbo_depth=3, turbo_quiet_s=0.0)
        off = self._engine(0)
        g = lambda: GenParams(max_new_tokens=25)  # noqa: E731
        assert on.generate(prompt, g()) == off.generate(prompt, g())

    def test_pipelined_single_fetch_per_chain(self):
        eng = self._engine(4, turbo_depth=2, turbo_quiet_s=0.0, max_seq=128)
        slot, first = eng.add_request(
            [3, 1, 4, 1, 5], GenParams(max_new_tokens=17)
        )
        calls, got = 0, [first]
        while eng.active[slot]:
            out = eng.step()
            calls += 1
            got.extend(out.get(slot, []))
        assert len(got) == 17
        # 16 post-prefill tokens / (depth 2 × 4-step macro) = 2 chains
        assert calls <= 2
        assert eng.finish_reason[slot] == "length"

    def test_pipelined_eos_mid_chain(self):
        # EOS inside segment 1 of a depth-2 chain: segment 2 runs fully
        # masked on device; the host replay stops at the eos token
        prompt = [5, 99, 321]
        ref = _reference_greedy(self.params, self.config, prompt, 4)
        eng = self._engine(4, turbo_depth=2, turbo_quiet_s=0.0, max_seq=128)
        slot, first = eng.add_request(
            prompt, GenParams(max_new_tokens=20, eos_id=ref[3])
        )
        got = [first]
        while eng.active[slot]:
            got.extend(eng.step().get(slot, []))
        assert got == ref[:4]
        assert eng.finish_reason[slot] == "stop"
        assert eng.lengths[slot] == len(prompt) + 3

    def test_device_state_cache_slot_reuse(self):
        # the cached device-side decode state must invalidate on
        # release + re-admission (slot reuse), not leak stale budgets
        eng = self._engine(4, turbo_depth=2, turbo_quiet_s=0.0, max_seq=128)
        off = self._engine(0)
        for prompt in ([5, 99, 321], [7, 8, 9, 10]):
            g = lambda: GenParams(max_new_tokens=9)  # noqa: E731
            assert eng.generate(prompt, g()) == off.generate(prompt, g())

    def test_device_state_cache_staggered_admission(self):
        # a turbo chain caches device state; a new admission mid-run
        # must invalidate it so the fresh slot's budget/eos are seen
        eng = self._engine(4, turbo_depth=2, turbo_quiet_s=0.0, max_seq=128)
        p1, p2 = [10, 20, 30], [400, 3, 77, 9]
        ref1 = _reference_greedy(self.params, self.config, p1, 12)
        ref2 = _reference_greedy(self.params, self.config, p2, 8)
        s1, t1 = eng.add_request(p1, GenParams(max_new_tokens=12))
        got1, got2 = [t1], []
        got1.extend(eng.step().get(s1, []))  # chain runs, state cached
        s2, t2 = eng.add_request(p2, GenParams(max_new_tokens=8))
        got2.append(t2)
        while eng.active[s1] or eng.active[s2]:
            out = eng.step()
            got1.extend(out.get(s1, []))
            got2.extend(out.get(s2, []))
        assert got1 == ref1
        assert got2 == ref2

    def test_sampled_batch_bypasses_turbo(self):
        eng = self._engine(8, max_batch=1, max_seq=128)
        slot, _ = eng.add_request(
            [5, 6, 7, 8], GenParams(max_new_tokens=6, temperature=1.0, seed=3)
        )
        while eng.active[slot]:
            out = eng.step()
            for toks in out.values():
                assert len(toks) == 1  # per-step sampler path only

    def test_turbo_waits_for_pending_prefill(self):
        eng = self._engine(8, max_batch=2, max_seq=256, prefill_chunk=32)
        s1, _ = eng.add_request([3, 14, 15], GenParams(max_new_tokens=20))
        # a long prompt is mid-chunk: decode must stay per-step so the
        # scheduler can interleave the remaining chunks
        s2 = eng.start_request(list(range(1, 97)), GenParams(max_new_tokens=4))
        out = eng.step()
        assert len(out.get(s1, [])) == 1
        assert s2 in eng.prefilling_slots()


class TestPenaltyScopes:
    def test_prompt_tokens_do_not_feed_additive_penalties(self):
        """OpenAI semantics: presence/frequency penalties count only
        GENERATED tokens — a long prompt must not pre-ban its own
        vocabulary on the first sampled token."""
        config = llama.LLAMA_TINY
        params = init_params(config, 0)
        prompt = [7, 8, 9] * 8
        base = InferenceEngine(config, params, max_batch=1, max_seq=128)
        pen = InferenceEngine(config, params, max_batch=1, max_seq=128)
        a = base.generate(prompt, GenParams(max_new_tokens=1))
        # huge penalties: if prompt tokens counted, the first token's
        # distribution would shift; generated-only counts are empty at
        # the first token, so greedy argmax must be identical
        b = pen.generate(
            prompt,
            GenParams(
                max_new_tokens=1, presence_penalty=2.0, frequency_penalty=2.0
            ),
        )
        assert a == b


class TestSpecWithFamilyDeltas:
    def test_lossless_on_gemma2_style_config(self):
        """verify_step must honor per-layer sliding windows, softcaps,
        qk-norm-free sandwich norms etc. — speculation on a config with
        all deltas enabled must equal the non-speculative stream."""
        config = llama.dataclasses.replace(
            llama.LLAMA_TINY,
            norm_offset=True, embed_scale=True, post_norms=True,
            hidden_act="gelu_tanh", sliding_window=16, sliding_pattern=2,
            attn_softcap=30.0, logit_softcap=20.0,
        )
        params = init_params(config, 3)
        prompt = [4, 5, 6] * 8
        on = InferenceEngine(
            config, params, max_batch=1, max_seq=128, spec_draft=4
        )
        off = InferenceEngine(
            config, params, max_batch=1, max_seq=128, spec_draft=0
        )
        a = on.generate(prompt, GenParams(max_new_tokens=10))
        b = off.generate(prompt, GenParams(max_new_tokens=10))
        assert a == b

    def test_lossless_with_qk_norm(self):
        config = llama.dataclasses.replace(llama.LLAMA_TINY, qk_norm=True)
        params = init_params(config, 4)
        prompt = [9, 9, 2] * 6
        on = InferenceEngine(
            config, params, max_batch=1, max_seq=128, spec_draft=3
        )
        off = InferenceEngine(
            config, params, max_batch=1, max_seq=128, spec_draft=0
        )
        assert on.generate(prompt, GenParams(max_new_tokens=8)) == \
            off.generate(prompt, GenParams(max_new_tokens=8))


class TestMLADecode:
    """DeepSeek MLA serving: the absorbed-form engine (compressed
    [B, T, rank+rope] latent cache, MQA-over-latent attention) must
    reproduce the non-absorbed llama.forward rollout token-exactly —
    covering the dense first-k prelude, sigmoid/bias/group routing,
    chunked prefill, turbo macro-steps, and speculative verification."""

    config = llama.MLA_TINY

    def setup_method(self):
        self.params = init_params(self.config, 0)

    def test_cache_is_compressed_latent(self):
        from dstack_tpu.serve.engine import init_cache

        cache = init_cache(self.config, 2, 32)
        assert set(cache) == {"ckv"}
        c = self.config
        assert cache["ckv"].shape == (
            c.n_layers, 2, 32, c.kv_lora_rank + c.qk_rope_head_dim
        )

    def test_greedy_matches_full_forward(self):
        eng = InferenceEngine(
            self.config, self.params, max_batch=2, max_seq=64,
            spec_draft=0, turbo_steps=0,
        )
        prompt = [5, 99, 321, 7, 250, 41, 18]
        out = eng.generate(prompt, GenParams(max_new_tokens=8, temperature=0.0))
        assert out == _reference_greedy(self.params, self.config, prompt, 8)

    def test_chunked_prefill_matches(self):
        eng = InferenceEngine(
            self.config, self.params, max_batch=2, max_seq=96,
            prefill_chunk=16, spec_draft=0, turbo_steps=0,
        )
        prompt = list(range(3, 40))  # 37 tokens → 3 chunks
        out = eng.generate(prompt, GenParams(max_new_tokens=6, temperature=0.0))
        assert out == _reference_greedy(self.params, self.config, prompt, 6)

    def test_turbo_matches_per_step(self):
        prompt = [5, 99, 321, 7, 250]
        on = InferenceEngine(
            self.config, self.params, max_batch=2, max_seq=64,
            spec_draft=0, turbo_steps=8,
        )
        off = InferenceEngine(
            self.config, self.params, max_batch=2, max_seq=64,
            spec_draft=0, turbo_steps=0,
        )
        g = lambda: GenParams(max_new_tokens=13)  # noqa: E731
        assert on.generate(prompt, g()) == off.generate(prompt, g())

    def test_speculative_lossless(self):
        # a repetitive prompt gives the n-gram drafter material
        prompt = [7, 8, 9, 7, 8, 9, 7, 8]
        spec = InferenceEngine(
            self.config, self.params, max_batch=2, max_seq=96,
            spec_draft=4, turbo_steps=0,
        )
        plain = InferenceEngine(
            self.config, self.params, max_batch=2, max_seq=96,
            spec_draft=0, turbo_steps=0,
        )
        g = lambda: GenParams(max_new_tokens=16)  # noqa: E731
        assert spec.generate(prompt, g()) == plain.generate(prompt, g())

    def test_continuous_batching_isolated(self):
        eng = InferenceEngine(
            self.config, self.params, max_batch=4, max_seq=64,
            spec_draft=0, turbo_steps=0,
        )
        p1 = [10, 20, 30, 40, 50]
        p2 = [400, 3, 77]
        ref1 = _reference_greedy(self.params, self.config, p1, 6)
        ref2 = _reference_greedy(self.params, self.config, p2, 6)
        s1, t1 = eng.add_request(p1, GenParams(max_new_tokens=6))
        got1 = [t1]
        for _ in range(2):
            got1.extend(eng.step().get(s1, []))
        s2, t2 = eng.add_request(p2, GenParams(max_new_tokens=6))
        got2 = [t2]
        while eng.active[s1] or eng.active[s2]:
            out = eng.step()
            got1.extend(out.get(s1, []))
            got2.extend(out.get(s2, []))
        assert got1 == ref1
        assert got2 == ref2


class TestPrefixCache:
    """Automatic prefix caching: chunk-aligned KV rows of a cached
    prompt are device-copied into the new slot and their prefill chunks
    skipped — output must be token-identical to a cold engine."""

    config = llama.LLAMA_TINY

    def setup_method(self):
        self.params = init_params(self.config, 0)

    def _engine(self, **kw):
        kw.setdefault("max_batch", 3)
        kw.setdefault("max_seq", 96)
        kw.setdefault("prefill_chunk", 16)
        kw.setdefault("spec_draft", 0)
        kw.setdefault("turbo_steps", 0)
        return InferenceEngine(self.config, self.params, **kw)

    def test_hit_is_token_exact(self):
        shared = list(range(40, 80))  # 40-token shared "system prompt"
        p1 = shared + [3, 1]
        p2 = shared + [9, 9, 2]
        cold = self._engine(prefix_cache=False)
        ref2 = cold.generate(p2, GenParams(max_new_tokens=6))
        eng = self._engine()
        eng.generate(p1, GenParams(max_new_tokens=4))
        out2 = eng.generate(p2, GenParams(max_new_tokens=6))
        assert eng.prefix_hits == 1
        # 40 shared tokens, chunk 16 → 32 rows copied, 2 chunks skipped
        assert eng.prefix_tokens_reused == 32
        assert out2 == ref2

    def test_source_active_during_reuse(self):
        shared = list(range(10, 50))
        p1 = shared + [5]
        p2 = shared + [7, 8]
        cold = self._engine(prefix_cache=False)
        ref1 = cold.generate(p1, GenParams(max_new_tokens=8))
        ref2 = self._engine(prefix_cache=False).generate(
            p2, GenParams(max_new_tokens=6))
        eng = self._engine()
        s1, t1 = eng.add_request(p1, GenParams(max_new_tokens=8))
        got1 = [t1]
        got1.extend(eng.step().get(s1, []))  # s1 mid-decode
        s2, t2 = eng.add_request(p2, GenParams(max_new_tokens=6))
        assert eng.prefix_hits == 1
        got2 = [t2]
        while eng.active[s1] or eng.active[s2]:
            out = eng.step()
            got1.extend(out.get(s1, []))
            got2.extend(out.get(s2, []))
        assert got1 == ref1
        assert got2 == ref2

    def test_short_prompts_never_reuse(self):
        eng = self._engine()
        eng.generate([1, 2, 3], GenParams(max_new_tokens=2))
        eng.generate([1, 2, 3, 4], GenParams(max_new_tokens=2))
        assert eng.prefix_hits == 0

    def test_registry_evicted_on_slot_reuse(self):
        eng = self._engine(max_batch=1)
        p = list(range(40))
        eng.generate(p + [1], GenParams(max_new_tokens=2))
        assert 0 in eng._prefix_registry
        # the only slot is also the only candidate: reuse must disable
        # itself rather than copy from the slot being overwritten
        eng.generate(p + [2], GenParams(max_new_tokens=2))
        assert eng.prefix_hits == 0
        assert eng._prefix_registry.get(0) == p + [2]

    def test_mla_prefix_cache(self):
        config = llama.MLA_TINY
        params = init_params(config, 0)
        shared = list(range(30, 70))
        p2 = shared + [3, 4]
        cold = InferenceEngine(
            config, params, max_batch=2, max_seq=96, prefill_chunk=16,
            spec_draft=0, turbo_steps=0, prefix_cache=False)
        ref = cold.generate(p2, GenParams(max_new_tokens=5))
        eng = InferenceEngine(
            config, params, max_batch=2, max_seq=96, prefill_chunk=16,
            spec_draft=0, turbo_steps=0)
        eng.generate(shared + [1], GenParams(max_new_tokens=3))
        out = eng.generate(p2, GenParams(max_new_tokens=5))
        assert eng.prefix_hits == 1
        assert out == ref


class TestKVQuant:
    """int8 KV cache: per-(token, head) scales, dequant fused into the
    attention dots. Quantization perturbs logits slightly, so tests
    assert bounded drift and structural correctness, not token
    equality."""

    config = llama.LLAMA_TINY

    def setup_method(self):
        self.params = init_params(self.config, 0)

    def _engine(self, kv_quant, **kw):
        kw.setdefault("max_batch", 2)
        kw.setdefault("max_seq", 64)
        kw.setdefault("spec_draft", 0)
        kw.setdefault("turbo_steps", 0)
        return InferenceEngine(self.config, self.params, kv_quant=kv_quant, **kw)

    def test_cache_layout(self):
        eng = self._engine("int8")
        import jax.numpy as jnp

        assert eng.cache["k"].dtype == jnp.int8
        assert eng.cache["k_s"].shape == eng.cache["k"].shape[:-1]

    def test_roundtrip_error_bounded(self):
        from dstack_tpu.serve.engine import kv_dequant, kv_quantize
        import jax.numpy as jnp
        import numpy as np

        x = jax.random.normal(jax.random.key(1), (2, 4, 8, 32), jnp.float32)
        q, s = kv_quantize(x)
        back = kv_dequant(q, s, jnp.float32)
        rel = np.abs(np.asarray(back - x)).max() / np.abs(np.asarray(x)).max()
        assert rel < 1.5 / 127  # half-step absmax error

    def test_scales_stored_f32_under_bf16_compute(self):
        """Scales stay FLOAT32 even when the model computes in bf16
        (bf16 scale storage would stack ~0.4% multiplicative error on
        every dequantized vector), and dequant applies the f32 scale at
        full precision — only the result rounds to bf16."""
        import dataclasses

        import jax.numpy as jnp
        import numpy as np

        from dstack_tpu.serve.engine import init_cache, kv_dequant, kv_quantize

        bf16_cfg = dataclasses.replace(self.config, dtype=jnp.bfloat16)
        cache = init_cache(bf16_cfg, 2, 32, kv_quant="int8")
        assert cache["k_s"].dtype == jnp.float32
        assert cache["v_s"].dtype == jnp.float32
        assert cache["k"].dtype == jnp.int8

        x = jax.random.normal(jax.random.key(2), (2, 4, 8, 32), jnp.float32)
        q, s = kv_quantize(x)
        back = np.asarray(kv_dequant(q, s, jnp.bfloat16), np.float32)
        rel = np.abs(back - np.asarray(x)).max() / np.abs(np.asarray(x)).max()
        # int8 half-step + one bf16 RESULT rounding — no second
        # scale-rounding term
        assert rel < 1.5 / 127 + 0.005, rel

    def test_decode_logits_close_to_exact(self):
        from dstack_tpu.serve.engine import GenParams as GP

        prompt = [5, 99, 321, 7, 250, 41, 18]
        exact = self._engine(None)
        quant = self._engine("int8")
        se, _ = exact.add_request(list(prompt), GP(max_new_tokens=2))
        sq, _ = quant.add_request(list(prompt), GP(max_new_tokens=2))
        import numpy as np
        from dstack_tpu.serve.engine import decode_step
        import jax.numpy as jnp

        toks = jnp.asarray([prompt[-1], 0], jnp.int32)
        pos = jnp.asarray([len(prompt), 0], jnp.int32)
        mask = jnp.asarray([True, False])
        le, _ = decode_step(exact.params, exact.cache, toks, pos,
                            exact.config, write_mask=mask)
        lq, _ = decode_step(quant.params, quant.cache, toks, pos,
                            quant.config, write_mask=mask)
        diff = np.abs(np.asarray(le[0]) - np.asarray(lq[0])).max()
        spread = np.abs(np.asarray(le[0])).max()
        assert diff < 0.05 * max(spread, 1.0), (diff, spread)

    def test_generation_and_prefix_cache(self):
        eng = self._engine("int8", max_seq=96, prefill_chunk=16, max_batch=3)
        shared = list(range(40, 80))
        out1 = eng.generate(shared + [3], GenParams(max_new_tokens=5))
        assert len(out1) == 5
        out2 = eng.generate(shared + [9, 2], GenParams(max_new_tokens=5))
        assert len(out2) == 5
        assert eng.prefix_hits == 1  # the copy fn handles the scales too

    def test_speculative_runs(self):
        eng = self._engine("int8", max_seq=96, spec_draft=4)
        prompt = [7, 8, 9, 7, 8, 9, 7, 8]
        out = eng.generate(prompt, GenParams(max_new_tokens=12))
        assert len(out) <= 12 and len(out) > 0

    def test_mla_refuses(self):
        import pytest

        config = llama.MLA_TINY
        params = init_params(config, 0)
        with pytest.raises(ValueError, match="MLA"):
            InferenceEngine(config, params, max_batch=2, max_seq=32,
                            kv_quant="int8")


class TestAdaptiveTurbo:
    """Adaptive macro-step K: floor while requests arrive/wait,
    exponential ramp to turbo_steps when arrival-quiet, snap back on
    pressure — a new arrival must not wait a 128-step device loop."""

    config = llama.LLAMA_TINY

    def _engine(self, **kw):
        params = init_params(self.config, 0)
        kw.setdefault("max_batch", 2)
        kw.setdefault("max_seq", 256)
        kw.setdefault("spec_draft", 0)
        kw.setdefault("turbo_steps", 64)
        kw.setdefault("turbo_quiet_s", 0.0)  # quiet immediately
        return InferenceEngine(self.config, params, **kw)

    def test_ramp_and_snap_back(self):
        eng = self._engine()
        eng.add_request(list(range(1, 9)), GenParams(max_new_tokens=200))
        eng._last_admit = 0.0  # pretend the admission was long ago
        caps = [eng._adaptive_turbo_cap() for _ in range(5)]
        assert caps == [16, 32, 64, 64, 64]
        # pressure: a waiting request snaps K back to the floor
        eng.waiting_requests = 1
        assert eng._adaptive_turbo_cap() == 8
        eng.waiting_requests = 0
        assert eng._adaptive_turbo_cap() == 16  # ramps again

    def test_fresh_arrival_holds_floor(self):
        eng = self._engine(turbo_quiet_s=60.0)
        eng.add_request(list(range(1, 9)), GenParams(max_new_tokens=200))
        # the admission just happened → inside the quiet window
        assert eng._adaptive_turbo_cap() == 8
        assert eng._adaptive_turbo_cap() == 8

    def test_turbo_step_emits_at_most_cap(self):
        eng = self._engine()
        slot, _ = eng.add_request(list(range(1, 9)), GenParams(max_new_tokens=200))
        eng._last_admit = 0.0
        out = eng.step()  # first turbo macro-step after quiet: K=16
        assert 0 < len(out.get(slot, [])) <= 16
        total = sum(len(v) for v in out.values())
        assert total <= 16


class TestExpertParallelServing:
    def test_ep_mesh_matches_single_device(self):
        """MoE serving over an ep mesh: experts shard over the expert
        axis (GSPMD turns the dispatch einsums into all_to_all) and the
        greedy stream must match unsharded serving exactly."""
        from dstack_tpu.parallel.mesh import MeshConfig, make_mesh

        config = llama.MOE_TINY
        params = init_params(config, 0)
        prompt = [11, 22, 33, 44]
        ref = InferenceEngine(
            config, params, max_batch=2, max_seq=64,
            spec_draft=0, turbo_steps=0,
        ).generate(prompt, GenParams(max_new_tokens=5))
        mesh = make_mesh(MeshConfig(dp=1, fsdp=1, ep=2, tp=2))
        eng = InferenceEngine(
            config, params, max_batch=2, max_seq=64, mesh=mesh,
            spec_draft=0, turbo_steps=0,
        )
        assert eng.generate(prompt, GenParams(max_new_tokens=5)) == ref


def _drive_packed(eng, prompts, gens, stagger=None):
    """Admit prompts at staggered wave offsets, drive prefill_wave +
    step interleaved to completion → per-request token lists."""
    stagger = stagger or [0] * len(prompts)
    slots, outs = {}, [[] for _ in prompts]
    admitted, wave = 0, 0
    def live():
        return any(eng.active[s] for s in slots)
    while admitted < len(prompts) or eng.prefilling_slots() or live():
        while (
            admitted < len(prompts)
            and stagger[admitted] <= wave
            and eng.free_slots()
        ):
            s = eng.start_request(prompts[admitted], gens[admitted])
            slots[s] = admitted
            admitted += 1
        for s, t in eng.prefill_wave().items():
            outs[slots[s]].append(t)
        for s, toks in eng.step().items():
            if s in slots:
                outs[slots[s]].extend(toks)
        wave += 1
        assert wave < 500
    return outs


class TestPackedPrefill:
    """Packed multi-slot prefill (one [G, C] dispatch per chunk wave)
    must be token-identical to serial per-prompt prefill — the
    masked-future invariant: short rows, pad rows, and unequal starts
    all scatter out of range instead of corrupting neighbors."""

    config = llama.LLAMA_TINY

    def setup_method(self):
        self.params = init_params(self.config, 0)

    def _engine(self, **kw):
        kw.setdefault("max_batch", 4)
        kw.setdefault("max_seq", 128)
        kw.setdefault("prefill_chunk", 16)
        kw.setdefault("prefill_pack", 4)
        kw.setdefault("spec_draft", 0)
        kw.setdefault("turbo_steps", 0)
        return InferenceEngine(self.config, self.params, **kw)

    def test_staggered_greedy_burst_matches_reference(self):
        # lengths straddle chunk boundaries; arrival 3 joins mid-wave
        # so the pack holds rows at unequal starts
        prompts = [
            [(7 * i + 3) % self.config.vocab_size for i in range(40)],
            [5, 99, 321, 7, 250],
            [(11 * i + 2) % self.config.vocab_size for i in range(23)],
            [(5 * i + 1) % self.config.vocab_size for i in range(33)],
        ]
        gens = [GenParams(max_new_tokens=5) for _ in prompts]
        eng = self._engine()
        outs = _drive_packed(eng, prompts, gens, stagger=[0, 0, 0, 1])
        for p, got in zip(prompts, outs):
            assert got == _reference_greedy(self.params, self.config, p, 5)
        # the burst actually packed: fewer dispatches than serial chunks
        rows = eng.metrics.family("dtpu_serve_prefill_pack_rows")
        assert rows.sum() > rows.count()  # some dispatch carried > 1 row

    def test_seeded_sampled_burst_matches_serial(self):
        prompts = [list(range(3, 40)), list(range(60, 85)), [9, 9, 2, 7]]
        mk = lambda: [  # noqa: E731
            GenParams(max_new_tokens=6, temperature=0.9, seed=11),
            GenParams(max_new_tokens=6, temperature=1.3, seed=5),
            GenParams(max_new_tokens=6, temperature=0.7, seed=2),
        ]
        packed = _drive_packed(self._engine(), prompts, mk())
        serial = _drive_packed(self._engine(prefill_pack=0), prompts, mk())
        assert packed == serial

    def test_prefix_hit_row_packs_at_unequal_start(self):
        """A prefix-cache-resumed row (start 32) packs with a fresh row
        (start 0) in one dispatch; both streams must stay exact."""
        shared = list(range(40, 80))
        p2 = shared + [9, 9, 2]
        p3 = [7, 3, 1, 4, 4, 2, 9] * 3
        cold = self._engine(prefix_cache=False, prefill_pack=0)
        ref2 = cold.generate(p2, GenParams(max_new_tokens=5))
        ref3 = cold.generate(p3, GenParams(max_new_tokens=5))
        eng = self._engine()
        eng.generate(shared + [3, 1], GenParams(max_new_tokens=3))
        outs = _drive_packed(
            eng, [p2, p3],
            [GenParams(max_new_tokens=5), GenParams(max_new_tokens=5)],
        )
        assert eng.prefix_hits == 1
        assert outs[0] == ref2
        assert outs[1] == ref3

    def test_mla_packed_matches_serial(self):
        config = llama.MLA_TINY
        params = init_params(config, 0)
        mk = lambda n: InferenceEngine(  # noqa: E731
            config, params, max_batch=4, max_seq=96, prefill_chunk=16,
            prefill_pack=n, spec_draft=0, turbo_steps=0,
        )
        prompts = [list(range(3, 40)), [5, 99, 321, 7]]
        gens = lambda: [GenParams(max_new_tokens=4)] * 2  # noqa: E731
        assert _drive_packed(mk(4), prompts, gens()) == \
            _drive_packed(mk(0), prompts, gens())

    def test_release_mid_wave_frees_slot(self):
        eng = self._engine()
        p = [(3 * i) % self.config.vocab_size for i in range(60)]
        s1 = eng.start_request(p, GenParams(max_new_tokens=4))
        s2 = eng.start_request([1, 2, 3], GenParams(max_new_tokens=4))
        eng.prefill_wave()  # s2 completes, s1 mid-prompt
        eng.release(s1)
        assert s1 in eng.free_slots()
        ref = _reference_greedy(self.params, self.config, [4, 5, 6], 3)
        assert eng.generate([4, 5, 6], GenParams(max_new_tokens=3)) == ref

    def test_lone_aligned_row_takes_serial_path(self):
        """A single chunk-aligned pending prompt keeps the static-start
        serial path (flash-kernel eligible); a burst takes the packed
        one."""
        eng = self._engine()
        eng.start_request(list(range(40)), GenParams(max_new_tokens=2))
        eng.prefill_wave()
        assert not eng._packed_fns  # serial: (C, start) variant only
        assert eng._chunk_fns
        eng.start_request(list(range(50, 90)), GenParams(max_new_tokens=2))
        eng.prefill_wave()
        assert eng._packed_fns  # two rows pending → packed dispatch


class TestDecodeStateMirror:
    """_plain_step keeps (token, position, budget, active) device-
    resident between steps instead of re-uploading host lists per
    sampled token; EVERY host-side slot mutation must invalidate the
    mirror (the _invalidate_decode_cache contract) or decode silently
    runs from stale state."""

    config = llama.LLAMA_TINY

    def setup_method(self):
        self.params = init_params(self.config, 0)

    def _engine(self, **kw):
        kw.setdefault("max_batch", 2)
        kw.setdefault("max_seq", 64)
        kw.setdefault("spec_draft", 0)
        kw.setdefault("turbo_steps", 0)
        return InferenceEngine(self.config, self.params, **kw)

    def test_mirror_set_after_step_cleared_on_mutation(self):
        eng = self._engine()
        slot, _ = eng.add_request([5, 9, 21], GenParams(max_new_tokens=8))
        assert eng._turbo_state is None  # activation invalidated it
        eng.step()
        assert eng._turbo_state is not None  # mirror survives the step
        eng.release(slot)
        assert eng._turbo_state is None  # release must invalidate

    def test_slot_reuse_not_stale(self):
        # a fresh request into a just-released slot must decode from
        # its own state, not the mirror of the previous occupant
        eng = self._engine(max_batch=1)
        ref = self._engine(max_batch=1)
        for prompt in ([5, 99, 321], [7, 8, 9, 10]):
            g = lambda: GenParams(  # noqa: E731
                max_new_tokens=7, temperature=1.1, seed=13
            )
            assert eng.generate(prompt, g()) == ref.generate(prompt, g())

    def test_staggered_admission_sampled_not_stale(self):
        # admission mid-stream mutates slot state: the mirror must
        # rebuild or the newcomer decodes from garbage
        eng = self._engine(max_batch=3, max_seq=128)
        one = self._engine(max_batch=3, max_seq=128)
        g1 = lambda: GenParams(max_new_tokens=8, temperature=0.9, seed=3)  # noqa: E731
        g2 = lambda: GenParams(max_new_tokens=6, temperature=1.2, seed=9)  # noqa: E731
        p1, p2 = [10, 20, 30, 40], [400, 3, 77]
        ref1 = one.generate(p1, g1())
        ref2 = one.generate(p2, g2())
        s1, t1 = eng.add_request(p1, g1())
        got1, got2 = [t1], []
        got1.extend(eng.step().get(s1, []))  # mirror now cached
        s2, t2 = eng.add_request(p2, g2())
        got2.append(t2)
        while eng.active[s1] or eng.active[s2]:
            out = eng.step()
            got1.extend(out.get(s1, []))
            got2.extend(out.get(s2, []))
        assert got1 == ref1
        assert got2 == ref2

    def test_sampling_params_mirror_reused_and_invalidated(self):
        # the 7 per-slot sampling-parameter lists only change on
        # admission/release, so the sampled path must NOT re-upload
        # them per token (the DTPU002 defect this mirror fixed) — and
        # a new admission with different params must rebuild them
        eng = self._engine(max_batch=2, max_seq=128)
        s1, _ = eng.add_request(
            [5, 9, 21], GenParams(max_new_tokens=8, temperature=0.9, seed=3)
        )
        # activation publishes a fresh mirror already holding the new
        # request's knobs (it sampled the first token through it)
        first = eng._sampling_state
        assert first is not None
        assert abs(float(first[0][s1]) - 0.9) < 1e-6  # temps row
        eng.step()
        assert eng._sampling_state is first  # survives the per-token advance
        eng.step()
        assert eng._sampling_state is first  # reused, not re-uploaded
        s2, _ = eng.add_request(
            [7, 8], GenParams(max_new_tokens=4, temperature=1.3, seed=9)
        )
        rebuilt = eng._sampling_state
        assert rebuilt is not None and rebuilt is not first  # admission rebuilt
        assert abs(float(rebuilt[0][s2]) - 1.3) < 1e-6  # temps row
        assert abs(float(rebuilt[0][s1]) - 0.9) < 1e-6  # s1's row kept


class TestCompileCacheAccounting:
    """Packing must not reintroduce a per-(start-combination) compile
    zoo: packed variants are keyed (G, C) with TRACED starts, so a
    mixed packed/serial/prefix-hit workload stays within
    (log2 pack + 1) × (log2 chunk/16 + 1) packed variants and the
    serial path's documented (C, start) grid."""

    config = llama.LLAMA_TINY

    def test_variant_count_bounded_across_start_combinations(self):
        import math

        params = init_params(self.config, 0)
        chunk, pack = 16, 4
        eng = InferenceEngine(
            self.config, params, max_batch=4, max_seq=128,
            prefill_chunk=chunk, prefill_pack=pack,
            spec_draft=0, turbo_steps=0,
        )
        gen = lambda: GenParams(max_new_tokens=2)  # noqa: E731
        shared = list(range(40, 80))
        # serial request (registers a reusable prefix), then three
        # bursts with different length mixes and a prefix-hit row —
        # many distinct start combinations through the packed path
        eng.generate(shared + [1], gen())
        bursts = [
            [list(range(3, 40)), [5, 6, 7]],
            [shared + [9, 2], list(range(60, 95)), [4, 4]],
            [list(range(10, 73)), list(range(20, 41)), [8], [9, 1, 2]],
        ]
        for prompts in bursts:
            _drive_packed(eng, prompts, [gen() for _ in prompts])
        packed_bound = (int(math.log2(pack)) + 1) * (
            int(math.log2(eng.prefill_chunk // 16)) + 1
        )
        assert len(eng._packed_fns) <= packed_bound, eng._packed_fns
        # serial variants: chunk-aligned starts only (short buckets at
        # start 0 + one per chunk-multiple start) — never one per odd
        # packed start
        assert all(s % chunk == 0 for (_, s) in eng._chunk_fns)
        n_packed = len(eng._packed_fns)
        # MORE start combinations must not mint new packed variants
        _drive_packed(
            eng,
            [list(range(30, 95)), list(range(5, 22)), [7, 7, 7]],
            [gen()] * 3,
        )
        assert len(eng._packed_fns) == n_packed


class TestLogitBiasMinP:
    config = llama.LLAMA_TINY

    def setup_method(self):
        self.params = init_params(self.config, 0)
        self.eng = InferenceEngine(
            self.config, self.params, max_batch=2, max_seq=64,
            spec_draft=0, turbo_steps=0,
        )

    def test_positive_bias_forces_token(self):
        prompt = [5, 9, 21, 7]
        out = self.eng.generate(
            prompt, GenParams(max_new_tokens=3, logit_bias={"77": 100.0}))
        assert out == [77, 77, 77]

    def test_negative_bias_bans_argmax(self):
        prompt = [5, 9, 21, 7]
        base = self.eng.generate(prompt, GenParams(max_new_tokens=1))
        banned = self.eng.generate(
            prompt,
            GenParams(max_new_tokens=1, logit_bias={str(base[0]): -100.0}))
        assert banned[0] != base[0]

    def test_min_p_one_is_greedy(self):
        """min_p=1.0 keeps only the argmax token — a seeded sampled
        stream collapses to the greedy stream."""
        prompt = [5, 9, 21, 7, 3]
        greedy = self.eng.generate(prompt, GenParams(max_new_tokens=6))
        sampled = self.eng.generate(
            prompt,
            GenParams(max_new_tokens=6, temperature=1.5, min_p=1.0, seed=7))
        assert sampled == greedy

    def test_min_p_zero_still_varies(self):
        prompt = [5, 9, 21, 7, 3]
        greedy = self.eng.generate(prompt, GenParams(max_new_tokens=8))
        sampled = self.eng.generate(
            prompt,
            GenParams(max_new_tokens=8, temperature=3.0, min_p=0.0, seed=7))
        assert sampled != greedy  # hot sampling without the floor differs


class TestResumableGeneration:
    """Mid-stream failover's core premise (serving.md §9): a partially
    generated sequence is just a longer prompt. Re-prefilling
    prompt+delivered on a FRESH engine (= another replica) must
    continue the original token stream exactly — greedy trivially,
    seeded sampling via ``GenParams.seed_skip`` replaying the
    per-token PRNG advance."""

    def setup_method(self):
        self.config = llama.LLAMA_TINY
        self.params = init_params(self.config, 0)

    def _engine(self):
        return InferenceEngine(
            self.config, self.params, max_batch=2, max_seq=64
        )

    def test_greedy_resume_continues_identically(self):
        prompt = [5, 99, 321, 7, 250]
        full = self._engine().generate(
            prompt, GenParams(max_new_tokens=10, temperature=0.0)
        )
        assert len(full) == 10
        cut = 4  # tokens the client already received before the death
        resumed = self._engine().generate(
            prompt + full[:cut],
            GenParams(max_new_tokens=10 - cut, temperature=0.0),
        )
        assert resumed == full[cut:]

    def test_seeded_resume_replays_prng(self):
        prompt = [5, 9, 21, 33]
        full = self._engine().generate(
            prompt, GenParams(max_new_tokens=10, temperature=1.1, seed=13)
        )
        assert len(full) == 10
        cut = 5
        g = GenParams(
            max_new_tokens=10 - cut, temperature=1.1, seed=13, seed_skip=cut
        )
        resumed = self._engine().generate(prompt + full[:cut], g)
        assert resumed == full[cut:]

    def test_seeded_resume_with_repetition_penalty(self):
        """The multiplicative repetition penalty sees prompt+generated
        tokens; on resume the delivered tokens re-enter via the prompt
        mark, so the penalty state — and hence the stream — is exact."""
        prompt = [5, 9, 21, 33, 7]
        g0 = GenParams(
            max_new_tokens=8, temperature=0.9, seed=3,
            repetition_penalty=1.3,
        )
        full = self._engine().generate(prompt, g0)
        assert len(full) == 8
        cut = 3
        g = GenParams(
            max_new_tokens=8 - cut, temperature=0.9, seed=3,
            repetition_penalty=1.3, seed_skip=cut,
        )
        resumed = self._engine().generate(prompt + full[:cut], g)
        assert resumed == full[cut:]

    def test_seed_skip_zero_is_identity(self):
        prompt = [5, 9, 21, 33]
        a = self._engine().generate(
            prompt, GenParams(max_new_tokens=6, temperature=1.1, seed=13)
        )
        b = self._engine().generate(
            prompt,
            GenParams(max_new_tokens=6, temperature=1.1, seed=13, seed_skip=0),
        )
        assert a == b


class TestAbandonStep:
    """The engine watchdog's epoch guard: a step abandoned mid-wedge
    must return empty-handed when it finally wakes, never corrupt the
    reused slot state."""

    def setup_method(self):
        config = llama.LLAMA_TINY
        params = init_params(config, 0)
        self.eng = InferenceEngine(config, params, max_batch=2, max_seq=64)

    def test_abandon_reports_wedge_phase_and_bumps_epoch(self):
        self.eng._step_wedge = ("slot", 1)
        epoch = self.eng._step_epoch
        assert self.eng.abandon_step() == ("slot", 1)
        assert self.eng._step_epoch == epoch + 1
        assert self.eng._step_wedge is None
        assert self.eng.abandon_step() is None  # nothing in flight now

    def test_stale_step_returns_empty_after_abandon(self):
        """Simulate the watchdog racing a wedged step: bumping the
        epoch mid-step makes the step discard its result (the fault
        hook runs between the per-slot fires, exactly where a hang
        wakes up)."""
        from dstack_tpu import faults

        slot, tok = self.eng.add_request([5, 9, 21], GenParams(max_new_tokens=4))
        calls = []
        real_fire = faults.fire

        def abandoning_fire(point, **ctx):
            if point == "serve.engine.step" and not calls:
                calls.append(ctx)
                self.eng.abandon_step()  # the watchdog gave up on us
            return real_fire(point, **ctx)

        faults.fire = abandoning_fire
        try:
            assert self.eng.step() == {}  # stale epoch: no tokens, no mutation
        finally:
            faults.fire = real_fire
        # slot state untouched by the abandoned step: a normal step
        # afterwards continues the stream
        assert self.eng.active[slot]
        out = self.eng.step()
        assert slot in out and out[slot]


class TestFlightRecorder:
    """Engine-side flight recorder wiring (obs/flight.py): per-step
    and per-wave records with strictly host-side batch composition,
    compile accounting into the ENGINE's registry, and the wedge
    record + post-mortem on abandon_step — the black box the watchdog
    chaos acceptance reads."""

    config = llama.LLAMA_TINY

    def setup_method(self):
        from dstack_tpu.obs import flight

        self.params = init_params(self.config, 0)
        self._prior = flight.get_recorder()
        self.rec = flight.enable(buffer=256)

    def teardown_method(self):
        from dstack_tpu.obs import flight

        if self._prior is not None:
            flight._recorder = self._prior
            flight.record = self._prior.record
        else:
            flight.disable()

    def _engine(self, **kw):
        kw.setdefault("max_batch", 4)
        kw.setdefault("max_seq", 128)
        return InferenceEngine(self.config, self.params, **kw)

    def test_step_records_phase_timing_and_traces(self):
        eng = self._engine(turbo_steps=0, spec_draft=0)
        gen = GenParams(max_new_tokens=4)
        gen.trace_id = "feedc0de"
        slot, tok = eng.add_request([5, 9, 21, 7], gen)
        while eng.active[slot]:
            eng.step()
        recs = self.rec.records(200)
        prefills = [r for r in recs if r["phase"] == "prefill"]
        steps = [r for r in recs if r["phase"] == "decode"]
        assert prefills and steps
        p = prefills[-1]
        assert p["slots"] == [slot] and p["g"] == 1 and p["rows"] == 1
        assert p["dispatch_s"] > 0
        assert p["traces"] == {slot: "feedc0de"}
        s = steps[-1]
        assert s["slots"] == [slot]
        assert s["tokens"] >= 1
        assert s["dispatch_s"] > 0 and s["host_s"] >= 0
        assert 0.0 <= s["kv_util"] <= 1.0
        assert s["traces"] == {slot: "feedc0de"}
        # spec/turbo paths name themselves too
        eng2 = self._engine(turbo_steps=8, spec_draft=0)
        eng2.generate([5, 9, 21, 7], GenParams(max_new_tokens=6))
        assert any(r["phase"] == "turbo" for r in self.rec.records(50))

    def test_packed_wave_records_bucket_composition(self):
        eng = self._engine(
            prefill_chunk=16, prefill_pack=4, spec_draft=0, turbo_steps=0
        )
        _drive_packed(
            eng,
            [list(range(3, 40)), list(range(60, 95)), [5, 6, 7]],
            [GenParams(max_new_tokens=2) for _ in range(3)],
        )
        waves = [
            r for r in self.rec.records(200)
            if r["phase"] == "prefill_packed"
        ]
        assert waves, "packed waves must flight-record"
        w = waves[0]
        assert w["rows"] == 3 and w["g"] == 4  # 3 rows → G=4 bucket
        assert len(w["slots"]) == 3 and len(w["starts"]) == 3
        assert w["dispatch_s"] > 0

    def test_compile_accounting_lands_in_engine_registry(self):
        eng = self._engine(spec_draft=0, turbo_steps=0)
        eng.generate([5, 9, 21, 7], GenParams(max_new_tokens=3))
        compiles = eng.metrics.family("dtpu_serve_compiles_total")
        # the cold path compiled at least the chunk prefill + decode
        assert compiles.value("chunk") >= 1
        assert compiles.value("decode") >= 1
        assert eng.metrics.family(
            "dtpu_serve_compile_seconds"
        ).count("chunk") >= 1
        # ring carries the causing bucket key for the memoized grid
        keys = [
            r.get("key") for r in self.rec.records(200)
            if r["phase"] == "compile" and r.get("fn") == "chunk"
        ]
        assert keys and all(k for k in keys)
        # cache-size gauges reflect the memoized grids at scrape time
        eng.update_state_gauges()
        g = eng.metrics.family("dtpu_serve_compile_cache_entries")
        assert g.value("chunk") == len(eng._chunk_fns) >= 1

    def test_abandon_step_writes_wedge_record_and_postmortem(self):
        eng = self._engine(turbo_steps=0, spec_draft=0)
        eng.fault_ctx = {"replica": "r7"}
        gen = GenParams(max_new_tokens=8)
        gen.trace_id = "abad1dea"
        slot, _ = eng.add_request([5, 9, 21, 7], gen)
        pm0 = len(self.rec.postmortems())
        eng._step_wedge = ("slot", slot)  # the watchdog's view mid-hang
        assert eng.abandon_step() == ("slot", slot)
        # the ring's LAST record is the wedge marker naming the slot
        # and its trace — what the post-mortem's tail carries
        last = self.rec.records(1)[0]
        assert last["phase"] == "wedge"
        assert last["slot"] == slot and last["trace"] == "abad1dea"
        assert last["replica"] == "r7"
        pms = self.rec.postmortems()
        assert len(pms) == pm0 + 1
        pm = pms[-1]
        assert pm["reason"] == "watchdog_abort"
        assert pm["ctx"]["wedge"] == f"slot:{slot}"
        assert pm["ctx"]["slots"] == {slot: "abad1dea"}
        assert pm["records"][-1]["phase"] == "wedge"
        # a None phase (step finished concurrently) must NOT post-mortem
        assert eng.abandon_step() is None
        assert len(self.rec.postmortems()) == pm0 + 1

    def test_disabled_engine_writes_nothing(self):
        from dstack_tpu.obs import flight

        flight.disable()
        assert flight.record is flight._noop_record
        eng = self._engine(spec_draft=0, turbo_steps=0)
        eng.generate([5, 9, 21, 7], GenParams(max_new_tokens=3))
        # jit sites carry NO wrapper (identity) when built disabled
        from dstack_tpu.obs.flight import JitWatch

        assert not isinstance(eng._decode, JitWatch)
        assert not any(
            isinstance(f, JitWatch) for f in eng._chunk_fns.values()
        )
        # re-enabling later shows an empty ring: nothing was recorded
        rec = flight.enable(buffer=8)
        assert rec.records(10) == []


class TestSteadyStateRecompiles:
    """The recompile regression gate (the runtime complement of
    DTPU003's noqa pragmas): run the engine through mixed greedy /
    sampled / packed traffic TWICE — the first pass compiles the
    power-of-two bucket grid, the second pass must compile NOTHING.
    If a bucketing contract breaks (e.g. a memoization dict keyed by a
    caller-supplied value), this test fails before any TPU ever pays
    the stall."""

    config = llama.LLAMA_TINY

    def setup_method(self):
        from dstack_tpu.obs import flight

        self._prior = flight.get_recorder()
        self.rec = flight.enable(buffer=512)

    def teardown_method(self):
        from dstack_tpu.obs import flight

        if self._prior is not None:
            flight._recorder = self._prior
            flight.record = self._prior.record
        else:
            flight.disable()

    def _mixed_pass(self, eng):
        gen = lambda **kw: GenParams(max_new_tokens=3, **kw)  # noqa: E731
        # greedy serial (short + long buckets), sampled, seeded with
        # penalties, logit-bias, and a packed burst with a prefix hit
        eng.generate(list(range(3, 20)), gen())
        eng.generate(list(range(40, 80)) + [1], gen())
        eng.generate([5, 9, 21, 7], gen(temperature=0.8, seed=3))
        eng.generate(
            [5, 9, 21, 7, 3],
            gen(temperature=0.9, seed=5, repetition_penalty=1.2),
        )
        eng.generate([5, 9, 21], gen(logit_bias={"7": 2.0}))
        _drive_packed(
            eng,
            [list(range(40, 80)) + [9, 2], list(range(60, 95)), [4, 4]],
            [gen() for _ in range(3)],
        )

    def test_second_pass_compiles_nothing(self):
        params = init_params(self.config, 0)
        eng = InferenceEngine(
            self.config, params, max_batch=4, max_seq=128,
            prefill_chunk=16, prefill_pack=4, spec_draft=0,
            turbo_steps=4,
        )
        self._mixed_pass(eng)
        compiles = eng.metrics.family("dtpu_serve_compiles_total")
        first = {
            labels[0]: v for labels, v in compiles.items()
        }
        assert first, "cold pass must have compiled something"
        # the boot-compile manifest captured exactly the variants the
        # cold pass visited (same repr stringification as the flight
        # ring, so the two views can never disagree on identity)
        observed_cold = {
            e["fn"] + (e["key"] or "")
            for e in self.rec.compile_events(512)
        }
        assert eng.compile_manifest() == observed_cold
        eng.mark_flight_warm()
        self._mixed_pass(eng)  # identical traffic: all buckets warm
        second = {
            labels[0]: v for labels, v in compiles.items()
        }
        assert second == first, (
            "steady-state traffic minted new compile variants: "
            f"{ {k: second[k] - first.get(k, 0) for k in second} }"
        )
        recompiles = eng.metrics.family("dtpu_serve_recompiles_total")
        assert recompiles.items() == [], "recompiles flagged after warmup"
        assert not any(
            r["phase"] == "recompile" for r in self.rec.records(512)
        )
        # ... and therefore zero warmup-coverage gaps: every pass-2 key
        # sits inside the pass-1 manifest
        gaps = eng.metrics.family("dtpu_serve_warmup_gap_compiles_total")
        assert gaps.items() == [], "gap detector fired on covered traffic"

    def test_skipped_warmup_bucket_fails_the_gate(self):
        """The negative half of the manifest gate: a deliberately THIN
        warmup (greedy serial only — it never visits the packed
        prefill grid or the sampling variants) marks warm, then full
        mixed traffic arrives. Every compile it pays must be flagged
        as a warmup-coverage gap — the un-warmed-grid-cell bug class
        detected, not merely priced as a generic recompile."""
        from dstack_tpu.obs import boot

        params = init_params(self.config, 0)
        eng = InferenceEngine(
            self.config, params, max_batch=4, max_seq=128,
            prefill_chunk=16, prefill_pack=4, spec_draft=0,
            turbo_steps=4,
        )
        gen = lambda **kw: GenParams(max_new_tokens=3, **kw)  # noqa: E731
        eng.generate(list(range(3, 20)), gen())  # the whole "warmup"
        manifest = eng.compile_manifest()
        assert manifest, "thin warmup still compiles its own bucket"
        eng.mark_flight_warm()
        self._mixed_pass(eng)
        gaps = eng.metrics.family("dtpu_serve_warmup_gap_compiles_total")
        gap_total = sum(v for _, v in gaps.items())
        assert gap_total > 0, (
            "mixed traffic compiled outside a thin warmup manifest but "
            "the gap detector stayed silent"
        )
        # the manifest froze at warm: post-warm compiles never
        # retroactively join it (else the gate would self-heal shut)
        assert eng.compile_manifest() == manifest
        # manifest_diff tells the same story from the flight events
        observed = {
            e["fn"] + (e["key"] or "")
            for e in self.rec.compile_events(512)
        }
        diff = boot.manifest_diff(manifest, observed)
        assert diff["gaps"], diff
        assert gap_total == len(diff["gaps"]), (gap_total, diff)
