"""Multi-tenant QoS chaos scenarios.

The acceptance story for overload isolation is behavioral, not
unit-level: one abusive tenant flooding the OpenAI server at many
times its budget must (a) receive 429 + monotone ``Retry-After`` —
never a raw 5xx, never an engine wedge — and (b) leave a victim
tenant's TTFT essentially unmoved. Plus: the ``serve.admit`` /
``routing.admit`` fault points force the shed path deterministically,
and the token bucket's schedule is a pure function of its clock.
"""

import asyncio
import time

from dstack_tpu import faults, qos
from dstack_tpu.models import llama
from dstack_tpu.qos import PriorityPending, QoSPolicy, TokenBucket
from dstack_tpu.serve.engine import InferenceEngine
from dstack_tpu.serve.openai_server import build_app
from dstack_tpu.serve.tokenizer import ByteTokenizer
from tests.shared import init_params


class TestTokenBucketDeterminism:
    def test_schedule_is_pure_function_of_clock(self):
        """Seeded (fake) time → the exact admit/shed sequence, twice."""

        def run_schedule():
            t = [0.0]
            b = TokenBucket(rate=2.0, burst=2.0, clock=lambda: t[0])
            out = []
            # 0.0s: burst of 4 → 2 admit, 2 shed
            for _ in range(4):
                out.append(b.try_acquire())
            t[0] = 0.5  # one token refilled
            out.append(b.try_acquire())
            out.append(b.try_acquire())
            t[0] = 10.0  # long quiet: refill caps at burst
            for _ in range(3):
                out.append(b.try_acquire())
            return out

        expected = [True, True, False, False, True, False, True, True, False]
        assert run_schedule() == expected
        assert run_schedule() == expected

    def test_retry_after_is_monotone_under_flood(self):
        """With no admits in between, successive shed hints never grow:
        the hint tracks the refill schedule, not the shed count."""
        t = [0.0]
        b = TokenBucket(rate=0.5, burst=1.0, clock=lambda: t[0])
        assert b.try_acquire()
        hints = []
        for i in range(5):
            t[0] = 0.1 * (i + 1)
            assert not b.try_acquire()
            hints.append(b.retry_after())
        assert hints == sorted(hints, reverse=True)
        # and following the final hint lands on a token
        t[0] = 0.5 + hints[-1]
        assert b.try_acquire()

    def test_refund_restores_spent_tokens_capped_at_burst(self):
        """The two-phase serve charge refunds its pre-parse token on a
        fan-out shed: tokens come back exactly, never past burst, and
        the post-refund full-cost deficit equals the pre-refund
        extra-cost deficit (so the returned hint is the full-cost
        wait)."""
        t = [0.0]
        b = TokenBucket(rate=1.0, burst=4.0, clock=lambda: t[0])
        assert b.try_acquire()  # the pre-parse token (4 -> 3)
        assert not b.try_acquire(5.0)  # extra=5 > 3: shed
        hint_pre = b.retry_after(5.0)
        b.refund(1.0)
        assert b.retry_after(6.0) == hint_pre  # full cost, same deficit
        assert b.try_acquire(4.0)  # the refund restored the full burst
        b.refund(99.0)
        assert b.tokens == 4.0  # capped at burst

    def test_zero_rate_bucket_is_hard_closed(self):
        b = TokenBucket(rate=0.0, burst=1.0, clock=lambda: 0.0)
        assert b.try_acquire()  # the initial burst token
        assert not b.try_acquire()
        assert b.retry_after() == 3600.0


class TestTenantBuckets:
    def test_full_map_evicts_idle_buckets_before_overflowing(self):
        """Rotated throwaway identities (e.g. unverified Bearer tokens)
        must not poison the bounded map forever: once their buckets
        refill to full they are evicted — losslessly, a full bucket is
        indistinguishable from a fresh one — and new tenants get real
        buckets again instead of the shared overflow."""
        from dstack_tpu.qos import TenantBuckets

        t = [0.0]
        tb = TenantBuckets(rate=1.0, burst=2.0, max_tenants=4,
                           clock=lambda: t[0])
        for i in range(4):  # fill the map, drain each bucket
            b = tb.bucket(f"throwaway-{i}")
            assert b.try_acquire() and b.try_acquire()
        # map full + buckets drained: a new tenant lands in overflow
        assert tb.bucket("late") is tb.bucket(TenantBuckets._OVERFLOW)
        t[0] = 2.0  # every drained bucket refills to full → evictable
        fresh = tb.bucket("late2")
        assert fresh is not tb.bucket(TenantBuckets._OVERFLOW)
        assert fresh.try_acquire()

    def test_active_buckets_survive_eviction_sweep(self):
        from dstack_tpu.qos import TenantBuckets

        t = [0.0]
        tb = TenantBuckets(rate=0.1, burst=2.0, max_tenants=2,
                           clock=lambda: t[0])
        active = tb.bucket("active")
        assert active.try_acquire()  # partially drained: NOT evictable
        b = tb.bucket("idle")  # full: evictable
        assert b.is_idle_full()
        t[0] = 1.0
        tb.bucket("new")  # sweep evicts only "idle"
        assert tb.bucket("active") is active

    def test_nonpositive_max_tenants_clamped_to_one(self):
        """A bad max_tenants (< 1) must not silently collapse every
        tenant into the overflow bucket."""
        from dstack_tpu.qos import TenantBuckets

        tb = TenantBuckets(rate=1.0, burst=1.0, max_tenants=-1,
                           clock=lambda: 0.0)
        assert tb.max_tenants == 1
        assert tb.bucket("a").try_acquire()


class TestPriorityPending:
    def test_interactive_pops_ahead_of_batch_fifo_within_class(self):
        q = PriorityPending()

        async def drive():
            q.push("b1", qos.PRIORITY_BATCH)
            q.push("s1", qos.PRIORITY_STANDARD)
            q.push("i1", qos.PRIORITY_INTERACTIVE)
            q.push("i2", qos.PRIORITY_INTERACTIVE)
            order = []
            while q.qsize():
                order.append(q.pop_admissible(lambda r: True))
            return order

        assert asyncio.run(drive()) == ["i1", "i2", "s1", "b1"]

    def test_skipped_items_keep_position_and_discard_drops(self):
        q = PriorityPending()

        async def drive():
            q.push("capped", qos.PRIORITY_INTERACTIVE)
            q.push("dead", qos.PRIORITY_INTERACTIVE)
            q.push("ok", qos.PRIORITY_BATCH)
            got = q.pop_admissible(
                lambda r: r != "capped", discard=lambda r: r == "dead"
            )
            assert got == "ok"
            # the capped item is still queued, first in line
            assert q.pop_admissible(lambda r: True) == "capped"
            return q.qsize()

        assert asyncio.run(drive()) == 0

    def test_pop_admissible_many_charges_within_one_walk(self):
        """The slot-batch pop: an accepting predicate charges its
        budget, so one tenant cannot take every slot of the batch even
        though all its entries arrived first; skipped entries keep
        their heap position for the next tick."""
        q = PriorityPending()

        async def drive():
            for i in range(4):
                q.push(("abuser", i), qos.PRIORITY_INTERACTIVE)
            q.push(("victim", 0), qos.PRIORITY_INTERACTIVE)
            held = {}

            def cap_1(item):
                t = item[0]
                if held.get(t, 0) >= 1:
                    return False
                held[t] = held.get(t, 0) + 1
                return True

            got = q.pop_admissible_many(3, cap_1)
            # one per tenant despite 3 free slots and abuser's 4 entries
            assert got == [("abuser", 0), ("victim", 0)]
            # the skipped abuser backlog is intact and in order
            rest = q.pop_admissible_many(10, lambda r: True)
            return rest

        assert asyncio.run(drive()) == [
            ("abuser", 1), ("abuser", 2), ("abuser", 3)
        ]

    def test_any_admissible_sees_through_a_capped_flood(self):
        """The adaptive-turbo hint source: a cap-blocked backlog is not
        arrival pressure; an admissible victim behind it is."""
        q = PriorityPending()

        async def drive():
            for i in range(50):
                q.push(("abuser", i), qos.PRIORITY_INTERACTIVE)
            blocked = lambda r: r[0] != "abuser"  # noqa: E731
            assert not q.any_admissible(blocked)
            q.push(("victim", 0), qos.PRIORITY_BATCH)
            assert q.any_admissible(blocked)
            assert not q.any_admissible(
                blocked, discard=lambda r: r[0] == "victim"
            )
            return q.qsize()  # scan never mutates the queue

        assert asyncio.run(drive()) == 51


def _make_client(qos_policy=None, max_batch=4):
    from aiohttp.test_utils import TestClient, TestServer

    config = llama.LLAMA_TINY
    params = init_params(config, 0)
    engine = InferenceEngine(config, params, max_batch=max_batch, max_seq=128)
    app = build_app(
        engine, ByteTokenizer(), "llama-tiny", qos_policy=qos_policy
    )
    return TestClient(TestServer(app))


class TestForcedShed:
    async def test_serve_admit_fault_forces_429_with_retry_after(
        self, fault_plan
    ):
        """A chaos plan drives the shed path deterministically — no
        bucket configuration required — and the injected Retry-After
        value surfaces on the response."""
        client = _make_client()
        await client.start_server()
        try:
            fault_plan({"rules": [
                {"point": "serve.admit", "action": "raise",
                 "error": "http:429", "retry_after": 7, "nth": 1},
            ]})
            r = await client.post(
                "/v1/completions",
                json={"model": "llama-tiny", "prompt": "ab", "max_tokens": 2},
            )
            assert r.status == 429
            assert r.headers.get("Retry-After") == "7"
            faults.clear()
            r = await client.post(
                "/v1/completions",
                json={"model": "llama-tiny", "prompt": "ab", "max_tokens": 2},
            )
            assert r.status == 200
        finally:
            await client.close()

    async def test_routing_admit_fault_forces_shed_at_proxy_edge(
        self, fault_plan
    ):
        """The shared edge helper (proxy/gateway planes) sheds on a
        forced routing.admit fault, counting it per tenant."""
        from dstack_tpu.qos.metrics import get_qos_registry

        fault_plan({"rules": [
            {"point": "routing.admit", "action": "raise",
             "error": "http:429", "retry_after": 3,
             "ctx": {"tenant": "mallory"}},
        ]})
        before = get_qos_registry().family("dtpu_qos_shed_total").value("mallory")
        hint = qos.edge_admit(
            QoSPolicy(), None, "mallory", project="p", run_name="svc"
        )
        assert hint == 3
        # a different tenant is untouched by the ctx-matched rule
        assert qos.edge_admit(QoSPolicy(), None, "alice") is None
        after = get_qos_registry().family("dtpu_qos_shed_total").value("mallory")
        assert after == before + 1
        snap = qos.run_edge_snapshot("p", "svc")
        assert snap is not None and snap["shed"] >= 1


class TestFloodIsolation:
    """The tentpole invariant: an abusive tenant flooding at ~10× its
    budget must not move a victim tenant's TTFT p95 beyond tolerance,
    and must see 429 + monotone Retry-After, never a 5xx."""

    # the serve edge only trusts the proxy-asserted X-DTPU-Tenant
    # (tenant_from_headers(trust_header=True) never digests the raw —
    # unvalidated — Authorization header, which reaches replicas
    # verbatim on the nginx custom-domain path); these headers model
    # what the proxy/gateway injects after authenticating each client
    VICTIM = {
        "Authorization": "Bearer victim-token",
        qos.TENANT_HEADER: "victim",
    }
    ABUSER = {
        "Authorization": "Bearer abuser-token",
        qos.TENANT_HEADER: "abuser",
    }

    ABUSE_BODY = {
        "model": "llama-tiny",
        "prompt": "flood " * 8,
        "max_tokens": 8,
    }

    async def _victim_ttft(self, client, n=8):
        """Client-observed TTFT (queue wait + prefill) over n paced
        sequential requests (a well-behaved interactive user stays
        inside its own budget) → sorted list of seconds."""
        ttfts = []
        for i in range(n):
            await asyncio.sleep(0.12)
            t0 = time.perf_counter()
            async with client.post(
                "/v1/completions",
                json={
                    "model": "llama-tiny",
                    # vary the prompt so prefix caching can't short-cut
                    # loaded runs relative to the baseline
                    "prompt": f"measure {i} " + "x" * 16,
                    "max_tokens": 2,
                },
                headers={
                    **self.VICTIM,
                    qos.PRIORITY_HEADER: "interactive",
                },
            ) as r:
                assert r.status == 200, await r.text()
                await r.read()
            ttfts.append(time.perf_counter() - t0)
        return sorted(ttfts)

    async def test_flood_does_not_move_victim_ttft(self):
        # budget generous enough for the paced victim (~6 rps), an
        # order of magnitude under the flood's attempt rate — and small
        # enough that ADMITTED abuse (≤ rps × max_tokens tok/s) cannot
        # saturate the engine: QoS isolates what it rate-limits
        policy = QoSPolicy(rps=6.0, burst=8.0, tenant_inflight=2)
        client = _make_client(qos_policy=policy, max_batch=4)
        await client.start_server()
        try:
            # warm EVERY shape both phases will hit — including the
            # CONCURRENT composition (victim prefill while abuse slots
            # decode): the first mixed-batch tick otherwise pays an XLA
            # compile / compile-cache load inside a measured window,
            # which reads as a fake TTFT regression
            async def _one_abuse():
                async with client.post(
                    "/v1/completions", json=self.ABUSE_BODY, headers=self.ABUSER
                ) as r:
                    await r.read()
                    return r.status

            warm_abuse = [asyncio.create_task(_one_abuse()) for _ in range(2)]
            await self._victim_ttft(client, n=2)
            assert all(s == 200 for s in await asyncio.gather(*warm_abuse))

            async def _measure_under_flood():
                """One (baseline, flood) measurement round. The abuser
                invariants — 429 + Retry-After, never 5xx, no wedged
                slots afterwards — are asserted unconditionally; only
                the victim-latency comparison is returned for the
                caller's tolerance/retry policy."""
                baseline = await self._victim_ttft(client)
                p95_base = baseline[int(0.95 * (len(baseline) - 1))]

                # abusive tenant: a sustained concurrent flood at ~10×
                # the bucket budget, long generations to hog slots if
                # admitted
                stop = asyncio.Event()
                abuse_results = []

                async def abuse():
                    while not stop.is_set():
                        try:
                            async with client.post(
                                "/v1/completions",
                                json=self.ABUSE_BODY,
                                headers={
                                    **self.ABUSER,
                                    qos.PRIORITY_HEADER: "batch",
                                },
                            ) as r:
                                abuse_results.append(
                                    (r.status, r.headers.get("Retry-After"))
                                )
                                await r.read()
                        except Exception as e:  # noqa: BLE001 - recorded
                            abuse_results.append(("error", repr(e)))
                        await asyncio.sleep(0.01)

                flooders = [asyncio.create_task(abuse()) for _ in range(6)]
                try:
                    await asyncio.sleep(0.3)  # flood reaches steady state
                    loaded = await self._victim_ttft(client)
                finally:
                    stop.set()
                    await asyncio.gather(*flooders, return_exceptions=True)
                p95_loaded = loaded[int(0.95 * (len(loaded) - 1))]

                # abuser: plenty of sheds, all 429 + Retry-After, no 5xx
                statuses = [s for s, _ in abuse_results]
                assert statuses, "flood never issued a request"
                assert all(s in (200, 429) for s in statuses), statuses
                sheds = [(s, ra) for s, ra in abuse_results if s == 429]
                assert len(sheds) >= len(statuses) // 2, (
                    f"flood was barely shed: {len(sheds)}/{len(statuses)}"
                )
                for _, ra in sheds:
                    assert ra is not None and int(ra) >= 1

                # server is healthy after the storm: no wedged slots
                h = None
                for _ in range(50):
                    r = await client.get("/health")
                    h = await r.json()
                    if h["inflight"] == 0:
                        break
                    await asyncio.sleep(0.1)
                assert h is not None and h["inflight"] == 0
                return p95_base, p95_loaded

            # victim: every request served; p95 within 20% + an
            # absolute floor for CPU scheduler/timer jitter at
            # tiny-model latencies. The measurement is a latency SLO
            # sampled on shared CI hardware — one background hiccup can
            # blow a single window — so the bound may be retried;
            # genuine starvation (an abuser holding every slot) fails
            # every round, since it is engine state, not noise.
            rounds = []
            for _ in range(3):
                p95_base, p95_loaded = await _measure_under_flood()
                rounds.append((p95_base, p95_loaded))
                if p95_loaded <= p95_base * 1.2 + 0.2:
                    break
            else:
                raise AssertionError(
                    "victim TTFT p95 moved under flood in every round: "
                    + ", ".join(
                        f"{b:.3f}s -> {z:.3f}s" for b, z in rounds
                    )
                )
        finally:
            await client.close()

    async def test_monotone_retry_after_within_burst(self):
        """Back-to-back sheds (no admits in between) report
        non-increasing Retry-After hints that shrink as the refill
        progresses — a client obeying the header lands on a token."""
        # refill so slow (1 token / 10s) that the first request's XLA
        # compile time cannot sneak a token back into the bucket
        policy = QoSPolicy(rps=0.1, burst=2.0)
        client = _make_client(qos_policy=policy, max_batch=2)
        await client.start_server()
        try:
            for _ in range(2):  # drain the burst (first pays compiles)
                r = await client.post(
                    "/v1/completions",
                    json={"model": "llama-tiny", "prompt": "a", "max_tokens": 1},
                    headers=self.ABUSER,
                )
                assert r.status == 200
            hints = []
            for i in range(3):
                if i:
                    await asyncio.sleep(1.0)  # refill progresses
                r = await client.post(
                    "/v1/completions",
                    json={"model": "llama-tiny", "prompt": "a", "max_tokens": 1},
                    headers=self.ABUSER,
                )
                assert r.status == 429
                hints.append(int(r.headers["Retry-After"]))
            assert hints == sorted(hints, reverse=True), hints
            assert hints[-1] < hints[0], hints  # strictly shrinking
        finally:
            await client.close()

    async def test_n_choices_spend_n_tokens_not_one(self):
        """``n`` is a fan-out of n engine generations: it must cost n
        bucket tokens (one token buying n=8 generations would hand an
        abusive tenant 8× a compliant tenant's decode budget), a
        fan-out shed must refund the pre-parse token (sheds are free
        of charge — retrying on the hint must not drain the budget),
        and an n that can never fit the burst is a 400, not a 429
        whose Retry-After could never be obeyed."""
        # refill ~0: the budget is exactly the burst for this test
        policy = QoSPolicy(rps=0.001, burst=4.0)
        client = _make_client(qos_policy=policy, max_batch=4)
        await client.start_server()
        try:
            # n=2 costs 2 of the burst-4 budget (1 pre-parse + 1 extra)
            r = await client.post(
                "/v1/completions",
                json={"model": "llama-tiny", "prompt": "a",
                      "max_tokens": 1, "n": 2},
                headers=self.ABUSER,
            )
            assert r.status == 200, await r.text()
            assert len((await r.json())["choices"]) == 2
            # n=4 needs 4 > the 2 left: shed at the fan-out charge
            r = await client.post(
                "/v1/completions",
                json={"model": "llama-tiny", "prompt": "a",
                      "max_tokens": 1, "n": 4},
                headers=self.ABUSER,
            )
            assert r.status == 429
            assert int(r.headers["Retry-After"]) >= 1
            # the shed refunded its pre-parse token: the 2 remaining
            # tokens still buy an n=2 — without the refund only 1
            # would be left and this would shed too
            r = await client.post(
                "/v1/completions",
                json={"model": "llama-tiny", "prompt": "a",
                      "max_tokens": 1, "n": 2},
                headers=self.ABUSER,
            )
            assert r.status == 200, await r.text()
            # budget now truly spent: a single request sheds pre-parse
            r = await client.post(
                "/v1/completions",
                json={"model": "llama-tiny", "prompt": "a", "max_tokens": 1},
                headers=self.ABUSER,
            )
            assert r.status == 429
            assert int(r.headers["Retry-After"]) >= 1
            # n=8 > burst 4 can NEVER be admitted under this policy —
            # an honest 400 (no unfulfillable Retry-After promise)...
            r = await client.post(
                "/v1/completions",
                json={"model": "llama-tiny", "prompt": "a",
                      "max_tokens": 1, "n": 8},
                headers=self.VICTIM,
            )
            assert r.status == 400
            assert "burst" in (await r.json())["detail"]
            # ...and it charged the victim nothing: the full burst
            # still buys n=4
            r = await client.post(
                "/v1/completions",
                json={"model": "llama-tiny", "prompt": "a",
                      "max_tokens": 1, "n": 4},
                headers=self.VICTIM,
            )
            assert r.status == 200, await r.text()
        finally:
            await client.close()
