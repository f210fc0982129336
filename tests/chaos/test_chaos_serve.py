"""Serve-plane invariant: an engine-step fault fails only the inflight
request(s); the scheduler loop survives and the server keeps serving.
"""

from dstack_tpu import faults
from dstack_tpu.models import llama
from dstack_tpu.serve.engine import InferenceEngine
from dstack_tpu.serve.openai_server import build_app
from dstack_tpu.serve.tokenizer import ByteTokenizer
from tests.shared import init_params


async def _client():
    from aiohttp.test_utils import TestClient, TestServer

    config = llama.LLAMA_TINY
    params = init_params(config, 0)
    engine = InferenceEngine(config, params, max_batch=4, max_seq=128)
    app = build_app(engine, ByteTokenizer(), "llama-tiny")
    client = TestClient(TestServer(app))
    await client.start_server()
    return client


class TestEngineStepFault:
    async def test_step_fault_fails_inflight_only_server_survives(
        self, fault_plan
    ):
        """One injected engine-step crash: the inflight request answers
        500 (not a hang, not a dead server); the NEXT request decodes
        normally on the same engine."""
        client = await _client()
        try:
            # warm request before the fault proves the path works
            r = await client.post(
                "/v1/completions",
                json={"model": "llama-tiny", "prompt": "ab", "max_tokens": 3},
            )
            assert r.status == 200
            fault_plan({"rules": [
                {"point": "serve.engine.step", "action": "raise", "nth": 1},
            ]})
            r = await client.post(
                "/v1/completions",
                json={"model": "llama-tiny", "prompt": "ab", "max_tokens": 3},
            )
            assert r.status == 500
            detail = (await r.json())["detail"]
            assert "injected fault" in detail
            # fault budget spent (nth=1): the engine must still serve
            faults.clear()
            r = await client.post(
                "/v1/completions",
                json={"model": "llama-tiny", "prompt": "ab", "max_tokens": 3},
            )
            assert r.status == 200
            d = await r.json()
            assert d["usage"]["completion_tokens"] >= 1
            # and /health still answers with a clean engine
            r = await client.get("/health")
            assert r.status == 200
            h = await r.json()
            assert h["inflight"] == 0
        finally:
            await client.close()


async def _client_with(watchdog_seconds=0.0, qos_policy=None, max_batch=4):
    from aiohttp.test_utils import TestClient, TestServer

    config = llama.LLAMA_TINY
    params = init_params(config, 0)
    engine = InferenceEngine(config, params, max_batch=max_batch, max_seq=128)
    if watchdog_seconds:
        # as `openai_server.main` does before it serves: a watchdog of
        # 0.3 s armed over a cold engine times the sandbox's compiler
        # (trace + compile of one tiny step is 0.3-0.4 s here, more
        # under xdist load), not a wedged slot
        from dstack_tpu.serve.openai_server import _warmup_engine

        _warmup_engine(engine)
    app = build_app(
        engine, ByteTokenizer(), "llama-tiny",
        qos_policy=qos_policy, watchdog_seconds=watchdog_seconds,
    )
    client = TestClient(TestServer(app))
    await client.start_server()
    return client, engine


class TestEngineWatchdog:
    async def test_wedged_slot_aborted_others_complete(self, fault_plan):
        """Acceptance: an injected serve.engine.step hang on ONE slot →
        the watchdog aborts only that slot within its budget; the other
        in-flight request completes normally and the server keeps
        serving afterwards."""
        import asyncio

        client, engine = await _client_with(watchdog_seconds=0.3)
        watchdog = engine.metrics.family("dtpu_serve_watchdog_aborts_total")
        try:
            # hang slot 0's per-slot fire for 1s (> watchdog, short
            # enough to drain before the event loop closes)
            fault_plan({"rules": [
                {"point": "serve.engine.step", "ctx": {"slot": 0},
                 "action": "hang", "seconds": 1.0, "times": 1},
            ]})

            async def one(prompt):
                r = await client.post(
                    "/v1/completions",
                    json={"model": "llama-tiny", "prompt": prompt,
                          "max_tokens": 12},
                )
                return r.status, await r.json()

            # two concurrent requests: admission order gives the first
            # slot 0 (the hang target), the second slot 1
            (s1, d1), (s2, d2) = await asyncio.gather(
                one("abcd"), one("wxyz")
            )
            statuses = sorted([s1, s2])
            assert statuses == [200, 500], (d1, d2)
            failed = d1 if s1 == 500 else d2
            ok = d2 if s1 == 500 else d1
            assert "watchdog" in failed["detail"]
            # the survivor decoded its full budget, not a truncation
            assert ok["usage"]["completion_tokens"] >= 1
            assert watchdog.value() == 1
            # the wedged slot's KV is freed and the server keeps serving
            s, d = await one("again")
            assert s == 200
            r = await client.get("/health")
            h = await r.json()
            assert h["inflight"] == 0
            # let the abandoned (still-sleeping) step thread drain so
            # closing the event loop doesn't destroy a pending task
            await asyncio.sleep(1.0)
        finally:
            await client.close()


class TestRequestDeadlines:
    async def test_deadline_expired_slot_freed_and_unstarted_refund(
        self, fault_plan
    ):
        """Acceptance: a deadline-expired request frees its KV slot and
        refunds its un-started QoS token. The refund is asserted
        functionally: with a 1-token bucket, a follow-up request only
        admits if the aborted one gave its token back."""
        from dstack_tpu import qos as qos_mod

        client, engine = await _client_with(
            qos_policy=qos_mod.QoSPolicy(rps=0.001, burst=1.0),
        )
        expired = engine.metrics.family("dtpu_serve_deadline_expired_total")
        try:
            # huge injected clock skew: every armed deadline reads
            # expired at the first scheduler sweep — before any token
            fault_plan({"rules": [
                {"point": "serve.deadline", "action": "corrupt",
                 "value": 1e9},
            ]})
            r = await client.post(
                "/v1/completions",
                json={"model": "llama-tiny", "prompt": "abcd",
                      "max_tokens": 12},
                headers={qos_mod.DEADLINE_HEADER: "30"},
            )
            assert r.status == 504
            assert "deadline" in (await r.json())["detail"]
            assert expired.value() == 1
            faults.clear()
            # KV freed: nothing in flight, every slot back in the pool
            rh = await client.get("/health")
            h = await rh.json()
            assert h["inflight"] == 0 and h["active_slots"] == 0
            assert engine.free_slots() == list(range(engine.max_batch))
            # bucket state: burst 1, refill ~0 — this request only
            # admits because the aborted one refunded its token
            r = await client.post(
                "/v1/completions",
                json={"model": "llama-tiny", "prompt": "abcd",
                      "max_tokens": 2},
            )
            assert r.status == 200
        finally:
            await client.close()

    async def test_unarmed_requests_never_expire(self, fault_plan):
        """The skew fault only bites requests that ARMED a deadline:
        no header, no default → no expiry even under infinite skew."""
        client, engine = await _client_with()
        try:
            fault_plan({"rules": [
                {"point": "serve.deadline", "action": "corrupt",
                 "value": 1e9},
            ]})
            r = await client.post(
                "/v1/completions",
                json={"model": "llama-tiny", "prompt": "ab",
                      "max_tokens": 3},
            )
            assert r.status == 200
        finally:
            await client.close()


class TestPreFirstTokenRefund:
    async def test_disconnect_before_first_token_refunds(self):
        """Satellite: a client that disconnects after QoS admission but
        before its first token refunds its bucket token — asserted on
        the scheduler/bucket state machine directly (the timing window
        is too narrow to hit reliably over a real socket)."""
        from dstack_tpu import qos as qos_mod
        from dstack_tpu.serve.openai_server import Scheduler, _Request
        from dstack_tpu.serve.engine import GenParams
        from dstack_tpu.serve.tokenizer import ByteTokenizer

        config = llama.LLAMA_TINY
        params = init_params(config, 0)
        engine = InferenceEngine(config, params, max_batch=2, max_seq=64)
        sched = Scheduler(engine, ByteTokenizer())
        bucket = qos_mod.TokenBucket(rate=0.001, burst=2.0)
        assert bucket.try_acquire()  # the edge admission charge
        req = _Request([5, 6, 7], GenParams(max_new_tokens=4))
        req.bucket = bucket
        await sched.submit(req)
        sched.cancel(req)  # client gone before any scheduler tick
        assert req.refunded
        assert bucket.tokens == 2.0  # charge returned
        # a STARTED request keeps its charge
        assert bucket.try_acquire()
        req2 = _Request([5, 6, 7], GenParams(max_new_tokens=4))
        req2.bucket = bucket
        req2.started = True
        sched.cancel(req2)
        assert not req2.refunded
        assert bucket.tokens == 1.0


class TestWatchdogRaces:
    """The two watchdog/step races the review surfaced: a step that
    completes concurrently with the trip is harvested (not treated as
    a batch-wide wedge), and a dispatch-abandoned step quiesces the
    scheduler until its thread actually returns."""

    class _SlowEngine:
        """step() is slow-but-alive; wedge marker clears on return."""

        def __init__(self, step_seconds):
            import threading
            import time as _time

            from dstack_tpu.serve.metrics import new_serve_registry

            self.metrics = new_serve_registry()
            self._step_seconds = step_seconds
            self._step_wedge = ("dispatch",)
            self.released = []
            self.finished_abandoned = 0

        def step(self):
            import time as _time

            _time.sleep(self._step_seconds)
            self._step_wedge = None
            return {0: [42]}

        def abandon_step(self):
            phase = self._step_wedge
            self._step_wedge = None
            return phase

        def finish_abandoned_step(self):
            self.finished_abandoned += 1

        def release(self, slot):
            self.released.append(slot)

    async def test_phase_none_harvests_completed_step(self):
        """Watchdog trips while the step has ALREADY cleared its wedge
        marker (slow step, not a wedge): the result is harvested and
        no request is aborted."""
        import asyncio

        from dstack_tpu.serve.openai_server import Scheduler, _Request
        from dstack_tpu.serve.engine import GenParams
        from dstack_tpu.serve.tokenizer import ByteTokenizer

        engine = self._SlowEngine(step_seconds=0.3)
        engine._step_wedge = None  # marker already cleared at trip time
        sched = Scheduler(engine, ByteTokenizer(), watchdog_seconds=0.05)
        req = _Request([1], GenParams(max_new_tokens=2))
        sched.by_slot[0] = req
        out = await sched._guarded_step()
        assert out == {0: [42]}  # harvested, not discarded
        assert req.error is None and engine.released == []
        assert engine.metrics.family(
            "dtpu_serve_watchdog_aborts_total"
        ).value() == 0

    async def test_dispatch_wedge_quiesces_until_thread_returns(self):
        """A dispatch-phase wedge fails the batch AND parks the
        scheduler (no admission/dispatch) until the stuck thread
        returns; new arrivals fail fast with 503 meanwhile."""
        import asyncio

        from dstack_tpu.serve.openai_server import Scheduler, _Request
        from dstack_tpu.serve.engine import GenParams
        from dstack_tpu.serve.tokenizer import ByteTokenizer

        engine = self._SlowEngine(step_seconds=0.5)
        sched = Scheduler(engine, ByteTokenizer(), watchdog_seconds=0.05)
        req = _Request([1], GenParams(max_new_tokens=2))
        sched.by_slot[0] = req
        out = await sched._guarded_step()
        assert out is None
        assert "watchdog" in req.error
        assert engine.released == [0]
        assert sched._abandoned is not None and not sched._abandoned.done()
        # quiesced tick: a queued arrival fails fast instead of hanging
        late = _Request([2], GenParams(max_new_tokens=2))
        await sched.submit(late)
        await sched._tick()
        assert late.error_status == 503 and "wedged" in late.error
        assert sched._abandoned is not None
        # once the thread returns, the next tick reclaims the engine
        await asyncio.sleep(0.6)
        assert sched._abandoned.done()
        sched.pending.push(_Request([3], GenParams(max_new_tokens=2)), 1)
        try:
            await asyncio.wait_for(sched._tick(), timeout=2.0)
        except Exception:
            pass  # the fake engine lacks the full tick surface
        assert sched._abandoned is None
        assert engine.finished_abandoned == 1
