"""The serve loop's host phases: always-on histograms timed by their
callers (``dtpu_serve_host_gap_seconds``, ``_tick_host_``,
``_detokenize_``, ``_stream_write_``; an engine call split into
``_step_enqueue_`` / ``_step_wait_`` / ``_step_finish_`` and
``_prefill_host_``; the gap between two calls into ``_loop_return_`` /
``_loop_yield_`` / ``_worker_start_``; ``_first_delta_lag_``) and,
while a profiler capture runs, the same intervals as ``dtpu.*`` spans
on its ``/host:CPU`` plane. Since PR 41 the scheduler does not yield
between a hand-over and the next engine call: ``_loop_yield_`` reads
about zero and there is no ``dtpu.loop.yield`` span
(``test_stream_overlap.py`` has the overlap itself)."""

import asyncio
import glob
import os
import statistics
import time

import pytest

from dstack_tpu.obs import flight
from dstack_tpu.serve.metrics import new_serve_registry
from tests.serve.test_openai_server import _client as _server_client

PHASES = (
    "host_gap", "tick_host", "detokenize", "stream_write",
    "step_enqueue", "step_wait", "step_finish", "prefill_host",
    "loop_return", "loop_yield", "worker_start", "first_delta_lag",
)
STEP_PARTS = ("step_enqueue", "step_wait", "step_finish")
# with tick_host, what dtpu_serve_host_gap_seconds is made of
GAP_PARTS = ("loop_return", "tick_host", "loop_yield", "worker_start")


async def _client():
    client = await _server_client()
    return client, client.app["scheduler"].engine


async def _stream(client, prompt: str, max_tokens: int) -> int:
    """One streamed chat completion → SSE chunks received."""
    r = await client.post("/v1/chat/completions", json={
        "model": "llama-tiny", "stream": True, "max_tokens": max_tokens,
        "messages": [{"role": "user", "content": prompt}],
        # keep to ASCII ids so every token is a visible delta
        "logit_bias": {str(i): -100 for i in range(128, 512)},
    })
    assert r.status == 200
    chunks = 0
    async for line in r.content:
        if line.startswith(b"data: {"):
            chunks += 1
    return chunks


def _hist(engine, phase):
    return engine.metrics.family(f"dtpu_serve_{phase}_seconds")


def _sums(engine, phases=PHASES) -> dict:
    return {p: _hist(engine, p).sum() for p in phases}


async def _two_streams() -> dict:
    """Two concurrent streams on a fresh server → every reading the
    cases below judge. ``engine.step`` is wrapped ON THE WORKER THREAD:
    at its entry the gap before the call and all its parts have been
    observed, so the sums read there are consistent with each other."""
    client, engine = await _client()
    got = {"steps": [], "at_entry": []}
    step = engine.step

    def timed_step():
        got["at_entry"].append(_sums(engine, ("host_gap",) + GAP_PARTS))
        before = sum(_sums(engine, STEP_PARTS).values())
        t0 = time.perf_counter()
        out = step()
        wall = time.perf_counter() - t0
        if out:
            got["steps"].append(
                (wall, sum(_sums(engine, STEP_PARTS).values()) - before)
            )
        return out

    engine.step = timed_step
    try:
        await asyncio.sleep(0.2)  # parked with no request
        got["parked"] = {p: _hist(engine, p).count() for p in PHASES}
        t0 = time.perf_counter()
        got["chunks"] = await asyncio.gather(
            _stream(client, "abc", 24), _stream(client, "wxyz", 24)
        )
        got["wall"] = time.perf_counter() - t0
        await asyncio.sleep(0.05)  # the handlers' last call_soon
        got["count"] = {p: _hist(engine, p).count() for p in PHASES}
        got["sum"] = _sums(engine)
        got["tokens"] = engine.metrics.family(
            "dtpu_serve_tokens_generated_total"
        ).value()
    finally:
        await client.close()
    return got


@pytest.fixture(scope="module")
def two_streams():
    return asyncio.run(_two_streams())


class TestHostPhaseHistograms:
    @pytest.mark.parametrize("phase", PHASES)
    def test_zero_while_parked_and_counted_after_two_streams(
        self, two_streams, phase
    ):
        got = two_streams
        assert min(got["chunks"]) >= 2
        assert got["parked"][phase] == 0
        assert got["count"][phase] > 0
        assert got["sum"][phase] > 0.0
        if phase != "first_delta_lag":  # that one overlaps: a request each
            assert got["sum"][phase] <= got["wall"]

    def test_a_token_is_detokenized_once_and_a_chunk_written_once(
        self, two_streams
    ):
        got = two_streams
        # every delivered token was detokenized once (+1 final flush a
        # stream), every chunk written once
        assert got["count"]["detokenize"] >= got["tokens"]
        assert got["count"]["stream_write"] >= sum(got["chunks"]) - 2

    def test_enqueue_wait_and_finish_add_up_to_the_steps_wall_time(
        self, two_streams
    ):
        steps = two_streams["steps"]
        assert len(steps) >= 20
        # the three share their clock reads: nothing of a call is
        # counted twice ...
        assert all(parts <= wall for wall, parts in steps)
        # ... and nothing but the outer call and the three observes is
        # left out: to 1 % (in the median call: a worker preempted
        # between the outer reads only reads longer)
        short = statistics.median((wall - parts) / wall for wall, parts in steps)
        assert short <= 0.01, steps
        assert two_streams["count"]["step_wait"] == len(steps)

    def test_the_named_parts_of_the_gap_add_up_to_most_of_it(
        self, two_streams
    ):
        """Between the entries of two engine calls of one busy stretch
        Σhost_gap = Σloop_return + Σtick_host + Σloop_yield +
        Σworker_start + a few lines of the scheduler's loop."""
        at = two_streams["at_entry"]
        first, last = at[2], at[-1]
        gap = last["host_gap"] - first["host_gap"]
        named = sum(last[p] - first[p] for p in GAP_PARTS)
        assert gap > 0
        # a tick's admission is observed with its tick, one call later
        # than the gap it lies in: the first and the last of them (µs)
        # stand on the wrong side of the two readings
        assert 0.7 * gap <= named <= 1.01 * gap, (named, gap)

    def test_first_delta_lag_counts_a_request_a_step_never_a_token(
        self, two_streams
    ):
        got = two_streams
        # at most one observation a live request a hand-over (an
        # emitting step's, a finished prefill's); the macro-step case
        # below is where a count a token would read higher
        calls = len(got["steps"]) + got["count"]["prefill_host"]
        assert 2 <= got["count"]["first_delta_lag"] <= 2 * calls

    async def test_gap_counts_only_while_requests_hold_slots(self):
        client, engine = await _client()
        try:
            gap = _hist(engine, "host_gap")
            await _stream(client, "abc", 12)
            # parked again: the wait for the next request is not a gap,
            # nor are the parts noted before the park
            await asyncio.sleep(0.05)
            parked = {p: _hist(engine, p).count() for p in PHASES}
            await asyncio.sleep(0.3)
            assert {p: _hist(engine, p).count() for p in PHASES} == parked
            # ... and neither is the first engine call after it: every
            # busy stretch has one engine call more than it has gaps
            await _stream(client, "k", 2)
            calls = sum(
                engine.metrics.family(f"dtpu_serve_{c}_total").value()
                for c in ("prefill_dispatches", "decode_steps")
            )
            assert parked["host_gap"] < gap.count() <= calls - 2
            for p in ("loop_return", "worker_start"):
                assert _hist(engine, p).count() == gap.count(), p
            assert _hist(engine, "loop_yield").count() <= gap.count()
        finally:
            await client.close()

    async def test_a_macro_step_hands_over_once_a_request(self):
        """Greedy streams take the macro-step (several tokens a request
        a call): the lag counts the hand-overs, not the tokens."""
        client, engine = await _client()
        try:
            r = await client.post("/v1/chat/completions", json={
                "model": "llama-tiny", "stream": True, "max_tokens": 24,
                "temperature": 0, "messages": [{"role": "user", "content": "abc"}],
            })
            assert r.status == 200
            async for _ in r.content:
                pass
            await asyncio.sleep(0.05)
            steps = engine.metrics.family("dtpu_serve_decode_steps_total").value()
            tokens = engine.metrics.family(
                "dtpu_serve_tokens_generated_total"
            ).value()
            assert tokens == 24 and steps < tokens  # macro-steps ran
            # one live request: at most one observation a hand-over
            # (the prefill's and each step's)
            assert _hist(engine, "first_delta_lag").count() <= steps + 1
            for p in STEP_PARTS:
                assert _hist(engine, p).count() == steps, p
        finally:
            await client.close()


CAPTURE_SPANS = (
    "dtpu.engine.step", "dtpu.engine.prefill", "dtpu.tick.host",
    "dtpu.stream.detokenize", "dtpu.stream.write",
    "dtpu.engine.wait", "dtpu.engine.finish",
)


async def _capture() -> None:
    client, _ = await _client()
    try:
        await _stream(client, "warm", 4)  # compile outside the capture
        r = await client.post("/debug/profiler/start")
        assert r.status == 200
        try:
            await _stream(client, "abc", 6)
        finally:
            r = await client.post("/debug/profiler/stop")
        assert r.status == 200
    finally:
        await client.close()


@pytest.fixture(scope="module")
def capture(tmp_path_factory):
    """One capture of one stream → ``{span name: [(start, end, stats)]}``
    of the ``dtpu.*`` events on ``/host:CPU``, and the flight ring."""
    from jax.profiler import ProfileData

    from dstack_tpu.obs import profiling

    trace_dir = str(tmp_path_factory.mktemp("traces"))
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("DTPU_PROFILER_DIR", trace_dir)
        assert not profiling.is_tracing()
        asyncio.run(_capture())
    files = glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    )
    assert files
    host = [
        p for p in ProfileData.from_file(files[-1]).planes
        if p.name == "/host:CPU"
    ]
    assert host
    spans: dict = {}
    for i, line in enumerate(host[0].lines):  # a line a thread
        for e in line.events:
            if e.name.startswith("dtpu."):
                spans.setdefault(e.name, []).append((
                    e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats, line=i),
                ))
    ring = {r["seq"]: r for r in flight.get_recorder().records(512)}
    return spans, ring


class TestSpansInACapture:
    @pytest.mark.parametrize("name", CAPTURE_SPANS)
    def test_span_is_on_the_host_plane(self, capture, name):
        assert capture[0].get(name), sorted(capture[0])

    def test_the_step_span_names_its_flight_record(self, capture):
        spans, ring = capture
        for _, _, s in spans["dtpu.engine.step"]:
            # the step span names the flight record that is its own
            rec = ring[int(s["seq"])]
            assert rec["phase"] == s["phase"]
            assert s["phase"] in ("decode", "turbo", "spec")
            # ... which shows the same split a step
            assert 0.0 <= rec["wait_s"] <= rec["dispatch_s"]

    def test_every_wait_lies_inside_an_engine_call(self, capture):
        spans, _ = capture
        calls = spans["dtpu.engine.step"] + spans["dtpu.engine.prefill"]
        for t0, t1, _ in spans["dtpu.engine.wait"]:
            assert any(c0 <= t0 and t1 <= c1 for c0, c1, _ in calls), (t0, t1)
        # a step's first finish span (the path's bookkeeping) too; its
        # second (step()'s counters and flight record) follows the span
        steps = spans["dtpu.engine.step"]
        inside = [
            f for f in spans["dtpu.engine.finish"]
            if any(c0 <= f[0] and f[1] <= c1 for c0, c1, _ in steps)
        ]
        assert len(inside) == len(steps)
        assert len(spans["dtpu.engine.finish"]) == 2 * len(steps)

    def test_the_scheduler_yields_nowhere_but_in_an_engine_call(self, capture):
        assert "dtpu.loop.yield" not in capture[0]

    def test_the_handlers_spans_lie_on_another_line_than_the_engines(self, capture):
        """The handlers' turn is the next call's await: a delta is
        written on the loop thread's line while the call is out on a
        worker thread's (``test_stream_overlap.py`` parks a call to
        show the two at once; here the threads race for the GIL)."""
        spans, _ = capture

        def lines(*names):
            return {s["line"] for n in names for _, _, s in spans[n]}

        loop = lines("dtpu.stream.write", "dtpu.stream.detokenize", "dtpu.tick.host")
        assert len(loop) == 1
        assert not loop & lines(
            "dtpu.engine.step", "dtpu.engine.prefill", "dtpu.engine.wait"
        )


@pytest.mark.parametrize("live, tokens", [(4, 1), (16, 8)])
def test_what_the_added_lines_cost_a_call(capsys, live, tokens):
    """The lines this accounting adds to one decode cycle, without an
    engine or a server: the clock reads, the no-op spans, the noted
    parts and the observes of one ``engine.step`` call (two fetches),
    one ``Scheduler._engine_call`` + hand-over + the next tick's first
    lines, and ``live`` handlers of ``tokens`` tokens each (a plain step of a thin batch, a
    macro-step of a full one). Prints µs a cycle (``CHANGES.md`` quotes
    it); asserts no time."""
    from dstack_tpu.obs import profiling

    family = new_serve_registry().family
    clock = time.perf_counter

    class Req:
        handed_at = None

    class Sched:
        calls_in_flight = 0
        handed_over_at = None

    sched = Sched()
    reqs = [Req() for _ in range(live)]
    m_lag = family("dtpu_serve_first_delta_lag_seconds")
    m_tokens = family("dtpu_serve_stream_tokens_total")
    m_overlapped = family("dtpu_serve_stream_tokens_overlapped_total")

    def cycle():
        # engine.step: reset, two fetches, two finish spans, three observes
        t_all0 = clock()
        wait_s = 0.0
        for _ in range(2):
            t0 = clock()
            with profiling.span("dtpu.engine.wait"):
                pass
            fetched_at = clock()
            wait_s += fetched_at - t0
        with profiling.span("dtpu.engine.finish"):
            pass
        with profiling.span("dtpu.engine.finish"):
            round(wait_s, 6)
        t1 = clock()
        finish = t1 - fetched_at
        family("dtpu_serve_step_enqueue_seconds").observe(
            t1 - t_all0 - wait_s - finish
        )
        family("dtpu_serve_step_wait_seconds").observe(wait_s)
        family("dtpu_serve_step_finish_seconds").observe(finish)
        # Scheduler._engine_call, both sides of the hop
        parts = [("dtpu_serve_loop_yield_seconds", 0.0)]
        for name, seconds in parts:
            family(name).observe(seconds)
        parts.clear()
        t_hop = clock()
        sched.calls_in_flight += 1
        family("dtpu_serve_worker_start_seconds").observe(clock() - t_hop)
        sched.calls_in_flight -= 1
        parts.append(("dtpu_serve_loop_return_seconds", clock() - t_hop))
        family(parts[0][0]).observe(parts[0][1])
        # _hand_over: one read, a test a token; its return's stamp and
        # the next tick's reading of it
        now = clock()
        for r in reqs:
            for _ in range(tokens):
                if r.handed_at is None:
                    r.handed_at = now
        sched.handed_over_at = clock()
        if sched.handed_over_at is not None:
            parts.append(
                ("dtpu_serve_loop_yield_seconds", clock() - sched.handed_over_at)
            )
            sched.handed_over_at = None
        # the handlers: two counts and a test a token, one note and one
        # observe a request, two incs a park
        lag_s = []
        for r in reqs:
            taken = [0, 0]
            for _ in range(tokens):
                taken[0] += 1
                taken[1] += sched.calls_in_flight > 0
                if r.handed_at is not None:
                    lag_s.append(clock() - r.handed_at)
                    r.handed_at = None
            m_tokens.inc(taken[0])
            m_overlapped.inc(taken[1])
        for v in lag_s:
            m_lag.observe(v)

    n = 2000
    cycle()
    t0 = clock()
    for _ in range(n):
        cycle()
    per = (clock() - t0) / n * 1e6
    with capsys.disabled():
        print(f"\nadded host-phase lines: {per:.1f} us a decode cycle "
              f"({live} requests x {tokens} tokens; CPU of this sandbox)")
    assert family("dtpu_serve_step_wait_seconds").count() == n + 1
