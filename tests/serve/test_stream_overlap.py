"""The stream handlers' turn lies inside the NEXT engine call's await:
``Scheduler._tick`` does not give the loop away between a token
hand-over and the call that follows it, so detokenize + SSE write run
while the device computes. Shown with an engine whose calls park on the
worker thread until the test lets them through: the deltas of call *n*
reach the client while call *n + 1* is in flight, a disconnect in that
turn frees its slot at the next tick's sweep, and what a client reads
is what it read before (one delta a token, in order)."""

import asyncio
import json
import threading
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from dstack_tpu.models import llama
from dstack_tpu.serve.engine import InferenceEngine
from dstack_tpu.serve.openai_server import build_app
from dstack_tpu.serve.tokenizer import ByteTokenizer
from tests.shared import init_params

GAP_PARTS = ("loop_return", "tick_host", "loop_yield", "worker_start")
# keep to ASCII ids: every token is a visible delta, and a stream takes
# the plain step (one token a call)
ASCII = {str(i): -100 for i in range(128, 512)}


async def _client(watchdog_seconds=0.0, max_batch=4):
    config = llama.LLAMA_TINY
    params = init_params(config, 0)
    engine = InferenceEngine(config, params, max_batch=max_batch, max_seq=128)
    app = build_app(
        engine, ByteTokenizer(), "llama-tiny", watchdog_seconds=watchdog_seconds
    )
    client = TestClient(TestServer(app))
    await client.start_server()
    return client, engine, app["scheduler"]


class Gate:
    """Parks every ``engine.step`` call ON THE WORKER THREAD, the engine
    call in flight, until the test lets it through. At a call's entry
    the gap before it and the gap's parts have been observed, so the
    sums read there are consistent with each other."""

    def __init__(self, engine):
        self.entered = 0
        self.at_entry = []
        self._permits = threading.Semaphore(0)
        self._open = False
        step = engine.step
        family = engine.metrics.family

        def gated():
            self.at_entry.append({
                p: family(f"dtpu_serve_{p}_seconds").sum()
                for p in ("host_gap",) + GAP_PARTS
            })
            self.entered += 1
            if not self._open:
                assert self._permits.acquire(timeout=30)
            return step()

        engine.step = gated

    def let_one_through(self):
        self._permits.release()

    def open(self):
        self._open = True
        self._permits.release()  # the one call that may be parked


async def _until(cond, what, seconds=10.0):
    t0 = time.monotonic()
    while not cond():
        assert time.monotonic() - t0 < seconds, what
        await asyncio.sleep(0.002)


async def _post(client, prompt, max_tokens, **extra):
    r = await client.post("/v1/chat/completions", json={
        "model": "llama-tiny", "stream": True, "max_tokens": max_tokens,
        "temperature": 0, "logit_bias": ASCII,
        "messages": [{"role": "user", "content": prompt}], **extra,
    })
    assert r.status == 200
    return r


async def _read(r, deltas: list) -> None:
    async for line in r.content:
        if line.startswith(b"data: {"):
            delta = json.loads(line[6:])["choices"][0]["delta"]
            if delta.get("content"):
                deltas.append(delta["content"])


def _counter(engine, name):
    return engine.metrics.family(f"dtpu_serve_stream_{name}_total").value()


@pytest.mark.parametrize("watchdog_seconds", [0.0, 20.0], ids=["watchdog_off", "watchdog_on"])
async def test_the_deltas_of_a_call_reach_the_client_while_the_next_is_in_flight(
    watchdog_seconds,
):
    client, engine, sched = await _client(watchdog_seconds)
    try:
        assert _counter(engine, "tokens") == 0  # exported from boot
        assert _counter(engine, "tokens_overlapped") == 0
        gate = Gate(engine)
        deltas: list = []
        r = await _post(client, "abc", 12)
        reader = asyncio.create_task(_read(r, deltas))
        for n in range(1, 9):
            # call n (a step) is in flight, parked on the worker ...
            await _until(lambda: gate.entered == n, f"step {n} never started")
            # ... and what was handed over before it (n = 1: the prefill
            # wave's first token; else step n - 1's) reaches the client
            await _until(lambda: len(deltas) == n, f"delta {n} never came")
            assert gate.entered == n and sched.calls_in_flight == 1
            gate.let_one_through()
        gate.open()
        await asyncio.wait_for(reader, 30)
        assert len(deltas) == 12  # one delta a token, as ever
        await asyncio.sleep(0.05)  # the handler's last observe_noted
        assert _counter(engine, "tokens") == 12
        assert _counter(engine, "tokens_overlapped") >= 8
        # nothing of the loop is given away between a hand-over and the
        # scheduler's next line: observed once a hand-over, and ~0
        yielded = engine.metrics.family("dtpu_serve_loop_yield_seconds")
        assert yielded.count() >= 8
        assert yielded.sum() / yielded.count() < 1e-3
        # ... and the gap's named parts still add up to it
        first, last = gate.at_entry[1], gate.at_entry[-1]
        gap = last["host_gap"] - first["host_gap"]
        named = sum(last[p] - first[p] for p in GAP_PARTS)
        assert gap > 0 and 0.7 * gap <= named <= 1.01 * gap, (named, gap)
        assert sched.calls_in_flight == 0
    finally:
        await client.close()


async def _two_streams(disconnect: bool) -> dict:
    """Streams A and B side by side, every step gated; with
    ``disconnect`` A's client goes away after its third delta, while a
    call is in flight."""
    client, engine, sched = await _client()
    got = {"cancelled_in_flight": []}
    try:
        gate = Gate(engine)
        cancel = sched.cancel

        def watched_cancel(req):
            got["cancelled_in_flight"].append(
                (sched.calls_in_flight, req.finish_reason)
            )
            cancel(req)

        sched.cancel = watched_cancel
        a, b = [], []
        ra = await _post(client, "abcd", 24)
        rb = await _post(client, "wxyz", 16)
        read_a = asyncio.create_task(_read(ra, a))
        read_b = asyncio.create_task(_read(rb, b))
        await _until(lambda: gate.entered >= 1, "no step started")
        while len(a) < 3 or len(b) < 3:
            gate.let_one_through()
            await asyncio.sleep(0.01)
        if disconnect:
            read_a.cancel()
            ra.close()  # the client of A is gone, a call is parked
            assert sched.calls_in_flight == 1
            n = gate.entered
            # A's handler learns of it at its next write, in the turn it
            # gets while the next call is in flight
            while not got["cancelled_in_flight"]:
                gate.let_one_through()
                await _until(lambda: gate.entered > n, "no further step")
                n = gate.entered
                await asyncio.sleep(0.02)
            got["a_at_cancel"] = len(a)
        gate.open()
        await asyncio.wait_for(read_b, 30)
        if not disconnect:
            await asyncio.wait_for(read_a, 30)
        await _until(
            lambda: not sched.by_slot and not sched.by_prefill, "a slot stayed held"
        )
        await _until(
            lambda: len(engine.free_slots()) == engine.max_batch, "a slot stayed held"
        )
        got["a"], got["b"] = "".join(a), "".join(b)
        got["b_deltas"] = len(b)
    finally:
        await client.close()
    return got


async def test_a_disconnect_while_a_call_is_in_flight_frees_its_slot_and_loses_nothing():
    whole = await _two_streams(disconnect=False)
    cut = await _two_streams(disconnect=True)
    # the handler's cancel landed with an engine call in flight (before
    # its request finished), touched nothing of the engine then ...
    in_flight, finish = cut["cancelled_in_flight"][0]
    assert in_flight == 1 and finish is None
    assert cut["a_at_cancel"] < 24
    # ... the other stream read token for token what it reads beside a
    # stream that stays, and every slot came back (_two_streams waits)
    assert cut["b"] == whole["b"] and cut["b_deltas"] == 16
    assert whole["a"].startswith(cut["a"]) and len(whole["a"]) == 24


@pytest.mark.parametrize("bias", [ASCII, None], ids=["plain_steps", "macro_steps"])
async def test_a_greedy_streams_text_is_the_completions(bias):
    client, engine, _ = await _client()
    try:
        body = {
            "model": "llama-tiny", "max_tokens": 24, "temperature": 0,
            "messages": [{"role": "user", "content": "abc"}],
        }
        if bias:
            body["logit_bias"] = bias
        r = await client.post("/v1/chat/completions", json=body)
        assert r.status == 200
        text = (await r.json())["choices"][0]["message"]["content"]
        deltas: list = []
        r = await client.post("/v1/chat/completions", json=dict(body, stream=True))
        assert r.status == 200
        await _read(r, deltas)
        # a stream holds back a trailing half of a multi-byte character
        assert "".join(deltas) == text.rstrip("�")
        if bias:
            assert len(deltas) == 24
        else:  # several tokens a call, still one delta a visible token
            steps = engine.metrics.family("dtpu_serve_decode_steps_total").value()
            assert steps < 2 * 23
    finally:
        await client.close()
