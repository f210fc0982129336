"""Tentpole acceptance (PR 13): ONE trace id spans a mid-stream
failover.

The stack is real end to end — two live openai_server replicas behind
``forward_with_failover`` — and a ``serve.stream`` fault kills the
serving replica on the 2nd relayed chunk, exactly the PR-9 resume
scenario. The distributed trace must then tell the whole story from
one id: the router's forward root, TWO ``router.dispatch`` legs as
siblings (the dead one marked error, the resume leg marked
``resume=True``), and BOTH replica-side ``serve.request`` spans
parented to their legs with QoS admission, queue, prefill, and decode
phases populated — with zero client-visible 5xx.

Everything runs in one process, so the module-global tracer ring holds
the STITCHED trace (router + both replicas), which is also what the
loadgen soak's tail attribution reads.
"""

import asyncio
import json

import aiohttp
import pytest
from aiohttp import web
from aiohttp.test_utils import TestClient, TestServer

from dstack_tpu import qos
from dstack_tpu.models import llama
from dstack_tpu.obs import tracing
from dstack_tpu.routing.forward import forward_with_failover
from dstack_tpu.routing.pool import PoolConfig, ReplicaPool
from dstack_tpu.serve.engine import InferenceEngine
from dstack_tpu.serve.openai_server import build_app
from dstack_tpu.serve.tokenizer import ByteTokenizer
from tests.shared import init_params


@pytest.fixture(autouse=True)
def _fresh_tracer():
    """Each test starts with an empty, generously-sized ring and
    leaves the process tracer as it found it."""
    prior = tracing.get_tracer()
    tracing.enable(buffer=512)
    yield
    if prior is not None:
        tracing._tracer = prior
        tracing.span = prior.span
    else:
        tracing.disable()


def _sse_text(raw: bytes) -> tuple[str, bool, bool]:
    """→ (delta text, saw [DONE], saw an error event)."""
    text, done, err = "", False, False
    for block in raw.split(b"\n\n"):
        for line in block.split(b"\n"):
            if not line.startswith(b"data:"):
                continue
            data = line[5:].strip()
            if data == b"[DONE]":
                done = True
                continue
            obj = json.loads(data)
            if "error" in obj:
                err = True
                continue
            delta = obj["choices"][0].get("delta") or {}
            text += delta.get("content") or ""
    return text, done, err


class _Router:
    def __init__(self, replicas):
        self.pool = ReplicaPool("p", "svc", PoolConfig(startup_grace=0.0))
        self.pool.sync(replicas)
        self.session = None

    def app(self) -> web.Application:
        app = web.Application()

        async def handler(request):
            if self.session is None:
                self.session = aiohttp.ClientSession()
            return await forward_with_failover(
                request, self.pool, self.session,
                request.match_info["path"],
            )

        app.router.add_route("*", "/{path:.*}", handler)

        async def cleanup(_):
            if self.session is not None:
                await self.session.close()

        app.on_cleanup.append(cleanup)
        return app


async def _serving_stack(qos_policy=None):
    config = llama.LLAMA_TINY
    params = init_params(config, 0)
    servers, engines = [], []
    for _ in range(2):
        engine = InferenceEngine(config, params, max_batch=2, max_seq=128)
        server = TestServer(build_app(
            engine, ByteTokenizer(), "llama-tiny", qos_policy=qos_policy,
        ))
        await server.start_server()
        servers.append(server)
        engines.append(engine)
    router = _Router([
        (f"r{i}", s.host, s.port) for i, s in enumerate(servers)
    ])
    client = TestClient(TestServer(router.app()))
    await client.start_server()
    return client, servers, engines


_CHAT_PAYLOAD = {
    "model": "llama-tiny",
    "messages": [{"role": "user", "content": "abcdefg"}],
    "max_tokens": 24,
    "stream": True,
    # pin the random-init model to ASCII (ban non-byte ids incl. eos):
    # resume splices TEXT, and banning eos guarantees enough chunks
    # for the kill to land (the stream-resume suite's trick)
    "logit_bias": {
        str(i): -100 for i in range(128, llama.LLAMA_TINY.vocab_size)
    },
}


def _spans_by_name(trace: dict) -> dict:
    out: dict = {}
    for s in trace["spans"]:
        out.setdefault(s["name"], []).append(s)
    return out


class TestTraceContinuityAcrossFailover:
    async def test_one_trace_two_legs_resume_parented(self, fault_plan):
        """THE acceptance scenario: kill the serving replica on chunk 2
        → one trace holds the dead leg and the resume leg as siblings
        under the forward root, both replicas' serve.request spans
        parent to their legs, and every phase is populated."""
        client, servers, engines = await _serving_stack(
            qos_policy=qos.QoSPolicy(rps=1000.0, burst=1000.0)
        )
        try:
            fault_plan({"rules": [
                {"point": "serve.stream", "action": "raise",
                 "error": "connect", "nth": 2},
            ]})
            r = await client.post("/v1/chat/completions", json=_CHAT_PAYLOAD)
            assert r.status == 200  # zero client-visible 5xx
            tid = r.headers.get(tracing.TRACE_HEADER)
            assert tid, "router did not echo the trace id to the client"
            text, done, err = _sse_text(await r.read())
            assert done and text and not err

            trace = tracing.get_trace(tid)
            assert trace is not None, "trace rotated out of the ring"
            by_name = _spans_by_name(trace)

            # the router half: one forward root, two dispatch legs
            root = by_name["router.forward"][0]
            assert root["parent_id"] is None
            legs = sorted(
                by_name["router.dispatch"], key=lambda s: s["attrs"]["attempt"]
            )
            assert len(legs) == 2
            # SIBLINGS under the forward root — the stitched-failover
            # shape the issue names
            assert all(s["parent_id"] == root["span_id"] for s in legs)
            dead, resumed = legs
            assert dead["status"] == "error"
            assert dead["attrs"]["resume"] is False
            assert resumed["attrs"]["resume"] is True
            assert resumed["status"] == "ok"
            assert dead["attrs"]["replica"] != resumed["attrs"]["replica"]
            # pick events landed on the forward span
            picks = [
                e for e in root["events"] if e["name"] == "replica_pick"
            ]
            assert len(picks) == 2

            # the replica half: one serve.request per leg, each
            # parented to ITS dispatch leg (the X-DTPU-Trace chain)
            serves = by_name["serve.request"]
            assert len(serves) == 2
            parents = {s["parent_id"] for s in serves}
            assert parents == {dead["span_id"], resumed["span_id"]}
            continuation = next(
                s for s in serves if s["parent_id"] == resumed["span_id"]
            )
            assert continuation["attrs"].get("resumed") is True

            # phases populated: QoS admission event on the FIRST leg
            # only (the resume leg is never re-admitted), queue +
            # prefill + decode spans per serve.request
            first_serve = next(
                s for s in serves if s["parent_id"] == dead["span_id"]
            )
            admits = [
                e for e in first_serve["events"] if e["name"] == "edge_admit"
            ]
            assert admits and admits[0]["attrs"]["shed"] is False
            assert not any(
                e["name"] == "edge_admit" for e in continuation["events"]
            )
            serve_ids = {s["span_id"] for s in serves}
            for phase in ("serve.queue", "serve.prefill", "serve.decode"):
                phase_spans = by_name.get(phase, [])
                assert len(phase_spans) == 2, f"{phase} missing a leg"
                assert all(
                    s["parent_id"] in serve_ids and s["duration_s"] >= 0
                    for s in phase_spans
                )
            decode = by_name["serve.decode"]
            assert any(
                e["name"] == "macro_step" for s in decode for e in s["events"]
            )
            # the killed leg's decode may end "cancelled" (the dead
            # replica notices the forwarder's disconnect) — but the
            # continuation's decode finished and reports its yield
            assert any(s["attrs"].get("tokens", 0) >= 1 for s in decode)

            # the TTFT histogram carries this trace as an exemplar on
            # at least one engine ("show me the trace behind p99")
            exemplars = [
                ex
                for e in engines
                for (_v, ex) in e.metrics.family(
                    "dtpu_serve_ttft_seconds"
                ).exemplars().values()
            ]
            assert tid in exemplars

            # /debug/traces?id= (served by a replica through the
            # router's catch-all) returns the same stitched trace
            r = await client.get(f"/debug/traces?id={tid}")
            assert r.status == 200
            payload = await r.json()
            assert payload["enabled"] and payload["trace"]["trace_id"] == tid
            assert len(payload["trace"]["spans"]) == len(trace["spans"])
        finally:
            await client.close()
            for s in servers:
                await s.close()

    async def test_clean_request_single_leg_trace(self):
        """No faults: one leg, one serve.request, phases nested, and
        the slowest listing surfaces the trace."""
        client, servers, _ = await _serving_stack()
        try:
            r = await client.post("/v1/chat/completions", json=_CHAT_PAYLOAD)
            assert r.status == 200
            tid = r.headers.get(tracing.TRACE_HEADER)
            text, done, err = _sse_text(await r.read())
            assert done and text and not err
            trace = tracing.get_trace(tid)
            by_name = _spans_by_name(trace)
            assert len(by_name["router.dispatch"]) == 1
            assert len(by_name["serve.request"]) == 1
            assert by_name["router.dispatch"][0]["status"] == "ok"
            listed = tracing.debug_payload({"slowest": "5"})["traces"]
            assert tid in {t["trace_id"] for t in listed}
        finally:
            await client.close()
            for s in servers:
                await s.close()

    async def test_disabled_tracing_serves_identically(self, fault_plan):
        """DTPU_TRACE=0 (the no-op rebinding) must leave the data path
        byte-identical — including THROUGH a mid-stream failover: same
        completion, zero 5xx, no trace header, nothing recorded. This
        plus the obs-level `span is _noop_span` identity pin is the
        zero-cost acceptance: the disabled path runs no tracing code
        at all."""
        client, servers, _ = await _serving_stack()
        try:
            r = await client.post("/v1/chat/completions", json=_CHAT_PAYLOAD)
            assert r.status == 200
            control, done, _ = _sse_text(await r.read())
            assert done and control
            tracing.disable()
            assert tracing.span is tracing._noop_span
            fault_plan({"rules": [
                {"point": "serve.stream", "action": "raise",
                 "error": "connect", "nth": 2},
            ]})
            r = await client.post("/v1/chat/completions", json=_CHAT_PAYLOAD)
            assert r.status == 200
            assert tracing.TRACE_HEADER not in r.headers
            text, done, err = _sse_text(await r.read())
            assert text == control and done and not err
            assert tracing.debug_payload({}) == {
                "enabled": False, "traces": [],
            }
        finally:
            await client.close()
            for s in servers:
                await s.close()

    async def test_client_supplied_trace_header_is_stripped(self):
        """A client-smuggled X-DTPU-Trace must never graft onto the
        server-side trace: the forwarder strips it (PROXY_ASSERTED
        list) and asserts its own context per leg."""
        client, servers, _ = await _serving_stack()
        try:
            forged = "deadbeefdeadbeef-12345678"
            r = await client.post(
                "/v1/chat/completions", json=_CHAT_PAYLOAD,
                headers={tracing.TRACE_HEADER: forged},
            )
            assert r.status == 200
            tid = r.headers.get(tracing.TRACE_HEADER)
            await r.read()
            assert tid and tid != "deadbeefdeadbeef"
            assert tracing.get_trace("deadbeefdeadbeef") is None
        finally:
            await client.close()
            for s in servers:
                await s.close()
