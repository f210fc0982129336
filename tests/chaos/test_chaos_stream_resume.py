"""Tentpole invariant (PR 10): a generation stream survives the death
of the replica producing it.

The acceptance chaos scenario runs the REAL data path end to end — two
live openai_server replicas behind ``forward_with_failover`` — and
kills one mid-stream via the ``serve.stream`` fault: the client must
receive the complete, byte-identical greedy completion with zero 5xx
and zero duplicated or missing tokens, and
``dtpu_router_stream_resumes_total`` must advance by exactly 1.

The protocol-level cases (partial-event drop, honest terminal error
events, eligibility gates, ``DTPU_STREAM_RESUME=0``) run against
scripted fake upstreams where chunk boundaries are deterministic.
"""

import asyncio
import json

import aiohttp
import pytest
from aiohttp import web
from aiohttp.test_utils import TestClient, TestServer

from dstack_tpu import qos
from dstack_tpu.models import llama
from dstack_tpu.qos.metrics import get_qos_registry
from dstack_tpu.routing import get_router_registry
from dstack_tpu.routing.forward import forward_with_failover
from dstack_tpu.routing.pool import PoolConfig, ReplicaPool
from dstack_tpu.serve.engine import InferenceEngine
from dstack_tpu.serve.openai_server import build_app
from dstack_tpu.serve.tokenizer import ByteTokenizer
from tests.shared import init_params


def _sse_events(raw: bytes) -> list:
    """Parse a client-received SSE body into its data payloads."""
    out = []
    for block in raw.split(b"\n\n"):
        for line in block.split(b"\n"):
            if line.startswith(b"data:"):
                out.append(line[5:].strip())
    return out


def _stream_text(events: list) -> tuple[str, list, bool]:
    """→ (concatenated delta text, chunk ids, saw [DONE])."""
    text, ids, done = "", [], False
    for data in events:
        if data == b"[DONE]":
            done = True
            continue
        obj = json.loads(data)
        assert "error" not in obj, f"client saw an error event: {obj}"
        ids.append(obj.get("id"))
        c0 = obj["choices"][0]
        delta = c0.get("delta") or {}
        text += delta.get("content") or ""
    return text, ids, done


class _Router:
    """forward_with_failover wired over a two-entry pool — the shape
    both the in-server proxy and the gateway embed."""

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
    """Two REAL replicas (same tiny model + params → identical greedy
    streams) behind a router → (router client, [replica servers])."""
    config = llama.LLAMA_TINY
    params = init_params(config, 0)
    servers = []
    for _ in range(2):
        engine = InferenceEngine(config, params, max_batch=2, max_seq=128)
        server = TestServer(build_app(
            engine, ByteTokenizer(), "llama-tiny", qos_policy=qos_policy,
        ))
        await server.start_server()
        servers.append(server)
    router = _Router([
        (f"r{i}", s.host, s.port) for i, s in enumerate(servers)
    ])
    client = TestClient(TestServer(router.app()))
    await client.start_server()
    return client, servers


_CHAT_PAYLOAD = {
    "model": "llama-tiny",
    "messages": [{"role": "user", "content": "abcdefg"}],
    "max_tokens": 24,
    "stream": True,
    # pin the random-init model to ASCII output (ban every non-byte id
    # incl. eos): resume splices TEXT back into the prompt, so the
    # stream must round-trip utf-8 exactly — a real tokenizer does
    # that for its own output, the byte tokenizer only for 0..127 —
    # and banning eos guarantees enough chunks for the kill to land
    "logit_bias": {
        str(i): -100 for i in range(128, llama.LLAMA_TINY.vocab_size)
    },
}


class TestMidStreamFailover:
    async def test_replica_killed_mid_stream_resumes_byte_identical(
        self, fault_plan
    ):
        """THE acceptance scenario: kill the serving replica on the 2nd
        relayed chunk → the second replica continues the stream; the
        client sees the control run's exact text, one completion id,
        a clean [DONE], and zero 5xx."""
        client, servers = await _serving_stack(
            qos_policy=qos.QoSPolicy(rps=1000.0, burst=1000.0)
        )
        resumes = get_router_registry().family(
            "dtpu_router_stream_resumes_total"
        )
        admitted = get_qos_registry().family("dtpu_qos_admitted_total")
        try:
            # control: the full greedy completion, no faults
            r = await client.post("/v1/chat/completions", json=_CHAT_PAYLOAD)
            assert r.status == 200
            control, _, done = _stream_text(_sse_events(await r.read()))
            assert done and control
            resumes_before = resumes.value()
            admitted_before = admitted.value(qos.ANONYMOUS_TENANT)
            fault_plan({"rules": [
                {"point": "serve.stream", "action": "raise",
                 "error": "connect", "nth": 2},
            ]})
            r = await client.post("/v1/chat/completions", json=_CHAT_PAYLOAD)
            assert r.status == 200  # zero client-visible 5xx
            text, ids, done = _stream_text(_sse_events(await r.read()))
            # complete, byte-identical: no token lost, none duplicated
            assert text == control
            assert done
            assert len(set(ids)) == 1  # resumed leg rewritten to one id
            assert resumes.value() == resumes_before + 1
            # resumed stream charged QoS exactly once: the continuation
            # leg's admission is skipped (X-DTPU-Resume), so the chaos
            # run added ONE admit despite two upstream legs
            assert admitted.value(qos.ANONYMOUS_TENANT) == admitted_before + 1
        finally:
            await client.close()
            for s in servers:
                await s.close()

    async def test_seeded_sampled_stream_resumes_identically(
        self, fault_plan
    ):
        """Seeded sampling resumes deterministically: the continuation
        replays the PRNG advance (GenParams.seed_skip), so the spliced
        stream equals the unbroken control run."""
        client, servers = await _serving_stack()
        payload = {
            **_CHAT_PAYLOAD, "temperature": 1.1, "seed": 13,
            "max_tokens": 20,
        }
        try:
            r = await client.post("/v1/chat/completions", json=payload)
            assert r.status == 200
            control, _, done = _stream_text(_sse_events(await r.read()))
            assert done and control
            fault_plan({"rules": [
                {"point": "serve.stream", "action": "raise",
                 "error": "connect", "nth": 2},
            ]})
            r = await client.post("/v1/chat/completions", json=payload)
            assert r.status == 200
            text, ids, done = _stream_text(_sse_events(await r.read()))
            assert text == control
            assert done and len(set(ids)) == 1
        finally:
            await client.close()
            for s in servers:
                await s.close()


# ---------------------------------------------------------------------------
# protocol-level cases against scripted upstreams
# ---------------------------------------------------------------------------


def _chunk(cid: str, text, finish=None) -> bytes:
    delta = {"role": "assistant"}
    if text is not None:
        delta["content"] = text
    obj = {
        "id": cid, "object": "chat.completion.chunk", "created": 1,
        "model": "m",
        "choices": [{"index": 0, "delta": delta, "finish_reason": finish}],
    }
    return b"data: " + json.dumps(obj).encode() + b"\n\n"


def _scripted_replica(script, seen_payloads):
    """A fake replica whose handler writes the scripted byte chunks
    (full control of SSE event boundaries) then closes WITHOUT
    [DONE] unless the script says otherwise."""

    async def handler(request):
        payload = await request.json()
        seen_payloads.append((request.headers.get(qos.RESUME_HEADER), payload))
        resp = web.StreamResponse(
            headers={"Content-Type": "text/event-stream"}
        )
        await resp.prepare(request)
        wrote = None
        for chunk in script(payload):
            wrote = chunk
            await resp.write(chunk)
        if not (wrote or b"").endswith(b"[DONE]\n\n"):
            # replica DEATH, not a clean finish: tear the socket down
            # mid-chunked-body so the forwarder sees a read error
            request.transport.close()
        return resp

    app = web.Application()
    app.router.add_post("/v1/chat/completions", handler)
    return app


async def _fake_stack(scripts):
    seen: list = []
    servers = []
    for script in scripts:
        server = TestServer(_scripted_replica(script, seen))
        await server.start_server()
        servers.append(server)
    router = _Router([
        (f"r{i}", s.host, s.port) for i, s in enumerate(servers)
    ])
    client = TestClient(TestServer(router.app()))
    await client.start_server()
    return client, servers, seen


class TestResumeProtocol:
    async def test_partial_event_dropped_and_regenerated(self):
        """At-most-once delivery: a half-received event is NOT
        forwarded; the continuation regenerates it — the client sees
        every token exactly once, under the original completion id."""

        def leg(payload):
            resume = (payload.get("dtpu_resume") or {}).get("text", "")
            if not resume:
                # first leg: two whole events + a PARTIAL third, die
                yield _chunk("orig", "Hello ")
                yield _chunk("orig", "wor")
                yield b'data: {"id": "orig", "choi'  # torn mid-event
                return
            # resume leg: a fresh id; must continue after 'Hello wor'
            assert resume == "Hello wor"
            yield _chunk("resumed", "ld!")
            yield _chunk("resumed", None, finish="stop")
            yield b"data: [DONE]\n\n"

        client, servers, seen = await _fake_stack([leg, leg])
        try:
            r = await client.post(
                "/v1/chat/completions",
                json={"stream": True, "messages": [], "model": "m"},
            )
            assert r.status == 200
            text, ids, done = _stream_text(_sse_events(await r.read()))
            assert text == "Hello world!"
            assert done
            assert set(ids) == {"orig"}  # resumed leg rewritten
            # the resume leg carried the proxy-asserted marker
            assert [h for h, _ in seen] == [None, "1"]
        finally:
            await client.close()
            for s in servers:
                await s.close()

    async def test_pool_exhausted_mid_stream_terminal_error_event(self):
        """Resume impossible (no replica left): the committed stream
        ends with an honest error event + [DONE], never a silent
        truncation or a hang."""

        def dies(payload):
            yield _chunk("orig", "Hel")
            # dies without [DONE]; no second leg will accept either

        def refuses(payload):
            # the "other replica" is also broken: it dies immediately
            # on the resume leg too
            return iter(())

        client, servers, seen = await _fake_stack([dies, refuses])
        try:
            r = await client.post(
                "/v1/chat/completions",
                json={"stream": True, "messages": [], "model": "m"},
            )
            assert r.status == 200
            events = _sse_events(await r.read())
            assert events[-1] == b"[DONE]"
            payloads = [json.loads(e) for e in events[:-1]]
            errors = [p for p in payloads if "error" in p]
            assert len(errors) == 1
            assert "resumed" in errors[0]["error"]["message"]
        finally:
            await client.close()
            for s in servers:
                await s.close()

    async def test_unseeded_sampling_is_not_resumed(self):
        """Sampling without a seed cannot replay: the stream takes the
        opaque path and upstream death ends it with a terminal error
        event — the second replica is never consulted."""

        def dies(payload):
            yield _chunk("orig", "Hel")

        def never(payload):
            raise AssertionError("ineligible stream must not resume")

        client, servers, seen = await _fake_stack([dies, never])
        try:
            r = await client.post(
                "/v1/chat/completions",
                json={"stream": True, "messages": [], "model": "m",
                      "temperature": 0.9},
            )
            assert r.status == 200
            events = _sse_events(await r.read())
            assert events[-1] == b"[DONE]"
            errors = [
                json.loads(e) for e in events[:-1]
                if b"error" in e
            ]
            assert len(errors) == 1
            assert len(seen) == 1  # one upstream leg only
        finally:
            await client.close()
            for s in servers:
                await s.close()

    async def test_resume_disabled_by_env(self, monkeypatch):
        monkeypatch.setenv("DTPU_STREAM_RESUME", "0")

        def dies(payload):
            yield _chunk("orig", "Hel")

        client, servers, seen = await _fake_stack([dies, dies])
        try:
            r = await client.post(
                "/v1/chat/completions",
                json={"stream": True, "messages": [], "model": "m"},
            )
            assert r.status == 200
            events = _sse_events(await r.read())
            assert events[-1] == b"[DONE]"
            assert any(b"error" in e for e in events[:-1])
            assert len(seen) == 1
        finally:
            await client.close()
            for s in servers:
                await s.close()

    async def test_lost_done_sentinel_is_replayed(self):
        """The generation finished but the replica died before [DONE]:
        the forwarder emits the sentinel itself instead of
        re-dispatching a finished stream."""

        def finished_no_done(payload):
            yield _chunk("orig", "Hi")
            yield _chunk("orig", None, finish="stop")

        def never(payload):
            raise AssertionError("finished stream must not resume")

        client, servers, seen = await _fake_stack([finished_no_done, never])
        try:
            r = await client.post(
                "/v1/chat/completions",
                json={"stream": True, "messages": [], "model": "m"},
            )
            assert r.status == 200
            events = _sse_events(await r.read())
            assert events[-1] == b"[DONE]"
            assert not any(b'"error"' in e for e in events)
            assert len(seen) == 1
        finally:
            await client.close()
            for s in servers:
                await s.close()


class TestEligibility:
    """The _resumable_stream gate: every 'provably equal' rule from
    serving.md §9's table, as units (no sockets)."""

    def _elig(self, payload, path="v1/chat/completions", method="POST"):
        from dstack_tpu.routing.forward import _resumable_stream

        return _resumable_stream(method, path, json.dumps(payload).encode())

    def test_greedy_chat_and_completions_eligible(self):
        assert self._elig({"stream": True, "messages": []}) is not None
        assert self._elig(
            {"stream": True, "prompt": "x"}, path="v1/completions"
        ) is not None

    def test_seeded_chat_eligible_but_completions_not(self):
        """Plain prompt extension cannot carry the PRNG advance: a
        seeded legacy-completions resume would silently diverge — it
        must take the honest-terminal-error path instead."""
        sampled = {"stream": True, "temperature": 1.1, "seed": 7}
        assert self._elig({**sampled, "messages": []}) is not None
        assert self._elig(
            {**sampled, "prompt": "x"}, path="v1/completions"
        ) is None

    def test_ineligible_shapes(self):
        base = {"stream": True, "messages": []}
        assert self._elig({**base, "temperature": 0.9}) is None  # no seed
        assert self._elig({**base, "presence_penalty": 0.5}) is None
        assert self._elig({**base, "frequency_penalty": 0.5}) is None
        assert self._elig({**base, "logprobs": True}) is None
        assert self._elig({**base, "n": 2}) is None
        assert self._elig({**base, "tools": [{"type": "function"}]}) is None
        assert self._elig({"messages": []}) is None  # not streaming
        assert self._elig(base, method="GET") is None
        assert self._elig(base, path="v1/embeddings") is None

    def test_deadline_header_rewrite_replaces_any_casing(self):
        """An HTTP/2 LB lowercases header names; the per-leg remaining-
        budget rewrite must REPLACE the stale value, not duplicate the
        header (the replica would read the full budget first)."""
        from dstack_tpu.routing.forward import filter_request_headers
        from dstack_tpu.utils.retry import Deadline

        send = filter_request_headers({"x-dtpu-deadline": "30", "A": "b"})
        deadline = Deadline(30.0)
        # the forwarder's per-leg rewrite, verbatim
        send = {
            k: v for k, v in send.items()
            if k.lower() != qos.DEADLINE_HEADER.lower()
        }
        send[qos.DEADLINE_HEADER] = f"{deadline.remaining():.3f}"
        matches = [k for k in send if k.lower() == "x-dtpu-deadline"]
        assert matches == [qos.DEADLINE_HEADER]
        assert float(send[qos.DEADLINE_HEADER]) <= 30.0
