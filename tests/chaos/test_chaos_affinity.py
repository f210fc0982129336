"""Tentpole acceptance (PR 11): prefix-affinity routing on the REAL
data path — two live openai_server replicas behind
``forward_with_failover``.

Three invariants, per the issue's acceptance bar:

1. **Stickiness pays.** Repeated turns of one chat session land on the
   same replica, and warm-turn TTFT (client time-to-first-SSE-chunk)
   beats the affinity-off control by ≥ 1.3× at p50 — the single-replica
   prefix-cache win (BENCH_r05: 7.7ms hit vs 14.3ms cold) survives
   multi-replica routing.
2. **Failover re-warms.** Killing the hot replica mid-session produces
   zero client 5xx — the session fails over to the survivor, the
   affinity map re-learns it, and subsequent turns prefix-hit there.
3. **Overload isolation.** When every session hashes to one replica,
   the imbalance cap sheds the excess to peers:
   ``dtpu_router_affinity_overrides_total`` advances and no replica
   ever exceeds the cap over the least-loaded peer while that peer
   idles.
"""

import asyncio
import dataclasses
import json
import time

import aiohttp
from aiohttp import web
from aiohttp.test_utils import TestClient, TestServer

from dstack_tpu import qos
from dstack_tpu.models import llama
from dstack_tpu.routing import get_router_registry
from dstack_tpu.routing.affinity import AffinityConfig, request_affinity
from dstack_tpu.routing.forward import forward_with_failover
from dstack_tpu.routing.pool import PoolConfig, ReplicaPool, ReplicaState
from dstack_tpu.serve.engine import InferenceEngine
from dstack_tpu.serve.openai_server import build_app
from dstack_tpu.serve.tokenizer import ByteTokenizer
from tests.shared import init_params

TENANT = "chaos-tenant"

# pin the random-init model to ASCII output (ban every non-byte id
# incl. eos): assistant replies are spliced back into the next turn's
# history, so the text must round-trip the byte tokenizer exactly,
# and banning eos keeps generations at their full token budget
_ASCII_BIAS = {
    str(i): -100 for i in range(128, llama.LLAMA_TINY.vocab_size)
}


def _payload(messages, max_tokens=8, stream=False):
    p = {
        "model": "llama-tiny",
        "messages": messages,
        "max_tokens": max_tokens,
        "logit_bias": _ASCII_BIAS,
    }
    if stream:
        p["stream"] = True
    return p


def _sse_text(raw: bytes) -> str:
    """Concatenated delta text of a client-received SSE body."""
    text = ""
    for block in raw.split(b"\n\n"):
        for line in block.split(b"\n"):
            if not line.startswith(b"data:"):
                continue
            data = line[5:].strip()
            if data == b"[DONE]":
                continue
            obj = json.loads(data)
            assert "error" not in obj, f"client saw an error event: {obj}"
            delta = obj["choices"][0].get("delta") or {}
            text += delta.get("content") or ""
    return text


class _Router:
    """forward_with_failover over a real pool, with a pick log so the
    tests can assert WHERE each request landed. Injects the
    proxy-asserted tenant header exactly like the in-server proxy."""

    def __init__(self, replicas):
        self.pool = ReplicaPool("p", "svc", PoolConfig(startup_grace=0.0))
        self.pool.sync(replicas)
        # the probe loop would promote live replicas to READY; without
        # it the first success pins ALL serial traffic to one replica
        # (READY outranks STARTING) and neither mode would ever spread
        for e in self.pool.entries.values():
            e.state = ReplicaState.READY
        self.session = None
        self.picks = []
        self.acquire_imbalance = []  # (rid, outstanding spread) per acquire
        orig_pick = self.pool.pick
        orig_acquire = self.pool.acquire

        def logging_pick(exclude=(), affinity=None, **kw):
            # **kw: pass through forwarder-supplied extras (e.g. the
            # trace span) so the shim tracks, never changes, the API
            e = orig_pick(exclude=exclude, affinity=affinity, **kw)
            if e is not None:
                self.picks.append(e.replica_id)
            return e

        def logging_acquire(entry):
            orig_acquire(entry)
            outs = {
                rid: self.pool.get(rid).outstanding
                for rid in self.pool.replica_ids()
            }
            self.acquire_imbalance.append(
                (entry.replica_id,
                 outs[entry.replica_id] - min(outs.values()))
            )

        self.pool.pick = logging_pick
        self.pool.acquire = logging_acquire

    def app(self) -> web.Application:
        app = web.Application()

        async def handler(request):
            if self.session is None:
                self.session = aiohttp.ClientSession()
            return await forward_with_failover(
                request, self.pool, self.session,
                request.match_info["path"],
                extra_headers={qos.TENANT_HEADER: TENANT},
            )

        app.router.add_route("*", "/{path:.*}", handler)

        async def cleanup(_):
            if self.session is not None:
                await self.session.close()

        app.on_cleanup.append(cleanup)
        return app


async def _serving_stack(
    n=2, max_batch=4, max_seq=1024, prefill_chunk=32, n_layers=2
):
    """n REAL replicas (same tiny model + params) behind a logging
    router → (client, servers, engines, router)."""
    config = dataclasses.replace(llama.LLAMA_TINY, n_layers=n_layers)
    params = init_params(config, 0)
    servers, engines = [], []
    for _ in range(n):
        engine = InferenceEngine(
            config, params, max_batch=max_batch, max_seq=max_seq,
            prefill_chunk=prefill_chunk,
        )
        server = TestServer(
            build_app(engine, ByteTokenizer(), "llama-tiny")
        )
        await server.start_server()
        servers.append(server)
        engines.append(engine)
    router = _Router([
        (f"r{i}", s.host, s.port) for i, s in enumerate(servers)
    ])
    client = TestClient(TestServer(router.app()))
    await client.start_server()
    return client, servers, engines, router


async def _close(client, servers):
    await client.close()
    for s in servers:
        await s.close()


async def _chat_turn(client, messages, max_tokens=8) -> str:
    """One non-streaming turn → assistant text."""
    r = await client.post(
        "/v1/chat/completions", json=_payload(messages, max_tokens)
    )
    assert r.status == 200, await r.text()
    body = await r.json()
    return body["choices"][0]["message"]["content"]


async def _stream_turn(client, messages, max_tokens=8):
    """One streaming turn → (TTFT seconds, assistant text). TTFT is
    request-start to first SSE body chunk: the server prepares headers
    immediately but emits the first data event only with the first
    sampled token, so this is client-observed TTFT."""
    t0 = time.perf_counter()
    r = await client.post(
        "/v1/chat/completions",
        json=_payload(messages, max_tokens, stream=True),
    )
    assert r.status == 200
    ttft = None
    buf = b""
    async for chunk in r.content.iter_chunked(4096):
        if ttft is None:
            ttft = time.perf_counter() - t0
        buf += chunk
    assert ttft is not None
    return ttft, _sse_text(buf)


def _turn_text(i: int, t: int) -> str:
    word = "abcdefgh"[i % 8]
    return f"session {i} turn {t}: " + " ".join(
        f"{word}{j}{word * 3}" for j in range(18)
    )


class TestSessionStickinessAndWarmTTFT:
    async def test_warm_turns_stick_and_beat_the_control(self):
        """Acceptance (1): same-session turns land on one replica and
        warm-turn TTFT p50 beats affinity-off by ≥ 1.3×. Eight layers,
        so that a prompt chunk costs something beside a request's fixed
        ~50 ms on the CPU: since the prefill programs stopped copying
        the whole cache a chunk (PR 29), two layers' chunks cost a third
        of what they did and reuse saved 12 ms of 70, not 38 of 100."""
        client, servers, engines, router = await _serving_stack(n_layers=8)
        pool = router.pool
        sessions, turns = 3, 3
        try:
            async def run_workload(timed: bool) -> list:
                """ONE streaming request per (session, turn), sessions
                interleaved turn by turn — an odd per-turn request
                count, so the control's round-robin cannot accidentally
                re-align sessions to replicas. → warm-turn TTFTs."""
                histories = [
                    [{"role": "user", "content": _turn_text(i, 0)}]
                    for i in range(sessions)
                ]
                warm = []
                for t in range(turns):
                    for i in range(sessions):
                        if t > 0:
                            histories[i].append(
                                {"role": "user",
                                 "content": _turn_text(i, t)}
                            )
                        ttft, reply = await _stream_turn(
                            client, histories[i]
                        )
                        if timed and t > 0:
                            warm.append(ttft)
                        # the reply is greedy off identical weights on
                        # both replicas, so histories stay identical
                        # across modes and turn t+1 extends turn t's
                        # prompt exactly
                        histories[i].append(
                            {"role": "assistant", "content": reply}
                        )
                return warm

            def reset():
                for e in engines:
                    e.reset_prefix_cache()
                pool.affinity.clear()
                pool._rr = 0
                router.picks.clear()

            def per_session_picks():
                return {
                    i: router.picks[i::sessions] for i in range(sessions)
                }

            # untimed passes compile every chunk/copy variant the timed
            # passes will hit, per mode (the control's partial-overlap
            # hits compile different copy lengths than affinity-on)
            pool.affinity.config = AffinityConfig(enabled=True)
            await run_workload(timed=False)
            reset()
            on_warm = await run_workload(timed=True)
            for i, picks in per_session_picks().items():
                assert len(set(picks)) == 1, (
                    f"session {i} scattered: {picks}"
                )

            pool.affinity.config = AffinityConfig(enabled=False)
            reset()
            await run_workload(timed=False)
            reset()
            off_warm = await run_workload(timed=True)
            # the control must actually scatter (least-outstanding RR
            # over serial requests) — otherwise the comparison is void
            assert any(
                len(set(picks)) > 1
                for picks in per_session_picks().values()
            )
            p50_on = sorted(on_warm)[len(on_warm) // 2]
            p50_off = sorted(off_warm)[len(off_warm) // 2]
            assert p50_off / p50_on >= 1.3, (
                f"warm TTFT p50: affinity on {p50_on * 1e3:.1f}ms, "
                f"off {p50_off * 1e3:.1f}ms — speedup "
                f"{p50_off / max(p50_on, 1e-9):.2f}x < 1.3x"
            )
        finally:
            await _close(client, servers)


class TestHotReplicaDeathRewarms:
    async def test_failover_zero_5xx_and_rewarm_on_survivor(self):
        """Acceptance (2): kill the session's hot replica → the next
        turns succeed (zero 5xx), the affinity map re-learns the
        survivor, and the session prefix-hits there again."""
        client, servers, engines, router = await _serving_stack()
        pool = router.pool
        history = [{"role": "user", "content": _turn_text(0, 0)}]
        try:
            for t in (1, 2):
                reply = await _chat_turn(client, history)
                history.append({"role": "assistant", "content": reply})
                history.append(
                    {"role": "user", "content": _turn_text(0, t)}
                )
            hot = router.picks[-1]
            assert set(router.picks) == {hot}  # warmed onto one replica
            hot_ix = int(hot[1:])
            survivor_ix = 1 - hot_ix
            survivor = f"r{survivor_ix}"
            await servers[hot_ix].close()

            hits_before = engines[survivor_ix].prefix_hits
            # two more turns: the first fails over (connect error →
            # retry on the survivor, no client-visible error), the
            # second prefix-hits the survivor's freshly-registered
            # history
            for t in (3, 4):
                reply = await _chat_turn(client, history)
                history.append({"role": "assistant", "content": reply})
                history.append(
                    {"role": "user", "content": _turn_text(0, t)}
                )
            assert router.picks[-1] == survivor
            key = request_affinity(
                "v1/chat/completions", {"messages": history}, TENANT
            )
            assert pool.affinity.lookup(key) == survivor
            assert engines[survivor_ix].prefix_hits > hits_before
        finally:
            await _close(client, servers)


class TestImbalanceFloodOverride:
    async def test_flood_to_one_replica_sheds_within_cap(self):
        """Acceptance (3): all sessions mapped to one replica + a
        concurrent flood → the override path sheds to peers, the
        counter advances, and no acquire ever exceeds the cap over
        the least-loaded replica."""
        client, servers, engines, router = await _serving_stack(
            max_batch=8
        )
        pool = router.pool
        cap = 1
        pool.affinity.config = AffinityConfig(
            enabled=True, max_imbalance=cap
        )
        overrides = get_router_registry().family(
            "dtpu_router_affinity_overrides_total"
        )
        n = 6
        floods = []
        for i in range(n):
            messages = [{"role": "user", "content": _turn_text(i, 0)}]
            key = request_affinity(
                "v1/chat/completions", {"messages": messages}, TENANT
            )
            pool.affinity.record(key, "r0")  # everyone hashes to r0
            floods.append(messages)
        try:
            # one warm-up request per replica compiles the kernels so
            # the flood actually overlaps instead of serializing
            # behind a one-off XLA compile
            for rid in ("r0", "r1"):
                warm_messages = [
                    {"role": "user", "content": f"warm {rid}"}
                ]
                k = request_affinity(
                    "v1/chat/completions",
                    {"messages": warm_messages}, TENANT,
                )
                pool.affinity.record(k, rid)
                await _chat_turn(client, warm_messages)
            router.acquire_imbalance.clear()
            o0 = overrides.value()

            async def flood_one(messages):
                r = await client.post(
                    "/v1/chat/completions",
                    json=_payload(messages, max_tokens=32, stream=True),
                )
                body = await r.read()
                return r.status, body

            results = await asyncio.gather(
                *(flood_one(m) for m in floods)
            )
            assert all(status == 200 for status, _ in results)
            assert overrides.value() > o0, "override path never fired"
            spread = {rid for rid, _ in router.acquire_imbalance}
            assert spread == {"r0", "r1"}, (
                f"peers idled through the flood: {spread}"
            )
            # the cap's invariant: at no acquire did any replica hold
            # more than cap+1 over the least-loaded one (honoring
            # affinity at exactly cap, then incrementing, is the max)
            worst = max(d for _, d in router.acquire_imbalance)
            assert worst <= cap + 1, (
                f"imbalance {worst} exceeded cap {cap}: "
                f"{router.acquire_imbalance}"
            )
        finally:
            await _close(client, servers)
