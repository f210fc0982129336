"""The serve loop's host phases: four always-on histograms timed by
their callers (``dtpu_serve_host_gap_seconds``, ``_tick_host_``,
``_detokenize_``, ``_stream_write_``) and, while a profiler capture
runs, the same intervals as ``dtpu.*`` spans on its ``/host:CPU``
plane."""

import asyncio
import glob
import os
import time

from dstack_tpu.obs import flight
from tests.serve.test_openai_server import _client as _server_client

PHASES = ("host_gap", "tick_host", "detokenize", "stream_write")


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


class TestHostPhaseHistograms:
    async def test_gap_counts_only_while_requests_hold_slots(self):
        client, engine = await _client()
        try:
            gap = _hist(engine, "host_gap")
            # parked with no request: no engine call, no gap
            await asyncio.sleep(0.2)
            assert gap.count() == 0
            assert all(_hist(engine, p).count() == 0 for p in PHASES)

            t0 = time.perf_counter()
            chunks = await asyncio.gather(
                _stream(client, "abc", 12), _stream(client, "wxyz", 12)
            )
            wall = time.perf_counter() - t0
            assert min(chunks) >= 2
            for p in PHASES:
                h = _hist(engine, p)
                assert h.count() > 0, p
                assert 0.0 < h.sum() <= wall, p
            # every delivered token was detokenized once (+1 final
            # flush a stream), every chunk written once
            tokens = engine.metrics.family(
                "dtpu_serve_tokens_generated_total"
            ).value()
            assert _hist(engine, "detokenize").count() >= tokens
            assert _hist(engine, "stream_write").count() >= sum(chunks) - 2

            # parked again: the wait for the next request is not a gap
            await asyncio.sleep(0.05)
            parked = gap.count()
            await asyncio.sleep(0.3)
            assert gap.count() == parked
            # ... and neither is the first engine call after it: every
            # busy stretch has one engine call more than it has gaps
            await _stream(client, "k", 2)
            calls = sum(
                engine.metrics.family(f"dtpu_serve_{c}_total").value()
                for c in ("prefill_dispatches", "decode_steps")
            )
            assert parked < gap.count() <= calls - 2
        finally:
            await client.close()


class TestSpansInACapture:
    async def test_engine_step_and_stream_write_on_host_plane(
        self, tmp_path, monkeypatch
    ):
        from jax.profiler import ProfileData

        from dstack_tpu.obs import profiling

        monkeypatch.setenv("DTPU_PROFILER_DIR", str(tmp_path / "traces"))
        assert not profiling.is_tracing()
        client, engine = await _client()
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
        files = glob.glob(
            os.path.join(str(tmp_path / "traces"), "**", "*.xplane.pb"),
            recursive=True,
        )
        assert files
        host = [
            p for p in ProfileData.from_file(files[-1]).planes
            if p.name == "/host:CPU"
        ]
        assert host
        names = {
            e.name for line in host[0].lines for e in line.events
            if e.name.startswith("dtpu.")
        }
        assert {
            "dtpu.engine.step", "dtpu.engine.prefill", "dtpu.tick.host",
            "dtpu.stream.detokenize", "dtpu.stream.write",
        } <= names
        # the step span says which flight record is its own
        stats = [
            dict(e.stats) for line in host[0].lines for e in line.events
            if e.name == "dtpu.engine.step"
        ]
        assert stats
        ring = {r["seq"]: r for r in flight.get_recorder().records(512)}
        for s in stats:
            # the step span names the flight record that is its own
            assert ring[int(s["seq"])]["phase"] == s["phase"]
            assert s["phase"] in ("decode", "turbo", "spec")
