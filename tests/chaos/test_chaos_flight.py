"""Flight-recorder chaos acceptance (ISSUE 15): with the recorder on,
an injected ``serve.engine.step`` slot hang → watchdog abort produces
a post-mortem whose LAST record names the wedged slot and whose trace
id matches the aborted request's trace; ``DTPU_FLIGHT=0`` pins the
no-op identity and the instrumented decode path shows no measurable
throughput regression vs flight-off."""

import asyncio
import subprocess
import sys
import time
from pathlib import Path

import pytest

from dstack_tpu import faults
from dstack_tpu.models import llama
from dstack_tpu.obs import flight, tracing
from dstack_tpu.serve.engine import GenParams, InferenceEngine
from dstack_tpu.serve.openai_server import build_app
from dstack_tpu.serve.tokenizer import ByteTokenizer
from tests.shared import init_params

REPO = Path(__file__).resolve().parents[2]


@pytest.fixture(autouse=True)
def _fresh_flight_and_tracing():
    """Each test gets a fresh recorder AND tracer; module state is
    restored afterwards (the acceptance stitches flight records to
    trace ids, so both must be live and clean)."""
    prior_rec = flight.get_recorder()
    prior_tracer = tracing.get_tracer()
    flight.enable(buffer=256)
    tracing.enable(buffer=64)
    yield
    if prior_rec is not None:
        flight._recorder = prior_rec
        flight.record = prior_rec.record
    else:
        flight.disable()
    if prior_tracer is not None:
        tracing._tracer = prior_tracer
        tracing.span = prior_tracer.span
    else:
        tracing.disable()


async def _watchdog_client(watchdog_seconds=0.3):
    from aiohttp.test_utils import TestClient, TestServer

    config = llama.LLAMA_TINY
    params = init_params(config, 0)
    engine = InferenceEngine(config, params, max_batch=4, max_seq=128)
    if watchdog_seconds:
        # as `openai_server.main` does before it serves: a 0.3 s
        # watchdog over a cold engine times the sandbox's compiler, not
        # a wedged slot (see tests/chaos/test_chaos_serve.py)
        from dstack_tpu.serve.openai_server import _warmup_engine

        _warmup_engine(engine)
    app = build_app(
        engine, ByteTokenizer(), "llama-tiny",
        watchdog_seconds=watchdog_seconds,
    )
    client = TestClient(TestServer(app))
    await client.start_server()
    return client, engine


class TestFlightChaosAcceptance:
    async def test_watchdog_postmortem_names_wedged_slot_and_trace(
        self, fault_plan
    ):
        """THE acceptance: slot-0 hang → watchdog abort → the flight
        post-mortem's last record is the wedge marker naming slot 0,
        and its trace id equals the X-DTPU-Trace the aborted request's
        500 echoed to the client — the flight ring and the distributed
        trace describe the SAME incident."""
        client, engine = await _watchdog_client(watchdog_seconds=0.3)
        rec = flight.get_recorder()
        try:
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
                return r.status, await r.json(), r.headers.get(
                    tracing.TRACE_HEADER
                )

            (s1, d1, t1), (s2, d2, t2) = await asyncio.gather(
                one("abcd"), one("wxyz")
            )
            assert sorted([s1, s2]) == [200, 500], (d1, d2)
            failed_trace = t1 if s1 == 500 else t2
            assert failed_trace, "the 500 must echo its trace id"
            pms = rec.postmortems()
            assert pms, "watchdog abort must capture a post-mortem"
            pm = pms[-1]
            assert pm["reason"] == "watchdog_abort"
            assert pm["ctx"]["wedge"] == "slot:0"
            last = pm["records"][-1]
            assert last["phase"] == "wedge"
            assert last["slot"] == 0
            assert last["trace"] == failed_trace
            # the wedged request's trace id also sits in the affected-
            # slots attribution
            assert pm["ctx"]["slots"].get("0", pm["ctx"]["slots"].get(0)) \
                == failed_trace
            # the surviving stream's steps kept flight-recording around
            # the incident and the abort is visible to probes
            r = await client.get("/health")
            h = await r.json()
            assert h["flight"]["postmortems"] >= 1
            # /debug/flight exposes the same snapshot over HTTP
            r = await client.get("/debug/flight?postmortems=5")
            p = await r.json()
            assert p["postmortems"][-1]["ctx"]["wedge"] == "slot:0"
            # let the abandoned (still-sleeping) step thread drain
            await asyncio.sleep(1.0)
        finally:
            await client.close()

    async def test_engine_error_postmortem(self, fault_plan):
        """A raising serve.engine.step lands an engine_error
        post-mortem carrying the error text (the scheduler-side
        capture)."""
        client, engine = await _watchdog_client(watchdog_seconds=0.0)
        rec = flight.get_recorder()
        try:
            fault_plan({"rules": [
                {"point": "serve.engine.step", "action": "raise",
                 "error": "injected", "times": 1},
            ]})
            r = await client.post(
                "/v1/completions",
                json={"model": "llama-tiny", "prompt": "abcd",
                      "max_tokens": 8},
            )
            assert r.status == 500
            pms = [
                p for p in rec.postmortems()
                if p["reason"] == "engine_error"
            ]
            assert pms and "injected" in pms[-1]["ctx"]["error"]
            # server keeps serving after the post-mortem
            faults.clear()
            r = await client.post(
                "/v1/completions",
                json={"model": "llama-tiny", "prompt": "abcd",
                      "max_tokens": 2},
            )
            assert r.status == 200
        finally:
            await client.close()

    def test_flight_off_pins_noop_identity(self):
        """DTPU_FLIGHT=0 in a fresh process: flight.record IS the
        module no-op and an engine built disabled carries no JitWatch
        wrapper at all (the zero-cost half of the acceptance)."""
        code = (
            "from dstack_tpu.obs import flight\n"
            "assert flight.record is flight._noop_record\n"
            "import jax\n"
            "from dstack_tpu.models import llama\n"
            "from dstack_tpu.serve.engine import GenParams, "
            "InferenceEngine\n"
            "cfg = llama.LLAMA_TINY\n"
            "eng = InferenceEngine(cfg, llama.init_params(cfg, "
            "jax.random.key(0)), max_batch=2, max_seq=64)\n"
            "assert not isinstance(eng._decode, flight.JitWatch)\n"
            "eng.generate([5, 9, 21], GenParams(max_new_tokens=2))\n"
            "assert not any(isinstance(f, flight.JitWatch) "
            "for f in eng._chunk_fns.values())\n"
        )
        proc = subprocess.run(
            [sys.executable, "-c", code], cwd=REPO,
            capture_output=True, text=True, timeout=240,
            env={
                "PATH": "/usr/bin:/bin", "DTPU_FLIGHT": "0",
                "JAX_PLATFORMS": "cpu", "HOME": "/tmp",
            },
        )
        assert proc.returncode == 0, proc.stderr

    def test_no_measurable_decode_throughput_regression(self):
        """Bench half of the acceptance: the SAME warm engine decodes
        a fixed step count with the recorder off and on; the
        instrumented path must not measurably regress (generous 2x
        bound — flight writes are a few dict ops against a ~ms jit
        dispatch, and CPU CI timing is noisy)."""
        config = llama.LLAMA_TINY
        params = init_params(config, 0)
        eng = InferenceEngine(
            config, params, max_batch=2, max_seq=512,
            spec_draft=0, turbo_steps=0,
        )

        def run_steps(n):
            slot, _ = eng.add_request(
                [5, 9, 21, 7], GenParams(max_new_tokens=n + 1)
            )
            # warm the decode variant outside the timed region
            eng.step()
            t0 = time.perf_counter()
            for _ in range(n):
                eng.step()
            dt = time.perf_counter() - t0
            eng.release(slot)
            return dt

        n = 40
        run_steps(8)  # compile + cache warm
        flight.disable()
        off = min(run_steps(n) for _ in range(3))
        flight.enable(buffer=256)
        on = min(run_steps(n) for _ in range(3))
        assert on <= 2.0 * off + 0.05, (
            f"flight-on decode {on:.4f}s vs flight-off {off:.4f}s"
        )
