"""Serving latency under load: TTFT / inter-token latency vs concurrency.

An aggregate decode rate says nothing about what a single request
experiences when it arrives mid-macro-step. This harness
drives the FULL serving stack (OpenAI HTTP app → Scheduler → engine)
with C concurrent streaming clients and reports per-request TTFT and
inter-token gaps, for turbo K ∈ {1, 8, 32, 128} with the adaptive-K
policy on (default) or pinned off (``--no-adaptive`` sets
``turbo_quiet_s=0`` and pre-ramps K to the max so the old fixed-K
behavior is measurable).

Run on the target TPU for real numbers::

    python tools/latency_bench.py --model llama-3.2-1b --batch 16 \
        --concurrency 1 4 16 32 --turbo 1 8 32 128

``--platform cpu`` runs (llama-tiny) are smoke tests of the harness
itself; without it the harness needs the chip. Prints one JSON line per
(concurrency, turbo) cell, each naming the device it ran on.
"""

import argparse
import asyncio
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def _pct(xs, q):
    if not xs:
        return None
    xs = sorted(xs)
    i = min(len(xs) - 1, int(round(q * (len(xs) - 1))))
    return xs[i]


async def _one_client(client, prompt: str, gen_len: int) -> dict:
    """One streaming chat request → timing record."""
    t0 = time.perf_counter()
    times = []
    async with client.post(
        "/v1/chat/completions",
        json={
            "messages": [{"role": "user", "content": prompt}],
            "max_tokens": gen_len,
            "stream": True,
            "temperature": 0,
        },
    ) as resp:
        assert resp.status == 200, await resp.text()
        async for raw in resp.content:
            line = raw.decode().strip()
            if not line.startswith("data: ") or line == "data: [DONE]":
                continue
            obj = json.loads(line[len("data: "):])
            delta = obj.get("choices", [{}])[0].get("delta", {})
            if delta.get("content"):
                times.append(time.perf_counter())
    if not times:
        return {"ttft_ms": None, "itl_ms": [], "tokens": 0}
    return {
        "ttft_ms": (times[0] - t0) * 1e3,
        # chunk gaps approximate ITL (a chunk may carry >1 token under
        # turbo; that IS the latency a client sees)
        "itl_ms": [
            (b - a) * 1e3 for a, b in zip(times, times[1:])
        ],
        "tokens": len(times),
    }


async def bench_cell(
    make_engine, tokenizer, concurrency: int, turbo: int,
    n_requests: int, prompt_len: int, gen_len: int, adaptive: bool,
) -> dict:
    from aiohttp.test_utils import TestClient, TestServer

    from dstack_tpu.serve.openai_server import build_app

    engine = make_engine(turbo, adaptive)
    app = build_app(engine, tokenizer, "bench")
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        base = "lorem ipsum dolor sit amet " * (prompt_len // 27 + 1)
        # warmup: compile prefill chunks + every decode_loop K-variant
        # the adaptive ramp can reach, outside the timed window
        await _one_client(client, base[:prompt_len] + "req9", gen_len)
        await _one_client(client, base[:prompt_len] + "req8", gen_len)
        t0 = time.perf_counter()
        sem = asyncio.Semaphore(concurrency)
        results = []

        async def worker(i: int):
            async with sem:
                # distinct prompt tails avoid prefix-cache hits
                # flattering TTFT
                # fixed-width suffix: constant token length across
                # requests, so the last prefill chunk's (len, start)
                # variant compiles once in warmup, not per request
                r = await _one_client(
                    client, f"{base[:prompt_len]}req{i % 10}", gen_len
                )
                results.append(r)

        await asyncio.gather(*(worker(i) for i in range(n_requests)))
        wall = time.perf_counter() - t0
    finally:
        await client.close()
    ttfts = [r["ttft_ms"] for r in results if r["ttft_ms"] is not None]
    itls = [g for r in results for g in r["itl_ms"]]
    toks = sum(r["tokens"] for r in results)
    return {
        "metric": "serve_latency_under_load",
        "concurrency": concurrency,
        "turbo": turbo,
        "adaptive_k": adaptive,
        "requests": n_requests,
        "ttft_ms_p50": round(_pct(ttfts, 0.5), 1) if ttfts else None,
        "ttft_ms_p99": round(_pct(ttfts, 0.99), 1) if ttfts else None,
        "itl_ms_p50": round(_pct(itls, 0.5), 1) if itls else None,
        "itl_ms_p99": round(_pct(itls, 0.99), 1) if itls else None,
        "throughput_tok_s": round(toks / wall, 1),
        "wall_s": round(wall, 1),
    }


async def main_async(args) -> int:
    import jax

    from dstack_tpu.models import llama
    from dstack_tpu.serve.engine import InferenceEngine
    from dstack_tpu.serve.tokenizer import ByteTokenizer
    from dstack_tpu.utils.backend import enable_compile_cache, select_platform

    device = select_platform(args.platform)
    enable_compile_cache()

    config = llama.CONFIGS[args.model]
    params = llama.init_params(config, jax.random.key(0))
    if args.quantize == "int8":
        from dstack_tpu.models.quant import quantize_tree

        params = quantize_tree(params, config)

    def make_engine(turbo, adaptive):
        eng = InferenceEngine(
            config, params, max_batch=args.batch, max_seq=args.max_seq,
            spec_draft=0, turbo_steps=turbo, kv_quant=args.kv_quant,
            turbo_quiet_s=0.5 if adaptive else 0.0,
            # near-identical bench prompts would prefix-hit and skip
            # prefill — this bench measures the COLD path
            prefix_cache=False,
        )
        if not adaptive:
            eng._turbo_k = max(turbo, 1)  # pre-ramped: fixed-K baseline
            eng.waiting_requests = 0
            # keep it pinned: quiet window 0 and no snap-back floor
            eng._adaptive_turbo_cap = lambda: max(turbo, 1)  # type: ignore
        return eng

    tokenizer = ByteTokenizer()
    for concurrency in args.concurrency:
        for turbo in args.turbo:
            cell = await bench_cell(
                make_engine, tokenizer, concurrency, turbo,
                n_requests=args.requests or concurrency * 3,
                prompt_len=args.prompt_len, gen_len=args.gen_len,
                adaptive=not args.no_adaptive,
            )
            cell["model"] = args.model
            cell["device"] = device
            print(json.dumps(cell), flush=True)
    return 0


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="llama-tiny")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--max-seq", type=int, default=1024)
    p.add_argument("--prompt-len", type=int, default=256)
    p.add_argument("--gen-len", type=int, default=64)
    p.add_argument("--requests", type=int, default=0,
                   help="total requests per cell (default 3x concurrency)")
    p.add_argument("--concurrency", type=int, nargs="+", default=[1, 4])
    p.add_argument("--turbo", type=int, nargs="+", default=[1, 8])
    p.add_argument("--quantize", default=None, choices=["int8"])
    p.add_argument("--kv-quant", default=None, choices=["int8"])
    p.add_argument("--no-adaptive", action="store_true")
    p.add_argument("--platform", default=None)
    args = p.parse_args()
    return asyncio.run(main_async(args))


if __name__ == "__main__":
    sys.exit(main())
