"""Serving benchmark: decode throughput + TTFT for the slot engine.

``python -m dstack_tpu.serve.bench --model llama-3.2-1b --batch 8``
drives the engine directly (no HTTP) and prints one JSON line:
tokens/s decode throughput across concurrent slots, per-request TTFT
through chunked prefill, and the speculative-decoding step ratio on a
repetitive workload. Run it on the target TPU to size ``--max-batch``
and ``--spec-draft`` for a service; ``--platform cpu`` runs are smoke
tests only and say ``"platform": "cpu"`` in their ``device`` block.

``--sessions N`` switches to the multi-replica chat-session workload:
N seeded multi-turn conversations from interleaved tenants are routed
across ``--replicas`` in-process engines through the REAL
:class:`~dstack_tpu.routing.pool.ReplicaPool` picker — once with
prefix-affinity routing, once with the plain least-outstanding
control — and the JSON reports warm-turn TTFT p50/p95 for both, the
speedup, prefix-hit counts, and session stickiness (serving.md §10).

Workload generation (burst prompts, repetitive phrases, session
conversations) comes from :mod:`dstack_tpu.loadgen.textgen` — ONE
seeded-workload implementation shared with the traffic-replay soak
harness (serving.md §11), so "the bench's sessions" and "the soak's
sessions" can never drift apart. Every result carries the ``device``
block of :func:`dstack_tpu.utils.backend.device_info`; the CLI needs an
accelerator unless ``--platform cpu`` is given.
"""

import argparse
import json
import time

from dstack_tpu.loadgen.report import percentile as _percentile
from dstack_tpu.loadgen.textgen import (
    conversation_texts,
    repetitive_prompts,
    token_prompts,
)
from dstack_tpu.utils.backend import (
    device_info,
    enable_compile_cache,
    select_platform,
)


def _drive_burst(eng, prompts, gen_len):
    """Admit every prompt at once (concurrent arrival), then drive
    prefill waves + decode interleaved to completion — the scheduler's
    tick pattern, minus HTTP."""
    from dstack_tpu.serve.engine import GenParams

    slots = [
        eng.start_request(list(p), GenParams(max_new_tokens=gen_len))
        for p in prompts
    ]
    while eng.prefilling_slots() or any(eng.active[s] for s in slots):
        if eng.prefilling_slots():
            eng.prefill_wave()
        if any(eng.active[s] for s in slots):
            eng.step()
    for s in slots:
        eng.release(s)


def _concurrent_arrival_bench(eng, rng, vocab, burst, prompt_len, gen_len):
    """Burst TTFT + prefill-dispatch accounting → result dict.

    Runs the SAME burst twice — packed (the engine's prefill_pack) and
    serial (prefill_pack temporarily 0) — so one JSON line shows the
    dispatch reduction and the TTFT-under-load it buys."""
    ttft_hist = eng.metrics.family("dtpu_serve_ttft_seconds")
    disp = eng.metrics.family("dtpu_serve_prefill_dispatches_total")
    prompts = token_prompts(rng, vocab, burst, prompt_len)
    pack = eng.prefill_pack

    def measure():
        eng.reset_prefix_cache()  # identical-length bursts must not hit
        ttft_hist.clear()
        d0 = disp.value()
        _drive_burst(eng, prompts, gen_len)
        return {
            "ttft_ms_p50": round((ttft_hist.quantile(0.5) or 0.0) * 1e3, 1),
            "ttft_ms_p95": round((ttft_hist.quantile(0.95) or 0.0) * 1e3, 1),
            "prefill_dispatches": int(disp.value() - d0),
        }

    # warm both paths' compile variants outside the timed bursts
    _drive_burst(eng, prompts, 2)
    eng.prefill_pack = 0
    _drive_burst(eng, prompts, 2)
    eng.prefill_pack = pack
    packed = measure()
    eng.prefill_pack = 0
    serial = measure()
    eng.prefill_pack = pack
    return {
        "burst": burst,
        "prefill_pack": pack,
        "packed": packed,
        "serial": serial,
        "dispatch_ratio": round(
            serial["prefill_dispatches"]
            / max(packed["prefill_dispatches"], 1),
            2,
        ),
    }


def run_bench(
    model: str = "llama-tiny",
    batch: int = 4,
    max_seq: int = 1024,
    prompt_len: int = 256,
    gen_len: int = 64,
    spec_draft: int = 0,
    repetitive: bool = False,
    quantize=None,
    turbo_steps: int = 8,
    turbo_depth: int = 1,
    kv_quant=None,
    prefill_chunk: int = 256,
    prefill_pack: int = 4,
    arrival_burst: int = 0,  # 0 = off; else concurrent-arrival mode size
    decode_kernel=None,  # None/"einsum" | "flash" (ragged pallas read)
) -> dict:
    """Measure the engine directly → result dict (importable core;
    the root ``bench.py`` embeds this next to the training number)."""
    import jax
    import numpy as np

    from dstack_tpu.models import llama
    from dstack_tpu.serve.engine import GenParams, InferenceEngine

    config = llama.CONFIGS[model]
    if quantize == "int8":
        # the accelerator only ever sees the quantized tree (a bf16 8B
        # tree cannot coexist with its int8 copy inside a v5e's 16 GiB
        # HBM). On an accelerator every leaf is generated device-side
        # by jitted PRNG (no ~8 GB host→device copy). The numpy host
        # path stays for CPU smoke runs (no transfer there, and it
        # dodges per-leaf compiles).
        if jax.default_backend() == "cpu":
            from dstack_tpu.models.quant import random_quantized_params

            params = jax.device_put(random_quantized_params(config))
        else:
            from dstack_tpu.models.quant import (
                random_quantized_params_on_device,
            )

            params = random_quantized_params_on_device(config)
    else:
        params = llama.init_params(config, jax.random.key(0))
    if arrival_burst and arrival_burst > batch:
        raise ValueError(
            f"--arrival-burst {arrival_burst} needs --batch >= burst "
            f"(got {batch}): the burst is admitted all at once"
        )
    eng = InferenceEngine(
        config, params, max_batch=batch, max_seq=max_seq,
        spec_draft=spec_draft, turbo_steps=turbo_steps,
        turbo_depth=turbo_depth, kv_quant=kv_quant,
        prefill_chunk=prefill_chunk, prefill_pack=prefill_pack,
        decode_kernel=decode_kernel,
    )
    rng = np.random.default_rng(0)
    if repetitive:
        prompts = repetitive_prompts(
            rng, config.vocab_size, batch, prompt_len
        )
    else:
        prompts = token_prompts(rng, config.vocab_size, batch, prompt_len)

    # warmup compiles every kernel the timed sections will hit: the
    # full-length prompt's prefill chunks, the decode path at the SAME
    # generation length (the turbo macro-step is budget-capped to
    # power-of-2 step counts, so a short warmup would leave the timed
    # loop's longer decode_loop variants uncompiled), and (with
    # --spec-draft) the speculative verify step — otherwise
    # multi-second XLA compiles land inside the TTFT/throughput numbers
    spec = eng.spec_draft
    eng.spec_draft = 0  # force the plain/turbo decode to compile
    slot, _ = eng.add_request(
        list(prompts[0]), GenParams(max_new_tokens=gen_len)
    )
    while eng.active[slot]:
        eng.step()
    eng.release(slot)
    eng.spec_draft = spec
    if spec:
        phrase = prompts[0][:16]
        warm = (phrase * (prompt_len // 16 + 1))[:prompt_len]
        slot, _ = eng.add_request(warm, GenParams(max_new_tokens=6))
        while eng.active[slot]:
            eng.step()  # repetition drafts → verify kernel compiles
        eng.release(slot)

    # cold TTFT must stay cold: the warmup request registered its
    # prompt for prefix reuse — drop it (repetitive mode's identical
    # prompts would otherwise prefix-hit and flatter the numbers)
    eng.reset_prefix_cache()

    # Timed sections read the ENGINE's own obs histograms — the same
    # series the openai_server exports from /metrics — instead of
    # bench-local stopwatches, so bench and production publish one
    # source of truth. Warmup observations are dropped first.
    ttft_hist = eng.metrics.family("dtpu_serve_ttft_seconds")
    step_hist = eng.metrics.family("dtpu_serve_decode_step_seconds")
    tok_counter = eng.metrics.family("dtpu_serve_tokens_generated_total")
    ttft_hist.clear()

    # TTFT: admission → first sampled token, per request (chunked
    # prefill) — observed inside the engine at slot activation
    slots = []
    for prompt in prompts:
        # per-admission clear: in repetitive mode requests 2..N would
        # otherwise prefix-hit against request 1's registration
        eng.reset_prefix_cache()
        slot, _ = eng.add_request(
            prompt, GenParams(max_new_tokens=gen_len)
        )
        slots.append(slot)
    assert ttft_hist.count() == len(prompts)

    # decode throughput across all concurrent slots: tokens / engine
    # step wall-time, both from the registry (histogram sum deltas)
    tokens0, secs0 = tok_counter.value(), step_hist.sum()
    t0 = time.perf_counter()
    steps = 0
    while any(eng.active[s] for s in slots):
        eng.step()
        steps += 1
    dt = time.perf_counter() - t0
    tokens = int(tok_counter.value() - tokens0)
    step_secs = step_hist.sum() - secs0
    for s in slots:
        eng.release(s)
    # snapshot the quantiles NOW: the prefix-cache section below admits
    # more requests, whose TTFT observations must not shift the p50
    ttft_ms_p50 = round((ttft_hist.quantile(0.5) or 0.0) * 1e3, 1)
    ttft_ms_p99 = round((ttft_hist.quantile(0.99) or 0.0) * 1e3, 1)

    # prefix-cache TTFT: a request sharing a long prefix with a served
    # one skips the shared chunks (chunk-aligned device copy). Prompt
    # pair at 2× prompt_len so at least one chunk is reusable.
    C = eng.prefill_chunk
    # mirror start_request's tail truncation (max_new_tokens=2 here) so
    # the precompiled copy variant matches the engine's actual reuse
    plen2 = min(2 * prompt_len, max_seq - 3)
    long_prompt = rng.integers(1, config.vocab_size, plen2).tolist()
    follow = long_prompt[:-8] + rng.integers(1, config.vocab_size, 8).tolist()
    reuse = min(plen2 - 8, len(follow) - 1) // C * C
    ttft_prefix_ms = ttft_long_cold_ms = None
    # batch 1 cannot prefix-hit: the only slot is also the source
    if reuse >= C and batch >= 2:
        import jax.numpy as jnp

        # warm the (chunk, start) prefill variants past prompt_len —
        # the earlier sections never prefilled a 2× prompt, and a cold
        # XLA compile would masquerade as prefill time
        warm = rng.integers(1, config.vocab_size, plen2).tolist()
        slot, _ = eng.add_request(warm, GenParams(max_new_tokens=2))
        while eng.active[slot]:
            eng.step()
        eng.release(slot)
        eng.reset_prefix_cache()
        ttft_hist.clear()  # isolate: the single cold sample IS the number
        slot, _ = eng.add_request(long_prompt, GenParams(max_new_tokens=2))
        ttft_long_cold_ms = round((ttft_hist.quantile(0.5) or 0.0) * 1e3, 1)
        while eng.active[slot]:
            eng.step()
        eng.release(slot)
        # compile the copy variant outside the timed window (slot 0
        # onto itself is a semantic no-op)
        eng.cache = eng.get_copy_fn(reuse)(
            eng.cache, jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32)
        )
        hits0 = eng.prefix_hits
        ttft_hist.clear()
        slot, _ = eng.add_request(follow, GenParams(max_new_tokens=2))
        ttft_prefix_ms = round((ttft_hist.quantile(0.5) or 0.0) * 1e3, 1)
        assert eng.prefix_hits == hits0 + 1, "expected a prefix hit"
        while eng.active[slot]:
            eng.step()
        eng.release(slot)

    # concurrent-arrival mode: an N-prompt burst through the packed
    # prefill wave vs serial per-prompt prefill — dispatch counts and
    # TTFT p50/p95 under load, from the engine's own histograms
    concurrent = None
    if arrival_burst:
        concurrent = _concurrent_arrival_bench(
            eng, rng, config.vocab_size, arrival_burst, prompt_len, gen_len
        )

    return {
        "metric": f"serve_decode_tokens_per_sec[{model},batch={batch}]",
        # engine-step time, not the bench loop's wall clock: the same
        # number a /metrics scrape of a production server derives
        "value": round(tokens / max(step_secs, 1e-9), 1),
        "unit": "tokens/s",
        "extra": {
            "ttft_ms_p50": ttft_ms_p50,
            "ttft_ms_p99": ttft_ms_p99,
            "wall_tokens_per_sec": round(tokens / max(dt, 1e-9), 1),
            # 2×-length prompt pair: cold full prefill vs prefix-hit
            "ttft_long_cold_ms": ttft_long_cold_ms,
            "ttft_prefix_hit_ms": ttft_prefix_ms,
            "prefix_reuse_tokens": reuse if reuse >= C else 0,
            "decode_steps": steps,
            "tokens": tokens,
            "tokens_per_step": round(tokens / max(steps, 1), 2),
            # N-prompt burst: packed vs serial prefill dispatches + TTFT
            "concurrent": concurrent,
            # the engine's EFFECTIVE pack width (power-of-2-floored,
            # capped at batch), not the raw argument
            "prefill_pack": eng.prefill_pack,
            "spec_draft": spec_draft,
            "turbo_steps": turbo_steps,
            "turbo_depth": turbo_depth,
            "quantize": quantize,
            "kv_quant": kv_quant,
            "decode_kernel": decode_kernel or "einsum",
            "device": device_info(),
        },
    }


def run_session_bench(
    model: str = "llama-tiny",
    replicas: int = 2,
    sessions: int = 6,
    turns: int = 4,
    tenants: int = 2,
    gen_len: int = 8,
    turn_chars: int = 160,
    batch: int = 8,
    max_seq: int = 2048,
    prefill_chunk: int = 64,
    seed: int = 0,
) -> dict:
    """Multi-session chat workload over ≥2 in-process replicas, routed
    by the real pool picker: prefix-affinity on vs off → result dict.

    Each session is a seeded multi-turn conversation (its own tenant,
    interleaved with the others turn by turn, assistant replies fed
    back into the history — the prompt of turn *k+1* extends turn
    *k*'s). Affinity-on routes each turn through
    ``pool.pick(affinity=...)`` exactly like the production forwarder;
    the control uses the same pool with affinity disabled (plain
    least-outstanding + round-robin ties). Warm turns (2..N) are where
    the KV either is or is not where the router sends the request —
    their TTFT p50/p95 is the headline. Both passes run once untimed
    first so XLA compiles (chunk and prefix-copy variants) never land
    in the measured numbers."""
    import jax
    import numpy as np

    from dstack_tpu.models import llama
    from dstack_tpu.proxy.model_tgi import DEFAULT_CHAT_TEMPLATE, render_chat
    from dstack_tpu.routing.affinity import AffinityConfig, request_affinity
    from dstack_tpu.routing.pool import PoolConfig, ReplicaPool
    from dstack_tpu.serve.engine import GenParams, InferenceEngine
    from dstack_tpu.serve.tokenizer import ByteTokenizer

    if replicas < 2:
        raise ValueError("--replicas must be >= 2: the point is routing")
    config = llama.CONFIGS[model]
    params = llama.init_params(config, jax.random.key(0))
    tok = ByteTokenizer()
    engines = [
        InferenceEngine(
            config, params, max_batch=batch, max_seq=max_seq,
            prefill_chunk=prefill_chunk,
        )
        for _ in range(replicas)
    ]
    pool = ReplicaPool("bench", "sessions", PoolConfig(startup_grace=0.0))
    pool.sync([(f"r{i}", "inproc", i) for i in range(replicas)])
    by_rid = {f"r{i}": engines[i] for i in range(replicas)}

    def _conversations():
        """Seeded turn texts, regenerated identically per pass — the
        loadgen generator, so bench sessions and soak sessions are the
        same workload."""
        return conversation_texts(
            np.random.default_rng(seed), sessions, turns, turn_chars
        )

    def run_pass(affinity_on: bool, timed: bool) -> dict:
        for eng in engines:
            eng.reset_prefix_cache()
        pool.affinity.clear()
        pool.affinity.config = AffinityConfig(enabled=affinity_on)
        pool._rr = 0
        convs = _conversations()
        histories = [[] for _ in range(sessions)]
        last_rid = [None] * sessions
        warm_ttft_ms, cold_ttft_ms = [], []
        sticky = moved = 0
        hits0 = {rid: e.prefix_hits for rid, e in by_rid.items()}
        # sessions arrive in a seeded-shuffled order each turn: real
        # traffic has no fixed arrival order, and a FIXED order would
        # let the control's round-robin tie-break accidentally pin
        # session s to replica s%N — a stickiness the load-only picker
        # does not actually promise
        order_rng = np.random.default_rng(seed + 1)
        for t in range(turns):
            order = list(range(sessions))
            order_rng.shuffle(order)
            for s in order:
                tenant = f"tenant-{s % tenants}"
                histories[s].append(
                    {"role": "user", "content": convs[s][t]}
                )
                key = request_affinity(
                    "chat/completions",
                    {"messages": histories[s]},
                    tenant,
                )
                entry = pool.pick(affinity=key if affinity_on else None)
                pool.affinity.record(key, entry.replica_id)
                eng = by_rid[entry.replica_id]
                prompt_ids = tok.encode(render_chat(
                    histories[s], DEFAULT_CHAT_TEMPLATE
                ))
                t0 = time.perf_counter()
                slot, first = eng.add_request(
                    prompt_ids, GenParams(max_new_tokens=gen_len)
                )
                ttft_ms = (time.perf_counter() - t0) * 1e3
                out = [first]
                while eng.active[slot]:
                    for toks in eng.step().get(slot, []):
                        out.append(toks)
                eng.release(slot)
                histories[s].append(
                    {"role": "assistant", "content": tok.decode(out)}
                )
                if timed:
                    (warm_ttft_ms if t > 0 else cold_ttft_ms).append(ttft_ms)
                    if t > 0:
                        if entry.replica_id == last_rid[s]:
                            sticky += 1
                        else:
                            moved += 1
                last_rid[s] = entry.replica_id
        if not timed:
            return {}
        warm_total = max(1, sticky + moved)
        return {
            "ttft_warm_ms_p50": round(_percentile(warm_ttft_ms, 0.5), 1),
            "ttft_warm_ms_p95": round(_percentile(warm_ttft_ms, 0.95), 1),
            "ttft_cold_ms_p50": round(_percentile(cold_ttft_ms, 0.5), 1),
            "prefix_hits": sum(
                e.prefix_hits - hits0[rid] for rid, e in by_rid.items()
            ),
            "same_replica_rate": round(sticky / warm_total, 3),
        }

    results = {}
    for name, on in (("affinity_on", True), ("affinity_off", False)):
        run_pass(on, timed=False)  # compile warm-up, identical schedule
        results[name] = run_pass(on, timed=True)
    on, off = results["affinity_on"], results["affinity_off"]
    return {
        "metric": f"serve_session_ttft_warm_ms[{model},replicas={replicas}]",
        "value": on["ttft_warm_ms_p50"],
        "unit": "ms",
        "extra": {
            **results,
            "warm_ttft_speedup_p50": round(
                off["ttft_warm_ms_p50"] / max(on["ttft_warm_ms_p50"], 1e-9), 2
            ),
            "sessions": sessions,
            "turns": turns,
            "tenants": tenants,
            "replicas": replicas,
            "gen_len": gen_len,
            "turn_chars": turn_chars,
            "prefill_chunk": prefill_chunk,
            "seed": seed,
            "device": device_info(),
        },
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="llama-tiny")
    p.add_argument("--batch", type=int, default=4, help="concurrent slots")
    p.add_argument("--max-seq", type=int, default=1024)
    p.add_argument("--prompt-len", type=int, default=256)
    p.add_argument("--gen-len", type=int, default=64)
    p.add_argument("--spec-draft", type=int, default=0)
    p.add_argument(
        "--repetitive", action="store_true",
        help="tile a short phrase as the prompt (RAG/summarization-like "
             "repetition where prompt-lookup speculation pays off); "
             "random prompts measure the no-speculation floor",
    )
    p.add_argument("--quantize", default=None, choices=["int8"])
    p.add_argument(
        "--kv-quant", default=None, choices=["int8"],
        help="int8 KV cache (halves decode cache HBM traffic)",
    )
    p.add_argument(
        "--turbo-steps", type=int, default=8,
        help="device-side decode steps per dispatch (0/1 = per-token)",
    )
    p.add_argument(
        "--turbo-depth", type=int, default=1,
        help="macro-steps kept in flight per host round trip (pipelined "
             "turbo; >1 amortizes the host↔device round trip)",
    )
    p.add_argument(
        "--prefill-chunk", type=int, default=256,
        help="prefill chunk length (prefix reuse is chunk-granular)",
    )
    p.add_argument(
        "--prefill-pack", type=int, default=4,
        help="max prompt chunks packed into one prefill dispatch "
             "(0/1 = serial per-prompt prefill)",
    )
    p.add_argument(
        "--arrival-burst", type=int, default=0,
        help="concurrent-arrival mode: admit this many prompts at once "
             "and report packed-vs-serial prefill dispatch counts and "
             "TTFT p50/p95 under load (requires --batch >= burst)",
    )
    p.add_argument(
        "--decode-kernel", default=None, choices=["einsum", "flash"],
        help="decode attention path: masked einsum (default) or the "
             "ragged pallas kernel (each slot reads only its own "
             "cache prefix)",
    )
    p.add_argument(
        "--sessions", type=int, default=0,
        help="multi-session chat-workload mode: route this many seeded "
             "multi-turn conversations across --replicas engines via "
             "the real pool picker and report warm-turn TTFT with "
             "prefix-affinity routing on vs off (0 = regular bench)",
    )
    p.add_argument(
        "--replicas", type=int, default=2,
        help="in-process replicas for --sessions mode (>= 2)",
    )
    p.add_argument(
        "--turns", type=int, default=4,
        help="turns per conversation in --sessions mode",
    )
    p.add_argument(
        "--tenants", type=int, default=2,
        help="tenant identities the sessions interleave across "
             "(the affinity session key is tenant-scoped)",
    )
    p.add_argument(
        "--turn-chars", type=int, default=160,
        help="approximate user-message length per turn (--sessions)",
    )
    p.add_argument(
        "--output", default=None,
        help="also write the result JSON to this file (e.g. "
             "BENCH_r06.json)",
    )
    p.add_argument(
        "--platform", default=None,
        help="cpu for a smoke run; without it the bench needs an "
             "accelerator and exits non-zero when there is none",
    )
    args = p.parse_args(argv)

    select_platform(args.platform)
    enable_compile_cache()

    def emit(result: dict) -> int:
        line = json.dumps(result)
        print(line)
        if args.output:
            with open(args.output, "w") as f:
                f.write(line + "\n")
        return 0

    if args.sessions:
        return emit(run_session_bench(
            model=args.model,
            replicas=args.replicas,
            sessions=args.sessions,
            turns=args.turns,
            tenants=args.tenants,
            gen_len=args.gen_len,
            turn_chars=args.turn_chars,
            batch=args.batch,
            max_seq=args.max_seq,
            prefill_chunk=args.prefill_chunk,
        ))

    result = run_bench(
        model=args.model,
        batch=args.batch,
        max_seq=args.max_seq,
        prompt_len=args.prompt_len,
        gen_len=args.gen_len,
        spec_draft=args.spec_draft,
        repetitive=args.repetitive,
        quantize=args.quantize,
        turbo_steps=args.turbo_steps,
        turbo_depth=args.turbo_depth,
        kv_quant=args.kv_quant,
        decode_kernel=args.decode_kernel,
        prefill_chunk=args.prefill_chunk,
        prefill_pack=args.prefill_pack,
        arrival_burst=args.arrival_burst,
    )
    return emit(result)


if __name__ == "__main__":
    import sys

    sys.exit(main())
