"""Headline benchmark: train-step tokens/sec/chip on the flagship model.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "tokens/s/chip", "vs_baseline": N,
   "device": {"platform", "kind", "count"}, "extra": {...}}

The reference publishes no framework perf numbers (BASELINE.md), so
``vs_baseline`` is hardware-normalized: measured model-FLOPs utilization
(MFU, against the published peak of the device the run is on) divided
by a 0.40 MFU target — the level a well-tuned production JAX stack
reaches on this class of model. >1.0 beats that bar.

One process, on the chip. Without an accelerator — or on one whose peak
``train/step.py`` does not list — it exits non-zero with the reason and
prints no number. (Its rewrite into per-cell workloads is ROADMAP A1.)
"""

import json
import os
import statistics
import sys
import time


def train_bench(
    config=None,
    batch: int = 8,
    seq: int = 1024,
    steps: int = 20,
    opt_bits: int = 32,
    grad_accum: int = 1,
    loss_impl: str = "fused",
) -> dict:
    """One parameterized train-step measurement (used by the headline
    bench AND tools/roofline_levers.py's lever sweep). ``batch`` is the
    TOTAL batch; with ``grad_accum > 1`` each microbatch is
    batch/grad_accum and one optimizer update covers the whole batch."""
    import jax
    import jax.numpy as jnp

    from dstack_tpu.models import llama
    from dstack_tpu.parallel.mesh import MeshConfig, make_mesh
    from dstack_tpu.train.step import (
        default_optimizer,
        flops_per_token,
        make_train_step,
        peak_flops,
        sharded_init,
    )

    peak = peak_flops(jax.devices()[0].device_kind)
    config = config or llama.LLAMA_32_1B
    mesh = make_mesh(
        MeshConfig(dp=1, fsdp=1, sp=1, tp=1), devices=jax.devices()[:1]
    )
    opt = default_optimizer(lr=1e-4, opt_bits=opt_bits)
    state, _ = sharded_init(config, opt, mesh, seed=0)
    step_fn = make_train_step(
        config, opt, mesh, grad_accum=grad_accum, loss_impl=loss_impl
    )

    tokens = jax.random.randint(jax.random.key(1), (batch, seq), 0, config.vocab_size)
    data = {
        "tokens": tokens,
        "targets": jnp.roll(tokens, -1, axis=1),
        "mask": jnp.ones_like(tokens),
    }

    # warmup / compile
    for _ in range(2):
        state, m = step_fn(state, data)
        jax.block_until_ready(m["loss"])

    # Steady-state timing: `inner` dependent steps per host sync, like a
    # training loop that logs every N steps.
    inner = 1 if steps <= 3 else 5
    times = []
    for _ in range(max(steps // inner, 3)):
        t0 = time.perf_counter()
        for _ in range(inner):
            state, m = step_fn(state, data)
        jax.block_until_ready(m["loss"])
        times.append((time.perf_counter() - t0) / inner)

    dt = statistics.median(times)
    tokens_per_sec = batch * seq / dt
    mfu = tokens_per_sec * flops_per_token(config, seq) / peak
    loss = round(float(jax.device_get(m["loss"])), 4)
    del state, m, data, step_fn, opt
    jax.clear_caches()
    return {
        "tokens_per_sec": tokens_per_sec,
        "mfu": mfu,
        "step_time_s": dt,
        "loss": loss,
        "batch": batch,
        "seq": seq,
        "opt_bits": opt_bits,
        "grad_accum": grad_accum,
        "loss_impl": loss_impl,
    }


def _bench(quick: bool = False) -> dict:
    from dstack_tpu.models import llama
    from dstack_tpu.serve.bench import run_bench as serve_bench
    from dstack_tpu.utils.backend import enable_compile_cache, select_platform

    device = select_platform(None)  # no accelerator → SystemExit
    enable_compile_cache()
    config = llama.LLAMA_32_1B
    # batch 8 × seq 1024 is the shape that fits one 16 GB chip with f32
    # Adam state (batch 16 needs int8 state: DTPU_BENCH_* knobs +
    # tools/roofline_levers.py)
    batch, seq = 8, 1024
    steps = 10 if quick else 20

    # roofline-lever knobs (official variants; the headline default
    # stays the accum=1/f32 per-step measurement)
    batch = int(os.environ.get("DTPU_BENCH_BATCH", batch))
    opt_bits = int(os.environ.get("DTPU_BENCH_OPT_BITS", "32"))
    grad_accum = int(os.environ.get("DTPU_BENCH_GRAD_ACCUM", "1"))
    loss_impl = os.environ.get("DTPU_BENCH_LOSS_IMPL", "fused")

    t = train_bench(
        config=config, batch=batch, seq=seq, steps=steps,
        opt_bits=opt_bits, grad_accum=grad_accum, loss_impl=loss_impl,
    )
    # serving measurement (decode tok/s + TTFT) rides along in extra —
    # the driver records ONE line, so both numbers live on it. The
    # training state (params + Adam moments, ~15GB f32 for the 1B
    # model) was freed by train_bench or the serving engine's second
    # param copy + KV cache OOMs a 16GB v5e chip.
    serve_model = "llama-3.2-1b"
    serve = serve_bench(
        model=serve_model, batch=16, max_seq=1024,
        prompt_len=256, gen_len=64 if quick else 128,
        turbo_steps=128,
        turbo_depth=int(os.environ.get("DTPU_BENCH_TURBO_DEPTH", "1")),
    )
    return {
        "metric": (
            f"train_tokens_per_sec_per_chip[{_config_name(config)},bf16,"
            f"{device['platform']}]"
        ),
        "value": round(t["tokens_per_sec"], 1),  # one chip
        "unit": "tokens/s/chip",
        "vs_baseline": round(t["mfu"] / 0.40, 3),
        "device": device,
        "extra": {
            "mfu": round(t["mfu"], 4),
            "step_time_s": round(t["step_time_s"], 4),
            "batch": batch,
            "seq": seq,
            "loss": t["loss"],
            "params_b": round(config.num_params() / 1e9, 3),
            "opt_bits": opt_bits,
            "grad_accum": grad_accum,
            "serve": {
                "decode_tokens_per_sec": serve["value"],
                "ttft_ms_p50": serve["extra"]["ttft_ms_p50"],
                # prefix caching: 2×-length prompt pair, cold vs hit
                "ttft_long_cold_ms": serve["extra"].get("ttft_long_cold_ms"),
                "ttft_prefix_hit_ms": serve["extra"].get("ttft_prefix_hit_ms"),
                "model": serve_model,
            },
        },
    }


def _config_name(config) -> str:
    from dstack_tpu.models import llama

    for name, c in llama.CONFIGS.items():
        if c == config:
            return name
    return "custom"


def main() -> None:
    print(json.dumps(_bench(quick="--quick" in sys.argv)))


if __name__ == "__main__":
    main()
