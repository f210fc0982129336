"""MFU sweep: find the best single-chip train-step configuration fast.

Measures tokens/s/chip + MFU for a grid of (batch, seq, loss_impl,
remat) on the flagship model in ONE process on the chip, prints a
table, and names the winner — the numbers `bench.py` should then pin.
MFU is against the published peak of the device the sweep runs on
(train/step.py DEVICE_PEAKS); without an accelerator it exits non-zero.

Usage:
  python tools/mfu_sweep.py
"""

import argparse
import dataclasses
import itertools
import json
import os
import statistics
import sys
import time

# runnable as `python tools/mfu_sweep.py` from the repo root
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def measure(config, batch, seq, loss_impl, remat, steps, peak_flops):
    import jax
    import jax.numpy as jnp

    from dstack_tpu.parallel.mesh import MeshConfig, make_mesh
    from dstack_tpu.train.step import (
        default_optimizer,
        flops_per_token,
        make_train_step,
        sharded_init,
    )

    cfg = dataclasses.replace(config, remat=remat)
    mesh = make_mesh(
        MeshConfig(dp=1, fsdp=1, sp=1, tp=1), devices=jax.devices()[:1]
    )
    opt = default_optimizer(lr=1e-4)
    state, _ = sharded_init(cfg, opt, mesh, seed=0)
    step_fn = make_train_step(cfg, opt, mesh, loss_impl=loss_impl)
    tokens = jax.random.randint(jax.random.key(1), (batch, seq), 0, cfg.vocab_size)
    data = {
        "tokens": tokens,
        "targets": jnp.roll(tokens, -1, axis=1),
        "mask": jnp.ones_like(tokens),
    }

    sync = jax.block_until_ready
    t_compile = time.perf_counter()
    state, m = step_fn(state, data)
    sync(m["loss"])
    compile_s = time.perf_counter() - t_compile
    state, m = step_fn(state, data)
    sync(m["loss"])
    inner = 1 if steps <= 3 else 5
    times = []
    for _ in range(max(steps // inner, 3)):
        t0 = time.perf_counter()
        for _ in range(inner):
            state, m = step_fn(state, data)
        sync(m["loss"])
        times.append((time.perf_counter() - t0) / inner)
    dt = statistics.median(times)
    tps = batch * seq / dt
    mfu = tps * flops_per_token(cfg, seq) / peak_flops
    # free everything before the next grid point
    del state, m, data, step_fn, opt
    jax.clear_caches()
    return {
        "batch": batch, "seq": seq, "loss_impl": loss_impl, "remat": remat,
        "tok_s": round(tps, 1), "mfu": round(mfu, 4),
        "step_s": round(dt, 4), "compile_s": round(compile_s, 1),
    }


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="llama-3.2-1b")
    p.add_argument("--quick", action="store_true")
    p.add_argument("--batches", default="4,8,16", help="comma list")
    p.add_argument("--seqs", default="1024,2048", help="comma list")
    args = p.parse_args()

    from dstack_tpu.models import llama
    from dstack_tpu.train.step import peak_flops
    from dstack_tpu.utils.backend import enable_compile_cache, select_platform

    device = select_platform(None)  # no accelerator → SystemExit
    enable_compile_cache()
    peak = peak_flops(device["kind"])
    model = args.model
    config = llama.CONFIGS[model]
    batches = [int(x) for x in args.batches.split(",")]
    seqs = [int(x) for x in args.seqs.split(",")]
    steps = 10 if args.quick else 20
    grid = [
        (b, s, li, rm)
        for (b, s), li, rm in itertools.product(
            itertools.product(batches, seqs),
            ("fused", "chunked"),
            (True, False),
        )
    ]

    results = []
    for b, s, li, rm in grid:
        try:
            r = measure(config, b, s, li, rm, steps, peak)
        except Exception as e:  # OOM configs report and move on
            r = {
                "batch": b, "seq": s, "loss_impl": li, "remat": rm,
                "error": f"{type(e).__name__}: {str(e)[:120]}",
            }
        results.append(r)
        print(json.dumps(r), flush=True)

    ok = [r for r in results if "mfu" in r]
    if ok:
        best = max(ok, key=lambda r: r["mfu"])
        print(json.dumps({"best": best, "model": model, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
