"""The benchmark's children: everything that touches JAX runs here, one
process after the other, while the parent (``run.py``) stays off JAX.

- ``serve``: registers the cell's configuration under its name in
  ``llama.CONFIGS``, hands the program the benchmark's seeded weights in
  place of its random init, follows the program's warm-up with the
  cell's own grid (``warm_grid``), and calls ``openai_server.main`` with
  the configuration file's flags: the real entry point, scheduler,
  engine, warm-up and HTTP path. Nothing in the program is edited. When
  the server has shut down (SIGTERM) it writes ``device.json``.
- ``check``: after the server has gone, makes the same weights again and
  runs the plain reference over served prompts and tokens.
- ``trace``: reduces a profiler capture (CPU-only child; reading the
  file needs ``jax.profiler``).
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: renders a request as its content alone, so a prompt of n words is n tokens
CHAT_TEMPLATE = "{% for m in messages %}{{ m['content'] }}{% endfor %}"


def compile_cache_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` where the machine sets it, else one
    fixed path inside the checkout (the program's own default)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_compile_cache"
    )


def _select_platform(platform, chips: int) -> None:
    """Initialise the backend and refuse the CPU unless asked for by
    name, and fewer chips than the cell needs."""
    import jax

    if platform:
        jax.config.update("jax_platforms", platform)
    devices = jax.devices()
    if not platform and devices[0].platform == "cpu":
        raise SystemExit("no accelerator: jax.devices() found only the CPU")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chips, jax found {len(devices)}")
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def device_block(chips: int) -> dict:
    import jax

    devices = jax.devices()[:chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max((p for p in peaks if p is not None), default=None),
    }


def build_llama_config(llama_config: dict):
    import jax.numpy as jnp
    from dstack_tpu.models import llama

    kw = dict(llama_config)
    kw["dtype"] = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        kw.get("dtype", "bfloat16")
    ]

    def frozen(v):  # a per-layer pattern of pairs is a list of lists in JSON
        return tuple(frozen(x) for x in v) if isinstance(v, list) else v

    return llama.LlamaConfig(**{k: frozen(v) for k, v in kw.items()})


def _pow2_at_least(n: int) -> int:
    p = 16
    while p < n:
        p *= 2
    return p


def warm_grid(engine, prompt_tokens, temperature: float) -> None:
    """After the program's own warm-up, before the port opens: compile
    every variant this cell's traffic can reach and the program's grid
    leaves out, through the same engine calls the program's
    ``_warmup_engine`` uses. The engine buckets a prompt's length and a
    chunk's length by powers of two from 16, a serial chunk by its
    start, and a packed wave by (rows, chunk bucket); what packs with
    what depends on arrival timing, so the grid is driven here and not
    over HTTP. ``prompt_tokens`` is the mix's ``[lo, hi]``; with a
    ``temperature`` above 0 the requests alternate sampled and greedy,
    so mixed batches compile too."""
    from dstack_tpu.serve.engine import GenParams

    lo, hi = prompt_tokens
    chunk = engine.prefill_chunk
    n = 0

    def gen():
        nonlocal n
        n += 1
        if temperature > 0 and n % 2:
            return GenParams(max_new_tokens=3, temperature=temperature, seed=n)
        return GenParams(max_new_tokens=3)

    def drain(slots):
        while any(engine.active[s] for s in slots):
            engine.step()
        for s in slots:
            engine.release(s)

    def prompt(length):
        return [(i % 251) + 1 for i in range(length)]

    # serial: one prompt in every length bucket (a prompt longer than a
    # chunk walks every chunk start below it)
    p = _pow2_at_least(lo)
    while True:
        slot, _ = engine.add_request(prompt(min(p, hi)), gen())
        drain([slot])
        if p >= hi:
            break
        p *= 2
    # packed: every (rows, chunk bucket); the last chunk of a prompt
    # longer than a chunk can be of any length
    c = 16 if hi > chunk else min(_pow2_at_least(lo), chunk)
    while c <= min(_pow2_at_least(hi), chunk):
        g = 2
        while g <= engine.prefill_pack and g <= engine.max_batch:
            slots = [engine.start_request(prompt(c), gen()) for _ in range(g)]
            pending = set(slots)
            while pending:
                pending -= set(engine.prefill_wave())
            drain(slots)
            g *= 2
        c *= 2
    if engine.spec_draft:
        # the speculative verify step: a lone greedy request drafts once
        # the bigram (its prompt's last token, its first token) stands
        # earlier in its prompt. The first token is found by asking,
        # then the prompt is rebuilt around it until it holds still.
        base, first = prompt(48), None
        for _ in range(4):
            head = [] if first is None else [base[-1], first]
            slot, tok = engine.add_request(head + base, GenParams(max_new_tokens=4))
            drain([slot])
            if tok == first:
                break
            first = tok
    engine.reset_prefix_cache()
    engine.mark_flight_warm()


def serve(args) -> int:
    with open(args.config) as f:
        cfg = json.load(f)
    _select_platform(args.platform, args.chips)

    from dstack_tpu.models import llama
    from dstack_tpu.serve import engine as engine_mod
    from dstack_tpu.serve import openai_server

    from benchmark import weights

    # the names this launcher replaces or calls: a program that has
    # moved them must stop the run here, not change what it measures
    for owner, names in (
        (llama, ("CONFIGS", "init_params")),
        (openai_server, ("_warmup_engine", "main")),
        (engine_mod.InferenceEngine, (
            "add_request", "start_request", "prefill_wave", "step", "release",
            "reset_prefix_cache", "mark_flight_warm",
        )),
    ):
        for n in names:
            if not hasattr(owner, n):
                raise SystemExit(f"the program no longer has {owner.__name__}.{n}")

    name = cfg["name"]
    llama.CONFIGS[name] = build_llama_config(cfg["llama_config"])
    took_weights = []

    def seeded_params(config, key):
        took_weights.append(True)
        return weights.make_params(cfg, args.seed)

    llama.init_params = seeded_params
    program_warmup = openai_server._warmup_engine
    warm = json.loads(args.warm_traffic)

    def warmup(engine):
        if not took_weights:
            raise SystemExit("the server did not take the benchmark's seeded weights")
        program_warmup(engine)
        warm_grid(engine, warm["prompt_tokens"], float(warm["temperature"]))
        # the parent looks for this before it sends anything
        with open(os.path.join(args.out, "warm.json"), "w") as f:
            json.dump({"manifest": len(engine.compile_manifest())}, f)

    openai_server._warmup_engine = warmup
    argv = [
        "--model", name, "--port", str(args.port), "--tp", str(args.chips),
        "--tokenizer", args.tokenizer, "--chat-template", CHAT_TEMPLATE,
        *cfg["serve_flags"],
    ]
    if args.quantize:
        argv += ["--quantize", args.quantize]  # the control: the program's own lower precision
    if args.platform:
        argv += ["--platform", args.platform]
    try:
        rc = openai_server.main(argv)
    finally:
        # the peak is the served process's own: written once it has shut down
        with open(os.path.join(args.out, "device.json"), "w") as f:
            json.dump(device_block(args.chips), f)
    return rc or 0


def check(args) -> int:
    with open(args.config) as f:
        cfg = json.load(f)
    _select_platform(args.platform, args.chips)

    from benchmark.reference import check as ref_check

    with open(args.requests) as f:
        requests = json.load(f)
    out = ref_check.run(cfg, args.seed, requests, control=args.control)
    with open(args.result, "w") as f:
        json.dump(out, f)
    return 0


def trace(args) -> int:
    from benchmark.trace import reduce as trace_reduce

    out = trace_reduce.reduce_dir(args.dir, chips=args.chips)
    with open(args.result, "w") as f:
        json.dump(out, f)
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    sub = p.add_subparsers(dest="mode", required=True)
    for mode in ("serve", "check", "trace"):
        s = sub.add_parser(mode)
        s.add_argument("--chips", type=int, default=1)
        s.add_argument("--platform", default=None)
        if mode != "trace":
            s.add_argument("--config", required=True)
            s.add_argument("--seed", type=int, required=True)
    s = sub.choices["serve"]
    s.add_argument("--port", type=int, required=True)
    s.add_argument("--tokenizer", required=True)
    s.add_argument("--out", required=True)
    s.add_argument("--warm-traffic", required=True,
                   help='JSON {"prompt_tokens": [lo, hi], "temperature": t} of the cell\'s mix')
    s.add_argument("--quantize", default=None, choices=["int8"])
    s = sub.choices["check"]
    s.add_argument("--requests", required=True)
    s.add_argument("--result", required=True)
    s.add_argument("--control", default=None, choices=["int8"])
    s = sub.choices["trace"]
    s.add_argument("--dir", required=True)
    s.add_argument("--result", required=True)
    args = p.parse_args(argv)
    return {"serve": serve, "check": check, "trace": trace}[args.mode](args)


if __name__ == "__main__":
    sys.exit(main())
