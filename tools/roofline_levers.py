"""Measure the roofline levers from docs/guides/perf-roofline.md.

The levers (8-bit optimizer state, grad accumulation, the batch size
the f32-Adam OOM wall forbade) were analyzed, not measured. This sweep
runs each variant of the 1B train bench on the chip and prints one JSON
line per variant. Each variant is its own child process, one after the
other, so an OOM variant doesn't sink the sweep; the parent never
imports jax (the chip belongs to one process at a time). Without an
accelerator every child exits non-zero and says why.

Variants (all Llama-3.2-1B, seq 1024, single chip):
  base        batch 8,  f32 Adam, accum 1   — round-3 headline config
  opt8        batch 8,  int8 Adam, accum 1  — halves the optimizer tail
  opt8-b16    batch 16, int8 Adam, accum 1  — the freed ~7.4 GB buys 2x batch
  opt8-accum  batch 32, int8 Adam, accum 4  — amortizes the update 4x
              (microbatch 8 keeps the matmul M; chunked CE keeps logits
              HBM at one chunk so the bigger batch fits)
"""

import json
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]

VARIANTS = [
    ("base", dict(batch=8, opt_bits=32, grad_accum=1, loss_impl="fused")),
    ("opt8", dict(batch=8, opt_bits=8, grad_accum=1, loss_impl="fused")),
    ("opt8-b16", dict(batch=16, opt_bits=8, grad_accum=1, loss_impl="fused")),
    ("opt8-accum", dict(batch=32, opt_bits=8, grad_accum=4, loss_impl="chunked")),
]

CHILD = """
import json, sys
from bench import train_bench
from dstack_tpu.utils.backend import enable_compile_cache, select_platform
device = select_platform(None)  # no accelerator -> SystemExit
enable_compile_cache()
r = train_bench(seq=1024, steps=10, **json.loads(sys.argv[1]))
r["device"] = device
print(json.dumps(r))
"""


def main() -> int:
    measured = 0
    for name, spec in VARIANTS:
        t0 = time.time()
        try:
            proc = subprocess.run(
                [sys.executable, "-c", CHILD, json.dumps(spec)],
                cwd=REPO, timeout=1500, capture_output=True, text=True,
            )
        except subprocess.TimeoutExpired:
            print(json.dumps({"variant": name, "error": "timeout 1500s"}))
            continue
        lines = [l for l in proc.stdout.strip().splitlines() if l.startswith("{")]
        if proc.returncode != 0 or not lines:
            print(json.dumps({
                "variant": name,
                "error": (proc.stderr or proc.stdout).strip()[-300:],
            }))
            continue
        out = json.loads(lines[-1])
        out["variant"] = name
        out["wall_s"] = round(time.time() - t0, 1)
        for k in ("mfu", "step_time_s", "tokens_per_sec"):
            if k in out:
                out[k] = round(out[k], 4)
        print(json.dumps(out), flush=True)
        measured += 1
    # an OOM variant is a result; a sweep that measured nothing is not
    return 0 if measured else 1


if __name__ == "__main__":
    sys.exit(main())
