"""A/B the serving decode attention paths on the real chip: masked
einsum (reads the full ``Tmax`` cache row per slot per step) vs the
ragged pallas kernel (``ops/flash_decode`` — each slot reads only the
blocks covering its own length).

One JSON line per (kernel, config) cell, via the serve bench's own
measurement loop so the numbers are directly comparable with the other
serving evidence. The configs bracket the regimes the kernel targets:
the headline serve shape (short context fully written — parity check:
ragged ≈ full there), and a long-max_seq short-prompt shape where most
of every cache row is unwritten (ragged should win on HBM traffic).
"""

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> int:
    # --cpu-smoke: interpret-mode control-flow check, not a measurement;
    # without it the A/B needs the chip and exits non-zero when absent
    smoke = "--cpu-smoke" in sys.argv
    from dstack_tpu.serve.bench import run_bench
    from dstack_tpu.utils.backend import enable_compile_cache, select_platform

    select_platform("cpu" if smoke else None)
    enable_compile_cache()
    # the head_dim-64 tiny is the smallest kernel-eligible preset
    model = "llama-tiny-64" if smoke else "llama-3.2-1b"
    cells = (
        # (batch, max_seq, prompt_len, gen_len, turbo)
        [(2, 256, 32, 8, 4)] if smoke else [
            (16, 1024, 256, 64, 128),  # headline serve shape
            (8, 2048, 256, 64, 128),  # long rows, short prompts: ragged regime
        ]
    )
    for batch, max_seq, plen, glen, turbo in cells:
        for kernel in ("einsum", "flash"):
            try:
                r = run_bench(
                    model=model, batch=batch, max_seq=max_seq,
                    prompt_len=plen, gen_len=glen, spec_draft=0,
                    turbo_steps=turbo, kv_quant="int8",
                    decode_kernel=kernel,
                )
            except ValueError as e:  # unsupported shape → record, move on
                print(json.dumps({"decode_kernel": kernel, "error": str(e)}))
                continue
            r["extra"]["max_seq"] = max_seq
            r["extra"]["prompt_len"] = plen
            print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
