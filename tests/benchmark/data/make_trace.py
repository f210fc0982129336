"""How ``tests/benchmark/data/v5e_small.xplane.pb`` was recorded (on the
chip, PR 23): two jitted programs, five rounds, a host pause between
them, under the profiler. Run on a machine with a TPU:

    python3 tests/benchmark/data/make_trace.py <out dir>
"""

import glob
import shutil
import sys
import time


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def decode_step(x):
        return jnp.tanh(x @ x)

    @jax.jit
    def prefill_chunk_step(x):
        return (x @ x @ x).sum()

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    decode_step(x).block_until_ready()
    prefill_chunk_step(x).block_until_ready()
    jax.profiler.start_trace(out)
    for i in range(5):
        with jax.profiler.TraceAnnotation("bench.round", i=i):
            decode_step(x).block_until_ready()
            time.sleep(0.002)
            prefill_chunk_step(x).block_until_ready()
    jax.profiler.stop_trace()
    src = sorted(glob.glob(f"{out}/plugins/profile/*/*.xplane.pb"))[-1]
    shutil.copy(src, f"{out}/v5e_small.xplane.pb")
    print("device", jax.devices()[0].device_kind, "wrote", f"{out}/v5e_small.xplane.pb")


if __name__ == "__main__":
    main(sys.argv[1])
