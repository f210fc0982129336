"""Device-free pins of the latent-attention serving programs of a
configuration the benchmark runs (``deepseek-v2-lite-9l``): the sha256
of each program's lowered StableHLO text at the cell's shapes, with
abstract weights and cache. The text carries no source locations, so
moving or renaming code does not move it; an operation added to, taken
from or reordered in the program does. A PR that means to change what
these programs compute takes the pins again (``python
tests/serve/test_program_pins.py`` prints them) and says so; one that
adds an architecture beside them must leave them as they are."""

import hashlib
import json
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: the three decode programs: taken on the tree of PR 26 (before issue 27
#: moved a line) and untouched since, which is the proof that PR 29 left
#: the decode programs alone. The four prefill programs: taken again on
#: the tree of PR 29, which moved their cache from the layer scan's
#: xs → ys into its carry on purpose (the sameness tests of
#: ``test_prefill_inplace.py`` passed first; PR 28, refused for a claim
#: and not for its code, had taken the same four digests)
PINS = {
    "decode_step": "b04eb128c0bfb96c3389b5cded7a0831fa4ba5700abb8e9788173e7be03fe4cd",
    "decode_loop": "719adef2066bfc901d7883b59313e6b3a3f6ea3ecc737452f5581eaaa7a1d789",
    "verify_step": "56ce65d4985c8d8a942b813a4931f5675c2781f9f269a6a146a51d9618960d38",
    "prefill_chunk_step@0": "288d0834c3cce37700920d780da73acb7143ca300bd0939f209fe17830e6226e",
    "prefill_chunk_step@256": "69a3129b1016f7d17ad1a984fce1a54d0253457d1fb07c92cfef418748ea8638",
    "prefill_packed_step@2": "ec960d7ac9100619ad9f9353a2603d8ed7b0379497310d36c539bad7ff5bedcd",
    "prefill_packed_step@4": "12148c36bc0cf3d7115f858f3c7990cb1a1d049d7f6a349e1b7b82afcc89d730",
}


def _lowered(name: str):
    from benchmark import launch
    from dstack_tpu.models import llama
    from dstack_tpu.serve import engine as E

    with open(os.path.join(ROOT, "benchmark", "configs", "deepseek-v2-lite-9l.json")) as f:
        cfg = json.load(f)
    c = launch.build_llama_config(cfg["llama_config"])
    B, T = 16, 8192  # the configuration's serve_flags
    params = llama.abstract_params(c)
    cache = jax.eval_shape(lambda: E.init_cache(c, B, T))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    flag = jax.ShapeDtypeStruct((B,), jnp.bool_)
    if name == "decode_step":
        fn = partial(E.decode_step, config=c)
        args = (params, cache, i32(B), i32(B))
        kw = {"write_mask": flag}
    elif name == "decode_loop":
        fn = partial(E.decode_loop, config=c, steps=8, max_seq=T)
        args = (params, cache, i32(B), i32(B), i32(B), flag, i32(B))
        kw = {}
    elif name == "verify_step":
        fn = partial(E.verify_step, config=c)
        args = (params, cache, i32(B, 5), i32(B))
        kw = {"write_mask": flag}
    elif name.startswith("prefill_chunk_step"):
        fn = partial(E.prefill_chunk_step, config=c, start=int(name.split("@")[1]))
        args = (params, cache, i32(1, 256), i32(), i32())
        kw = {}
    elif name.startswith("prefill_packed_step"):
        g = int(name.split("@")[1])
        fn = partial(E.prefill_packed_step, config=c)
        args = (params, cache, i32(g, 256), i32(g), i32(g), i32(g))
        kw = {}
    else:
        raise KeyError(name)
    return jax.jit(fn).lower(*args, **kw)


NAMES = (
    "decode_step", "decode_loop", "verify_step", "prefill_chunk_step@0",
    "prefill_chunk_step@256", "prefill_packed_step@2", "prefill_packed_step@4",
)


def digest(name: str) -> str:
    return hashlib.sha256(_lowered(name).as_text().encode()).hexdigest()


@pytest.mark.parametrize("name", NAMES)
def test_latent_programs_lower_to_what_they_did(name):
    assert digest(name) == PINS[name]


if __name__ == "__main__":
    print(json.dumps({n: digest(n) for n in NAMES}, indent=1))
