"""What the tests of one process build once and share.

The suite's seconds are tracing and compiling, not arithmetic: an engine
program called eagerly compiles its scans anew every call, a new
``jax.jit`` wrapper compiles what the process has compiled before, and
``init_params`` op by op is hundreds of small programs a preset. A test
takes its parameters and its programs from here; what it asserts stays
in its file.
"""

import functools

import jax

from dstack_tpu.models import llama


@functools.lru_cache(maxsize=None)
def _jitted(fn, variant, statics):
    return jax.jit(functools.partial(fn, **dict(statics)))


def jitted(fn, variant=None, **statics):
    """``jax.jit(partial(fn, **statics))``, one wrapper a process for one
    function and one set of (hashable) static arguments. A test that
    traces under a patched global names the patch in ``variant``: a trace
    is kept with its wrapper, and must not serve the unpatched callers."""
    return _jitted(fn, variant, tuple(sorted(statics.items())))


@functools.lru_cache(maxsize=None)
def _params(config, seed, depth):
    return jitted(llama.init_params, config=config, depth=depth)(
        key=jax.random.key(seed)
    )


def init_params(config, seed: int, depth: int = 0) -> dict:
    """``llama.init_params(config, jax.random.key(seed), depth)`` drawn by
    one program, once a process; the containers are the caller's own (a
    test may replace a leaf), the arrays are shared."""
    return jax.tree.map(lambda leaf: leaf, _params(config, seed, depth))
