"""Seeded random weights, made on the device in one jitted call.

The benchmark owns the weights: the launcher hands this tree to the
program in place of its op-by-op random init (same leaves, shapes and
dtypes as ``models/llama.py init_params`` lays them out), and the
reference child makes the very same tree again from the same seed after
the program has gone. Nothing the program computed reaches the
reference.

Which leaves there are is the architecture's to say: the reference
module a configuration names states its tree (``leaf_shapes``). What is
common to all stays here: the draw and its blocks, one key a leaf (the
path's crc32 folded into the seed's key, so a leaf's values do not
depend on which other leaves the tree has), the norm leaves' identity
init, one jitted call.

Leaves are drawn layer by layer (``lax.map`` over the stacked leading
dim, row blocks for the embedding tables) so the float32 temporaries of
the normal draw stay at one layer's size beside 10 GB of weights.
"""

import importlib
import math
import zlib

import jax
import jax.numpy as jnp

_BLOCK_ELEMS = 1 << 27  # ~0.5 GB of f32 per draw


def _dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


def leaf_spec(cfg: dict) -> dict:
    """``{path: (shape, scale)}``, nested: the tree that the
    configuration's architecture states it reads (``leaf_shapes`` of the
    module the configuration file names under ``reference``). Scale
    None = a norm leaf (identity init), else the std of the normal
    draw. Any number of layer groups is only a nested spec."""
    ref = importlib.import_module(f"benchmark.reference.{cfg['reference']}")
    return ref.leaf_shapes(cfg["llama_config"])


def flatten(tree, prefix=""):
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict):
            yield from flatten(v, f"{prefix}{k}/")
        else:
            yield f"{prefix}{k}", v


def _draw(key, shape, scale, dt):
    """Normal draw of ``shape`` in blocks along the leading dim."""
    lead = shape[0]
    per = math.prod(shape[1:])
    blocks = lead
    # fewest blocks (a divisor of the leading dim) under the cap
    for b in range(1, lead + 1):
        if lead % b == 0 and (lead // b) * per <= _BLOCK_ELEMS:
            blocks = b
            break
    rows = lead // blocks

    def one(k):
        x = jax.random.normal(k, (rows,) + tuple(shape[1:]), jnp.float32)
        return (x * scale).astype(dt)

    out = jax.lax.map(one, jax.random.split(key, blocks))
    return out.reshape(shape)


def make_params(cfg: dict, seed: int) -> dict:
    """The whole tree of the configuration file ``cfg``, one jitted
    call, already in the served dtype."""
    c = cfg["llama_config"]
    dt = _dtype(c.get("dtype", "bfloat16"))
    ln1p = c.get("norm_type", "rms") == "layernorm1p"
    spec = leaf_spec(cfg)

    def build(key):
        def fill(tree, prefix=""):
            out = {}
            for k in sorted(tree):
                v = tree[k]
                if isinstance(v, dict):
                    out[k] = fill(v, f"{prefix}{k}/")
                    continue
                shape, scale = v
                if scale is None:
                    # LayerNorm1p stores (scale-1, bias): zeros are
                    # identity; RMSNorm weights are ones
                    out[k] = (jnp.zeros if ln1p else jnp.ones)(shape, dt)
                else:
                    leaf_key = jax.random.fold_in(
                        key, zlib.crc32(f"{prefix}{k}".encode()) & 0x7FFFFFFF
                    )
                    out[k] = _draw(leaf_key, shape, scale, dt)
            return out

        return fill(spec)

    # the seed may exceed 31 bits: fold it into the key in two halves
    key = jax.random.fold_in(
        jax.random.key(int(seed) & 0x7FFFFFFF), (int(seed) >> 31) & 0x7FFFFFFF
    )
    return jax.jit(build)(key)


def num_params(cfg: dict) -> int:
    return sum(math.prod(s) for _, (s, _) in flatten(leaf_spec(cfg)))
