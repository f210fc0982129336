"""Seeded random weights, made on the device in one jitted call.

The benchmark owns the weights: the launcher hands this tree to the
program in place of its op-by-op random init (same leaves, shapes and
dtypes as ``models/llama.py init_params`` lays them out), and the
reference child makes the very same tree again from the same seed after
the program has gone. Nothing the program computed reaches the
reference.

Leaves are drawn layer by layer (``lax.map`` over the stacked leading
dim, row blocks for the embedding tables) so the float32 temporaries of
the normal draw stay at one layer's size beside 10 GB of weights.
"""

import math
import zlib

import jax
import jax.numpy as jnp

_STD = 0.02
_BLOCK_ELEMS = 1 << 27  # ~0.5 GB of f32 per draw


def _dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


def leaf_shapes(c: dict) -> dict:
    """``{path: (shape, scale)}``; scale None = a norm leaf (identity
    init), else the std of the normal draw. ``c`` is the configuration
    file's ``llama_config`` group."""
    H, V = c["hidden_size"], c["vocab_size"]
    n_layers, k_dense = c["n_layers"], c.get("first_k_dense", 0)
    L = n_layers - k_dense
    down = _STD / math.sqrt(2 * n_layers)
    ln1p = c.get("norm_type", "rms") == "layernorm1p"

    def norm(*lead):
        return (lead + ((2, H) if ln1p else (H,)), None)

    def attn(n):
        if c.get("kv_lora_rank", 0):
            r, rope = c["kv_lora_rank"], c["qk_rope_head_dim"]
            nope, vd, nh = c["qk_nope_head_dim"], c["v_head_dim"], c["n_heads"]
            return {
                "wq": ((n, H, nh * (nope + rope)), _STD),
                "wkv_a": ((n, H, r + rope), _STD),
                "kv_a_norm": ((n, r), None),
                "wkv_b": ((n, r, nh * (nope + vd)), _STD),
                "wo": ((n, nh * vd, H), down),
            }
        q = c["n_heads"] * c["head_dim"]
        kv = c["n_kv_heads"] * c["head_dim"]
        return {
            "wq": ((n, H, q), _STD), "wk": ((n, H, kv), _STD),
            "wv": ((n, H, kv), _STD), "wo": ((n, q, H), down),
        }

    layers = {"attn_norm": norm(L), "mlp_norm": norm(L), **attn(L)}
    F = c["intermediate_size"]
    if c.get("n_experts", 0):
        E = c["n_experts"]
        layers.update({
            "w_router": ((L, H, E), _STD),
            "w_gate": ((L, E, H, F), _STD),
            "w_up": ((L, E, H, F), _STD),
            "w_down": ((L, E, F, H), down),
        })
        if c.get("moe_shared_expert"):
            FS = c.get("moe_shared_intermediate") or F
            layers.update({
                "w_shared_gate": ((L, H, FS), _STD),
                "w_shared_up": ((L, H, FS), _STD),
                "w_shared_down": ((L, FS, H), down),
            })
    else:
        layers.update({"w_up": ((L, H, F), _STD), "w_down": ((L, F, H), down)})
        if not c.get("mlp_gateless"):
            layers["w_gate"] = ((L, H, F), _STD)
    tree = {
        "embed": ((V, H), _STD),
        "layers": layers,
        "final_norm": norm(),
    }
    if k_dense:
        FD = c.get("dense_intermediate") or F
        tree["dense_layers"] = {
            "attn_norm": norm(k_dense), "mlp_norm": norm(k_dense),
            **attn(k_dense),
            "w_gate": ((k_dense, H, FD), _STD),
            "w_up": ((k_dense, H, FD), _STD),
            "w_down": ((k_dense, FD, H), down),
        }
    if not c.get("tie_embeddings"):
        tree["lm_head"] = ((H, V), _STD)
    return tree


def _flatten(tree, prefix=""):
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict):
            yield from _flatten(v, f"{prefix}{k}/")
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


def make_params(llama_config: dict, seed: int) -> dict:
    """The whole tree, one jitted call, already in the served dtype."""
    c = llama_config
    dt = _dtype(c.get("dtype", "bfloat16"))
    ln1p = c.get("norm_type", "rms") == "layernorm1p"
    spec = leaf_shapes(c)

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


def num_params(llama_config: dict) -> int:
    return sum(math.prod(s) for _, (s, _) in _flatten(leaf_shapes(llama_config)))
