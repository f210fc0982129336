"""Weight-only int8 quantization for serving.

Decode is HBM-bandwidth-bound: the chip reads every weight once per
token while the MXU idles. Storing the projection matrices (attention,
dense/MoE/shared-expert FFNs — see LAYER_TARGETS — and the LM head) as
int8 with per-output-channel scales halves the bytes per step — the dequantize is a cast the MXU input pipeline absorbs plus
one per-channel multiply that XLA fuses into the matmul's epilogue.

Per-output-channel absmax scaling is exact under the contraction: for
W[:, o] quantized as q[:, o]·s[o], x·W ≈ (x·q)·s column-wise, so the
scale multiplies the OUTPUT — no input statistics, no calibration data.

``quantize_tree`` rewrites a params pytree: every target leaf ``name``
becomes ``name_q`` (int8, same shape) + ``name_s`` (f32 scale per
output channel); :func:`dstack_tpu.models.llama._proj` consumes either
form, so training-free quantized serving works through every existing
path (forward, prefill, decode, LoRA bypass on a quantized base).

Norms, biases, and the embedding table stay in model dtype: they are a
rounding error of the byte budget, and the embedding is a gather (no
matmul to fuse a dequant into). MoE expert stacks ([L, E, in, out])
quantize through the same rank-generic absmax — per (expert, output
channel) scales — and models/moe.py resolves the ``_q``/``_s`` form in
its batched expert einsums; routers stay full precision (tiny, and
routing decisions are precision-sensitive). MLA models quantize their
expert/FFN stacks and ``wo`` — nearly all of a DeepSeek checkpoint's
bytes — while the latent attention projections stay full precision
(raw-einsum/absorbed-reshape consumers; see :func:`quant_targets`).
"""

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from dstack_tpu.models.llama import LlamaConfig

# projection leaves quantized inside each layer ([L, in, out] stacks;
# the MoE expert stacks [L, E, in, out] and the fused shared experts
# ride the same rank-generic per-output-channel quantization)
LAYER_TARGETS = (
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
    "w_shared_gate", "w_shared_up", "w_shared_down",
)


def quantize_weight(w) -> tuple[np.ndarray, np.ndarray]:
    """[..., in, out] → (int8 [..., in, out], f32 scale [..., out]).

    Per-output-channel absmax: q = round(w / s), s = absmax_in / 127.
    Runs on HOST (numpy): serving paths hand the engine a host tree so
    big models go straight into sharded device buffers — quantizing
    eagerly on device would commit every full-precision stack to chip 0
    first, the exact OOM the host-tree contract avoids.
    """
    w32 = np.asarray(w, np.float32)
    absmax = np.max(np.abs(w32), axis=-2)  # [..., out]
    s = np.where(absmax == 0.0, 1.0, absmax / 127.0).astype(np.float32)
    q = np.clip(np.round(w32 / s[..., None, :]), -127, 127).astype(np.int8)
    return q, s


def dequantize_weight(q, s, dtype: Any) -> jax.Array:
    return (jnp.asarray(q, jnp.float32) * jnp.asarray(s)[..., None, :]).astype(dtype)


def quant_targets(config: LlamaConfig) -> tuple:
    """The projection leaves int8 covers for this config.

    MLA models (DeepSeek) keep their latent attention projections
    (``wq_a/wq_b/wkv_a/wkv_b``) in full precision: they are consumed by
    raw einsums and the absorbed-form reshape, and the latent path is
    already the compression — while the expert/FFN stacks and ``wo``
    (a ``_proj`` consumer) carry nearly all of a DeepSeek checkpoint's
    bytes and quantize exactly like any other family's."""
    if config.mla:
        # derived, not hardcoded: a future FFN target added to
        # LAYER_TARGETS must not silently serve full-precision on MLA
        return tuple(
            t for t in LAYER_TARGETS if t not in ("wq", "wk", "wv")
        )
    return LAYER_TARGETS


def _quantize_stack(stack: dict, targets: tuple) -> dict:
    out = {}
    for name, leaf in stack.items():
        if name in targets:
            q, s = quantize_weight(leaf)  # asarray(f32) happens inside
            out[name + "_q"] = q
            out[name + "_s"] = s
        else:
            out[name] = leaf
    return out


def quantize_tree(params: dict, config: LlamaConfig) -> dict:
    """Params pytree → serving pytree with int8 projection weights.

    Quantizes the per-layer projections and the LM head (when untied);
    embedding, norms, biases, and LoRA adapters pass through. The
    DeepSeek dense prelude (``dense_layers``) quantizes its FFN like
    the main stack; see :func:`quant_targets` for the MLA carve-out.
    """
    targets = quant_targets(config)
    out = {
        k: v for k, v in params.items()
        if k not in ("layers", "dense_layers", "lm_head")
    }
    out["layers"] = _quantize_stack(params["layers"], targets)
    if "dense_layers" in params:
        out["dense_layers"] = _quantize_stack(
            params["dense_layers"], targets
        )
    if "lm_head" in params:
        q, s = quantize_weight(params["lm_head"])
        out["lm_head_q"] = q
        out["lm_head_s"] = s
    return out


def quant_param_specs(specs: dict, config: LlamaConfig = None) -> dict:
    """Logical-axis spec tree for a quantized params tree.

    ``name_q`` shards exactly like ``name``; ``name_s`` keeps only the
    output-channel axis (the last spec entry), so tensor-parallel
    serving shards scales alongside their columns. ``config`` picks the
    per-config target set (MLA quantizes FFN + ``wo`` only) — omitted,
    the full LAYER_TARGETS set is assumed (pre-MLA callers).
    """
    targets = quant_targets(config) if config is not None else LAYER_TARGETS

    def spec_stack(stack: dict) -> dict:
        out = {}
        for name, spec in stack.items():
            if name in targets:
                out[name + "_q"] = spec
                # drop the input-dim axis: ("layers", in, out) → ("layers", out)
                out[name + "_s"] = spec[:-2] + spec[-1:]
            else:
                out[name] = spec
        return out

    out = {
        k: v for k, v in specs.items()
        if k not in ("layers", "dense_layers", "lm_head")
    }
    out["layers"] = spec_stack(specs["layers"])
    if "dense_layers" in specs:
        out["dense_layers"] = spec_stack(specs["dense_layers"])
    if "lm_head" in specs:
        out["lm_head_q"] = specs["lm_head"]
        out["lm_head_s"] = specs["lm_head"][-1:]
    return out


def is_quantized(params: dict) -> bool:
    return any(k.endswith("_q") for k in params.get("layers", {}))


def random_quantized_params(config: LlamaConfig, seed: int = 0) -> dict:
    """Benchmark-only: the int8 serving tree with random values, built
    directly in numpy.

    ``init_params`` → ``quantize_tree`` materializes the full-precision
    tree through JAX's host PRNG first — tens of minutes of threefry on
    a small host for an 8B model. Decode throughput/latency are
    weight-value-independent, so the bench path emits random int8
    projections (+ jittered per-channel scales, so no two channels
    dequantize identically) and random-normal bf16 for everything
    else. Leaf shapes come from ``jax.eval_shape`` over the real
    ``init_params``; the quantized layout (targets, ``_q``/``_s``
    naming, scale shapes) mirrors :func:`quantize_tree` by hand — the
    structure-parity test in ``tests/compute/test_quant.py`` is what
    actually pins the two together."""
    shapes = _random_tree_shapes(config, seed)
    rng = np.random.default_rng(seed)

    def dense(leaf) -> np.ndarray:
        dt = np.dtype(leaf.dtype)
        # standard_normal only emits float32/64; cast after
        return (
            rng.standard_normal(leaf.shape, np.float32) * 0.02
        ).astype(dt)

    def q_and_s(leaf) -> tuple[np.ndarray, np.ndarray]:
        q = rng.integers(
            -127, 128, size=leaf.shape, dtype=np.int8
        )
        s_shape = leaf.shape[:-2] + leaf.shape[-1:]
        s = (
            rng.uniform(0.8, 1.2, s_shape) * (0.02 / 127.0)
        ).astype(np.float32)
        return q, s

    return _assemble_random_tree(shapes, dense, q_and_s)


def _random_tree_shapes(config: LlamaConfig, seed: int) -> dict:
    """Shared prologue for the random-tree generators: the
    unsupported-config guards and the ``eval_shape`` over the real
    ``init_params`` — one copy, so a new precondition cannot drift
    between the host and on-device paths."""
    from functools import partial

    from dstack_tpu.models import llama

    if config.mla:
        raise ValueError(
            "the bench's random int8 tree generator does not cover MLA "
            "configs (real checkpoints DO quantize via quantize_tree; "
            "the bench targets the llama family)"
        )
    shapes = jax.eval_shape(
        partial(llama.init_params, config), jax.random.key(seed)
    )
    if "dense_layers" in shapes:
        raise ValueError(
            "the bench's random int8 tree generator does not cover "
            "dense-prelude stacks (real checkpoints DO quantize via "
            "quantize_tree)"
        )
    return shapes


def _assemble_random_tree(shapes: dict, dense, q_and_s) -> dict:
    """Walk ``init_params``' shape tree into the quantized layout,
    generating each leaf through the supplied callbacks (numpy host
    path or jitted device path — same structure either way, which is
    what the parity test in tests/compute/test_quant.py pins)."""
    out: dict = {}
    for key, leaf in shapes.items():
        if key == "layers":
            layers: dict = {}
            for name, lf in leaf.items():
                if name in LAYER_TARGETS:
                    layers[name + "_q"], layers[name + "_s"] = q_and_s(lf)
                else:
                    layers[name] = dense(lf)
            out["layers"] = layers
        elif key == "lm_head":
            out["lm_head_q"], out["lm_head_s"] = q_and_s(leaf)
        else:
            # embedding / norms / nested aux trees pass through dense
            out[key] = jax.tree_util.tree_map(dense, leaf)
    return out


def random_quantized_params_on_device(
    config: LlamaConfig, seed: int = 0
) -> dict:
    """Benchmark-only: :func:`random_quantized_params`, but every leaf
    is generated ON the accelerator by a small jitted PRNG program.

    The numpy tree costs a host build plus a ~8 GB ``device_put`` for
    an 8B model; here only compiled programs and 16-byte keys reach
    the device and the threefry runs at chip speed. Same tree structure
    and value distributions as the numpy path."""
    from functools import partial

    shapes = _random_tree_shapes(config, seed)
    root = jax.random.key(seed)
    leaf_no = iter(range(1 << 30))

    @partial(jax.jit, static_argnums=(1, 2))
    def _dense(k, shape, dtype):
        return (
            jax.random.normal(k, shape, jnp.float32) * 0.02
        ).astype(dtype)

    @partial(jax.jit, static_argnums=(1,))
    def _q(k, shape):
        return jax.random.randint(k, shape, -127, 128, dtype=jnp.int8)

    @partial(jax.jit, static_argnums=(1,))
    def _s(k, shape):
        return jax.random.uniform(
            k, shape, jnp.float32, 0.8, 1.2
        ) * (0.02 / 127.0)

    def _key():
        return jax.random.fold_in(root, next(leaf_no))

    def dense(leaf):
        return _dense(_key(), tuple(leaf.shape), np.dtype(leaf.dtype))

    def q_and_s(leaf):
        s_shape = tuple(leaf.shape[:-2] + leaf.shape[-1:])
        return (
            _q(_key(), tuple(leaf.shape)),
            _s(_key(), s_shape),
        )

    return _assemble_random_tree(shapes, dense, q_and_s)
