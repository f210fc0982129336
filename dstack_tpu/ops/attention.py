"""Attention ops: dispatching entry point (pallas flash kernel / XLA).

The hot op of every model (SURVEY.md's compute-plane requirement). The
pallas kernels live in :mod:`dstack_tpu.ops.flash` — KV-block grid with
double-buffered DMA streaming, online softmax, custom VJP with pallas
backward kernels, GQA via index_map. This module keeps the
shape/platform dispatch and the XLA fallback used off-TPU (CPU tests,
virtual meshes) and for non-tiling shapes (decode steps, tiny models).
"""

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dstack_tpu.ops.flash import (  # re-exported public kernel API
    flash_attention,
    flash_attention_with_lse,
    flash_supported,
)

NEG_INF = -1e30

__all__ = [
    "attention",
    "flash_attention",
    "flash_attention_with_lse",
    "flash_supported",
]


def sink_softmax(s: jax.Array, sink: jax.Array) -> jax.Array:
    """Softmax over the last axis with a learned sink logit joining the
    DENOMINATOR only (gpt-oss attention sinks: an always-present column
    that absorbs probability mass and is dropped from the value sum —
    HF's concat-then-drop eager path in streaming form). ``s`` is the
    pre-masked f32 scores; ``sink`` must broadcast against ``s`` with a
    trailing singleton key axis."""
    m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), sink)
    e = jnp.exp(s - m)
    return e / (jnp.sum(e, axis=-1, keepdims=True) + jnp.exp(sink - m))


def _xla_attention(
    q: jax.Array,  # [B, H, Tq, D]
    k: jax.Array,  # [B, Hkv, Tk, D]
    v: jax.Array,
    causal: bool,
    scale: float,
    q_offset=0,  # int, or [B] int32 per-row offsets (packed prefill)
    window: int = 0,
    softcap: float = 0.0,
    chunk: int = 0,
    sinks: "Optional[jax.Array]" = None,  # [H] per-head sink logits
) -> jax.Array:
    b, h, tq, d = q.shape
    hkv = k.shape[1]
    if hkv != h:
        assert h % hkv == 0, f"GQA heads {h} not divisible by kv heads {hkv}"
        k = jnp.repeat(k, h // hkv, axis=1)
        v = jnp.repeat(v, h // hkv, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)  # cap raw scores, then mask
    if causal or window or chunk:
        tk = k.shape[2]
        # a scalar offset broadcasts ([1, Tq, 1] rows); a [B] vector
        # gives per-row causal frontiers (packed multi-slot prefill:
        # each row's chunk sits at its own global start)
        off = jnp.reshape(jnp.asarray(q_offset, jnp.int32), (-1, 1, 1))
        qi = off + jnp.arange(tq)[None, :, None]  # [B|1, Tq, 1]
        kj = jnp.arange(tk)[None, None, :]  # [1, 1, Tk]
        keep = (
            (qi >= kj) if causal
            else jnp.ones((off.shape[0], tq, tk), bool)
        )
        if window:
            # HF sliding-window convention: key j visible to query i
            # iff 0 <= i - j < window
            keep = keep & (qi - kj < window)
        if chunk:
            # Llama4 chunked attention: key j visible to query i iff
            # both land in the same `chunk`-token block (blockwise
            # local, not a sliding window)
            keep = keep & (qi // chunk == kj // chunk)
        s = jnp.where(keep[:, None], s, NEG_INF)  # broadcast over heads
    if sinks is not None:
        p = sink_softmax(s, sinks.astype(jnp.float32).reshape(1, -1, 1, 1))
    else:
        p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def sink_postscale(
    o: jax.Array,  # [B, H, Tq, D] sink-less attention output
    lse: jax.Array,  # [B, H, Tq] f32 logsumexp of the same call
    sinks: jax.Array,  # [H] learned sink logits
) -> jax.Array:
    """Apply gpt-oss attention sinks AFTER a sink-less softmax.

    The sink joins the DENOMINATOR only (:func:`sink_softmax`), so the
    sinked output is an exact rescale of the sink-less one:
    ``p_sink @ v = (p @ v) · l / (l + e^{sink-m}) = o · σ(lse - sink)``
    — which lets the pallas flash kernel serve sink models without a
    sink column in the kernel (forward only: ``lse`` from
    :func:`flash_attention_with_lse` has no VJP)."""
    gate = jax.nn.sigmoid(
        lse - sinks.astype(jnp.float32).reshape(1, -1, 1)
    )[..., None]
    return (o.astype(jnp.float32) * gate).astype(o.dtype)


def _per_shard(kernel, shard, with_lse: bool = False):
    """``kernel(q, k, v)`` as is, or — with ``shard=(mesh, spec)`` from
    :func:`dstack_tpu.parallel.sharding.kernel_shard` — per shard under
    ``shard_map``: GSPMD cannot partition a Mosaic call, and attention
    needs no collective across batch rows or KV-head groups."""
    if shard is None:
        return kernel
    mesh, spec = shard
    # dtpu: noqa[DTPU012] spec comes from parallel/sharding.kernel_shard, which draws its axis names from the rule table (checked there) and drops axes that do not divide the operand
    return jax.shard_map(
        kernel, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=(spec, P(*spec[:3])) if with_lse else spec,
        check_vma=False,
    )


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    q_offset=0,  # int, or [B] int32 per-row offsets
    window: int = 0,  # 0 = full attention; else sliding window size
    softcap: float = 0.0,  # 0 = off; else tanh soft-cap on scores
    chunk: int = 0,  # 0 = off; else Llama4 blockwise-chunk size
    sinks: Optional[jax.Array] = None,  # [H] gpt-oss attention sinks
    impl: Optional[str] = None,  # None=auto | "flash" | "xla"
    sinks_forward_only: bool = False,  # caller never differentiates
    shard: Optional[tuple] = None,  # (mesh, spec): kernel runs per shard
) -> jax.Array:
    """Dispatching attention entry point used by models.

    ``q_offset`` may be a ``[B]`` int32 vector giving each batch row its
    own causal frontier (packed multi-slot prefill: concurrent prompt
    chunks at unequal starts share one dispatch). The pallas kernel
    tiles exactly one static offset per call, so vector offsets always
    take the masked-einsum path (window/softcap/chunk/sinks included).

    On a multi-device mesh pass ``shard`` (:func:`kernel_shard`): the
    XLA path partitions on its own, the pallas kernel cannot.
    """
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if isinstance(q_offset, jax.Array) and q_offset.ndim > 0:
        return _xla_attention(
            q, k, v, causal=causal, scale=scale, q_offset=q_offset,
            window=window, softcap=softcap, chunk=chunk, sinks=sinks,
        )
    if sinks is not None:
        # sinks join the softmax DENOMINATOR only, so a sink-less flash
        # pass rescaled by σ(lse - sink) is exact (sink_postscale) —
        # but lse has no VJP, so only forward-only callers (serving
        # prefill) may ride it; training keeps the masked XLA path
        if (
            sinks_forward_only
            and not chunk
            and (impl == "flash" or (impl is None and flash_supported(q, k)))
        ):
            o, lse = _per_shard(partial(
                flash_attention_with_lse, causal=causal, scale=scale,
                q_offset=q_offset, window=window, softcap=softcap,
            ), shard, with_lse=True)(q, k, v)
            return sink_postscale(o, lse, sinks)
        return _xla_attention(
            q, k, v, causal=causal, scale=scale, q_offset=q_offset,
            window=window, softcap=softcap, chunk=chunk, sinks=sinks,
        )
    if chunk and causal and q_offset + q.shape[2] <= chunk:
        # all queries live in the first chunk, and causal masking
        # already hides every key past them — identical to plain
        # causal regardless of the KV buffer length (serving prefill
        # passes the full cache row), so the flash path stays eligible
        chunk = 0
    if chunk:
        # the pallas kernel has no chunk mask; blockwise-local layers
        # beyond one chunk take the masked XLA path
        return _xla_attention(
            q, k, v, causal=causal, scale=scale, q_offset=q_offset,
            window=window, softcap=softcap, chunk=chunk,
        )
    if impl == "flash" or (impl is None and flash_supported(q, k)):
        return _per_shard(partial(
            flash_attention, causal=causal, scale=scale, q_offset=q_offset,
            window=window, softcap=softcap,
        ), shard)(q, k, v)
    return _xla_attention(
        q, k, v, causal=causal, scale=scale, q_offset=q_offset,
        window=window, softcap=softcap,
    )
