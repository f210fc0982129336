"""Flash decode for serving: a pallas kernel for batched one-token GQA
attention over the slot KV cache.

The serving engine's decode attention is an einsum over the FULL cache
row ``[B, Hkv, Tmax, D]`` with a ``kj <= position`` mask
(serve/engine.py::decode_step) — every step streams ``Tmax`` keys per
slot from HBM regardless of how much of the row is actually written.
Decode is HBM-bandwidth-bound, so that full-width read is the cost
that grows linearly with ``max_seq`` and slot count (the bench comment
on batch 32/64 regressions).

This kernel makes the read *ragged*: per-slot ``positions`` ride the
scalar-prefetch lane, and the KV block index map clamps block indices
past a slot's length to the last live block — pallas elides the
repeated DMA (same trick as the causal clamp in ops/flash.py), so the
unwritten tail of every cache row costs neither bandwidth nor compute.
A short request in a long-context batch reads only its own prefix.

Supported in-kernel (mirroring decode_step's einsum semantics):
- GQA grouping: q arrives ``[B, Hkv, G, D]``, the cache is streamed
  once at KV width (no G× read amplification).
- int8 KV: the cache blocks load as int8 with their per-(token, head)
  f32 scales and dequantize in VMEM — HBM traffic stays int8, which is
  the entire point of ``kv_quant="int8"``.
- sliding window as a TRACED value (per-layer windows ride the
  lax.scan over layers): masked in-kernel, and leading blocks wholly
  below the window are clamp-skipped like the tail.
- tanh softcap (static), attention sinks (gpt-oss: a learned logit in
  the softmax denominator only, applied at the finish step).

Not supported (the engine falls back to the einsum path): MLA latent
caches and Llama4 chunked-attention layers.

The reference framework has no serving kernels to mirror (it is an
orchestrator, SURVEY.md §6); the GPU-world analog of this kernel is
paged/ragged decode attention in TPU serving stacks.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _decode_kernel(
    pos_ref,  # SMEM [B] int32: attend to kj <= pos[b]
    win_ref,  # SMEM [1] int32: sliding window (0 = full)
    q_ref,  # [1, 1, G, D]
    k_ref,  # [1, 1, BK, D] compute dtype or int8
    v_ref,
    *rest,  # optional (ks_ref, vs_ref [1, 1, 1, BK] f32), optional (sink_ref [1, G, 1] f32), then o_ref + scratch
    scale: float,
    softcap: float,
    block_k: int,
    num_k: int,
    quantized: bool,
    sinks: bool,
    rows_per_slot: int,
):
    from jax.experimental import pallas as pl

    it = iter(rest)
    ks_ref = next(it) if quantized else None
    vs_ref = next(it) if quantized else None
    sink_ref = next(it) if sinks else None
    o_ref = next(it)
    acc_sc = next(it)  # VMEM [G, D] f32
    m_sc = next(it)  # VMEM [G, 128] f32
    l_sc = next(it)  # VMEM [G, 128] f32

    b = pl.program_id(0)
    ki = pl.program_id(2)
    pos = pos_ref[b]
    win = win_ref[0]

    @pl.when(ki == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    # live block range for this slot (must agree with _kv_ix's clamp:
    # clamped-away blocks re-request a live block and skip compute).
    # Speculative verify (rows_per_slot = S > 1) extends the readable
    # range to the last drafted position; the window's lower bound
    # stays at row 0's (the loosest that covers every row).
    last = jnp.clip(
        (pos + rows_per_slot - 1) // block_k, 0, num_k - 1
    )
    first = jnp.where(
        win > 0, jnp.clip((pos - (win - 1)) // block_k, 0, num_k - 1), 0
    )
    live = jnp.logical_and(ki >= first, ki <= last)

    def compute():
        q = q_ref[0, 0]  # [G, D]
        k = k_ref[0, 0]  # [BK, D]
        v = v_ref[0, 0]
        if quantized:
            # per-token scales broadcast over D; HBM read was int8
            k = (k.astype(jnp.float32) * ks_ref[0, 0, 0][:, None]).astype(q.dtype)
            v = (v.astype(jnp.float32) * vs_ref[0, 0, 0][:, None]).astype(q.dtype)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [G, BK] f32
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        nrows = q_ref.shape[2]
        cols = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (nrows, block_k), 1
        )
        # rows are [G, S] flattened row-major: row r verifies the
        # token at pos + (r % S), so it sees keys up to there
        qpos = pos + jax.lax.broadcasted_iota(
            jnp.int32, (nrows, block_k), 0
        ) % rows_per_slot
        keep = cols <= qpos
        keep = jnp.logical_and(
            keep, jnp.logical_or(win == 0, qpos - cols < win)
        )
        s = jnp.where(keep, s, NEG_INF)
        m_prev = m_sc[:, :1]  # [G, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(jnp.where(s <= NEG_INF / 2, NEG_INF, s) - m_safe)
        alpha = jnp.where(
            m_prev <= NEG_INF / 2, jnp.zeros_like(m_prev), jnp.exp(m_prev - m_safe)
        )
        l_sc[:, :1] = l_sc[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_sc[...] = acc_sc[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_sc[:, :1] = m_new

    pl.when(live)(compute)

    @pl.when(ki == num_k - 1)
    def _finish():
        m = m_sc[:, :1]
        l = l_sc[:, :1]
        acc = acc_sc[...]
        if sinks:
            # the sink joins the DENOMINATOR only (ops/attention.py::
            # sink_softmax): rescale running stats to max(m, sink)
            snk = sink_ref[0]  # [G, 1] f32
            m_f = jnp.maximum(m, snk)
            alpha = jnp.where(
                m <= NEG_INF / 2, jnp.zeros_like(m), jnp.exp(m - m_f)
            )
            l = l * alpha + jnp.exp(snk - m_f)
            acc = acc * alpha
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc / l_safe).astype(o_ref.dtype)


def flash_decode(
    q: jax.Array,  # [B, Hkv, G, D] compute dtype
    k: jax.Array,  # [B, Hkv, T, D] compute dtype, or int8 with k_scale
    v: jax.Array,
    positions: jax.Array,  # [B] int32: attend to kj <= positions[b]
    *,
    scale: float,
    window: Optional[jax.Array] = None,  # traced int32 scalar; None/0 = full
    softcap: float = 0.0,
    sinks: Optional[jax.Array] = None,  # [Hkv, G] sink logits
    k_scale: Optional[jax.Array] = None,  # [B, Hkv, T] f32 (int8 cache)
    v_scale: Optional[jax.Array] = None,
    block_k: int = 512,
    interpret: bool = False,
    rows_per_slot: int = 1,
) -> jax.Array:
    """One-token-per-slot GQA attention over the cache → [B, Hkv, G, D].

    Ragged: each slot reads only the KV blocks covering
    ``positions[b]`` (and, with a window, only blocks inside it).

    ``rows_per_slot=S`` serves speculative verify: ``q``'s row axis is
    ``[G, S]`` flattened row-major, row ``g*S + s`` attends to keys
    ``<= positions[b] + s`` (the engine scatters the S candidate K/V
    into the cache before calling). ``sinks`` must then be pre-expanded
    to ``[Hkv, G*S]``.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, hkv, g, d = q.shape
    t = k.shape[2]
    if t % 128:
        raise ValueError(
            f"flash_decode: cache length {t} must be a multiple of 128 "
            "(gate callers with flash_decode_supported)"
        )
    quantized = k_scale is not None
    bk = min(block_k, t)
    while t % bk:
        bk -= 128
    num_k = t // bk

    if window is None:
        window = jnp.zeros((), jnp.int32)
    win_arr = jnp.asarray(window, jnp.int32).reshape(1)
    pos_arr = positions.astype(jnp.int32)

    def _kv_ix(bi, h, ki, pos_ref, win_ref):
        # must agree with the kernel's `live` range: tail blocks clamp
        # to the last live block, leading out-of-window blocks to the
        # first — re-requested blocks cost no DMA
        last = jnp.clip(
            (pos_ref[bi] + rows_per_slot - 1) // bk, 0, num_k - 1
        )
        ix = jnp.minimum(ki, last)
        first = jnp.where(
            win_ref[0] > 0,
            jnp.clip((pos_ref[bi] - (win_ref[0] - 1)) // bk, 0, num_k - 1),
            0,
        )
        return (bi, h, jnp.maximum(ix, first), 0)

    in_specs = [
        pl.BlockSpec((1, 1, g, d), lambda bi, h, ki, p, w: (bi, h, 0, 0)),
        pl.BlockSpec((1, 1, bk, d), _kv_ix),
        pl.BlockSpec((1, 1, bk, d), _kv_ix),
    ]
    args = [q, k, v]
    # Mosaic wants a block's last two dims to be multiples of (8, 128)
    # or to span the array: scales ride as [B, Hkv, 1, T] (a unit row
    # over the token lanes), sinks as [Hkv, G, 1] (a column per head)
    if quantized:
        def sc_ix(bi, h, ki, p, w):
            bi, h, ki, _ = _kv_ix(bi, h, ki, p, w)
            return (bi, h, 0, ki)

        in_specs += [pl.BlockSpec((1, 1, 1, bk), sc_ix)] * 2
        args += [
            s.astype(jnp.float32).reshape(b, hkv, 1, t)
            for s in (k_scale, v_scale)
        ]
    if sinks is not None:
        in_specs.append(
            pl.BlockSpec((1, g, 1), lambda bi, h, ki, p, w: (h, 0, 0))
        )
        args.append(sinks.astype(jnp.float32).reshape(hkv, g, 1))

    kernel = functools.partial(
        _decode_kernel,
        scale=scale,
        softcap=softcap,
        block_k=bk,
        num_k=num_k,
        quantized=quantized,
        sinks=sinks is not None,
        rows_per_slot=rows_per_slot,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, num_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, g, d), lambda bi, h, ki, p, w: (bi, h, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((g, d), jnp.float32),
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(pos_arr, win_arr, *args)


def flash_decode_supported(config, max_seq: int) -> bool:
    """Whether the engine may route decode attention through the
    kernel for this model/cache shape (the caller still falls back
    per-call when ``interpret`` isn't wanted off-TPU)."""
    return (
        not config.mla
        and not config.layer_types  # a window layer's cache is a ring
        and not config.attention_chunk_size
        and config.head_dim % 64 == 0
        and max_seq % 128 == 0
        and config.n_heads % config.n_kv_heads == 0
    )
