"""Flash attention for TPU: pallas forward + backward kernels, custom VJP.

The hot op of the compute plane. Design (pallas_guide playbook):

- Grid ``(B, H, num_q_blocks, num_kv_blocks)`` with
  ``dimension_semantics = (parallel, parallel, parallel, arbitrary)`` —
  the KV dimension is innermost/sequential, so pallas streams KV blocks
  through VMEM with automatically double-buffered DMA while the online-
  softmax accumulators live in VMEM scratch across KV steps.
- HBM traffic is O(T·D) per query block (no [T, T] score matrix ever
  touches HBM); the MXU sees [BQ, D]×[D, BK] and [BQ, BK]×[BK, D]
  matmuls in f32 accumulation over bf16 inputs.
- GQA is native: the kernel's K/V index_map sends query head ``h`` to KV
  head ``h // group`` — no ``jnp.repeat`` materialization.
- Backward is two pallas kernels (dq; dk/dv) using the saved
  logsumexp — the standard FlashAttention-2 recomputation scheme.
- Causal blocks above the diagonal skip their compute via ``pl.when``.

The reference framework has no kernels to mirror (it is an orchestrator,
SURVEY.md §6); the bar is bench.py's 0.40-MFU target.

``q_offset``/``kv_offset`` place the local Q/KV blocks at global
positions for causal masking across sequence shards.
parallel/ring_attention.py drives the kernels directly per ring step
(`_flash_fwd`/`_flash_bwd`) and merges the per-step partials by the
returned logsumexp; ``flash_attention_with_lse`` exposes the same
(o, lse) pair publicly.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30
# The backward kernels hold four [bq, bk] f32 temporaries (s, p, dp, ds)
# where the forward holds two: at the default 1024×1024 blocks that is
# 16 MiB, the whole default scoped VMEM, and the TPU compiler refused
# them for every T ≥ 2048 (17.07 MiB asked). A v5e core has 128 MiB.
_BWD_VMEM_LIMIT_BYTES = 32 * 1024 * 1024


def _pick_block(t: int, cap: int, unit: int = 128) -> int:
    """Largest multiple of ``unit`` that divides ``t`` and is ≤ cap."""
    if t % unit != 0:
        raise ValueError(f"sequence length {t} must be a multiple of {unit}")
    b = min(cap - cap % unit, t)
    while b > unit and t % b != 0:
        b -= unit
    return b


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref,  # [1, 1, BQ, D]
    k_ref,  # [1, 1, BK, D]
    v_ref,  # [1, 1, BK, D]
    o_ref,  # [1, 1, BQ, D]
    lse_ref,  # [1, 1, BQ, 1]
    acc_sc,  # VMEM [BQ, D] f32
    m_sc,  # VMEM [BQ, 128] f32
    l_sc,  # VMEM [BQ, 128] f32
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    num_k: int,
    q_offset: int,
    kv_offset: int,
    window: int,
    softcap: float,
):
    from jax.experimental import pallas as pl

    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    # global positions of this block's rows/cols
    q_lo = q_offset + qi * block_q
    k_lo = kv_offset + ki * block_k

    def compute():
        # inputs stay bf16 for the MXU; accumulation is f32
        q = q_ref[0, 0]  # [BQ, D]
        k = k_ref[0, 0]  # [BK, D]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [BQ, BK] f32
        if softcap:
            s = softcap * jnp.tanh(s / softcap)  # cap raw scores, then mask
        if causal or window:
            rows = q_lo + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            cols = k_lo + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            keep = rows >= cols if causal else rows == rows
            if window:
                keep = jnp.logical_and(keep, rows - cols < window)
            s = jnp.where(keep, s, NEG_INF)
        m_prev = m_sc[:, :1]  # [BQ, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # rows with no unmasked key yet keep exp(NEG_INF - NEG_INF) at 0
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

    live = None
    if causal:  # skip blocks strictly above the diagonal
        live = q_lo + block_q - 1 >= k_lo
    if window:  # skip blocks entirely below the sliding window
        below = k_lo + block_k - 1 >= q_lo - (window - 1)
        live = below if live is None else jnp.logical_and(live, below)
    if live is not None:
        pl.when(live)(compute)
    else:
        compute()

    @pl.when(ki == num_k - 1)
    def _finish():
        l = l_sc[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_sc[...] / l_safe).astype(o_ref.dtype)
        m = m_sc[:, :1]
        lse = jnp.where(
            l == 0.0, jnp.full_like(m, NEG_INF), m + jnp.log(l_safe)
        )
        lse_ref[0, 0] = lse  # [BQ, 1]


def _flash_fwd(
    q: jax.Array,  # [B, H, Tq, D]
    k: jax.Array,  # [B, Hkv, Tk, D]
    v: jax.Array,
    causal: bool,
    scale: float,
    block_q: int,
    block_k: int,
    q_offset: int,
    kv_offset: int,
    interpret: bool,
    window: int = 0,
    softcap: float = 0.0,
) -> tuple[jax.Array, jax.Array]:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, tq, d = q.shape
    hkv, tk = k.shape[1], k.shape[2]
    group = h // hkv
    bq = _pick_block(tq, block_q)
    bk = _pick_block(tk, block_k)
    num_k = tk // bk

    kernel = functools.partial(
        _fwd_kernel,
        scale=scale,
        causal=causal,
        block_q=bq,
        block_k=bk,
        num_k=num_k,
        q_offset=q_offset,
        kv_offset=kv_offset,
        window=window,
        softcap=softcap,
    )
    # For causal grids, clamp the KV block index at the diagonal (and,
    # with a sliding window, from below): steps outside re-request the
    # same block, which pallas elides (no DMA), so skipped blocks cost
    # neither bandwidth nor compute.
    kv_ix = _causal_kv_clamp(causal, bq, bk, q_offset, kv_offset, num_k, window)
    o, lse = pl.pallas_call(
        kernel,
        grid=(b, h, tq // bq, num_k),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec(
                (1, 1, bk, d), lambda b, h, qi, ki: (b, h // group, kv_ix(qi, ki), 0)
            ),
            pl.BlockSpec(
                (1, 1, bk, d), lambda b, h, qi, ki: (b, h // group, kv_ix(qi, ki), 0)
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, qi, ki: (b, h, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h, tq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    return o, lse


def _causal_kv_clamp(causal, bq, bk, q_offset, kv_offset, num_k, window=0):
    """KV block index map for (qi, ki) grids: identity when non-causal,
    else clamped to the last block intersecting q block qi's diagonal
    (and, with a sliding window, to the first block inside the window)."""
    if not causal and not window:
        return lambda qi, ki: ki

    def ix(qi, ki):
        ix = ki
        if causal:
            last = (q_offset + (qi + 1) * bq - 1 - kv_offset) // bk
            ix = jnp.minimum(ix, jnp.clip(last, 0, num_k - 1))
        if window:
            first = (q_offset + qi * bq - (window - 1) - kv_offset) // bk
            ix = jnp.maximum(ix, jnp.clip(first, 0, num_k - 1))
        return ix

    return ix


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(
    q_ref,  # [1, 1, BQ, D]
    k_ref,  # [1, 1, BK, D]
    v_ref,  # [1, 1, BK, D]
    do_ref,  # [1, 1, BQ, D]
    lse_ref,  # [1, 1, BQ, 1]
    delta_ref,  # [1, 1, BQ, 1]
    dq_ref,  # [1, 1, BQ, D]
    acc_sc,  # VMEM [BQ, D] f32
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    num_k: int,
    q_offset: int,
    kv_offset: int,
    window: int,
    softcap: float,
):
    from jax.experimental import pallas as pl

    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q_lo = q_offset + qi * block_q
    k_lo = kv_offset + ki * block_k

    def compute():
        q = q_ref[0, 0]  # bf16 into the MXU, f32 accumulation
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]  # [BQ, 1]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if softcap:
            t = jnp.tanh(s / softcap)
            s = softcap * t
        if causal or window:
            rows = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            keep = rows >= cols if causal else rows == rows
            if window:
                keep = jnp.logical_and(keep, rows - cols < window)
            s = jnp.where(keep, s, NEG_INF)
        p = jnp.exp(s - jnp.where(lse <= NEG_INF / 2, 0.0, lse))
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [BQ, BK]
        ds = p * (dp - delta) * scale
        if softcap:  # d(softcap·tanh(s/softcap))/ds = 1 - tanh²
            ds = ds * (1.0 - t * t)
        acc_sc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    live = None
    if causal:
        live = q_lo + block_q - 1 >= k_lo
    if window:
        below = k_lo + block_k - 1 >= q_lo - (window - 1)
        live = below if live is None else jnp.logical_and(live, below)
    if live is not None:
        pl.when(live)(compute)
    else:
        compute()

    @pl.when(ki == num_k - 1)
    def _finish():
        dq_ref[0, 0] = acc_sc[...].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref,  # [1, 1, BQ, D]
    k_ref,  # [1, 1, BK, D]
    v_ref,  # [1, 1, BK, D]
    do_ref,  # [1, 1, BQ, D]
    lse_ref,  # [1, 1, BQ, 1]
    delta_ref,  # [1, 1, BQ, 1]
    dk_ref,  # [1, 1, BK, D]
    dv_ref,  # [1, 1, BK, D]
    dk_sc,  # VMEM [BK, D] f32
    dv_sc,  # VMEM [BK, D] f32
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    num_q: int,
    num_inner: int,
    q_offset: int,
    kv_offset: int,
    window: int,
    softcap: float,
):
    """dk/dv for one KV block.

    The innermost grid dim walks ``group × num_q`` — all query blocks of
    every query head in this KV head's GQA group — so the group sum
    accumulates in VMEM scratch and dk/dv come out at KV-head
    granularity directly (no [B, Hq, T, D] intermediates in HBM).
    """
    from jax.experimental import pallas as pl

    ki = pl.program_id(2)
    j = pl.program_id(3)  # j = g * num_q + qi
    qi = jax.lax.rem(j, num_q)

    @pl.when(j == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    q_lo = q_offset + qi * block_q
    k_lo = kv_offset + ki * block_k

    def compute():
        q = q_ref[0, 0]  # bf16 into the MXU, f32 accumulation
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        # [BQ, 1] → [1, BQ]: columns index q rows in the transposed scores
        lse = lse_ref[0, 0].reshape(1, block_q)
        delta = delta_ref[0, 0].reshape(1, block_q)
        # transposed scores: s_t[k, q] = scale * <k_k, q_q>
        s_t = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [BK, BQ]
        if softcap:
            t = jnp.tanh(s_t / softcap)
            s_t = softcap * t
        if causal or window:
            rows_k = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 0)
            cols_q = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 1)
            keep = cols_q >= rows_k if causal else rows_k == rows_k
            if window:
                keep = jnp.logical_and(keep, cols_q - rows_k < window)
            s_t = jnp.where(keep, s_t, NEG_INF)
        p_t = jnp.exp(s_t - jnp.where(lse <= NEG_INF / 2, 0.0, lse))
        p_t = jnp.where(s_t <= NEG_INF / 2, 0.0, p_t)
        dv_sc[...] += jax.lax.dot_general(
            p_t.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp_t = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [BK, BQ]
        ds_t = p_t * (dp_t - delta) * scale
        if softcap:  # d(softcap·tanh(s/softcap))/ds = 1 - tanh²
            ds_t = ds_t * (1.0 - t * t)
        dk_sc[...] += jax.lax.dot_general(
            ds_t.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    live = None
    if causal:
        live = q_lo + block_q - 1 >= k_lo
    if window:
        below = k_lo + block_k - 1 >= q_lo - (window - 1)
        live = below if live is None else jnp.logical_and(live, below)
    if live is not None:
        pl.when(live)(compute)
    else:
        compute()

    @pl.when(j == num_inner - 1)
    def _finish():
        dk_ref[0, 0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[...].astype(dv_ref.dtype)


def _flash_bwd(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    o: jax.Array,
    lse: jax.Array,
    do: jax.Array,
    causal: bool,
    scale: float,
    block_q: int,
    block_k: int,
    q_offset: int,
    kv_offset: int,
    interpret: bool,
    window: int = 0,
    softcap: float = 0.0,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, tq, d = q.shape
    hkv, tk = k.shape[1], k.shape[2]
    group = h // hkv
    bq = _pick_block(tq, block_q)
    bk = _pick_block(tk, block_k)
    num_q, num_k = tq // bq, tk // bk

    # delta_i = rowsum(dO_i * O_i) — one cheap fused elementwise pass
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True
    )  # [B, H, Tq, 1]

    dq_kernel = functools.partial(
        _dq_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
        num_k=num_k, q_offset=q_offset, kv_offset=kv_offset,
        window=window, softcap=softcap,
    )
    kv_ix = _causal_kv_clamp(causal, bq, bk, q_offset, kv_offset, num_k, window)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b, h, num_q, num_k),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec(
                (1, 1, bk, d), lambda b, h, qi, ki: (b, h // group, kv_ix(qi, ki), 0)
            ),
            pl.BlockSpec(
                (1, 1, bk, d), lambda b, h, qi, ki: (b, h // group, kv_ix(qi, ki), 0)
            ),
            pl.BlockSpec((1, 1, bq, d), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, qi, ki: (b, h, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_BWD_VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # dk/dv directly at KV-head granularity: the inner grid dim sweeps
    # group × num_q query blocks while dk/dv accumulate in VMEM scratch.
    num_inner = group * num_q
    dkv_kernel = functools.partial(
        _dkv_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
        num_q=num_q, num_inner=num_inner, q_offset=q_offset, kv_offset=kv_offset,
        window=window, softcap=softcap,
    )

    def _qh(j):
        # query head for inner step j: this KV head's group member j // num_q
        return j // num_q

    if causal or window:
        # clamp the q block index into [diagonal, window end]: steps
        # outside re-request the same block (DMA elided, compute skipped)
        def _qi(ki, j):
            ix = j % num_q
            if causal:
                first = (kv_offset + ki * bk - q_offset) // bq
                ix = jnp.maximum(ix, jnp.clip(first, 0, num_q - 1))
            if window:
                # last q row that can see this KV block's newest key
                last = (
                    kv_offset + (ki + 1) * bk - 1 + (window - 1) - q_offset
                ) // bq
                ix = jnp.minimum(ix, jnp.clip(last, 0, num_q - 1))
            return ix
    else:
        def _qi(ki, j):
            return j % num_q

    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(b, hkv, num_k, num_inner),
        in_specs=[
            pl.BlockSpec(
                (1, 1, bq, d),
                lambda b, hkv, ki, j: (b, hkv * group + _qh(j), _qi(ki, j), 0),
            ),
            pl.BlockSpec((1, 1, bk, d), lambda b, hkv, ki, j: (b, hkv, ki, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, hkv, ki, j: (b, hkv, ki, 0)),
            pl.BlockSpec(
                (1, 1, bq, d),
                lambda b, hkv, ki, j: (b, hkv * group + _qh(j), _qi(ki, j), 0),
            ),
            pl.BlockSpec(
                (1, 1, bq, 1),
                lambda b, hkv, ki, j: (b, hkv * group + _qh(j), _qi(ki, j), 0),
            ),
            pl.BlockSpec(
                (1, 1, bq, 1),
                lambda b, hkv, ki, j: (b, hkv * group + _qh(j), _qi(ki, j), 0),
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, d), lambda b, hkv, ki, j: (b, hkv, ki, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, hkv, ki, j: (b, hkv, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_BWD_VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11)
)
def _flash(
    q, k, v, causal, scale, block_q, block_k, q_offset, kv_offset, interpret,
    window, softcap,
):
    o, _ = _flash_fwd(
        q, k, v, causal, scale, block_q, block_k, q_offset, kv_offset,
        interpret, window, softcap,
    )
    return o


def _flash_fwd_rule(
    q, k, v, causal, scale, block_q, block_k, q_offset, kv_offset, interpret,
    window, softcap,
):
    from jax.ad_checkpoint import checkpoint_name

    o, lse = _flash_fwd(
        q, k, v, causal, scale, block_q, block_k, q_offset, kv_offset,
        interpret, window, softcap,
    )
    # Tag residuals so a rematerialized layer (llama.forward uses
    # save_only_these_names("flash_residuals")) saves them instead of
    # re-running the forward kernel inside the backward pass.
    res = checkpoint_name((q, k, v, o, lse), "flash_residuals")
    return o, res


def _flash_bwd_rule(
    causal, scale, block_q, block_k, q_offset, kv_offset, interpret,
    window, softcap, res, do,
):
    q, k, v, o, lse = res
    dq, dk, dv = _flash_bwd(
        q, k, v, o, lse, do, causal, scale, block_q, block_k,
        q_offset, kv_offset, interpret, window, softcap,
    )
    return dq, dk, dv


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(
    q: jax.Array,  # [B, H, Tq, D]
    k: jax.Array,  # [B, Hkv, Tk, D]
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 1024,
    block_k: int = 1024,
    q_offset: int = 0,
    kv_offset: int = 0,
    interpret: bool = False,
    window: int = 0,
    softcap: float = 0.0,
) -> jax.Array:
    """Differentiable flash attention (pallas, TPU).

    GQA-native: ``k``/``v`` may have fewer heads (``H % Hkv == 0``).
    ``q_offset``/``kv_offset`` give the global positions of row/col 0
    for causal masking across sequence shards (ring attention).
    ``window`` masks keys older than the sliding window (Mistral/Gemma2
    convention: key j visible to query i iff i - j < window); blocks
    entirely outside the window are skipped, so long-sequence windowed
    attention costs O(T·window) not O(T²). ``softcap`` applies the
    Gemma2 tanh score cap (with its exact gradient in the backward
    kernels).
    """
    b, h, t, d = q.shape
    assert h % k.shape[1] == 0, (h, k.shape[1])
    scale = float(scale) if scale is not None else d**-0.5
    return _flash(
        q, k, v, causal, scale, block_q, block_k, q_offset, kv_offset,
        interpret, window, softcap,
    )


def flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 1024,
    block_k: int = 1024,
    q_offset: int = 0,
    kv_offset: int = 0,
    interpret: bool = False,
    window: int = 0,
    softcap: float = 0.0,
) -> tuple[jax.Array, jax.Array]:
    """Forward-only variant returning (o, logsumexp [B, H, Tq] f32).

    Used by ring attention to merge per-shard partials; not
    differentiable directly (ring handles its own VJP).
    """
    d = q.shape[-1]
    scale = float(scale) if scale is not None else d**-0.5
    o, lse = _flash_fwd(
        q, k, v, causal, scale, block_q, block_k, q_offset, kv_offset,
        interpret, window, softcap,
    )
    return o, lse[..., 0]


def flash_supported(q: jax.Array, k: jax.Array) -> bool:
    """Whether shapes/platform allow the pallas kernel."""
    b, h, t, d = q.shape
    if jax.default_backend() != "tpu":
        return False
    return (
        d % 64 == 0
        and t % 128 == 0
        and k.shape[2] % 128 == 0
        and h % k.shape[1] == 0
    )
