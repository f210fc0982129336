"""Ring attention: exact sequence-parallel attention over the ``sp`` axis.

Long-context strategy (SURVEY.md §5 "long-context"): the sequence dim is
sharded across devices; each step every device computes blockwise
attention of its local Q shard against the currently-held KV shard, then
rotates KV around the ring with ``ppermute`` (ICI neighbor exchange —
bandwidth-optimal on a TPU torus). Online log-sum-exp merging keeps the
result exact (Liu et al., Ring Attention; blockwise softmax as in Flash
Attention). Compute/communication overlap is left to XLA's latency
hiding scheduler, which pipelines ppermute with the matmuls.

Two per-step implementations:

- **pallas** (default on TPU for tile-aligned shapes): the per-step
  block runs the flash kernels from :mod:`dstack_tpu.ops.flash` — no
  [Tq, Tk] score materialization, GQA KV rotates at KV-head width. The
  ring has its own custom VJP: the backward pass makes a second ring
  sweep in which dk/dv accumulators travel with their KV blocks a full
  circle back to the owning device. Causal sliding windows run a
  Python-unrolled variant (static per-step offsets feed the kernel's
  window mask; out-of-window steps are elided at trace time →
  O(T·window)).
- **xla** fallback (CPU tests, virtual meshes, non-tiling shapes,
  non-causal windows): einsum blockwise softmax.

Causality is handled per ring step: blocks from earlier shards attend
fully, the diagonal step uses the causal kernel, later shards are
skipped (a `lax.switch` on the dynamic source index).

No NCCL analog exists or is needed: this *is* the distributed
communication backend for the sequence dimension.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from dstack_tpu.ops.flash import _flash_bwd, _flash_fwd

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# XLA fallback path (small/odd shapes, CPU virtual meshes)
# ---------------------------------------------------------------------------


def _block_attention(
    q: jax.Array,  # [B, H, Tq, D]
    k: jax.Array,  # [B, H, Tk, D]
    v: jax.Array,  # [B, H, Tk, D]
    bias: Optional[jax.Array],  # broadcastable to [B, H, Tq, Tk] or None
    scale: float,
    softcap: float = 0.0,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One KV-block of attention → (unnormalized out, running max, denom)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)  # cap raw scores, then mask
    if bias is not None:
        s = s + bias
    m = jnp.max(s, axis=-1)  # [B, H, Tq]
    # Guard fully-masked rows: exp(NEG_INF - NEG_INF) would be 1.
    m_safe = jnp.where(m <= NEG_INF / 2, 0.0, m)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    l = jnp.sum(p, axis=-1)  # [B, H, Tq]
    o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)
    return o.astype(jnp.float32), m_safe, l


def _merge(o1, m1, l1, o2, m2, l2):
    """Merge two blockwise-softmax partials (log-sum-exp combine)."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    l = l1 * a1 + l2 * a2
    o = o1 * a1[..., None] + o2 * a2[..., None]
    return o, m, l


def _mask_bias(
    tq: int, tk: int, q_offset, k_offset, causal: bool, window: int,
    dtype=jnp.float32,
) -> jax.Array:
    """Causal/sliding-window mask bias for Q rows [q_offset, q_offset+tq)
    vs K cols [k_offset, k_offset+tk) in global coordinates (offsets may
    be traced — ring step indices are)."""
    qi = q_offset + jnp.arange(tq)[:, None]
    kj = k_offset + jnp.arange(tk)[None, :]
    keep = (qi >= kj) if causal else jnp.ones((tq, tk), bool)
    if window:
        keep = keep & (qi - kj < window)
    return jnp.where(keep, 0.0, NEG_INF).astype(dtype)[None, None]


def _ring_xla_local(
    sp: int, axis_name: str, causal: bool, scale: float,
    window: int = 0, softcap: float = 0.0,
):
    """Per-shard ring attention body, einsum blocks (KV at full Q heads)."""

    def local_fn(q, k, v):
        idx = jax.lax.axis_index(axis_name)
        t_local = q.shape[2]  # per-shard sequence length
        q32 = q.astype(jnp.float32)

        def step(carry, r):
            o, m, l, kb, vb = carry
            # KV block currently held originated at ring position (idx - r) % sp
            src = (idx - r) % sp
            if causal or window:
                bias = _mask_bias(
                    t_local, t_local, idx * t_local, src * t_local,
                    causal, window,
                )
            else:
                bias = None
            ob, mb, lb = _block_attention(q32, kb, vb, bias, scale, softcap)
            o, m, l = _merge(o, m, l, ob, mb, lb)
            # rotate KV to the next device (ring neighbor over ICI)
            perm = [(i, (i + 1) % sp) for i in range(sp)]
            kb = jax.lax.ppermute(kb, axis_name, perm)
            vb = jax.lax.ppermute(vb, axis_name, perm)
            return (o, m, l, kb, vb), None

        o0 = jnp.zeros(q.shape[:3] + (v.shape[-1],), jnp.float32)
        m0 = jnp.full(q.shape[:3], NEG_INF, jnp.float32)
        l0 = jnp.zeros(q.shape[:3], jnp.float32)
        (o, m, l, _, _), _ = jax.lax.scan(
            step, (o0, m0, l0, k, v), jnp.arange(sp)
        )
        l = jnp.where(l == 0.0, 1.0, l)
        return (o / l[..., None]).astype(q.dtype)

    return local_fn


# ---------------------------------------------------------------------------
# pallas path: flash kernels per ring step, custom VJP
# ---------------------------------------------------------------------------


def _merge_lse(o, lse, o2, lse2):
    """Merge normalized partials by logsumexp weights.

    o/o2 [B, H, T, D] f32 (o2 may be model dtype), lse/lse2 [B, H, T, 1].
    """
    m = jnp.maximum(lse, lse2)
    m_safe = jnp.where(m <= NEG_INF / 2, 0.0, m)
    w1 = jnp.where(lse <= NEG_INF / 2, 0.0, jnp.exp(lse - m_safe))
    w2 = jnp.where(lse2 <= NEG_INF / 2, 0.0, jnp.exp(lse2 - m_safe))
    denom = w1 + w2
    denom = jnp.where(denom == 0.0, 1.0, denom)
    o_new = (o * w1 + o2.astype(jnp.float32) * w2) / denom
    lse_new = m_safe + jnp.log(denom)
    lse_new = jnp.where(m <= NEG_INF / 2, jnp.full_like(m, NEG_INF), lse_new)
    return o_new, lse_new


def _make_ring_pallas(
    sp: int,
    axis_name: str,
    causal: bool,
    scale: float,
    block_q: int,
    block_k: int,
    interpret: bool,
    softcap: float = 0.0,
):
    perm = [(i, (i + 1) % sp) for i in range(sp)]
    kw = dict(
        block_q=block_q, block_k=block_k, q_offset=0, kv_offset=0,
        interpret=interpret, softcap=softcap,
    )

    def branch_index(src, idx):
        if not causal:
            return jnp.int32(1)  # always full attention
        return jnp.where(src > idx, 0, jnp.where(src < idx, 1, 2))

    @jax.custom_vjp
    def ring(q, k, v):
        o, _ = _ring_fwd(q, k, v)
        return o

    def _ring_fwd(q, k, v):
        idx = jax.lax.axis_index(axis_name)
        b, h, tl, d = q.shape

        def f_skip(q, kb, vb):
            return (
                jnp.zeros(q.shape, q.dtype),
                jnp.full((b, h, tl, 1), NEG_INF, jnp.float32),
            )

        def f_full(q, kb, vb):
            return _flash_fwd(q, kb, vb, False, scale, **kw)

        def f_diag(q, kb, vb):
            return _flash_fwd(q, kb, vb, True, scale, **kw)

        def step(carry, r):
            o, lse, kb, vb = carry
            src = (idx - r) % sp
            ob, lseb = jax.lax.switch(
                branch_index(src, idx), (f_skip, f_full, f_diag), q, kb, vb
            )
            o, lse = _merge_lse(o, lse, ob, lseb)
            kb = jax.lax.ppermute(kb, axis_name, perm)
            vb = jax.lax.ppermute(vb, axis_name, perm)
            return (o, lse, kb, vb), None

        o0 = jnp.zeros(q.shape, jnp.float32)
        lse0 = jnp.full((b, h, tl, 1), NEG_INF, jnp.float32)
        (o, lse, _, _), _ = jax.lax.scan(step, (o0, lse0, k, v), jnp.arange(sp))
        return o.astype(q.dtype), lse

    def ring_fwd(q, k, v):
        o, lse = _ring_fwd(q, k, v)
        return o, (q, k, v, o, lse)

    def ring_bwd(res, do):
        q, k, v, o, lse = res
        idx = jax.lax.axis_index(axis_name)

        def b_skip(q, kb, vb):
            return (
                jnp.zeros(q.shape, q.dtype),
                jnp.zeros(kb.shape, kb.dtype),
                jnp.zeros(vb.shape, vb.dtype),
            )

        def b_full(q, kb, vb):
            return _flash_bwd(q, kb, vb, o, lse, do, False, scale, **kw)

        def b_diag(q, kb, vb):
            return _flash_bwd(q, kb, vb, o, lse, do, True, scale, **kw)

        def step(carry, r):
            dq, kb, vb, dkb, dvb = carry
            src = (idx - r) % sp
            dq_p, dk_p, dv_p = jax.lax.switch(
                branch_index(src, idx), (b_skip, b_full, b_diag), q, kb, vb
            )
            dq = dq + dq_p.astype(jnp.float32)
            dkb = dkb + dk_p.astype(jnp.float32)
            dvb = dvb + dv_p.astype(jnp.float32)
            # rotate KV *and* their gradient accumulators; after sp
            # rotations the dk/dv buffers land back on the owner.
            kb, vb, dkb, dvb = (
                jax.lax.ppermute(x, axis_name, perm) for x in (kb, vb, dkb, dvb)
            )
            return (dq, kb, vb, dkb, dvb), None

        dq0 = jnp.zeros(q.shape, jnp.float32)
        dk0 = jnp.zeros(k.shape, jnp.float32)
        dv0 = jnp.zeros(v.shape, jnp.float32)
        (dq, _, _, dk, dv), _ = jax.lax.scan(
            step, (dq0, k, v, dk0, dv0), jnp.arange(sp)
        )
        return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)

    ring.defvjp(ring_fwd, ring_bwd)
    return ring


def _ring_live_steps(sp: int, t_local: int, window: int) -> int:
    """Ring steps that can contain in-window pairs. Step r's nearest
    (q, k) distance is ``r*t_local - (t_local - 1)``; once that reaches
    the window, the step — and every later one — is all-masked and can
    be skipped STATICALLY. This is what makes windowed sp attention
    O(T·window) instead of O(T²/sp)."""
    if not window:
        return sp
    return min(sp, max(1, -(-(window - 1) // t_local) + 1))


def _make_ring_pallas_window(
    sp: int,
    axis_name: str,
    scale: float,
    block_q: int,
    block_k: int,
    interpret: bool,
    window: int,
    softcap: float,
    t_local: int,
):
    """Causal sliding-window ring on the flash kernels.

    The scan-based ring can't express windows (kernel offsets are
    static parameters), but the RELATIVE offset between the local Q
    shard and ring step ``r``'s KV block is ``r*t_local`` for every
    device that keeps the step — static per step. So the ring unrolls
    in Python: each step calls the kernel with its own static
    ``q_offset``, devices that received a wrapped (future) block skip
    via ``lax.cond``, and steps entirely beyond the window are elided
    at trace time. The backward sweep fast-forwards the dk/dv
    accumulators home with ONE shifted ppermute instead of rotating
    through the skipped steps.
    """
    perm = [(i, (i + 1) % sp) for i in range(sp)]
    r_live = _ring_live_steps(sp, t_local, window)
    kw = dict(
        block_q=block_q, block_k=block_k, kv_offset=0,
        interpret=interpret, window=window, softcap=softcap,
    )

    @jax.custom_vjp
    def ring(q, k, v):
        o, _ = _ring_fwd(q, k, v)
        return o

    def _ring_fwd(q, k, v):
        idx = jax.lax.axis_index(axis_name)
        b, h, tl, d = q.shape

        def f_skip(q, kb, vb):
            return (
                jnp.zeros(q.shape, q.dtype),
                jnp.full((b, h, tl, 1), NEG_INF, jnp.float32),
            )

        # r = 0: the diagonal block (causal + window inside the shard)
        o, lse = _flash_fwd(q, k, v, True, scale, q_offset=0, **kw)
        o = o.astype(jnp.float32)
        kb, vb = k, v
        for r in range(1, r_live):
            kb = jax.lax.ppermute(kb, axis_name, perm)
            vb = jax.lax.ppermute(vb, axis_name, perm)

            def f_run(q, kb, vb, _r=r):
                # past block at static distance _r*t_local: causality
                # holds for every pair, the window masks the far end
                return _flash_fwd(
                    q, kb, vb, False, scale, q_offset=_r * t_local, **kw
                )

            ob, lseb = jax.lax.cond(idx >= r, f_run, f_skip, q, kb, vb)
            o, lse = _merge_lse(o, lse, ob, lseb)
        return o.astype(q.dtype), lse

    def ring_fwd(q, k, v):
        o, lse = _ring_fwd(q, k, v)
        return o, (q, k, v, o, lse)

    def ring_bwd(res, do):
        q, k, v, o, lse = res
        idx = jax.lax.axis_index(axis_name)

        def b_skip(q, kb, vb):
            return (
                jnp.zeros(q.shape, q.dtype),
                jnp.zeros(kb.shape, kb.dtype),
                jnp.zeros(vb.shape, vb.dtype),
            )

        dq_p, dk_p, dv_p = _flash_bwd(
            q, k, v, o, lse, do, True, scale, q_offset=0, **kw
        )
        dq = dq_p.astype(jnp.float32)
        dkb = dk_p.astype(jnp.float32)
        dvb = dv_p.astype(jnp.float32)
        kb, vb = k, v
        for r in range(1, r_live):
            kb, vb, dkb, dvb = (
                jax.lax.ppermute(x, axis_name, perm)
                for x in (kb, vb, dkb, dvb)
            )

            def b_run(q, kb, vb, _r=r):
                return _flash_bwd(
                    q, kb, vb, o, lse, do, False, scale,
                    q_offset=_r * t_local, **kw
                )

            dq_p, dk_p, dv_p = jax.lax.cond(idx >= r, b_run, b_skip, q, kb, vb)
            dq = dq + dq_p.astype(jnp.float32)
            dkb = dkb + dk_p.astype(jnp.float32)
            dvb = dvb + dv_p.astype(jnp.float32)
        shift = sp - (r_live - 1)
        if shift % sp:
            # fast-forward the accumulators the rest of the way home in
            # one hop (the elided steps would only have rotated them)
            fperm = [(i, (i + shift) % sp) for i in range(sp)]
            dkb = jax.lax.ppermute(dkb, axis_name, fperm)
            dvb = jax.lax.ppermute(dvb, axis_name, fperm)
        return dq.astype(q.dtype), dkb.astype(k.dtype), dvb.astype(v.dtype)

    ring.defvjp(ring_fwd, ring_bwd)
    return ring


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------


def _pallas_ok(
    h: int, hkv: int, t_local: int, d: int, interpret: bool, window: int,
    causal: bool = True,
) -> bool:
    if not interpret and jax.default_backend() != "tpu":
        return False
    if window and not causal:
        # non-causal windows need signed (wrapped) offsets per device;
        # only the XLA ring expresses those. Causal windows run on the
        # unrolled pallas ring (static per-step offsets).
        return False
    return d % 64 == 0 and t_local % 128 == 0 and h % hkv == 0


def ring_attention(
    q: jax.Array,  # [B, H, T, D] — seq sharded over "sp"
    k: jax.Array,  # [B, Hkv, T, D]
    v: jax.Array,  # [B, Hkv, T, D]
    *,
    mesh: Mesh,
    causal: bool = True,
    scale: Optional[float] = None,
    axis_name: str = "sp",
    impl: Optional[str] = None,  # None=auto | "pallas" | "xla"
    block_q: int = 1024,
    block_k: int = 1024,
    interpret: bool = False,
    window: int = 0,  # sliding window (global token coordinates)
    softcap: float = 0.0,  # Gemma2 tanh score cap
) -> jax.Array:
    """Exact multi-device attention with KV rotating around the ``sp`` ring.

    Inputs/outputs are *global* arrays (sharded over ``axis_name`` on the
    sequence dim); internally runs as shard_map.
    """
    sp = mesh.shape[axis_name]
    if sp == 1:
        from dstack_tpu.ops.attention import attention as local_attention

        return local_attention(
            q, k, v, causal=causal, scale=scale, window=window, softcap=softcap
        )

    scale = float(scale) if scale is not None else q.shape[-1] ** -0.5
    t_local = q.shape[2] // sp
    if impl == "pallas" and window and not causal:
        raise ValueError(
            "ring_attention: non-causal sliding window requires the "
            "xla path (wrapped offsets are signed per device)"
        )
    use_pallas = impl == "pallas" or (
        impl is None
        and _pallas_ok(
            q.shape[1], k.shape[1], t_local, q.shape[3], interpret, window,
            causal,
        )
    )

    if use_pallas:
        # GQA KV stays at KV-head width: the flash kernels group
        # natively, and the ring rotates the smaller buffers.
        if window:
            local_fn = _make_ring_pallas_window(
                sp, axis_name, scale, block_q, block_k, interpret,
                window, softcap, t_local,
            )
        else:
            local_fn = _make_ring_pallas(
                sp, axis_name, causal, scale, block_q, block_k, interpret,
                softcap=softcap,
            )
    else:
        if k.shape[1] != q.shape[1]:  # GQA: expand KV heads before the ring
            assert q.shape[1] % k.shape[1] == 0
            rep = q.shape[1] // k.shape[1]
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
        local_fn = _ring_xla_local(
            sp, axis_name, causal, scale, window=window, softcap=softcap
        )

    spec = P(None, None, axis_name, None)  # seq sharded; heads follow outer
    return jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)
