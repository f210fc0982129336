"""Flash decode for serving: a pallas kernel for batched one-token GQA
attention over the slot KV cache, which reads the keys each slot holds.

The einsum form of the engine's decode attention goes over the FULL
cache row ``[B, Hkv, Tmax, D]`` under a ``kj <= position`` mask
(serve/engine.py::_decode_layer): every step streams ``Tmax`` keys a
slot from HBM, whatever the slot holds and whether or not it is live.
Decode is HBM-bandwidth-bound, so that read is the cost that grows with
``max_seq`` and with the slots reserved, not with the traffic served.

This kernel makes the read *ragged*: per-slot ``positions`` ride the
scalar-prefetch lane, and the KV block index map sends the block
indices past a slot's length to a block that is fetched anyway —
pallas elides the repeated DMA (same trick as the causal clamp in
ops/flash.py), so the unwritten tail of every cache row costs neither
bandwidth nor compute. That block is the first of the next slot that
holds keys: its DMA then runs under grid steps that compute nothing.
A slot that holds nothing (finished, empty, mid-prefill: the engine
hands it length 0) requests the same, so it moves no byte at all.

What it reads is the STACKED cache leaf ``[L, B, Hkv, T, D]`` in place:
the layer's row rides the scalar-prefetch lane too and is one more
coordinate of the block index. A kernel fed a layer's slice of a
scanned stack makes the compiler copy that slice out a layer a token.
A grid step takes all KV heads of one block of keys (``block_keys``:
about 2 MiB of K and V), since a step costs about half a microsecond
whatever it moves (PERF.md §6, PR 43: 2.9 µs a live step of 2 MiB,
724 GB/s over whole rows, 0.44-0.55 µs a step that moves nothing).

The blocks have TWO forms, taken by how the leaf lies in device memory
(:func:`tokens_on_lanes`, no flag). A head that fills the 128 lanes
(``D`` % 128 == 0) lies as it is declared and a block is ``[Hkv, BK,
D]``: scores ``q · kᵀ``, values ``p · v``. A narrower head (``D`` 64)
lies ``[.., D, T]``, its TOKENS on the lanes, and a Mosaic operand is
taken in the row-major order of its logical shape: fed that leaf as
declared, the first form makes the compiler re-lay both leaves out
whole, a token step (device-free: ``temp`` 2.1-5.5 GB). So the kernel
is handed ``swapaxes(leaf, -1, -2)``, the order the leaf already has
(a bitcast in the compiled program), and a block is ``[Hkv, D, BK]``
with the keys on the lanes: scores ``q · k``, values ``p · vᵀ`` (the
shape ``q · kᵀ`` has in the first form), the same running softmax,
masks, index map and scales (which lie ``[.., Hkv, T]`` either way).

Supported in-kernel (mirroring the einsum's semantics):
- GQA grouping: q arrives ``[B, Hkv, G, D]``, the cache is streamed
  once at KV width (no G× read amplification).
- the token's own key, not yet in the cache (``k_new`` / ``v_new``):
  the engine's decode scan only reads the cache and writes all layers'
  rows after it, so the new key starts the running softmax and the
  cache is masked at ``kj < position``.
- int8 KV: the cache blocks load as int8 with their per-(token, head)
  f32 scales, which multiply the scores and the probabilities in VMEM
  — HBM traffic stays int8, the point of ``kv_quant="int8"``.
- sliding window as a TRACED value (per-layer windows ride the
  lax.scan over layers): masked in-kernel, and leading blocks wholly
  below the window are clamp-skipped like the tail.
- tanh softcap (static), attention sinks (gpt-oss: a learned logit in
  the softmax denominator only, applied at the finish step).
- speculative verify (``rows_per_slot`` = S rows a slot, their keys
  already written).

Not supported (the engine keeps the einsum, :func:`reads_live_keys`):
MLA latent caches, a window layer's ring (read in row order), Llama4
chunked-attention layers.

The reference framework has no serving kernels to mirror (it is an
orchestrator, SURVEY.md §6); the GPU-world analog of this kernel is
paged/ragged decode attention in TPU serving stacks.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30

#: K and V bytes one grid step moves, at most (see :func:`block_keys`)
BLOCK_BYTES = 2 << 20


def block_keys(n_kv_heads: int, head_dim: int, rows: int, itemsize: int = 2) -> int:
    """Keys a grid step reads of every KV head → the ONE block rule: as
    many as make K and V of the step :data:`BLOCK_BYTES` (a step's fixed
    cost is then a tenth of its DMA, and a short context is rounded up
    by a few hundred keys at most), a multiple of 128 that divides the
    cache row."""
    most = BLOCK_BYTES // (2 * n_kv_heads * head_dim * itemsize)
    bk = max(128, min(rows, most) // 128 * 128)
    while rows % bk:
        bk -= 128
    return bk


def tokens_on_lanes(width: int) -> bool:
    """Whether a cache leaf [..., T, width] lies in device memory with
    its TOKENS on the 128 lanes: the TPU compiler's own choice for a
    minor axis that does not fill them (head_dim 64, the latent's 576,
    a window latent's 1088), since it wastes no lane. What holds in
    place on such a leaf is the other form of what holds on a leaf
    with its width on the lanes (head_dim 128): this kernel's blocks,
    and the engine's writes (``serve/engine.py``)."""
    return width % 128 != 0


def _live_blocks(pos, win, held, block_k: int, num_k: int):
    """(first, last) key block a slot at query position ``pos`` reads,
    its cache holding the keys below ``held``, under window ``win`` (0 =
    full; its lower bound is row 0's, the loosest that covers every
    row): the ONE reckoning of the kernel's compute range and of the
    index map that fetches it."""
    last = jnp.clip((held - 1) // block_k, 0, num_k - 1)
    first = jnp.where(
        win > 0, jnp.clip((pos - (win - 1)) // block_k, 0, num_k - 1), 0
    )
    return first, last


def _decode_kernel(
    pos_ref,  # SMEM [B] int32: the slot's query position
    tail_ref,  # SMEM [B] int32: the slot whose block this slot's steps past its keys request (index map only)
    meta_ref,  # SMEM [2] int32: the layer's row of the stack, the sliding window (0 = full)
    q_ref,  # [1, Hkv, R, D]
    k_ref,  # [1, 1, Hkv, BK, D] compute dtype or int8 ([1, 1, Hkv, D, BK]: ``keys_minor``)
    v_ref,
    *rest,  # optional (kn_ref, vn_ref [1, Hkv, 1, D]), optional (ks_ref, vs_ref [1, 1, Hkv, BK] f32), optional (sink_ref [Hkv, R, 1] f32), then o_ref + scratch
    scale: float,
    softcap: float,
    block_k: int,
    num_k: int,
    new_row: bool,
    quantized: bool,
    sinks: bool,
    rows_per_slot: int,
    keys_minor: bool,
):
    from jax.experimental import pallas as pl

    it = iter(rest)
    kn_ref = next(it) if new_row else None
    vn_ref = next(it) if new_row else None
    ks_ref = next(it) if quantized else None
    vs_ref = next(it) if quantized else None
    sink_ref = next(it) if sinks else None
    o_ref = next(it)
    acc_sc = next(it)  # VMEM [Hkv, R, D] f32
    m_sc = next(it)  # VMEM [Hkv, R, 128] f32
    l_sc = next(it)  # VMEM [Hkv, R, 128] f32

    b = pl.program_id(0)
    ki = pl.program_id(1)
    pos = pos_ref[b]
    win = meta_ref[1]
    nrows = q_ref.shape[2]

    def capped(s):
        return softcap * jnp.tanh(s / softcap) if softcap else s

    # every KV head at once, as batched products and whole-array
    # updates: eight copies of the body, a head each, cost a serving
    # program's boot a second and a half a kernel it holds (trace and
    # lowering, paid on a compile-cache hit too), once a program

    @pl.when(ki == 0)
    def _init():
        if not new_row:
            acc_sc[...] = jnp.zeros_like(acc_sc)
            m_sc[...] = jnp.full_like(m_sc, NEG_INF)
            l_sc[...] = jnp.zeros_like(l_sc)
            return
        # the token's own key is always visible to it: the running
        # softmax starts from it (max = its score, sum = 1, values = v)
        s_new = capped(jnp.sum(
            q_ref[0].astype(jnp.float32) * kn_ref[0].astype(jnp.float32),
            axis=-1, keepdims=True,
        ) * scale)  # [Hkv, R, 1]
        m_sc[...] = jnp.broadcast_to(s_new, m_sc.shape)
        l_sc[...] = jnp.ones(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.broadcast_to(vn_ref[0].astype(jnp.float32), acc_sc.shape)

    # live block range for this slot (the index map's: steps outside it
    # request a block that is fetched anyway and skip compute). The
    # cache holds keys below ``held``: up to the last drafted position
    # where the rows' keys are written (speculative verify,
    # rows_per_slot = S), below the position where the new key comes
    # beside it.
    held = pos + rows_per_slot - (1 if new_row else 0)
    first, last = _live_blocks(pos, win, held, block_k, num_k)
    live = jnp.logical_and(
        jnp.logical_and(ki >= first, ki <= last), held > 0
    )

    def compute():
        cols = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, nrows, block_k), 2
        )
        # rows are [G, S] flattened row-major: row r verifies the
        # token at pos + (r % S), so it sees keys up to there
        qpos = pos + jax.lax.broadcasted_iota(
            jnp.int32, (1, nrows, block_k), 1
        ) % rows_per_slot
        keep = cols < qpos if new_row else cols <= qpos
        keep = jnp.logical_and(
            keep, jnp.logical_or(win == 0, qpos - cols < win)
        )
        q = q_ref[0]  # [Hkv, R, D]
        k = k_ref[0, 0]  # [Hkv, BK, D], or [Hkv, D, BK] with the keys on the lanes
        v = v_ref[0, 0]
        # the axis of a block the keys lie on: q · kᵀ and p · v where it
        # is the rows, q · k and p · vᵀ where it is the lanes
        k_d, v_t = (1, 2) if keys_minor else (2, 1)
        if quantized:
            # int8 values are exact in the compute dtype; the
            # per-token scales multiply the scores and the
            # probabilities (a row of lanes a head), not K and V
            k, v = k.astype(q.dtype), v.astype(q.dtype)
        s = jax.lax.dot_general(
            q, k, (((2,), (k_d,)), ((0,), (0,))), preferred_element_type=jnp.float32
        )  # [Hkv, R, BK] f32
        if quantized:
            s = s * ks_ref[0, 0][:, None, :]
        s = jnp.where(keep, capped(s * scale), NEG_INF)
        m_prev = m_sc[...][:, :, :1]  # [Hkv, R, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(jnp.where(s <= NEG_INF / 2, NEG_INF, s) - m_safe)
        alpha = jnp.where(
            m_prev <= NEG_INF / 2, jnp.zeros_like(m_prev), jnp.exp(m_prev - m_safe)
        )
        l_new = l_sc[...][:, :, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if quantized:
            p = p * vs_ref[0, 0][:, None, :]
        acc_sc[...] = acc_sc[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (v_t,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        l_sc[...] = jnp.broadcast_to(l_new, l_sc.shape)
        m_sc[...] = jnp.broadcast_to(m_new, m_sc.shape)

    pl.when(live)(compute)

    @pl.when(ki == num_k - 1)
    def _finish():
        m = m_sc[...][:, :, :1]
        l = l_sc[...][:, :, :1]
        acc = acc_sc[...]
        if sinks:
            # the sink joins the DENOMINATOR only (ops/attention.py::
            # sink_softmax): rescale running stats to max(m, sink)
            snk = sink_ref[...]  # [Hkv, R, 1] f32
            m_f = jnp.maximum(m, snk)
            alpha = jnp.where(
                m <= NEG_INF / 2, jnp.zeros_like(m), jnp.exp(m - m_f)
            )
            l = l * alpha + jnp.exp(snk - m_f)
            acc = acc * alpha
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc / l_safe).astype(o_ref.dtype)


def flash_decode(
    q: jax.Array,  # [B, Hkv, G, D] compute dtype
    k: jax.Array,  # [L, B, Hkv, T, D] (or one layer's [B, Hkv, T, D]) compute dtype, or int8 with k_scale
    v: jax.Array,
    positions: jax.Array,  # [B] int32: each slot's query position
    *,
    scale: float,
    layer=0,  # (traced) int32: the row of the stacked ``k`` / ``v`` to read
    k_new: Optional[jax.Array] = None,  # [B, Hkv, 1, D]: the token's own key, not in the cache
    v_new: Optional[jax.Array] = None,
    window: Optional[jax.Array] = None,  # traced int32 scalar; None/0 = full
    softcap: float = 0.0,
    sinks: Optional[jax.Array] = None,  # [Hkv, G] sink logits
    k_scale: Optional[jax.Array] = None,  # [L, B, Hkv, T] / [B, Hkv, T] f32 (int8 cache)
    v_scale: Optional[jax.Array] = None,
    block_k: Optional[int] = None,  # None: :func:`block_keys`
    interpret: bool = False,
    rows_per_slot: int = 1,
) -> jax.Array:
    """One-token-per-slot GQA attention over the cache → [B, Hkv, G, D].

    Ragged: each slot reads only the KV blocks that hold its keys (and,
    with a window, only blocks inside it), out of row ``layer`` of the
    stacked leaf where it lies.

    Without ``k_new`` the cache holds the token's own key: a slot
    attends to keys ``<= positions[b]``. With it the cache holds the
    keys ``< positions[b]`` and the token's own comes as an operand (a
    decode scan that writes its rows after the layers); a slot at
    position 0 then reads nothing: what the engine hands a slot that is
    not live.

    ``rows_per_slot=S`` serves speculative verify: ``q``'s row axis is
    ``[G, S]`` flattened row-major, row ``g*S + s`` attends to keys
    ``<= positions[b] + s`` (the engine scatters the S candidate K/V
    into the cache before calling). ``sinks`` must then be pre-expanded
    to ``[Hkv, G*S]``.
    """
    if k.ndim == 4:  # one layer's slice: a stack of one
        k, v = k[None], v[None]
        if k_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
    hkv, d, t = q.shape[1], q.shape[3], k.shape[3]
    if t % 128:
        raise ValueError(
            f"flash_decode: cache length {t} must be a multiple of 128 "
            "(gate callers with flash_decode_supported)"
        )
    if k_new is not None and rows_per_slot != 1:
        raise ValueError("flash_decode: k_new goes with one row a slot")
    bk = min(block_k or block_keys(hkv, d, t, k.dtype.itemsize), t)
    while t % bk:
        bk -= 128
    meta = jnp.stack([
        jnp.asarray(layer, jnp.int32).reshape(()),
        jnp.asarray(0 if window is None else window, jnp.int32).reshape(()),
    ])
    return _flash_decode(
        q, k, v, positions.astype(jnp.int32), meta, k_new, v_new, sinks,
        k_scale, v_scale, scale=scale, softcap=softcap, block_k=bk,
        interpret=interpret, rows_per_slot=rows_per_slot,
    )


@functools.partial(
    jax.jit,
    static_argnames=("scale", "softcap", "block_k", "interpret", "rows_per_slot"),
)
def _flash_decode(
    q, k, v, pos_arr, meta, k_new, v_new, sinks, k_scale, v_scale, *,
    scale, softcap, block_k, interpret, rows_per_slot,
):
    """:func:`flash_decode` behind its own ``jit``: a serving program
    that attends through the kernel in more than one layer scan (a model
    of layer groups: a prelude and a period) then lowers ONE copy of it,
    and a boot pays the kernel's lowering (a third of a second on a
    serving host, compile-cache hit or not) once a program."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, hkv, g, d = q.shape
    t, bk = k.shape[3], block_k
    num_k = t // bk
    # a leaf whose width does not fill the lanes lies [.., D, T] in
    # device memory: asked for in that order, the transposition is a
    # bitcast and the kernel's blocks are slices of the leaf where it is
    keys_minor = tokens_on_lanes(d)
    if keys_minor:
        k, v = jnp.swapaxes(k, 3, 4), jnp.swapaxes(v, 3, 4)
    quantized = k_scale is not None
    new_row = k_new is not None
    held_off = rows_per_slot - (1 if new_row else 0)
    # what a slot's steps past its last block (all steps of a slot that
    # holds no key) request: the FIRST block of the next slot that holds
    # some, so that its DMA runs under the steps that compute nothing
    # and is not waited for when that slot's turn comes; after the last
    # such slot, the block that one ended on (slot 0's before any): no
    # DMA at all
    slot = jnp.arange(b, dtype=jnp.int32)
    has = pos_arr + held_off > 0
    after = jnp.append(jax.lax.cummin(jnp.where(has, slot, b), reverse=True)[1:], b)
    before = jnp.maximum(jax.lax.cummax(jnp.where(has, slot, -1)), 0)
    tail = jnp.where(after < b, after, before).astype(jnp.int32)

    def key_ix(bi, ki, pos_ref, tail_ref, meta_ref):
        # (layer, slot, head, key block) a grid step asks for, by the
        # kernel's `live` range: leading out-of-window blocks clamp
        # to the first live block (one DMA, re-requested at no cost),
        # blocks past the last go to ``tail``'s (a later slot's first
        # block, else an earlier one's last)
        def blocks(at):
            return _live_blocks(
                pos_ref[at], meta_ref[1], pos_ref[at] + held_off, bk, num_k
            )

        first, last = blocks(bi)
        own = jnp.logical_and(pos_ref[bi] + held_off > 0, ki <= last)
        at = tail_ref[bi]
        t_first, t_last = blocks(at)
        return (
            meta_ref[0], jnp.where(own, bi, at), 0,
            jnp.where(own, jnp.maximum(ki, first), jnp.where(at > bi, t_first, t_last)),
        )

    def _kv_ix(*at):
        layer, slot, head, blk = key_ix(*at)
        return (layer, slot, head) + ((0, blk) if keys_minor else (blk, 0))

    def q_ix(bi, ki, *_):
        return (bi, 0, 0, 0)

    kv_block = (1, 1, hkv, d, bk) if keys_minor else (1, 1, hkv, bk, d)
    in_specs = [
        pl.BlockSpec((1, hkv, g, d), q_ix),
        pl.BlockSpec(kv_block, _kv_ix),
        pl.BlockSpec(kv_block, _kv_ix),
    ]
    args = [q, k, v]
    if new_row:
        in_specs += [pl.BlockSpec((1, hkv, 1, d), q_ix)] * 2
        args += [k_new.reshape(b, hkv, 1, d), v_new.reshape(b, hkv, 1, d)]
    # Mosaic wants a block's last two dims to be multiples of (8, 128)
    # or to span the array: the scales ride as they lie, [.., Hkv, T]
    # (every head's row of token lanes), the sinks as [Hkv, G, 1] (a
    # column a head)
    if quantized:
        in_specs += [pl.BlockSpec((1, 1, hkv, bk), key_ix)] * 2
        args += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]
    if sinks is not None:
        in_specs.append(pl.BlockSpec((hkv, g, 1), lambda bi, ki, *_: (0, 0, 0)))
        args.append(sinks.astype(jnp.float32).reshape(hkv, g, 1))

    kernel = functools.partial(
        _decode_kernel,
        scale=scale,
        softcap=softcap,
        block_k=bk,
        num_k=num_k,
        new_row=new_row,
        quantized=quantized,
        sinks=sinks is not None,
        rows_per_slot=rows_per_slot,
        keys_minor=keys_minor,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, num_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hkv, g, d), q_ix),
        scratch_shapes=[
            pltpu.VMEM((hkv, g, d), jnp.float32),
            pltpu.VMEM((hkv, g, 128), jnp.float32),
            pltpu.VMEM((hkv, g, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_decode",
    )(pos_arr, tail, meta, *args)


def flash_decode_supported(config, max_seq: int) -> bool:
    """Whether the kernel computes what this model's full-attention
    layers attend to over a cache row of ``max_seq`` keys: grouped-query
    K/V rows (no latent), the causal frontier with at most a sliding
    window (no Llama4 chunks), widths and rows it can tile. What a
    caller that ASKS for the kernel needs (``decode_kernel="flash"``);
    what the engine takes by itself is :func:`reads_live_keys`."""
    return (
        not config.mla
        and not config.attention_chunk_size
        and config.head_dim % 64 == 0
        and max_seq % 128 == 0
        and config.n_heads % config.n_kv_heads == 0
    )


def reads_live_keys(
    config, rows: int, *, ring: bool = False, quantized: bool = False,
    mesh=None, decode_kernel: Optional[str] = None,
) -> bool:
    """THE rule, at trace time, for one kind of layer of a grouped-query
    model: does its decode attention read the key blocks each live slot
    holds (this kernel) or every reserved row under a mask (the
    einsum)? ``config``: the layer's attention shape (its run's, in a
    model of groups); ``rows``: the rows a slot of its cache buffer
    holds; ``ring``: the buffer is a window layer's ring, whose rows lie
    in ring order and are few (the einsum stays); ``quantized``: the
    buffer is an (int8, scale) pair; ``decode_kernel``:
    what the caller asked for, ``"einsum"`` | ``"flash"`` (the kernel
    wherever it computes the layer, in interpret mode off the TPU: the
    tests' way in), or None: by what the program can see —

    - the TPU backend (the interpret mode is no serving path);
    - a plain bf16 leaf: inside a decode program the compiler stages
      BOTH whole scale leaves of an int8 pair in fast memory once a
      layer (device-free, Llama-3.2-1B at 16 x 2048, either width of
      head: two ``ConcatBitcast`` of [L, B, Hkv, T] f32 in the layer
      scan's body, as many bytes a step as the int8 rows themselves),
      so the pair keeps the einsum, whose dequant fuses into the dot;
    - no mesh, or one whose ``tp`` axis divides the KV heads (the
      engine's ``shard_map`` wrap: a shard's heads, no collective).

    The head's width chooses no longer between the forms, only the
    kernel's blocks (since PR 45): ``head_dim`` % 128 fills the lanes,
    ``head_dim`` 64 leaves the leaf with its TOKENS there and the
    kernel reads it in that order (:func:`tokens_on_lanes`); any other
    width :func:`flash_decode_supported` refuses. The kernel alone
    compiles for the chip in both block forms, bf16 and int8, at a
    cell's shapes, and the bf16 decode programs move no leaf around it
    (``tests/compute/test_tpu_compile.py``)."""
    if decode_kernel == "einsum" or ring:
        return False
    if not flash_decode_supported(config, rows):
        return False
    if decode_kernel == "flash":
        return True
    return (
        jax.default_backend() == "tpu"
        and not quantized
        and (mesh is None or config.n_kv_heads % mesh.shape.get("tp", 1) == 0)
    )
