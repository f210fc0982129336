"""KV-cache inference engine for the Llama family.

TPU-first decode design: everything is static-shaped. The engine owns a
fixed pool of ``max_batch`` sequence *slots* over preallocated KV caches
[L, B, Hkv, T_max, D]; requests prefill into a free slot and every
decode step advances all active slots at once (continuous batching
without dynamic shapes — one compiled step serves any mix of sequence
lengths, the XLA-friendly alternative to GPU paged-attention kernels).
Sampling (greedy / temperature / top-p) runs inside the same jit.

The reference framework has no inference engine at all (services run
user containers, reference examples use vLLM/TGI); this module makes
``type: service`` self-contained:
``python -m dstack_tpu.serve.openai_server`` is a runnable service
command on any slice the orchestrator provisions.
"""

import contextlib
import math
import time
from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from dstack_tpu import faults
from dstack_tpu.obs import boot as obs_boot
from dstack_tpu.obs import flight
from dstack_tpu.obs import profiling
from dstack_tpu.models import llama
from dstack_tpu.models.llama import (
    LlamaConfig,
    _proj,
    model_norm,
    qk_norm_apply,
    rms_norm,
)
from dstack_tpu.ops.flash_decode import (
    block_keys,
    flash_decode,
    flash_decode_supported,
    reads_live_keys,
    tokens_on_lanes as _tokens_on_lanes,
)
from dstack_tpu.utils.logging import get_logger

logger = get_logger("serve.engine")

NEG_INF = -1e30


@dataclass
class GenParams:
    max_new_tokens: int = 256
    temperature: float = 0.0  # 0 = greedy
    top_p: float = 1.0
    top_k: int = 0  # 0 = off
    repetition_penalty: float = 1.0  # HF-style multiplicative; 1 = off
    presence_penalty: float = 0.0  # OpenAI additive: once-seen tokens
    frequency_penalty: float = 0.0  # OpenAI additive: per occurrence
    min_p: float = 0.0  # mask tokens with p < min_p * p_max (0 = off)
    # OpenAI logit_bias: {token_id: bias in [-100, 100]} added to the
    # raw logits before sampling (±100 effectively bans/forces)
    logit_bias: Optional[dict] = None
    seed: Optional[int] = None  # per-request sampling seed
    # resumable generation: advance the seeded PRNG stream by this many
    # draws before the first sample, so a request whose prompt was
    # extended by n already-generated tokens (mid-stream failover
    # resume) samples token n+1 with EXACTLY the key the original
    # stream would have used. Ignored when seed is None (greedy resume
    # needs no RNG; unseeded sampling is not resumable).
    seed_skip: int = 0
    eos_id: Optional[int] = None
    stop: Optional[list] = None  # stop strings (matched by the server)
    # None = off; n >= 0 = collect logprobs with n alternatives (≤ 5)
    logprobs: Optional[int] = None
    # distributed-tracing exemplar id: when set, the engine attaches it
    # to the TTFT/TPOT histogram buckets this request lands in, so
    # "show me the trace behind p99" resolves through /metrics — the
    # engine itself opens no spans (serve.openai_server owns phases)
    trace_id: Optional[str] = None


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------


def kv_quantize(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """[..., D] → (int8 values, per-vector f32 scale [...]).

    Symmetric absmax per (token, head) vector — the granularity that
    keeps dequantization a cheap broadcast multiply XLA fuses into the
    attention dot, so the HBM read stays int8."""
    x32 = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x32), axis=-1) / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x32 / s[..., None]), -127, 127).astype(jnp.int8)
    return q, s


def kv_dequant(q: jax.Array, s: jax.Array, dtype) -> jax.Array:
    # multiply in f32 so the f32-stored scale is applied at full
    # precision; only the RESULT rounds to the compute dtype (casting
    # the scale itself to bf16 first would re-lose what f32 storage
    # bought). XLA fuses the widen-multiply-narrow into the adjacent
    # attention read either way.
    x = q.astype(jnp.float32) * s[..., None].astype(jnp.float32)
    return x.astype(dtype)


# Quantized caches travel through the compute paths as (int8, scale)
# TUPLE leaves in place of the plain array — lax.scan carries pytrees,
# so the prefill/decode/verify plumbing is untouched; only the
# write/read wrappers below branch. Dequantization sits adjacent to the
# attention dot so XLA fuses it into the operand read and the HBM
# traffic stays int8.


def _cache_pack(cache: dict) -> tuple:
    """dict → (ck, cv) where each is an array or an (int8, scale) pair."""
    if "k_s" in cache:
        return (cache["k"], cache["k_s"]), (cache["v"], cache["v_s"])
    return cache["k"], cache["v"]


def _cache_unpack(ck, cv) -> dict:
    if isinstance(ck, tuple):
        return {"k": ck[0], "k_s": ck[1], "v": cv[0], "v_s": cv[1]}
    return {"k": ck, "v": cv}


def _cwrite_chunk(ckv, layer, slot, start, new, axis: int = 1):
    """Write one slot's prefill chunk into layer ``layer`` of a STACKED
    cache leaf at the token offset ``start`` (static under the serial
    chunk; a lone row's of a model of layer groups traced), in place: ``new``
    [1, *slot] with C in the place of T, as stored (:func:`_cstored`);
    ``axis`` as in :func:`_cwrite_rows`. The engine keeps ``start`` a
    multiple of the chunk and a chunk is whole tiles, so the
    ``dynamic_update_slice`` lands where the donated buffer lies. (One
    shape is left to the compiler: a short prompt's 16-row bucket on a
    bf16 leaf with its tokens on the lanes is an eighth of a tile, and
    the leaf is re-laid out around the layer loop; block forms made it
    worse, PERF.md §6 PR 29.)"""
    if isinstance(ckv, tuple):
        return tuple(
            _cwrite_chunk(c, layer, slot, start, n, axis) for c, n in zip(ckv, new)
        )
    at = [layer, slot] + [0] * (ckv.ndim - 2)
    at[2 + axis] = start
    return jax.lax.dynamic_update_slice(ckv, new[None], at)


def _cread_rows(ckv, layer, slots, dtype):
    """``slots``' rows [G, *slot] of layer ``layer`` of a stacked cache
    leaf, in compute dtype: what a prefill chunk attends over (serial:
    G = 1; packed: the wave's rows). One ``dynamic_slice`` a row, so
    neither the layer's slice nor the leaf is copied out (one gather of
    the G rows made the compiler copy whole leaves: device-free,
    ``temp`` 1.8 GB on the dense packed wave)."""

    def rows(a):
        zeros, sizes = (0,) * (a.ndim - 2), (1, 1) + a.shape[2:]
        return jnp.concatenate([
            jax.lax.dynamic_slice(a, (layer, s) + zeros, sizes)[0] for s in slots
        ])

    if isinstance(ckv, tuple):
        return kv_dequant(rows(ckv[0]), rows(ckv[1]), dtype)
    return rows(ckv)


def _own_rows(rows):
    """``rows`` [..., T, width], read out of a leaf with its tokens on
    the lanes, for the flash kernel, which wants the width there → the
    same values in a buffer of their own. A ``dynamic_slice`` hands the
    kernel's layout on to what it slices, so the compiler re-lays the
    WHOLE leaf out before the layer loop and back after it
    (device-free: ``temp`` 1.5 GB beside V2-Lite's 1.36 GB latent, 2.1
    beside Llama-3.2-1B's 1.07 GB cache). A product with the identity is
    exact (one and zeros, summed in float32) and is the one operation
    whose operand's layout the compiler leaves alone: the transposition
    then costs one row a layer."""
    if not _tokens_on_lanes(rows.shape[-1]):
        return rows
    return jnp.einsum(
        "...td,de->...te", rows, jnp.eye(rows.shape[-1], dtype=rows.dtype),
        precision=jax.lax.Precision.HIGHEST,
    )


def _cfull(ckv, dtype):
    """The whole cache tensor in compute dtype (decode/verify einsums —
    the dequant multiply fuses into the dot, the HBM read stays int8)."""
    if isinstance(ckv, tuple):
        return kv_dequant(ckv[0], ckv[1], dtype)
    return ckv


def _cstored(new, like):
    """``new`` rows as the cache stores them: the array itself, or its
    (int8, scale) pair where ``like`` is a quantized leaf."""
    if isinstance(like, tuple):
        q, s = kv_quantize(new)
        return q, s.astype(like[1].dtype)
    return new


def _cwrite_rows(
    ckv, layer, positions, write_mask, new, axis: int = 1, unroll: bool = False,
    slots=None, counts=None, opaque_loop: bool = False,
):
    """Write ``S`` new tokens a slot into layers ``layer .. layer + N``
    of a STACKED cache leaf, in place: ``ckv`` [L, B, *slot] with the
    token axis at ``axis`` of the slot's shape ([Hkv, T, D] values: 1;
    [T, R] latents: 0), ``new`` [N, B, *slot] with S in the place of T
    (or [B, *slot]: one layer's; as stored, see :func:`_cstored`), for
    the tokens at ``positions[b] + s``. Row ``b`` of ``new`` is slot
    ``b``'s (a decode or verify step) or slot ``slots[b]``'s (a packed
    prefill wave's G rows), and only its first ``counts[b]`` tokens are
    written where given (a padded last chunk; 0: a pad row).

    A one-token ``.at[].set`` touches one row of a tile on the token
    axis, and the TPU compiler will not do that in place: it re-lays
    the layer's slice (or the whole cache) out so that a token is a
    whole tile, writes, and copies it back: two thirds of the decode
    program's device time (PERF.md §6, PR 25). So each slot reads the
    tile-aligned block around its position, selects the new rows in
    and writes the WHOLE block back: a ``dynamic_update_slice`` of
    whole tiles at a visibly aligned offset updates the donated buffer
    where it lies, and nothing the size of a layer's cache moves
    (``test_decode_program_holds_no_second_cache`` compiles it for the
    chip). Rows with ``write_mask`` false, and positions past the end,
    put the block back as read: their bytes stay.

    The slots go through a ``fori_loop`` (one small body: unrolled, the
    16 slots doubled the programs' size and cost every boot seconds of
    lowering and loading); ``unroll`` is for the latent, which the
    compiler re-lays out ``{3,2,1,0}`` under a nested loop and copies
    whole at every call (device-free: ``temp`` 0.33 → 2.0 GB).
    ``opaque_loop`` is for a wave of ONE row: a loop of one trip is
    inlined, the block write then stands bare in the layer scan, and
    the compiler re-lays every K/V leaf out whole around it
    (device-free at 16 × 8192 over two caches: 12 whole-leaf copies,
    ``temp`` 0.64 → 4.2 GB, past the chip); a trip count it cannot read
    (``min(1, 1 + slots[0])``, which is 1) keeps the loop."""
    if isinstance(ckv, tuple):
        return tuple(
            _cwrite_rows(
                c, layer, positions, write_mask, n, axis, unroll, slots, counts
            )
            for c, n in zip(ckv, new)
        )
    if new.ndim < ckv.ndim:  # one layer's rows
        new = new[None]
    t_ax = 2 + axis
    tmax, s = ckv.shape[t_ax], new.shape[t_ax]
    # tokens a tile holds: 128 lanes where the token axis is minor (the
    # scales), else 8 sublanes × the dtype's packing (bf16 16, int8 32)
    tile = 128 if t_ax == ckv.ndim - 1 else 8 * (4 // ckv.dtype.itemsize)
    w = min(tmax, tile * (1 + -(-(s - 1) // tile)))  # S rows span ≤ this
    base = (positions // tile) * tile  # [B]; a multiple of the tile, visibly
    start = jnp.clip(base, 0, tmax - w)  # what dynamic_slice clamps it to
    # unsigned: lax.dynamic_slice wraps every signed index (lt, add,
    # select), thrice the operations of the write itself
    base, layer = base.astype(jnp.uint32), jnp.asarray(layer, jnp.uint32)
    zero = jnp.zeros((), jnp.uint32)
    off = start[:, None] + jnp.arange(w)[None, :] - positions[:, None]
    wide = [1] * new.ndim
    wide[1], wide[t_ax] = new.shape[1], w
    most = s if counts is None else counts[:, None]
    hit = (write_mask[:, None] & (off >= 0) & (off < most)).reshape(wide)
    # the new row each block row would take, [N, B, *slot with W for T].
    # One token broadcasts: a gathered copy of it, W rows tall in the
    # scan's ys layout, talks XLA into re-laying the whole cache out
    src = new if s == 1 else jnp.take_along_axis(
        new, jnp.clip(off, 0, s - 1).reshape(wide), axis=t_ax
    )
    sizes = (new.shape[0], 1) + ckv.shape[2:t_ax] + (w,) + ckv.shape[t_ax + 1:]

    def one_slot(b, ckv):
        slot = b if slots is None else slots[b]
        at = [layer, jnp.asarray(slot, jnp.uint32)] + [zero] * (ckv.ndim - 2)
        at[t_ax] = base[b]
        mine = lambda a: jax.lax.dynamic_slice_in_dim(a, b, 1, 1)
        blk = jnp.where(mine(hit), mine(src), jax.lax.dynamic_slice(ckv, at, sizes))
        return jax.lax.dynamic_update_slice(ckv, blk, at)

    if unroll:
        for b in range(new.shape[1]):
            ckv = one_slot(b, ckv)
        return ckv
    n = new.shape[1]
    if n == 1 and slots is not None and opaque_loop:
        n = jnp.minimum(1, 1 + slots[0])
    return jax.lax.fori_loop(0, n, one_slot, ckv)


def _cwith_row(ckv, positions, write_mask, new):
    """One layer's slice [B, Hkv, T, D] (or its (int8, scale) pair)
    with each slot's new token selected in at its position: what the
    slice will hold once the row is written, without writing it. The
    select fuses into the attention's operand reads like the int8
    dequant does."""
    if isinstance(ckv, tuple):
        return tuple(
            _cwith_row(c, positions, write_mask, n) for c, n in zip(ckv, new)
        )
    hit = write_mask[:, None] & (
        jnp.arange(ckv.shape[2])[None, :] == positions[:, None]
    )  # [B, T]
    return jnp.where(
        hit.reshape(hit.shape[0], 1, -1, *[1] * (ckv.ndim - 3)), new, ckv
    )


def _clayer(ckv, layer):
    """Layer ``layer``'s slice of a stacked cache leaf as stored (array
    or (int8, scale) pair). Read by an attention einsum, the
    ``dynamic_slice`` fuses into the operand read like the int8 dequant:
    a dense layer's slice is never materialized."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False),
        ckv,
    )


def _indexed(c: LlamaConfig, tmax: int) -> bool:
    """Whether the sparse indexer can bite in a cache row of ``tmax``
    tokens. With no more than ``index_topk`` keys every causal key is
    chosen: then no index keys are cached and nothing is selected."""
    return bool(c.index_topk) and tmax > c.index_topk


def _attends_masked(cache: dict, run) -> bool:
    """Whether this run's layers of a latent model attend under an
    explicit mask in decode and verify (a window layer's over its ring, a
    full layer's the indexer's selection, which has its keys in the
    cache where it can bite): else under the causal mask alone, over as
    many key blocks as the live contexts hold (:func:`_attend_live`)."""
    return bool(run.window) or "idx" in cache


def _masked(c: LlamaConfig, tmax: int) -> bool:
    """Whether some layer attends under an explicit mask (a window
    ring's row order, an indexer's selection): then no static-offset
    kernel applies, and prefill has one form, the packed one."""
    return "window" in c.layer_types or _indexed(c, tmax)


def ring_rows(c: LlamaConfig, max_seq: int, chunk: int) -> int:
    """Rows a window layer's cache holds for one slot: a ring in which
    position ``p`` lives at row ``p % rows``. A call writes up to
    ``chunk`` new positions BEFORE it attends, and its oldest query
    still needs ``sliding_window - 1`` keys before it, so the ring holds
    both (rounded up to whole tiles); ``max_seq`` only caps it (a ring
    that long never wraps)."""
    return min(max_seq, -(-(c.sliding_window - 1 + chunk) // 16) * 16)


def _cache_shapes(
    c: LlamaConfig, max_batch: int, max_seq: int, chunk: int, kv_quant=None
) -> dict:
    """Buffer name → shape of the cache, one buffer a kind of state; the
    one place that says what rows a kind of layer holds, for both
    families. Full-attention layers (the dense prelude first, then
    ``layers``) hold ``max_seq`` rows a slot: the latent family's
    ``ckv`` [Lf, B, Tmax, rank+rope] and, where an indexer can bite,
    ``idx`` [Lf, B, Tmax, index_head_dim]; the grouped-query family's
    ``k`` / ``v`` [Lf, B, Hkv, Tmax, D] (with ``kv_quant`` their
    per-vector scales ``k_s`` / ``v_s`` [Lf, B, Hkv, Tmax]). Window
    layers hold a ring of :func:`ring_rows`: ``win`` [Lw, B, W,
    swa_rank+rope], or ``win_k`` / ``win_v`` [Lw, B, Hkv, W, D].
    ``moe_stats`` [2] int32 are the routing counts of a chip's share of
    the experts (picks held, token-layers routed; a third where the
    router has identity experts: the picks that fell on those) and
    ``moe_reads`` [2] int32 the experts whose weights the routed layer
    calls read and the experts held over the same calls, carried with
    the cache and fetched with a step's tokens (two leaves, the second
    new in PR 38: ``tests/benchmark`` unpacks the first by its length). A model of
    one kind of layer has ``ckv``, or ``k`` and ``v``, alone; a latent
    layer of several attention sublayers has a ``ckv`` row a sublayer,
    layer ``l``'s sublayer ``i`` at row ``l * sublayers + i``. Linear
    layers (``models/kda.py``) hold ``state`` [Ll, B, heads, D, D] in
    float32 and ``conv`` [Ll, B, K-1, 3 * heads * D], conv layers
    (``models/shortconv.py``) ``conv`` [Lc, B, K-1, hidden] alone, mamba
    layers (``models/mamba.py``) ``state`` [Lm, B, N, d_inner] in float32
    and ``conv`` [Lm, B, K-1, d_inner], none with a token axis (gmu and
    cross layers hold nothing; with ``diff_attn`` ``k`` / ``v`` and the
    rings hold a KV pair as one head, ``attend_config``); under
    group-limited routing ``moe_stats`` ends
    with the tokens one of whose eligible groups is held here."""
    n_win = c.layer_types.count("window")
    n_lin = c.layer_types.count("linear")
    n_conv = c.layer_types.count("conv")
    n_mamba = c.layer_types.count("mamba")
    n_full = c.n_kind("full")
    ring = ring_rows(c, max_seq, chunk) if n_win else 0
    if c.mla:
        rope = c.qk_rope_head_dim
        shapes = {
            "ckv": (
                n_full * c.sublayers, max_batch, max_seq, c.kv_lora_rank + rope
            )
        }
        if _indexed(c, max_seq):
            shapes["idx"] = shapes["ckv"][:3] + (c.index_head_dim,)
        if n_win:
            shapes["win"] = (
                n_win, max_batch, ring, c.window_config.kv_lora_rank + rope
            )
    else:
        # (differential attention: a KV pair side by side is one head of
        # twice the width, ``attend_config``; the bytes are the same)
        ac = c.attend_config
        kv = lambda n, t: (n, max_batch, ac.n_kv_heads, t, ac.head_dim)
        shapes = {"k": kv(n_full, max_seq), "v": kv(n_full, max_seq)}
        if kv_quant:
            shapes["k_s"] = shapes["v_s"] = shapes["k"][:-1]
        if n_win:
            shapes["win_k"] = shapes["win_v"] = kv(n_win, ring)
    if n_lin:
        # a linear layer keeps no rows: a slot's whole past is its state
        # (float32) and the convolution's tail, whatever max_seq is
        heads = (c.n_heads, c.linear_head_dim)
        shapes["state"] = (n_lin, max_batch, *heads, c.linear_head_dim)
        shapes["conv"] = (
            n_lin, max_batch, c.linear_conv - 1, 3 * heads[0] * heads[1]
        )
    if n_conv:
        # nor does a conv layer: its convolution's tail over the hidden
        shapes["conv"] = (n_conv, max_batch, c.conv_taps - 1, c.hidden_size)
    if n_mamba:
        # a mamba layer (``models/mamba.py``): its recurrence's state,
        # float32, the channels on the lanes, and its convolution's
        # tail; gmu and cross layers hold nothing (a cross layer reads
        # row 0 of ``k`` / ``v``)
        shapes["state"] = (n_mamba, max_batch, c.ssm_state, c.ssm_inner)
        shapes["conv"] = (n_mamba, max_batch, c.ssm_conv - 1, c.ssm_inner)
    if c.experts_held:
        shapes["moe_stats"] = (_moe_counts(c) - 2,)
        shapes["moe_reads"] = (2,)
    return shapes


#: the name ``tests/benchmark`` knows it by (the benchmark's files are
#: not a program PR's to edit)
_mla_cache_shapes = _cache_shapes


#: the cache's leaves that are counts and no slot's state
_COUNTS = ("moe_stats", "moe_reads")

#: the cache's leaves that hold a slot's past whole and not by position:
#: no prefix of theirs can be copied (a state at a shared length exists
#: only if it was kept, and none is: PERF.md §7)
_STATES = ("state", "conv")

#: the token axis of a cache leaf that holds rows by position: what
#: ``copy_cache_prefix`` can copy a prefix of (a window ring is copied
#: whole). MLA latent and index keys [L,B,T,R]; k/v [L,B,H,T,D]; the
#: int8 scales k_s/v_s [L,B,H,T]
_T_AXIS = {"ckv": 2, "idx": 2, "k": 3, "v": 3, "k_s": 3, "v_s": 3}


def _moe_counts(c: LlamaConfig) -> int:
    """Counts a routed layer call of a chip's share of the experts
    yields (:func:`_mlp_out`): ``moe_stats``' and then ``moe_reads``'."""
    return 4 + bool(c.zero_experts) + bool(c.router_groups)


def _moe_stats(cache: dict):
    """The cache's routing counts as the one vector the layer bodies add
    a call's counts to; None for a model that holds every expert."""
    if "moe_stats" not in cache:
        return None
    return jnp.concatenate([cache[n] for n in _COUNTS])


def _with_moe_stats(cache: dict, stats) -> dict:
    """``cache`` with :func:`_moe_stats`' vector put back leaf by leaf."""
    if stats is None:
        return cache
    return {**cache, "moe_stats": stats[:-2], "moe_reads": stats[-2:]}


def _is_ring(name: str) -> bool:
    """Whether cache buffer ``name`` is a window layers' ring."""
    return name.startswith("win")


def _ring_mask(qpos: jax.Array, newest: jax.Array, rows: int, window: int):
    """Which rows of a window ring a query may see → bool [B, S, rows].
    ``qpos`` [B, S] query positions, ``newest`` [B] the last position
    the slot has written (this call's included). Row ``r`` holds the
    newest position congruent to ``r``; a row not yet written (position
    < 0), ahead of the query, or ``window`` or more behind it is
    masked."""
    r = jnp.arange(rows)
    held = newest[:, None] - jnp.mod(newest[:, None] - r[None, :], rows)
    age = qpos[:, :, None] - held[:, None, :]
    return (held[:, None, :] >= 0) & (age >= 0) & (age < window)


#: keys a block of an attention that goes by its keys in blocks under a
#: running softmax (a row whose length it does not divide: their gcd)
_KEY_BLOCK = 512

#: most bytes of f32 scores a masked latent attention holds at once (a
#: verify step of 16 slots x 5 tokens against 8192 keys is 0.34 GB)
_SCORE_BYTES = 1 << 29


def _attend_masked(
    q_abs: jax.Array,  # [B, H, S, R] absorbed queries
    rows: jax.Array,  # [B, T, R] each row's cached latents
    mask: jax.Array,  # [B, S, T] bool
    h: jax.Array,  # [B, S, E] normed hidden (the gate reads it)
    layer: dict,
    gc: LlamaConfig,  # the group's attention shape
    window: int,
    n_keys=None,  # [B]: keys that can be visible a row (None: all T)
) -> jax.Array:
    """Absorbed latent attention under an explicit mask (an indexer's
    selection, a window ring's row order) → [B, S, o_dim], gated: the
    form every layer of a model of groups takes, in prefill, decode and
    verify alike. Where the f32 scores of all rows fit half a GB (a
    decode or verify step, a window ring) they are taken at once. Else (a
    prefill chunk against thousands of keys) the rows go by one after
    the other and each row's keys in blocks under a running softmax, as
    many blocks as hold its ``n_keys``: the work follows the context a
    row has, not ``max_seq``."""
    b, nh, s, _ = q_abs.shape
    t, rank = rows.shape[1], gc.kv_lora_rank
    scale = gc.attention_scale
    q_abs = q_abs.astype(rows.dtype)

    def attend(q, r, m):  # q [H, S, R], r [T, R], m [S, T]
        sc = jnp.einsum(
            "hsr,tr->hst", q, r, preferred_element_type=jnp.float32
        ) * scale
        pr = jax.nn.softmax(jnp.where(m[None], sc, NEG_INF), axis=-1)
        return jnp.einsum("hst,tr->hsr", pr.astype(r.dtype), r[:, :rank])

    kb = math.gcd(t, _KEY_BLOCK)  # keys a block

    def attend_blocks(args):
        q, r, m, blocks = args

        def block(i, carry):
            top, den, acc = carry  # running max [H,S], sum [H,S], values [H,S,rank]
            rk = jax.lax.dynamic_slice_in_dim(r, i * kb, kb, 0)
            sc = jnp.einsum(
                "hsr,tr->hst", q, rk, preferred_element_type=jnp.float32
            ) * scale
            sc = jnp.where(
                jax.lax.dynamic_slice_in_dim(m, i * kb, kb, 1)[None], sc, NEG_INF
            )
            new_top = jnp.maximum(top, sc.max(-1))
            # a query with no visible key so far keeps top = NEG_INF and
            # sums its masked keys at weight 1; its first visible key
            # scales all of that away (keep = exp(NEG_INF - score) = 0)
            keep = jnp.exp(top - new_top)
            pr = jnp.exp(sc - new_top[..., None])
            acc = acc * keep[..., None] + jnp.einsum(
                "hst,tr->hsr", pr.astype(rk.dtype), rk[:, :rank],
                preferred_element_type=jnp.float32,
            )
            return new_top, den * keep + pr.sum(-1), acc

        _, den, acc = jax.lax.fori_loop(
            0, blocks, block,
            (
                jnp.full((nh, s), NEG_INF, jnp.float32),
                jnp.zeros((nh, s), jnp.float32),
                jnp.zeros((nh, s, rank), jnp.float32),
            ),
        )
        return (acc / den[..., None]).astype(r.dtype)

    with jax.named_scope("dtpu.attn_window" if window else "dtpu.attn_full"):
        if b * nh * s * t * 4 <= _SCORE_BYTES:
            o_lat = jax.vmap(attend)(q_abs, rows, mask)
        else:
            blocks = jnp.full((b,), t // kb, jnp.int32)
            if n_keys is not None:
                blocks = jnp.clip(-(-n_keys // kb), 1, t // kb).astype(jnp.int32)
            o_lat = jax.lax.map(attend_blocks, (q_abs, rows, mask, blocks))
        return _latent_values(o_lat, _mla_kb(layer, gc)[1], h, layer, gc)


def _live_key_blocks(positions, write_mask, s: int, tmax: int):
    """Key blocks of :data:`_KEY_BLOCK` (as many as divide ``tmax``)
    that hold the longest context among a step's LIVE slots, its ``s``
    new tokens included → (int32 scalar ≥ 1, keys a block). A dead
    slot's position is stale and does not count."""
    kb = math.gcd(tmax, _KEY_BLOCK)
    n_keys = jnp.max(jnp.where(write_mask, positions + s, 0))
    return jnp.clip(-(-n_keys // kb), 1, tmax // kb).astype(jnp.int32), kb


def _attend_live(
    q_abs: jax.Array,  # [B, H, S, R] absorbed queries
    ckv: jax.Array,  # [L, B, T, R] the stacked latents, this step's written
    li,  # the layer's row of ``ckv``
    positions: jax.Array,  # [B] position of each slot's first query
    write_mask: jax.Array,  # [B] bool: the step's live slots
    gc: LlamaConfig,
) -> jax.Array:
    """Absorbed latent attention of a decode (S = 1) or verify step under
    the causal mask alone → [B, H, S, rank]. The layer's keys go by in
    blocks under a running softmax, as many blocks as hold the longest
    context among the LIVE slots: a server reserves ``max_seq`` rows a
    slot for its longest request and serves mostly short ones, and the
    work follows what the slots hold (at a context near ``max_seq`` it
    is the whole row, in ``T / 512`` blocks). Each block is one
    ``dynamic_slice`` of the stacked leaf, so no layer's slice is
    materialized (whole, it was a copy of 151 MB a layer a token at
    16 × 8192: PERF.md §6, PR 35). A dead slot's row attends over the
    same blocks under its stale position: finite, and discarded."""
    b, nh, s, width = q_abs.shape
    rank, scale = gc.kv_lora_rank, gc.attention_scale
    blocks, kb = _live_key_blocks(positions, write_mask, s, ckv.shape[2])
    q_abs = q_abs.astype(ckv.dtype)
    qpos = (positions[:, None] + jnp.arange(s)[None, :])[:, None, :, None]
    li = jnp.asarray(li, jnp.int32)

    def block(i, carry):
        top, den, acc = carry  # running max [B,H,S], sum [B,H,S], values [B,H,S,rank]
        rk = jax.lax.dynamic_slice(
            ckv, (li, 0, i * kb, 0), (1, b, kb, width)
        )[0]  # [B, kb, R]
        sc = jnp.einsum(
            "bhsr,btr->bhst", q_abs, rk, preferred_element_type=jnp.float32
        ) * scale
        kj = i * kb + jnp.arange(kb)
        sc = jnp.where(kj[None, None, None, :] <= qpos, sc, NEG_INF)
        # key 0 is visible to every query, so after the first block no
        # running maximum is NEG_INF
        new_top = jnp.maximum(top, sc.max(-1))
        keep = jnp.exp(top - new_top)
        pr = jnp.exp(sc - new_top[..., None])
        acc = acc * keep[..., None] + jnp.einsum(
            "bhst,btr->bhsr", pr.astype(rk.dtype), rk[..., :rank],
            preferred_element_type=jnp.float32,
        )
        return new_top, den * keep + pr.sum(-1), acc

    with jax.named_scope("dtpu.attn_full"):
        _, den, acc = jax.lax.fori_loop(
            0, blocks, block,
            (
                jnp.full((b, nh, s), NEG_INF, jnp.float32),
                jnp.zeros((b, nh, s), jnp.float32),
                jnp.zeros((b, nh, s, rank), jnp.float32),
            ),
        )
        return (acc / den[..., None]).astype(ckv.dtype)


def _attend_causal(q_abs, rows, q_offset, gc: LlamaConfig, c: LlamaConfig):
    """Absorbed latent attention of a prefill chunk under the causal
    mask alone → [B, H, C, rank]: MQA with one rank+rope-wide kv head
    whose value is the latent itself, so the flash kernel applies where
    ``q_offset`` is static (a serial chunk's start; a packed wave's is a
    vector of starts) and the widths tile."""
    from dstack_tpu.ops.attention import attention

    k_abs = rows[:, None]  # [B, 1, Tmax, R] — one shared kv head
    v_abs = jnp.concatenate(
        [rows[..., : gc.kv_lora_rank], jnp.zeros_like(rows[..., gc.kv_lora_rank :])],
        axis=-1,
    )[:, None]
    return attention(
        q_abs.astype(c.dtype), k_abs, v_abs, causal=True,
        scale=gc.attention_scale, q_offset=q_offset,
    )[..., : gc.kv_lora_rank]


def init_cache(
    config: LlamaConfig,
    max_batch: int,
    max_seq: int,
    mesh=None,
    kv_quant=None,  # None | "int8"
    chunk: int = 256,  # most tokens one call writes a slot (sizes a window ring)
) -> dict:
    """Preallocated KV cache: k/v [L, B, Hkv, T_max, D] in model dtype,
    KV heads sharded over ``tp`` when serving on a mesh.

    ``kv_quant="int8"``: k/v store as int8 with per-(token, head) f32
    scales (``k_s``/``v_s`` [L, B, Hkv, T_max]) — decode is
    HBM-bandwidth-bound on the cache read, so halving the bytes per
    cached value is ~2× less decode cache traffic and doubles the
    context that fits. The cache dict's ``k_s`` key is the signal the
    compute paths branch on. Not combined with MLA (the latent cache
    is already the compression).

    MLA (DeepSeek): ONE latent tensor ``ckv`` [L, B, T_max,
    kv_lora_rank + qk_rope_head_dim] — the absorbed-attention form
    caches the shared compressed latent plus the single-head rope key
    instead of per-head K/V. For V2/V3 shapes (rank 512 + rope 64 vs
    128 heads × 2 × 192/128 wide) that is a ~50-100× smaller cache and
    proportionally less HBM traffic per decoded token — the reason MLA
    exists. Replicated over ``tp`` (it has no head dim; the q heads
    shard instead). A model of layer GROUPS, of either family, holds a
    buffer a kind of state (:func:`_cache_shapes`): ``ckv`` or ``k`` /
    ``v`` for the full-attention layers, ``idx`` for their indexer
    keys, ``win`` or ``win_k`` / ``win_v`` for the window layers, whose
    rows are set by the window and not by ``max_seq``.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    if kv_quant and (config.mla or config.layer_types):
        raise ValueError(
            "kv_quant combines with neither MLA (the latent cache is "
            "already the compression) nor layer groups"
        )
    if kv_quant not in (None, "int8"):
        raise ValueError(f"unknown kv_quant {kv_quant!r}")
    shapes = _cache_shapes(config, max_batch, max_seq, chunk, kv_quant)

    def buf(n: str):
        s = shapes[n]
        # per-(token, head) scales stored in FLOAT32: the quantizer
        # computes f32 absmax scales, and rounding them to bf16 would
        # stack up to ~0.4% multiplicative error on every dequantized
        # vector on top of the int8 error, for ~1.5% byte savings
        dt = (
            jnp.int32 if n in _COUNTS
            else jnp.float32 if n.endswith("_s") or n == "state"
            else jnp.int8 if kv_quant else config.dtype
        )
        if mesh is None:
            return jnp.zeros(s, dt)
        # allocate directly sharded: a host-side zeros + device_put would
        # materialize the full cache on one chip first. K/V shard over
        # their heads; a latent has none (the q heads shard instead)
        spec = [None] * len(s)
        if not config.mla and len(s) > 2 and n not in _STATES:
            spec[2] = "tp"
        # dtpu: noqa[DTPU003] loop over the fixed cache buffer names at engine construction — bounded and once
        return jax.jit(
            partial(jnp.zeros, s, dt), out_shardings=NamedSharding(mesh, P(*spec))
        )()

    return {n: buf(n) for n in shapes}


# ---------------------------------------------------------------------------
# model: prefill + single-token decode over the cache
# ---------------------------------------------------------------------------


def _apply_rope_batch(
    x: jax.Array, cos: jax.Array, sin: jax.Array, interleaved: bool = False
) -> jax.Array:
    """x [B, H, 1, D]; cos/sin [B, D/2] (per-slot positions). Narrower
    cos/sin (GLM partial rotary) rotate only the leading dims."""
    from dstack_tpu.models.llama import rope_partial

    if 2 * cos.shape[-1] < x.shape[-1]:
        return rope_partial(
            lambda xx: _apply_rope_batch(xx, cos, sin, interleaved), x, cos
        )
    c = cos[:, None, None, :].astype(x.dtype)
    s = sin[:, None, None, :].astype(x.dtype)
    if interleaved:  # Llama4: complex rotation of (even, odd) pairs
        x1, x2 = x[..., 0::2], x[..., 1::2]
        out = jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
        return out.reshape(x.shape)
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _rope_rows(
    t: jax.Array,  # [B, Hh, S, D]
    cos: jax.Array,  # [B, S, D/2] per-(row, step) angles
    sin: jax.Array,
    interleaved: bool = False,
) -> jax.Array:
    """Rope with per-(row, step) angles — the grid form used wherever a
    batch of rows sits at unequal positions: speculative verify (width
    S) and packed multi-slot prefill (width C). Narrower cos/sin (GLM
    partial rotary) rotate only the leading dims; ``interleaved`` is
    the Meta/Llama4 complex-pair convention (always on for MLA)."""
    from dstack_tpu.models.llama import rope_partial

    if 2 * cos.shape[-1] < t.shape[-1]:
        return rope_partial(
            lambda tt: _rope_rows(tt, cos, sin, interleaved), t, cos
        )
    cc = cos[:, None].astype(t.dtype)  # [B, 1, S, D/2]
    ss = sin[:, None].astype(t.dtype)
    if interleaved:
        t1, t2 = t[..., 0::2], t[..., 1::2]
        out = jnp.stack([t1 * cc - t2 * ss, t2 * cc + t1 * ss], axis=-1)
        return out.reshape(t.shape)
    d2 = t.shape[-1] // 2
    t1, t2 = t[..., :d2], t[..., d2:]
    return jnp.concatenate([t1 * cc - t2 * ss, t2 * cc + t1 * ss], axis=-1)


def _mlp(x: jax.Array, layer: dict, c: LlamaConfig) -> jax.Array:
    """x + MLP sublayer (shared by prefill and decode)."""
    return x + _mlp_out(x, layer, c)


def _mlp_out(
    x: jax.Array, layer: dict, c: LlamaConfig, valid=None
) -> jax.Array:
    """The MLP sublayer output alone (Cohere's parallel block adds it
    next to the attention output instead of sequentially). With
    ``valid`` ([B, T] bool, the real tokens) → (output, int32 counts in
    :func:`_moe_stats`' order: the router picks that landed on an
    expert held here, the tokens routed, [the picks of identity experts
    where the router has them,] [the tokens with a held group among their
    eligible ones under group-limited routing,] the experts whose weights the call
    read, the experts held), for a chip's share of the experts
    (``experts_held``)."""
    from dstack_tpu.models.llama import act_fn

    m = (
        model_norm(x, layer.get("mlp_norm", layer.get("attn_norm")), c)
        if c.pre_norm else x  # OLMo-2 norms the OUTPUT instead
        # (parallel_block shares attn_norm — Cohere's single input norm)
    )
    # key off w_router in the LAYER: DeepSeek first_k_dense prelude
    # layers are dense inside an MoE model (see llama._mlp_block)
    if c.n_experts and "w_router" in layer:
        from dstack_tpu.models import moe

        mo, aux = moe.moe_mlp(
            m, layer, c.n_experts, c.experts_per_token, c.capacity_factor,
            None, None, renorm=c.router_renorm,
            sigmoid_input=c.router_sigmoid_input,
            score=c.router_score, groups=c.router_groups,
            routed_scale=c.routed_scale,
            topk_softmax=c.router_topk_softmax,
            act=c.moe_act, act_limit=c.act_limit,
            held=c.experts_held, valid=valid, zero=c.zero_experts,
        )
        if valid is not None:
            picks = jnp.stack(
                [aux["held_picks"], jnp.sum(valid).astype(jnp.int32)]
                + ([aux["zero_picks"]] if c.zero_experts else [])
                + ([aux["group_hit"]] if c.router_groups else [])
                + [aux["experts_read"], aux["experts_held"]]
            )
    else:
        picks = (
            None if valid is None else jnp.zeros((_moe_counts(c),), jnp.int32)
        )
        u = _proj(layer, "w_up", m, "bte,ef->btf", "bte,er->btr", "btr,rf->btf")
        if c.proj_bias:
            u = u + layer["b_up"]
        if c.mlp_gateless:  # Nemotron (config-driven: int8 renames
            # w_gate to w_gate_q, so key presence would misdetect)
            inner = act_fn(c)(u)
        else:
            g = _proj(layer, "w_gate", m, "bte,ef->btf", "bte,er->btr", "btr,rf->btf")
            inner = act_fn(c)(g) * u
        mo = _proj(
            layer, "w_down", inner,
            "btf,fe->bte", "btf,fr->btr", "btr,re->bte",
        )
        if c.proj_bias:
            mo = mo + layer["b_down"]
    if c.post_norms:
        mo = model_norm(mo, layer["mlp_post_norm"], c)
    if c.residual_multiplier:  # Granite scales the sublayer output
        mo = mo * jnp.asarray(c.residual_multiplier, mo.dtype)
    return mo if valid is None else (mo, picks)


def _qkv(h: jax.Array, layer: dict, c: LlamaConfig, cross: bool = False) -> tuple:
    q = _proj(layer, "wq", h, "bte,ed->btd", "bte,er->btr", "btr,rd->btd")
    if cross:  # a cross layer has queries alone: another layer's rows are its keys
        return (q + layer["bq"] if c.qkv_bias else q), None, None
    k = _proj(layer, "wk", h, "bte,ed->btd", "bte,er->btr", "btr,rd->btd")
    v = _proj(layer, "wv", h, "bte,ed->btd", "bte,er->btr", "btr,rd->btd")
    if c.qkv_bias:
        q, k, v = q + layer["bq"], k + layer["bk"], v + layer["bv"]
    if c.qk_norm_flat:  # OLMo-2: norm the full projection width
        q = rms_norm(q, layer["q_norm"], c.norm_eps)
        k = rms_norm(k, layer["k_norm"], c.norm_eps)
    return q, k, v


def _dense_in(
    x: jax.Array, layer: dict, c: LlamaConfig, rope, nope, temp, cross: bool = False
) -> tuple:
    """A dense layer's way into attention, the one copy prefill, decode
    and verify share: norm, projections, heads apart, per-head q/k norm,
    rope, Llama4's norm after it → (q [B, H, S, D], k, v [B, Hkv, S, D]).
    ``rope``: heads → the same rotated at this layer's angles (a grid's
    or a vector's: the caller's). ``nope``: whether the layer skips rope
    (Llama4): a Python bool where the program unrolls its layers
    (prefill), a traced flag where they ride one scan (decode, verify),
    and then both forms are computed and one selected. ``temp``: q → q
    under the NoPE query temperature at the caller's positions.
    Differential attention (``diff_attn``: no rope) → the same at
    ``c.attend_config``, packed a pair (``llama.diff_pack``); ``cross``:
    a layer of queries alone (k, v None)."""
    b, s = x.shape[0], x.shape[1]
    h = model_norm(x, layer["attn_norm"], c) if c.pre_norm else x
    if c.diff_attn:
        return llama.diff_pack(*_qkv(h, layer, c, cross), c)
    q, k, v = _qkv(h, layer, c)
    q = q.reshape(b, s, c.n_heads, c.head_dim).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, c.n_kv_heads, c.head_dim).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, c.n_kv_heads, c.head_dim).transpose(0, 2, 1, 3)
    if c.qk_norm:  # per-head q/k norm (Qwen3 rms / Cohere ln)
        q, k = qk_norm_apply(q, k, layer, c)
    static = isinstance(nope, bool)
    if static and nope:  # the unrotated q/k, q under its temperature
        return (temp(q) if c.attn_temp_scale else q), k, v
    q_ro, k_ro = rope(q), rope(k)
    if c.qk_l2_norm:  # Llama4: weightless L2 norm after rope
        q_ro = llama.l2_norm(q_ro, c.norm_eps)
        k_ro = llama.l2_norm(k_ro, c.norm_eps)
    if static:
        return q_ro, k_ro, v
    q_no = temp(q) if c.attn_temp_scale else q
    return jnp.where(nope, q_no, q_ro), jnp.where(nope, k, k_ro), v


def _dense_out(
    x: jax.Array, o: jax.Array, layer: dict, c: LlamaConfig, stats=None, valid=None
):
    """A dense layer's way out, the one copy: attention output ``o``
    [B, S, q_dim] (heads in query-head order) under the head-wise gate
    (``attn_gate``), through ``wo`` (its bias, post norm and
    multiplier), the residual, the MLP sublayer → x. With ``stats``
    (the [2] int32 routing counts a chip's share of the experts keeps,
    ``cache["moe_stats"]``) → (x, stats plus this layer's counts of
    :func:`_mlp_out` over the ``valid`` tokens)."""
    if c.attn_gate:
        b, s = o.shape[:2]
        h = model_norm(x, layer["attn_norm"], c) if c.pre_norm else x
        o = llama.head_gate(
            o.reshape(b, s, c.n_heads, c.head_dim), h, layer, c, "bsh,bshd->bshd"
        ).reshape(o.shape)
    ao = _proj(layer, "wo", o, "btd,de->bte", "btd,dr->btr", "btr,re->bte")
    if c.proj_bias or c.wo_bias and "bo" in layer:  # (wo_bias: the attending layers')
        ao = ao + layer["bo"]
    if c.post_norms:
        ao = model_norm(ao, layer["attn_post_norm"], c)
    if c.residual_multiplier:  # Granite scales the sublayer output
        ao = ao * jnp.asarray(c.residual_multiplier, ao.dtype)
    if stats is not None:
        mo, picks = _mlp_out(x + ao, layer, c, valid=valid)
        return x + ao + mo, stats + picks
    if c.parallel_block:  # Cohere: joint residual add
        return x + ao + _mlp_out(x, layer, c)
    return _mlp(x + ao, layer, c)


def _dense_probs(s, qpos, window, nope, layer: dict, c: LlamaConfig, ring=None):
    """Scaled scores ``s`` [B, Hkv, G, (S,) T] of the queries at ``qpos``
    [B(, S)] against a slot's whole cache row → probabilities, the one
    copy decode and verify share: Gemma2's softcap, the causal frontier,
    the layer's (traced) sliding ``window``, Llama4's chunks on rope
    layers (``nope`` traced), gpt-oss's sink column. ``ring`` [B] (a
    window layer of a model of groups): the row is a ring whose newest
    position is ``ring[b]``, and what a query sees is :func:`_ring_mask`'s
    (``window`` static there)."""
    if c.attn_softcap:
        s = c.attn_softcap * jnp.tanh(s / c.attn_softcap)
    if ring is not None:
        mask = _ring_mask(
            qpos.reshape(qpos.shape[0], -1), ring, s.shape[-1], window
        ).reshape((qpos.shape[0], 1, 1) + qpos.shape[1:] + s.shape[-1:])
    else:
        kj = jnp.arange(s.shape[-1])[(None,) * (s.ndim - 1)]
        # [B, 1, 1, (S,) 1]: heads and groups broadcast, keys last
        qpos = qpos[(slice(None), None, None) + (slice(None),) * (qpos.ndim - 1) + (None,)]
        mask = kj <= qpos
        mask = jnp.logical_and(mask, jnp.logical_or(window == 0, qpos - kj < window))
        if c.attention_chunk_size:
            # Llama4: rope layers attend within their chunk only
            start = (qpos // c.attention_chunk_size) * c.attention_chunk_size
            mask = jnp.logical_and(mask, jnp.logical_or(nope, kj >= start))
    s = jnp.where(mask, s, NEG_INF)
    if not c.attn_sinks:
        return jax.nn.softmax(s, axis=-1)
    # speculative verify attends with the SAME sink column as decode —
    # omitting it there would silently verify drafts against a
    # different model. [Hkv, G] regroup matches the query-head order
    from dstack_tpu.ops.attention import sink_softmax

    return sink_softmax(
        s,
        layer["sinks"].astype(jnp.float32).reshape(
            (1, c.n_kv_heads, -1) + (1,) * (s.ndim - 3)
        ),
    )


# --- MLA (DeepSeek) absorbed attention pieces --------------------------------
#
# Identity behind the absorbed form: per head, k_nope = ckv · W_kb^nope
# and v = ckv · W_kb^v, so
#   q_nope · k_nope = (q_nope · W_kb^nope) · ckv      (absorb into q)
#   attn_out        = (probs · ckv) · W_kb^v          (absorb into out)
# which turns attention into MQA with ONE shared kv "head"
# [ckv ; k_pe] of width rank+rope whose value IS the latent — exact up
# to float reassociation, and the cache never materializes per-head K/V
# (llama.mla_qkv documents the non-absorbed training form).


def _mla_kb(layer: dict, c: LlamaConfig) -> tuple[jax.Array, jax.Array]:
    """wkv_b [rank, H*(nope+v)] → (w_kb_nope [rank,H,nope], w_kb_v
    [rank,H,v])."""
    w = layer["wkv_b"].reshape(
        c.kv_lora_rank, c.n_heads, c.qk_nope_head_dim + c.v_head_dim
    )
    return w[..., : c.qk_nope_head_dim], w[..., c.qk_nope_head_dim :]


def _mla_q(h: jax.Array, layer: dict, c: LlamaConfig) -> tuple:
    """Normed hidden [B,T,H] → (q [B, Hq, T, qk_head_dim] pre-rope, the
    query latent [B, T, q_lora_rank] or None: the indexer reads it)."""
    b, t, _ = h.shape
    qa = None
    if c.q_lora_rank:
        qa = llama.mla_q_latent(h, layer, c)
        q = jnp.einsum("btr,rd->btd", qa, layer["wq_b"])
    else:
        q = jnp.einsum("bte,ed->btd", h, layer["wq"])
    return q.reshape(b, t, c.n_heads, c.qk_head_dim).transpose(0, 2, 1, 3), qa


def _mla_latents(
    h: jax.Array, layer: dict, c: LlamaConfig
) -> tuple[jax.Array, jax.Array]:
    """Normed hidden [B,T,H] → (ckv [B,T,rank] normed, k_pe [B,T,rope]
    un-roped)."""
    kv_a = jnp.einsum("bte,ed->btd", h, layer["wkv_a"])
    ckv = rms_norm(kv_a[..., : c.kv_lora_rank], layer["kv_a_norm"], c.norm_eps)
    ckv = llama.mla_rescale(ckv, c.kv_lora_rank, c)
    return ckv, kv_a[..., c.kv_lora_rank :]


def _latent_in(x, layer: dict, gc: LlamaConfig, c: LlamaConfig, rope, rope_key=None):
    """A latent layer's way into attention, the one copy the four latent
    programs share → (h the normed hidden, qa the query latent or None,
    q_nope [B, H, S, nope], q_pe [B, H, S, rope] rotated, the new cache
    rows [B, S, rank+rope]: latents beside the rotated shared key).
    ``gc``: the layer's group's attention shape. ``rope``: heads
    [B, Hh, S, D] → the same rotated at the caller's positions;
    ``rope_key``: the one shared key [B, S, rope] → the same rotated
    (decode's vector of angles takes it another way round than a grid)."""
    h = rms_norm(x, layer["attn_norm"], c.norm_eps)
    q, qa = _mla_q(h, layer, gc)  # [B, H, S, qk_head_dim]
    q_nope = q[..., : gc.qk_nope_head_dim]
    q_pe = rope(q[..., gc.qk_nope_head_dim :])
    ckv, k_pe = _mla_latents(h, layer, gc)  # [B,S,rank], [B,S,rope]
    k_pe = rope_key(k_pe) if rope_key else rope(k_pe[:, None])[:, 0]
    return h, qa, q_nope, q_pe, jnp.concatenate([ckv, k_pe], axis=-1)


def _latent_absorb(q_nope, q_pe, layer: dict, gc: LlamaConfig) -> tuple:
    """→ (the absorbed queries [B, H, S, rank+rope], w_kb_v): after the
    layer's rows are written and read, as the programs order it."""
    w_kb_nope, w_kb_v = _mla_kb(layer, gc)
    q_lat = jnp.einsum("bhsn,rhn->bhsr", q_nope, w_kb_nope)
    return jnp.concatenate([q_lat, q_pe], axis=-1), w_kb_v


def _latent_mask(cache: dict, li, run, h, qa, layer: dict, rope, put, get, qpos, newest):
    """The mask a layer of groups attends under → (cache, bool [B, S, T]
    | None): a window layer's over its ring; a full layer's the indexer's
    selection where it can bite, its index keys written (``put(cache,
    name, li, new=)``) and read (``get(leaf, li)``) as the latents are;
    None where every causal key is seen. ``qpos`` [B, S] the queries'
    positions, ``newest`` [B] the last position a slot has written
    (read for a ring alone: callers compute it for window layers only,
    so that a full layer's program holds no operation it does not use)."""
    if run.window:
        return cache, _ring_mask(qpos, newest, cache["win"].shape[2], run.window)
    if "idx" not in cache:
        return cache, None
    gc = run.config
    q_i, k_i, w_i = llama.index_qkw(h, qa, layer, gc, rope)
    cache = put(cache, "idx", li, new=k_i)
    return cache, llama.index_select(
        q_i, w_i, get(cache["idx"], li),
        jnp.arange(cache["idx"].shape[2])[None, None, :] <= qpos[:, :, None],
        gc.index_topk,
    )


def _latent_values(o_lat, w_kb_v, h, layer: dict, gc: LlamaConfig) -> jax.Array:
    """Attention output in the latent [B, H, S, rank] → through the
    value half of ``wkv_b`` and the head-wise gate → [B, S, o_dim]."""
    o = jnp.einsum("bhsr,rhv->bshv", o_lat, w_kb_v)
    o = llama.head_gate(o, h, layer, gc, "bsh,bshv->bshv")
    return o.reshape(o.shape[0], o.shape[1], gc.o_dim)


def _latent_wo(x, o, layer: dict):
    """A latent attention's output ``o`` [B, S, o_dim] through ``wo``
    onto the residual."""
    return x + _proj(layer, "wo", o, "btd,de->bte", "btd,dr->btr", "btr,re->bte")


def _mlp_picks(x, cache: dict, layer: dict, c: LlamaConfig, valid):
    """:func:`_mlp_out` of ``layer`` → (output, its int32 routing counts
    over the ``valid`` tokens). A model that holds every expert has no
    ``moe_stats`` in its cache: nothing is counted, nothing is traced,
    and the counts are None."""
    if "moe_stats" not in cache:
        return _mlp_out(x, layer, c), None
    return _mlp_out(x, layer, c, valid=valid)


def _count_picks(cache: dict, picks) -> dict:
    """``cache`` with a layer's routing counts added to its own."""
    if picks is None:
        return cache
    return _with_moe_stats(cache, _moe_stats(cache) + picks)


def _latent_out(x, cache: dict, o, layer: dict, c: LlamaConfig, valid):
    """The common tail of a latent layer in every program: the output
    through ``wo``, the residual, the MLP sublayer → (x, cache)."""
    x = _latent_wo(x, o, layer)
    mo, picks = _mlp_picks(x, cache, layer, c, valid)
    return x + mo, _count_picks(cache, picks)


def _state_rows(cache: dict, li, slots=None, fresh=None) -> tuple:
    """Layer ``li``'s rows of the :data:`_STATES` leaves the cache holds
    (a linear layer's (state, tail), a conv layer's (tail,)): every
    slot's, or the rows of ``slots`` [G] (one slice a row, as
    :func:`_cread_rows`); zeros where ``fresh`` [G]: a request that
    starts at position 0 starts from nothing, whatever its slot's last
    request left."""
    names = [n for n in _STATES if n in cache]
    rows = [
        _clayer(cache[n], li) if slots is None
        else _cread_rows(cache[n], li, slots, None)
        for n in names
    ]
    if fresh is not None:
        # (the state's zero a Python scalar, the tail's an array's: the
        # two lower apart by one no-op convert, and stay as PR 42 wrote
        # them so that its programs' pins stand)
        rows = [
            jnp.where(
                fresh[(slice(None),) + (None,) * (r.ndim - 1)],
                0.0 if n == "state" else jnp.zeros((), r.dtype), r,
            )
            for n, r in zip(names, rows)
        ]
    return tuple(rows)


def _state_store(cache: dict, li, slots, live, new) -> dict:
    """``new`` (:func:`_state_rows`' leaves, in its order) written in
    place over layer ``li``'s rows of the stacked leaves: every slot's,
    or row b into slot ``slots[b]``. A row ``live`` [B] marks dead (a
    finished slot, a wave's pad row, which carries slot 0) puts back
    what is there AT ITS TURN: the rows go one after the other, so a
    pad row behind the real row of the same slot keeps that row's
    write."""
    out = dict(cache)
    keep = lambda n, o, on: jnp.where(on.reshape((-1,) + (1,) * (n.ndim - 1)), n, o)
    for name, rows in zip([n for n in _STATES if n in cache], new):
        buf = cache[name]
        if slots is None:
            rows = keep(rows, _clayer(buf, li), live)
            buf = jax.lax.dynamic_update_index_in_dim(buf, rows, li, 0)
        else:
            li32 = jnp.asarray(li, jnp.int32)
            zeros = (jnp.zeros((), jnp.int32),) * (buf.ndim - 2)
            for b in range(rows.shape[0]):
                at = (li32, slots[b]) + zeros
                row = rows[b][None, None]
                cur = jax.lax.dynamic_slice(buf, at, row.shape)
                buf = jax.lax.dynamic_update_slice(buf, keep(row, cur, live[b]), at)
        out[name] = buf
    return out


#: what a verify step keeps of each drafted position of a layer that
#: holds a state until the count of accepted drafts is known
#: (:func:`_state_commit`), by the layers' kind: a linear layer's k, v,
#: g, beta and the convolution's new rows; a conv layer's new rows
_PENDING = {
    "linear": ("pend_k", "pend_v", "pend_g", "pend_b", "pend_pre"),
    "conv": ("pend_u",),
    "mamba": ("pend_x", "pend_dt", "pend_b", "pend_pre"),
}


def _state_kind(c: LlamaConfig) -> str:
    """The kind of the model's layers that hold a slot's past whole
    (``llama.STATE_KINDS``; a model has one such kind at most)."""
    return next(k for k in llama.STATE_KINDS if k in c.layer_types)


def _state_mixer(c: LlamaConfig, live, slots=None, fresh=None, real=None,
                 counts=None, commit: bool = True):
    """→ ``mix(x, layer, cache, li) -> (y [B, S, P] for wo, cache)``: a
    linear or conv layer's mixer (``models/kda.py``,
    ``models/shortconv.py``) over row ``li`` of the cache's
    :data:`_STATES` leaves, read and written in place
    (:func:`_state_rows`, :func:`_state_store`). ``real`` [B, S]: the
    tokens that move the state, ``counts`` [B] of them a row (None:
    all). ``commit`` false is the verify step's: the state stays, and
    each position's inputs go to the :data:`_PENDING` leaves. A mamba
    layer's ``y`` is the pair (y, m): its scan's output beside it."""
    kind = _state_kind(c)
    mixer = llama.mixer_of(kind)

    def mix(x, layer, cache, li):
        h = model_norm(x, layer["attn_norm"], c)
        rows = _state_rows(cache, li, slots, fresh)
        if commit:
            y, *rows = mixer.mix(h, layer, c, *rows, real, counts)
            return y, _state_store(cache, li, slots, live, rows)
        y, *rest, inputs = mixer.mix_parts(h, layer, c, *rows)
        if kind == "mamba":  # (y, the scan's output: the gmu layers')
            y = (y, rest[0])
        cache = {**cache, **{
            n: jax.lax.dynamic_update_index_in_dim(cache[n], a, li, 0)
            for n, a in zip(_PENDING[kind], inputs)
        }}
        return y, cache

    return mix


def _state_pending(cache: dict, c: LlamaConfig, s: int) -> dict:
    """``cache`` with zeroed :data:`_PENDING` leaves for ``s`` positions."""
    n, b, _, width = cache["conv"].shape
    new_rows = lambda: jnp.zeros((n, b, s, width), cache["conv"].dtype)
    if "state" not in cache:
        return {**cache, "pend_u": new_rows()}
    f32 = jnp.float32
    if _state_kind(c) == "mamba":  # state [n, B, N, d_inner]
        n_state = cache["state"].shape[2]
        return {
            **cache,
            **{k: jnp.zeros((n, b, s, width), f32) for k in ("pend_x", "pend_dt")},
            "pend_b": jnp.zeros((n, b, s, n_state), f32),
            "pend_pre": new_rows(),
        }
    nh, d = cache["state"].shape[2:4]
    return {
        **cache,
        **{k: jnp.zeros((n, b, s, nh, d), f32) for k in _PENDING["linear"][:3]},
        "pend_b": jnp.zeros((n, b, s, nh), f32),
        "pend_pre": new_rows(),
    }


def _state_commit(
    cache: dict, n_tokens, write_mask, c: LlamaConfig, params=None
) -> dict:
    """The verify step's second half for the layers that hold a state:
    each live slot's state and tail advanced by its first ``n_tokens``
    [B] positions (the last token and the accepted drafts) and by no
    rejected one → the cache without the :data:`_PENDING` leaves.
    ``params``: the model's (a mamba layer's decay is its weight's)."""
    from dstack_tpu.models import kda, mamba

    names = _PENDING[_state_kind(c)]
    pend = [cache[k] for k in names]
    cache = {k: v for k, v in cache.items() if k not in names}
    if _state_kind(c) == "mamba":

        def one_ssm(cache, xs):
            li, a_log, *inputs = xs
            state, tail = _state_rows(cache, li)
            with jax.named_scope("dtpu.ssm.scan"):
                new = mamba.advance(
                    state, tail, {"ssm_a_log": a_log}, inputs, n_tokens
                )
            return _state_store(cache, li, None, write_mask, new), None

        cache, _ = jax.lax.scan(one_ssm, cache, (
            jnp.arange(pend[0].shape[0]),
            params[llama.STACK_OF["mamba"]]["ssm_a_log"], *pend,
        ))
        return cache
    if "state" not in cache:  # a tail alone: every layer's at once
        with jax.named_scope("dtpu.conv.tail"):
            tail = jax.vmap(kda.next_tail, in_axes=(0, 0, None))(
                pend[0], cache["conv"], n_tokens
            )
        keep = write_mask[None, :, None, None]
        return {**cache, "conv": jnp.where(keep, tail, cache["conv"])}
    real = jnp.arange(pend[0].shape[2])[None, :] < n_tokens[:, None]  # [B, S]

    def one(cache, xs):
        li, k, v, g, beta, pre = xs
        state, tail = _state_rows(cache, li)
        with jax.named_scope("dtpu.linear.state"):
            _, state = kda.rule(
                jnp.zeros_like(k), k, v,
                jnp.where(real[..., None, None], g, 0.0),
                jnp.where(real[..., None], beta, 0.0), state,
            )
        tail = kda.next_tail(pre, tail, n_tokens)
        return _state_store(cache, li, None, write_mask, (state, tail)), None

    cache, _ = jax.lax.scan(one, cache, (jnp.arange(pend[0].shape[0]), *pend))
    return cache


def _latent_layer(attend, c: LlamaConfig, valid, mix=None):
    """A latent program's attention, ``attend(x, layer, cache, row, run)
    -> (o [B, S, o_dim], cache)`` over row ``row`` of its cache buffers,
    → the ``one_layer(x, layer, cache, li, run)`` that
    :func:`_mla_layers_inplace` drives, the one copy the four programs
    share: the plain layer (:func:`_latent_out`), or, for a model whose
    layer holds several sublayers (``sublayers`` > 1), each sublayer's
    attention over its own cache row and its dense FFN, with the
    layer's experts read after the first attention and their sum
    carried to behind the last FFN (``llama._shortcut_layer`` is the
    training path's)."""
    n = c.sublayers

    def one_layer(x, layer, cache, li, run):
        if run.kind == "linear":  # ``mix``: the program's :func:`_state_mixer`
            y, cache = mix(x, layer, cache, li)
            return _latent_out(x, cache, y, layer, c, valid)
        if n == 1:
            o, cache = attend(x, layer, cache, li, run)
            return _latent_out(x, cache, o, layer, c, valid)
        for i in range(n):
            sub = layer[f"sub{i}"]
            o, cache = attend(x, sub, cache, n * li + i, run)
            x = _latent_wo(x, o, sub)
            if i == 0:
                with jax.named_scope("dtpu.scmoe"):
                    branch, picks = _mlp_picks(
                        x, cache, llama.expert_branch_of(layer), c, valid
                    )
                cache = _count_picks(cache, picks)
            x = x + _mlp_out(x, sub, c)
        return x + branch, cache

    return one_layer


def _embed_lookup(params: dict, tokens: jax.Array, c: LlamaConfig) -> jax.Array:
    x = params["embed"].at[tokens].get(mode="fill", fill_value=0).astype(c.dtype)
    if c.embed_scale:
        x = x * jnp.asarray(c.hidden_size**0.5, c.dtype)
    if c.embed_multiplier:
        x = x * jnp.asarray(c.embed_multiplier, c.dtype)
    return x


def _head_logits(
    params: dict, x: jax.Array, c: LlamaConfig, eq: str = "be,ev->bv"
) -> jax.Array:
    """Post-final-norm hidden → f32 logits with the Gemma2 cap; ``eq``
    picks the einsum shape ([B,H]→[B,V] default, [B,S,H]→[B,S,V] for
    the speculative verify step)."""
    from dstack_tpu.models.llama import head_logits_einsum

    logits = head_logits_einsum(params, x, c, eq)
    if c.logit_scale:
        logits = logits * c.logit_scale  # Cohere
    if c.logit_softcap:
        logits = c.logit_softcap * jnp.tanh(logits / c.logit_softcap)
    return logits


def _expert_rows(stack: dict, x: jax.Array, c: LlamaConfig):
    """A layer stack on its way into a layer scan → (what the scan may
    slice, ``rows(layer, j)``: the scan's layer ``j`` of the stack with
    what was kept back). Where a layer's call of ``moe.moe_mlp`` on
    hidden states shaped as ``x`` [B, T, H] reads only the picked
    experts, the three expert stacks stay whole and the layer gets
    ``moe.Row(stack, j)`` of each (its docstring: why); else the stack
    goes as it came and ``rows`` hands the layer back."""
    from dstack_tpu.models import moe

    if not (
        c.n_experts and "w_router" in stack and moe.reads_picked_experts(
            stack, *x.shape[:2], c.n_experts, c.experts_per_token,
            c.capacity_factor, None,
            sigmoid_input=c.router_sigmoid_input, act=c.moe_act,
        )
    ):
        return stack, lambda layer, j: layer
    whole = {w: stack[w] for w in moe.EXPERT_STACKS}
    return (
        {k: v for k, v in stack.items() if k not in whole},
        lambda layer, j: {
            **layer, **{w: moe.Row(a, j) for w, a in whole.items()}
        },
    )


def _mla_layers_inplace(params: dict, cache: dict, x: jax.Array, one_layer, c):
    """Drive ``one_layer(x, layer, cache, li, run) -> (x, cache)`` over
    the model's layer runs (``llama.layer_runs``: the ``first_k_dense``
    prelude, unrolled, then runs of consecutive layers of one group,
    each one ``lax.scan`` over its slice of its group's stack; a
    DeepSeek model is the prelude and one run) with the STACKED cache
    buffers as the carry of the prelude and of every scan alike: layer
    ``li`` of its buffers writes its new rows into the donated buffers
    in place and reads its rows from them → (x, cache). Every serving
    program's form, prefill and decode: handed through a scan as
    xs → ys the cache is held twice and copied whole (PERF.md §6, PR 25
    and PR 29). A model with linear layers is walked by its periods
    (:func:`_walk_layer_groups`: (linear x 5, full) is one scan body
    however deep the model)."""
    if "linear" in c.layer_types:
        (x, cache), _ = _walk_layer_groups(
            params, (x, cache),
            lambda carry, layer, li, run: (
                one_layer(carry[0], layer, carry[1], li, run), None
            ),
            c,
        )
        return x, cache
    k_dense = c.first_k_dense
    for run in llama.layer_runs(c):
        if run.key == "dense_layers":
            for j in range(run.lo, run.hi):
                lyr = jax.tree.map(lambda a: a[j], params["dense_layers"])
                x, cache = one_layer(x, lyr, cache, j, run)
            continue
        base = run.lo if run.window else k_dense + run.lo
        stack, rows = _expert_rows(params[run.key], x, c)

        def scan_fn(carry, layer_and_ix, run=run, rows=rows, base=base):
            (xx, cc), (layer, li) = carry, layer_and_ix
            layer = rows(layer, li - base + run.lo)
            return one_layer(xx, layer, cc, li, run), None

        (x, cache), _ = jax.lax.scan(
            scan_fn, (x, cache), (
                llama.run_slice(stack, run),
                base + jnp.arange(run.hi - run.lo),
            ),
        )
    return x, cache


def _prefill_chunk_mla(
    params: dict,
    cache: dict,
    tokens: jax.Array,  # [1, C]
    slot: jax.Array,
    last_ix: jax.Array,
    c: LlamaConfig,
    *,
    start: int,
) -> tuple[jax.Array, dict]:
    """MLA chunked prefill in the absorbed form: the chunk's latents
    write into the slot's ``ckv`` row, then the absorbed queries attend
    over the row as MQA with one rank+rope-wide kv head whose value is
    the latent itself — the flash kernel applies when the widths tile,
    and no per-head K/V ever materializes. A model with masked layers
    (an indexer, a window ring) has one prefill form, the packed one:
    its chunk is a wave of one row."""
    from dstack_tpu.models.llama import apply_rope, dual_rope_freqs

    if _masked(c, cache["ckv"].shape[2]):
        return _prefill_packed_mla(
            params, cache, tokens, slot[None],
            jnp.full((1,), start, jnp.int32), last_ix[None], c,
        )
    cl = tokens.shape[1]
    x = _embed_lookup(params, tokens, c)
    chunk_pos = start + jnp.arange(cl)
    (cos, sin), _ = dual_rope_freqs(c, chunk_pos)
    si = slot.astype(jnp.int32)
    # a chip's share of the experts counts its picks over the real tokens
    valid = (jnp.arange(cl) <= last_ix)[None] if "moe_stats" in cache else None

    def attend(x, layer, cache, li, run):
        # cache["ckv"] [Lf, B_pool, Tmax, rank+rope]: this layer is row li
        gc = run.config
        h, _, q_nope, q_pe, new_rows = _latent_in(
            x, layer, gc, c, lambda t: apply_rope(t, cos, sin, interleaved=True)
        )
        cache = {
            **cache,
            "ckv": _cwrite_chunk(cache["ckv"], li, si, start, new_rows, axis=0),
        }
        row = _own_rows(_cread_rows(cache["ckv"], li, si[None], c.dtype))  # [1, Tmax, R]
        q_abs, w_kb_v = _latent_absorb(q_nope, q_pe, layer, gc)
        o = _attend_causal(q_abs, row, start, gc, c)
        return _latent_values(o, w_kb_v, h, layer, gc), cache

    mix = None
    if "state" in cache:  # a chunk at position 0 starts from no state
        mix = _state_mixer(
            c, jnp.ones((1,), bool), si[None], jnp.full((1,), start == 0),
            (jnp.arange(cl) <= last_ix)[None], (last_ix + 1)[None],
        )
    x, cache = _mla_layers_inplace(
        params, cache, x, _latent_layer(attend, c, valid, mix), c
    )
    x = rms_norm(x, params["final_norm"], c.norm_eps)
    last = jnp.take_along_axis(
        x, last_ix[None, None, None].astype(jnp.int32), axis=1
    )[:, 0]
    return _head_logits(params, last, c), cache


def _cwrite_ring(
    buf, li, positions, write_mask, new, axis: int = 0, unroll: bool = True,
    slots=None, counts=None, **kw,
):
    """:func:`_cwrite_rows` into a window layers' ring: ``new``
    [B, *slot] with S in the place of T goes to rows ``(positions[b] +
    s) %`` the ring's. A block write does not wrap, so a step's few
    tokens go in one at a time, and a prefill chunk (``counts`` given)
    in two pieces: the rows that fit before the ring's end, then the
    rest from row 0."""
    put = partial(
        _cwrite_rows, layer=li, write_mask=write_mask, axis=axis, unroll=unroll,
        slots=slots, **kw,
    )
    t_ax = 1 + axis  # of ``new``
    rows, s = buf.shape[1 + t_ax], new.shape[t_ax]
    if counts is None:
        for j in range(s):
            buf = put(
                buf, positions=jnp.mod(positions + j, rows),
                new=new[(slice(None),) * t_ax + (slice(j, j + 1),)],
            )
        return buf
    first = jnp.mod(positions, rows)
    fit = rows - first  # rows up to the ring's end; past it they drop
    buf = put(buf, positions=first, new=new, counts=counts)
    rest = jnp.take_along_axis(
        new,
        jnp.expand_dims(
            jnp.clip(fit[:, None] + jnp.arange(s)[None, :], 0, s - 1),
            [a for a in range(1, new.ndim) if a != t_ax],
        ),
        axis=t_ax,
    )
    return put(
        buf, positions=jnp.zeros_like(first), new=rest,
        counts=jnp.clip(counts - fit, 0, s),
    )


def _stacked_write(
    cache: dict, name: str, li, positions, write_mask, new, slots=None, counts=None
):
    """``new`` [B, S, width] written in place into layer ``li`` of the
    latent buffer ``name`` at each row's ``positions[b] + s`` (rows,
    ``slots`` and ``counts`` as in :func:`_cwrite_rows`); in the window
    ring modulo its rows (:func:`_cwrite_ring`) → the cache with that
    buffer replaced."""
    write = _cwrite_ring if _is_ring(name) else partial(_cwrite_rows, axis=0, unroll=True)
    buf = write(cache[name], li, positions, write_mask, new, slots=slots, counts=counts)
    return {**cache, name: buf}


def _decode_step_mla(
    params: dict,
    cache: dict,
    tokens: jax.Array,  # [B]
    positions: jax.Array,  # [B]
    c: LlamaConfig,
    write_mask: jax.Array,
) -> tuple[jax.Array, dict]:
    """Absorbed MLA decode: per layer, stream the slot's latent row
    ONCE at rank+rope width — for DeepSeek-V3 that is ~100× fewer HBM
    bytes than materialized per-head K/V in the bandwidth-bound decode
    regime. A full layer with an indexer scores the slot's cached index
    keys, selects, and attends under the selection; a window layer
    attends over its ring."""
    from dstack_tpu.models.llama import dual_rope_freqs

    x = _embed_lookup(params, tokens, c)[:, None, :]
    ropes = dual_rope_freqs(c, positions)  # [B, rope/2] each
    valid = write_mask[:, None] if "moe_stats" in cache else None

    def attend(x, layer, cache, li, run):
        # cache: the stacked buffers; this layer is row li of its group's
        gc = run.config
        cos, sin = llama.layer_rope(ropes, c, run.window)
        rope = lambda t: _apply_rope_batch(t, cos, sin, interleaved=True)
        name = "win" if run.window else "ckv"
        tmax = cache[name].shape[2]
        h, qa, q_nope, q_pe, new_row = _latent_in(
            x, layer, gc, c, rope,
            rope_key=lambda t: rope(t[:, :, None])[:, 0, 0][:, None],
        )
        cache = _stacked_write(cache, name, li, positions, write_mask, new_row)
        if _attends_masked(cache, run):
            row = _clayer(cache[name], li)  # [B, Tmax, R]
        w_kb_nope, w_kb_v = _mla_kb(layer, gc)
        q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, :, 0], w_kb_nope)
        q_abs = jnp.concatenate([q_lat, q_pe[:, :, 0]], axis=-1)  # [B,H,R]
        mask = None
        if run.window:
            mask = _ring_mask(positions[:, None], positions, tmax, run.window)
        elif "idx" in cache:
            q_i, k_i, w_i = llama.index_qkw(h, qa, layer, gc, rope)
            cache = _stacked_write(cache, "idx", li, positions, write_mask, k_i)
            mask = llama.index_select(
                q_i, w_i, _clayer(cache["idx"], li),
                jnp.arange(tmax)[None, None, :] <= positions[:, None, None],
                gc.index_topk,
            )
        if mask is not None:
            o = _attend_masked(q_abs[:, :, None], row, mask, h, layer, gc, run.window)
        else:
            o_lat = _attend_live(
                q_abs[:, :, None], cache[name], li, positions, write_mask, gc
            )
            o = _latent_values(o_lat, w_kb_v, h, layer, gc)
        return o, cache

    mix = None
    if "state" in cache:  # a dead slot's state and tail stay
        mix = _state_mixer(c, write_mask, real=write_mask[:, None])
    x, cache = _mla_layers_inplace(
        params, cache, x, _latent_layer(attend, c, valid, mix), c
    )
    x = rms_norm(x, params["final_norm"], c.norm_eps)
    return _head_logits(params, x[:, 0], c), cache


def _verify_step_mla(
    params: dict,
    cache: dict,
    tokens: jax.Array,  # [B, S]
    positions: jax.Array,  # [B]
    c: LlamaConfig,
    write_mask: jax.Array,
    draft_len=None,  # [B]: drafts a row holds (a model with linear layers)
) -> tuple[jax.Array, dict]:
    """Absorbed-form multi-token decode (speculative verification)."""
    from dstack_tpu.models.llama import dual_rope_freqs

    b, sdraft = tokens.shape
    x = _embed_lookup(params, tokens, c)
    pos_grid = positions[:, None] + jnp.arange(sdraft)[None, :]  # [B, S]
    ropes = jax.tree.map(
        lambda a: a.reshape(b, sdraft, c.qk_rope_head_dim // 2),
        dual_rope_freqs(c, pos_grid.reshape(-1)),
    )
    valid = (
        jnp.broadcast_to(write_mask[:, None], (b, sdraft))
        if "moe_stats" in cache else None
    )
    write = partial(_stacked_write, positions=positions, write_mask=write_mask)

    def attend(x, layer, cache, li, run):
        gc = run.config
        cos, sin = llama.layer_rope(ropes, c, run.window)
        # MLA rope is always interleaved
        rope_rows = lambda t: _rope_rows(t, cos, sin, interleaved=True)
        name = "win" if run.window else "ckv"
        h, qa, q_nope, q_pe, new_rows = _latent_in(x, layer, gc, c, rope_rows)
        cache = write(cache, name, li, new=new_rows)
        if _attends_masked(cache, run):
            row = _clayer(cache[name], li)  # [B, Tmax, R]
        q_abs, w_kb_v = _latent_absorb(q_nope, q_pe, layer, gc)
        cache, mask = _latent_mask(
            cache, li, run, h, qa, layer, rope_rows, write, _clayer,
            pos_grid, positions + (sdraft - 1) if run.window else None,
        )
        if mask is not None:
            o = _attend_masked(
                q_abs, row, mask, h, layer, gc, run.window,
                n_keys=positions + sdraft,
            )
        else:
            o_lat = _attend_live(
                q_abs, cache[name], li, positions, write_mask, gc
            )
            o = _latent_values(o_lat, w_kb_v, h, layer, gc)
        return o, cache

    mix = None
    if "state" in cache:
        # a rejected draft must not have moved a state: the layers read
        # theirs and keep every position's inputs, and the states are
        # advanced once the logits say how many drafts stand
        mix = _state_mixer(c, write_mask, commit=False)
        cache = _state_pending(cache, c, sdraft)
    x, cache = _mla_layers_inplace(
        params, cache, x, _latent_layer(attend, c, valid, mix), c
    )
    x = rms_norm(x, params["final_norm"], c.norm_eps)
    logits = _head_logits(params, x, c, eq="bse,ev->bsv")
    if mix is not None:
        cache = _state_commit(
            cache, _tokens_standing(logits, tokens, draft_len), write_mask, c
        )
    return logits, cache


def _tokens_standing(logits, tokens, draft_len):
    """Of a verify step's positions a slot, how many stand → [B] int32
    in 1..S: the last token and the drafts that the greedy pick of the
    position before each confirms, up to the first that it does not:
    the engine's own rule (``InferenceEngine._accept_drafts``), taken
    here because a state, unlike a cache row, cannot be masked later.
    ``draft_len`` [B]: the drafts a row really holds (None: S - 1)."""
    s = tokens.shape[1]
    agree = jnp.argmax(logits[:, :-1], axis=-1).astype(jnp.int32) == tokens[:, 1:]
    if draft_len is not None:
        agree = agree & (jnp.arange(s - 1)[None, :] < draft_len[:, None])
    return 1 + jnp.sum(jnp.cumprod(agree.astype(jnp.int32), axis=1), axis=1)


def prefill(
    params: dict,
    tokens: jax.Array,  # [1, Tp] int32, right-padded
    lengths: jax.Array,  # [1] int32 true length
    slot: jax.Array,  # [] int32: cache row to write
    config: LlamaConfig,
    cache: dict,
) -> tuple[jax.Array, dict]:
    """One-shot prompt prefill → (last-token logits [1, V], cache).

    Thin wrapper over :func:`prefill_chunk_step` at ``start=0`` — ONE
    code path for prompt processing, so model-family changes can't
    drift between the one-shot form (tests, simple callers) and the
    engine's chunked loop."""
    assert tokens.shape[0] == 1, "one-shot prefill is single-sequence"
    return prefill_chunk_step(
        params, cache, tokens, slot, lengths[0] - 1, config, start=0
    )


def _scan_layers_kv(params: dict, cache: dict, x: jax.Array, one_layer, c):
    """Drive ``one_layer(x, ck, cv, layer, li, window, nope) -> (x, ck,
    cv)`` over the grouped scan layout (static per-layer windows / NoPE
    flags ride the unrolled group; see :func:`llama.grouped_scan_layout`)
    with the STACKED cache buffers ``ck`` / ``cv`` as the carry of the
    scan and of its unrolled tail alike: layer ``li`` writes its chunk
    into the donated buffers in place and reads its rows from them →
    (final hidden, updated cache). ONE copy of the scan/tail plumbing
    shared by the chunked and packed prefill forms, so a layout change
    cannot silently diverge them."""
    from dstack_tpu.models.llama import (
        grouped_scan_layout,
        layer_nope,
        sublayer,
    )

    stack, rows = _expert_rows(params["layers"], x, c)
    g, windows, xs_main, xs_tail = grouped_scan_layout(c, stack)
    nopes = layer_nope(c)
    n_main = c.n_layers - (c.n_layers % g if g > 1 else 0)

    def group_fn(carry, group_and_ix):
        group, gi = group_and_ix
        for i in range(g):
            carry = one_layer(
                *carry, rows(sublayer(group, i, g), gi * g + i), gi * g + i,
                windows[i], nopes[i],
            )
        return carry, None

    carry, _ = jax.lax.scan(
        group_fn, (x, *_cache_pack(cache)), (xs_main, jnp.arange(n_main // g))
    )
    # pattern doesn't divide the layer count (Gemma3): unroll the last
    # layers after the scan
    for j in range(n_main, c.n_layers):
        carry = one_layer(
            *carry, rows(jax.tree.map(lambda a: a[j - n_main], xs_tail), j), j,
            windows[j], nopes[j],
        )
    x, ck, cv = carry
    return x, _cache_unpack(ck, cv)


def _walk_layer_groups(params: dict, carry, one_layer, c: LlamaConfig, one=None):
    """Drive ``one_layer(carry, layer, li, run) -> (carry, y)`` over a
    model of layer GROUPS (a grouped-query one; a latent one with linear
    layers, its carry ``(x, cache)``), in the order of
    ``llama.layer_segments``: the prelude, then ONE ``lax.scan`` over the
    periods whose body is one period (each of its runs a scan over its
    share of its group's stack), then what is left over (folded again
    where a second pattern repeats in it), so that the program holds a
    layer body a run of a period and does not grow with depth. ``li`` is the layer's row in its kind's cache buffers
    (full layers: the prelude first). → (carry, [(run, first row, ys
    stacked in row order)] a run of the prelude and of the tail and a
    stack of the period: what a program that only READS the cache in
    its scans writes after them, one block write a buffer). ``one``: a
    traced int32 that reads 1 (a decode step's, where some run is ONE
    layer that reads a buffer the step writes after its scans: a scan of
    one trip is inlined, the read then stands bare in ``decode_loop``'s
    token loop, and the compiler copies the whole leaf into the write's
    loop and out of it, 2 x 0.67 GB a leaf a token at 32 x 8192,
    device-free; a trip count it cannot read keeps the loop)."""
    row = partial(llama.run_row, c)  # the run's first layer → its cache row

    def scan_run(carry, run, ahead=0):
        # the layers are indexed out of the whole stack (what lax.scan
        # does with its xs): handed down as a period's share through an
        # outer scan's xs, a period's weights were copied out a period
        # (device-free: 2.4 GB of ``temp``, 3 layers x 32 experts)
        stack, rows = _expert_rows(params[run.key], carry[0], c)

        def layer_fn(carry, j):
            layer = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(
                    a, run.lo + ahead + j, 0, keepdims=False
                ),
                stack,
            )
            layer = rows(layer, run.lo + ahead + j)
            return one_layer(carry, layer, row(run) + ahead + j, run)

        if one is not None and run.hi - run.lo == 1:
            ys = jax.tree.map(
                lambda a: jnp.zeros((1,) + a.shape, a.dtype),
                jax.eval_shape(lambda cr: layer_fn(cr, 0)[1], carry),
            )

            def trip(j, carry_ys):
                carry, y = layer_fn(carry_ys[0], j)
                return carry, jax.tree.map(lambda a: a[None], y)

            return jax.lax.fori_loop(0, one, trip, (carry, ys))
        return jax.lax.scan(layer_fn, carry, jnp.arange(run.hi - run.lo))

    out = []
    # a model of one repeating pattern is one segment; of two (a lower
    # half of one period, an upper half of another) one after the other
    for plan in llama.layer_segments(c):
        for run in plan.head:
            carry, ys = scan_run(carry, run)
            out.append((run, row(run), ys))
        if plan.count:

            def period_fn(carry, i, plan=plan):
                ys = {key: [] for key in plan.per}
                for run in plan.period:
                    carry, y = scan_run(carry, run, i * plan.per[run.key])
                    ys[run.key].append(y)
                return carry, {
                    key: y[0] if len(y) == 1 else jax.tree.map(
                        lambda *a: jnp.concatenate(a), *y
                    )
                    for key, y in ys.items()
                }

            carry, ys = jax.lax.scan(period_fn, carry, jnp.arange(plan.count))
            seen = set()
            for run in plan.period:
                if run.key not in seen:  # once a stack: its first run's row
                    seen.add(run.key)
                    out.append((run, row(run), jax.tree.map(
                        lambda a: a.reshape((-1,) + a.shape[2:]), ys[run.key]
                    )))
        for run in plan.tail:
            carry, ys = scan_run(carry, run)
            out.append((run, row(run), ys))
    return carry, out


def _group_kv(run) -> tuple[str, str]:
    """The cache buffers a run of a grouped-query model of groups keeps
    its keys and values in (a cross layer's: the full layer's, which it
    reads)."""
    return ("win_k", "win_v") if run.window else ("k", "v")


def _shared_zeros(c: LlamaConfig, x: jax.Array, cache=None) -> dict:
    """What layers of a model hand to layers further up at the same
    positions, before any has, a part of every group program's carry:
    ``m`` [B, S, d_inner], the latest mamba layer's scan output (the gmu
    layers read it); with ``cache`` (a decode step, which else only
    reads its buffers in its scans) the full layer's ``k`` / ``v``
    buffers themselves, which the cross layers read after it has
    written this token's row. Empty, no leaf of any program, for a
    model with neither."""
    shared = {}
    if "gmu" in c.layer_types:
        shared["m"] = jnp.zeros(x.shape[:2] + (c.ssm_inner,), x.dtype)
    if cache is not None and "cross" in c.layer_types:
        shared["k"], shared["v"] = cache["k"], cache["v"]
    return shared


def _lambda_tables(c: LlamaConfig) -> dict:
    """kind → lambda_init of its layers by their row (differential
    attention; empty for any other model)."""
    if not c.diff_attn:
        return {}
    return {
        k: llama.diff_lambda_init(c, k)
        for k in ("full", "window", "cross") if k in c.layer_types
    }


def _mixed_layer(x, layer, kind: str, c: LlamaConfig, shared: dict, mix):
    """The mixer of a layer of a grouped-query model that does not
    attend → (y for ``wo``, shared, what ``mix`` returned besides): a
    conv or mamba layer's through ``mix(x, layer) -> (y, rest)`` (the
    program's way of reading and keeping its state), a mamba layer's
    scan output kept for the gmu layers; a gmu layer's from that."""
    if kind == "gmu":
        h = model_norm(x, layer["attn_norm"], c)
        return llama.gmu_mix(h, shared["m"], layer), shared, None
    y, rest = mix(x, layer)
    if kind == "mamba":
        y, m = y
        if "m" in shared:
            shared = {**shared, "m": m}
    return y, shared, rest


#: the kinds of layer of a grouped-query model of groups that do not attend
_MIXED = ("conv", "mamba", "gmu")


def _attend_rows(
    q: jax.Array,  # [B, H, S, D]
    k: jax.Array,  # [B, Hkv, T, D] each row's cached keys
    v: jax.Array,
    mask: jax.Array,  # [B, S, T] bool
    c: LlamaConfig,
    n_keys,  # [B]: rows of the T that can be visible
) -> jax.Array:
    """Grouped-query attention of a prefill chunk under an explicit mask
    (a window ring's row order, a full row's causal frontier at a traced
    start) → [B, S, q_dim]: the cache is read at KV width, the group's
    query heads ride one einsum. A short row (a ring) is scored at
    once; a long one goes by a sequence at a time, its keys in blocks
    under a running softmax, as many blocks as hold its ``n_keys``: the
    work follows the context a row has, not ``max_seq`` (as
    :func:`_attend_masked` does for the latent family)."""
    b, nh, s, d = q.shape
    hkv, t = k.shape[1], k.shape[2]
    scale, cap = c.attention_scale, c.attn_softcap
    q = q.reshape(b, hkv, nh // hkv, s, d)

    def scores(q, k, m):  # q [Hkv, G, S, D], k [Hkv, T', D], m [S, T']
        sc = jnp.einsum(
            "hgsd,htd->hgst", q, k, preferred_element_type=jnp.float32
        ) * scale
        if cap:
            sc = cap * jnp.tanh(sc / cap)
        return jnp.where(m[None, None], sc, NEG_INF)

    def attend(q, k, v, m):
        pr = jax.nn.softmax(scores(q, k, m), axis=-1)
        return jnp.einsum("hgst,htd->hgsd", pr.astype(v.dtype), v)

    kb = math.gcd(t, _KEY_BLOCK)  # keys a block

    def attend_blocks(args):
        q, k, v, m, blocks = args

        def block(i, carry):
            top, den, acc = carry  # running max, sum [Hkv,G,S], values [Hkv,G,S,D]
            sc = scores(
                q, jax.lax.dynamic_slice_in_dim(k, i * kb, kb, 1),
                jax.lax.dynamic_slice_in_dim(m, i * kb, kb, 1),
            )
            new_top = jnp.maximum(top, sc.max(-1))
            # (a query with no visible key so far: see _attend_masked)
            keep = jnp.exp(top - new_top)
            pr = jnp.exp(sc - new_top[..., None])
            acc = acc * keep[..., None] + jnp.einsum(
                "hgst,htd->hgsd", pr.astype(v.dtype),
                jax.lax.dynamic_slice_in_dim(v, i * kb, kb, 1),
                preferred_element_type=jnp.float32,
            )
            return new_top, den * keep + pr.sum(-1), acc

        lead = q.shape[:-1]
        _, den, acc = jax.lax.fori_loop(
            0, blocks, block,
            (
                jnp.full(lead, NEG_INF, jnp.float32),
                jnp.zeros(lead, jnp.float32),
                jnp.zeros(lead + (d,), jnp.float32),
            ),
        )
        return (acc / den[..., None]).astype(v.dtype)

    if t <= 2 * kb and b * nh * s * t * 4 <= _SCORE_BYTES:
        o = jax.vmap(attend)(q, k, v, mask)
    else:
        blocks = jnp.clip(-(-n_keys // kb), 1, t // kb).astype(jnp.int32)
        o = jax.lax.map(attend_blocks, (q, k, v, mask, blocks))
    # [B, Hkv, G, S, D] → query-head order a token
    return o.transpose(0, 3, 1, 2, 4).reshape(b, s, nh * d)


def _group_out(x, cache: dict, o, layer: dict, gc: LlamaConfig, valid):
    """The common tail of a layer of a grouped-query model of groups in
    the programs that carry the cache (prefill, verify): ``o`` (the
    attention's output, or a conv layer's mixed rows) through
    :func:`_dense_out`, the routing counts into the cache → (x, cache)."""
    stats = _moe_stats(cache)
    if stats is None:
        return _dense_out(x, o, layer, gc), cache
    x, stats = _dense_out(x, o, layer, gc, stats, valid)
    return x, _with_moe_stats(cache, stats)


def _prefill_packed_groups(
    params: dict,
    cache: dict,
    tokens: jax.Array,  # [G, C]
    slots: jax.Array,  # [G]
    starts: jax.Array,  # [G] traced per-row start positions
    last_ix: jax.Array,  # [G]; -1 marks an inactive pad row
    c: LlamaConfig,
) -> tuple[jax.Array, dict]:
    """Packed prefill of a grouped-query model of layer GROUPS, its one
    prefill form (:func:`_masked`; a serial chunk is a wave of one row):
    each row's real tokens go into its slot's rows of the run's buffers
    in place (a window layer's into its ring), and the chunk attends
    over them under the run's mask."""
    from dstack_tpu.models.llama import dual_rope_freqs

    g, cl = tokens.shape
    x = _embed_lookup(params, tokens, c)
    pos_grid = starts[:, None] + jnp.arange(cl)[None, :]  # [G, C]
    ropes = jax.tree.map(
        lambda a: a.reshape(g, cl, a.shape[-1]),
        dual_rope_freqs(c, pos_grid.reshape(-1)),
    )
    si = slots.astype(jnp.int32)
    # positions past each row's real tokens (padding, pad rows) keep
    # their bytes — the masked-future invariant
    valid = jnp.arange(cl)[None, :] <= last_ix[:, None]  # [G, C]
    newest = starts + jnp.maximum(last_ix, 0)  # [G] the last position written
    ac = c.attend_config  # the cache's shape (every run's: heads vary, not these)
    put = dict(
        positions=starts, write_mask=last_ix >= 0, slots=si, counts=last_ix + 1,
        axis=1, unroll=_tokens_on_lanes(ac.head_dim), opaque_loop=True,
    )
    # a lone row on a full layer's leaf with its tokens on the lanes
    # (head_dim 64): the block form of ONE row, bare or in a loop, makes
    # the compiler re-lay the whole leaf out around the layer scans
    # (device-free: ``temp`` 1.6 GB beside three layers' K/V); the whole
    # chunk as one ``dynamic_update_slice`` at the row's start, the
    # serial chunk's form, holds in place. What it writes past the row's
    # real tokens is the masked future; it needs the chunk to lie inside
    # the row, as the engine's chunk starts do where ``max_seq`` is whole
    # chunks
    lone_chunk = (
        g == 1 and "k" in cache and _tokens_on_lanes(ac.head_dim)
        and cache["k"].shape[3] % cl == 0
    )
    mix = None
    if "conv" in cache:
        # padded positions and pad rows leave a state and a tail
        # untouched; a row at position 0 starts from none
        mix = _state_mixer(
            c, last_ix >= 0, si, (starts == 0) & (last_ix >= 0), valid,
            last_ix + 1,
        )
    lam = _lambda_tables(c)

    def one_layer(carry, layer, li, run):
        x, cache, shared = carry
        gc = run.config
        if run.kind in _MIXED:
            y, shared, new_cache = _mixed_layer(
                x, layer, run.kind, c, shared,
                lambda x, layer: mix(x, layer, cache, li),
            )
            cache = cache if new_cache is None else new_cache
            return (*_group_out(x, cache, y, layer, gc, valid), shared), None
        cross = run.kind == "cross"
        cos, sin = llama.layer_rope(ropes, c, run.window)
        q, k, v = _dense_in(
            x, layer, gc,
            lambda t: _rope_rows(t, cos, sin, interleaved=c.rope_interleaved),
            False, None, cross,
        )
        write = _cwrite_ring if run.window else _cwrite_rows
        rows = []  # the wave's rows of the run's buffers, [G, Hkv, T, D] each
        for name, new in zip(_group_kv(run), (k, v)):
            if cross:  # the one full layer's rows, which it has written
                rows.append(_cread_rows(cache[name], 0, si, q.dtype))
                continue
            if lone_chunk and not run.window:
                buf = _cwrite_chunk(cache[name], li, si[0], starts[0], new)
            else:
                buf = write(cache[name], li, new=new, **put)
            cache = {**cache, name: buf}
            rows.append(_cread_rows(buf, li, si, new.dtype))
        t = rows[0].shape[2]
        with _attn_scope(run.window, cross=cross):
            if run.window:
                mask = _ring_mask(pos_grid, newest, t, run.window)
            else:
                mask = jnp.arange(t)[None, None, :] <= pos_grid[:, :, None]
            o = _attend_rows(
                q, *rows, mask, gc.attend_config, jnp.minimum(newest + 1, t)
            )
        if c.diff_attn:
            o = llama.diff_combine(o, layer, gc, lam[run.kind][li])
        return (*_group_out(x, cache, o, layer, gc, valid), shared), None

    (x, cache, _), _ = _walk_layer_groups(
        params, (x, cache, _shared_zeros(c, x)), one_layer, c
    )
    x = model_norm(x, params["final_norm"], c)
    last = jnp.take_along_axis(
        x, jnp.maximum(last_ix, 0)[:, None, None].astype(jnp.int32), axis=1
    )[:, 0]
    return _head_logits(params, last, c), cache


def _prefill_one_layer(
    c: LlamaConfig,
    ropes: tuple,
    *,
    rope_apply,  # (t [B, Hh, C, D], cos, sin) → roped t
    temp_apply,  # (q) → NoPE-temperature-scaled q (Llama4)
    kv_update,  # (ck, cv, li, k, v [B, Hkv, C, D]) → (ck, cv, row_k, row_v)
    q_offset,  # static int (serial chunk) or [B] vector (packed)
    mesh=None,  # tp mesh: the flash kernel runs per KV-head shard
):
    """Build the dense prefill attention+MLP sublayer shared by the
    serial chunk and packed multi-slot forms. The two forms differ ONLY
    in rope application, NoPE temperature broadcasting, the cache
    write/read, and the causal offset — injected here so every
    model-family branch (qk norm, sinks, softcap, post norms, parallel
    block, ...) exists ONCE and packed-vs-serial parity cannot drift."""
    from dstack_tpu.models.llama import layer_rope
    from dstack_tpu.ops.attention import attention
    from dstack_tpu.parallel.sharding import default_rules, kernel_shard

    scale = c.attention_scale

    def one_layer(x, ck, cv, layer, li, window, nope):
        # ck/cv [L, B_pool, Hkv, Tmax, D]: the stacked cache, this layer li
        b, cl = x.shape[0], x.shape[1]
        cos, sin = layer_rope(ropes, c, window)
        q, k, v = _dense_in(
            x, layer, c, lambda t: rope_apply(t, cos, sin), nope, temp_apply
        )
        # write the chunk K/V into the slot rows, then attend over the
        # whole rows: positions past each causal frontier are masked,
        # so stale data beyond the prompts is never read
        ck, cv, row_k, row_v = kv_update(ck, cv, li, k, v)
        o = attention(
            q, row_k, row_v, causal=True, scale=scale, q_offset=q_offset,
            window=window, softcap=c.attn_softcap,
            chunk=0 if nope else c.attention_chunk_size,
            sinks=layer.get("sinks") if c.attn_sinks else None,
            # serving never differentiates: sink models may ride the
            # flash kernel + exact σ(lse - sink) rescale on TPU
            sinks_forward_only=True,
            shard=kernel_shard(mesh, default_rules(), b, c.n_kv_heads),
        )
        o = o.transpose(0, 2, 1, 3).reshape(b, cl, c.q_dim)
        return _dense_out(x, o, layer, c), ck, cv

    return one_layer


def prefill_chunk_step(
    params: dict,
    cache: dict,
    tokens: jax.Array,  # [1, C] int32 chunk (right-padded on the last one)
    slot: jax.Array,  # [] int32 cache row
    last_ix: jax.Array,  # [] int32: prompt's last real index MINUS start
    config: LlamaConfig,
    *,
    start: int,  # static: global position of the chunk's first token
    mesh=None,  # static: the engine's tp mesh (flash kernel per shard)
) -> tuple[jax.Array, dict]:
    """One prompt chunk → (logits at ``last_ix`` [1, V], cache).

    Chunked prefill: the chunk's K/V are written into the slot's cache
    row first, then the chunk queries attend over the row's prefix with
    causal masking at the STATIC ``start`` offset — so the pallas flash
    kernel applies (per-layer windows/softcaps included) and no
    [C, T_max] score matrix materializes. A long prompt becomes
    ceil(Tp/C) identical-shape calls, letting the scheduler run decode
    steps for other slots between chunks instead of stalling them for
    the whole prompt (and collapsing the per-length compile zoo into
    per-(C, start) variants the persistent cache reuses).
    """
    from dstack_tpu.models.llama import (
        apply_rope,
        attn_temp_scales,
        dual_rope_freqs,
    )

    c = config
    if c.mla:
        return _prefill_chunk_mla(
            params, cache, tokens, slot, last_ix, c, start=start
        )
    if c.layer_types:  # layer groups: a wave of one row
        return _prefill_packed_groups(
            params, cache, tokens, slot[None],
            jnp.full((1,), start, jnp.int32), last_ix[None], c,
        )
    x = _embed_lookup(params, tokens, c)
    chunk_pos = start + jnp.arange(tokens.shape[1])
    si = slot.astype(jnp.int32)

    def kv_update(ck, cv, li, k, v):
        ck = _cwrite_chunk(ck, li, si, start, _cstored(k, ck))
        cv = _cwrite_chunk(cv, li, si, start, _cstored(v, cv))
        return (
            ck, cv,
            _own_rows(_cread_rows(ck, li, si[None], k.dtype)),
            _own_rows(_cread_rows(cv, li, si[None], v.dtype)),
        )

    one_layer = _prefill_one_layer(
        c, dual_rope_freqs(c, chunk_pos),
        rope_apply=lambda t, cos, sin: apply_rope(
            t, cos, sin, interleaved=c.rope_interleaved
        ),
        temp_apply=lambda q: q * attn_temp_scales(chunk_pos, c)[
            None, None, :, None
        ].astype(q.dtype),
        kv_update=kv_update,
        q_offset=start,  # STATIC: the pallas flash kernel applies
        mesh=mesh,
    )
    x, cache = _scan_layers_kv(params, cache, x, one_layer, c)
    x = model_norm(x, params["final_norm"], c)
    last = jnp.take_along_axis(
        x, last_ix[None, None, None].astype(jnp.int32), axis=1
    )[:, 0]
    return _head_logits(params, last, c), cache


def _prefill_packed_mla(
    params: dict,
    cache: dict,
    tokens: jax.Array,  # [G, C]
    slots: jax.Array,  # [G]
    starts: jax.Array,  # [G] traced per-row start positions
    last_ix: jax.Array,  # [G]; -1 marks an inactive pad row
    c: LlamaConfig,
) -> tuple[jax.Array, dict]:
    """MLA packed prefill: G concurrent prompt chunks write their
    latents into their own ``ckv`` rows (in-place block writes of the
    real tokens) and attend in the absorbed MQA form with per-row
    causal frontiers."""
    from dstack_tpu.models.llama import dual_rope_freqs

    g, cl = tokens.shape
    x = _embed_lookup(params, tokens, c)
    pos_grid = starts[:, None] + jnp.arange(cl)[None, :]  # [G, C]
    ropes = jax.tree.map(
        lambda a: a.reshape(g, cl, c.qk_rope_head_dim // 2),
        dual_rope_freqs(c, pos_grid.reshape(-1)),
    )
    si = slots.astype(jnp.int32)
    # positions past each row's real tokens (padding, pad rows) keep
    # their bytes — the masked-future invariant
    valid = jnp.arange(cl)[None, :] <= last_ix[:, None]  # [G, C]
    write = partial(
        _stacked_write, positions=starts, write_mask=last_ix >= 0, slots=si,
        counts=last_ix + 1,
    )
    read = lambda leaf, li: _cread_rows(leaf, li, si, c.dtype)

    def attend(x, layer, cache, li, run):
        # cache: the stacked buffers; this layer is row li of its group's
        # (latents, and index keys where an indexer bites)
        gc = run.config
        cos, sin = llama.layer_rope(ropes, c, run.window)
        # MLA rope is always interleaved
        rope_rows = lambda t: _rope_rows(t, cos, sin, interleaved=True)
        name = "win" if run.window else "ckv"
        h, qa, q_nope, q_pe, new_rows = _latent_in(x, layer, gc, c, rope_rows)
        cache = write(cache, name, li, new=new_rows)
        row = read(cache[name], li)  # [G, Tmax, R]
        q_abs, w_kb_v = _latent_absorb(q_nope, q_pe, layer, gc)
        cache, mask = _latent_mask(
            cache, li, run, h, qa, layer, rope_rows, write, read, pos_grid,
            starts + jnp.maximum(last_ix, 0) if run.window else None,
        )
        if mask is None and g * gc.n_heads * cl * row.shape[1] * 4 > _SCORE_BYTES:
            # the causal mask alone, and a wave whose f32 scores over
            # whole rows would not fit half a GB (64 heads x 256 x 8192
            # a row): the masked form's rows in key blocks, as many as
            # hold each row's context
            mask = jnp.arange(row.shape[1])[None, None, :] <= pos_grid[:, :, None]
        if mask is not None:
            o = _attend_masked(
                q_abs, row, mask, h, layer, gc, run.window,
                n_keys=starts + jnp.maximum(last_ix, 0) + 1,
            )
        else:
            o = _attend_causal(q_abs, row, starts, gc, c)  # [G, H, C, rank]
            o = _latent_values(o, w_kb_v, h, layer, gc)
        return o, cache

    mix = None
    if "state" in cache:
        # padded positions and pad rows leave state and tail untouched;
        # a row at position 0 starts from no state
        mix = _state_mixer(
            c, last_ix >= 0, si, (starts == 0) & (last_ix >= 0), valid,
            last_ix + 1,
        )
    x, cache = _mla_layers_inplace(
        params, cache, x, _latent_layer(attend, c, valid, mix), c
    )
    x = rms_norm(x, params["final_norm"], c.norm_eps)
    last = jnp.take_along_axis(
        x, jnp.maximum(last_ix, 0)[:, None, None].astype(jnp.int32), axis=1
    )[:, 0]
    return _head_logits(params, last, c), cache


def prefill_packed_step(
    params: dict,
    cache: dict,
    tokens: jax.Array,  # [G, C] int32 chunk rows (right-padded)
    slots: jax.Array,  # [G] int32 cache rows (distinct per real row)
    starts: jax.Array,  # [G] int32 TRACED per-row global start positions
    last_ix: jax.Array,  # [G] int32 last real index minus start; -1 = pad row
    config: LlamaConfig,
) -> tuple[jax.Array, dict]:
    """Packed multi-slot prefill: G prompt chunks, one dispatch →
    (per-row logits at ``last_ix`` [G, V], cache).

    Generalizes :func:`prefill_chunk_step` from ``[1, C]`` + static
    ``start`` to ``[G, C]`` with traced per-row starts (the ``pos_grid``
    form :func:`verify_step` uses at decode width S, here at prefill
    width C): a burst of N arrivals costs ceil(N/G) dispatches per
    chunk wave instead of N batch-1 passes that underfill the MXU.
    Per-row rope angles come from the position grid, cache writes are
    in-place block writes (:func:`_cwrite_rows`) that leave out what
    short rows and inactive pad rows (``last_ix = -1``) have past their
    real tokens, and attention gets per-row causal
    frontiers via the vector ``q_offset`` (masked-einsum path — the
    pallas kernel can't tile per-row offsets). Because ``starts`` is
    traced, ONE compile per (G, C) shape serves every start
    combination — including prefix-cache-resumed rows at unequal
    starts — where the serial path compiles per (C, start).
    """
    from dstack_tpu.models.llama import attn_temp_scales, dual_rope_freqs

    c = config
    if c.mla:
        return _prefill_packed_mla(
            params, cache, tokens, slots, starts, last_ix, c
        )
    if c.layer_types:
        return _prefill_packed_groups(
            params, cache, tokens, slots, starts, last_ix, c
        )
    g, cl = tokens.shape
    x = _embed_lookup(params, tokens, c)
    pos_grid = starts[:, None] + jnp.arange(cl)[None, :]  # [G, C]
    inv_shape = c.rope_dim // 2  # narrower under GLM partial rotary
    ropes = jax.tree.map(
        lambda a: a.reshape(g, cl, inv_shape),
        dual_rope_freqs(c, pos_grid.reshape(-1)),
    )
    si = slots.astype(jnp.int32)
    temp = (
        attn_temp_scales(pos_grid.reshape(-1), c).reshape(g, cl)
        if c.attn_temp_scale else None
    )

    def kv_update(ck, cv, li, k, v):
        # each row's real tokens go in at its own start; positions past
        # them (padding, pad rows) and past the cache's end keep their
        # bytes — the masked-future invariant
        # (a leaf with its tokens on the lanes is re-laid out whole a
        # layer under the rows' loop, like the latent: its rows go unrolled)
        put = partial(
            _cwrite_rows, layer=li, positions=starts, write_mask=last_ix >= 0,
            slots=si, counts=last_ix + 1, unroll=_tokens_on_lanes(c.head_dim),
        )
        ck, cv = put(ck, new=_cstored(k, ck)), put(cv, new=_cstored(v, cv))
        return (
            ck, cv,
            _cread_rows(ck, li, si, k.dtype), _cread_rows(cv, li, si, v.dtype),
        )

    one_layer = _prefill_one_layer(
        c, ropes,
        rope_apply=lambda t, cos, sin: _rope_rows(
            t, cos, sin, interleaved=c.rope_interleaved
        ),
        temp_apply=lambda q: q * temp[:, None, :, None].astype(q.dtype),
        kv_update=kv_update,
        q_offset=starts,  # VECTOR: per-row frontiers, masked-einsum path
    )
    x, cache = _scan_layers_kv(params, cache, x, one_layer, c)
    x = model_norm(x, params["final_norm"], c)
    last = jnp.take_along_axis(
        x, jnp.maximum(last_ix, 0)[:, None, None].astype(jnp.int32), axis=1
    )[:, 0]
    return _head_logits(params, last, c), cache


def _flash_attend(
    q_rows,  # [B, Hkv, R, D] — R = grp * rows_per_slot, row-major [G, S]
    ck, cv,  # the STACKED cache leaves: arrays or (int8, scale) tuples
    li,  # the layer's row of them
    positions,  # [B] int32
    window,  # traced int32 scalar (0 = full)
    *,
    config, scale, grp, rows_per_slot, sinks_leaf, mesh,
    new=None,  # (k, v) [B, Hkv, 1, D] as attended: the token's own, not in the cache
):
    """Shared flash_decode dispatch for decode_step (rows_per_slot=1,
    the token's own K/V beside the cache as ``new``) and verify_step
    (S>1, its rows written): quant-tuple unpack, per-row sink expansion,
    optional-arg threading, interpret detection, and the shard_map wrap
    under a mesh — ONE copy, so a kernel-signature or sharding-spec
    change cannot silently diverge decode from verify. The kernel reads
    row ``li`` of the stacked leaves where they lie: no layer's slice
    is an operand."""
    c = config
    kq, ks = (ck if isinstance(ck, tuple) else (ck, None))
    vq, vs = (cv if isinstance(cv, tuple) else (cv, None))
    sinks_arr = None
    if c.attn_sinks:
        # row g*S+s carries group g's sink (decode: S=1 → [Hkv, G])
        sinks_arr = jnp.broadcast_to(
            sinks_leaf.reshape(c.n_kv_heads, grp, 1),
            (c.n_kv_heads, grp, rows_per_slot),
        ).reshape(c.n_kv_heads, grp * rows_per_slot)
    interp = jax.default_backend() != "tpu"
    softcap = float(c.attn_softcap or 0.0)

    def _fd(q_, kq_, vq_, li_, pos_, win_, *opt):
        it = iter(opt)
        kn_ = next(it) if new is not None else None
        vn_ = next(it) if new is not None else None
        ks_ = next(it) if ks is not None else None
        vs_ = next(it) if ks is not None else None
        sk_ = next(it) if sinks_arr is not None else None
        return flash_decode(
            q_, kq_, vq_, pos_, scale=scale, layer=li_, k_new=kn_, v_new=vn_,
            window=win_, softcap=softcap, sinks=sk_, k_scale=ks_, v_scale=vs_,
            interpret=interp, rows_per_slot=rows_per_slot,
        )

    opt_args = list(new or ())
    if ks is not None:
        opt_args += [ks, vs]
    if sinks_arr is not None:
        opt_args.append(sinks_arr)
    li = jnp.asarray(li, jnp.int32)
    if mesh is None:
        return _fd(q_rows, kq, vq, li, positions, window, *opt_args)
    # per-shard kernel over the tp axis (KV heads local to each shard;
    # attention is per-head → no collectives). Axes the specs don't
    # mention (dp/fsdp/ep) replicate.
    from jax.sharding import PartitionSpec as P

    h4 = P(None, "tp", None, None)
    h5 = P(None, None, "tp", None, None)
    in_specs = [h4, h5, h5, P(), P(None), P()]
    if new is not None:
        in_specs += [h4] * 2
    if ks is not None:
        in_specs += [P(None, None, "tp", None)] * 2
    if sinks_arr is not None:
        in_specs.append(P("tp", None))
    return jax.shard_map(
        _fd, mesh=mesh, in_specs=tuple(in_specs), out_specs=h4,
        check_vma=False,
    )(q_rows, kq, vq, li, positions, window, *opt_args)


def decode_step(
    params: dict,
    cache: dict,
    tokens: jax.Array,  # [B] int32: the freshly sampled tokens
    positions: jax.Array,  # [B] int32: where to write (== current length)
    config: LlamaConfig,
    write_mask: jax.Array = None,  # [B] bool: rows allowed to write K/V
    decode_kernel: Optional[str] = None,  # None: by the rule | "einsum" | "flash"
    mesh=None,  # static: shard_map the flash kernel over this mesh
) -> tuple[jax.Array, dict]:
    """One token for every slot → (logits [B, V], cache).

    ``write_mask`` guards the cache writes: inactive rows (finished, or
    mid-chunked-prefill for another request) must not scribble stale
    K/V into their slot — a decode step interleaved between prefill
    chunks would otherwise corrupt the prompt being written.

    A grouped-query layer over a plain row buffer attends through the
    ragged pallas kernel (:func:`dstack_tpu.ops.flash_decode.flash_decode`)
    — each live slot reads only the cache blocks that hold its own keys
    instead of the full ``Tmax`` row, a slot that may not write none —
    where :func:`~dstack_tpu.ops.flash_decode.reads_live_keys` says so
    (on the TPU, by the model's shape), else through the masked einsum;
    ``decode_kernel`` is a caller's or a test's way to ask for one form.
    With a ``mesh``, the kernel runs per-shard under ``shard_map``
    (q/cache sharded over KV heads on ``tp``, everything else
    replicated — attention is per-head, so no collectives are needed
    inside; GSPMD cannot partition a pallas call on its own).
    """
    from dstack_tpu.models.llama import (
        attn_temp_scales,
        dual_rope_freqs,
        layer_nope,
        layer_windows,
    )

    c = config
    b = tokens.shape[0]
    if write_mask is None:
        write_mask = jnp.ones((b,), bool)
    if c.mla:
        return _decode_step_mla(
            params, cache, tokens, positions, c, write_mask
        )
    if c.layer_types:
        return _decode_step_groups(
            params, cache, tokens, positions, c, write_mask, decode_kernel, mesh
        )
    x = _embed_lookup(params, tokens, c)[:, None, :]
    (cos, sin), (cos_l, sin_l) = dual_rope_freqs(c, positions)  # [B, D/2]
    # decode attention is a masked einsum, so *traced* per-layer window
    # and NoPE flags can ride the scan — no grouped unrolling needed
    windows = jnp.asarray(layer_windows(c), jnp.int32)
    nopes = jnp.asarray(layer_nope(c), bool)
    has_nope = any(layer_nope(c))
    temp = (
        attn_temp_scales(positions, c) if c.attn_temp_scale else None
    )  # [B]

    ck, cv = _cache_pack(cache)  # the stacked [L,B,Hkv,Tmax,D] buffers
    stack, rows = _expert_rows(params["layers"], x, c)

    def layer_fn(x, layer_and_flags):
        layer, window, nope, li = layer_and_flags
        layer = rows(layer, li)
        # Gemma3 dual rope rides the traced window too: sliding layers
        # (window > 0) rotate with the local-theta pair
        cs, sn = (
            (jnp.where(window > 0, cos_l, cos), jnp.where(window > 0, sin_l, sin))
            if c.rope_local_theta else (cos, sin)
        )
        return _decode_layer(
            x, layer, li, c, ck, cv, positions, write_mask,
            lambda t: _apply_rope_batch(t, cs, sn, interleaved=c.rope_interleaved),
            # Llama4 NoPE layers keep the unrotated q/k: a traced flag
            nope if has_nope else False,
            lambda q: q * temp[:, None, None, None].astype(q.dtype),
            window, decode_kernel=decode_kernel, mesh=mesh,
        )

    x, (k_rows, v_rows) = jax.lax.scan(
        layer_fn, x, (stack, windows, nopes, jnp.arange(c.n_layers)),
    )
    cache = _cache_unpack(
        _cwrite_rows(ck, 0, positions, write_mask, k_rows),
        _cwrite_rows(cv, 0, positions, write_mask, v_rows),
    )
    x = model_norm(x, params["final_norm"], c)
    return _head_logits(params, x[:, 0], c), cache


def _decode_layer(
    x, layer: dict, li, c: LlamaConfig, ck, cv, positions, write_mask,
    rope, nope, temp, window, ring=None, stats=None,
    decode_kernel: Optional[str] = None, mesh=None, lam0=None, held=None,
):
    """One dense layer of a decode step, the one copy → (x, this token's
    (k, v) rows as stored[, stats]). (Differential attention attends at
    ``c.attend_config``, a KV pair one head, and combines the pair's two
    outputs under the layer's ``lam0``. ``held``: the buffers hold this
    token's row by the time the layer attends, as a verify step's do
    (a model whose cross layers read the one full layer's buffers
    carries them through its walk): ``"write"``, the full layer's, which
    writes its row first and → (x, ck, cv[, stats]); ``"read"``, a cross
    layer's, which has queries alone and → (x[, stats]).) ``c``: the layer's attention shape
    (its run's, in a model of groups); ``ck`` / ``cv``: the stacked
    buffers its kind of layer keeps, of which this layer is row ``li``;
    ``rope``, ``nope``, ``temp`` as :func:`_dense_in` takes them;
    ``window`` traced where the layers ride one scan, static in a model
    of groups, whose window layers' buffers are rings (``ring`` = the
    positions, and the new row lies at ``positions % rows``); ``stats``
    as :func:`_dense_out` takes them (the live slots are the valid
    tokens). Which form attends: :func:`~dstack_tpu.ops.flash_decode.
    reads_live_keys` (``decode_kernel``: what a caller asked for)."""
    b = x.shape[0]
    ac = c.attend_config  # the shape the attention itself runs at
    q, k, v = _dense_in(x, layer, c, rope, nope, temp, cross=held == "read")
    # the scan only READS the stacked cache: this token's K/V goes
    # beside it into the attention and out as ys; all layers' rows
    # are written after the scan, in place, 2 × B small blocks a
    # step where writing inside the scan costs that a layer
    if held != "read":
        k_new, v_new = _cstored(k, ck), _cstored(v, cv)
    if held == "write":
        ck = _cwrite_rows(ck, li, positions, write_mask, k_new)
        cv = _cwrite_rows(cv, li, positions, write_mask, v_new)
    live_keys = reads_live_keys(
        ac, jax.tree.leaves(ck)[0].shape[3], ring=ring is not None,
        quantized=isinstance(ck, tuple), mesh=mesh, decode_kernel=decode_kernel,
    )
    if not live_keys:
        # the einsum's operands: this token's K/V selected into the
        # layer's slice on its way into the attention (masked rows keep
        # theirs)
        at = positions if ring is None else _ring_row(positions, ck)
        if held:
            ckl, cvl = _clayer(ck, li), _clayer(cv, li)
        else:
            ckl = _cwith_row(_clayer(ck, li), at, write_mask, k_new)
            cvl = _cwith_row(_clayer(cv, li), at, write_mask, v_new)
        ckf = _cfull(ckl, q.dtype)  # int8 caches dequant INSIDE the dot
        cvf = _cfull(cvl, q.dtype)
    # Grouped-query: q regrouped [B, Hkv, G, D] against the
    # [B, Hkv, T, D] cache — decode is HBM-bandwidth-bound on the KV
    # read, so the cache is streamed ONCE at KV width instead of
    # materializing a G×-wider repeat (4× read amplification for
    # 32q/8kv models).
    grp = ac.n_heads // ac.n_kv_heads
    qg = q[:, :, 0, :].reshape(b, ac.n_kv_heads, grp, ac.head_dim)
    if live_keys:
        # ragged pallas read out of the stacked leaf: each slot's key
        # blocks up to its length, none for a slot that may not write
        # (its output is discarded), the token's own key (as stored:
        # what the einsum selects in) folded into the softmax
        with _attn_scope(None, cross=held == "read"):
            o = _flash_attend(
                qg, ck, cv, li, jnp.where(write_mask, positions, 0), window,
                config=ac, scale=ac.attention_scale, grp=grp, rows_per_slot=1,
                sinks_leaf=layer.get("sinks"), mesh=mesh,
                new=None if held else (_cfull(k_new, q.dtype), _cfull(v_new, q.dtype)),
            )
    else:
        # attend over the cache prefix (mask: j <= position, and within
        # the layer's sliding window when set)
        with _attn_scope(window, cross=held == "read"):
            s = jnp.einsum(
                "bhgd,bhkd->bhgk", qg, ckf, preferred_element_type=jnp.float32
            ) * ac.attention_scale
            p = _dense_probs(s, positions, window, nope, layer, ac, ring)
            o = jnp.einsum("bhgk,bhkd->bhgd", p.astype(cvf.dtype), cvf)
    # [B, Hkv, G, D] row-major flatten == query-head order
    o = o.reshape(b, 1, ac.q_dim)
    if c.diff_attn:
        o = llama.diff_combine(o, layer, c, lam0)
    rows = () if held == "read" else (ck, cv) if held else ((k_new, v_new),)
    if stats is None:
        return (_dense_out(x, o, layer, c), *rows)
    x, stats = _dense_out(x, o, layer, c, stats, write_mask[:, None])
    return ((x, stats), *rows)


def _attn_scope(window, cross: bool = False):
    """The named scope a capture finds a layer's attention under, where
    its kind is static (a model of layer groups): ``dtpu.attn_window`` |
    ``dtpu.attn_full``, a cross layer's read of another layer's rows
    ``dtpu.cross_attn``; none where the window rides a scan as data."""
    if cross:
        return jax.named_scope("dtpu.cross_attn")
    if not isinstance(window, int):
        return contextlib.nullcontext()
    return jax.named_scope("dtpu.attn_window" if window else "dtpu.attn_full")


def _ring_row(positions, buf):
    """The row of a window ring ``buf`` [L, B, Hkv, W, D] that holds
    position ``positions``."""
    return jnp.mod(positions, buf.shape[3])


def _decode_step_groups(
    params: dict, cache: dict, tokens, positions, c: LlamaConfig, write_mask,
    decode_kernel: Optional[str] = None, mesh=None,
) -> tuple[jax.Array, dict]:
    """:func:`decode_step` of a grouped-query model of layer GROUPS: the
    same layer (:func:`_decode_layer`) at each run's attention shape
    against its kind's buffers, a window layer's a ring (the einsum over
    its few rows; a full layer's row buffer by the rule, like a model
    of one kind); every scan reads the cache, and each buffer takes its
    runs' new rows in one block write a run after them (PR 25's form).
    A conv layer (``models/shortconv.py``) reads its tail and hands the
    tail after the token out the same way (a dead slot's as it was), a
    mamba layer its state and tail; a gmu layer reads the last mamba
    layer's scan output out of the carry; where cross layers read the
    one full layer's buffers, those ride the carry too and the full
    layer writes its row before it attends (``_decode_layer``'s ``held``)."""
    from dstack_tpu.models.llama import dual_rope_freqs

    x = _embed_lookup(params, tokens, c)[:, None, :]
    ropes = dual_rope_freqs(c, positions)  # ([B, D/2] each) full, window
    live = write_mask[:, None]
    lam = _lambda_tables(c)
    states = [n for n in _STATES if n in cache]

    def state_mix(x, layer, li):
        # a conv or mamba layer reads its rows and hands the rows after
        # the token out (a dead slot's as they were)
        h = model_norm(x, layer["attn_norm"], c)
        y, *rows = llama.mixer_of(_state_kind(c)).mix(
            h, layer, c, *_state_rows(cache, li), live
        )
        return y, tuple(rows)

    def one_layer(carry, layer, li, run):
        x, stats, shared = carry
        if run.kind in _MIXED:
            y, shared, rows = _mixed_layer(
                x, layer, run.kind, c, shared,
                lambda x, layer: state_mix(x, layer, li),
            )
            out = _dense_out(x, y, layer, c, stats, live)
            return (*(out if stats is not None else (out, None)), shared), rows
        cos, sin = llama.layer_rope(ropes, c, run.window)
        nk, nv = _group_kv(run)
        # the buffers the cross layers read ride the carry: the full
        # layer writes its row into them, then it and they attend over
        # what is held (written after the scans, a leaf of ONE layer was
        # copied whole into the write's loop and out of it a token of
        # ``decode_loop``, 4 x 0.67 GB at 32 x 8192, device-free)
        held = (
            None if "k" not in shared or run.window
            else "read" if run.kind == "cross" else "write"
        )
        bufs = (shared[nk], shared[nv]) if held else (cache[nk], cache[nv])
        out, *rows = _decode_layer(
            x, layer, 0 if held == "read" else li, run.config, *bufs,
            positions, write_mask,
            lambda t: _apply_rope_batch(t, cos, sin, interleaved=c.rope_interleaved),
            False, None, run.window, positions if run.window else None, stats,
            decode_kernel, mesh,
            lam0=lam[run.kind][li] if lam else None, held=held,
        )
        if held == "write":
            shared = {**shared, nk: rows[0], nv: rows[1]}
        return (
            (*(out if stats is not None else (out, None)), shared),
            None if held else rows[0],
        )

    (x, stats, shared), written = _walk_layer_groups(
        params, (x, _moe_stats(cache), _shared_zeros(c, x, cache)), one_layer, c,
        # (a run of ONE mamba layer: see ``one``)
        one=jnp.minimum(1, 1 + positions[0]) if "cross" in c.layer_types else None,
    )
    cache = _with_moe_stats(dict(cache), stats)
    cache.update({n: shared[n] for n in ("k", "v") if n in shared})
    for run, first, rows in written:
        if rows is None:  # layers that keep nothing, or wrote their own
            continue
        if run.kind in llama.STATE_KINDS:  # the run's tails (and states), whole
            scope = "dtpu.conv.tail" if run.kind == "conv" else "dtpu.ssm.scan"
            with jax.named_scope(scope):
                for name, new in zip(states, rows):
                    cache[name] = jax.lax.dynamic_update_slice_in_dim(
                        cache[name], new, first, 0
                    )
            continue
        for name, new in zip(_group_kv(run), rows):
            at = _ring_row(positions, cache[name]) if run.window else positions
            cache[name] = _cwrite_rows(
                cache[name], first, at, write_mask, new,
                unroll=_tokens_on_lanes(c.attend_config.head_dim),
            )
    x = model_norm(x, params["final_norm"], c)
    return _head_logits(params, x[:, 0], c), cache


def advance_decode_state(
    tok: jax.Array,  # [B] int32 last token per slot
    pos: jax.Array,  # [B] int32 current lengths
    rem: jax.Array,  # [B] int32 generation budget left
    act: jax.Array,  # [B] bool
    eos_ids: jax.Array,  # [B] int32 (-1 = no EOS)
    sampled: jax.Array,  # [B] int32 freshly sampled tokens
    *,
    max_seq: int,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One decode step's slot-state transition → (tok, pos, rem, act).

    THE single copy of the per-token deactivation rules
    (eos/budget/cache-end), used by :func:`decode_loop`'s device-side
    scan AND the engine's per-step device mirror — the host replay
    (``_advance_slot``) applies the same rules, so the two cannot
    drift without the turbo parity tests failing."""
    new_tok = jnp.where(act, sampled.astype(jnp.int32), tok)
    step = act.astype(jnp.int32)
    pos = pos + step
    rem = rem - step
    act = act & (new_tok != eos_ids) & (rem > 0) & (pos < max_seq - 1)
    return new_tok, pos, rem, act


def decode_loop(
    params: dict,
    cache: dict,
    tokens: jax.Array,  # [B] int32: last sampled token per slot
    positions: jax.Array,  # [B] int32 current lengths
    remaining: jax.Array,  # [B] int32 generation budget left
    active: jax.Array,  # [B] bool
    eos_ids: jax.Array,  # [B] int32 (-1 = no EOS)
    config: LlamaConfig,
    *,
    steps: int,  # static: decode steps per macro-step
    max_seq: int,  # static: cache row length
    decode_kernel: Optional[str] = None,
    mesh=None,
) -> tuple[jax.Array, dict, jax.Array, jax.Array, jax.Array, jax.Array]:
    """``steps`` greedy decode steps entirely on device → (emitted
    [steps, B] int32 with -1 for inactive rows, cache, last token,
    positions, remaining, active).

    The macro-step is the latency-hiding design for serving: one
    dispatch (and ONE host↔device round trip) advances every slot
    ``steps`` tokens, where the step-at-a-time loop pays a blocking
    transfer per token: the scan removes per-step dispatch overhead
    and lets XLA overlap the next step's compute with the emission
    buffer. Whether that still pays on a local chip is unmeasured
    (ROADMAP C4). Greedy-only (argmax rides
    inside the jit); sampled requests use the per-step path where the
    sampler sees live penalty state. Per-slot EOS/budget/cache-end
    deactivation happens on device so a finished slot stops writing
    K/V mid-loop (same write_mask guard as :func:`decode_step`).
    """

    def body(carry, _):
        cache, tok, pos, rem, act = carry
        logits, cache = decode_step(
            params, cache, tok, pos, config, write_mask=act,
            decode_kernel=decode_kernel, mesh=mesh,
        )
        new_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        tok, pos, rem, act2 = advance_decode_state(
            tok, pos, rem, act, eos_ids, new_tok, max_seq=max_seq
        )
        emitted = jnp.where(act, tok, -1)
        return (cache, tok, pos, rem, act2), emitted

    (cache, tok, pos, rem, act), toks = jax.lax.scan(
        body, (cache, tokens, positions, remaining, active), None,
        length=steps,
    )
    return toks, cache, tok, pos, rem, act


def verify_step(
    params: dict,
    cache: dict,
    tokens: jax.Array,  # [B, S] int32: last sampled token + S-1 draft tokens
    positions: jax.Array,  # [B] int32: row's current length (pos of tokens[:,0])
    config: LlamaConfig,
    write_mask: jax.Array,  # [B] bool
    decode_kernel: Optional[str] = None,  # "flash": the kernel, where asked for
    mesh=None,
    draft_len=None,  # [B] int32: the drafts a row holds (layers that hold a state)
) -> tuple[jax.Array, dict]:
    """Multi-token decode for speculative verification → (logits
    [B, S, V], cache).

    Generalizes :func:`decode_step` to S tokens per row at per-row
    offsets: one call verifies S-1 drafted tokens (prompt-lookup
    decoding), costing ~S× one decode step but replacing up to S steps
    when drafts are accepted. K/V for rejected positions is garbage
    until the real tokens decode over it — the same masked-future
    invariant padding relies on.
    """
    from dstack_tpu.models.llama import (
        attn_temp_scales,
        dual_rope_freqs,
        layer_nope,
        layer_windows,
    )

    c = config
    if c.mla:
        return _verify_step_mla(
            params, cache, tokens, positions, c, write_mask, draft_len
        )
    if c.layer_types:
        return _verify_step_groups(
            params, cache, tokens, positions, c, write_mask, draft_len
        )
    b, sdraft = tokens.shape
    x = _embed_lookup(params, tokens, c)  # [B, S, H]
    # per-row positions: row i covers [pos_i, pos_i + S)
    pos_grid = positions[:, None] + jnp.arange(sdraft)[None, :]  # [B, S]
    inv_shape = c.rope_dim // 2  # narrower under GLM partial rotary
    # rope per (row, step): build [B, S, D/2] then apply per-row
    (cos, sin), (cos_l, sin_l) = jax.tree.map(
        lambda a: a.reshape(b, sdraft, inv_shape),
        dual_rope_freqs(c, pos_grid.reshape(-1)),
    )
    windows = jnp.asarray(layer_windows(c), jnp.int32)
    nopes = jnp.asarray(layer_nope(c), bool)
    has_nope = any(layer_nope(c))
    temp = (
        attn_temp_scales(pos_grid.reshape(-1), c).reshape(b, sdraft)
        if c.attn_temp_scale else None
    )  # [B, S]

    stack, rows = _expert_rows(params["layers"], x, c)

    def layer_fn(carry, layer_and_flags):
        x, ck, cv = carry  # ck/cv: the stacked buffers, as in decode_step
        layer, window, nope, li = layer_and_flags
        layer = rows(layer, li)
        cs, sn = (
            (jnp.where(window > 0, cos_l, cos), jnp.where(window > 0, sin_l, sin))
            if c.rope_local_theta else (cos, sin)
        )
        return _verify_layer(
            x, layer, li, c, ck, cv, positions, pos_grid, write_mask,
            lambda t: _rope_rows(t, cs, sn, interleaved=c.rope_interleaved),
            nope if has_nope else False,
            lambda q: q * temp[:, None, :, None].astype(q.dtype),
            window, decode_kernel=decode_kernel, mesh=mesh,
        ), None

    (x, ks, vs), _ = jax.lax.scan(
        layer_fn, (x, *_cache_pack(cache)),
        (stack, windows, nopes, jnp.arange(c.n_layers)),
    )
    cache = _cache_unpack(ks, vs)
    x = model_norm(x, params["final_norm"], c)
    return _head_logits(params, x, c, eq="bse,ev->bsv"), cache


def _verify_layer(
    x, layer: dict, li, c: LlamaConfig, ck, cv, positions, pos_grid, write_mask,
    rope, nope, temp, window, ring=None, stats=None,
    decode_kernel: Optional[str] = None, mesh=None, unroll: bool = False,
    lam0=None, cross: bool = False,
):
    """One dense layer of a verify step, the one copy → (x, ck, cv[,
    stats]) (``lam0``, ``cross``: as :func:`_decode_layer`'s; a cross
    layer writes nothing: the full layer's rows are written by now): the S tokens' K/V written at their per-row positions into
    the stacked buffers ``ck`` / ``cv`` in place (a ring's one token at
    a time, :func:`_cwrite_ring`), then attended over. Arguments as
    :func:`_decode_layer` takes them; the einsum unless the caller asks
    for the kernel (no cell drafts: the kernel's verify form has not
    run on the chip)."""
    b, sdraft = pos_grid.shape
    ac = c.attend_config
    q, k, v = _dense_in(x, layer, c, rope, nope, temp, cross=cross)
    if cross:
        pass
    elif ring is None:
        ck = _cwrite_rows(ck, li, positions, write_mask, _cstored(k, ck), unroll=unroll)
        cv = _cwrite_rows(cv, li, positions, write_mask, _cstored(v, cv), unroll=unroll)
    else:
        ck = _cwrite_ring(ck, li, positions, write_mask, k, axis=1, unroll=False)
        cv = _cwrite_ring(cv, li, positions, write_mask, v, axis=1, unroll=False)
    ckl, cvl = _clayer(ck, li), _clayer(cv, li)
    ckf = _cfull(ckl, q.dtype)  # int8 caches dequant INSIDE the dot
    cvf = _cfull(cvl, q.dtype)
    # grouped-query attention against the KV-width cache (see
    # decode_step): q [B, Hkv, G, S, D] · cache [B, Hkv, T, D]
    grp = ac.n_heads // ac.n_kv_heads
    qg = q.reshape(b, ac.n_kv_heads, grp, sdraft, ac.head_dim)
    if decode_kernel == "flash":
        # ragged verify: rows flatten [G, S] row-major; row g*S+s
        # attends keys <= pos+s inside the kernel (verify rides the
        # SAME dispatch — sink column included — as decode)
        qr = qg.reshape(b, ac.n_kv_heads, grp * sdraft, ac.head_dim)
        o = _flash_attend(
            qr, ck, cv, li, positions, window,
            config=ac, scale=ac.attention_scale, grp=grp, rows_per_slot=sdraft,
            sinks_leaf=layer.get("sinks"), mesh=mesh,
        ).reshape(b, ac.n_kv_heads, grp, sdraft, ac.head_dim)
    else:
        with _attn_scope(window, cross=cross):
            s = jnp.einsum(
                "bhgsd,bhkd->bhgsk", qg, ckf, preferred_element_type=jnp.float32
            ) * ac.attention_scale
            p = _dense_probs(s, pos_grid, window, nope, layer, ac, ring)
            o = jnp.einsum("bhgsk,bhkd->bhgsd", p.astype(cvf.dtype), cvf)
    o = o.transpose(0, 3, 1, 2, 4).reshape(b, sdraft, ac.q_dim)
    if c.diff_attn:
        o = llama.diff_combine(o, layer, c, lam0)
    if stats is None:
        return _dense_out(x, o, layer, c), ck, cv
    valid = jnp.broadcast_to(write_mask[:, None], (b, sdraft))
    return _dense_out(x, o, layer, c, stats, valid) + (ck, cv)


def _verify_step_groups(
    params: dict, cache: dict, tokens, positions, c: LlamaConfig, write_mask,
    draft_len=None,  # [B]: drafts a row holds (a model with conv layers)
) -> tuple[jax.Array, dict]:
    """:func:`verify_step` of a grouped-query model of layer GROUPS:
    :func:`_verify_layer` at each run's attention shape, the buffers the
    carry of every scan. A conv layer's tail, unlike a cache row, cannot
    be masked later: the layers read theirs and keep every position's
    new rows, and the tails are advanced once the logits say how many
    drafts stand (as the latent family's states, :func:`_state_commit`)."""
    from dstack_tpu.models.llama import dual_rope_freqs

    b, sdraft = tokens.shape
    x = _embed_lookup(params, tokens, c)
    pos_grid = positions[:, None] + jnp.arange(sdraft)[None, :]  # [B, S]
    ropes = jax.tree.map(
        lambda a: a.reshape(b, sdraft, a.shape[-1]),
        dual_rope_freqs(c, pos_grid.reshape(-1)),
    )
    mix = None
    if "conv" in cache:
        mix = _state_mixer(c, write_mask, commit=False)
        cache = _state_pending(cache, c, sdraft)
    lam = _lambda_tables(c)

    def one_layer(carry, layer, li, run):
        x, cache, shared = carry
        if run.kind in _MIXED:
            y, shared, new_cache = _mixed_layer(
                x, layer, run.kind, c, shared,
                lambda x, layer: mix(x, layer, cache, li),
            )
            cache = cache if new_cache is None else new_cache
            valid = jnp.broadcast_to(write_mask[:, None], (b, sdraft))
            return (*_group_out(x, cache, y, layer, c, valid), shared), None
        cos, sin = llama.layer_rope(ropes, c, run.window)
        nk, nv = _group_kv(run)
        stats = _moe_stats(cache)
        cross = run.kind == "cross"
        x, *rest = _verify_layer(
            x, layer, 0 if cross else li, run.config, cache[nk], cache[nv],
            positions, pos_grid, write_mask,
            lambda t: _rope_rows(t, cos, sin, interleaved=c.rope_interleaved),
            False, None, run.window,
            positions + (sdraft - 1) if run.window else None, stats,
            unroll=_tokens_on_lanes(c.attend_config.head_dim),
            lam0=lam[run.kind][li] if lam else None, cross=cross,
        )
        if stats is not None:
            cache = _with_moe_stats(cache, rest.pop(0))
        return (x, {**cache, nk: rest[0], nv: rest[1]}, shared), None

    (x, cache, _), _ = _walk_layer_groups(
        params, (x, cache, _shared_zeros(c, x)), one_layer, c
    )
    x = model_norm(x, params["final_norm"], c)
    logits = _head_logits(params, x, c, eq="bse,ev->bsv")
    if mix is not None:
        cache = _state_commit(
            cache, _tokens_standing(logits, tokens, draft_len), write_mask, c,
            params,
        )
    return logits, cache


def sample(
    logits: jax.Array,  # [B, V] f32
    key_data: jax.Array,  # [B, 2] uint32 per-slot PRNG key data
    temperature: jax.Array,  # [B]
    top_p: jax.Array,  # [B]
    top_k: jax.Array,  # [B] int32, 0 = off
    rep_pen: jax.Array,  # [B] f32, 1.0 = off
    counts: jax.Array,  # [B, V] int32: occurrences in prompt + generated
    pres_pen: jax.Array,  # [B] f32 additive presence penalty
    freq_pen: jax.Array,  # [B] f32 additive frequency penalty
    gen_counts: jax.Array,  # [B, V] int32: occurrences in GENERATED text
    logit_bias=None,  # [B, V] f32 additive bias (None = off)
    min_p=None,  # [B] f32: drop tokens with p < min_p·p_max (None = off)
    live=None,  # [B] bool: rows whose token is used (None = all)
) -> tuple[jax.Array, jax.Array]:
    """→ (tokens [B], advanced key_data). Greedy when temperature == 0,
    else penalized temperature/top-k/top-p/min-p sampling, selected per
    slot (static shapes). The filters (:func:`_filter_logits`: min-p's
    softmax, the [B, V] sort, the sorted softmax and cumsum) run only
    on a call where some ``live`` row set top-k, top-p or min-p —
    one ``lax.cond`` on what the call's own inputs say; otherwise the
    draw is from the temperature-scaled logits as they stand, which is
    what the filters leave of them when every row has them off. A dead
    row keeps its last request's values, so it is masked out of the
    decision (its token is never read). Per-slot keys make a request's
    stream deterministic under its ``seed`` regardless of which other
    slots are active, and the key evolution is the same on both
    branches.

    Penalty scopes follow their ecosystems: the HF-style multiplicative
    repetition penalty sees prompt + generated tokens, while OpenAI's
    additive presence/frequency penalties count only SAMPLED tokens
    (a long prompt must not pre-ban its own vocabulary)."""
    if logit_bias is not None:
        logits = logits + logit_bias  # OpenAI bias: pre-everything
    seen = counts > 0
    # HF repetition penalty: previously-seen tokens get logit/p when
    # positive, logit*p when negative (p > 1 discourages repeats)
    pen = rep_pen[:, None]
    penalized = jnp.where(logits > 0, logits / pen, logits * pen)
    logits = jnp.where(seen & (pen != 1.0), penalized, logits)
    # OpenAI additive penalties over generated-only counts
    logits = logits - pres_pen[:, None] * (gen_counts > 0).astype(jnp.float32)
    logits = logits - freq_pen[:, None] * gen_counts.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1)
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    asked = (top_k > 0) | (top_p < 1.0)
    if min_p is not None:
        asked |= min_p > 0.0
    if live is not None:
        asked &= live
    masked = jax.lax.cond(
        jnp.any(asked),
        lambda s: _filter_logits(s, top_p, top_k, min_p),
        lambda s: s,
        scaled,
    )
    keys = jax.vmap(jax.random.wrap_key_data)(key_data)
    splits = jax.vmap(lambda k: jax.random.split(k, 2))(keys)  # [B, 2]
    sampled = jax.vmap(jax.random.categorical)(splits[:, 1], masked)
    tokens = jnp.where(temperature <= 0.0, greedy, sampled)
    return tokens, jax.vmap(jax.random.key_data)(splits[:, 0])


def _filter_logits(scaled, top_p, top_k, min_p):
    """Temperature-scaled logits [B, V] → the same with every token a
    row's min-p / top-k / top-p excludes at NEG_INF; a row with all
    three off comes back as it went in. All rows pay the sort."""
    v = scaled.shape[-1]
    if min_p is not None:
        # min-p (applied before top-k/top-p): relative-probability floor
        probs_mp = jax.nn.softmax(scaled, axis=-1)
        floor = min_p[:, None] * jnp.max(probs_mp, axis=-1, keepdims=True)
        scaled = jnp.where(
            (min_p[:, None] <= 0.0) | (probs_mp >= floor), scaled, NEG_INF
        )
    # ONE [B, V] descending sort serves top-k and top-p — at a 128k
    # vocab it dominates per-token sampling cost, which is why
    # :func:`sample` calls this only when a live row asked for a filter
    sorted_full = jnp.sort(scaled, axis=-1)[:, ::-1]
    # top-k: drop everything below the k-th largest logit (ties at the
    # k-th value survive, HF TopKLogitsWarper semantics)
    kth_ix = jnp.clip(top_k - 1, 0, v - 1)
    kth = jnp.take_along_axis(sorted_full, kth_ix[:, None], axis=-1)
    scaled = jnp.where(
        (top_k[:, None] > 0) & (scaled < kth), NEG_INF, scaled
    )
    # the sorted view of the top-k-filtered logits is the full sort with
    # positions >= k masked (entries past the nucleus get ~0 prob)
    sorted_logits = jnp.where(
        (top_k[:, None] > 0)
        & (jnp.arange(v)[None, :] >= jnp.maximum(top_k, 1)[:, None]),
        NEG_INF,
        sorted_full,
    )
    # top-p: mask tokens beyond the nucleus. top_p >= 1 bypasses the
    # mask entirely — f32 cumsum over a big vocab may never reach 1.0,
    # which would silently collapse "full distribution" to greedy.
    sorted_probs = jax.nn.softmax(sorted_logits, axis=-1)
    cumulative = jnp.cumsum(sorted_probs, axis=-1)
    # smallest k with cumsum >= top_p; keep everything before it
    cutoff_ix = jnp.argmax(cumulative >= top_p[:, None], axis=-1)
    cutoff = jnp.take_along_axis(sorted_logits, cutoff_ix[:, None], axis=-1)
    masked = jnp.where(scaled >= cutoff, scaled, NEG_INF)
    return jnp.where(top_p[:, None] >= 1.0, scaled, masked)


def skip_key_data(kd: jax.Array, n) -> jax.Array:
    """Advance per-slot PRNG key data ``kd`` ([2] uint32) by ``n``
    draws, replaying exactly :func:`sample`'s per-token key evolution
    (``key' = split(key, 2)[0]``). Mid-stream resume uses this so a
    seeded-sampled request re-prefilled with n already-delivered tokens
    continues the ORIGINAL stream's randomness instead of restarting
    it. ``n`` is traced (one compile serves every resume length)."""

    def body(_, k):
        key = jax.random.wrap_key_data(k)
        return jax.random.key_data(jax.random.split(key, 2)[0])

    return jax.lax.fori_loop(0, n, body, kd)


TOP_LOGPROBS = 5  # static alternatives-per-token count (OpenAI max is 5)


def token_logprobs(
    logits: jax.Array,  # [B, V] f32 — raw model logits
    tokens: jax.Array,  # [B] the sampled tokens
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """→ (chosen logprob [B], top ids [B, K], top logprobs [B, K]).

    Computed from the RAW model distribution (pre-temperature/penalty),
    the convention OpenAI's API documents for ``logprobs``.
    """
    logp = jax.nn.log_softmax(logits, axis=-1)
    chosen = jnp.take_along_axis(logp, tokens[:, None], axis=-1)[:, 0]
    top_lp, top_ids = jax.lax.top_k(logp, TOP_LOGPROBS)
    return chosen, top_ids, top_lp


def _mark_seen(
    counts: jax.Array, gen_counts: jax.Array, rows: jax.Array, tokens: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Count sampled tokens in both maps (donated in-place updates)."""
    return counts.at[rows, tokens].add(1), gen_counts.at[rows, tokens].add(1)


def _mark_prompt(
    counts: jax.Array,
    gen_counts: jax.Array,
    slot: jax.Array,
    padded: jax.Array,
    tp: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Reset the slot's rows; count the prompt's first ``tp`` tokens in
    the all-tokens map only (padding indices are dropped out of range).
    Generated-only counts start at zero."""
    v = counts.shape[-1]
    row = jnp.zeros((v,), counts.dtype)
    idx = jnp.where(jnp.arange(padded.shape[0]) < tp, padded, v)
    row = row.at[idx].add(1, mode="drop")
    return (
        counts.at[slot].set(row),
        gen_counts.at[slot].set(jnp.zeros((v,), gen_counts.dtype)),
    )


# ---------------------------------------------------------------------------
# the engine: slots + continuous batching
# ---------------------------------------------------------------------------


def copy_cache_prefix(cache: dict, src, dst, *, p: int) -> dict:
    """Copy the first ``p`` cached positions of slot ``src`` into slot
    ``dst`` on device (prefix caching: a new request whose prompt shares
    a prefix with an already-cached sequence skips prefilling it).
    ``p`` is static (jitted per chunk-aligned length); src/dst are
    traced scalars so one compile serves every slot pair."""
    out = {}
    for name, a in cache.items():
        if name in _COUNTS:  # counts, not a slot's state
            out[name] = a
            continue
        if name not in _T_AXIS and not _is_ring(name):
            raise ValueError(
                f"cache leaf {name!r} holds no rows by position: no prefix "
                f"of it can be copied (known: {sorted(_T_AXIS)}, rings)"
            )
        rows = jax.lax.dynamic_index_in_dim(a, src, axis=1, keepdims=True)
        # a window ring is copied whole: its rows are not in position
        # order (the engine only offers a source whose ring still holds
        # the window before ``p``, InferenceEngine._find_prefix_source)
        if not _is_ring(name):
            rows = jax.lax.slice_in_dim(rows, 0, p, axis=_T_AXIS[name])
        idx = [jnp.asarray(0, jnp.int32)] * a.ndim
        idx[1] = dst
        out[name] = jax.lax.dynamic_update_slice(a, rows, tuple(idx))
    return out


def _common_prefix_len(a: list, b: list) -> int:
    n = min(len(a), len(b))
    i = 0
    while i < n and a[i] == b[i]:
        i += 1
    return i


def sharded_params(config: LlamaConfig, mesh, seed: int = 0) -> dict:
    """Initialize params directly under the mesh's shardings — the full
    tree never materializes on one chip (required for models bigger
    than a single device's HBM)."""
    from dstack_tpu.parallel.sharding import default_rules, tree_shardings

    shardings = tree_shardings(llama.param_specs(config), mesh, default_rules())
    init = jax.jit(
        lambda key: llama.init_params(config, key), out_shardings=shardings
    )
    return init(jax.random.key(seed))


class InferenceEngine:
    """Slot-based continuous batching over one compiled decode step.

    Synchronous core; the OpenAI server drives it from an asyncio loop
    (``add_request`` into a free slot, ``step`` advances all active
    slots and reports freshly sampled tokens per slot).
    """

    def __init__(
        self,
        config: LlamaConfig,
        params: dict,
        max_batch: int = 8,
        max_seq: int = 2048,
        seed: int = 0,
        mesh=None,
        prefill_chunk: int = 256,
        prefill_pack: int = 4,
        spec_draft: int = 4,
        turbo_steps: int = 8,
        prefix_cache: bool = True,
        kv_quant=None,  # None | "int8": quantized KV cache
        turbo_quiet_s: float = 0.5,
        turbo_depth: int = 1,
        decode_kernel: Optional[str] = None,  # None/"einsum" | "flash"
        registry=None,  # obs.Registry (default: a fresh serve registry)
    ):
        """``mesh``: serve tensor-parallel over the mesh's ``tp`` axis —
        params shard per the model's logical rules (heads/mlp/vocab over
        tp), the KV cache shards over KV heads, and GSPMD inserts the
        per-layer psums (how a 70B fits a v5e-16: BASELINE.md serving
        sizing). Requires n_kv_heads % tp == 0. For models bigger than
        one chip, pass params ALREADY sharded over this mesh
        (:func:`sharded_params`) — device_put here is a convenience for
        single-chip-sized trees."""
        self.config = config
        if mesh is not None:
            from dstack_tpu.models.quant import is_quantized, quant_param_specs
            from dstack_tpu.parallel.sharding import default_rules, tree_shardings

            tp = mesh.shape.get("tp", 1)
            if config.mla:
                # MLA: the latent cache has no head dim (replicated);
                # the q/out heads shard over tp instead
                if tp > 1 and config.n_heads % tp != 0:
                    raise ValueError(
                        f"n_heads {config.n_heads} not divisible by tp={tp}"
                    )
            elif tp > 1 and config.n_kv_heads % tp != 0:
                raise ValueError(
                    f"n_kv_heads {config.n_kv_heads} not divisible by tp={tp}"
                )
            specs = llama.param_specs(config)
            if is_quantized(params):
                specs = quant_param_specs(specs, config)
            shardings = tree_shardings(specs, mesh, default_rules())
            params = jax.device_put(params, shardings)
        self.params = params
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.kv_quant = kv_quant
        # telemetry at the source: the engine records TTFT/step-latency/
        # throughput itself, so the HTTP server's /metrics is the one
        # source every reader scrapes (no stopwatches beside it)
        from dstack_tpu.serve.metrics import new_serve_registry

        self.metrics = registry or new_serve_registry()
        self.metrics.family("dtpu_serve_max_slots").set(max_batch)
        self._admit_t0: dict[int, float] = {}  # slot → admission time
        self._trace_ids: dict[int, str] = {}  # slot → exemplar trace id
        self.cache = init_cache(
            config, max_batch, max_seq, mesh=mesh, kv_quant=kv_quant,
            chunk=max(16, min(prefill_chunk, max_seq)),
        )
        # a model whose layers attend under explicit masks has no
        # static-offset kernel to keep eligible: every prompt chunk, a
        # lone row's too, goes through the packed program at the full
        # chunk width, so its prefill grid is one program a G bucket
        # (a start a program and a bucket a tail would be ~35 programs
        # of ~10 s each at every cold boot). So has a grouped-query model
        # of layer groups without a window: its serial chunk IS a packed
        # wave of one row (:func:`_prefill_packed_groups`), and a program
        # a start would hold the same text sixteen times over
        self._packed_only = bool(
            _masked(config, max_seq) or config.layer_types and not config.mla
        )
        # a chip's share of the experts counts its routing on the device
        # (cache["moe_stats"], ["moe_reads"]); the last reading, to
        # publish differences
        self._moe_stats_seen = np.zeros((_moe_counts(config),), np.int64)
        # full-attention layers with an indexer that can bite (host-side
        # counters of what it selects, from positions alone)
        self._indexer_layers = (
            config.n_kind("full")
            if config.mla and _indexed(config, max_seq) else 0
        )
        # ragged pallas decode attention (ops/flash_decode): the program
        # takes it by itself where ``reads_live_keys`` holds (None);
        # "einsum" / "flash" are a caller's way to ask for one form, the
        # kernel only of a supported model/cache shape. Works under a
        # tp mesh too — decode_step shard_maps the kernel per KV-head
        # shard (GSPMD can't partition a pallas call on its own)
        if decode_kernel not in (None, "einsum", "flash"):
            raise ValueError(
                f"decode_kernel={decode_kernel!r}: expected 'einsum' or "
                "'flash' (a typo here would silently measure the wrong "
                "path)"
            )
        if decode_kernel == "flash" and not flash_decode_supported(
            config.attend_config, max_seq
        ):
            raise ValueError(
                "decode_kernel='flash' unsupported for this model/"
                "max_seq (MLA, chunked attention, head_dim % 64, "
                "or max_seq % 128)"
            )
        self.decode_kernel = decode_kernel
        # full-attention layers, which reserve max_seq rows a slot, and
        # the keys a block of their decode attention where it reads only
        # the blocks the live contexts hold (0: whole rows): the latent
        # family under the causal mask alone up to the LONGEST live
        # context (:func:`_attend_live`), the grouped-query family each
        # live slot's own (``_slot_keys``: the kernel, by the rule the
        # program itself takes at trace time)
        # (a latent layer of several attention sublayers: a row each; a
        # cross layer reads the one full layer's rows over again: the
        # count is of the layers that READ, what the key counters weigh)
        self._full_layers = (
            config.sublayers * config.n_kind("full") + config.n_kind("cross")
        )
        ac = config.attend_config
        self._slot_keys = bool(self._full_layers) and reads_live_keys(
            ac, max_seq, quantized=bool(kv_quant), mesh=mesh,
            decode_kernel=decode_kernel,
        )
        if self._slot_keys:  # the kernel's own block: a shard's heads, the cache's bytes
            self._key_block = block_keys(
                ac.n_kv_heads // (mesh.shape.get("tp", 1) if mesh else 1),
                ac.head_dim, max_seq,
                1 if kv_quant else jnp.dtype(config.dtype).itemsize,
            )
        elif config.mla and not _indexed(config, max_seq):
            self._key_block = math.gcd(max_seq, _KEY_BLOCK)
        else:
            self._key_block = 0
        # window layers, of either family: the rows of their ring (0:
        # none), the share of the cache's bytes they take, and host-side
        # counters of the keys their mask lets a decoded token see
        rings = [a for n, a in self.cache.items() if _is_ring(n)]
        self._ring_rows = rings[0].shape[-2] if rings else 0
        self._window_layers = config.layer_types.count("window")
        size = {
            n: a.size * a.dtype.itemsize
            for n, a in self.cache.items() if n not in _COUNTS
        }
        total = sum(size.values())
        self.metrics.family("dtpu_serve_kv_cache_bytes").set(total)
        self.metrics.family("dtpu_serve_kv_window_pool_percent").set(
            100.0 * sum(b for n, b in size.items() if _is_ring(n)) / total
        )
        # linear, conv and mamba layers: a state and / or a convolution
        # tail a slot, sized by the widths and not by max_seq; nothing of
        # theirs is addressed by position, so no prefix of a slot can
        # serve another request
        self._state_layers = sum(
            config.layer_types.count(k) for k in llama.STATE_KINDS
        )
        # layers that keep nothing (gmu, cross): the upper half of a
        # decoder-hybrid-decoder, whose prefill over a prompt's every
        # position only its last needs; counted beside the lower half's
        self._upper_layers = sum(
            config.layer_types.count(k) for k in ("gmu", "cross")
        )
        self.metrics.family("dtpu_serve_state_cache_percent").set(
            100.0 * sum(size.get(n, 0) for n in _STATES) / total
        )
        self._auto_seed = seed
        # per-slot host state
        self.lengths = [0] * max_batch  # tokens currently in cache
        self.active = [False] * max_batch
        self.remaining = [0] * max_batch
        self.eos = [None] * max_batch
        self.last_token = [0] * max_batch
        self.temps = [0.0] * max_batch
        self.top_ps = [1.0] * max_batch
        self.top_ks = [0] * max_batch
        self.rep_pens = [1.0] * max_batch
        self.pres_pens = [0.0] * max_batch
        self.freq_pens = [0.0] * max_batch
        self.min_ps = [0.0] * max_batch
        self.has_bias = [False] * max_batch
        self.finish_reason = [None] * max_batch  # "stop" | "length" once done
        self.want_logprobs = [False] * max_batch
        # most recent token's (logprob, [(alt_id, alt_lp), ...]) per slot
        self._last_logprobs: dict = {}
        # per-slot device state: PRNG keys + seen-token counts for the
        # repetition/presence/frequency penalties ([B, V] int32 —
        # ~4MB at a 128k vocab)
        self._key_data = jnp.zeros((max_batch, 2), jnp.uint32)
        self._seen = jnp.zeros((max_batch, config.vocab_size), jnp.int32)
        self._gen_counts = jnp.zeros((max_batch, config.vocab_size), jnp.int32)
        self._logit_bias = jnp.zeros((max_batch, config.vocab_size), jnp.float32)
        # [0..B) row index, built once: _plain_step's _mark_seen call
        # was allocating+uploading a fresh jnp.arange per sampled token
        # dtpu: noqa[DTPU002] one-time construction at engine init, not a hot path
        self._slot_iota = jnp.arange(max_batch)
        # device mirror of the 7 per-slot sampling-parameter lists
        # (temps/top_ps/top_ks/rep_pens/pres_pens/freq_pens/min_ps).
        # They only change on admission/release — exactly the
        # _invalidate_decode_cache events — yet the sampled decode path
        # re-uploaded all 7 host lists on EVERY generated token
        # (DTPU002). None = rebuild on next use.
        self._sampling_state = None

        # pending chunked prefills: slot → {tokens, tp, next (chunk
        # cursor), gen}
        self._prefilling: dict[int, dict] = {}
        # prompt-lookup speculative decoding (greedy slots): draft
        # spec_draft tokens from the last n-gram match in the slot's
        # history, verify them in ONE multi-token decode. 0 disables.
        self.spec_draft = max(0, spec_draft)
        self.spec_ngram = 2
        self.history: list = [[] for _ in range(max_batch)]
        # incremental {n-gram tuple: last index} per slot → O(1) draft
        # lookup instead of rescanning the history every step
        self._ngram_ix: list = [dict() for _ in range(max_batch)]
        # per-request acceptance tracking: slots whose drafts keep
        # getting rejected stop drafting (they'd only tax the batch)
        self._spec_tries = [0] * max_batch
        self._spec_accepted = [0] * max_batch
        self._spec_off = [False] * max_batch
        # chunk size: one compiled kernel per (C, start) pair instead of
        # one per prompt-length bucket; between chunks the scheduler can
        # run decode steps for other slots
        self.prefill_chunk = max(16, min(prefill_chunk, max_seq))
        # packed multi-slot prefill: prefill_wave() sweeps the pending
        # prompts each tick and packs up to this many chunk rows —
        # bucketed to powers of two — into ONE prefill_packed_step
        # dispatch with traced per-row starts. A burst of N arrivals
        # costs ceil(N/G) dispatches per chunk wave instead of N
        # underfilled batch-1 passes. 0/1 = serial per-slot prefill.
        # Floored to a power of two: G buckets must stay the log2 grid
        # the server warmup precompiles and the compile-cache
        # accounting bound documents.
        pack = max(0, min(prefill_pack, max_batch))
        while pack & (pack - 1):
            pack &= pack - 1
        self.prefill_pack = pack
        # automatic prefix caching: slots whose cache rows still hold a
        # fully-prefilled prompt (they stay valid after release, until
        # the slot is reused) → a new request sharing a chunk-aligned
        # prefix device-copies those rows and skips their prefill
        # chunks. Chunk alignment keeps the (C, start) compile grid
        # unchanged — a reused prefix resumes mid-grid, no new kernels.
        # a state at a shared prefix's end exists only if it was kept
        # there, and none is (PERF.md §7): a model with linear or conv
        # layers prefills every prompt whole, and its prefix counters stay 0
        self.prefix_cache = prefix_cache and not self._state_layers
        self._prefix_registry: dict[int, list] = {}  # slot → prompt ids
        self._copy_fns: dict = {}  # p → jitted copy_cache_prefix
        self.prefix_hits = 0
        self.prefix_tokens_reused = 0
        # device-side macro-steps for all-greedy batches (see
        # decode_loop): K tokens per dispatch/transfer. 0/1 = per-step.
        self.turbo_steps = max(0, turbo_steps)
        # ADAPTIVE K: a full-K loop makes a newly-arrived request wait
        # up to K device steps before its prefill (or a freed slot) —
        # a TTFT tax under load. K starts small, doubles per macro-step
        # once the engine has been arrival-quiet for turbo_quiet_s, and
        # snaps back to the floor whenever requests arrive or wait.
        self.turbo_quiet_s = turbo_quiet_s
        self.waiting_requests = 0  # hint set by the serving scheduler
        self._turbo_k = min(8, self.turbo_steps) or self.turbo_steps
        self._last_admit = 0.0
        # PIPELINED turbo: once the adaptive cap is fully open, chain
        # up to turbo_depth macro-steps device-side per step() call and
        # fetch their token buffers with ONE blocking transfer — each
        # un-chained macro-step pays a full host↔device round trip
        # (whether that matters on a local chip is ROADMAP C4's
        # question). decode_loop's returned device-side
        # (token, position, budget, active) state feeds the next
        # segment directly, so chaining never syncs mid-flight.
        self.turbo_depth = max(1, turbo_depth)
        # decode-state device residency: decode_loop returns the
        # post-chain (token, position, budget, active) arrays, and the
        # host replay applies the SAME transition rules — so the
        # returned arrays stay valid as next macro-step inputs until a
        # host-side mutation (admission, release, sampled/speculative
        # step) touches slot state. Caching them drops the five small
        # host→device uploads every macro-step otherwise pays.
        self._turbo_state = None  # (tok, pos, rem, act, eos) on device

        self._mesh = mesh  # shard_map target for the pallas kernels

        # donate caches: decode must update the KV buffers in place, not
        # copy ~GBs per token
        self._chunk_fns: dict = {}  # (C, start) → jitted prefill_chunk_step
        # (G, C) → jitted prefill_packed_step: starts are TRACED, so the
        # packed grid is (log2 G buckets) × (log2 C buckets) — it cannot
        # grow with start combinations (tests/serve/test_engine.py's
        # compile-cache accounting test pins the bound)
        self._packed_fns: dict = {}
        # slots the most recent prefill_wave dispatched — the failure
        # domain a caller should release when that dispatch raises
        self.last_wave_slots: list = []
        # flight recorder (obs/flight.py): every jit site below is
        # wrapped for compile accounting — first-trace events counted
        # and timed per fn with the causing bucket key — and a compile
        # observed after mark_flight_warm() is flagged as a
        # steady-state recompile (the runtime DTPU003). watch_jit is
        # the IDENTITY when DTPU_FLIGHT=0, so disabled engines carry
        # no wrapper at all. `_last_step_phase` names the dispatch
        # path the current step() took for its flight record.
        self._flight_warm = False
        self._last_step_phase = "decode"
        # host-phase accounting of the engine call now running on the
        # worker thread (step / prefill_wave reset them at entry, _fetch
        # adds to them): seconds inside the blocking fetches so far, and
        # when the last of them returned
        self._wait_s = 0.0
        self._fetched_at = 0.0
        # boot-compile manifest (obs/boot.py helpers): every compile
        # BEFORE mark_flight_warm() records its per-fn key here; a
        # compile AFTER of a key absent from the manifest is a
        # warmup-coverage gap — warmup never visited that bucket, so a
        # live request paid the trace. Host-side set bookkeeping only
        # (DTPU002: no device sync on the compile path).
        self._compile_manifest: set = set()
        _watch = self._watch_jit
        self._decode = _watch(
            partial(
                decode_step, config=config,
                decode_kernel=self.decode_kernel, mesh=mesh,
            ),
            "decode", donate_argnums=(1,),
        )
        self._verify = _watch(
            partial(
                verify_step, config=config,
                decode_kernel=self.decode_kernel, mesh=mesh,
            ),
            "verify", donate_argnums=(1,),
        )
        self._sample = _watch(sample, "sample")
        self._turbo_fns: dict = {}  # steps → jitted decode_loop
        self._argmax = _watch(partial(jnp.argmax, axis=-1), "argmax")
        # per-step device mirror of the slot-state transition (shared
        # with decode_loop's scan body): _plain_step advances the cached
        # decode state on device instead of re-uploading five host
        # lists per sampled token
        self._advance_state = _watch(
            partial(advance_decode_state, max_seq=max_seq), "advance_state"
        )
        self._logprobs = _watch(token_logprobs, "logprobs")
        self._mark_seen = _watch(
            _mark_seen, "mark_seen", donate_argnums=(0, 1)
        )
        self._mark_prompt = _watch(
            _mark_prompt, "mark_prompt", donate_argnums=(0, 1)
        )
        self._skip_key = _watch(skip_key_data, "skip_key")
        # watchdog plumbing: the serve scheduler runs step() on a worker
        # thread and may give up on a wedged dispatch (abandon_step).
        # The abandoned thread checks the epoch after every pre-dispatch
        # suspension point and before publishing, so its eventual return
        # can never corrupt slot state the scheduler has since reused.
        self._step_epoch = 0
        self._step_wedge: Optional[tuple] = None  # ("slot", i) | ("dispatch",)
        # extra context merged into every serve.engine.step fire —
        # multi-replica-in-one-process harnesses set e.g.
        # {"replica": "r1"} so a chaos rule can target ONE engine
        # (production runs one engine per process and leaves it empty)
        self.fault_ctx: dict = {}

    @property
    def devices(self) -> set:
        """The devices this engine's KV cache lives on: one chip, or
        the tp mesh — where the replica runs."""
        return jax.tree_util.tree_leaves(self.cache)[0].devices()

    def free_slots(self) -> list[int]:
        return [
            i for i in range(self.max_batch)
            if not self.active[i] and i not in self._prefilling
        ]

    def _watch_jit(self, fn, label: str, key=None, **jit_kw):
        """THE engine jit site. Compiles ``fn`` under the name of the
        function it wraps — a ``functools.partial`` has no name of its
        own and XLA would call the program ``jit__unknown`` — so a
        profiler capture names every program on the device's line
        (``jit_decode_step``, ``jit_prefill_chunk_step``, ...), then
        hands it to ``flight.watch_jit`` under ``label``, the ``fn``
        label of ``dtpu_serve_compiles_total`` (PERF.md §3 has the
        label ↔ program table)."""
        if isinstance(fn, partial):
            fn.__name__ = fn.func.__name__
        return flight.watch_jit(
            jax.jit(fn, **jit_kw), label, registry=self.metrics, key=key,
            warm=lambda: self._flight_warm,
            on_compile=self._note_boot_compile,
        )

    def _chunk_fn(self, cl: int, start: int):
        key = (cl, start)
        if key not in self._chunk_fns:
            # dtpu: noqa[DTPU003] cl is power-of-2-bucketed and start chunk-aligned by prefill_step; grid ≤ log2(C) × (T/C)
            self._chunk_fns[key] = self._watch_jit(
                partial(
                    prefill_chunk_step, config=self.config, start=start,
                    mesh=self._mesh,
                ),
                "chunk", key=key, donate_argnames=("cache",),
            )
        return self._chunk_fns[key]

    def _packed_fn(self, g: int, cl: int):
        key = (g, cl)
        if key not in self._packed_fns:
            # dtpu: noqa[DTPU003] prefill_wave buckets g and cl to powers of two; grid ≤ log2(G) × log2(C), pinned by the compile-cache accounting test
            self._packed_fns[key] = self._watch_jit(
                partial(prefill_packed_step, config=self.config),
                "packed", key=key, donate_argnames=("cache",),
            )
        return self._packed_fns[key]

    def _find_prefix_source(self, prompt: list) -> tuple[int, Optional[int]]:
        """Longest chunk-aligned cached prefix of ``prompt`` among
        registered slots → (reusable length, source slot)."""
        C = self.prefill_chunk
        best_len, best_src = 0, None
        # a window ring keeps a slot's newest rows only: a source serves
        # a prefix only while the window before its end is still there
        ring = self._ring_rows
        for s, cached in self._prefix_registry.items():
            common = _common_prefix_len(cached, prompt)
            # at least one real tail token must prefill (it produces
            # the first-token logits), and reuse stays chunk-aligned
            reuse = min(common, len(prompt) - 1) // C * C
            if ring and self.lengths[s] > reuse + ring - self.config.sliding_window + 1:
                continue
            if reuse >= C and reuse > best_len:
                best_len, best_src = reuse, s
        return best_len, best_src

    def start_request(self, prompt: list[int], gen: GenParams) -> int:
        """Reserve a slot and queue the prompt for chunked prefill
        (host bookkeeping only). Raises RuntimeError when full.

        With ``prefix_cache``, a prompt sharing a chunk-aligned prefix
        with a registered slot's cached prompt device-copies those KV
        rows and starts prefill after them — TTFT for a shared system
        prompt drops to the unshared tail's prefill time."""
        free = self.free_slots()
        if not free:
            raise RuntimeError("no free slots")
        self._last_admit = time.monotonic()  # arrival signal → small K
        # cap the generation budget by the cache, then keep as much
        # prompt tail as fits alongside it (never less than 1 token)
        gen.max_new_tokens = max(1, min(gen.max_new_tokens, self.max_seq - 2))
        keep = max(1, self.max_seq - 1 - gen.max_new_tokens)
        if len(prompt) > keep:
            prompt = prompt[-keep:]
        reuse_len, src = (
            self._find_prefix_source(prompt) if self.prefix_cache else (0, None)
        )
        return self._start_request_inner(prompt, gen, free, reuse_len, src)

    def get_copy_fn(self, p: int):
        """Jitted prefix-copy for reuse length ``p`` — the single
        construction point (the server warmup precompiles via this, so
        its variants can't drift from what start_request builds)."""
        if p not in self._copy_fns:
            # dtpu: noqa[DTPU003] p is chunk-aligned by _find_prefix_source (reuse // C * C), ≤ max_seq/prefill_chunk variants, warmup precompiles them
            self._copy_fns[p] = self._watch_jit(
                partial(copy_cache_prefix, p=p), "copy", key=p,
                donate_argnums=(0,),
            )
        return self._copy_fns[p]

    def _start_request_inner(self, prompt, gen, free, reuse_len, src) -> int:
        # prefer slots NOT holding a reusable prefix (preserve the
        # registry), and never overwrite the chosen source itself
        candidates = [s for s in free if s != src] or free
        slot = min(
            candidates, key=lambda s: (s in self._prefix_registry, s)
        )
        if slot == src:
            reuse_len, src = 0, None
        self._prefix_registry.pop(slot, None)  # rows about to be overwritten
        start = 0
        if self._state_layers:
            # the slot's last request's state goes: the first chunk, at
            # position 0, starts from zeros on the device
            self.metrics.family("dtpu_serve_state_resets_total").inc(1)
        if src is not None and reuse_len > 0:
            self.cache = self.get_copy_fn(reuse_len)(
                self.cache, jnp.asarray(src, jnp.int32),
                jnp.asarray(slot, jnp.int32),
            )
            start = reuse_len
            self.prefix_hits += 1
            self.prefix_tokens_reused += reuse_len
            self.metrics.family("dtpu_serve_prefix_hits_total").inc(1)
            self.metrics.family("dtpu_serve_prefix_tokens_reused_total").inc(
                reuse_len
            )
        self._admit_t0[slot] = time.perf_counter()
        self._prefilling[slot] = {
            "prompt": list(prompt),
            "tp": len(prompt),
            "next": start,  # next chunk's global start position
            "gen": gen,
        }
        return slot

    def prefill_step(self, slot: int):
        """Process ONE prompt chunk for ``slot``; None while incomplete,
        the first sampled token once the prompt is fully prefetched."""
        st = self._prefilling.get(slot)
        if st is None:
            # released concurrently (client cancelled mid-chunk)
            return None
        if self._packed_only:
            self.last_wave_slots = [slot]
            return self._packed_wave([slot], {slot: st}).get(slot)
        tp, start = st["tp"], st["next"]
        if tp <= self.prefill_chunk:
            # short prompt: one chunk at the smallest power-of-2 bucket
            cl = 16
            while cl < tp:
                cl *= 2
            cl = min(cl, self.prefill_chunk)
        else:
            cl = self.prefill_chunk
        # never overflow the cache row: dynamic_update_slice would CLAMP
        # an out-of-range start and silently shift the written K/V
        cl = min(cl, self.max_seq - start)
        chunk = st["prompt"][start : start + cl]
        final = start + cl >= tp
        chunk = chunk + [0] * (cl - len(chunk))
        # logits index only matters on the final chunk
        last_ix = (tp - 1 - start) if final else (cl - 1)
        t0 = time.perf_counter()
        # the span holds the activation too: its dtpu.engine.wait (the
        # first token's fetch) lies inside it
        with profiling.span("dtpu.engine.prefill", rows=1, cl=cl):
            logits, self.cache = self._chunk_fn(cl, start)(
                self.params,
                self.cache,
                jnp.asarray([chunk], jnp.int32),
                jnp.asarray(slot, jnp.int32),
                jnp.asarray(last_ix, jnp.int32),
            )
            self.metrics.family("dtpu_serve_prefill_dispatches_total").inc(1)
            self.metrics.family("dtpu_serve_prefill_pack_rows").observe(1)
            if flight.enabled():
                # host-side data only (the DTPU002 contract): serial chunk
                # at its static (C, start) bucket, one row
                flight.record(
                    phase="prefill", slots=[slot], rows=1, g=1, cl=cl,
                    start=start, final=final,
                    dispatch_s=round(time.perf_counter() - t0, 6),
                    traces=(
                        {slot: st["gen"].trace_id} if st["gen"].trace_id
                        else None
                    ),
                    **self.fault_ctx,
                )
            if not final:
                st["next"] = start + cl
                return None
            gen = st["gen"]
            if self._prefilling.pop(slot, None) is None:
                return None  # released while the final chunk ran
            return self._activate(slot, st["prompt"], tp, gen, logits)

    def prefill_wave(self) -> dict[int, int]:
        """ONE prefill dispatch advancing up to ``prefill_pack`` pending
        prompts a chunk each → {slot: first token} for prompts that
        completed this wave (empty while all are mid-prompt).

        The packed call (:func:`prefill_packed_step`) takes traced
        per-row starts, so rows at unequal positions — fresh arrivals
        next to prefix-cache-resumed ones — share one dispatch and one
        compiled variant per (G, C) bucket. A lone chunk-aligned row
        takes the serial per-slot path instead (static start keeps the
        pallas flash prefill kernel eligible); rows a packed wave left
        at a non-chunk-aligned start finish packed at G=1 rather than
        minting serial (C, start) compile variants per odd start.
        """
        # SNAPSHOT the pending states first: Scheduler.cancel() can
        # release a slot (popping its _prefilling entry) from the event
        # loop while the wave runs on a worker thread — every
        # pre-dispatch read goes through the snapshot, the wave-wide
        # form of the serial path's released-concurrently guard. A
        # cancelled row's chunk still dispatches harmlessly (its slot
        # can't be reassigned until the next scheduler tick) and is
        # skipped at activation below.
        t0 = time.perf_counter()
        self._wait_s = 0.0
        states = {}
        for s in list(self._prefilling):
            st = self._prefilling.get(s)
            if st is not None:
                states[s] = st
        if not states:
            return {}
        pending = list(states)
        if not self._packed_only and (
            self.prefill_pack <= 1
            or (
                len(pending) == 1
                and states[pending[0]]["next"] % self.prefill_chunk == 0
            )
        ):
            slot = pending[0]
            self.last_wave_slots = [slot]
            tok = self.prefill_step(slot)
            out = {} if tok is None else {slot: tok}
        else:
            rows = pending[: max(1, self.prefill_pack)]
            # published BEFORE dispatch: on an engine error the caller
            # fails exactly the rows that were in the failing dispatch,
            # not every queued prefill (slots beyond prefill_pack never
            # ran)
            self.last_wave_slots = list(rows)
            out = self._packed_wave(rows, states)
        # the call's host time: a non-final chunk does not sync, so all
        # of it is enqueue; a final one waits in _activate's fetches
        self.metrics.family("dtpu_serve_prefill_host_seconds").observe(
            time.perf_counter() - t0 - self._wait_s
        )
        return out

    def _packed_wave(self, rows: list, states: dict) -> dict[int, int]:
        """One :func:`prefill_packed_step` dispatch over ``rows`` (slots
        of ``states``, the snapshot of their pending prefills) →
        {slot: first token} of the prompts it completed."""
        # chunk length: the power-of-2 bucket covering the widest
        # remaining chunk in the pack, capped at prefill_chunk (the
        # serial path's short-prompt bucketing, shared across rows); a
        # packed-only engine keeps to the full chunk, one program a G
        need = max(
            min(states[s]["tp"] - states[s]["next"], self.prefill_chunk)
            for s in rows
        )
        cl = self.prefill_chunk if self._packed_only else 16
        while cl < need:
            cl *= 2
        cl = min(cl, self.prefill_chunk)
        # bucket G by powers of two so the (G, C) compile grid stays
        # log2 × log2; pad rows carry last_ix = -1 (every write drops)
        g = 1
        while g < len(rows):
            g *= 2
        g = min(g, max(1, self.prefill_pack))
        tok_rows, slot_ix, starts, last_ix = [], [], [], []
        final = {}
        for s in rows:
            st = states[s]
            tp, start = st["tp"], st["next"]
            chunk = st["prompt"][start : start + cl]
            final[s] = start + cl >= tp
            tok_rows.append(chunk + [0] * (cl - len(chunk)))
            slot_ix.append(s)
            starts.append(start)
            last_ix.append((tp - 1 - start) if final[s] else (cl - 1))
        for _ in range(g - len(rows)):
            tok_rows.append([0] * cl)
            slot_ix.append(0)
            starts.append(0)
            last_ix.append(-1)
        t0 = time.perf_counter()
        # the span holds the activations too (see prefill_step)
        with profiling.span("dtpu.engine.prefill", rows=len(rows), cl=cl):
            logits, self.cache = self._packed_fn(g, cl)(
                self.params,
                self.cache,
                jnp.asarray(tok_rows, jnp.int32),
                jnp.asarray(slot_ix, jnp.int32),
                jnp.asarray(starts, jnp.int32),
                jnp.asarray(last_ix, jnp.int32),
            )
            self.metrics.family("dtpu_serve_prefill_dispatches_total").inc(1)
            self.metrics.family("dtpu_serve_prefill_pack_rows").observe(len(rows))
            if self._upper_layers:
                # the prompt positions this wave computed, through the
                # layers that keep something and through those that keep
                # nothing: the same today (PERF.md §7)
                real = sum(ix + 1 for ix in last_ix[: len(rows)])
                self.metrics.family("dtpu_serve_prefill_lower_rows_total").inc(real)
                self.metrics.family("dtpu_serve_prefill_upper_rows_total").inc(real)
            if flight.enabled():
                # batch composition straight from the wave's host lists:
                # the (G, C) bucket, real rows packed, per-row starts
                flight.record(
                    phase="prefill_packed", g=g, cl=cl, rows=len(rows),
                    slots=list(rows), starts=starts[: len(rows)],
                    dispatch_s=round(time.perf_counter() - t0, 6),
                    traces={
                        s: states[s]["gen"].trace_id
                        for s in rows
                        if states[s]["gen"].trace_id
                    } or None,
                    **self.fault_ctx,
                )
            out: dict[int, int] = {}
            for i, s in enumerate(rows):
                st = self._prefilling.get(s)
                if st is None:
                    continue  # released while the wave ran
                if not final[s]:
                    st["next"] += cl
                    continue
                self._prefilling.pop(s, None)
                out[s] = self._activate(
                    s, st["prompt"], st["tp"], st["gen"], logits[i : i + 1]
                )
            return out

    def add_request(
        self, prompt: list[int], gen: GenParams
    ) -> tuple[int, int]:
        """Prefill ``prompt`` into a free slot → (slot, first sampled
        token). Raises RuntimeError when full. Blocking convenience
        over start_request/prefill_step (the scheduler drives those
        incrementally to interleave decode between chunks)."""
        slot = self.start_request(prompt, gen)
        tok = None
        while tok is None:
            tok = self.prefill_step(slot)
        return slot, tok

    def _activate(
        self, slot: int, prompt: list[int], tp: int, gen: GenParams,
        logits: jax.Array,
    ) -> int:
        """Final-prefill tail: seed the PRNG stream, mark seen tokens,
        sample the first token, and publish the slot state."""
        # per-request PRNG stream: explicit seed or a fresh auto seed
        if gen.seed is not None:
            req_seed = int(gen.seed)
        else:
            self._auto_seed += 1
            req_seed = self._auto_seed
        kd = jax.random.key_data(jax.random.key(req_seed))
        if gen.seed is not None and gen.seed_skip > 0:
            # resumable generation: replay the n key advances the
            # delivered tokens consumed, so the continuation samples
            # from the original stream's key sequence (skip_key_data)
            kd = self._skip_key(kd, gen.seed_skip)
        self._key_data = self._key_data.at[slot].set(kd)
        pad = 16  # bucket the mark_prompt compile per power-of-2 length
        while pad < tp:
            pad *= 2
        marked = list(prompt) + [0] * (pad - tp)
        # slot/tp ride along as traced scalars — only the prompt itself
        # is a host list that must cross to device
        self._seen, self._gen_counts = self._mark_prompt(
            self._seen, self._gen_counts, slot,
            jnp.asarray(marked, jnp.int32), tp,
        )
        if gen.logit_bias or self.has_bias[slot]:
            # skip the vocab-size upload when the row is known zero
            # (buffer starts zeroed; has_bias tracks any write)
            import numpy as np

            bias_row = np.zeros((self.config.vocab_size,), np.float32)
            for tid, bv in (gen.logit_bias or {}).items():
                t = int(tid)
                if 0 <= t < self.config.vocab_size:
                    bias_row[t] = float(bv)
            self._logit_bias = self._logit_bias.at[slot].set(bias_row)
        self.min_ps[slot] = gen.min_p
        self.has_bias[slot] = bool(gen.logit_bias)
        # publish the request's sampling knobs to the host lists FIRST,
        # then sample through row slices of the device-resident mirror
        # (_sampling_params) — the previous shape uploaded seven fresh
        # single-element arrays per activation
        self.temps[slot] = gen.temperature
        self.top_ps[slot] = gen.top_p
        self.top_ks[slot] = gen.top_k
        self.rep_pens[slot] = gen.repetition_penalty
        self.pres_pens[slot] = gen.presence_penalty
        self.freq_pens[slot] = gen.frequency_penalty
        self._sampling_state = None  # the writes above made any cached mirror stale
        sp = self._sampling_params()
        temps, top_ps, top_ks, rep_pens, pres_pens, freq_pens, min_ps = sp
        row = slice(slot, slot + 1)
        self._count_sample([slot])
        toks, kd = self._sample(
            logits,
            self._key_data[row],
            temps[row],
            top_ps[row],
            top_ks[row],
            rep_pens[row],
            self._seen[row],
            pres_pens[row],
            freq_pens[row],
            self._gen_counts[row],
            self._logit_bias[row],
            min_ps[row],
        )
        tok = self._fetch(toks).tolist()[0]
        self._key_data = self._key_data.at[slot].set(kd[0])
        self._seen, self._gen_counts = self._mark_seen(
            self._seen, self._gen_counts, self._slot_iota[row], toks
        )
        self.want_logprobs[slot] = gen.logprobs is not None
        if gen.logprobs is not None:
            lp, tids, tlps = (
                a.tolist()
                for a in self._fetch(self._logprobs(logits, toks))
            )
            # tolist() above already yields python floats/ints
            self._last_logprobs[slot] = (
                lp[0],
                list(zip(tids[0], tlps[0])),
            )
        if gen.trace_id:
            self._trace_ids[slot] = gen.trace_id
        t_admit = self._admit_t0.pop(slot, None)
        if t_admit is not None:
            self.metrics.family("dtpu_serve_ttft_seconds").observe(
                time.perf_counter() - t_admit, exemplar=gen.trace_id,
            )
        self.metrics.family("dtpu_serve_tokens_generated_total").inc(1)
        self.active[slot] = True
        self._invalidate_decode_cache()  # activation mutated slot state
        # the sampling-param lists were published BEFORE the mirror was
        # built above and nothing after touched them — restore so the
        # next sampled token reuses the same device arrays (same idiom
        # as _plain_step's restore)
        self._sampling_state = sp
        if self.prefix_cache:
            # the slot's rows now hold this fully-prefilled prompt;
            # they stay reusable until the slot is reassigned
            self._prefix_registry[slot] = list(prompt)
        self.history[slot] = []
        self._ngram_ix[slot] = {}
        self._spec_tries[slot] = 0
        self._spec_accepted[slot] = 0
        self._spec_off[slot] = False
        self._record_tokens(slot, list(prompt) + [tok])
        self.lengths[slot] = tp
        self.remaining[slot] = gen.max_new_tokens - 1
        self.eos[slot] = gen.eos_id
        self.last_token[slot] = tok
        self.finish_reason[slot] = None
        if tok == gen.eos_id or gen.max_new_tokens <= 1:
            # finished immediately; slot never enters the decode loop
            self.active[slot] = False
            self.finish_reason[slot] = "stop" if tok == gen.eos_id else "length"
        return tok

    def _record_tokens(self, slot: int, toks: list) -> None:
        """Append to the slot's history, keeping the n-gram index
        current (the index stores each n-gram's LAST occurrence, added
        lazily one step behind so lookups never match the tail itself)."""
        h = self.history[slot]
        ix = self._ngram_ix[slot]
        n = self.spec_ngram
        for tok in toks:
            h.append(tok)
            # register the n-gram ENDING at the previous position: the
            # trailing n-gram stays unindexed until a newer token lands
            if len(h) > n:
                gram = tuple(h[-n - 1 : -1])
                ix[gram] = len(h) - 1 - n
        return None

    def _find_draft(self, slot: int) -> list:
        """Prompt-lookup draft: tokens that followed the most recent
        earlier occurrence of the history's trailing n-gram (O(1) via
        the incremental index)."""
        if not self.spec_draft or self._spec_off[slot]:
            return []
        h = self.history[slot]
        n = self.spec_ngram
        if len(h) <= n:
            return []
        j = self._ngram_ix[slot].get(tuple(h[-n:]))
        if j is None:
            return []
        return h[j + n : j + n + self.spec_draft]

    def step(self) -> dict:
        """Advance every active slot → {slot: [tokens]}. Slots that hit
        EOS/max tokens (or the cache end) deactivate. Greedy batches
        with an n-gram draft take the speculative path and may emit
        several tokens per call; otherwise each list has one token.

        Wraps the dispatch in the step-latency/TPOT histograms —
        recorded here, at the engine, which /metrics renders."""
        epoch = self._step_epoch
        t_all0 = time.perf_counter()
        self._wait_s = 0.0
        # chaos hook (no-op calls when no plan is installed), fired once
        # per live slot with ctx slot=<i>: a raise provokes mid-decode
        # engine death (the scheduler loop must fail only the inflight
        # requests and keep serving); a hang with a ctx slot wedges
        # exactly that slot's step — the shape the scheduler's watchdog
        # attributes via _step_wedge and aborts via abandon_step().
        for i in range(self.max_batch):
            if not self.active[i]:
                continue
            self._step_wedge = ("slot", i)
            faults.fire("serve.engine.step", slot=i, **self.fault_ctx)
            if epoch != self._step_epoch:
                # the watchdog abandoned this step while it was wedged
                # here; slot state may have been reused since — return
                # without touching anything
                return {}
        self._step_wedge = ("dispatch",)
        t0 = time.perf_counter()
        out = self._step_dispatch()
        self._step_wedge = None
        # NOTE: no epoch check after the dispatch — its host/device
        # mutations already happened, so discarding `out` could only
        # hide them (and would LOSE tokens when a step completes
        # concurrently with a watchdog trip). A dispatch-abandoned
        # step is instead neutralized by the scheduler: it quiesces
        # the engine until this thread returns, then calls
        # :meth:`finish_abandoned_step` before dispatching again.
        if out:
            with profiling.span("dtpu.engine.finish"):
                self._account_step(out, time.perf_counter() - t0, t_all0)
            # the call by phase, from shared clock reads: enqueue is
            # what is left of the call's wall time, so the three add up
            # to it exactly (every emitting path fetched at least once)
            t1 = time.perf_counter()
            finish = t1 - self._fetched_at
            m = self.metrics
            m.family("dtpu_serve_step_enqueue_seconds").observe(
                t1 - t_all0 - self._wait_s - finish
            )
            m.family("dtpu_serve_step_wait_seconds").observe(self._wait_s)
            m.family("dtpu_serve_step_finish_seconds").observe(finish)
        return out

    def _account_step(self, out: dict, dt: float, t_all0: float) -> None:
        """An emitting step's counters, histograms and flight record
        (``dt``: the dispatch's wall time, ``t_all0``: step()'s entry)."""
        n_tokens = sum(len(v) for v in out.values())
        m = self.metrics
        m.family("dtpu_serve_decode_steps_total").inc(1)
        m.family("dtpu_serve_decode_step_seconds").observe(dt)
        m.family("dtpu_serve_tokens_generated_total").inc(n_tokens)
        self._count_decode_keys(out)
        if self._indexer_layers:
            self._count_keys(
                out, self.config.index_topk, self._indexer_layers,
                "dtpu_serve_indexer_keys_selected_total",
                "dtpu_serve_indexer_keys_in_context_total",
            )
        if self._window_layers:
            self._count_keys(
                out, self.config.sliding_window, self._window_layers,
                "dtpu_serve_window_keys_visible_total",
                "dtpu_serve_window_keys_in_context_total",
            )
        if n_tokens and dt > 0:
            # TPOT covers the whole batch: exemplar from the slot
            # that yielded the most tokens this dispatch (ties by
            # slot order) — any live trace explains the step
            ex = None
            for s in sorted(out, key=lambda s: -len(out[s])):
                ex = self._trace_ids.get(s)
                if ex is not None:
                    break
            m.family("dtpu_serve_tpot_seconds").observe(
                dt / n_tokens, exemplar=ex,
            )
        if flight.enabled():
            # one flight record per emitting step — strictly
            # host-side fields (slot lists, perf counters, the
            # prefix-registry snapshot; DTPU002-clean), with the
            # trace ids riding the step for post-mortem stitching
            flight.record(
                phase=self._last_step_phase,
                slots=list(out),
                tokens=n_tokens,
                dispatch_s=round(dt, 6),
                wait_s=round(self._wait_s, 6),
                host_s=round(
                    max(0.0, time.perf_counter() - t_all0 - dt), 6
                ),
                kv_util=round(self.kv_cache_utilization(), 4),
                prefix_slots=len(self._prefix_registry),
                traces={
                    s: self._trace_ids[s]
                    for s in out
                    if s in self._trace_ids
                } or None,
                **self.fault_ctx,
            )
            flight.maybe_poll_memory(self.metrics)

    def _step_dispatch(self) -> dict:
        live = [i for i in range(self.max_batch) if self.active[i]]
        if not live:
            return {}
        phase, drafts = "decode", None
        if self.spec_draft > 0 and self._all_greedy(live):
            drafts = {i: self._find_draft(i) for i in live}
            drafting = sum(1 for d in drafts.values() if d)
            # non-drafting slots pay ~(S×) decode compute for nothing —
            # speculate only when at least half the batch drafts
            if drafting and drafting * 2 >= len(live):
                phase = "spec"
        if (
            phase == "decode"
            and self.turbo_steps > 1
            and not self._prefilling  # don't starve queued prompt chunks
            and self._all_greedy(live)
        ):
            phase = "turbo"
        self._last_step_phase = phase
        # on a capture's clock (obs/profiling.py): `seq` is the flight
        # record this step writes next (/debug/flight carries the slots'
        # trace ids, so a step in a capture leads to /debug/traces with
        # no third identifier; a compile record may take the number
        # first, then the step's is the one after)
        rec = flight.get_recorder()
        with profiling.span(
            "dtpu.engine.step",
            seq=rec.seq + 1 if rec is not None else 0, phase=phase,
        ):
            if phase == "spec":
                return self._spec_step(live, drafts)
            if phase == "turbo":
                return self._turbo_step(live)
            return {i: [tok] for i, tok in self._plain_step(live).items()}

    def _spec_step(self, live: list, drafts: dict) -> dict:
        """One verify_step call emits 1..spec_draft+1 tokens per slot."""
        self._invalidate_decode_cache()  # advancing outside the turbo replay
        sdraft = self.spec_draft + 1
        rows = []
        for i in range(self.max_batch):
            d = drafts.get(i, [])
            row = [self.last_token[i]] + d
            row = row + [0] * (sdraft - len(row))
            rows.append(row[:sdraft])
        # layers that hold a state advance it in the call, by the drafts
        # that stand: the program has to know how many a row holds
        held = {}
        if self._state_layers:
            # dtpu: noqa[DTPU002] the drafts' lengths are this call's own host data, uploaded with its rows (B int32)
            held["draft_len"] = jnp.asarray(
                [len(drafts.get(i, [])) for i in range(self.max_batch)], jnp.int32
            )
        logits, self.cache = self._verify(
            self.params,
            self.cache,
            jnp.asarray(rows, jnp.int32),
            jnp.asarray(self.lengths, jnp.int32),
            write_mask=jnp.asarray(self.active, bool),
            **held,
        )
        # the shared jitted argmax (an op-by-op jnp.argmax here paid
        # uncompiled dispatch overhead every speculative step); ONE
        # fetch + tolist() so the accept loop compares plain ints
        got = self._fetch(self._and_stats(self._argmax(logits)))
        with profiling.span("dtpu.engine.finish"):
            return self._accept_drafts(
                live, drafts, self._routed(got).tolist()  # [B, S]
            )

    def _accept_drafts(self, live: list, drafts: dict, preds: list) -> dict:
        """The host half of :meth:`_spec_step`: per slot, the verified
        prefix of its draft plus one token."""
        out: dict = {}
        for i in live:
            draft = drafts.get(i, [])
            emitted = [preds[i][0]]
            for j, dtok in enumerate(draft):
                if preds[i][j] != dtok:
                    break
                emitted.append(preds[i][j + 1])
            if draft:
                self._spec_tries[i] += 1
                self._spec_accepted[i] += len(emitted) - 1
                if (
                    self._spec_tries[i] >= 4
                    and self._spec_accepted[i] < self._spec_tries[i]
                ):
                    # < 1 accepted draft token per try: drafting this
                    # slot costs more than it saves
                    self._spec_off[i] = True
            toks = []
            for tok in emitted:
                toks.append(tok)
                if not self._advance_slot(i, tok):
                    break
            if toks:
                out[i] = toks
            # note: _seen is not updated here — the spec path is gated
            # to repetition_penalty == 1.0, where seen has no effect
        return out

    def _arrival_busy(self) -> bool:
        """Requests waiting or recently admitted: the regime where long
        device loops tax a newcomer's first token."""
        return (
            self.waiting_requests > 0
            or (time.monotonic() - self._last_admit) < self.turbo_quiet_s
        )

    def _adaptive_turbo_cap(self) -> int:
        """Current macro-step budget: the floor (8) while requests are
        arriving/waiting, doubling toward ``turbo_steps`` once
        arrival-quiet — so a saturated single-stream batch still gets
        the full-K dispatch amortization, but a newly-arrived request
        never waits a 128-step loop for its first token."""
        if self.turbo_steps <= 1:
            return self.turbo_steps
        floor = min(8, self.turbo_steps)
        if self._arrival_busy():
            self._turbo_k = floor
        else:
            self._turbo_k = min(self._turbo_k * 2, self.turbo_steps)
        return self._turbo_k

    def _turbo_fn(self, steps: int):
        if steps not in self._turbo_fns:
            # dtpu: noqa[DTPU003] _turbo_step buckets steps to powers of two capped at turbo_steps; ≤ log2(turbo_steps) variants
            self._turbo_fns[steps] = self._watch_jit(
                partial(
                    decode_loop, config=self.config, steps=steps,
                    max_seq=self.max_seq,
                    decode_kernel=self.decode_kernel, mesh=self._mesh,
                ),
                "turbo", key=steps, donate_argnums=(1,),
            )
        return self._turbo_fns[steps]

    def _invalidate_decode_cache(self) -> None:
        """EVERY host-side slot-state mutation — activation, release,
        sampled/speculative advance, any future cancel/abort or budget
        edit touching ``active``/``lengths``/``remaining``/``last_token``
        — must call this. ``_turbo_step`` trusts the cached device
        arrays otherwise and would silently decode from stale state
        (wrong tokens, no error). The slot-reuse and staggered-admission
        parity tests in tests/serve/test_engine.py pin the contract."""
        self._turbo_state = None
        self._sampling_state = None

    def _sampling_params(self) -> tuple:
        """Device-resident mirrors of the per-slot sampling-parameter
        lists, rebuilt only after a host-side slot mutation (the
        :meth:`_invalidate_decode_cache` contract — activation/release
        are the only writers of these lists). Without the mirror the
        sampled decode path uploads seven host lists per token."""
        if self._sampling_state is None:
            fields = (
                (self.temps, jnp.float32),
                (self.top_ps, jnp.float32),
                (self.top_ks, jnp.int32),
                (self.rep_pens, jnp.float32),
                (self.pres_pens, jnp.float32),
                (self.freq_pens, jnp.float32),
                (self.min_ps, jnp.float32),
            )
            self._sampling_state = tuple(
                jnp.asarray(v, dt)  # dtpu: noqa[DTPU002] THE mirror rebuild — runs only after an invalidation (admission/release), never per token
                for v, dt in fields
            )
        return self._sampling_state

    def _decode_state(self) -> tuple:
        """Device-resident (token, position, budget, active, eos)
        mirrors of the per-slot host lists, rebuilt only after a
        host-side mutation (the :meth:`_invalidate_decode_cache`
        contract). Shared by the turbo macro-step AND the per-step
        paths — without the mirror, ``_plain_step`` re-uploads five
        host lists to device on EVERY sampled token, transfers that
        dominate decode on a remote device."""
        if self._turbo_state is None:
            eos = [
                self.eos[i] if self.eos[i] is not None else -1
                for i in range(self.max_batch)
            ]
            self._turbo_state = (
                jnp.asarray(self.last_token, jnp.int32),
                jnp.asarray(self.lengths, jnp.int32),
                jnp.asarray(self.remaining, jnp.int32),
                jnp.asarray(self.active, bool),
                jnp.asarray(eos, jnp.int32),
            )
        return self._turbo_state

    def _turbo_step(self, live: list) -> dict:
        """One decode_loop macro-step → {slot: [tokens]}. The host
        replays the device's per-step deactivation rules token by token
        so lengths/remaining/finish_reason stay exactly as ``steps``
        sequential :meth:`_plain_step` calls would have left them."""
        # cap the loop by the widest live budget (a near-finished batch
        # must not pay turbo_steps masked forward passes for one
        # token), bucketed to powers of two so the compile-cache holds
        # at most log2(turbo_steps) variants
        budget = max(self.remaining[i] for i in live)
        needed = min(self._adaptive_turbo_cap(), budget)
        steps = 1
        while steps < needed:
            steps *= 2
        steps = min(steps, self.turbo_steps)
        # pipelined segments: only in the saturated regime — cap fully
        # open AND arrival-quiet (with turbo_steps ≤ 8 the busy floor
        # equals the cap, so the cap alone can't prove quiet) — and
        # never past the widest remaining budget; arrivals would
        # otherwise wait depth×K device steps for their first token
        depth = 1
        if (
            self.turbo_depth > 1
            and steps == self.turbo_steps
            and self._turbo_k == self.turbo_steps
            and not self._arrival_busy()
        ):
            depth = min(self.turbo_depth, -(-budget // steps))
        tok_d, pos_d, rem_d, act_d, eos_d = self._decode_state()
        segs = []
        for _ in range(depth):
            toks_dev, self.cache, tok_d, pos_d, rem_d, act_d = (
                self._turbo_fn(steps)(
                    self.params, self.cache,
                    tok_d, pos_d, rem_d, act_d, eos_d,
                )
            )
            segs.append(toks_dev)
        self._turbo_state = (tok_d, pos_d, rem_d, act_d, eos_d)
        # ONE blocking fetch for every in-flight segment ([depth*steps, B]):
        # K×depth tokens amortize this one round trip
        got = self._fetch(self._and_stats(segs))
        with profiling.span("dtpu.engine.finish"):
            toks = np.concatenate(self._routed(got), axis=0).tolist()
            out: dict = {}
            for i in live:
                emitted: list = []
                for k in range(depth * steps):
                    tok = toks[k][i]  # plain int: the fetch tolist()'d once
                    if tok < 0:  # row deactivated on an earlier step
                        break
                    emitted.append(tok)
                    if not self._advance_slot(i, tok):
                        break
                if emitted:
                    out[i] = emitted
                # _seen is not updated here — turbo is gated to slots with
                # no penalties, where the counts can't affect sampling
            return out

    def _fetch(self, x):
        """THE blocking device→host fetch of the engine calls (``step``'s
        three paths, the first token and logprobs of a prefill): ``x`` is
        already built, so what produced it counts as enqueue. The time
        parked here (the device runs, or the transfer does) is the
        running call's ``dtpu_serve_step_wait_seconds`` and is taken off
        its ``dtpu_serve_prefill_host_seconds``; span ``dtpu.engine.wait``
        in a capture."""
        t0 = time.perf_counter()
        with profiling.span("dtpu.engine.wait"):
            # dtpu: noqa[DTPU002] the engine's one fetch site: a step's tokens (one round trip a macro-step), an activation's first token, logprobs where asked
            got = jax.device_get(x)
        self._fetched_at = time.perf_counter()
        self._wait_s += self._fetched_at - t0
        return got

    def _and_stats(self, x):
        """What a step is about to fetch and, for the same transfer,
        the routing counts a chip's share of the experts keeps on the
        device (``cache["moe_stats"]`` and ``["moe_reads"]``, which every
        program adds to, prefill included): ``x`` alone for a model that
        holds every expert. :meth:`_routed` takes what was fetched apart."""
        if "moe_stats" not in self.cache:
            return x
        return (x, *(self.cache[n] for n in _COUNTS))

    def _routed(self, got):
        """The fetched ``x`` of :meth:`_and_stats`; what the routing
        counts grew by since the last fetch goes to the
        ``dtpu_serve_moe_*`` counters (the picks of identity experts,
        and all picks beside them, for a router that has such)."""
        if "moe_stats" not in self.cache:
            return got
        x, *now = got
        now = np.concatenate(now).astype(np.int64)
        # int32 on the device: it wraps, the difference does not
        picks, routed, *more, read, held = (
            (now - self._moe_stats_seen) % (1 << 32)
        ).tolist()
        self._moe_stats_seen = now
        zero = more[:1] if self.config.zero_experts else []
        if self.config.router_groups:
            self.metrics.family("dtpu_serve_moe_tokens_group_hit_total").inc(more[-1])
        self.metrics.family("dtpu_serve_moe_picks_held_total").inc(picks)
        self.metrics.family("dtpu_serve_moe_tokens_routed_total").inc(routed)
        self.metrics.family("dtpu_serve_moe_experts_read_total").inc(read)
        self.metrics.family("dtpu_serve_moe_experts_held_total").inc(held)
        if zero:
            self.metrics.family("dtpu_serve_moe_picks_zero_total").inc(zero[0])
            self.metrics.family("dtpu_serve_moe_picks_total").inc(
                routed * self.config.experts_per_token
            )
        return x

    def _count_keys(self, out: dict, cap: int, layers: int, kept: str, total: str) -> None:
        """Host-side, from positions alone: the keys a mask that keeps at
        most ``cap`` of a context (the sparse indexer's selection over
        the full layers, the window layers' window) let this step's
        tokens attend to → counter ``kept``, and the causal keys in
        their contexts → ``total``, over the ``layers`` it acts on."""
        let = seen = 0
        for slot, toks in out.items():
            # the slot's last token was decoded with lengths[slot] keys
            # in its context, the one before with one fewer
            for ctx in range(self.lengths[slot] - len(toks) + 1, self.lengths[slot] + 1):
                let += min(ctx, cap)
                seen += ctx
        self.metrics.family(kept).inc(let * layers)
        self.metrics.family(total).inc(seen * layers)

    def _count_decode_keys(self, out: dict) -> None:
        """Host-side, from positions alone, what the program took from
        ``positions`` and ``write_mask`` on the device: the key rows the
        full layers' decode attention read in this call's token steps →
        ``dtpu_serve_decode_keys_read_total``, beside the rows the
        slots reserve → ``dtpu_serve_decode_keys_reserved_total``. A
        program that reads whole rows counts read = reserved; the latent
        family reads every slot's blocks up to the longest live context,
        the grouped-query kernel each emitting slot's own blocks and
        nothing of the other slots."""
        steps = max(len(t) for t in out.values())
        kb, spec = self._key_block, self._last_step_phase == "spec"
        if spec:  # one call: S rows a slot
            steps = 1
        reserved = self.max_seq * steps * self.max_batch
        if not kb or (spec and self._slot_keys):  # whole rows (verify keeps the einsum)
            rows = reserved
        elif self._slot_keys:
            # slot i's k-th token found lengths[i] - len(t) + k keys in
            # the cache (its own came beside them) and read whole blocks
            # of them: one pass over the slots, no list a token step
            def blocks_below(n):  # sum of ceil(j / kb) over 0 <= j < n
                full, rest = divmod(max(n - 1, 0), kb)
                return kb * full * (full + 1) // 2 + rest * (full + 1)

            rows = kb * sum(
                blocks_below(self.lengths[i]) - blocks_below(self.lengths[i] - len(t))
                for i, t in out.items()
            )
        else:
            if spec:
                longest = [
                    max(self.lengths[i] - len(t) for i, t in out.items())
                    + self.spec_draft + 1
                ]
            else:  # token step k: the slots that emitted a k-th token
                longest = [
                    max(
                        self.lengths[i] - len(t) + k + 1
                        for i, t in out.items() if len(t) > k
                    )
                    for k in range(steps)
                ]
            rows = self.max_batch * sum(
                kb * min(-(-n // kb), self.max_seq // kb) for n in longest
            )
        m = self.metrics
        m.family("dtpu_serve_decode_keys_read_total").inc(rows * self._full_layers)
        m.family("dtpu_serve_decode_keys_reserved_total").inc(
            reserved * self._full_layers
        )

    def _all_greedy(self, live: list) -> bool:
        """True when every live slot is plain-greedy with no penalties
        or logprobs — the gate shared by the speculative path and the
        argmax fast path. ANY new sampling knob must be added here."""
        return all(
            self.temps[i] <= 0.0
            and self.rep_pens[i] == 1.0
            and self.pres_pens[i] == 0.0
            and self.freq_pens[i] == 0.0
            and self.min_ps[i] == 0.0
            and not self.has_bias[i]
            and not self.want_logprobs[i]
            for i in live
        )

    def _count_sample(self, rows: list) -> None:
        """One :func:`sample` call over slots ``rows``: the same
        decision the program takes on the device (does any of them set
        top-k, top-p or min-p, so that the call sorts the vocabulary),
        from the host lists."""
        m = self.metrics
        m.family("dtpu_serve_sample_calls_total").inc(1)
        if any(
            self.top_ks[i] > 0 or self.top_ps[i] < 1.0 or self.min_ps[i] > 0.0
            for i in rows
        ):
            m.family("dtpu_serve_sample_filtered_calls_total").inc(1)

    def _plain_step(self, live: list) -> dict[int, int]:
        # device-resident decode state: tokens/positions/active come
        # from the cached mirror (rebuilt only after a host-side slot
        # mutation — the _invalidate_decode_cache contract) instead of
        # re-uploading the host lists on every sampled token
        tok_d, pos_d, rem_d, act_d, eos_d = self._decode_state()
        logits, self.cache = self._decode(
            self.params, self.cache, tok_d, pos_d, write_mask=act_d,
        )
        sp = None
        if self._all_greedy(live):
            # all-greedy batch: argmax only — the general sampler's
            # penalty passes over [B, V], its noise draw and the count
            # update buy nothing here
            sampled_dev = self._argmax(logits)
        else:
            sp = self._sampling_params()
            temps, top_ps, top_ks, rep_pens, pres_pens, freq_pens, min_ps = sp
            self._count_sample(live)
            sampled_dev, self._key_data = self._sample(
                logits,
                self._key_data,
                temps,
                top_ps,
                top_ks,
                rep_pens,
                self._seen,
                pres_pens,
                freq_pens,
                self._gen_counts,
                self._logit_bias,
                min_ps,
                act_d,  # a released slot keeps its last request's filters
            )
            self._seen, self._gen_counts = self._mark_seen(
                self._seen, self._gen_counts, self._slot_iota, sampled_dev
            )
            if any(self.want_logprobs[i] for i in live):
                lp, tids, tlps = (
                    a.tolist()
                    for a in self._fetch(self._logprobs(logits, sampled_dev))
                )
                for i in live:
                    if self.want_logprobs[i]:
                        # tolist() above already yields python floats/ints
                        self._last_logprobs[i] = (
                            lp[i],
                            list(zip(tids[i], tlps[i])),
                        )
        adv = self._advance_state(
            tok_d, pos_d, rem_d, act_d, eos_d, sampled_dev
        )
        got = self._fetch(self._and_stats(sampled_dev))
        with profiling.span("dtpu.engine.finish"):
            out = self._emit(live, self._routed(got))
            # _emit invalidated the mirror; the host replay applied the
            # SAME transition advance_decode_state just did on device,
            # so the advanced arrays are the valid next-step inputs
            self._turbo_state = (*adv, eos_d)
            # _emit's invalidation also dropped the sampling-params
            # mirror, but the per-token advance never touches those
            # lists — restore so the next sampled token reuses the same
            # device arrays
            self._sampling_state = sp
            return out

    def _advance_slot(self, i: int, tok: int) -> bool:
        """Publish ONE sampled token for slot ``i`` — the single copy
        of the per-token bookkeeping shared by the plain, speculative,
        and turbo emission paths: length/budget accounting, history for
        the n-gram draft index, and the eos→stop / budget→length
        finish rules (eos wins when both hit on the same token).
        Returns whether the slot is still active."""
        self.lengths[i] += 1
        self.remaining[i] -= 1
        self.last_token[i] = tok
        self._record_tokens(i, [tok])
        if tok == self.eos[i]:
            self.active[i] = False
            self.finish_reason[i] = "stop"
        elif self.remaining[i] <= 0 or self.lengths[i] >= self.max_seq - 1:
            self.active[i] = False
            self.finish_reason[i] = "length"
        return self.active[i]

    def _emit(self, live: list, sampled) -> dict[int, int]:
        """Publish one sampled token per live slot (host bookkeeping).
        ``sampled`` is already host-resident (callers device_get once);
        one tolist() yields plain ints — no per-element numpy scalar
        boxing in the per-token loop."""
        self._invalidate_decode_cache()  # advancing outside the turbo replay
        toks = sampled.tolist() if hasattr(sampled, "tolist") else list(sampled)
        out: dict[int, int] = {}
        for i in live:
            tok = toks[i]
            out[i] = tok
            self._advance_slot(i, tok)
        return out

    def take_logprobs(self, slot: int):
        """(logprob, [(alt_id, alt_lp), ...]) of the slot's most recent
        token, or None when the request didn't ask for logprobs."""
        return self._last_logprobs.pop(slot, None)

    def prefilling_slots(self) -> list[int]:
        """Slots with a queued/in-progress chunked prefill (admission
        order)."""
        return list(self._prefilling)

    def abandon_step(self) -> Optional[tuple]:
        """Watchdog entry: give up on a wedged :meth:`step` running on
        a worker thread → the wedge phase — ``("slot", i)`` when the
        hang is attributable to one slot's pre-dispatch work (the
        chaos-injectable shape: only that slot need die; the epoch
        bump makes the sleeping thread return before it touches any
        state), ``("dispatch",)`` when the jitted dispatch itself is
        stuck (the whole batch is the failure domain, and the caller
        must QUIESCE — no admission, no new dispatch — until the stuck
        thread actually returns, then call
        :meth:`finish_abandoned_step`), or None when the step finished
        concurrently with the trip (the caller should harvest its
        result, not abort anything)."""
        phase = self._step_wedge
        self._step_epoch += 1
        self._step_wedge = None
        if phase is not None and flight.enabled():
            # flight-record the wedge itself — the attribution the
            # post-mortem's LAST record carries: the wedged slot and
            # its trace id when attributable, a dispatch marker when
            # the jitted dispatch hung with no single culprit
            if phase[0] == "slot":
                flight.record(
                    phase="wedge", slot=phase[1],
                    trace=self._trace_ids.get(phase[1]),
                    **self.fault_ctx,
                )
            else:
                flight.record(
                    phase="wedge", dispatch=True, **self.fault_ctx
                )
            flight.post_mortem(
                "watchdog_abort",
                registry=self.metrics,
                wedge=(
                    f"slot:{phase[1]}" if phase[0] == "slot" else "dispatch"
                ),
                slots={
                    i: self._trace_ids.get(i)
                    for i in range(self.max_batch)
                    if self.active[i]
                },
                **self.fault_ctx,
            )
        return phase

    def finish_abandoned_step(self) -> None:
        """Called once a dispatch-abandoned step's thread has actually
        returned: the stale step rebuilt the device decode mirrors
        from slot state the scheduler has since released — drop them
        so the next dispatch rebuilds from current host truth."""
        self._invalidate_decode_cache()

    def release(self, slot: int) -> None:
        self.active[slot] = False
        self._invalidate_decode_cache()
        self._prefilling.pop(slot, None)
        self._admit_t0.pop(slot, None)
        self._trace_ids.pop(slot, None)
        self._last_logprobs.pop(slot, None)

    def warm_prefix_copies(self) -> None:
        """Pre-compile every chunk-aligned prefix-copy variant (slot 0
        onto itself is a semantic no-op — trivial fused copies, but a
        cold jit inside start_request would land the compile wait on a
        production request's TTFT, and a post-warmup compile is
        exactly what the flight recorder flags as a recompile). ONE
        copy of the loop shared by the server warmup and the soak
        harness, so their definitions of "warm" cannot drift."""
        if not self.prefix_cache:
            return
        # dtpu: noqa[DTPU002] one-time warmup constant (slot index 0), uploaded once outside any dispatch path
        zero = jnp.asarray(0, jnp.int32)
        p = self.prefill_chunk
        while p < self.max_seq:
            self.cache = self.get_copy_fn(p)(self.cache, zero, zero)
            p += self.prefill_chunk

    def mark_flight_warm(self) -> None:
        """Declare the warmup complete: every expected compile variant
        exists, so any compile the flight recorder observes from here
        on is a STEADY-STATE RECOMPILE — flagged as a ``recompile``
        ring record + ``dtpu_serve_recompiles_total`` (the runtime
        complement of lint rule DTPU003's bucketing pragmas). Called
        by the server warmup and the soak harness after their warmup
        traffic; per-engine, so one process's replicas warm
        independently."""
        self._flight_warm = True

    @property
    def flight_warm(self) -> bool:
        return self._flight_warm

    def _note_boot_compile(
        self, fn_name: str, key, seconds: float, recompile: bool
    ) -> None:
        """watch_jit on_compile hook: warmup compiles populate the
        boot-compile manifest; a post-warm compile of a variant the
        manifest never saw is a WARMUP-COVERAGE GAP — warmup skipped
        that bucket, so a live request just paid its first trace
        (``dtpu_serve_warmup_gap_compiles_total{fn}``). A post-warm
        compile of a covered variant is a plain recompile (retrace of
        a warmed shape: jit cache eviction, donation mismatch) and
        already counted by the flight recorder."""
        mk = obs_boot.manifest_key(fn_name, key)
        if not self._flight_warm:
            self._compile_manifest.add(mk)
            return
        if mk not in self._compile_manifest:
            fam = self.metrics.family("dtpu_serve_warmup_gap_compiles_total")
            if fam is not None:
                fam.inc(1, fn_name)
            logger.warning(
                "warmup-coverage gap: %s compiled %.3fs post-warm but was "
                "never visited by warmup (manifest of %d variants)",
                mk, seconds, len(self._compile_manifest),
            )

    def compile_manifest(self) -> set:
        """The boot-compile manifest: every ``manifest_key`` warmup
        visited (frozen in practice once ``mark_flight_warm`` runs).
        Copy — callers diff it against observed steady-state keys via
        ``obs.boot.manifest_diff``."""
        return set(self._compile_manifest)

    def reset_prefix_cache(self) -> None:
        """Forget every registered reusable prompt prefix (no device
        work — the KV rows just stop being reuse candidates). For
        warmup/bench isolation: synthetic prompts must not prefix-hit
        real traffic or a measured cold run."""
        self._prefix_registry.clear()

    def kv_cache_utilization(self) -> float:
        """Cached tokens across live (active or prefilling) slots as a
        fraction of total cache capacity. Called from the /metrics
        handler on the event loop while the scheduler mutates slot
        state in a worker thread — snapshot the prefill dict first
        (list() is atomic under the GIL; iterating the live dict could
        hit 'changed size during iteration')."""
        prefilling = list(self._prefilling.values())
        live_tokens = sum(
            self.lengths[i]
            for i in range(self.max_batch)
            if self.active[i]
        ) + sum(st["next"] for st in prefilling)
        return live_tokens / float(self.max_batch * self.max_seq)

    def prefix_stats(self) -> dict:
        """Prefix-cache registry occupancy for ``/health`` and the
        router's affinity score (serving.md §10): lifetime hit count,
        occupied registry slots, occupancy ratio, and total cached
        prompt tokens still reusable. Snapshot the registry first —
        this runs on the event loop while the scheduler mutates slots
        in a worker thread (same contract as kv_cache_utilization)."""
        cached = list(self._prefix_registry.values())
        return {
            "prefix_hits": self.prefix_hits,
            "prefix_slots": len(cached),
            "prefix_occupancy": round(len(cached) / float(self.max_batch), 6),
            "prefix_tokens": sum(len(p) for p in cached),
        }

    def update_state_gauges(self) -> None:
        """Refresh the engine-state gauges (called at scrape time — a
        gauge that only changes when requests move needs no per-step
        writes)."""
        active = sum(1 for a in self.active if a)
        m = self.metrics
        m.family("dtpu_serve_active_slots").set(active)
        m.family("dtpu_serve_max_slots").set(self.max_batch)
        m.family("dtpu_serve_batch_occupancy_ratio").set(
            active / float(self.max_batch)
        )
        m.family("dtpu_serve_kv_cache_utilization_ratio").set(
            round(self.kv_cache_utilization(), 6)
        )
        m.family("dtpu_serve_prefix_slots").set(
            self.prefix_stats()["prefix_slots"]
        )
        # compile-cache footprint of the memoized jit grids (the
        # log2-bucket contracts bound these; a growing gauge in steady
        # state is the compile-storm signal the recompile counter
        # explains)
        m.family("dtpu_serve_compile_cache_entries").set(
            len(self._chunk_fns), "chunk"
        )
        m.family("dtpu_serve_compile_cache_entries").set(
            len(self._packed_fns), "packed"
        )
        m.family("dtpu_serve_compile_cache_entries").set(
            len(self._turbo_fns), "turbo"
        )
        m.family("dtpu_serve_compile_cache_entries").set(
            len(self._copy_fns), "copy"
        )
        # scrape-time device-memory freshness (throttled; honest
        # no-op on backends without stats)
        flight.maybe_poll_memory(m)

    def generate(self, prompt: list[int], gen: GenParams) -> list[int]:
        """Convenience single-prompt generation (tests, CLI)."""
        slot, tok = self.add_request(prompt, gen)
        out = [tok]
        if tok == gen.eos_id:
            return out
        while self.active[slot]:
            step_out = self.step()
            for tok in step_out.get(slot, []):
                if tok == gen.eos_id:
                    break
                out.append(tok)
        self.release(slot)
        return out
