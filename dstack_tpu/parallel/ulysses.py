"""Ulysses attention: all-to-all sequence parallelism over ``sp``.

The second long-context strategy next to ring attention
(parallel/ring_attention.py). Instead of rotating KV shards around a
ring (sp ppermute steps), one ``all_to_all`` reshards activations from
sequence-sharded to *head*-sharded, every device runs full-sequence
attention over its head subset, and a second ``all_to_all`` reshards
back (DeepSpeed-Ulysses; on TPU both collectives ride ICI).

Trade-offs vs the ring:

- 2 collectives total instead of ``sp`` neighbor exchanges — wins when
  sp is large and the per-step compute can't hide the ppermute latency.
- The local attention sees the FULL sequence, so the pallas flash
  kernel applies with *static* masking params — sliding windows and
  softcaps work on the fast path (the ring must fall back to its XLA
  path for windows, since inter-shard offsets are traced there).
- Requires the head dim to split: ``H % sp == 0`` (GQA KV heads are
  expanded to query width first when ``Hkv % sp != 0``). Ring has no
  head-count constraint.
- Peak activation memory holds a [B, H/sp, T, D] full-sequence slab;
  the ring only ever holds [B, H, T/sp, D] blocks.

Differentiability is free: ``all_to_all`` is linear and the flash
kernel has its own VJP — no custom ring-style backward sweep needed.

No NCCL analog exists or is needed; with ring attention this *is* the
distributed communication backend for the sequence dimension
(SURVEY.md §5 long-context).
"""

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from dstack_tpu.ops.attention import attention


def _expand_kv(k: jax.Array, h: int, sp: int) -> jax.Array:
    """Minimally repeat KV heads so the head dim splits by ``sp``.

    The repeat factor is the smallest ``r`` with ``sp | hkv*r`` and
    ``hkv*r | h`` (the second keeps the per-device GQA group integral;
    contiguous-repeat alignment then matches the query chunks exactly).
    Repeating to full query width would inflate the full-sequence KV
    slabs — Ulysses' memory weak spot — by ``h/hkv`` instead of ``r``.
    """
    hkv = k.shape[1]
    if hkv % sp == 0:
        return k
    assert h % hkv == 0
    r = sp // math.gcd(hkv, sp)
    if h % (hkv * r) != 0:  # group wouldn't stay integral: full width
        r = h // hkv
    return jnp.repeat(k, r, axis=1)


def ulysses_attention(
    q: jax.Array,  # [B, H, T, D] — seq sharded over "sp"
    k: jax.Array,  # [B, Hkv, T, D]
    v: jax.Array,  # [B, Hkv, T, D]
    *,
    mesh: Mesh,
    causal: bool = True,
    scale: Optional[float] = None,
    axis_name: str = "sp",
    window: int = 0,
    softcap: float = 0.0,
    impl: Optional[str] = None,  # forwarded to ops.attention
) -> jax.Array:
    """Exact multi-device attention via head⇄sequence all_to_all.

    Inputs/outputs are *global* arrays sharded over ``axis_name`` on the
    sequence dim (same contract as :func:`ring_attention`).
    """
    sp = mesh.shape[axis_name]
    if sp == 1:
        return attention(
            q, k, v, causal=causal, scale=scale, window=window,
            softcap=softcap, impl=impl,
        )
    b, h, t, d = q.shape
    if h % sp != 0:
        raise ValueError(
            f"ulysses needs n_heads {h} divisible by sp={sp} (use ring "
            "attention otherwise)"
        )
    scale = float(scale) if scale is not None else d**-0.5
    k = _expand_kv(k, h, sp)
    v = _expand_kv(v, h, sp)

    def local_fn(q, k, v):
        # local [B, H, T/sp, D] → scatter heads / gather sequence
        def seq_to_heads(x):
            return jax.lax.all_to_all(
                x, axis_name, split_axis=1, concat_axis=2, tiled=True
            )  # → [B, H/sp (or Hkv/sp), T, D]

        qh = seq_to_heads(q)
        kh = seq_to_heads(k)
        vh = seq_to_heads(v)
        oh = attention(
            qh, kh, vh, causal=causal, scale=scale, window=window,
            softcap=softcap, impl=impl,
        )
        # heads back together, sequence back to shards
        return jax.lax.all_to_all(
            oh, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    spec = P(None, None, axis_name, None)
    kv_spec = P(None, None, axis_name, None)
    return jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(spec, kv_spec, kv_spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)
