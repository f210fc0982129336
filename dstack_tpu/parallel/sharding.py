"""Logical-axis sharding rules → ``PartitionSpec``s.

Model code annotates every parameter with *logical* axis names
(``"embed"``, ``"vocab"``, ``"heads"``, ``"mlp"``, …). A rule table maps
logical names to mesh axes per parallelism strategy; XLA then inserts the
collectives (all-gather for fsdp params, psum for tp partials). This is
the flax ``logical_to_mesh`` idea done on plain pytrees.
"""

from dataclasses import dataclass
from typing import Any, Optional, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxis = Union[str, tuple[str, ...], None]

# Default rule table: logical axis -> mesh axis (or tuple).
DEFAULT_RULES: dict[str, MeshAxis] = {
    "batch": ("dp", "fsdp", "ep"),
    "batch_noexp": ("dp", "fsdp"),  # batch dim of ep-sharded MoE tensors
    "seq": "sp",
    "kv_seq": None,  # KV sequence stays replicated outside ring attention
    "embed": None,
    "embed_fsdp": "fsdp",  # param embed dim sharded for ZeRO-3
    "vocab": "tp",
    "heads": "tp",
    "kv_heads": "tp",
    "head_dim": None,
    "mlp": "tp",
    "experts": "ep",
    "layers": None,  # stacked (scanned) layer dim
    "stages": "pp",  # pipeline stages (pipeline.py uses its own mesh)
}


@dataclass
class ShardingRules:
    rules: dict[str, MeshAxis]

    def spec(self, logical_axes: tuple[Optional[str], ...]) -> P:
        return P(*(self.rules.get(a) if a is not None else None for a in logical_axes))

    def mesh_sharding(
        self, mesh: Mesh, logical_axes: tuple[Optional[str], ...]
    ) -> NamedSharding:
        return NamedSharding(mesh, filter_spec_for_mesh(self.spec(logical_axes), mesh))


def filter_spec_for_mesh(spec: P, mesh: Mesh) -> P:
    """Drop mesh axes the mesh doesn't define (e.g. "pp" on a 5-axis mesh)."""
    names = set(mesh.axis_names)

    def keep(entry: MeshAxis) -> MeshAxis:
        if entry is None:
            return None
        if isinstance(entry, str):
            return entry if entry in names else None
        kept = tuple(a for a in entry if a in names)
        return kept if kept else None

    return P(*(keep(e) for e in spec))


def default_rules(overrides: Optional[dict[str, MeshAxis]] = None) -> ShardingRules:
    rules = dict(DEFAULT_RULES)
    if overrides:
        rules.update(overrides)
    return ShardingRules(rules)


def tree_pspecs(spec_tree: Any, rules: ShardingRules) -> Any:
    """Map a pytree of logical-axis tuples to a pytree of PartitionSpecs."""
    return jax.tree.map(
        lambda axes: rules.spec(axes),
        spec_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(a, (str, type(None))) for a in x),
    )


def tree_shardings(spec_tree: Any, mesh: Mesh, rules: ShardingRules) -> Any:
    return jax.tree.map(
        lambda axes: rules.mesh_sharding(mesh, axes),
        spec_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(a, (str, type(None))) for a in x),
    )


def constrain(
    x: jax.Array,
    rules: ShardingRules,
    *logical_axes: Optional[str],
    mesh: Optional[Mesh] = None,
) -> jax.Array:
    """``with_sharding_constraint`` by logical axes; no-op without a mesh."""
    if mesh is None:
        return x
    spec = filter_spec_for_mesh(rules.spec(tuple(logical_axes)), mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def kernel_shard(
    mesh: Optional[Mesh],
    rules: ShardingRules,
    batch: int,
    kv_heads: int,
) -> Optional[tuple[Mesh, P]]:
    """``(mesh, spec)`` for running a per-(batch, head) attention kernel
    under ``shard_map`` — ``spec`` fits both the ``[B, H, T, D]`` and
    the ``[B, Hkv, T, D]`` operands — or None on a one-device mesh.

    GSPMD cannot partition a Mosaic (Pallas) call — on a real multi-chip
    mesh the compiler refuses it — so the kernel has to run per shard.
    Attention is independent per batch row and per KV-head group, so the
    batch and head axes shard without collectives. A mesh axis whose
    size does not divide its dim is left out (that dim replicates and
    each shard computes all of it); q and kv heads share one axis, so
    GQA groups never straddle a shard."""
    if mesh is None or mesh.size == 1:
        return None

    def fit(logical: str, dim: int) -> MeshAxis:
        entry = filter_spec_for_mesh(rules.spec((logical,)), mesh)[0]
        axes = (entry,) if isinstance(entry, str) else (entry or ())
        ways = 1
        for a in axes:
            ways *= mesh.shape[a]
        return entry if dim % ways == 0 else None

    return mesh, P(fit("batch", batch), fit("kv_heads", kv_heads), None, None)
