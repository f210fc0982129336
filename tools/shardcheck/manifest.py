"""The registered manifest of the engine's jit surface + parallel/ entries.

Each :class:`Entry` names one jitted function the serve engine
dispatches (the ``_watch``/``_watch_jit`` names in
``serve/engine.py``) or one ``parallel/`` entry point, and knows how
to build abstract arguments for it and what output structure the
engine relies on. The runner (``__main__``) eval_shapes every entry
over every :data:`GRIDS` mesh; :func:`engine_jit_sites` is the
AST-level coverage scan that forces new engine jit sites to register
here.

This module imports JAX lazily — ``--validate`` (manifest
well-formedness + coverage) runs with no JAX at all.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable

REPO = Path(__file__).resolve().parent.parent.parent
ENGINE_PATH = REPO / "dstack_tpu" / "serve" / "engine.py"

#: AbstractMesh grids the gate verifies against — axis names must be
#: drawn from parallel/mesh.py AXES (dtpu-lint DTPU012 checks that
#: statically; here a typo fails the abstract trace).
GRIDS: dict[str, tuple[tuple[str, int], ...]] = {
    "tp2": (("tp", 2),),
    "tp4": (("tp", 4),),
    "dp2xtp2": (("dp", 2), ("tp", 2)),
}

# abstract problem dims — chosen so every grid divides evenly and the
# flash-decode cache-length floor (multiples of 128) is respected
B = 2        # engine batch / slots
T = 128      # max_seq (cache length)
S = 4        # speculative verify width
C = 16       # prefill chunk length
G = 2        # packed prefill group
STEPS = 4    # turbo decode_loop steps
SEQ = 64     # parallel/ attention sequence length
HEADS = 8    # divisible by tp4 and by sp=2 (ulysses head split)
KV_HEADS = 4
HEAD_DIM = 32


@dataclass(frozen=True)
class Entry:
    """One verified jit surface: ``build(ctx)`` returns
    ``(fn, args, kwargs)`` of abstract values; ``check(ctx, out)``
    raises AssertionError when the traced output breaks the engine's
    structural contract (shapes/dtypes/donation aliasing)."""

    name: str
    kind: str  # "engine" | "parallel"
    build: Callable
    check: Callable
    notes: str = ""


MANIFEST: dict[str, Entry] = {}


def register(name: str, kind: str, *, notes: str = ""):
    def deco(build_and_check):
        build, check = build_and_check()
        if name in MANIFEST:
            raise ValueError(f"duplicate shardcheck entry {name!r}")
        MANIFEST[name] = Entry(name, kind, build, check, notes)
        return build_and_check

    return deco


# ---------------------------------------------------------------------------
# abstract context: config + mesh + eval_shape'd params/cache per grid
# ---------------------------------------------------------------------------


@dataclass
class Ctx:
    grid: str
    mesh: object  # jax.sharding.AbstractMesh
    config: object  # LlamaConfig
    params: object  # abstract param tree
    cache: dict  # abstract KV cache tree
    _sds: Callable = field(default=None, repr=False)

    def sds(self, shape, dtype):
        return self._sds(shape, dtype)

    def i32(self, *shape):
        import jax.numpy as jnp

        return self.sds(shape, jnp.int32)

    def f32(self, *shape):
        import jax.numpy as jnp

        return self.sds(shape, jnp.float32)


def make_ctx(grid: str) -> Ctx:
    """Abstract config/params/cache for one mesh grid — device-free:
    params and cache come out of ``jax.eval_shape`` (the cache builder
    jits with ``out_shardings`` over the AbstractMesh, which traces
    fine without devices)."""
    from dataclasses import replace
    from functools import partial

    import jax
    from jax.sharding import AbstractMesh

    from dstack_tpu.models import llama
    from dstack_tpu.serve import engine as eng

    # LLAMA_TINY widened so heads/kv-heads/mlp divide every grid's tp
    config = replace(
        llama.LLAMA_TINY,
        n_heads=HEADS,
        n_kv_heads=KV_HEADS,
        hidden_size=HEADS * HEAD_DIM,
        intermediate_size=2 * HEADS * HEAD_DIM,
        max_seq_len=2 * T,
    )
    names, sizes = zip(*GRIDS[grid])
    mesh = AbstractMesh(sizes, names)
    params = jax.eval_shape(partial(llama.init_params, config), jax.random.key(0))
    cache = jax.eval_shape(lambda: eng.init_cache(config, B, T, mesh=mesh))
    return Ctx(grid, mesh, config, params, cache, _sds=jax.ShapeDtypeStruct)


# ---------------------------------------------------------------------------
# structural checks
# ---------------------------------------------------------------------------


def _assert_shape(out, shape, dtype=None, what="output"):
    assert tuple(out.shape) == tuple(shape), (
        f"{what}: shape {tuple(out.shape)} != expected {tuple(shape)}"
    )
    if dtype is not None:
        assert out.dtype == dtype, (
            f"{what}: dtype {out.dtype} != expected {dtype}"
        )


def _assert_cache_roundtrip(ctx, cache_out, what):
    """Donated-cache contract: the returned cache tree must be
    structurally identical to the input (donation aliasing requires
    it; a drift here is a silent reallocation per step on device)."""
    import jax

    in_s = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), ctx.cache)
    out_s = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), cache_out)
    assert in_s == out_s, (
        f"{what}: cache tree drifted across the step: {in_s} -> {out_s}"
    )


# ---------------------------------------------------------------------------
# engine jit surface (names match _watch/_watch_jit registration)
# ---------------------------------------------------------------------------


@register("decode", "engine")
def _decode():
    def build(ctx):
        from functools import partial

        from dstack_tpu.serve import engine as eng

        fn = partial(
            eng.decode_step, config=ctx.config, decode_kernel="einsum",
            mesh=ctx.mesh,
        )
        return fn, (ctx.params, ctx.cache, ctx.i32(B), ctx.i32(B)), {}

    def check(ctx, out):
        import jax.numpy as jnp

        logits, cache = out
        _assert_shape(logits, (B, ctx.config.vocab_size), jnp.float32, "logits")
        _assert_cache_roundtrip(ctx, cache, "decode")

    return build, check


@register("verify", "engine")
def _verify():
    def build(ctx):
        from functools import partial

        import jax.numpy as jnp

        from dstack_tpu.serve import engine as eng

        fn = partial(
            eng.verify_step, config=ctx.config, decode_kernel="einsum",
            mesh=ctx.mesh,
        )
        args = (ctx.params, ctx.cache, ctx.i32(B, S), ctx.i32(B))
        return fn, args, {"write_mask": ctx.sds((B,), jnp.bool_)}

    def check(ctx, out):
        import jax.numpy as jnp

        logits, cache = out
        _assert_shape(
            logits, (B, S, ctx.config.vocab_size), jnp.float32, "logits"
        )
        _assert_cache_roundtrip(ctx, cache, "verify")

    return build, check


@register("sample", "engine")
def _sample():
    def build(ctx):
        import jax.numpy as jnp

        from dstack_tpu.serve import engine as eng

        v = ctx.config.vocab_size
        args = (
            ctx.f32(B, v),                       # logits
            ctx.sds((B, 2), jnp.uint32),         # key_data
            ctx.f32(B), ctx.f32(B), ctx.i32(B),  # temperature, top_p, top_k
            ctx.f32(B),                          # rep_pen
            ctx.i32(B, v),                       # counts
            ctx.f32(B), ctx.f32(B),              # pres_pen, freq_pen
            ctx.i32(B, v),                       # gen_counts
        )
        return eng.sample, args, {}

    def check(ctx, out):
        import jax.numpy as jnp

        tokens, key_data = out
        _assert_shape(tokens, (B,), jnp.int32, "tokens")
        _assert_shape(key_data, (B, 2), jnp.uint32, "key_data")

    return build, check


@register("argmax", "engine")
def _argmax():
    def build(ctx):
        from functools import partial

        import jax.numpy as jnp

        return (
            partial(jnp.argmax, axis=-1),
            (ctx.f32(B, ctx.config.vocab_size),),
            {},
        )

    def check(ctx, out):
        _assert_shape(out, (B,), None, "argmax")

    return build, check


@register("advance_state", "engine")
def _advance_state():
    def build(ctx):
        from functools import partial

        import jax.numpy as jnp

        from dstack_tpu.serve import engine as eng

        fn = partial(eng.advance_decode_state, max_seq=T)
        args = (
            ctx.i32(B), ctx.i32(B), ctx.i32(B),
            ctx.sds((B,), jnp.bool_), ctx.i32(B), ctx.i32(B),
        )
        return fn, args, {}

    def check(ctx, out):
        import jax.numpy as jnp

        tok, pos, rem, act = out
        for a, name in ((tok, "tok"), (pos, "pos"), (rem, "rem")):
            _assert_shape(a, (B,), jnp.int32, name)
        _assert_shape(act, (B,), jnp.bool_, "act")

    return build, check


@register("logprobs", "engine")
def _logprobs():
    def build(ctx):
        from dstack_tpu.serve import engine as eng

        return (
            eng.token_logprobs,
            (ctx.f32(B, ctx.config.vocab_size), ctx.i32(B)),
            {},
        )

    def check(ctx, out):
        from dstack_tpu.serve.engine import TOP_LOGPROBS

        chosen, top_ids, top_lp = out
        _assert_shape(chosen, (B,), None, "chosen")
        _assert_shape(top_ids, (B, TOP_LOGPROBS), None, "top_ids")
        _assert_shape(top_lp, (B, TOP_LOGPROBS), None, "top_lp")

    return build, check


@register("mark_seen", "engine")
def _mark_seen():
    def build(ctx):
        from dstack_tpu.serve import engine as eng

        v = ctx.config.vocab_size
        return (
            eng._mark_seen,
            (ctx.i32(B, v), ctx.i32(B, v), ctx.i32(B), ctx.i32(B)),
            {},
        )

    def check(ctx, out):
        v = ctx.config.vocab_size
        _assert_shape(out[0], (B, v), None, "counts")
        _assert_shape(out[1], (B, v), None, "gen_counts")

    return build, check


@register("mark_prompt", "engine")
def _mark_prompt():
    def build(ctx):
        from dstack_tpu.serve import engine as eng

        v = ctx.config.vocab_size
        args = (
            ctx.i32(B, v), ctx.i32(B, v), ctx.i32(), ctx.i32(T), ctx.i32()
        )
        return eng._mark_prompt, args, {}

    def check(ctx, out):
        v = ctx.config.vocab_size
        _assert_shape(out[0], (B, v), None, "counts")
        _assert_shape(out[1], (B, v), None, "gen_counts")

    return build, check


@register("skip_key", "engine")
def _skip_key():
    def build(ctx):
        import jax.numpy as jnp

        from dstack_tpu.serve import engine as eng

        return eng.skip_key_data, (ctx.sds((2,), jnp.uint32), ctx.i32()), {}

    def check(ctx, out):
        import jax.numpy as jnp

        _assert_shape(out, (2,), jnp.uint32, "key_data")

    return build, check


@register("chunk", "engine")
def _chunk():
    def build(ctx):
        from functools import partial

        from dstack_tpu.serve import engine as eng

        fn = partial(eng.prefill_chunk_step, config=ctx.config, start=0)
        return fn, (ctx.params, ctx.cache, ctx.i32(1, C), ctx.i32(), ctx.i32()), {}

    def check(ctx, out):
        logits, cache = out
        _assert_shape(logits, (1, ctx.config.vocab_size), None, "logits")
        _assert_cache_roundtrip(ctx, cache, "chunk")

    return build, check


@register("packed", "engine")
def _packed():
    def build(ctx):
        from functools import partial

        from dstack_tpu.serve import engine as eng

        fn = partial(eng.prefill_packed_step, config=ctx.config)
        args = (
            ctx.params, ctx.cache, ctx.i32(G, C), ctx.i32(G), ctx.i32(G),
            ctx.i32(G),
        )
        return fn, args, {}

    def check(ctx, out):
        logits, cache = out
        _assert_shape(logits, (G, ctx.config.vocab_size), None, "logits")
        _assert_cache_roundtrip(ctx, cache, "packed")

    return build, check


@register("copy", "engine")
def _copy():
    def build(ctx):
        from functools import partial

        from dstack_tpu.serve import engine as eng

        fn = partial(eng.copy_cache_prefix, p=C)
        return fn, (ctx.cache, ctx.i32(), ctx.i32()), {}

    def check(ctx, out):
        _assert_cache_roundtrip(ctx, out, "copy")

    return build, check


@register("turbo", "engine")
def _turbo():
    def build(ctx):
        from functools import partial

        import jax.numpy as jnp

        from dstack_tpu.serve import engine as eng

        fn = partial(
            eng.decode_loop, config=ctx.config, steps=STEPS, max_seq=T,
            decode_kernel="einsum", mesh=ctx.mesh,
        )
        args = (
            ctx.params, ctx.cache, ctx.i32(B), ctx.i32(B), ctx.i32(B),
            ctx.sds((B,), jnp.bool_), ctx.i32(B),
        )
        return fn, args, {}

    def check(ctx, out):
        toks, cache = out[0], out[1]
        _assert_shape(toks, (STEPS, B), None, "tokens")
        _assert_cache_roundtrip(ctx, cache, "turbo")

    return build, check


# ---------------------------------------------------------------------------
# parallel/ entry points — run over the grid's "tp" axis (every grid
# has one); the trace validates axis binding + divisibility end to end
# ---------------------------------------------------------------------------


def _qkv(ctx):
    return (
        ctx.f32(B, HEADS, SEQ, HEAD_DIM),
        ctx.f32(B, KV_HEADS, SEQ, HEAD_DIM),
        ctx.f32(B, KV_HEADS, SEQ, HEAD_DIM),
    )


@register("ring_attention", "parallel", notes="xla ring over the tp axis")
def _ring():
    def build(ctx):
        from functools import partial

        from dstack_tpu.parallel.ring_attention import ring_attention

        fn = partial(ring_attention, mesh=ctx.mesh, axis_name="tp", impl="xla")
        return fn, _qkv(ctx), {}

    def check(ctx, out):
        _assert_shape(out, (B, HEADS, SEQ, HEAD_DIM), None, "ring out")

    return build, check


@register("ulysses_attention", "parallel", notes="head<->seq all_to_all over tp")
def _ulysses():
    def build(ctx):
        from functools import partial

        from dstack_tpu.parallel.ulysses import ulysses_attention

        fn = partial(ulysses_attention, mesh=ctx.mesh, axis_name="tp")
        return fn, _qkv(ctx), {}

    def check(ctx, out):
        _assert_shape(out, (B, HEADS, SEQ, HEAD_DIM), None, "ulysses out")

    return build, check


@register(
    "pipeline_apply", "parallel",
    notes="GPipe loop over tp as the stage axis (jax.shard_map with "
    "partial-manual axis_names)",
)
def _pipeline():
    def build(ctx):
        from functools import partial

        import jax.numpy as jnp

        from dstack_tpu.parallel.pipeline import pipeline_apply

        pp = dict(GRIDS[ctx.grid])["tp"]
        d = 16

        def stage_fn(local, x, extras):
            return x @ local["w"][0], jnp.float32(0.0)

        fn = partial(
            pipeline_apply, stage_fn, mesh=ctx.mesh, axis_name="tp",
            extras=None,
        )
        args = (
            {"w": ctx.f32(pp, 1, d, d)},  # [pp, L/pp, d, d]
            ctx.f32(4, 8, d),             # [n_micro, mb, d]
        )
        return fn, args, {}

    def check(ctx, out):
        ys, aux = out
        _assert_shape(ys, (4, 8, 16), None, "pipeline out")
        _assert_shape(aux, (), None, "aux")

    return build, check


# ---------------------------------------------------------------------------
# coverage: every named engine jit site must have a manifest entry
# ---------------------------------------------------------------------------


def engine_jit_sites(path: Path = ENGINE_PATH) -> list[tuple[str, int]]:
    """(name, line) for every ``_watch(jax.jit(...), "name")`` and
    ``self._watch_jit(jax.jit(...), "name", ...)`` registration in the
    engine — pure AST, no imports, so ``--validate`` stays offline."""
    tree = ast.parse(path.read_text(), filename=str(path))
    sites = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        name = (
            fn.id
            if isinstance(fn, ast.Name)
            else fn.attr if isinstance(fn, ast.Attribute) else None
        )
        if name not in ("_watch", "_watch_jit"):
            continue
        if len(node.args) >= 2 and isinstance(node.args[1], ast.Constant) \
                and isinstance(node.args[1].value, str):
            sites.append((node.args[1].value, node.lineno))
    return sites


def coverage_failures(
    path: Path = ENGINE_PATH, manifest: dict = None
) -> list[str]:
    """Engine jit names with no manifest entry (the gate's teeth: a
    new jit site must register here before it ships)."""
    manifest = MANIFEST if manifest is None else manifest
    engine_names = {n for n, e in manifest.items() if e.kind == "engine"}
    out = []
    for name, line in engine_jit_sites(path):
        if name not in manifest:
            out.append(
                f"engine jit site '{name}' ({path.name}:{line}) has no "
                "tools/shardcheck manifest entry — register it in "
                "tools/shardcheck/manifest.py so the abstract-trace gate "
                "covers it"
            )
    seen = {n for n, _ in engine_jit_sites(path)}
    for name in sorted(engine_names - seen):
        out.append(
            f"manifest entry '{name}' (kind=engine) matches no "
            f"_watch/_watch_jit site in {path.name} — stale entry, remove "
            "or rename it"
        )
    return out


def validate_manifest(manifest: dict = None) -> list[str]:
    """Offline structural validation (no JAX): entries well-formed,
    grids declared, names unique by construction."""
    manifest = MANIFEST if manifest is None else manifest
    problems = []
    if not GRIDS:
        problems.append("no mesh grids declared")
    for gname, axes in GRIDS.items():
        for ax, n in axes:
            if not (isinstance(ax, str) and isinstance(n, int) and n >= 2):
                problems.append(f"grid {gname}: bad axis spec ({ax!r}, {n!r})")
    for name, e in manifest.items():
        if e.kind not in ("engine", "parallel"):
            problems.append(f"entry {name}: unknown kind {e.kind!r}")
        if not callable(e.build) or not callable(e.check):
            problems.append(f"entry {name}: build/check not callable")
    return problems
