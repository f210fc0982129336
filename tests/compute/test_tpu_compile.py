"""The main path's kernels and jitted steps COMPILE for a TPU v5e —
device-free, at published Llama-3.2-1B widths.

libtpu is installed here without a chip; it compiles for a *described*
topology (``v5e:2x2``), and the Pallas TPU lowering refuses there
exactly what it would refuse on the chip: block shapes that do not tile
(8, 128), slices the layout cannot express, programs that do not fit
16 GB. Interpret-mode tests cannot see any of that (the int8-KV scale
and sink blocks of ``flash_decode`` passed them for rounds and were
refused by this compile). Nothing runs: a compile that passes is not a
chip run — ``chip_smoke.py`` is. Shapes only, never arrays (there is no
device to hold one).

Code that asks ``jax.default_backend()`` still sees the CPU, so the
kernel entry points are called directly (``impl="flash"`` /
``interpret=False``), and the one engine step whose dispatch is a
platform gate is steered in the test (``_as_tpu``).
"""

import dataclasses
import functools
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
# libtpu guards the CHIP with a /tmp lockfile, one process at a time;
# nothing here touches a chip, and pytest-xdist workers must not skip
# each other out of the topology
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from dstack_tpu.models import llama
from dstack_tpu.ops.flash import flash_attention
from dstack_tpu.ops.flash_decode import flash_decode
from dstack_tpu.serve import engine as eng

HBM_BYTES = 16e9  # one v5e chip
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu / topology not describable here
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    """A described-device executable is written to the persistent cache
    but can never be read back without a chip (each later compile would
    warn and recompile), so the cache is off around this module's cases,
    and on again behind them: the tests a worker runs afterwards (most
    of ``tests/serve``) find what its neighbours compiled. Until PR 47 it
    stayed off for the rest of the process, on PR 33's reading that a
    process which had loaded libtpu died reading a CPU executable back;
    what it read was an entry a neighbour was still writing (PR 37), and
    ``tests/conftest.py`` now writes an entry whole or not at all."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def _as_tpu(monkeypatch):
    """Steer the platform gates (``flash_supported``, ``interp =``) the
    way the chip would: they read ``jax.default_backend()``."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _compile(fn, *args, **jit_kw):
    return jax.jit(fn, **jit_kw).lower(*args).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def _fits(compiled) -> float:
    m = compiled.memory_analysis()
    total = (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes - m.alias_size_in_bytes
    )
    assert total < HBM_BYTES, f"{total / 1e9:.2f} GB does not fit 16 GB"
    return total


# ---------------------------------------------------------------------------
# ops/flash.py — training attention and the serve prefill chunk
# ---------------------------------------------------------------------------


def _flash_fwd(sds):
    q = sds((8, 32, 1024, 64), BF16)
    kv = sds((8, 8, 1024, 64), BF16)
    return lambda q, k, v: flash_attention(q, k, v, causal=True), (q, kv, kv)


def _flash_bwd(t=1024, heads=32, d=64):
    """Forward + both backward kernels. ``t=2048`` is ``finetune``'s
    default ``--seq-len``: with 1024×1024 backward blocks the compiler
    refused it for want of VMEM (found by the first chip run of the
    LoRA path; every earlier capture trained at 1024)."""

    def build(sds):
        q = sds((8, heads, t, d), BF16)
        kv = sds((8, 8, t, d), BF16)

        def loss(q, k, v):
            o = flash_attention(q, k, v, causal=True)
            return o.astype(jnp.float32).sum()

        return jax.grad(loss, argnums=(0, 1, 2)), (q, kv, kv)

    return build


def _prefill_chunk(q_offset, row):
    def build(sds):
        q = sds((1, 32, 256, 64), BF16)
        kv = sds((1, 8, row, 64), BF16)
        return (
            lambda q, k, v: flash_attention(
                q, k, v, causal=True, q_offset=q_offset
            ),
            (q, kv, kv),
        )

    return build


def _flash_window_softcap(sds):
    q = sds((2, 16, 1024, 128), BF16)
    kv = sds((2, 8, 1024, 128), BF16)
    return (
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=512, softcap=50.0
        ),
        (q, kv, kv),
    )


# ---------------------------------------------------------------------------
# ops/flash_decode.py — ragged decode/verify over the slot cache
# ---------------------------------------------------------------------------

_B, _HKV, _G, _T, _D = 16, 8, 4, 2048, 64


def _decode(rows_per_slot=1, int8=False, sinks=False):
    def build(sds):
        rows = _G * rows_per_slot
        args = [
            sds((_B, _HKV, rows, _D), BF16),
            sds((_B, _HKV, _T, _D), jnp.int8 if int8 else BF16),
            sds((_B, _HKV, _T, _D), jnp.int8 if int8 else BF16),
            sds((_B,), jnp.int32),
        ]
        names = []
        if int8:
            args += [sds((_B, _HKV, _T), jnp.float32)] * 2
            names += ["k_scale", "v_scale"]
        if sinks:
            args.append(sds((_HKV, rows), jnp.float32))
            names.append("sinks")

        def fn(q, k, v, pos, *opt):
            return flash_decode(
                q, k, v, pos, scale=_D**-0.5, rows_per_slot=rows_per_slot,
                **dict(zip(names, opt)),
            )

        return fn, tuple(args)

    return build


def _decode_stacked(layers, g, t, int8=False, sinks=False, d=128):
    """What the engine's decode scan hands the kernel where
    ``reads_live_keys`` holds: the stacked leaf [L, 16, 8, T, d] read
    in place at a traced row, the token's own K/V beside it, the block
    rule's own block (the chat, the mixed and, at ``d`` 64, with the
    keys on a block's lanes, the rag cell's shapes)."""

    def build(sds):
        kv = jnp.int8 if int8 else BF16
        args = [
            sds((_B, _HKV, g, d), BF16), sds((layers, _B, _HKV, t, d), kv),
            sds((layers, _B, _HKV, t, d), kv), sds((_B,), jnp.int32),
            sds((), jnp.int32), sds((), jnp.int32),
            sds((_B, _HKV, 1, d), BF16), sds((_B, _HKV, 1, d), BF16),
        ]
        names = ["layer", "window", "k_new", "v_new"]
        if int8:
            args += [sds((layers, _B, _HKV, t), jnp.float32)] * 2
            names += ["k_scale", "v_scale"]
        if sinks:
            args.append(sds((_HKV, g), jnp.float32))
            names.append("sinks")

        def fn(q, k, v, pos, *opt):
            return flash_decode(
                q, k, v, pos, scale=d**-0.5, softcap=30.0 if sinks else 0.0,
                **dict(zip(names, opt)),
            )

        return fn, tuple(args)

    return build


KERNELS = {
    "flash_fwd": _flash_fwd,
    "flash_bwd": _flash_bwd(),
    "flash_bwd_seq2048": _flash_bwd(t=2048),
    "flash_bwd_seq4096_d128": _flash_bwd(t=4096, heads=16, d=128),
    "prefill_chunk_256_of_2048": _prefill_chunk(256, 2048),
    "prefill_chunk_4096_of_8192": _prefill_chunk(4096, 8192),
    "flash_window_softcap_d128": _flash_window_softcap,
    "decode_bf16": _decode(),
    "decode_verify_rows5": _decode(rows_per_slot=5),
    "decode_int8_kv": _decode(int8=True),
    "decode_sinks": _decode(sinks=True),
    "decode_verify_int8_sinks": _decode(rows_per_slot=5, int8=True, sinks=True),
    "decode_stacked_new_row_chat": _decode_stacked(32, 3, 1536),
    "decode_stacked_new_row_mixed": _decode_stacked(4, 6, 8192),
    "decode_stacked_new_row_int8_sinks": _decode_stacked(32, 3, 1536, int8=True, sinks=True),
    "decode_stacked_new_row_rag": _decode_stacked(10, 4, 8192, d=64),
    "decode_stacked_new_row_rag_int8_sinks": _decode_stacked(10, 4, 8192, int8=True, sinks=True, d=64),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles(topo, name):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = KERNELS[name](sds)
    assert _has_kernel(_compile(fn, *args)), "no tpu_custom_call in program"


# ---------------------------------------------------------------------------
# the engine's jitted steps at serve shapes (max_batch 16, max_seq 2048)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _abstract_params(config):
    """The parameters' shapes, traced once a configuration: the cases of
    one model share them (a trace of ``init_params`` is not the compile
    a case is here for)."""
    return jax.eval_shape(lambda: llama.init_params(config, jax.random.key(0)))


@functools.lru_cache(maxsize=None)
def _abstract_cache(config, max_batch, max_seq, kv_quant=None):
    return jax.eval_shape(
        lambda: eng.init_cache(config, max_batch, max_seq, kv_quant=kv_quant)
    )


def _abstract_engine_state(config, sharding, max_batch=16, max_seq=2048):
    """(params, cache, sds) as shapes placed on ``sharding`` (None: the
    caller places them)."""

    def place(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
            tree,
        )

    params = _abstract_params(config)
    cache = _abstract_cache(config, max_batch, max_seq)
    return place(params), place(cache), lambda shape, dt: jax.ShapeDtypeStruct(
        shape, dt, sharding=sharding
    )


def test_decode_step_llama_1b_fits(topo):
    config = llama.LLAMA_32_1B
    params, cache, sds = _abstract_engine_state(
        config, SingleDeviceSharding(topo.devices[0])
    )
    compiled = _compile(
        lambda p, c, t, pos, m: eng.decode_step(p, c, t, pos, config, m),
        params, cache, sds((16,), jnp.int32), sds((16,), jnp.int32),
        sds((16,), jnp.bool_), donate_argnums=(1,),
    )
    _fits(compiled)


def test_decode_step_flash_kernel_llama_1b(topo, _as_tpu):
    """The ragged decode asked for (``decode_kernel="flash"``) where the
    rule would not take it (head_dim 64) inside the whole step, int8 KV:
    the variant the block-spec repair unblocks."""
    config = llama.LLAMA_32_1B
    sharding = SingleDeviceSharding(topo.devices[0])
    params, _, sds = _abstract_engine_state(config, sharding)
    cache = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        jax.eval_shape(
            lambda: eng.init_cache(config, 16, 2048, kv_quant="int8")
        ),
    )
    compiled = _compile(
        lambda p, c, t, pos, m: eng.decode_step(
            p, c, t, pos, config, m, decode_kernel="flash"
        ),
        params, cache, sds((16,), jnp.int32), sds((16,), jnp.int32),
        sds((16,), jnp.bool_), donate_argnums=(1,),
    )
    assert _has_kernel(compiled)
    _fits(compiled)


def test_prefill_packed_step_llama_1b_fits(topo):
    config = llama.LLAMA_32_1B
    params, cache, sds = _abstract_engine_state(
        config, SingleDeviceSharding(topo.devices[0])
    )
    g, c = 4, 256  # the widest default bucket: prefill_pack × prefill_chunk
    compiled = _compile(
        lambda p, ca, t, s, st, li: eng.prefill_packed_step(
            p, ca, t, s, st, li, config
        ),
        params, cache, sds((g, c), jnp.int32), sds((g,), jnp.int32),
        sds((g,), jnp.int32), sds((g,), jnp.int32), donate_argnums=(1,),
    )
    _fits(compiled)


def test_prefill_chunk_step_llama_1b_uses_kernel(topo, _as_tpu):
    """The serial prefill path (every unpacked request): on ``tpu`` the
    platform gate must land on the compiled Pallas kernel, not the XLA
    reference and not interpret mode."""
    config = llama.LLAMA_32_1B
    params, cache, sds = _abstract_engine_state(
        config, SingleDeviceSharding(topo.devices[0])
    )
    compiled = _compile(
        lambda p, ca, t, s, li: eng.prefill_chunk_step(
            p, ca, t, s, li, config, start=256
        ),
        params, cache, sds((1, 256), jnp.int32), sds((), jnp.int32),
        sds((), jnp.int32), donate_argnums=(1,),
    )
    assert _has_kernel(compiled)
    _fits(compiled)


def test_mla_moe_decode_step_deepseek_widths(topo):
    """One dense + one expert layer at DeepSeek-V2-Lite widths through
    ``_decode_step_mla`` + ``_mlp_out``'s MoE branch: ROADMAP B1's
    cells live on this path and it had never met the TPU compiler."""
    config = dataclasses.replace(llama.DEEPSEEK_V2_LITE, n_layers=2)
    assert config.mla and config.first_k_dense == 1 and config.n_experts
    params, cache, sds = _abstract_engine_state(
        config, SingleDeviceSharding(topo.devices[0])
    )
    assert "w_router" in params["layers"]  # the expert layer is there
    compiled = _compile(
        lambda p, c, t, pos, m: eng.decode_step(p, c, t, pos, config, m),
        params, cache, sds((16,), jnp.int32), sds((16,), jnp.int32),
        sds((16,), jnp.bool_), donate_argnums=(1,),
    )
    _fits(compiled)


@functools.lru_cache(maxsize=None)
def _layer_groups_config(name="dots3-note-prev-5l-ep8"):
    """A benchmark configuration of layer groups at its published
    widths: ``benchmark/configs/dots3-note-prev-5l-ep8.json`` (two
    latent shapes, an indexer, a window ring, 32 of 256 experts held),
    or ``laguna-s-2.1-13l-ep8`` (grouped-query layers of 48 | 72 query
    heads, K/V at ``max_seq`` beside K/V rings, 13 layers)."""
    import json

    from benchmark import launch

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "configs", name + ".json")) as f:
        return launch.build_llama_config(json.load(f)["llama_config"])


@pytest.mark.parametrize("program", ["decode_step", "prefill_packed_step"])
def test_layer_groups_programs_fit_at_published_widths(topo, program):
    """The longdoc cell's programs are no toys: a chip's share of the
    experts and the three caches at the cell's shapes (16 slots x 8192,
    a packed wave of 4 x 256) are over a quarter of the chip before a
    program has any ``temp``. That the masked latent attention, the
    indexer's sort, the ring writes and the held experts meet the TPU
    compiler and fit it is held where these programs are compiled
    (``test_decode_program_holds_no_second_cache[decode_step-
    layer_groups]``, ``test_prefill_program_holds_no_second_cache
    [prefill_packed_step@4-layer_groups]``: ``_fits``): until PR 46 this
    test compiled both a second time, 53 s of the suite."""
    case = {"decode_step": "decode_step", "prefill_packed_step": "prefill_packed_step@4"}
    _, args, cache = _abstract_program(topo, case[program] + "-layer_groups")
    assert args[0]["layers"]["w_gate"].shape[:2] == (1, 32)  # held, of 256
    assert set(cache) == {"ckv", "idx", "win", "moe_stats", "moe_reads"}
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(args))
    assert held > 0.25 * HBM_BYTES


def test_window_cache_argument_bytes_do_not_follow_max_seq(topo):
    """Lowered for the chip at ``--max-seq`` 8192 and 16384, the decode
    step's arguments grow by the full layers' buffers alone: the window
    layers' ring is sized by the window."""
    config = _layer_groups_config()
    sizes = {}
    for max_seq in (8192, 16384):
        params, cache, sds = _abstract_engine_state(
            config, SingleDeviceSharding(topo.devices[0]), max_seq=max_seq
        )
        lowered = jax.jit(
            lambda p, c, t, pos, m: eng.decode_step(p, c, t, pos, config, m)
        ).lower(
            params, cache, sds((16,), jnp.int32), sds((16,), jnp.int32),
            sds((16,), jnp.bool_),
        )
        args = jax.tree.leaves(lowered.in_avals)
        sizes[max_seq] = (
            sum(a.size * a.dtype.itemsize for a in args), cache["win"].shape
        )
    (small, ring_a), (large, ring_b) = sizes[8192], sizes[16384]
    assert ring_a == ring_b == (3, 16, 768, 1088)
    # two full layers x 16 slots x 8192 more rows x (576 latent + 128 index) x bf16
    assert large - small == 2 * 16 * 8192 * (576 + 128) * 2


def test_grouped_query_rings_are_the_condition_for_fitting(topo):
    """``laguna-s-2.1-13l-ep8`` at 16 x 8192: the decode step's
    arguments are the weights (9.36 GB) and a cache of 2.60 GB, the 9
    window layers' K/V in rings of 768 rows; doubling ``--max-seq``
    grows the 4 full layers' buffers alone; with every layer at
    ``max_seq`` the cache would be 6.98 GB and the arguments 16.34 GB:
    past what ``_fits`` allows a program before any ``temp`` (with the
    decode step's 0.63 GB, past the chip's 15.75 GiB = 16.91 GB too)."""
    config = _layer_groups_config("laguna-s-2.1-13l-ep8")
    sizes = {}
    for max_seq in (8192, 16384):
        params, cache, sds = _abstract_engine_state(
            config, SingleDeviceSharding(topo.devices[0]), max_seq=max_seq
        )
        lowered = jax.jit(
            lambda p, c, t, pos, m: eng.decode_step(p, c, t, pos, config, m)
        ).lower(
            params, cache, sds((16,), jnp.int32), sds((16,), jnp.int32),
            sds((16,), jnp.bool_),
        )
        args = jax.tree.leaves(lowered.in_avals)
        sizes[max_seq] = sum(a.size * a.dtype.itemsize for a in args)
        assert cache["win_k"].shape == cache["win_v"].shape == (9, 16, 8, 768, 128)
        assert cache["k"].shape == (4, 16, 8, max_seq, 128)
    row = 2 * 16 * 8 * 128 * 2  # K and V, slots, KV heads, head_dim, bf16: a layer's bytes a row
    assert sizes[16384] - sizes[8192] == 4 * 8192 * row
    weights = 2 * config.num_params()
    assert weights == 2 * 4_681_933_824
    cache_bytes = 4 * 8192 * row + 9 * 768 * row
    assert 2.59e9 < cache_bytes < 2.61e9
    assert abs(sizes[8192] - weights - cache_bytes) < 1e6  # + tokens, positions, counts
    assert weights + 13 * 8192 * row > HBM_BYTES > sizes[8192] + 1e9
    assert weights + 13 * 8192 * row + 0.63e9 > 15.75 * 2**30


# ---------------------------------------------------------------------------
# the serving programs update the donated cache in place: no second cache,
# no layer's slice copied out and back (PERF.md §6 PR 25). Two thirds of
# the decode program's device time was such copies, and no CPU test can
# see them: they are the TPU compiler's answer to a one-token write into
# a tiled buffer, and to a cache that travels through ``lax.scan`` as
# xs → ys.
# ---------------------------------------------------------------------------

_INSTR = re.compile(
    r"\s*(?:ROOT )?%(?P<name>[\w.\-]+) = \w+\[(?P<dims>[\d,]*)\]\S* "
    r"(?P<op>[\w\-]+)\("
)
# what may carry a cache leaf's shape: the buffer on its way through the
# loops, and writes into it where it lies
_PASSES = {"parameter", "get-tuple-element", "bitcast", "dynamic-update-slice"}
_COPIES = {"copy", "pad", "concatenate", "slice"}


def _cache_sized_moves(hlo: str, stacked: set, layer: set) -> list:
    """Instructions of the optimized program that copy, allocate, pad,
    concatenate or slice something with a stacked cache leaf's shape,
    and instructions outside any fusion that materialize one layer's
    slice of it (``layer`` shapes). A fusion counts by its body: an
    in-place ``dynamic-update-slice`` fusion passes unless a copy of a
    slice rides in it (the parent's ``copy_dynamic-update-slice_fusion``
    into the scan's ``ys``)."""
    bodies: dict = {}  # computation → [(name, shape, op, line)]
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(", line)
        m = _INSTR.match(line)
        if head:
            body = bodies.setdefault(head.group(1), [])
        elif m and line.startswith("  "):
            shape = tuple(int(d) for d in m["dims"].split(",") if d)
            body.append((m["name"], shape, m["op"], line))
    fused = set(re.findall(r"calls=%([\w.\-]+)", hlo))

    def writes_in_place(fusion_line):
        inner = bodies[re.search(r"calls=%([\w.\-]+)", fusion_line).group(1)]
        return any(op == "dynamic-update-slice" for _, _, op, _ in inner) and not any(
            op in _COPIES and shape in stacked | layer for _, shape, op, _ in inner
        )

    found = []
    for comp, body in bodies.items():
        if comp in fused:
            continue
        for name, shape, op, line in body:
            if op in _PASSES or shape not in stacked | layer:
                continue
            if shape in layer:
                found.append(f"{comp}: {name} = {op}: a layer's slice")
            elif op != "fusion" or not writes_in_place(line):
                found.append(f"{comp}: {name} = {op}: a whole cache leaf")
    return found


def _serving_program(case, sds, place):
    """(fn, args, cache) of one serving program at a cell's shapes:
    ``<program>[@<start | G>]-<model>``."""
    b = 16
    i32 = lambda *shape: sds(shape, jnp.int32)
    mask = sds((b,), jnp.bool_)
    program, model = case.split("-", 1)
    program, _, at = program.partition("@")
    config, max_seq, kv_quant = {
        "minitron_4b": (llama.MINITRON_4B, 1536, None),
        "deepseek_v2_lite_9l": (
            dataclasses.replace(llama.DEEPSEEK_V2_LITE, n_layers=9), 8192, None
        ),
        "int8kv-llama_1b": (llama.LLAMA_32_1B, 2048, "int8"),
        "llama_1b": (llama.LLAMA_32_1B, 2048, None),
        "layer_groups": (None, 8192, None),
        "gqa_groups": (_layer_groups_config("laguna-s-2.1-13l-ep8"), 8192, None),
        "scmoe_zero": (_layer_groups_config("longcat-flash-chat-4l-ep32"), 8192, None),
        "linear_state": (_layer_groups_config("ling-3.0-flash-vl-13l-ep8"), 8192, None),
        "conv_gqa": (_layer_groups_config("lfm2-24b-a2b-ep8"), 8192, None),
        "ssm_yoco": (_layer_groups_config("phi-4-mini-flash-reasoning"), 8192, None),
    }[model]
    if model == "ssm_yoco":  # the cell runs 32 slots
        b, mask = 32, sds((32,), jnp.bool_)
    config = config or _layer_groups_config()
    params = place(_abstract_params(config))
    cache = place(_abstract_cache(config, b, max_seq, kv_quant))
    if program == "decode_loop":
        fn = lambda p, c, t, pos, rem, act, eos: eng.decode_loop(
            p, c, t, pos, rem, act, eos, config, steps=8, max_seq=max_seq
        )
        return fn, (params, cache, i32(b), i32(b), i32(b), mask, i32(b)), cache
    if program == "verify_step":
        fn = lambda p, c, t, pos, m: eng.verify_step(p, c, t, pos, config, m)
        return fn, (params, cache, i32(b, 5), i32(b), mask), cache
    if program == "prefill_chunk_step":  # a 256-token chunk, or a short prompt's 16
        cl, start = (16, 0) if at == "short" else (256, int(at))
        fn = lambda p, c, t, s, li: eng.prefill_chunk_step(
            p, c, t, s, li, config, start=start
        )
        return fn, (params, cache, i32(1, cl), i32(), i32()), cache
    if program == "prefill_packed_step":
        g = int(at)
        fn = lambda p, c, t, s, st, li: eng.prefill_packed_step(
            p, c, t, s, st, li, config
        )
        return fn, (params, cache, i32(g, 256), i32(g), i32(g), i32(g)), cache
    fn = lambda p, c, t, pos, m: eng.decode_step(p, c, t, pos, config, m)
    return fn, (params, cache, i32(b), i32(b), mask), cache


def _abstract_program(topo, case):
    """(fn, args, cache) of ``case`` (:func:`_serving_program`) as shapes
    placed on the described chip."""
    sharding = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    place = lambda tree: jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)
    return _serving_program(case, sds, place)


def _compiled_program(topo, case):
    """(compiled, args, cache) of ``case``, compiled the way the engine
    jits it: the cache donated. No two tests of this module compile one
    case: what is held of a program is held where it is compiled."""
    fn, args, cache = _abstract_program(topo, case)
    return _compile(fn, *args, donate_argnums=(1,)), args, cache


def _leaf_shapes(leaves) -> tuple:
    """(stacked, layer) shapes of ``leaves``, as :func:`_cache_sized_moves`
    takes them."""
    return (
        {leaf.shape for leaf in leaves},
        {sh for leaf in leaves for sh in (leaf.shape[1:], (1,) + leaf.shape[1:])},
    )


def _holds_no_second_cache(
    topo, case, room: float = 0.0, known: int = 0, temp_below: float = 0.0
):
    """Compile ``case`` the way the engine jits it (cache donated) and
    assert that no operation copies, pads, concatenates or slices a
    stacked cache leaf (but ``known`` whole-leaf copies, where the
    compiler still makes them), that no layer's slice of a stacked leaf
    is materialized outside a fusion, and that ``temp`` stays under a
    quarter of the cache plus ``room`` bytes of attention scores. The
    same of a layer stack's expert matrices ([L, count, H, F]): no copy
    of the stack, no layer's [count, H, F] outside a fusion (what an
    inner loop over a scan's slice of them costs: ``moe.Row``).
    ``temp_below`` (bytes): hold ``temp`` under that and the expert
    stacks alone, for a model whose masked attention copies a layer's
    rows by design (the longdoc cell)."""
    compiled, args, cache = _compiled_program(topo, case)
    experts = [
        a for n, a in args[0]["layers"].items()
        if n in ("w_gate", "w_up", "w_down") and a.ndim == 4
    ]
    moves = _cache_sized_moves(compiled.as_text(), *_leaf_shapes(experts))
    assert not moves, moves
    temp = compiled.memory_analysis().temp_size_in_bytes
    if temp_below:
        assert temp < temp_below, f"temp {temp / 1e9:.3f} GB"
        _fits(compiled)
        return compiled
    moves = _cache_sized_moves(
        compiled.as_text(),
        *_leaf_shapes([leaf for leaf in jax.tree.leaves(cache) if leaf.ndim > 1]),
    )
    assert len(moves) <= known and not any("slice" in m for m in moves), moves
    cache_bytes = sum(
        leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(cache)
    )
    assert temp < 0.25 * cache_bytes + room, (
        f"temp {temp / 1e9:.2f} GB beside a cache of {cache_bytes / 1e9:.2f} GB"
    )
    _fits(compiled)
    return compiled


#: what the compiler holds beside the mixed cell's cache that is no
#: cache: the window layers' stacked ``wq`` and ``wk`` [9, 3072, 9216 |
#: 1024], transposed whole once a call (a layer's projection weights are
#: transposed a layer in every dense model's decode scan; of this stack
#: the compiler hoists it out of the loops: PERF.md §7)
_WINDOW_QK = 2.0 * 9 * 3072 * (9216 + 1024)

#: what the compiler holds beside the agent cell's cache that is no
#: cache: each sublayer's stacked ``wq_b`` [4, 1536, 12288] and ``wkv_b``
#: [4, 512, 16384], transposed whole once a macro-step and parked for
#: the token loop (151 + 67 MB a sublayer; the one-token programs
#: transpose a layer's at a time and hold 5-11 MB)
_SCMOE_QB = 2.0 * 2 * 4 * (1536 * 12288 + 512 * 16384)

# case → room beside a quarter of the cache
_DECODE = {
    "decode_step-minitron_4b": 0,  # the chat cell: 16 × 1536, cache 3.22 GB
    "decode_loop-deepseek_v2_lite_9l": 0,  # reasoning: 16 × 8192, latent 1.36 GB
    "verify_step-minitron_4b": 0,  # S = 5 rows a slot on the chat cell's shapes
    "verify_step-deepseek_v2_lite_9l": 0,  # the same on reasoning's shapes
    "decode_step-int8kv-llama_1b": 0,  # (int8, scale) leaves, head_dim 64
    # the mixed cell: 16 × 8192, K/V of 4 full layers 2.15 GB + 9 rings 0.45 GB
    "decode_step-gqa_groups": _WINDOW_QK,
    "decode_loop-gqa_groups": _WINDOW_QK,
    "verify_step-gqa_groups": _WINDOW_QK,
    # the agent cell: 16 × 8192, a latent row a SUBLAYER, 8 rows 1.21 GB
    "decode_step-scmoe_zero": 0,
    "decode_loop-scmoe_zero": _SCMOE_QB,
    "verify_step-scmoe_zero": 0,
}

# the longdoc cell: 16 × 8192, latent 0.44 GB. Its masked attention takes a
# layer's rows out by design (three or four layer-sized moves a program),
# so what is held is the expert stacks [1 | 3, 32, 5120, 1536] and ``temp``
# at a tenth over both trees' reading (PR 38: equal on both)
_DECODE_EXPERTS = {
    "decode_step-layer_groups": 1.1 * 0.225e9,
    "decode_loop-layer_groups": 1.1 * 0.695e9,
    "verify_step-layer_groups": 1.1 * 0.717e9,
}


#: the decode programs whose full layers are grouped-query layers over a
#: plain row buffer: on the chip they attend through ``ops/flash_decode``
#: (``reads_live_keys``), which reads the stacked leaf where it lies,
#: head_dim 128 in blocks [keys, head], head_dim 64 (a leaf with its
#: tokens on the lanes; since PR 45) in blocks [head, keys]. Every other
#: case holds no kernel: latent layers, ``verify_step`` (the einsum, no
#: cell drafts) and the int8 pair (with the kernel in the program the
#: compiler stages both whole scale leaves in fast memory a layer: two
#: ``ConcatBitcast`` of [16, 16, 8, 2048] f32 in the scan's body, which
#: this test reads as whole-leaf moves; at head_dim 128 alike)
#: → ``temp`` of the einsum form (PR 42's tree), which the kernel form
#: may not exceed (PR 43's reading: 0.0027 / 0.635 / 0.757 GB)
_READS_LIVE_KEYS = {
    "decode_step-minitron_4b": 0.003e9,
    "decode_step-gqa_groups": 0.636e9,
    "decode_loop-gqa_groups": 0.758e9,
}


@pytest.mark.parametrize("case", sorted(_DECODE) + sorted(_DECODE_EXPERTS))
def test_decode_program_holds_no_second_cache(topo, _as_tpu, case):
    """Parent readings (PR 24's tree), ``temp`` / cache: decode_step
    3.32 / 3.22 GB, decode_loop 3.06 / 1.36 GB, verify_step 3.26 / 3.22
    GB, int8 0.79 / 0.57 GB. The latent's per-layer slice, copied once
    a layer until PR 35 (151 MB at these shapes: two layer-sized moves
    a program, ``temp`` beside the 1.36 GB latent decode_loop 0.330,
    verify_step 0.196 GB on PR 33's tree), is gone since the decode
    and verify steps read the layer's keys in blocks of 512 out of the
    stacked leaf (``engine._attend_live``): no move, decode_loop 0.179,
    verify_step 0.005 GB. The grouped-query layer groups (PR 33,
    no parent: the programs are new), ``temp`` beside a cache of 2.60
    GB and arguments of 11.96: decode_step 0.63, decode_loop 0.76,
    verify_step 0.96 GB, no cache-sized move in any. The latent layers
    of two sublayers (PR 37, new programs), ``temp`` beside a cache of
    1.21 GB and arguments of 11.55: decode_step 0.005, decode_loop
    0.505, verify_step 0.011 GB, no move. With the sublayers' leaves
    stacked [L, 2, ...] in one scanned leaf (this PR's first layout)
    the layer scan's slice had a consumer a sublayer and the compiler
    copied every such weight out a layer, 1.2 GB of copies a layer a
    token: decode_step 0.307, decode_loop 0.806, verify_step 0.314 GB;
    a sub-tree a sublayer ([L, ...] leaves) has none. The held experts
    read a picked expert at a time (PR 38, ``moe._picked_experts``: a
    loop inside the scanned layer body), ``temp`` on PR 37's tree | on
    PR 38's, GB: agent decode_step 0.005 | 0.005, decode_loop 0.505 |
    0.505, verify_step 0.011 | 0.011; mixed 0.634 | 0.634, 0.757 |
    0.757, 0.963 | 0.964; longdoc 0.225 | 0.225, 0.695 | 0.695, 0.717 |
    0.717; no expert stack moved in any, and no copy of half a million
    elements or more that the parent had not. With the loop reading the
    layer scan's own slice of the stacks (PR 38's first form) the
    compiler copied the layer's three stacks out for it a layer a
    token: agent decode_step ``temp`` 1.212 GB (three stacks of 0.403),
    three times the bytes the capacity form reads; handed the whole
    stack and the layer's row (``moe.Row``) the loop takes
    ``stack[layer, expert]`` in one slice inside each product's fusion."""
    if case in _DECODE_EXPERTS:
        compiled = _holds_no_second_cache(topo, case, temp_below=_DECODE_EXPERTS[case])
    else:
        compiled = _holds_no_second_cache(topo, case, room=_DECODE[case])
    assert _has_kernel(compiled) == (case in _READS_LIVE_KEYS)
    if case in _READS_LIVE_KEYS:  # no higher than the einsum form's reading
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp <= _READS_LIVE_KEYS[case], f"temp {temp / 1e9:.3f} GB"


def _scores(g, heads, max_seq, c=256):
    """Bytes of one float32 score a query a key: what a packed wave's
    masked-einsum attention holds beside the cache (twice on the latent:
    scores and probabilities)."""
    return 4.0 * g * heads * c * max_seq


# case → (room for attention scores, whole-leaf copies the compiler still makes)
_PREFILL = {
    # the chat cell: 16 × 1536, cache 3.22 GB
    "prefill_chunk_step@0-minitron_4b": (0, 0),
    "prefill_chunk_step@256-minitron_4b": (0, 0),
    "prefill_packed_step@2-minitron_4b": (0, 0),
    "prefill_packed_step@4-minitron_4b": (0, 0),
    # reasoning: 16 × 8192, latent 1.36 GB, its tokens on the lanes
    "prefill_chunk_step@0-deepseek_v2_lite_9l": (0, 0),
    "prefill_chunk_step@256-deepseek_v2_lite_9l": (0, 0),
    "prefill_packed_step@2-deepseek_v2_lite_9l": (2 * _scores(2, 16, 8192), 0),
    "prefill_packed_step@4-deepseek_v2_lite_9l": (2 * _scores(4, 16, 8192), 0),
    # longdoc: ckv, idx and the ring, 0.45 GB; a row's index scores are
    # 64 heads × 256 × 8192. A wave of ONE row still has its ckv and win
    # leaves re-laid out around the layers (parent: 18 moves, temp 0.90 GB)
    "prefill_packed_step@1-layer_groups": (_scores(1, 64, 8192), 6),
    "prefill_packed_step@4-layer_groups": (_scores(1, 64, 8192), 0),
    # the mixed cell: K/V at max_seq beside K/V rings, 2.60 GB; a serial
    # chunk is a wave of one row; a row's scores go by in blocks of 512
    # keys (72 heads × 256 × 512 × f32)
    "prefill_chunk_step@256-gqa_groups": (_WINDOW_QK, 0),
    "prefill_packed_step@1-gqa_groups": (_WINDOW_QK, 0),
    "prefill_packed_step@4-gqa_groups": (_WINDOW_QK + _scores(4, 72, 512), 0),
    # the agent cell: 8 latent rows, 1.21 GB; a lone row goes by the
    # serial chunk at its static start (prompts to 2048: starts to
    # 1792), a wave's rows one after the other with their keys in blocks
    # of 512 (64 heads × 256 × 512 × f32: at once a row's scores are
    # 1.07 GB, ``temp`` 2.31 and 4.47 GB for G 2 and 4)
    "prefill_chunk_step@0-scmoe_zero": (0, 0),
    "prefill_chunk_step@1792-scmoe_zero": (0, 0),
    "prefill_packed_step@2-scmoe_zero": (_scores(2, 64, 512), 0),
    "prefill_packed_step@4-scmoe_zero": (_scores(4, 64, 512), 0),
    # (int8, scale) leaves, head_dim 64; a short prompt's 16 rows are
    # half an int8 tile
    "prefill_chunk_step@short-int8kv-llama_1b": (0, 0),
    "prefill_chunk_step@256-int8kv-llama_1b": (0, 0),
    # head_dim 64 in bf16: tokens on the lanes (rows own their buffer,
    # a wave's rows go unrolled)
    "prefill_chunk_step@256-llama_1b": (0, 0),
    "prefill_packed_step@4-llama_1b": (_scores(4, 32, 2048), 0),
}


@pytest.mark.parametrize("case", sorted(_PREFILL))
def test_prefill_program_holds_no_second_cache(topo, _as_tpu, case):
    """The four prefill programs on the chip's own paths (the flash
    kernel under a serial chunk). Parent readings (PR 27's tree),
    ``temp`` / cache in GB and cache-sized moves: Minitron-4B chunk
    3.23 / 3.22 (8), packed G 4 3.73 / 3.22 (10); V2-Lite 9 layers
    chunk 2.87 / 1.36 (9), packed 4.04 / 1.36 (7); layer groups G 1
    0.90 / 0.45 (18), G 4 1.12 / 0.45 (13); int8 Llama-3.2-1B chunk
    0.58 / 0.57 (22); bf16 Llama-3.2-1B chunk 1.22 / 1.07 (13), packed
    1.75 / 1.07 (4). Since PR 29: chunk steps 0.4-5 MB, packed waves
    their scores. The grouped-query layer groups (PR 33, new programs):
    chunk and G 1 0.64, G 4 0.99 GB beside 2.60, no move. The latent
    layers of two sublayers (PR 37, new programs) beside 1.21 GB: chunk
    0.016, G 2 0.156, G 4 0.299 GB, no move (G 1, which no aligned
    prompt reaches, still copies two whole leaves: not a case). Left: a short prompt's 16-row bucket on a bf16
    head_dim-64 cache (16 of a tile's 128 lanes) is still re-laid out
    whole around the loop (temp 2.15 / 1.07 GB; not a case here)."""
    room, known = _PREFILL[case]
    _holds_no_second_cache(topo, case, room=room, known=known)


# the thinking cell (PR 42): 16 × 8192, eleven linear layers' states
# [11, 16, 32, 128, 128] in float32 (0.369 GB) and tails beside two latent
# layers' rows (0.302 GB). case → ``temp`` on PR 42's tree, GB
_LINEAR_STATE = {
    "decode_step-linear_state": 0.287,
    "decode_loop-linear_state": 0.373,
    "verify_step-linear_state": 0.213,
    "prefill_chunk_step@0-linear_state": 0.281,
    "prefill_chunk_step@768-linear_state": 0.281,
    "prefill_packed_step@2-linear_state": 1.140,
    "prefill_packed_step@4-linear_state": 0.616,
}


@pytest.mark.parametrize("case", sorted(_LINEAR_STATE))
def test_linear_state_program_updates_the_state_in_place(topo, _as_tpu, case):
    """Every serving program of a model with linear layers reads a
    layer's slice of the stacked ``state`` where it lies and writes it
    back there: no copy of the 369 MB stack, no layer's 33.5 MB slice
    outside a fusion (a copy a layer would be 4 GB a token step), the
    same for the latent rows and for the expert stacks of both layer
    kinds; ``temp`` within a tenth of PR 42's reading (what it holds is
    a layer's projection weights re-laid out, as in every dense scan
    here, and a packed wave's f32 scores: G 2 takes them at once, 2 x
    32 x 256 x 8192 x 4 B twice, G 4 in key blocks). The convolution's
    tail [11, 16, 3, 12288] (13 MB) IS re-laid out around the layer
    loop, three rows being no tile: 1.2 MB a layer, left as it is."""

    compiled, args, cache = _compiled_program(topo, case)
    experts = [
        a for stack in ("layers", "linear_layers") for n, a in args[0][stack].items()
        if n in ("w_gate", "w_up", "w_down") and a.ndim == 4
    ]
    hlo = compiled.as_text()
    assert not _cache_sized_moves(hlo, *_leaf_shapes(experts))
    # (the prelude layer's row is a constant: its in-place update fusion
    # addresses the stack by a ``slice`` where the scanned layers' have a
    # ``dynamic-slice``, which the checker takes for a copy riding in it)
    moves = [
        m for m in _cache_sized_moves(hlo, *_leaf_shapes([cache["state"], cache["ckv"]]))
        if "select_dynamic-update-slice_fusion" not in m
    ]
    # the verify step's blocks-of-tokens form carries the state through a
    # scan, whose first carry is the layer's slice taken out (33.5 MB a
    # layer a call, where the call moves gigabytes): known, and no more
    known = 2 if case.startswith("verify_step") else 0
    assert len(moves) <= known and not any("whole" in m for m in moves), moves
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 1.1e9 * _LINEAR_STATE[case], f"temp {temp / 1e9:.3f} GB"
    _fits(compiled)


# the rag cell (PR 44): 16 × 8192, ten full layers' K/V [10, 16, 8, 8192, 64]
# (2 x 1.342 GB, head_dim 64: their tokens on the lanes) beside thirty conv
# layers' tails [30, 16, 2, 2048] (3.9 MB), 7.29 GB of weights, forty layers
# walked by periods. case → ``temp`` on PR 46's tree, GB (the two decode
# programs on PR 44's, with the einsum: 0.026 / 0.152; on PR 45's, with the
# kernel and every held expert read: 0.0213 / 0.1443; since PR 46 they read
# the picked experts alone: 0.0087 / 0.1209)
_CONV_GQA = {
    "decode_step-conv_gqa": 0.009,
    "decode_loop-conv_gqa": 0.121,
    "verify_step-conv_gqa": 0.299,
    "prefill_packed_step@1-conv_gqa": 0.019,
    "prefill_packed_step@2-conv_gqa": 0.009,
    "prefill_packed_step@4-conv_gqa": 0.057,
}


@pytest.mark.parametrize("case", sorted(_CONV_GQA))
def test_conv_gqa_program_holds_no_second_cache(topo, _as_tpu, case):
    """Every serving program of the whole-depth conv | grouped-query
    model fits the chip beside its 10 GB of arguments and moves neither
    a K/V leaf nor a layer's slice of one, nor an expert stack. At
    head_dim 64 the K/V leaves lie with their tokens on the lanes, and
    three forms the wider heads take re-laid BOTH leaves out whole
    (device-free, this tree before its three cures: decode_step ``temp``
    2.69 GB, decode_loop 5.50, verify_step 5.39, a lone row's wave 5.39:
    15.4 GB in all, and 5.4 GB of copies a token step): the slots'
    block writes in a loop (now unrolled, decode and verify), and ONE
    row's block write in a wave (now the whole chunk as one
    ``dynamic_update_slice``, the serial chunk's form). The walk by
    periods holds the programs at seven loops or fewer however deep the
    model (21 runs unrolled would be 21 layer scans). Since PR 45 the
    two decode programs attend through ``ops/flash_decode`` in its
    keys-on-lanes block form: handed ``swapaxes(leaf, -1, -2)``, the
    order the leaf already lies in, the Mosaic call takes a ``bitcast``
    of the donated buffer (the block form of head_dim 128 fed this leaf
    made the compiler re-lay both leaves out whole, ``temp`` 2.1-5.5
    GB); ``verify_step`` and the prefill waves keep the einsum. Since
    PR 46 the two decode programs read the held experts some live token
    picked (``moe.reads_picked_experts``: 0.644 of 8 expected at 16
    tokens over a 64-wide top-4 router, a trip 18.9 MB): one loop of
    ``moe._picked_experts`` where a scan body calls a routed layer (the
    period's full layer and its conv run, the tail's two), the three
    stacks [9 | 27 | 1, 8, 2048, 1536] whole beside the scans
    (``engine._expert_rows``) and none of them, nor a layer's [8, ., .]
    of one, copied (5.74 GB of stacks beside 10.1 GB of arguments would
    not fit); a verify grid (80 tokens: 0.994) and the waves keep the
    capacity form."""
    compiled, args, cache = _compiled_program(topo, case)
    experts = [
        a for stack in ("layers", "conv_layers") for n, a in args[0][stack].items()
        if n in ("w_gate", "w_up", "w_down") and a.ndim == 4
    ]
    hlo = compiled.as_text()
    assert not _cache_sized_moves(hlo, *_leaf_shapes(experts))
    assert not _cache_sized_moves(hlo, *_leaf_shapes([cache["k"]]))
    assert _has_kernel(compiled) == case.startswith("decode_")
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 1.1e9 * _CONV_GQA[case] + 5e6, f"temp {temp / 1e9:.3f} GB"
    assert _fits(compiled) < 10.4e9
    # layer scans: prelude, period (and its two runs), tail's two, writes
    loops = hlo.count(" while(")
    assert loops <= 8
    if case.startswith("decode_"):  # its three | four scans and the four experts' loops
        assert loops == (4 + 4 if case.startswith("decode_loop") else 3 + 4)


# the longgen cell (PR 48): 32 x 8192, the whole Phi-4-mini-flash-reasoning:
# ONE full layer's K/V [1, 32, 10, 8192, 128] (a KV pair side by side is one
# head: 2 x 0.67 GB) that seven cross layers read too, eight rings
# [8, 32, 10, 768, 128] (2 x 0.50 GB), nine float32 states [9, 32, 16, 5120]
# (94 MB, the channels on the lanes) and tails, 7.70 GB of weights, 32 layers
# walked as two folded segments. case → ``temp`` on PR 48's tree, GB
_SSM_YOCO = {
    "decode_loop-ssm_yoco": 0.369,
    "prefill_packed_step@4-ssm_yoco": 0.295,
}


@pytest.mark.parametrize("case", sorted(_SSM_YOCO))
def test_ssm_yoco_program_holds_no_second_cache(topo, _as_tpu, case):
    """The macro-step and a wave of four rows of the whole model fit the
    chip beside 10.2 GB of arguments and copy neither the K/V leaf, nor
    a ring, nor the states whole. Three forms did, device-free, before
    their cures: the state declared [d_inner, 16] was re-laid out
    [16, d_inner] and back a token step (now declared so); a run of ONE
    mamba layer, its scan of one trip inlined into ``decode_loop``'s
    token loop, had the states copied into the write after the scans and
    out of it (now a loop whose trip count the compiler cannot read,
    ``_walk_layer_groups``' ``one``); and the K/V leaf of ONE layer,
    read in the scans and written after them, was copied whole into the
    write's loop and out of it, 4 x 0.67 GB a token (``temp`` 1.77 GB;
    now the leaf rides the walk's carry, the full layer writes its row
    and the cross layers read what is held). The macro-step attends
    through ``ops/flash_decode`` at head_dim 128 (a KV pair one head);
    the wave keeps the einsum. The upper half is ONE period's two scan
    bodies, not fourteen unrolled layers: the gmu stands in the text a
    dozen times (its few fusions), as often as with two periods."""
    compiled, args, cache = _compiled_program(topo, case)
    hlo = compiled.as_text()
    whole = {cache[n].shape for n in ("k", "v", "win_k", "win_v", "state")}
    assert not _cache_sized_moves(hlo, whole, set())
    assert _has_kernel(compiled) == case.startswith("decode_")
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 1.1e9 * _SSM_YOCO[case] + 5e6, f"temp {temp / 1e9:.3f} GB"
    assert 10.1e9 < _fits(compiled) < 10.7e9
    assert hlo.count(" while(") <= 16  # two segments' scans, the slots' writes
    assert 0 < sum("dtpu.gmu" in line for line in hlo.splitlines()) <= 14


# the plain family at head_dim 64 (Llama-3.2-1B, no cell), which the rule
# reaches since PR 45. case → (``temp`` on PR 44's tree with the einsum,
# GB; whole-leaf copies the compiler made there and still makes: the
# slots' block writes in ``decode_loop``'s token loop, cured in the walk
# of layer groups only, PERF.md §7)
_PLAIN_64 = {
    "decode_step-llama_1b": (0.0007, 0),
    "decode_loop-llama_1b": (2.3185, 6),
}


@pytest.mark.parametrize("case", sorted(_PLAIN_64))
def test_plain_head_dim_64_decode_reads_live_keys_at_the_einsums_temp(
    topo, _as_tpu, case
):
    """The kernel's keys-on-lanes form in the scan of a model of ONE
    kind of layer: it is there, it moves no leaf the einsum form did
    not move, and ``temp`` stays at the parent's reading (+ 1 MB: the
    kernel's own operands in padded tiles)."""
    compiled, _, cache = _compiled_program(topo, case)
    assert _has_kernel(compiled)
    parent_temp, parent_moves = _PLAIN_64[case]
    moves = _cache_sized_moves(compiled.as_text(), *_leaf_shapes([cache["k"]]))
    assert len(moves) <= parent_moves, moves
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= 1e9 * parent_temp + 1e6, f"temp {temp / 1e9:.4f} GB"
    _fits(compiled)


# ---------------------------------------------------------------------------
# four chips: GSPMD cannot partition a Mosaic call, so under a mesh the
# flash kernel must run per shard (parallel/sharding.kernel_shard). On
# virtual CPU devices the XLA path is taken and none of this is seen.
# ---------------------------------------------------------------------------


def _described_mesh(topo, **sizes):
    from dstack_tpu.parallel.mesh import AXES

    shape = tuple(sizes.get(a, 1) for a in AXES)
    return Mesh(np.asarray(topo.devices).reshape(shape), AXES)


def test_prefill_chunk_step_tp4_uses_kernel(topo, _as_tpu):
    """``openai_server --tp 4``: the serial prefill chunk over a cache
    sharded on KV heads."""
    from dstack_tpu.parallel.sharding import default_rules, tree_shardings

    config = llama.LLAMA_32_1B
    mesh = _described_mesh(topo, tp=4)
    params, cache, _ = _abstract_engine_state(config, None)
    params = jax.tree.map(
        lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
        params,
        tree_shardings(llama.param_specs(config), mesh, default_rules()),
    )
    kv_heads = NamedSharding(mesh, P(None, None, "tp", None, None))
    cache = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=kv_heads),
        cache,
    )

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(
            shape, dt, sharding=NamedSharding(mesh, P())
        )

    compiled = _compile(
        lambda p, ca, t, s, li: eng.prefill_chunk_step(
            p, ca, t, s, li, config, start=256, mesh=mesh
        ),
        params, cache, sds((1, 256), jnp.int32), sds((), jnp.int32),
        sds((), jnp.int32), donate_argnums=(1,),
    )
    assert _has_kernel(compiled)
    _fits(compiled)


def test_decode_step_tp4_reads_live_keys(topo, _as_tpu):
    """``openai_server --tp 4`` of a grouped-query model of head_dim 128
    (the chat cell's, depth cut: the layers are one scan body): the rule
    takes the ragged decode kernel by itself, per shard of two KV heads
    under ``shard_map``, over the stacked leaf sharded on its heads."""
    from dstack_tpu.parallel.sharding import default_rules, tree_shardings

    config = dataclasses.replace(llama.MINITRON_4B, n_layers=2)
    mesh = _described_mesh(topo, tp=4)
    params, cache, _ = _abstract_engine_state(config, None, max_seq=1536)
    params = jax.tree.map(
        lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
        params,
        tree_shardings(llama.param_specs(config), mesh, default_rules()),
    )
    kv_heads = NamedSharding(mesh, P(None, None, "tp", None, None))
    cache = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=kv_heads),
        cache,
    )

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(
            shape, dt, sharding=NamedSharding(mesh, P())
        )

    compiled = _compile(
        lambda p, ca, t, pos, m: eng.decode_step(p, ca, t, pos, config, m, mesh=mesh),
        params, cache, sds((16,), jnp.int32), sds((16,), jnp.int32),
        sds((16,), jnp.bool_), donate_argnums=(1,),
    )
    assert _has_kernel(compiled)
    _fits(compiled)


@pytest.mark.parametrize("fsdp,tp", [(4, 1), (2, 2)])
def test_sharded_forward_backward_uses_kernel(topo, _as_tpu, fsdp, tp):
    """``finetune --fsdp 4`` / ``--fsdp 2 --tp 2``: the model's forward
    and backward under the mesh at published widths (depth and sequence
    cut — the Mosaic compile of long-sequence blocks is what takes
    time), flash forward and both backward kernels per shard."""
    from dstack_tpu.parallel.sharding import tree_shardings
    from dstack_tpu.train.step import batch_sharding, rules_for_mesh

    config = dataclasses.replace(llama.LLAMA_32_1B, n_layers=1)
    mesh = _described_mesh(topo, fsdp=fsdp, tp=tp)
    rules = rules_for_mesh(mesh, None)
    params = jax.tree.map(
        lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
        jax.eval_shape(lambda: llama.init_params(config, jax.random.key(0))),
        tree_shardings(llama.param_specs(config), mesh, rules),
    )
    tokens = jax.ShapeDtypeStruct(
        (8, 256), jnp.int32, sharding=batch_sharding(mesh, rules)
    )

    def loss(p, t):
        hidden = llama.forward(
            p, t, config, mesh=mesh, rules=rules, return_hidden=True
        )
        return hidden.astype(jnp.float32).mean()

    compiled = _compile(jax.grad(loss), params, tokens)
    # forward, recomputed forward (remat), dq and dk/dv
    assert compiled.as_text().count("tpu_custom_call") >= 3
    _fits(compiled)


# ---------------------------------------------------------------------------
# ring attention on a described four-chip mesh
# ---------------------------------------------------------------------------


def test_ring_attention_step_kernel_sp4(topo):
    from dstack_tpu.parallel.ring_attention import ring_attention

    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("sp",))
    seq = NamedSharding(mesh, P(None, None, "sp", None))
    q = jax.ShapeDtypeStruct((1, 32, 4096, 64), BF16, sharding=seq)
    kv = jax.ShapeDtypeStruct((1, 8, 4096, 64), BF16, sharding=seq)
    compiled = _compile(
        lambda q, k, v: ring_attention(
            q, k, v, mesh=mesh, causal=True, impl="pallas"
        ),
        q, kv, kv,
    )
    text = compiled.as_text()
    assert "tpu_custom_call" in text  # the per-step flash kernel
    assert "collective-permute" in text  # KV rotating around the ring
