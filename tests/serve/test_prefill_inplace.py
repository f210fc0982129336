"""The prefill programs write the donated KV cache in place.

``prefill_chunk_step`` / ``prefill_packed_step`` (and their latent
twins) carry the STACKED cache through the layer loop: a serial chunk is
one ``dynamic_update_slice`` at its static start, a packed wave's rows
are tile-aligned blocks read, selected and written back
(``engine._cwrite_rows`` with the wave's slots), and attention reads the
slots' rows out of the stacked buffers. Until PR 29 each layer's slice
went through ``lax.scan`` as xs → ys and a wave's rows were a
``mode="drop"`` scatter. What the TPU compiler makes of the new form is
``tests/compute/test_tpu_compile.py``'s to check; here, on the CPU at
small widths and float32: the logits and the WHOLE cache are those of
the old form, which this file keeps as the plain reference (the old
scan drivers, the scatter write, the gathered read: ``_ref_*``), a
packed wave equals the serial chunks, and no byte moves that a chunk
does not own.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dstack_tpu.models import llama
from dstack_tpu.serve import engine as eng
from tests.shared import init_params, jitted

HERE = os.path.dirname(os.path.abspath(__file__))

B, TMAX, CHUNK = 4, 44, 16  # 44: no tile's multiple, the last block is a clamped one


def _layer_groups():
    """The benchmark's toy configuration of layer groups: an indexer
    that bites past 16 keys, a window ring of 32 rows that a 44-token
    prompt wraps, a share of the experts."""
    from benchmark import launch

    path = os.path.join(
        HERE, "..", "benchmark", "data", "groups", "configs", "tiny-mla-groups.json"
    )
    with open(path) as f:
        return dataclasses.replace(
            launch.build_llama_config(json.load(f)["llama_config"]),
            dtype=jnp.float32,
        )


CASES = {
    "dense": lambda: (llama.LLAMA_TINY, None),
    "dense-int8-kv": lambda: (llama.LLAMA_TINY, "int8"),
    # five layers in groups of three: one scan step and a tail of two
    "dense-grouped-tail": lambda: (
        dataclasses.replace(
            llama.LLAMA_TINY, n_layers=5, sliding_window=8, sliding_pattern=3
        ),
        None,
    ),
    "latent": lambda: (llama.MLA_TINY, None),  # a dense prelude, then experts
    "latent-indexer-ring": lambda: (_layer_groups(), None),
}


@pytest.fixture(scope="module", params=sorted(CASES))
def model(request):
    config, kv_quant = CASES[request.param]()
    params = init_params(config, 3)
    return request.param, config, kv_quant, params


# ---------------------------------------------------------------------------
# the plain reference: the forms of the tree before PR 29. The drivers
# hand each layer's slice of the cache through ``lax.scan`` as xs → ys
# (to the engine's per-layer code as a one-layer stack, layer 0); a
# wave's rows are scattered with out-of-range positions dropped, and
# read back by one gather.
# ---------------------------------------------------------------------------


def _ref_scan_layers_kv(params, cache, x, one_layer, c):
    """The dense driver as it was: the cache rides the grouped scan as
    xs, comes back as ys, and an unrolled tail's rows are appended."""
    ck_p, cv_p = eng._cache_pack(cache)
    g, windows, xs_main, xs_tail = llama.grouped_scan_layout(
        c, {"layer": params["layers"], "ck": ck_p, "cv": cv_p}
    )
    nopes = llama.layer_nope(c)
    stack1 = lambda t: jax.tree.map(lambda a: a[None], t)
    first = lambda t: jax.tree.map(lambda a: a[0], t)
    tree_stack = lambda lst: jax.tree.map(lambda *xs: jnp.stack(xs), *lst)

    def layer_of(x, sub, window, nope):
        x, ck, cv = one_layer(
            x, stack1(sub["ck"]), stack1(sub["cv"]), sub["layer"], 0, window, nope
        )
        return x, first(ck), first(cv)

    def group_fn(x, group):
        cks, cvs = [], []
        for i in range(g):
            x, ck, cv = layer_of(
                x, llama.sublayer(group, i, g), windows[i], nopes[i]
            )
            cks.append(ck)
            cvs.append(cv)
        if g == 1:
            return x, (cks[0], cvs[0])
        return x, (tree_stack(cks), tree_stack(cvs))

    x, (ks, vs) = jax.lax.scan(group_fn, x, xs_main)
    r = c.n_layers % g if g > 1 else 0
    if g > 1:  # [L'/g, g, ...] → [L', ...]
        unflat = lambda t: jax.tree.map(
            lambda a: a.reshape((c.n_layers - r,) + a.shape[2:]), t
        )
        ks, vs = unflat(ks), unflat(vs)
    if xs_tail is not None:
        tks, tvs = [], []
        for j in range(r):
            x, ck, cv = layer_of(
                x, jax.tree.map(lambda a: a[j], xs_tail),
                windows[c.n_layers - r + j], nopes[c.n_layers - r + j],
            )
            tks.append(ck)
            tvs.append(cv)
        cat = lambda a, t: jax.tree.map(
            lambda x1, x2: jnp.concatenate([x1, x2], axis=0), a, t
        )
        ks, vs = cat(ks, tree_stack(tks)), cat(vs, tree_stack(tvs))
    return x, eng._cache_unpack(ks, vs)


def _ref_mla_scan(params, cache, x, one_layer, c):
    """The latent driver as it was (``_mla_scan``): the prelude
    unrolled over ``cache[n][j]``, every other run one scan with its
    layers' slices of its group's buffers as xs → ys, the pieces
    stacked and concatenated afterwards; the routing counts ride the
    carry."""
    k_dense = c.first_k_dense
    stats = {n: cache[n] for n in eng._COUNTS if n in cache}
    out = {n: [] for n in cache if n not in eng._COUNTS}

    def layer_of(x, stats, layer, rows, run):
        one = {n: a[None] for n, a in rows.items()}
        x, one = one_layer(x, layer, {**one, **stats}, 0, run)
        return x, {n: one[n] for n in stats}, {n: one[n][0] for n in rows}

    for run in llama.layer_runs(c):
        if run.window:
            names = ("win",)
        else:
            names = ("ckv", "idx") if "idx" in cache else ("ckv",)
        if run.key == "dense_layers":
            pre = {n: [] for n in names}
            for j in range(run.lo, run.hi):
                lyr = jax.tree.map(lambda a: a[j], params["dense_layers"])
                x, stats, r = layer_of(
                    x, stats, lyr, {n: cache[n][j] for n in names}, run
                )
                for n in names:
                    pre[n].append(r[n])
            for n in names:
                out[n].append(jnp.stack(pre[n]))
            continue
        base = 0 if run.window else k_dense

        def scan_fn(carry, layer_and_rows, run=run):
            x, stats, rows = layer_of(*carry, *layer_and_rows, run)
            return (x, stats), rows

        (x, stats), ys = jax.lax.scan(
            scan_fn, (x, stats), (
                llama.run_slice(params[run.key], run),
                {n: cache[n][base + run.lo : base + run.hi] for n in names},
            ),
        )
        for n in names:
            out[n].append(ys[n])
    bufs = {n: jnp.concatenate(v, axis=0) for n, v in out.items()}
    return x, {**bufs, **stats}


def _ref_cwrite_rows(
    ckv, layer, positions, write_mask, new, axis=1, unroll=False,
    slots=None, counts=None,
):
    """The scatter write: each real token at its own position of its
    slot's row, a position that is masked, past its row's real tokens
    or past the end of the cache out of range and dropped."""
    if isinstance(ckv, tuple):
        return tuple(
            _ref_cwrite_rows(
                c, layer, positions, write_mask, n, axis, unroll, slots, counts
            )
            for c, n in zip(ckv, new)
        )
    assert new.ndim == ckv.ndim - 1, "the prefill programs write one layer's rows"
    t_ax = 1 + axis  # of new [G, *slot]
    tmax, s = ckv.shape[1 + t_ax], new.shape[t_ax]
    slots = jnp.arange(new.shape[0]) if slots is None else slots
    counts = jnp.full(new.shape[:1], s) if counts is None else counts
    real = write_mask[:, None] & (jnp.arange(s)[None, :] < counts[:, None])
    at = jnp.where(real, positions[:, None] + jnp.arange(s)[None, :], tmax)
    rows = jnp.moveaxis(new, t_ax, 1)  # [G, S, ...]: tokens beside their positions
    if axis == 0:  # [L, B, T, R]
        return ckv.at[layer, slots[:, None], at].set(rows, mode="drop")
    # values [L, B, H, T, D], scales [L, B, H, T]
    return ckv.at[layer, slots[:, None], :, at].set(rows, mode="drop")


def _ref_stacked_write(
    cache, name, li, positions, write_mask, new, slots=None, counts=None
):
    """The latent's scatter write; position ``p`` of a window ring at
    row ``p`` modulo its rows."""
    buf = cache[name]
    s, tmax = new.shape[1], buf.shape[2]
    real = write_mask[:, None] & (jnp.arange(s)[None, :] < counts[:, None])
    at = positions[:, None] + jnp.arange(s)[None, :]
    in_range = at < tmax
    if name == "win":
        at, in_range = jnp.mod(at, tmax), True
    at = jnp.where(real & in_range, at, tmax)
    return {**cache, name: buf.at[li, slots[:, None], at].set(new, mode="drop")}


def _ref_cread_rows(ckv, layer, slots, dtype):
    """The gathered read: the layer's slice, then the wave's rows."""
    if isinstance(ckv, tuple):
        return eng.kv_dequant(
            jnp.take(ckv[0][layer], slots, axis=0),
            jnp.take(ckv[1][layer], slots, axis=0), dtype,
        )
    return jnp.take(ckv[layer], slots, axis=0)


@pytest.fixture
def old_form(monkeypatch):
    """The engine's prefill programs traced over the reference forms."""
    monkeypatch.setattr(eng, "_scan_layers_kv", _ref_scan_layers_kv)
    monkeypatch.setattr(eng, "_mla_layers_inplace", _ref_mla_scan)
    monkeypatch.setattr(eng, "_cwrite_rows", _ref_cwrite_rows)
    monkeypatch.setattr(eng, "_stacked_write", _ref_stacked_write)
    monkeypatch.setattr(eng, "_cread_rows", _ref_cread_rows)


# ---------------------------------------------------------------------------
# the calls
# ---------------------------------------------------------------------------


def _noise_cache(config, kv_quant):
    """A cache full of seeded noise: what a chunk leaves alone is then
    told from what it wrote, byte for byte."""
    rng = np.random.default_rng(5)
    cache = eng.init_cache(config, B, TMAX, kv_quant=kv_quant, chunk=CHUNK)

    def fill(name, a):
        if a.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, a.shape), jnp.int8)
        if name.endswith("_s"):
            return jnp.asarray(rng.uniform(0.001, 0.02, a.shape), a.dtype)
        if a.dtype == jnp.int32:  # the routing counts
            return a
        return jnp.asarray(rng.normal(size=a.shape) * 0.5, a.dtype)

    return {n: fill(n, a) for n, a in cache.items()}


def _tokens(config, n, seed):
    rng = np.random.default_rng(seed)
    return [int(t) for t in rng.integers(1, config.vocab_size, n)]


class _Programs:
    """One compiled program a (form, step, configuration, start), as the
    engine holds them, shared by the tests of a case; traced on first
    use, over whatever forms the module has then (``form`` keeps the
    engine's own and the reference's apart)."""

    def __init__(self, config, form="engine"):
        self._jitted = lambda fn, **static: jitted(fn, form, config=config, **static)

    def serial(self, params, cache, toks, slot, start, n_real):
        row = toks + [0] * (CHUNK - len(toks))
        return self._jitted(eng.prefill_chunk_step, start=start)(
            params, cache, jnp.asarray([row], jnp.int32),
            jnp.asarray(slot, jnp.int32), jnp.asarray(n_real - 1, jnp.int32),
        )

    def packed(self, params, cache, rows):
        """``rows``: (tokens, slot, start, real tokens; 0 = a pad row)."""
        return self._jitted(eng.prefill_packed_step)(
            params, cache,
            jnp.asarray([t + [0] * (CHUNK - len(t)) for t, *_ in rows], jnp.int32),
            jnp.asarray([r[1] for r in rows], jnp.int32),
            jnp.asarray([r[2] for r in rows], jnp.int32),
            jnp.asarray([r[3] - 1 for r in rows], jnp.int32),
        )


def _script(config, kv_quant, params, form="engine") -> dict:
    """A slot's life and two waves on a cache of noise → every logits
    array and the whole cache at two points."""
    run, out = _Programs(config, form), {}
    cache = _noise_cache(config, kv_quant)
    a, b, c, d = (_tokens(config, n, s) for n, s in ((42, 1), (16, 2), (21, 3), (16, 4)))
    # slot 1: a whole chunk, then a short last one (10 real tokens)
    out["logits.serial@0"], cache = run.serial(params, cache, a[:16], 1, 0, 16)
    out["logits.serial@16"], cache = run.serial(params, cache, a[16:26], 1, 16, 10)
    out.update({f"cache.serial.{n}": v for n, v in cache.items()})
    # a wave at unequal starts: a fresh row, a short resumed row, a row
    # whose last token is the last position of max_seq, and the engine's
    # pad row (last_ix −1, on a slot a real row writes)
    out["logits.wave4"], cache = run.packed(params, cache, [
        (b, 0, 0, 16), (c[16:], 2, 16, 5), (d[:12], 3, TMAX - 12, 12), ([], 0, 0, 0),
    ])
    # slot 1 goes on from an odd start, over the ring's end (row 32),
    # beside a row that starts where the cache ends (nothing to write)
    out["logits.wave2"], cache = run.packed(params, cache, [
        (a[26:42], 1, 26, 16), (d, 2, TMAX, 16),
    ])
    out.update({f"cache.waves.{n}": v for n, v in cache.items()})
    return {k: np.asarray(v) for k, v in out.items()}


@pytest.fixture
def old_script(model, old_form):
    _, config, kv_quant, params = model
    return _script(config, kv_quant, params, form="reference")


def test_prefill_returns_what_the_old_form_returned(model, old_script, monkeypatch):
    """Logits and every cache leaf, WHOLE, after serial chunks and
    packed waves against the old form's, call for call (the serial form
    writes a padded chunk whole, as it did; the packed form its real
    tokens): a short last chunk, a pad row, rows at unequal starts, a
    position at the end of ``max_seq``, a chunk whose ring write wraps."""
    _, config, kv_quant, params = model
    monkeypatch.undo()  # the engine's own forms again
    assert eng._cread_rows is not _ref_cread_rows
    got = _script(config, kv_quant, params)
    assert sorted(got) == sorted(old_script)
    for name, want in old_script.items():
        if name.startswith("cache"):
            # the same arithmetic on the same values: bit for bit, the
            # int8 values and their float32 scales too
            assert got[name].tobytes() == want.tobytes(), name
        else:  # to a rounding of the reductions the compiler fuses otherwise
            np.testing.assert_allclose(got[name], want, rtol=2e-5, atol=2e-5, err_msg=name)


def test_packed_wave_at_unequal_starts_equals_the_serial_chunks(model):
    _, config, kv_quant, params = model
    run = _Programs(config)
    a, b = _tokens(config, 32, 7), _tokens(config, 16, 8)
    base = _noise_cache(config, kv_quant)
    _, base = run.serial(params, base, a[:16], 2, 0, 16)  # slot 2's first chunk
    l_a, serial = run.serial(params, base, a[16:], 2, 16, 16)
    l_b, serial = run.serial(params, serial, b, 0, 0, 16)
    l_w, wave = run.packed(params, base, [(b, 0, 0, 16), (a[16:], 2, 16, 16)])
    tol = 2e-2 if kv_quant else 2e-4
    np.testing.assert_allclose(np.asarray(l_w[0]), np.asarray(l_b[0]), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(l_w[1]), np.asarray(l_a[0]), rtol=tol, atol=tol)
    for name in wave:
        if name == "moe_reads":  # counted a routed CALL: one wave, two chunks
            continue
        got, want = np.asarray(wave[name]), np.asarray(serial[name])
        if got.dtype == np.int8:  # a value on a rounding edge may land one step off
            assert np.abs(got.astype(int) - want.astype(int)).max() <= 1, name
        else:
            np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5, err_msg=name)


def test_padding_and_pad_rows_keep_every_byte_past_the_real_tokens(model):
    """A packed row's padding, a pad row (``last_ix`` −1, on the slot a
    real row writes, as the engine pads) and a row at ``Tmax`` write
    nothing: byte for byte, in every layer of every leaf."""
    _, config, kv_quant, params = model
    before = _noise_cache(config, kv_quant)
    keep = {n: np.asarray(a) for n, a in before.items()}
    toks = _tokens(config, 16, 9)
    _, after = _Programs(config).packed(params, before, [
        (toks[:5], 2, 16, 5), (toks, 3, TMAX, 16), ([], 2, 0, 0), ([], 1, 0, 0),
    ])
    for name, leaf in after.items():
        if name in eng._COUNTS:
            continue
        leaf, was = np.asarray(leaf), keep[name]
        assert leaf.dtype == was.dtype
        ring = name == "win"
        t_ax = 2 if name in ("ckv", "idx", "win") else 3  # [L,B,T,R] | [L,B,H,T(,D)]
        for slot in (0, 1) + (() if ring else (3,)):  # untouched slots; the ring has no end
            assert leaf[:, slot].tobytes() == was[:, slot].tobytes(), (name, slot)
        wrote = np.take(leaf[:, 2], range(16, 21), axis=t_ax - 1)
        assert not np.array_equal(wrote, np.take(was[:, 2], range(16, 21), axis=t_ax - 1))
        rest = [t for t in range(leaf.shape[t_ax]) if not 16 <= t < 21]
        assert (
            np.take(leaf[:, 2], rest, axis=t_ax - 1).tobytes()
            == np.take(was[:, 2], rest, axis=t_ax - 1).tobytes()
        ), name


def test_first_tokens_logits_are_the_full_forwards(model):
    """Chunked through a clean cache, a prompt's last-token logits are
    ``llama.forward``'s (the ring wrapped, the indexer biting)."""
    case, config, kv_quant, params = model
    run = _Programs(config)
    toks = _tokens(config, 42, 11)
    cache = eng.init_cache(config, B, TMAX, kv_quant=kv_quant, chunk=CHUNK)
    for start in (0, 16):
        _, cache = run.serial(params, cache, toks[start : start + 16], 3, start, 16)
    logits, _ = run.packed(params, cache, [(toks[32:], 3, 32, 10), ([], 0, 0, 0)])
    full = jitted(llama.forward, config=config)(params, jnp.asarray([toks], jnp.int32))
    ref = np.asarray(full[0, -1])
    tol = 0.05 if kv_quant else 5e-2 if case == "latent-indexer-ring" else 2e-3
    assert np.abs(np.asarray(logits[0]) - ref).max() < tol * max(np.abs(ref).max(), 1.0)


@pytest.mark.parametrize("leaf", ["values", "int8-pair", "latent"])
def test_a_waves_block_write_is_the_scatter_write(leaf):
    """``_cwrite_rows`` with a wave's ``slots`` and ``counts`` against
    the scatter it replaced, bit for bit, inside one layer of the stack:
    slots out of order, two rows on one slot (a real row, then the pad
    row the engine puts on slot 0), a short row, a row over the end of
    the cache, one past it, one masked."""
    layers, pool, heads, dim, tmax, s = 3, 6, 2, 8, 44, 16
    slots = np.array([4, 0, 2, 5, 1, 0, 3])
    pos = np.array([16, 0, 7, tmax - 5, tmax, 0, 20])
    counts = np.array([16, 16, 5, 16, 16, 0, 9])
    mask = np.array([True, True, True, True, True, False, False])
    rng = np.random.default_rng(3)
    g = len(slots)
    if leaf == "latent":
        buf = jnp.asarray(rng.normal(size=(layers, pool, tmax, dim)), jnp.float32)
        new = jnp.asarray(rng.normal(size=(g, s, dim)), jnp.float32)
        axis = 0
    else:
        shape = (layers, pool, heads, tmax, dim)
        new = jnp.asarray(rng.normal(size=(g, heads, s, dim)), jnp.float32)
        axis = 1
        if leaf == "values":
            buf = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
            new = new.astype(jnp.bfloat16)
        else:
            buf = (
                jnp.asarray(rng.integers(-127, 128, shape), jnp.int8),
                jnp.asarray(rng.uniform(0.01, 1.0, shape[:-1]), jnp.float32),
            )
    args = (
        buf, 1, jnp.asarray(pos), jnp.asarray(mask), eng._cstored(new, buf),
    )
    kw = dict(axis=axis, slots=jnp.asarray(slots), counts=jnp.asarray(counts))
    want = _ref_cwrite_rows(*args, **kw)
    for unroll in (False, True):
        got = eng._cwrite_rows(*args, unroll=unroll, **kw)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), unroll
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(buf)):
        assert np.asarray(a).tobytes() != np.asarray(b).tobytes()  # and it wrote
