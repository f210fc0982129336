"""tools/shardcheck: the device-free abstract SPMD gate.

Three contracts pinned here:

- the shipped manifest passes over every AbstractMesh grid with zero
  devices (the CI gate itself);
- the gate has TEETH: a typo'd mesh-axis name fails the abstract
  trace, and an engine jit site with no manifest entry fails the
  coverage scan;
- ``--validate`` works offline (manifest well-formedness + coverage,
  no tracing).
"""

import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from tools.shardcheck.__main__ import main, run_entry  # noqa: E402
from tools.shardcheck.manifest import (  # noqa: E402
    GRIDS,
    MANIFEST,
    Entry,
    coverage_failures,
    engine_jit_sites,
    make_ctx,
    validate_manifest,
)


@pytest.fixture(scope="module")
def tp2_ctx():
    return make_ctx("tp2")


# ---------------------------------------------------------------------------
# offline half: manifest + coverage
# ---------------------------------------------------------------------------


class TestOffline:
    def test_manifest_validates(self):
        assert validate_manifest() == []

    def test_engine_coverage_complete(self):
        assert coverage_failures() == []

    def test_engine_jit_sites_scan_finds_the_surface(self):
        names = {n for n, _ in engine_jit_sites()}
        # the named _watch/_watch_jit surface the engine dispatches
        assert {
            "decode", "verify", "sample", "argmax", "advance_state",
            "logprobs", "mark_seen", "mark_prompt", "skip_key",
            "chunk", "packed", "copy", "turbo",
        } <= names

    def test_unregistered_jit_site_fails_coverage(self, tmp_path):
        fake = tmp_path / "engine.py"
        fake.write_text(textwrap.dedent(
            """
            def build(self):
                self._decode = _watch(jax.jit(decode_step), "decode")
                self._mystery = _watch(jax.jit(mystery_step), "mystery")
                self._chunk = self._watch_jit(jax.jit(chunk), "chunk", key=1)
            """
        ))
        manifest = {
            n: MANIFEST[n] for n in ("decode", "chunk")
        }
        problems = coverage_failures(fake, manifest)
        assert len(problems) == 1
        assert "mystery" in problems[0]
        assert "manifest entry" in problems[0]

    def test_stale_manifest_entry_flagged(self, tmp_path):
        fake = tmp_path / "engine.py"
        fake.write_text('x = _watch(jax.jit(f), "decode")\n')
        manifest = {n: MANIFEST[n] for n in ("decode", "turbo")}
        problems = coverage_failures(fake, manifest)
        assert len(problems) == 1
        assert "turbo" in problems[0] and "stale" in problems[0]

    def test_cli_validate_offline_exit_zero(self):
        proc = subprocess.run(
            [sys.executable, "-m", "tools.shardcheck", "--validate"],
            cwd=REPO, capture_output=True, text=True,
        )
        assert proc.returncode == 0, proc.stderr
        assert "ok" in proc.stdout


# ---------------------------------------------------------------------------
# abstract-trace half: the gate runs device-free and has teeth
# ---------------------------------------------------------------------------


class TestAbstractTrace:
    def test_full_gate_passes_device_free(self):
        # the CI invocation: every manifest entry over every grid, on
        # CPU with no devices of any mesh shape attached
        proc = subprocess.run(
            [sys.executable, "-m", "tools.shardcheck"],
            cwd=REPO, capture_output=True, text=True,
            env={"PATH": "/usr/bin:/bin:/usr/local/bin", "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 0, proc.stderr + proc.stdout
        assert "0 failed" in proc.stdout

    def test_grids_are_the_documented_three(self):
        assert set(GRIDS) == {"tp2", "tp4", "dp2xtp2"}

    def test_cheap_entries_pass_tp2(self, tp2_ctx):
        for name in ("sample", "logprobs", "skip_key", "advance_state",
                     "copy", "ring_attention"):
            r = run_entry(MANIFEST[name], "tp2", tp2_ctx)
            assert r.status == "pass", f"{name}: {r.detail}"

    def test_axis_typo_fails_loudly(self, tp2_ctx):
        # the seeded-typo fixture: a shard_map whose specs/collective
        # name an axis no grid declares must FAIL the abstract trace
        # (on a fleet this is a trace-time error on every host)
        def build(ctx):
            import jax
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            def body(x):
                return jax.lax.psum(x, "zz")

            def fn(x):
                return shard_map(
                    body, mesh=ctx.mesh, in_specs=P("zz"), out_specs=P(),
                    check_vma=False,
                )(x)

            return fn, (ctx.f32(8),), {}

        entry = Entry("typo_fixture", "parallel", build, lambda ctx, out: None)
        r = run_entry(entry, "tp2", tp2_ctx)
        assert r.status == "fail"
        assert "zz" in r.detail

    def test_indivisible_shape_fails_loudly(self, tp2_ctx):
        # tp4 can't shard 6 KV heads evenly — the evenness check fires
        # at trace time instead of on the fleet
        from functools import partial

        def build(ctx):
            from dstack_tpu.parallel.ring_attention import ring_attention

            fn = partial(
                ring_attention, mesh=ctx.mesh, axis_name="tp", impl="xla"
            )
            q = ctx.f32(2, 8, 65, 32)  # odd seq: not divisible by tp=2
            kv = ctx.f32(2, 4, 65, 32)
            return fn, (q, kv, kv), {}

        entry = Entry("indivisible", "parallel", build, lambda ctx, out: None)
        r = run_entry(entry, "tp2", tp2_ctx)
        assert r.status == "fail"

    def test_contract_drift_fails_check(self, tp2_ctx):
        # a manifest check that the traced output violates reports a
        # failure (signature drift can't slip through as a pass)
        real = MANIFEST["logprobs"]

        def bad_check(ctx, out):
            raise AssertionError("drifted")

        entry = Entry("drifted", "engine", real.build, bad_check)
        r = run_entry(entry, "tp2", tp2_ctx)
        assert r.status == "fail" and "drifted" in r.detail

    def test_main_single_entry_grid(self, capsys):
        assert main(["--grid", "tp2", "--entry", "sample"]) == 0
        outp = capsys.readouterr().out
        assert "1 passed" in outp
