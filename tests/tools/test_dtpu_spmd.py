"""SPMD lint layer: flow's axis-vocabulary/binding index + DTPU012-014.

Fixture trees mirror the real ``parallel/`` idiom — axis names thread
through parameters with string defaults (``axis_name: str = "sp"``)
into factory closures and shard_map bodies — because the rules' whole
point is resolving that flow interprocedurally. One fixture seeds the
axis-name typo the shardcheck gate also catches dynamically
(tests/tools/test_shardcheck.py::test_axis_typo_fails_loudly): the
static and abstract-trace gates must agree that shape is fatal.
"""

import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from tools.dtpu_lint.core import all_rules, run_lint  # noqa: E402
from tools.dtpu_lint.flow import (  # noqa: E402
    axis_vocabulary,
    axis_vocabulary_from_source,
    get_spmd_flow,
)

MESH_PY = """
import jax

AXES = ("dp", "pp", "fsdp", "ep", "sp", "tp")

def make_mesh():
    return None
"""


def _tree(tmp_path: Path, files: dict) -> Path:
    files.setdefault("dstack_tpu/parallel/mesh.py", MESH_PY)
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return tmp_path


def _run_rule(rule_id: str, root: Path) -> list:
    return sorted(
        all_rules()[rule_id].check_project(root),
        key=lambda f: (f.path, f.line),
    )


# ---------------------------------------------------------------------------
# axis vocabulary
# ---------------------------------------------------------------------------


class TestAxisVocabulary:
    def test_extracts_module_level_axes_tuple(self):
        assert axis_vocabulary_from_source(MESH_PY) == frozenset(
            {"dp", "pp", "fsdp", "ep", "sp", "tp"}
        )

    def test_real_repo_vocabulary(self):
        # the shipped mesh.py is the source of truth the rules check
        # against — a rename there must flow into the lint vocabulary
        assert axis_vocabulary(REPO) == frozenset(
            {"dp", "pp", "fsdp", "ep", "sp", "tp"}
        )

    def test_missing_mesh_file_means_empty_vocab(self, tmp_path):
        assert axis_vocabulary(tmp_path) == frozenset()

    def test_no_vocab_disables_dtpu012(self, tmp_path):
        root = tmp_path
        p = root / "dstack_tpu/parallel/ring.py"
        p.parent.mkdir(parents=True)
        p.write_text("import jax.lax as lax\ndef f(x):\n    return lax.psum(x, 'zz')\n")
        assert _run_rule("DTPU012", root) == []


# ---------------------------------------------------------------------------
# DTPU012 — axis names must be literals from the vocabulary
# ---------------------------------------------------------------------------


class TestDTPU012:
    def test_clean_param_default_idiom(self, tmp_path):
        # the real library shape: default "sp", factory closure, body
        root = _tree(tmp_path, {
            "dstack_tpu/parallel/ring.py": """
                import jax
                import jax.lax as lax
                from jax import shard_map
                from jax.sharding import PartitionSpec as P

                def _make_ring(sp, axis_name):
                    def local_fn(q):
                        return lax.psum(q, axis_name)
                    return local_fn

                def ring(q, *, mesh, axis_name: str = "sp"):
                    local_fn = _make_ring(2, axis_name)
                    spec = P(None, None, axis_name, None)
                    return shard_map(
                        local_fn, mesh=mesh, in_specs=(spec,),
                        out_specs=spec, check_vma=False,
                    )(q)
            """,
        })
        assert _run_rule("DTPU012", root) == []

    def test_literal_typo_in_collective(self, tmp_path):
        root = _tree(tmp_path, {
            "dstack_tpu/parallel/bad.py": """
                import jax.lax as lax

                def f(x):
                    return lax.psum(x, "tpp")
            """,
        })
        (f,) = _run_rule("DTPU012", root)
        assert "tpp" in f.message and "declared mesh axis" in f.message

    def test_typo_param_default_reported_at_definition(self, tmp_path):
        # the seeded axis-name-typo fixture: default "zz" flows into
        # the collective; the finding lands on the parameter default
        # (where the bad literal ENTERS), not the psum ten frames down
        root = _tree(tmp_path, {
            "dstack_tpu/parallel/ulysses.py": """
                import jax.lax as lax
                from jax import shard_map
                from jax.sharding import PartitionSpec as P

                def ulysses(q, *, mesh, axis_name: str = "zz"):
                    def local_fn(x):
                        return lax.all_to_all(x, axis_name, 1, 2)
                    spec = P(None, None, axis_name, None)
                    return shard_map(
                        local_fn, mesh=mesh, in_specs=(spec,),
                        out_specs=spec, check_vma=False,
                    )(q)
            """,
        })
        findings = _run_rule("DTPU012", root)
        assert findings, "typo'd default must be flagged"
        assert all("zz" in f.message for f in findings)
        # anchored at the def line (param default), same line for all
        assert {f.line for f in findings} == {6}

    def test_call_site_literal_reported_at_call_site(self, tmp_path):
        root = _tree(tmp_path, {
            "dstack_tpu/parallel/ring.py": """
                import jax.lax as lax

                def ring(q, axis_name: str = "sp"):
                    return lax.psum(q, axis_name)

                def caller(q):
                    return ring(q, axis_name="tipo")
            """,
        })
        findings = _run_rule("DTPU012", root)
        assert any("tipo" in f.message and f.line == 8 for f in findings), (
            findings
        )

    def test_shard_map_spec_literal_typo(self, tmp_path):
        root = _tree(tmp_path, {
            "dstack_tpu/parallel/pipe.py": """
                import jax.lax as lax
                from jax import shard_map
                from jax.sharding import PartitionSpec as P

                def apply(x, *, mesh):
                    def body(x):
                        return lax.psum(x, "pp")
                    return shard_map(
                        body, mesh=mesh, in_specs=(P("ppp"),),
                        out_specs=P(), check_vma=False,
                    )(x)
            """,
        })
        findings = _run_rule("DTPU012", root)
        assert any("ppp" in f.message for f in findings)

    def test_noqa_suppresses_with_reason(self, tmp_path):
        root = _tree(tmp_path, {
            "dstack_tpu/parallel/bad.py": """
                import jax.lax as lax

                def f(x):
                    # dtpu: noqa[DTPU012] exercised only under the test mesh
                    return lax.psum(x, "tpp")
            """,
        })
        assert _run_rule("DTPU012", root) == []


# ---------------------------------------------------------------------------
# DTPU013 — SPMD purity
# ---------------------------------------------------------------------------


class TestDTPU013:
    def test_host_sync_reachable_from_body_interprocedural(self, tmp_path):
        root = _tree(tmp_path, {
            "dstack_tpu/parallel/ring.py": """
                import jax
                import jax.lax as lax
                from jax import shard_map
                from jax.sharding import PartitionSpec as P

                def _helper(x):
                    return float(x.sum().item())

                def ring(q, *, mesh):
                    def local_fn(x):
                        s = _helper(x)
                        return lax.psum(x * s, "sp")
                    return shard_map(
                        local_fn, mesh=mesh, in_specs=(P("sp"),),
                        out_specs=P("sp"), check_vma=False,
                    )(q)
            """,
        })
        findings = _run_rule("DTPU013", root)
        assert any(".item()" in f.message for f in findings), findings

    def test_branch_on_per_shard_value_in_body(self, tmp_path):
        root = _tree(tmp_path, {
            "dstack_tpu/parallel/ring.py": """
                import jax.lax as lax
                from jax import shard_map
                from jax.sharding import PartitionSpec as P

                def ring(q, *, mesh):
                    def local_fn(x):
                        if x[0] > 0:
                            return lax.psum(x, "sp")
                        return x
                    return shard_map(
                        local_fn, mesh=mesh, in_specs=(P("sp"),),
                        out_specs=P("sp"), check_vma=False,
                    )(q)
            """,
        })
        findings = _run_rule("DTPU013", root)
        assert any("branch on per-shard value" in f.message for f in findings)

    def test_branch_on_static_shape_is_clean(self, tmp_path):
        root = _tree(tmp_path, {
            "dstack_tpu/parallel/ring.py": """
                import jax.lax as lax
                from jax import shard_map
                from jax.sharding import PartitionSpec as P

                def ring(q, *, mesh):
                    def local_fn(x):
                        if x.shape[0] > 1:
                            return lax.psum(x, "sp")
                        return lax.psum(x * 2, "sp")
                    return shard_map(
                        local_fn, mesh=mesh, in_specs=(P("sp"),),
                        out_specs=P("sp"), check_vma=False,
                    )(q)
            """,
        })
        assert _run_rule("DTPU013", root) == []

    def test_callback_flagged_in_traced_code(self, tmp_path):
        root = _tree(tmp_path, {
            "dstack_tpu/parallel/ring.py": """
                import jax
                import jax.lax as lax

                def collective_user(x):
                    jax.debug.callback(print, x)
                    return lax.psum(x, "sp")
            """,
        })
        findings = _run_rule("DTPU013", root)
        assert any("callback" in f.message for f in findings)


# ---------------------------------------------------------------------------
# DTPU014 — collective discipline
# ---------------------------------------------------------------------------


class TestDTPU014:
    def test_conditional_collective_interprocedural(self, tmp_path):
        # the body's HELPER runs the psum under a data-dependent
        # branch: members that skip it deadlock the rest of the fleet
        root = _tree(tmp_path, {
            "dstack_tpu/parallel/ring.py": """
                import jax.lax as lax
                from jax import shard_map
                from jax.sharding import PartitionSpec as P

                def _reduce_if_hot(x):
                    if x[0] > 0:
                        return lax.psum(x, "sp")
                    return x

                def ring(q, *, mesh):
                    def local_fn(x):
                        return _reduce_if_hot(x)
                    return shard_map(
                        local_fn, mesh=mesh, in_specs=(P("sp"),),
                        out_specs=P("sp"), check_vma=False,
                    )(q)
            """,
        })
        findings = _run_rule("DTPU014", root)
        assert any(
            "data-dependent Python control flow" in f.message
            for f in findings
        ), findings

    def test_unconditional_collective_is_clean(self, tmp_path):
        root = _tree(tmp_path, {
            "dstack_tpu/parallel/ring.py": """
                import jax.lax as lax
                from jax import shard_map
                from jax.sharding import PartitionSpec as P

                def ring(q, *, mesh):
                    def local_fn(x):
                        return lax.psum(x, "sp")
                    return shard_map(
                        local_fn, mesh=mesh, in_specs=(P("sp"),),
                        out_specs=P("sp"), check_vma=False,
                    )(q)
            """,
        })
        assert _run_rule("DTPU014", root) == []

    def test_body_axis_not_covered_by_specs(self, tmp_path):
        # body psums over "tp" but the shard_map's specs only name
        # "sp" — an unbound axis NameError at trace time on the fleet
        root = _tree(tmp_path, {
            "dstack_tpu/parallel/ring.py": """
                import jax.lax as lax
                from jax import shard_map
                from jax.sharding import PartitionSpec as P

                def ring(q, *, mesh):
                    def local_fn(x):
                        return lax.psum(x, "tp")
                    return shard_map(
                        local_fn, mesh=mesh, in_specs=(P("sp"),),
                        out_specs=P("sp"), check_vma=False,
                    )(q)
            """,
        })
        findings = _run_rule("DTPU014", root)
        assert any(
            "axis 'tp'" in f.message and "neither" in f.message
            for f in findings
        ), findings

    def test_axis_covered_through_param_binding(self, tmp_path):
        # specs and collective both resolve to "sp" through the
        # axis_name param — covered, no finding
        root = _tree(tmp_path, {
            "dstack_tpu/parallel/ring.py": """
                import jax.lax as lax
                from jax import shard_map
                from jax.sharding import PartitionSpec as P

                def ring(q, *, mesh, axis_name: str = "sp"):
                    def local_fn(x):
                        return lax.psum(x, axis_name)
                    spec = P(axis_name)
                    return shard_map(
                        local_fn, mesh=mesh, in_specs=(spec,),
                        out_specs=spec, check_vma=False,
                    )(q)
            """,
        })
        assert _run_rule("DTPU014", root) == []


# ---------------------------------------------------------------------------
# path-scoped project rules: the --changed-only integration
# ---------------------------------------------------------------------------


BAD_PARALLEL = """
import jax.lax as lax

def f(x):
    return lax.psum(x, "tpp")
"""


class TestScopedRuns:
    def test_changed_path_in_scope_runs_spmd_rules(self, tmp_path):
        root = _tree(tmp_path, {"dstack_tpu/parallel/bad.py": BAD_PARALLEL})
        findings = run_lint(
            root, paths=["dstack_tpu/parallel/bad.py"],
            rule_ids=["DTPU012"],
        )
        assert any(f.rule == "DTPU012" for f in findings)

    def test_changed_path_outside_scope_skips_spmd_rules(self, tmp_path):
        root = _tree(tmp_path, {
            "dstack_tpu/parallel/bad.py": BAD_PARALLEL,
            "dstack_tpu/server/util.py": "def g():\n    return 1\n",
        })
        # the bad parallel file exists, but only a non-scope path
        # changed — a pre-commit pass must not pay the project-wide
        # SPMD index for it, nor fail on the unrelated finding
        findings = run_lint(
            root, paths=["dstack_tpu/server/util.py"],
            rule_ids=["DTPU012"],
        )
        assert findings == []

    def test_findings_filtered_to_scanned_paths(self, tmp_path):
        root = _tree(tmp_path, {
            "dstack_tpu/parallel/bad.py": BAD_PARALLEL,
            "dstack_tpu/parallel/worse.py": BAD_PARALLEL.replace(
                '"tpp"', '"spp"'
            ),
        })
        findings = run_lint(
            root, paths=["dstack_tpu/parallel/bad.py"],
            rule_ids=["DTPU012"],
        )
        # worse.py's finding exists project-wide but its path was not
        # scanned — a changed-only pass reports only the changed file
        assert findings and all(
            f.path == "dstack_tpu/parallel/bad.py" for f in findings
        )


# ---------------------------------------------------------------------------
# the real tree stays clean (the zero-new-findings acceptance bar)
# ---------------------------------------------------------------------------


class TestRealTree:
    def test_repo_has_no_unpragmad_spmd_findings(self):
        flow = get_spmd_flow(REPO)
        assert flow.vocab  # mesh.py vocabulary extracted
        assert flow.bodies  # the parallel/ shard_map bodies indexed
        for rid in ("DTPU012", "DTPU013", "DTPU014"):
            assert _run_rule(rid, REPO) == [], rid
