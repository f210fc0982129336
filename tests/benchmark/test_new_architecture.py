"""A new architecture is new files: a copy of ``benchmark/`` takes the
fixture under ``data/arch`` (a configuration with a low-rank query, its
cell, its reference module with ``leaf_shapes``, its costs module) with
no file that was there changed, and the copy's own ``run.py`` then draws
the weights, serves the cell, checks the served tokens against the
fixture's reference and reads the fixture's step costs.

``--bench-dir`` alone would not do: it redirects configurations and
workloads, while ``benchmark.reference.*`` and ``benchmark.costs.*`` are
imported from the tree that ``run.py`` stands in. So the copy is a tree
of its own, with the program linked in from the checkout."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

CELL = "tiny-mla-lowq.reasoning"
ADDED = {
    "configs/tiny-mla-lowq.json", f"workloads/{CELL}.json",
    "reference/mla_lowrank_q.py", "costs/decode_lowrank_q.py",
}


def _hashes(top) -> dict:
    out = {}
    for d, _, files in os.walk(top):
        for fn in files:
            path = os.path.join(d, fn)
            with open(path, "rb") as f:
                out[os.path.relpath(path, top)] = hashlib.sha256(f.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def copy(root, arch, tmp_path_factory):
    """→ (the copy's root, hashes of ``benchmark/`` before the fixture's
    files went in, and after)."""
    top = tmp_path_factory.mktemp("arch") / "copy"
    shutil.copytree(os.path.join(root, "benchmark"), top / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _hashes(top / "benchmark")
    for rel in ADDED:
        dst = top / "benchmark" / rel
        assert not dst.exists(), rel
        shutil.copy(os.path.join(arch, rel), dst)
    # the program, importable from the checkout
    os.symlink(os.path.join(root, "dstack_tpu"), top / "dstack_tpu")
    return top, before, _hashes(top / "benchmark")


def _env(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu", TF_CPP_MIN_LOG_LEVEL="3")
    # the checkout's compile cache: the copy's path changes every run
    env.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(root, ".jax_compile_cache"))
    return env


def test_the_architecture_went_in_as_new_files_only(copy):
    _, before, after = copy
    assert set(after) - set(before) == ADDED
    assert {k: after[k] for k in before} == before


def test_the_copys_own_run_serves_and_checks_the_cell(root, copy):
    top, _, _ = copy
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", str(2**31 + 31),
         "--seconds", "4", "--trace", "1", "--platform", "cpu"],
        capture_output=True, text=True, cwd=top, env=_env(root), timeout=600,
    )
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["device"]["platform"] == "cpu" and line["rehearsal"] is True
    assert line["correct"] is True and line["attempted"] > 0 and line["failed"] == 0
    assert line["metrics"]["tokens_per_step"]["value"] > 0
    # the served tokens went past the fixture's reference, on weights
    # drawn from the fixture's tree (mla_moe's would find no ``wq``)
    assert "positions=" in out.stdout
    compared = [l for l in out.stdout.splitlines() if l.startswith("compared ")]
    assert len(compared) == 3 and all(l.endswith(" ok") for l in compared)


def test_decode_roofline_reads_the_architectures_own_costs(root, copy):
    """A CPU rehearsal carries counts only, so the reader is called
    directly with a made-up context: one request decoding alone for a
    made-up second of device time on a described v5e."""
    top, _, _ = copy
    code = (
        "import sys, json, types; sys.path.insert(0, %r)\n"
        "from benchmark import run\n"
        "from benchmark.costs import decode, decode_lowrank_q\n"
        "from benchmark.readers import decode_roofline\n"
        "_, cfg, _ = run.load_cell(%r, %r)\n"
        "rec = types.SimpleNamespace(req={'prompt_ids': [1] * 40},\n"
        "                            deltas=[(0.1 * i, 1) for i in range(1, 21)])\n"
        "ctx = {'trace': {'busy_s': 1.0, 'window_s': 4.0}, 'records': [rec],\n"
        "       'trace_span': {'start_done': 0.0, 'stop': 4.0}, 'config': cfg,\n"
        "       'device': {'kind': 'TPU v5 lite'}}\n"
        "own = decode_roofline.read(ctx)\n"
        "ctx['config'] = {k: v for k, v in cfg.items() if k != 'costs'}\n"
        "default = decode_roofline.read(ctx)\n"
        "lc = cfg['llama_config']\n"
        "print(json.dumps([cfg['costs'], own, default,\n"
        "                  decode_lowrank_q.decode_step(lc, 1, 51),\n"
        "                  decode.decode_step(lc, 1, 51)]))\n"
    ) % (str(top), str(top / "benchmark"), CELL)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                         cwd=top, env=_env(root), timeout=120)
    assert out.returncode == 0, out.stderr[-3000:]
    name, own, default, step, base = json.loads(out.stdout.strip().splitlines()[-1])
    assert name == "decode_lowrank_q"
    # 3 layers, hidden 64, rank 24, 4 heads of 16 + 8: the low-rank query
    # is 64 x 24 + 24 x 96 parameters a layer in place of 64 x 96
    more = 3 * (64 * 24 + 24 * 96 - 64 * 96)
    assert step["weight_bytes"] - base["weight_bytes"] == 2 * more
    assert step["flops"] - base["flops"] == 2 * more
    assert step["cache_bytes"] == base["cache_bytes"]
    assert step["bytes"] == step["weight_bytes"] + step["cache_bytes"]
    # 19 steps of one request alone, memory-bound at these sizes: the
    # share follows the bytes of the module the configuration names
    assert own == pytest.approx(100.0 * 19 * step["bytes"] / 819e9, rel=1e-9)
    assert default == pytest.approx(100.0 * 19 * base["bytes"] / 819e9, rel=1e-9)
    assert own < default
