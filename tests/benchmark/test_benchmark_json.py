"""BENCHMARK.json and the files it names keep the contract; a later PR
can add a configuration, a cell and a per-layer metric as new files plus
one entry each, editing no file that is there."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import validate


def test_committed_benchmark_validates(root):
    assert validate.validate(root) == []


def test_names_and_units(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    for m in b["end_to_end"] + b["per_layer"]:
        assert validate.NAME.match(m["name"]) and "/" not in m["name"]
        assert len(m["unit"]) <= 16 and " " not in m["unit"]
    assert b["command"] == ["python3", "benchmark/run.py"]
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.1 for m in b["end_to_end"])


def test_every_moves_names_a_metric_its_cells_report(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        cells = m.get("workloads") or e2e[m["moves"]].get(
            "workloads", [w["name"] for w in b["workloads"]]
        )
        for c in cells:
            with open(os.path.join(root, "benchmark", "workloads", f"{c}.json")) as f:
                assert m["moves"] in json.load(f)["end_to_end"]


def test_catalog_config_keeps_every_published_number(root):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    rows = {}
    with open(catalog) as f:
        for line in f:
            row = json.loads(line)
            rows[row["source_url"]] = row
    checked = 0
    for c in b["configs"]:
        row = rows.get(c["source"])
        if row is None:
            continue
        with open(os.path.join(root, c["file"])) as f:
            cfg = json.load(f)
        for k, v in row["config"].items():
            if k in c["reduced"]:
                continue
            assert cfg[k] == v, (c["name"], k)
        checked += 1
    assert checked >= 1  # DeepSeek-V2-Lite is in the catalog


@pytest.mark.parametrize("key", ["reference", "costs"])
def test_a_module_a_configuration_names_has_to_be_there(root, tmp_path, key):
    copy = tmp_path / "copy"
    shutil.copytree(os.path.join(root, "benchmark"), copy / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (copy / "tests" / "benchmark").mkdir(parents=True)
    shutil.copy(os.path.join(root, "BENCHMARK.json"), copy / "BENCHMARK.json")
    path = copy / "benchmark/configs/minitron-4b.json"
    with open(path) as f:
        cfg = json.load(f)
    cfg[key] = "nowhere"
    path.write_text(json.dumps(cfg))
    assert validate.validate(str(copy)) == [f"config minitron-4b: no benchmark/{key}/nowhere.py"]


@pytest.mark.parametrize("what", ["validator", "harness"])
def test_dummy_config_cell_and_metric_are_new_files_plus_one_entry(root, tmp_path, what):
    """Copy the benchmark, add one of each as NEW files and entries, and
    see the validator pass and the harness resolve them by name."""
    copy = tmp_path / "copy"
    shutil.copytree(os.path.join(root, "benchmark"), copy / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (copy / "tests" / "benchmark").mkdir(parents=True)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    with open(copy / "benchmark/configs/minitron-4b.json") as f:
        cfg = json.load(f)
    cfg["name"] = "dummy-model"
    (copy / "benchmark/configs/dummy-model.json").write_text(json.dumps(cfg))
    with open(copy / "benchmark/workloads/minitron-4b.chat.json") as f:
        wl = json.load(f)
    wl.update(name="dummy-model.burst", config="dummy-model", why="a dummy cell")
    (copy / "benchmark/workloads/dummy-model.burst.json").write_text(json.dumps(wl))
    metric = {
        "name": "dummy_metric", "layer": "scheduler", "unit": "requests",
        "source": "program_counter", "better": "higher", "reader": "dummy_reader",
        "args": {"k": 2}, "moves": "itl_p95_ms",
    }
    (copy / "benchmark/metrics/dummy_metric.json").write_text(json.dumps(metric))
    (copy / "benchmark/readers/dummy_reader.py").write_text(
        "def read(ctx, k):\n    return k * ctx['seconds']\n"
    )
    b["configs"].append({"name": "dummy-model", "source": "https://example.org/dummy",
                         "file": "benchmark/configs/dummy-model.json", "reduced": [],
                         "why": "a dummy"})
    b["workloads"].append({"name": "dummy-model.burst", "config": "dummy-model",
                           "traffic": "burst", "chips": 1, "why": "a dummy cell"})
    b["per_layer"].append({k: metric[k] for k in
                           ("name", "unit", "better", "source", "layer", "moves")})
    (copy / "BENCHMARK.json").write_text(json.dumps(b))
    if what == "validator":
        assert validate.validate(str(copy)) == []
        return
    code = (
        "import sys, json; sys.path.insert(0, %r)\n"
        "from benchmark import run, readers\n"
        "wl, cfg, path = run.load_cell(%r, 'dummy-model.burst')\n"
        "defs = run.load_metric_defs(wl)\n"
        "print(json.dumps([cfg['name'], sorted(defs), "
        "readers.read(defs['dummy_metric'], {'seconds': 3})]))\n"
    ) % (str(copy), str(copy / "benchmark"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                         cwd=str(copy), timeout=60)
    assert out.returncode == 0, out.stderr
    name, defs, value = json.loads(out.stdout.strip().splitlines()[-1])
    assert name == "dummy-model" and "dummy_metric" in defs and value == 6
    # ... and in that directory, which holds only BENCHMARK.json and the
    # files under paths, the command itself refuses to run
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "dummy-model.burst",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=str(copy), timeout=60,
    )
    assert out.returncode != 0 and not out.stdout.strip()
