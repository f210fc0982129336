"""Check ``BENCHMARK.json`` and the files it names against the
benchmark's contract, before any chip time is spent:

    python3 benchmark/validate.py [root]

Prints each fault and exits 1 if there is any. The tests run it on the
committed files and on a copy with a dummy configuration, cell and
metric added.
"""

import json
import os
import re
import sys

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KEYS = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
WIDTH = re.compile(r"(hidden_size|intermediate|latent|state_size|proj|_dim$|_rank$|head_dim|head_size|expan|experts_per_tok)")


def _line(s, what, faults):
    if not (isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s):
        faults.append(f"{what}: not 1..200 characters on one line")


def validate(root: str) -> list:
    faults = []
    path = os.path.join(root, "BENCHMARK.json")
    if os.path.getsize(path) > 64 * 1024:
        faults.append("BENCHMARK.json is over 64 KiB")
    with open(path) as f:
        b = json.load(f)
    if set(b) != KEYS:
        faults.append(f"top-level keys are {sorted(b)}, want {sorted(KEYS)}")
        return faults
    paths = b["paths"]
    if not (1 <= len(paths) <= 16 and all(PATH.match(p) and not p.startswith("/") and ".." not in p for p in paths)):
        faults.append("paths: 1..16 relative directories")
    for p in paths:
        if not os.path.isdir(os.path.join(root, p)):
            faults.append(f"paths: {p} is not a directory")
    if not (isinstance(b["command"], list) and 1 <= len(b["command"]) <= 32):
        faults.append("command: a list of 1..32 strings")
    for w in b["command"]:
        _line(w, "command word", faults)
        if w.startswith("/") or ".." in w:
            faults.append(f"command word {w!r} leaves the repo")
    rs = b["run_seconds"]
    if not (isinstance(rs, int) and 1 <= rs <= 51):
        faults.append("run_seconds: a whole number 1..51")
    elif (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 > 43200:
        faults.append("run_seconds: a full check of 24 cells would not fit 43200 s")

    def under_paths(f):
        return any(f == p or f.startswith(p.rstrip("/") + "/") for p in paths)

    configs = {}
    if not 1 <= len(b["configs"]) <= 24:
        faults.append("configs: 1..24")
    files = set()
    for c in b["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            faults.append(f"config {c.get('name')}: keys {sorted(c)}")
            continue
        if not NAME.match(c["name"]) or c["name"] in configs:
            faults.append(f"config name {c['name']!r}")
        configs[c["name"]] = c
        _line(c["source"], f"config {c['name']} source", faults)
        _line(c["why"], f"config {c['name']} why", faults)
        if not under_paths(c["file"]) or not os.path.isfile(os.path.join(root, c["file"])):
            faults.append(f"config {c['name']}: file {c['file']} not under paths or missing")
        else:
            # the modules the harness looks up by the names in the file
            with open(os.path.join(root, c["file"])) as f:
                cf = json.load(f)
            for group, module in (("reference", cf.get("reference")),
                                  ("costs", cf.get("costs", "decode"))):
                if not os.path.isfile(os.path.join(root, "benchmark", group, f"{module}.py")):
                    faults.append(f"config {c['name']}: no benchmark/{group}/{module}.py")
        if c["file"] in files:
            faults.append(f"config file {c['file']} used twice")
        files.add(c["file"])
        if len(c["reduced"]) > 16:
            faults.append(f"config {c['name']}: over 16 reduced keys")
        for k in c["reduced"]:
            if not NAME.match(k) or WIDTH.search(k):
                faults.append(f"config {c['name']}: reduced key {k!r} is no name or names a width")
    cells, pairs, used = {}, set(), set()
    if not 1 <= len(b["workloads"]) <= 24:
        faults.append("workloads: 1..24")
    for w in b["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            faults.append(f"workload {w.get('name')}: keys {sorted(w)}")
            continue
        if not NAME.match(w["name"]) or w["name"] in cells:
            faults.append(f"workload name {w['name']!r}")
        cells[w["name"]] = w
        if not NAME.match(w["traffic"]) or w["config"] not in configs:
            faults.append(f"workload {w['name']}: traffic or config")
        if (w["config"], w["traffic"]) in pairs:
            faults.append(f"workload {w['name']}: pair appears twice")
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        if w["chips"] not in (1, 4):
            faults.append(f"workload {w['name']}: chips")
        _line(w["why"], f"workload {w['name']} why", faults)
    four = sum(1 for w in cells.values() if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        faults.append("over a quarter of the cells ask for four chips")
    for n in configs:
        if n not in used:
            faults.append(f"config {n} is used by no cell")
    # the harness's own files for each cell
    bench = os.path.join(root, "benchmark")
    cell_e2e = {}
    for n, w in cells.items():
        wf = os.path.join(bench, "workloads", f"{n}.json")
        if not os.path.isfile(wf):
            faults.append(f"workload {n}: {wf} missing")
            continue
        with open(wf) as f:
            wl = json.load(f)
        for k in ("config", "chips", "why"):
            if wl.get(k) != w[k]:
                faults.append(f"workload {n}: {k} differs between BENCHMARK.json and its file")
        if not os.path.isfile(os.path.join(bench, "kinds", f"{wl['kind']}.py")):
            faults.append(f"workload {n}: no kind {wl['kind']}")
        cell_e2e[n] = wl["end_to_end"]
    e2e = {}
    if not 1 <= len(b["end_to_end"]) <= 16:
        faults.append("end_to_end: 1..16")
    for m in b["end_to_end"]:
        if not set(m) <= {"name", "unit", "better", "bound", "source", "workloads"} or not {
            "name", "unit", "better", "bound", "source"
        } <= set(m):
            faults.append(f"end_to_end {m.get('name')}: keys {sorted(m)}")
            continue
        if not NAME.match(m["name"]) or m["name"] in e2e:
            faults.append(f"end_to_end name {m['name']!r}")
        e2e[m["name"]] = m
        if not UNIT.match(m["unit"]) or m["better"] not in ("lower", "higher"):
            faults.append(f"end_to_end {m['name']}: unit or better")
        if m["source"] not in ("host_clock", "device_trace"):
            faults.append(f"end_to_end {m['name']}: source")
        if not 0.01 <= m["bound"] <= 0.1:
            faults.append(f"end_to_end {m['name']}: bound {m['bound']}")
        reported = {n for n, units in cell_e2e.items() if m["name"] in units}
        listed = set(m.get("workloads", cells))
        if reported != listed:
            faults.append(f"end_to_end {m['name']}: listed {sorted(listed)}, cells report it in {sorted(reported)}")
        for n in reported:
            if cell_e2e[n][m["name"]] != m["unit"]:
                faults.append(f"end_to_end {m['name']}: unit differs in cell {n}")
    if "setup_s" not in e2e:
        faults.append("end_to_end: no setup_s")
    for n, units in cell_e2e.items():
        if "setup_s" not in units or len(units) < 2:
            faults.append(f"cell {n}: reports setup_s and one more end-to-end metric")
        for k in units:
            if k not in e2e:
                faults.append(f"cell {n}: reports {k}, which BENCHMARK.json lacks")
    seen = set(e2e)
    layers_of_cell = {n: 0 for n in cells}
    if not 1 <= len(b["per_layer"]) <= 128:
        faults.append("per_layer: 1..128")
    for m in b["per_layer"]:
        need = {"name", "unit", "better", "source", "layer", "moves"}
        if not need <= set(m) or not set(m) <= need | {"workloads"}:
            faults.append(f"per_layer {m.get('name')}: keys {sorted(m)}")
            continue
        if not NAME.match(m["name"]) or m["name"] in seen:
            faults.append(f"per_layer name {m['name']!r}")
        seen.add(m["name"])
        if not UNIT.match(m["unit"]) or m["better"] not in ("lower", "higher") or m["source"] not in SOURCES:
            faults.append(f"per_layer {m['name']}: unit, better or source")
        _line(m["layer"], f"per_layer {m['name']} layer", faults)
        if m["moves"] not in e2e:
            faults.append(f"per_layer {m['name']}: moves {m['moves']!r} is no end-to-end metric")
            continue
        for n in m.get("workloads", [n for n, u in cell_e2e.items() if m["moves"] in u]):
            if n not in cell_e2e or m["moves"] not in cell_e2e[n]:
                faults.append(f"per_layer {m['name']}: cell {n} does not report {m['moves']}")
            elif n in layers_of_cell:
                layers_of_cell[n] += 1
        mf = os.path.join(bench, "metrics", f"{m['name']}.json")
        if not os.path.isfile(mf):
            faults.append(f"per_layer {m['name']}: {mf} missing")
            continue
        with open(mf) as f:
            md = json.load(f)
        for k in ("unit", "better", "source", "layer", "moves"):
            if md.get(k) != m[k]:
                faults.append(f"per_layer {m['name']}: {k} differs between BENCHMARK.json and its file")
        if not os.path.isfile(os.path.join(bench, "readers", f"{md['reader']}.py")):
            faults.append(f"per_layer {m['name']}: no reader {md['reader']}")
    for n, k in layers_of_cell.items():
        if not k:
            faults.append(f"cell {n}: no per-layer metric")
    return faults


def main(argv=None) -> int:
    root = (argv or sys.argv[1:] or [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))])[0]
    faults = validate(root)
    for f in faults:
        print("FAULT", f)
    print(f"{len(faults)} faults")
    return 1 if faults else 0


if __name__ == "__main__":
    sys.exit(main())
