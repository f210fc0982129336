"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This process never imports JAX: everything that needs the chip runs in
children (``launch.py``), one after the other. The last line of
standard output is the result, one JSON object. With no accelerator, or
in a directory without the program, it exits non-zero and prints no
result. ``--platform cpu`` is a rehearsal for the tests at tiny sizes:
its line names the CPU and carries counts only, never a time or a rate.
"""

import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def load_cell(bench_dir: str, name: str):
    """→ (workload, configuration, configuration path)."""
    with open(os.path.join(bench_dir, "workloads", f"{name}.json")) as f:
        workload = json.load(f)
    cfg_path = os.path.join(bench_dir, "configs", f"{workload['config']}.json")
    with open(cfg_path) as f:
        return workload, json.load(f), cfg_path


def load_metric_defs(workload: dict) -> dict:
    """The per-layer metrics this cell reports: every file under
    ``benchmark/metrics`` whose ``moves`` metric the cell reports (and
    whose optional ``cells`` list names it)."""
    out = {}
    mdir = os.path.join(ROOT, "benchmark", "metrics")
    for fn in sorted(os.listdir(mdir)):
        if not fn.endswith(".json"):
            continue
        with open(os.path.join(mdir, fn)) as f:
            m = json.load(f)
        if m["moves"] not in workload["end_to_end"]:
            continue
        if "cells" in m and workload["name"] not in m["cells"]:
            continue
        out[m["name"]] = m
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=[0, 1], default=0)
    p.add_argument("--platform", default=None, help="cpu: rehearsal at tiny sizes (tests)")
    p.add_argument(
        "--bench-dir", default=os.path.join(ROOT, "benchmark"),
        help="where workloads/ and configs/ are looked up (tests point at tiny ones)",
    )
    p.add_argument(
        "--control", default=None, choices=["int8", "int8-reference"],
        help="run the lower-precision control (sets the limits; `correct` is then "
             "expected to be false): int8 = the program's own --quantize int8; "
             "int8-reference = the reference in W8A8 put in the program's place",
    )
    p.add_argument("--rate", type=float, default=None,
                   help="override an open-loop cell's rate (the sweep that finds its knee)")
    args = p.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "dstack_tpu")):
        print("the program (dstack_tpu/) is not in this directory", file=sys.stderr)
        return 2
    workload, cfg, cfg_path = load_cell(args.bench_dir, args.workload)
    kind = importlib.import_module(f"benchmark.kinds.{workload['kind']}")
    line = kind.run(args, workload, cfg, cfg_path, load_metric_defs(workload))
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
