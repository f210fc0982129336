"""A CPU rehearsal of each cell at a tiny size prints a well-formed last
line that names the CPU and carries counts only; without ``--platform``
the harness refuses to measure where there is no chip."""

import json
import os
import subprocess
import sys

import pytest


def _run(root, tiny, cell, *extra, seed=2**31 + 17):
    env = dict(os.environ, JAX_PLATFORMS="cpu", TF_CPP_MIN_LOG_LEVEL="3")
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed", str(seed),
         "--seconds", "4", "--bench-dir", tiny, *extra],
        capture_output=True, text=True, cwd=root, env=env, timeout=600,
    )


@pytest.mark.parametrize("cell,trace", [
    ("tiny-dense.chat", "1"), ("tiny-mla-moe.reasoning", "0"), ("tiny-mla-moe.reasoning", "1"),
])
def test_rehearsal_prints_a_well_formed_line_that_names_the_cpu(root, tiny, cell, trace):
    out = _run(root, tiny, cell, "--trace", trace, "--platform", "cpu")
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    assert line["rehearsal"] is True
    assert line["correct"] is True and line["attempted"] > 0 and line["failed"] == 0
    # counts only: no time, rate or device share from a CPU run
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    counters = {m["name"] for m in b["per_layer"] if m["source"] == "program_counter"}
    assert set(line["metrics"]) <= counters
    if trace == "1":
        assert line["metrics"]["compiles_in_window"]["value"] >= 0
        if "reasoning" in cell:  # it moves tokens/s, which the chat cell does not report
            assert line["metrics"]["tokens_per_step"]["value"] > 0
    # every number compared is printed beside its limit
    compared = [l for l in out.stdout.splitlines() if l.startswith("compared ")]
    # the chat cell also compares what its sampled requests were served
    assert len(compared) == (4 if "chat" in cell else 3)
    assert all("limit=" in l and l.endswith(" ok") for l in compared)


def test_refuses_to_measure_without_a_chip(root, tiny):
    out = _run(root, tiny, "tiny-dense.chat", "--trace", "0")
    assert out.returncode != 0
    assert not any(l.startswith("{") for l in out.stdout.splitlines())
