"""`correct` has teeth: with the timed path broken underneath it comes
out false, and the lower-precision control is read through the same
harness (at the cells' own sizes it fails; PERF.md §2 has the chip
readings the limits were set from)."""

import argparse
import os

from benchmark import run
from benchmark.kinds import serve


def _args(**kw):
    base = dict(seed=2**31 + 23, seconds=4.0, trace=0, platform="cpu", control=None)
    base.update(kw)
    return argparse.Namespace(**base)


def _drive(tiny, cell, launcher=serve.LAUNCH, **kw):
    workload, cfg, cfg_path = run.load_cell(tiny, cell)
    return serve.run(_args(**kw), workload, cfg, cfg_path,
                     run.load_metric_defs(workload), launcher=launcher)


def test_a_token_altered_where_it_is_produced_is_not_correct(tiny, capsys):
    broken = os.path.join(os.path.dirname(__file__), "broken_launch.py")
    line = _drive(tiny, "tiny-dense.chat", launcher=broken, seed=2**31 + 29)
    out = capsys.readouterr().out
    assert line["correct"] is False and line["attempted"] > 0
    assert "served_gap_max" in out and "EXCEEDED" in out


def test_the_programs_own_int8_is_run_through_the_same_harness(tiny, capsys):
    line = _drive(tiny, "tiny-dense.chat", control="int8")
    out = capsys.readouterr().out
    gap = [l for l in out.splitlines() if l.startswith("compared served_gap_max=")]
    assert len(gap) == 1
    value = float(gap[0].split("=")[1].split()[0])
    # the server ran with --quantize int8 and its tokens were read against
    # the float32 reference (at toy sizes a few dozen served tokens need
    # not move: test_references reads the control over more positions)
    assert value >= 0.0 and line["attempted"] > 0
    with open(os.path.join(serve.ROOT, ".bench_runs",
                           f"tiny-dense.chat-{2**31 + 23}-0", "server.log")) as f:
        assert "weights quantized to int8" in f.read()
