"""The per-layer metrics that read the program's names and phase
histograms: the readers on hand-made contexts, and the entries as
``BENCHMARK.json`` lists them."""

import json
import os

import pytest

from benchmark import readers, validate
from benchmark.readers import program_ms, prom_rate, prom_value
from benchmark.run import load_cell, load_metric_defs

DECODE = ["decode_step", "decode_loop", "verify_step"]
NEW = {
    "decode_device_ms": "itl_p95_ms", "decode_device_share": "itl_p95_ms",
    "prefill_device_ms": "itl_p95_ms", "prefill_device_share": "itl_p95_ms",
    "host_gap_ms": "itl_p95_ms", "host_gap_share": "itl_p95_ms",
    "tick_host_ms": "itl_p95_ms", "detokenize_us_per_token": "itl_p95_ms",
    "stream_write_us_per_token": "itl_p95_ms", "boot_compile_s": "setup_s",
}


def _trace(programs_s, program_calls, busy_s=2.0):
    return {"busy_s": busy_s, "window_s": 4.0, "programs_s": programs_s,
            "program_calls": program_calls}


class TestProgramMs:
    def test_time_over_calls_of_the_named_programs(self):
        ctx = {"trace": _trace(
            {"decode_step": 1.0, "decode_loop": 0.8, "sample": 0.3, "_unknown": 9.0},
            {"decode_step": 20, "decode_loop": 2, "sample": 20, "_unknown": 3},
        )}
        # a macro-step counts once: (1.0 + 0.8) s over 22 calls
        assert program_ms.read(ctx, programs=DECODE) == pytest.approx(1800.0 / 22)

    @pytest.mark.parametrize("trace", [
        None,  # an untraced run
        _trace({"_unknown": 3.0}, {"_unknown": 60}),  # the parent: no names
        _trace({"decode_step": 0.0}, {"decode_step": 0}),  # zero calls
    ])
    def test_nothing_to_read_is_none(self, trace):
        assert program_ms.read({"trace": trace}, programs=DECODE) is None


class TestPromRate:
    CTX = {
        "prom_before": {"gap_sum": 1.0}, "prom_after": {"gap_sum": 6.1},
        "seconds": 51.0,
    }

    def test_increase_per_second_of_the_window(self):
        assert prom_rate.read(self.CTX, name="gap_sum", scale=100.0) == pytest.approx(10.0)

    def test_series_new_in_the_window_counts_from_zero(self):
        ctx = dict(self.CTX, prom_before={})
        assert prom_rate.read(ctx, name="gap_sum") == pytest.approx(6.1 / 51.0)

    def test_absent_series_is_none(self):
        assert prom_rate.read(self.CTX, name="dtpu_serve_host_gap_seconds_sum") is None


class TestPromValue:
    CTX = {"prom_before": {"compile_sum": 41.5, "zero": 0.0},
           "prom_after": {"compile_sum": 41.5}}

    def test_value_at_the_windows_start(self):
        assert prom_value.read(self.CTX, name="compile_sum") == 41.5
        after = dict(self.CTX, prom_after={"compile_sum": 44.0})
        assert prom_value.read(after, name="compile_sum") == 41.5  # not the window's end

    def test_zero_is_a_reading_and_absent_is_none(self):
        assert prom_value.read(self.CTX, name="zero") == 0.0
        assert prom_value.read(self.CTX, name="dtpu_serve_compile_seconds_sum") is None


class TestEntries:
    def test_the_ten_are_listed_and_the_benchmark_validates(self, root):
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
        assert {n: per_layer[n]["moves"] for n in NEW} == NEW
        assert validate.validate(root) == []

    @pytest.mark.parametrize("cell", ["minitron-4b.chat", "deepseek-v2-lite-9l.reasoning"])
    def test_every_cell_reads_all_ten(self, root, cell):
        workload, _, _ = load_cell(os.path.join(root, "benchmark"), cell)
        assert set(NEW) <= set(load_metric_defs(workload))

    @pytest.mark.parametrize("name", sorted(NEW))
    def test_reads_the_program_and_leaves_the_parent_out(self, root, name):
        """Against a program with the names and histograms each metric
        reads a number; against the parent's (``jit__unknown``, no new
        series) it finds nothing and does not raise — except the boot
        metric, whose series the parent has too, and the shares, which
        the reader that was there gives as 0 % of the busy time."""
        with open(os.path.join(root, "benchmark", "metrics", f"{name}.json")) as f:
            metric = json.load(f)
        before = {"dtpu_serve_compile_seconds_sum": 40.0,
                  "dtpu_serve_tokens_generated_total": 100.0}
        after = {"dtpu_serve_compile_seconds_sum": 40.0,
                 "dtpu_serve_tokens_generated_total": 1100.0}
        parent = {
            "prom_before": before, "prom_after": after, "seconds": 51.0,
            "trace": _trace({"_unknown": 1.9, "sample": 0.1}, {"_unknown": 80, "sample": 40}),
        }
        got = readers.read(metric, parent)
        on_parent = {"boot_compile_s": 40.0, "decode_device_share": 0.0,
                     "prefill_device_share": 0.0}
        assert got == on_parent.get(name)
        phases = ("host_gap", "tick_host", "detokenize", "stream_write")
        change = dict(
            parent,
            prom_after=dict(after, **{
                f"dtpu_serve_{p}_seconds_{k}": v
                for p in phases for k, v in (("sum", 0.5), ("count", 250.0))
            }),
            trace=_trace(
                {"decode_step": 1.0, "prefill_chunk_step": 0.5, "sample": 0.1},
                {"decode_step": 20, "prefill_chunk_step": 10, "sample": 20},
            ),
        )
        assert readers.read(metric, change) > 0
