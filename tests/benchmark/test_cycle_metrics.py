"""The nine per-layer metrics that name the decode cycle's host part
(an engine call split into enqueue / wait / finish, the gap between two
calls into loop return / yield / worker start, hand-over → first
delta): the one new reader on hand-made contexts, and the entries as
``BENCHMARK.json`` lists them."""

import json
import os

import pytest

from benchmark import readers, validate
from benchmark.readers import prom_rate_sum
from benchmark.run import load_cell, load_metric_defs

# metric → the histogram families it reads
CYCLE = {
    "step_enqueue_ms": ("step_enqueue",),
    "step_wait_ms": ("step_wait",),
    "step_finish_ms": ("step_finish",),
    "step_host_share": ("step_enqueue", "step_finish"),
    "prefill_host_ms": ("prefill_host",),
    "loop_return_ms": ("loop_return",),
    "loop_yield_ms": ("loop_yield",),
    "worker_start_ms": ("worker_start",),
    "first_delta_lag_ms": ("first_delta_lag",),
}
CELLS = [
    "minitron-4b.chat", "deepseek-v2-lite-9l.reasoning",
    "dots3-note-prev-5l-ep8.longdoc", "laguna-s-2.1-13l-ep8.mixed",
    "longcat-flash-chat-4l-ep32.agent",
]


class TestPromRateSum:
    NAMES = ["enqueue_sum", "finish_sum"]
    CTX = {
        "prom_before": {"enqueue_sum": 1.0, "finish_sum": 0.5},
        "prom_after": {"enqueue_sum": 4.0, "finish_sum": 2.6},
        "seconds": 51.0,
    }

    def test_summed_increase_per_second_of_the_window(self):
        got = prom_rate_sum.read(self.CTX, names=self.NAMES, scale=100.0)
        assert got == pytest.approx((3.0 + 2.1) / 51.0 * 100.0)

    def test_series_new_in_the_window_counts_from_zero(self):
        ctx = dict(self.CTX, prom_before={"enqueue_sum": 1.0})
        assert prom_rate_sum.read(ctx, names=self.NAMES) == pytest.approx(
            (3.0 + 2.6) / 51.0
        )

    def test_one_absent_name_is_none(self):
        ctx = dict(self.CTX, prom_after={"enqueue_sum": 4.0})
        assert prom_rate_sum.read(ctx, names=self.NAMES) is None

    def test_no_increase_is_a_reading(self):
        ctx = dict(self.CTX, prom_before=self.CTX["prom_after"])
        assert prom_rate_sum.read(ctx, names=self.NAMES) == 0.0


@pytest.fixture(scope="module")
def per_layer(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return {m["name"]: m for m in json.load(f)["per_layer"]}


class TestEntries:
    def test_the_nine_are_listed_and_the_benchmark_validates(self, root, per_layer):
        for name in CYCLE:
            entry = per_layer[name]
            assert entry["moves"] == "itl_p95_ms", name
            assert entry["source"] == "program_span", name
            # read in every cell: the scrape sums label sets, and the
            # families are unlabelled
            assert "workloads" not in entry, name
        assert validate.validate(root) == []

    @pytest.mark.parametrize("cell", CELLS)
    def test_every_cell_reads_all_nine(self, root, cell):
        workload, _, _ = load_cell(os.path.join(root, "benchmark"), cell)
        assert set(CYCLE) <= set(load_metric_defs(workload))

    @pytest.mark.parametrize("name", sorted(CYCLE))
    def test_reads_the_program_and_leaves_the_parent_out(self, root, per_layer, name):
        """Against the parent's counters (none of the new families) a
        metric finds nothing and does not raise; against the change's it
        reads a number."""
        with open(os.path.join(root, "benchmark", "metrics", f"{name}.json")) as f:
            metric = json.load(f)
        assert metric["layer"] == per_layer[name]["layer"]
        before = {"dtpu_serve_host_gap_seconds_sum": 1.0,
                  "dtpu_serve_host_gap_seconds_count": 100.0,
                  "dtpu_serve_tokens_generated_total": 100.0}
        after = {"dtpu_serve_host_gap_seconds_sum": 6.0,
                 "dtpu_serve_host_gap_seconds_count": 500.0,
                 "dtpu_serve_tokens_generated_total": 1100.0}
        parent = {"prom_before": before, "prom_after": after,
                  "seconds": 51.0, "trace": None}
        assert readers.read(metric, parent) is None
        grown = {
            f"dtpu_serve_{fam}_seconds_{k}": v
            for fam in CYCLE[name] for k, v in (("sum", 2.04), ("count", 400.0))
        }
        # a histogram exports nothing until its first observation: a
        # series new in the window counts from zero
        change = dict(parent, prom_after=dict(after, **grown))
        want = 4.0 * len(CYCLE[name]) if name.endswith("_share") else 5.1
        assert readers.read(metric, change) == pytest.approx(want)

