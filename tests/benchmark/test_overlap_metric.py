"""``stream_overlap_share``: of the tokens the stream handlers took off
their queues, the share taken while an engine call was in flight (the
handlers' turn lies inside the next call's await, PR 41). The entry as
``BENCHMARK.json`` lists it, its file on hand-made contexts, on a live
server's own scrapes and on a CPU rehearsal's line; and
``loop_yield_ms``, which the same change empties, still reads a number."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import readers, validate
from benchmark.kinds.serve import parse_prometheus
from benchmark.run import load_cell, load_metric_defs

NAME = "stream_overlap_share"
CELLS = [
    "minitron-4b.chat", "deepseek-v2-lite-9l.reasoning",
    "dots3-note-prev-5l-ep8.longdoc", "laguna-s-2.1-13l-ep8.mixed",
    "longcat-flash-chat-4l-ep32.agent",
]


def _metric(root, name):
    with open(os.path.join(root, "benchmark", "metrics", f"{name}.json")) as f:
        return json.load(f)


def test_the_entry_is_listed_and_the_benchmark_validates(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        entry = {m["name"]: m for m in json.load(f)["per_layer"]}[NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "service (stream handler)",
        "moves": "itl_p95_ms",
    }  # no `workloads`: every cell reports itl_p95_ms and reads it
    metric = _metric(root, NAME)
    assert metric["reader"] == "prom_ratio" and metric["args"]["scale"] == 100.0
    assert {k: metric[k] for k in entry} == entry
    assert validate.validate(root) == []


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reads_it(root, cell):
    workload, _, _ = load_cell(os.path.join(root, "benchmark"), cell)
    assert NAME in load_metric_defs(workload)


def test_reads_the_program_and_leaves_the_parent_out(root):
    """Against the parent's counters (neither family) the metric finds
    nothing and does not raise; against the change's it reads a share."""
    metric = _metric(root, NAME)
    before = {"dtpu_serve_tokens_generated_total": 100.0}
    after = {"dtpu_serve_tokens_generated_total": 1100.0}
    parent = {"prom_before": before, "prom_after": after, "seconds": 51.0, "trace": None}
    assert readers.read(metric, parent) is None
    change = {
        "prom_before": dict(
            before, dtpu_serve_stream_tokens_total=90.0,
            dtpu_serve_stream_tokens_overlapped_total=10.0,
        ),
        "prom_after": dict(
            after, dtpu_serve_stream_tokens_total=1090.0,
            dtpu_serve_stream_tokens_overlapped_total=960.0,
        ),
        "seconds": 51.0, "trace": None,
    }
    assert readers.read(metric, change) == pytest.approx(95.0)
    # a window in which no handler took a token has no share
    idle = dict(change, prom_after=change["prom_before"])
    assert readers.read(metric, idle) is None


async def test_both_metrics_read_a_live_servers_scrapes(root):
    """The harness's own scrape and readers over a CPU server: the
    share is a number near 100 (a handler's turn lies inside the next
    call's await) and ``loop_yield_ms`` is still read, and about 0."""
    from tests.serve.test_stream_overlap import ASCII, _client

    client, _, _ = await _client()
    try:
        async def scrape():
            return parse_prometheus(await (await client.get("/metrics")).text())

        before = await scrape()
        # exported from boot: a scrape before any stream reads 0, not nothing
        assert before["dtpu_serve_stream_tokens_total"] == 0.0
        assert before["dtpu_serve_stream_tokens_overlapped_total"] == 0.0
        r = await client.post("/v1/chat/completions", json={
            "model": "llama-tiny", "stream": True, "max_tokens": 24,
            "messages": [{"role": "user", "content": "abc"}],
            "logit_bias": ASCII,
        })
        assert r.status == 200
        async for _ in r.content:
            pass
        ctx = {"prom_before": before, "prom_after": await scrape(),
               "seconds": 4.0, "trace": None}
    finally:
        await client.close()
    assert ctx["prom_after"]["dtpu_serve_stream_tokens_total"] == 24.0
    share = readers.read(_metric(root, NAME), ctx)
    # the first token follows a prefill wave, the last may be taken
    # after the scheduler has parked: the rest lie inside a step's await
    assert 80.0 <= share <= 100.0
    yielded = readers.read(_metric(root, "loop_yield_ms"), ctx)
    assert yielded is not None and 0.0 <= yielded < 1.0


def test_the_rehearsal_line_carries_the_share(root, tiny):
    """A traced CPU rehearsal of the closed-loop cell prints the share
    (a count of tokens, so it may stand on a CPU line)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", TF_CPP_MIN_LOG_LEVEL="3")
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "tiny-mla-moe.reasoning",
         "--seed", str(2**31 + 41), "--seconds", "4", "--bench-dir", tiny,
         "--trace", "1", "--platform", "cpu"],
        capture_output=True, text=True, cwd=root, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True
    got = line["metrics"][NAME]
    assert got["unit"] == "%" and 50.0 < got["value"] <= 100.0
    # a time: never on a CPU line, whatever it reads
    assert "loop_yield_ms" not in line["metrics"]
