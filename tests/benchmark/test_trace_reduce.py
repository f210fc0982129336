"""trace/reduce.py on hand-made planes and on one small capture recorded
on the v5e (``data/make_trace.py``)."""

import os

import pytest

from benchmark.trace import reduce as R

PLANES = {
    "/device:TPU:0": {
        "XLA Ops": [
            ("fusion.1", 1_000.0, 500.0), ("dot.2", 1_400.0, 600.0),  # overlap 100 ns
            ("fusion.1", 100_000.0, 1_000.0),
        ],
        "XLA Modules": [
            ("jit_decode_step(123)", 1_000.0, 1_000.0),
            ("jit_prefill_chunk_step(9)", 100_000.0, 1_000.0),
        ],
    },
    "/device:TPU:1": {"XLA Ops": [("fusion.1", 0.0, 200_000.0)], "XLA Modules": []},
    "/host:CPU": {
        "main": [("$engine.py:1 step", 0.0, 200_000.0), ("$x.py:2 sample", 30_000.0, 40_000.0)],
    },
}


def test_union_merges_overlaps():
    assert R.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_program_names():
    assert R.program_name("jit_decode_step(1234567)") == "decode_step"
    assert R.program_name("jit_decode_loop") == "decode_loop"


def test_busy_idle_programs_and_gaps_on_one_chip():
    out = R.reduce_planes(PLANES, chips=1)
    assert out["chips"] == 1
    assert out["window_s"] == pytest.approx(200e-6)
    assert out["busy_s"] == pytest.approx((1_000 + 1_000) / 1e9)  # union, not sum
    assert out["programs_s"] == {
        "decode_step": pytest.approx(1e-6), "prefill_chunk_step": pytest.approx(1e-6)
    }
    assert out["program_calls"] == {"decode_step": 1, "prefill_chunk_step": 1}
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(1.5e-6) and ops["dot.2"] == pytest.approx(0.6e-6)
    gaps = dict(out["breakdown"]["idle_gaps"])
    # 2..100 us: its midpoint lies inside `sample`; 101..200 us: only `step` covers it
    assert gaps["$x.py:2 sample"] == pytest.approx(98e-6)
    assert gaps["$engine.py:1 step"] == pytest.approx(99e-6)
    assert sum(gaps.values()) == pytest.approx(out["window_s"] - out["busy_s"], rel=0.02)


def test_busy_is_averaged_over_the_chips_used():
    out = R.reduce_planes(PLANES, chips=2)
    assert out["chips"] == 2
    assert out["busy_s"] == pytest.approx((2_000 + 200_000) / 2 / 1e9)


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        R.reduce_planes({"/host:CPU": PLANES["/host:CPU"]})


def test_recorded_v5e_capture():
    path = os.path.join(os.path.dirname(__file__), "data", "v5e_small.xplane.pb")
    out = R.reduce_planes(R.load(path), chips=1)
    assert set(out["programs_s"]) >= {"decode_step", "prefill_chunk_step"}
    assert out["program_calls"]["decode_step"] == 5
    assert out["program_calls"]["prefill_chunk_step"] == 5
    assert 0 < out["busy_s"] < out["window_s"]
    # five 2 ms host pauses lie between the programs
    assert out["window_s"] - out["busy_s"] > 0.008
    assert out["programs_s"]["prefill_chunk_step"] > out["programs_s"]["decode_step"]
    assert out["breakdown"]["device_ops"] and out["breakdown"]["idle_gaps"]
