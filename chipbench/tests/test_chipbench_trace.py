"""The reduction from a profiler trace to busy time, idle share and breakdown."""

import gzip
import json
import pathlib
from types import SimpleNamespace as NS

import pytest

from chipbench import trace

DATA = pathlib.Path(__file__).resolve().parent / "data"

# Two chips over a 1 s window.  Chip 0: two overlapping fusions cover
# 0-0.3 s, an all-reduce 0.5-0.6 s.  Chip 1: one fusion 0-0.5 s.  The host
# was in a step 0-0.6 s, reading the loss 0.3-0.45 s inside it, and making a
# batch 0.6-1.0 s.
HAND = {
    "window": [0.0, 1e9],
    "devices": {
        "0": [["fusion.1", 0.0, 2e8], ["fusion.2", 1e8, 2e8], ["all-reduce.1", 5e8, 1e8]],
        "1": [["fusion.1", 0.0, 5e8]],
    },
    "host": [["bench.window", 0.0, 1e9], ["bench.step", 0.0, 6e8],
             ["bench.loss", 3e8, 1.5e8], ["bench.batch", 6e8, 4e8]],
}


def test_hand_worked_trace():
    r = trace.reduce(HAND)
    assert r["window_s"] == pytest.approx(1.0)
    assert r["busy_s"] == pytest.approx((0.4 + 0.5) / 2)
    assert r["idle_share"] == pytest.approx(0.55)
    ops = dict(r["breakdown"]["device_ops"])
    assert ops == pytest.approx({"fusion.1": 0.35, "fusion.2": 0.1, "all-reduce.1": 0.05})
    # chip 0 idles 0.3-0.5 s (loss read-back) and 0.6-1.0 s (batch); chip 1 0.5-1.0 s
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert gaps == pytest.approx({"bench.loss": 0.1, "bench.batch": 0.45})
    assert trace.device_seconds(HAND, lambda n: "all-reduce" in n) == pytest.approx(0.05)


def test_ops_outside_the_window_do_not_count():
    t = dict(HAND, window=[0.0, 2.5e8])
    r = trace.reduce(t)
    assert r["busy_s"] == pytest.approx((0.25 + 0.25) / 2)
    assert dict(r["breakdown"]["device_ops"]) == pytest.approx({"fusion.1": 0.35, "fusion.2": 0.1})


@pytest.mark.parametrize("name", sorted(p.name for p in DATA.glob("*.json.gz")))
def test_recorded_chip_trace(name):
    """A trace recorded on a v5e, cut to a step or a few calls, with what
    the reduction read from it then."""
    rec = json.loads(gzip.decompress((DATA / name).read_bytes()))
    r = trace.reduce(rec["trace"])
    for key in ("busy_s", "window_s", "idle_share"):
        assert r[key] == pytest.approx(rec["reduced"][key], rel=1e-9)
    assert 0.0 < r["busy_s"] <= r["window_s"]
    gaps = sum(v for _, v in r["breakdown"]["idle_gaps"])
    assert gaps == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    assert r["breakdown"] == rec["reduced"]["breakdown"]


def test_nested_ops_count_once_in_the_breakdown():
    """A while op holds its body's ops on the same line: its own time is
    what the body leaves, and busy time counts the outer interval once."""
    t = {"window": [0.0, 1e9], "host": [["bench.window", 0.0, 1e9]],
         "devices": {"0": [["while w", 0.0, 6e8], ["fusion a", 1e8, 2e8],
                           ["fusion b", 3e8, 1e8], ["fusion c", 7e8, 1e8]]}}
    r = trace.reduce(t)
    assert r["busy_s"] == pytest.approx(0.7)
    assert dict(r["breakdown"]["device_ops"]) == pytest.approx(
        {"while w": 0.3, "fusion a": 0.2, "fusion b": 0.1, "fusion c": 0.1})
    assert dict(r["breakdown"]["idle_gaps"]) == pytest.approx({trace.NO_SPAN: 0.3})


def test_op_names_drop_layouts_and_operands():
    text = ("%fusion.623 = (f32[2,16,4096]{2,1,0:T(8,128)S(1)}, f32[2,16,4096,4096]"
            "{2,3,1,0:T(8,128)}) fusion(f32[2,16,4096,4096]{2,3,1,0:T(8,128)} %fusion.622), "
            "kind=kOutput, calls=%fused_computation.533")
    assert trace.op_name(text) == "fusion fusion.623 (f32[2,16,4096], f32[2,16,4096,4096])"
    assert trace.op_name("all-reduce.1") == "all-reduce.1"


def test_a_launch_keeps_the_ids_its_stats_give():
    event = NS(start_ns=10, duration_ns=2, stats=[("run_id", 7), ("queue_id", 0)])
    assert trace._launch(event) == [10.0, 2.0, 7, None]
    event = NS(start_ns=10, duration_ns=2, stats=[("device_ordinal", 3), ("run_id", 8)])
    assert trace._launch(event) == [10.0, 2.0, 8, 3]


def test_launches_name_no_idle_gap():
    with_launches = dict(HAND, launches=[[3.1e8, 1e6, 1, 0], [7e8, 1e6, 2, 0]])
    assert trace.reduce(with_launches) == trace.reduce(HAND)
