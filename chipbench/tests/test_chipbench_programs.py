"""The readers of what runs inside and around each program execution:
schedule scopes, in-loop idle, the host-to-device clock and the call split."""

import gzip
import json
import pathlib
from types import SimpleNamespace as NS

import pytest

from chipbench import bench, programs, trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
RECORDED = "collective-pairs-1kib"
TREE, NATIVE = "schedules.all_reduce.tree", "schedules.all_reduce.native"

# Two chips over a 10 us window, 2 applications per program.  Chip 0 runs
# program A (1-3 us): a while (1.1-2.9 us) holds a fusion of the tree
# schedule, an inner while that holds its permute, and the harness's own
# fusion; a copy follows the loop.  Then program B (5-7 us), the native
# schedule, and program C, which runs past the window.  Chip 1 runs B alone.
HAND = {
    "window": [0.0, 10_000.0],
    "devices": {
        "0": [["while outer", 1100.0, 1800.0], ["fusion a", 1200.0, 200.0],
              ["while inner", 1450.0, 600.0],
              ["collective-permute-done cp.1 f32[8]", 1500.0, 500.0],
              ["fusion scale", 2100.0, 100.0], ["copy c", 2950.0, 40.0],
              ["while outer", 5100.0, 1800.0], ["all-reduce ar.1 f32[8]", 5200.0, 800.0],
              ["fusion n", 6100.0, 200.0],
              ["while outer", 9600.0, 800.0], ["all-reduce ar.1 f32[8]", 9700.0, 200.0]],
        "1": [["while outer", 5100.0, 1800.0], ["all-reduce ar.1 f32[8]", 5200.0, 1200.0],
              ["fusion n", 6500.0, 100.0]],
    },
    "host": [["bench.window", 0.0, 10_000.0], ["bench.call", 500.0, 3000.0],
             ["bench.call", 4600.0, 3000.0]],
}
SCOPES = {"names": ["", TREE, NATIVE],
          "ops": {"0": [0, 1, 0, 1, 0, 0, 0, 2, 2, 0, 2], "1": [0, 2, 2]}}
PROGRAMS = {"0": [[1000.0, 3000.0], [5000.0, 7000.0], [9500.0, 10_500.0]],
            "1": [[5000.0, 7000.0]]}


def is_collective(name):
    return name.startswith(("all-reduce", "collective-permute"))


def test_scope_is_the_outermost_schedule_component():
    assert programs.scope_of("jit(loop)/while/body/schedules.all_reduce.tree/add:") == TREE
    nested = "jit(f)/schedules.barrier.chain/schedules.all_reduce.tree/ppermute"
    assert programs.scope_of(nested) == "schedules.barrier.chain"
    assert programs.scope_of("jit(loop)/while/body/mul") == ""


def test_nesting_splits_outermost_holders_from_leaves():
    outer, leaves = programs.nesting(HAND["devices"]["0"])
    assert outer == [[1100.0, 2900.0], [5100.0, 6900.0], [9600.0, 10_400.0]]
    assert [1450.0, 2050.0] not in outer + leaves  # the inner while holds the permute
    assert [1500.0, 2000.0] in leaves and [2950.0, 2990.0] in leaves


def test_in_loop_idle():
    # chip 0: while A 1800 - (200 + 500 + 100) = 1000 ns, while B 1800 - 1000 = 800 ns,
    # C is cut by the window: 1800 ns over 2 programs of 2; chip 1: 1800 - 1300 = 500 ns
    assert programs.loop_idle_us(HAND, 2) == pytest.approx(1e-3 * (450 + 250) / 2)


def test_scope_attribution_under_a_nested_while():
    r = programs.scope_readings(HAND, SCOPES, PROGRAMS, 2, is_collective)
    assert r[TREE] == pytest.approx({"device_us": 0.35, "collective_ops": 0.5})
    assert r[NATIVE] == pytest.approx({"device_us": (0.5 + 0.65) / 2, "collective_ops": 0.5})
    assert r["*"] == pytest.approx({"device_us": (0.425 + 0.65) / 2})
    assert programs.schedule_device_us(HAND, SCOPES, PROGRAMS, 2) == pytest.approx(0.5375)
    unscoped = {"names": [""], "ops": {c: [0] * len(o) for c, o in HAND["devices"].items()}}
    assert programs.schedule_device_us(HAND, unscoped, PROGRAMS, 2) is None


def test_clock_is_the_intersection_of_every_execution_bound():
    # (launch, start, done, end): offsets in [1000, 2000] and [1200, 1900]
    pairs = [(1000.0, 0.0, 3000.0, 1000.0), (5200.0, 4000.0, 6900.0, 5000.0)]
    c = programs.offset_bounds(pairs)
    bounds = (c["lo_ns"], c["hi_ns"], c["offset_ns"], c["error_ns"])
    assert bounds == (1200.0, 1900.0, 1550.0, 700.0)
    empty = programs.offset_bounds(pairs + [(8000.0, 7500.0, 9000.0, 8800.0)])
    assert empty["offset_ns"] is None and empty["error_ns"] is None
    assert "no common offset" in empty["note"]
    assert programs.offset_bounds([])["offset_ns"] is None


def _event(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=list(stats.items()))


def test_programs_and_clock_pair_executions_by_run_id():
    profile = NS(planes=[
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Ops", events=[_event("fusion", 10.0, 5.0)]),
            NS(name="XLA Modules", events=[_event("jit_loop(1)", 0.0, 1000.0, run_id=7),
                                           _event("jit_loop(1)", 4000.0, 1000.0, run_id=8)])]),
        NS(name="/host:CPU", lines=[NS(name="main", events=[
            _event("DoEnqueueProgram", 1000.0, 50.0, run_id=7, device_ordinal=0),
            _event("DoEnqueueProgram", 1100.0, 50.0, run_id=7, device_ordinal=0),
            _event("DoEnqueueProgram", 5200.0, 50.0, run_id=8, device_ordinal=0),
            _event("DoEnqueueProgram", 900.0, 50.0, run_id=7, device_ordinal=1),
            _event("CompleteCallbacks", 3000.0, 50.0, run_id=7, device_ordinal=0),
            _event("CompleteCallbacks", 6900.0, 50.0, run_id=8, device_ordinal=0),
            _event("bench.call", 0.0, 9000.0)])]),
    ])
    progs, clock = programs.programs_and_clock(profile)
    assert progs == {"0": [[0.0, 1000.0], [4000.0, 5000.0]]}
    assert clock["0"]["pairs"] == 2
    assert (clock["0"]["lo_ns"], clock["0"]["hi_ns"]) == (1200.0, 1900.0)


def test_launch_us_from_each_call_to_its_first_launch():
    # the window's calls [500, 3500] and [4600, 7600] first launch at 1200 and
    # 4700 (a launch at 400 is before the call, one at 8000 after it); a call
    # at 9800, cut by the window, does not count; 2 applications a call
    t = dict(HAND, host=HAND["host"] + [["bench.call", 9800.0, 400.0]],
             launches=[[400.0, 10.0, 1, 0], [1200.0, 10.0, 2, 0], [1250.0, 10.0, 2, 1],
                       [4700.0, 10.0, 3, None], [8000.0, 10.0, None, None]])
    metric = bench.metric_reader("launch_us")
    assert metric({"trace": t, "applications": 4, "attempted": 2}) == pytest.approx(
        1e-3 * (700 + 100) / 2 / 2)
    assert metric({"trace": dict(t, launches=[[8000.0, 10.0, 1, 0]]), "applications": 4,
                   "attempted": 2}) is None
    assert metric({"trace": dict(t, launches=[]), "applications": 4, "attempted": 2}) is None


def test_call_split_on_the_aligned_clock():
    clock = {"0": {"offset_ns": 300.0, "error_ns": 100.0}, "1": {"offset_ns": None}}
    progs = {"0": [[1000.0, 3000.0], [5000.0, 7000.0]], "1": [[5000.0, 7000.0]]}
    split = programs.call_split(HAND, progs, clock)
    # aligned [1300, 3300] in the call [500, 3500], [5300, 7300] in [4600, 7600]
    assert split == pytest.approx({"launch": 0.75, "program": 2.0, "return": 0.25,
                                   "between": 2.0, "calls": 2, "matched": 2, "error_us": 0.1})
    assert programs.call_split(HAND, progs, {"0": {"offset_ns": None}}) is None


def _space(planes):
    space = programs._xspace_class()()
    for name, stat_names, metadata, line in planes:
        plane = space.planes.add(name=name)
        for key, stat in stat_names.items():
            plane.stat_metadata.add(key=key).value.name = stat
        for key, (md_name, stats) in metadata.items():
            md = plane.event_metadata.add(key=key).value
            md.name = md_name
            for stat_id, value in stats:
                s = md.stats.add(metadata_id=stat_id)
                if isinstance(value, str):
                    s.str_value = value
                else:
                    s.ref_value = value
        ops = plane.lines.add(name="XLA Ops")
        for meta_id in line:
            ops.events.add(metadata_id=meta_id)
    return space.SerializeToString()


FUSION = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%fc"
WHILE = "%while = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), condition=%c, body=%b"
PSUM = "%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %x), replica_groups={}, to_apply=%add"
SPACE = _space([
    ("/device:TPU:0", {1: "tf_op", 2: "source", 3: "jit(f)/schedules.broadcast.native/psum"},
     {10: (FUSION, [(2, "a.py:1"), (1, "jit(f)/while/body/schedules.all_reduce.tree/add:")]),
      11: (WHILE, [(2, "a.py:2")]),
      12: (PSUM, [(1, 3)])},
     [11, 10, 12, 10]),
    ("/device:TPU:1", {}, {1: (FUSION, [])}, [1]),
])


def test_op_scopes_read_the_tf_op_stat_of_each_op():
    devices = {"0": [[trace.op_name(n), 0.0, 1.0] for n in (WHILE, FUSION, PSUM, FUSION)]}
    scopes = programs.op_scopes(SPACE, devices)
    assert scopes == {"names": ["", TREE, "schedules.broadcast.native"],
                      "ops": {"0": [0, 1, 2, 1]}}


@pytest.mark.parametrize("kept", [
    [[trace.op_name(n), 0.0, 1.0] for n in (WHILE, FUSION, PSUM)],
    [[trace.op_name(n), 0.0, 1.0] for n in (WHILE, PSUM, FUSION, FUSION)],
], ids=["count", "order"])
def test_op_scopes_refuse_ops_that_are_not_the_ones_kept(kept):
    with pytest.raises(ValueError):
        programs.op_scopes(SPACE, {"0": kept})


@pytest.fixture(scope="module")
def recorded():
    return json.loads(gzip.decompress((DATA / f"{RECORDED}.json.gz").read_bytes()))


@pytest.fixture(scope="module")
def raw(tmp_path_factory):
    """The directory of the recorded raw trace, and what ``extract`` keeps of it."""
    path = tmp_path_factory.mktemp("recorded")
    (path / "t.xplane.pb").write_bytes(
        gzip.decompress((DATA / f"{RECORDED}.xplane.pb.gz").read_bytes()))
    return path, trace.extract(path)


def test_recorded_trace_reads_as_it_did_on_the_chip(recorded, raw):
    """The raw trace of one round of the 12 (op, schedule) pairs on a v5e
    2x2, and what ``extract`` and ``read`` kept from it then; the program
    launches, which ``extract`` keeps since, are 4 a call, one per chip."""
    path, kept = raw
    assert {k: kept[k] for k in recorded["trace"]} == recorded["trace"]
    new = programs.read(path, kept["devices"])
    assert new == {k: recorded[k] for k in ("scopes", "programs", "clock")}
    launches = kept["launches"]
    assert len(launches) == 4 * recorded["readings"]["calls"]
    assert sorted({launch[3] for launch in launches}) == [0, 1, 2, 3]
    assert launches == sorted(launches, key=lambda launch: launch[0])
    assert launches[0] == [151354466.0, 37320.0, 13, 0]


def test_launch_us_on_the_recorded_trace(recorded, raw):
    """From each call's start to its first launch: 765.4 us a call, at 8
    applications a call; the parent of the change that put the trip count
    on the chips once, whose every call put it there from the host."""
    kept, k, calls = raw[1], recorded["per_program"], recorded["readings"]["calls"]
    metric = bench.metric_reader("launch_us")
    us = metric({"trace": kept, "applications": calls * k, "attempted": calls})
    assert us == 95.67032291666668
    assert metric({"trace": recorded["trace"], "applications": calls * k,
                   "attempted": calls}) is None  # recorded before launches were kept


def test_recorded_idle_gaps_are_named_by_harness_spans(raw):
    """The launches ``extract`` keeps name no idle gap: only ``bench.*`` spans do."""
    kept = raw[1]
    assert all(name.startswith("bench.") for name, _, _ in kept["host"])
    gaps = trace.reduce(kept)["breakdown"]["idle_gaps"]
    assert gaps and {name for name, _ in gaps} <= {"bench.call", "bench.round", trace.NO_SPAN}
    without = trace.reduce({k: v for k, v in kept.items() if k != "launches"})
    assert without["breakdown"] == trace.reduce(kept)["breakdown"]


def test_recorded_trace_readings(recorded):
    rec, k = recorded, recorded["per_program"]
    was = rec["readings"]
    is_coll = bench.load_cell("noc-collectives.2x2").runner().is_collective
    scopes = programs.scope_readings(rec["trace"], rec["scopes"], rec["programs"], k, is_coll)
    assert scopes == was["scopes"]
    assert programs.loop_idle_us(rec["trace"], k) == was["loop_idle_us"]
    assert programs.call_split(rec["trace"], rec["programs"], rec["clock"]) == was["split"]
    sched = programs.schedule_device_us(rec["trace"], rec["scopes"], rec["programs"], k)
    assert sched == was["schedule_device_us"]
    assert 0 < sched <= was["busy_us_per_application"]
    metric = bench.metric_reader("loop_idle_us")
    calls = was["calls"]
    assert metric({"trace": rec["trace"], "applications": calls * k, "attempted": calls}) == \
        was["loop_idle_us"]


def test_recorded_trace_scopes(recorded):
    """Each schedule's collective ops per application are whole and carry
    its scope, with two exceptions the compiler makes.  The three software
    reduce_scatter programs compile to one program, which JAX's compilation
    cache, blind to metadata, serves under the first one's scope; the native
    reduce_scatter at 1 KiB becomes an all-reduce with no op_name."""
    rec, k = recorded, recorded["per_program"]
    names = set(rec["scopes"]["names"])
    pairs = {f"schedules.{op}.{s}" for op in ("broadcast", "all_reduce")
             for s in ("native", "chain", "pipelined", "tree")}
    assert names == pairs | {"", "schedules.reduce_scatter.chain"}
    is_coll = bench.load_cell("noc-collectives.2x2").runner().is_collective
    for name, r in rec["readings"]["scopes"].items():
        assert name == "*" or r["collective_ops"] == round(r["collective_ops"]) > 0
    for chip, ops in rec["trace"]["devices"].items():
        unscoped = []
        for s, e in programs.inside(rec["programs"][chip], rec["trace"]["window"]):
            held = [(op, sc) for op, sc in zip(ops, rec["scopes"]["ops"][chip])
                    if s <= op[1] and op[1] + op[2] <= e]
            if not any(sc for _, sc in held):
                unscoped.append({op[0].split()[0] for op, _ in held if is_coll(op[0])})
        assert unscoped == [{"all-reduce"}]


def test_traced_run_notes_the_call_split_and_scopes(recorded, raw):
    """A traced collective run logs what ``programs.py`` reads from its trace:
    the call split and each scope's readings, as they were read on the chip."""
    from chipbench import run as bench_run

    path, kept = raw
    k, calls = recorded["per_program"], recorded["readings"]["calls"]
    is_coll = bench.load_cell("noc-collectives.2x2").runner().is_collective
    record = {"trace": kept, "applications": calls * k, "attempted": calls,
              "is_collective": is_coll}
    notes = bench_run.program_notes(path, record)
    was = recorded["readings"]
    assert notes[0] == f"call split, us per call on the aligned clock: {was['split']}"
    assert notes[1:] == [f"scope {name}, per application: {r}"
                         for name, r in was["scopes"].items()]
    assert bench_run.program_notes(path, dict(record, is_collective=None)) == []
