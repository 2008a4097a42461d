"""Every cell of BENCHMARK.json resolves, by name, to files of its own."""

import json
import pathlib
import re

import pytest

from chipbench import bench

ROOT = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WORKLOADS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys_and_paths():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["chipbench"]
    assert SPEC["command"][1].startswith("chipbench/")
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_units_and_entry_keys():
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"} and NAME.match(c["name"])
        assert c["file"].startswith("chipbench/") and (ROOT / c["file"]).is_file()
        assert all(NAME.match(k) for k in c["reduced"])
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", WORKLOADS)) <= set(WORKLOADS)
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    moves = {m["name"] for m in SPEC["end_to_end"]}
    assert all(m["moves"] in moves for m in SPEC["per_layer"])
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(1, len(WORKLOADS) // 2)


def test_reduced_keys_differ_only_where_listed():
    """A configuration file keeps every size of its source but those in ``reduced``."""
    for c in SPEC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
    mix = json.loads((ROOT / "chipbench/traffic/collective-mix.json").read_text())
    # the paper's message sizes, sections 4-5: 1-32 KiB per member
    assert mix["sizes_kib"] == [1, 2, 4, 8, 16, 32]
    assert set(mix["schedules"]) == {"native", "chain", "pipelined", "tree"}


@pytest.mark.parametrize("workload", WORKLOADS)
def test_workload_resolves_to_its_files(workload):
    cell = bench.load_cell(workload)
    assert cell.config["name"] == cell.config_name
    assert hasattr(cell.runner(), "Runner") and hasattr(cell.runner(), "compare")
    assert cell.reference() is not None
    limits = json.loads((ROOT / "chipbench/limits" / f"{workload}.json").read_text())["limits"]
    assert limits and all(v >= 0 for v in limits.values())
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(bench.metric_reader(m["name"]))


# Every committed per-layer metric, and every reader under ``metrics/``: the
# train cell's two readers wait there for the cell that lists them.
READERS = sorted({m["name"] for m in SPEC["per_layer"]}
                 | {p.stem for p in (bench.HERE / "metrics").glob("*.py")})


@pytest.mark.parametrize("metric", READERS)
def test_metric_reader_finds_nothing_in_an_empty_record(metric):
    assert bench.metric_reader(metric)({}) is None


def test_metrics_report_only_where_what_they_move_reports():
    """A per-layer metric reports in no cell that lacks the end-to-end metric
    it moves, and the collective cell reports none of the train metrics."""
    cells = {m["name"]: set(m.get("workloads", WORKLOADS))
             for m in SPEC["end_to_end"] + SPEC["per_layer"]}
    for m in SPEC["per_layer"]:
        assert cells[m["name"]] <= cells[m["moves"]], m["name"]
    cell = bench.load_cell("noc-collectives.2x2")
    reported = {m["name"] for m in cell.end_to_end + cell.per_layer}
    assert not reported & {"train_tokens_per_s", "mfu.train", "idle_share.train"}
