"""Resolves a cell of ``BENCHMARK.json`` to its files, by name."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
BENCHMARK = ROOT / "BENCHMARK.json"


def load_module(path: pathlib.Path, name: str | None = None):
    """Imports a file by path (metric files have dots in their names)."""
    spec = importlib.util.spec_from_file_location(name or f"chipbench_{path.stem}", path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict          # configs/<config>.json, as run
    traffic_name: str
    traffic: dict         # traffic/<traffic>.json
    end_to_end: tuple     # metric entries this cell reports with --trace 0
    per_layer: tuple      # metric entries this cell reports with --trace 1

    def reference(self):
        return load_module(HERE / "reference" / f"{self.config['reference']}.py",
                           f"chipbench_ref_{self.config['reference']}")

    def runner(self):
        return load_module(HERE / "runners" / f"{self.traffic['kind']}.py",
                           f"chipbench_runner_{self.traffic['kind']}")


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def metric_reader(name: str):
    """``metrics/<name>.py``; its ``read(record)`` returns a number or None."""
    return load_module(HERE / "metrics" / f"{name}.py", f"chipbench_metric_{name}").read


def load_cell(workload: str, benchmark: pathlib.Path = BENCHMARK) -> Cell:
    spec = json.loads(benchmark.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in {benchmark.name}; have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config_entry = configs[w["config"]]
    config = json.loads((benchmark.parent / config_entry["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(
        name=workload, chips=int(w["chips"]),
        config_name=w["config"], config=config,
        traffic_name=w["traffic"], traffic=traffic,
        end_to_end=tuple(m for m in spec["end_to_end"] if _reports(m, workload)),
        per_layer=tuple(m for m in spec["per_layer"] if _reports(m, workload)),
    )
