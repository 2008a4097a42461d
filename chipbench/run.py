#!/usr/bin/env python3
"""Runs one cell of BENCHMARK.json once, on the TPU, and prints its result.

  python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (process start to the first timed operation: loading, weights,
compiling and warming every shape the cell uses) is ``setup_s``.  Then the
cell's runner measures for ``--seconds`` with the profiler off
(``--trace 0``: the cell's end-to-end metrics) or traces a short window
(``--trace 1``: its per-layer metrics, read by ``metrics/<name>.py``; a
collective window also logs its call split and per-scope readings).
After the window the program's state is freed and the runner compares what
the timed path produced with the plain reference; each number compared is
printed beside its limit, last on standard error and last in the result.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with ``--trace 1``
``breakdown``), then ``checks``.  Without a TPU, or with fewer chips than the
cell asks for, the run exits with code 3 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
NO_CHIP_EXIT = 3
NO_PROGRAM_EXIT = 4
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = HERE / "out" / "trace"
TPU_LOG_DIR = HERE / "out" / "tpu_logs"  # libtpu's own logs, else under /tmp
LAST_TRACE = HERE / "out" / "last_trace.json"  # the plain trace the reduction read


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_limits(workload: str) -> dict:
    return json.loads((HERE / "limits" / f"{workload}.json").read_text())["limits"]


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def setup_paths() -> None:
    """The benchmark's own package and the program under test (``src/``)."""
    if not (ROOT / "src" / "repro").is_dir():
        log(f"no program under test: {ROOT / 'src' / 'repro'} is missing")
        sys.exit(NO_PROGRAM_EXIT)
    for path in (str(ROOT), str(ROOT / "src")):
        if path not in sys.path:
            sys.path.insert(0, path)


def keep_tpu_logs() -> None:
    """libtpu logs to a fixed path under /tmp unless told otherwise."""
    if "TPU_LOG_DIR" not in os.environ:
        TPU_LOG_DIR.mkdir(parents=True, exist_ok=True)
        os.environ["TPU_LOG_DIR"] = str(TPU_LOG_DIR)


def use_cache(jax) -> None:
    """JAX's persistent compilation cache, at a fixed path in the checkout."""
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def result_metrics(cell, record: dict, setup_s: float, trace: bool) -> dict:
    from chipbench.bench import metric_reader

    out = {}
    if not trace:
        for m in cell.end_to_end:
            value = setup_s if m["name"] == "setup_s" else record[m["name"]]
            out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out
    for m in cell.per_layer:
        value = metric_reader(m["name"])(record)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def program_notes(trace_dir, record: dict) -> list[str]:
    """Of a traced collective window: each call split into launch, program,
    return and the time between calls on the aligned clock, and the device
    time and collective ops per application of each schedule's scope
    (``programs.py``).  Notes only, no metric."""
    if not record.get("is_collective") or not record.get("attempted"):
        return []
    from chipbench import programs

    trace = record["trace"]
    try:
        read = programs.read(trace_dir, trace["devices"])
    except ValueError as e:  # the file's ops are not the ones extract kept
        return [f"program readings: not read ({e})"]
    per_program = record["applications"] // record["attempted"]
    split = programs.call_split(trace, read["programs"], read["clock"])
    scopes = programs.scope_readings(trace, read["scopes"], read["programs"], per_program,
                                     record["is_collective"])
    return ([f"call split, us per call on the aligned clock: {split}"]
            + [f"scope {name}, per application: {r}" for name, r in scopes.items()])


def run(args, cell=None, limits=None, require_chip: bool = True, runner_hook=None) -> dict:
    """One run; returns the result object.  ``cell``, ``limits``,
    ``require_chip=False`` and ``runner_hook`` (called with the runner before
    set-up) are for the tests, which drive a run on the CPU at a small size,
    some with the timed path broken underneath."""
    from chipbench.bench import load_cell
    from chipbench.clock import CompileClock
    from chipbench import device as dev

    cell = cell or load_cell(args.workload)
    limits = limits or load_limits(cell.name)
    import jax

    if require_chip:
        use_cache(jax)
        devices = dev.chips(jax, cell.chips)
    else:
        devices = jax.devices()[: cell.chips]
    clock = CompileClock(jax)
    runner = cell.runner().Runner(cell, args.seed, devices, jax, limits)
    if runner_hook is not None:
        runner_hook(runner)
    runner.setup()
    setup_s = time.perf_counter() - T_START
    log(f"set-up: {setup_s} s, of which compiling {clock.seconds} s ({clock.compiles} compiles)")

    trace_dir = None
    if args.trace:
        trace_dir = TRACE_DIR
        shutil.rmtree(trace_dir, ignore_errors=True)
    compiles_before = clock.compiles
    record = runner.window(args.seconds, trace_dir)
    window_compiles = clock.compiles - compiles_before
    device = dev.describe(devices)
    record["peaks"] = dev.peaks(device["kind"]) if require_chip else {}
    if args.trace:
        from chipbench import trace as tr

        t0 = time.perf_counter()
        record["trace"] = tr.extract(trace_dir)
        record["reduced"] = tr.reduce(record["trace"])
        device["busy_s"] = record["reduced"]["busy_s"]
        device["window_s"] = record["reduced"]["window_s"]
        record.setdefault("notes", []).extend(program_notes(trace_dir, record))
        log(f"trace read in {time.perf_counter() - t0} s")
        shutil.rmtree(trace_dir, ignore_errors=True)
        LAST_TRACE.write_text(json.dumps({"trace": record["trace"], "reduced": record["reduced"]}))
    runner.release()

    t0 = time.perf_counter()
    checks = runner.check()
    log(f"reference and comparison: {time.perf_counter() - t0} s")
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values()) and record["failed"] == 0

    result = {
        "correct": correct,
        "attempted": record["attempted"],
        "failed": record["failed"],
        "metrics": result_metrics(cell, record, setup_s, bool(args.trace)),
        "device": device,
    }
    if args.trace:
        result["breakdown"] = record["reduced"]["breakdown"]
    result["checks"] = checks

    for note in record.get("notes", []):
        log(note)
    log(f"compiles inside the window: {window_compiles}")
    log(f"peak device memory: {device['memory_peak_bytes']} bytes")
    log(f"attempted {record['attempted']}, failed {record['failed']}")
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    return result


def main(argv=None) -> int:
    args = parse(argv)
    setup_paths()
    keep_tpu_logs()
    from chipbench.device import NoChip

    try:
        result = run(args)
    except NoChip as e:
        log(f"no chip: {e}")
        return NO_CHIP_EXIT
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
