#!/usr/bin/env python3
"""Readings that set a cell's limits, from many seeds in one process.

  python3 chipbench/study.py --workload <name> --seeds 1,2,3 \\
      [--control-seeds 1,2,3] [--faults no_exchange:1,2,3] [--out <file>]

For each seed it builds the cell's timed path as a run does (set-up drives
the first calls), frees the program's state and compares with the plain
reference: the lower readings.  For each control seed it puts the reference
computed one precision lower in the program's place (the upper readings);
for each planted fault, the program with that fault.  One JSON line per
reading goes to ``--out`` and to stdout.  Not a part of any run of the
benchmark.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parent / "src")]


def seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def readings(cell, seed, jax, devices, fault=None, control=False) -> list[dict]:
    """The program's comparison (with ``fault`` planted, if given) and, with
    ``control``, the control's, against one reference."""
    from chipbench.faults import FAULTS

    mod = cell.runner()
    runner = mod.Runner(cell, seed, devices, jax, limits={})
    if fault:
        FAULTS[cell.traffic["kind"]][fault](runner)
    t0 = time.perf_counter()
    runner.setup()
    runner.release()
    ref = runner.reference()
    out = [{"seed": seed, "what": fault or "program",
            "numbers": mod.compare(runner.readings, ref),
            "seconds": time.perf_counter() - t0}]
    if control:
        out.append({"seed": seed, "what": "control",
                    "numbers": mod.compare(runner.reference(mod.CONTROL), ref)})
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True, help="a cell of BENCHMARK.json")
    p.add_argument("--seeds", type=seeds, default=[])
    p.add_argument("--control-seeds", type=seeds, default=[])
    p.add_argument("--faults", action="append", default=[],
                   help="<fault>:<seed>,<seed>,...")
    p.add_argument("--out")
    args = p.parse_args(argv)

    from chipbench.run import keep_tpu_logs, use_cache

    keep_tpu_logs()
    import jax

    from chipbench import device as dev
    from chipbench.bench import load_cell

    cell = load_cell(args.workload)
    use_cache(jax)
    devices = dev.chips(jax, cell.chips)
    jobs = [(None, s, s in args.control_seeds) for s in args.seeds]
    jobs += [(None, s, True) for s in args.control_seeds if s not in args.seeds]
    for spec in args.faults:
        name, _, ss = spec.partition(":")
        jobs += [(name, s, False) for s in seeds(ss)]
    out = open(args.out, "a") if args.out else None
    for fault, seed, control in jobs:
        for r in readings(cell, seed, jax, devices, fault, control):
            line = json.dumps(r)
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
