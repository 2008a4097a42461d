"""Cells of BENCHMARK.json cut to a size the CPU runs in seconds, for the tests."""

import argparse
import dataclasses

from chipbench import bench

COLLECTIVES = "noc-collectives.2x2"
SEED = 2**33 + 77


def collectives_cell():
    cell = bench.load_cell(COLLECTIVES)
    return dataclasses.replace(cell, traffic=dict(cell.traffic, sizes_kib=[1, 2],
                                                  calls_per_case=4))


def args(cell, seed=SEED):
    return argparse.Namespace(workload=cell.name, seed=seed, seconds=0.2, trace=0)
