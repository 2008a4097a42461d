"""A run with its timed path broken underneath comes out ``correct: false``.

These drive the whole of a run but the look for a chip, at a small size on
four virtual CPU devices, against the cell's own limits; the control (the
reference one precision lower in the program's place) has to read far above
the program.
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

from chipbench.faults import FAULTS

ROOT = pathlib.Path(__file__).resolve().parents[2]


_COLLECTIVES = """
import json, sys
sys.path[:0] = [{root!r}, {root!r} + "/src"]
import jax
from chipbench import run as bench_run, study
import pytest

from chipbench.faults import FAULTS
from chipbench.tests import small
cell = small.collectives_cell()
out = {{"clean": bench_run.run(small.args(cell), cell=cell, require_chip=False)["correct"]}}
for name, hook in FAULTS["collectives"].items():
    out[name] = bench_run.run(small.args(cell), cell=cell, require_chip=False,
                              runner_hook=hook)["correct"]
prog, ctrl = study.readings(cell, small.SEED, jax, jax.devices()[:4], control=True)
out["program"], out["control"] = prog["numbers"]["max_error"], ctrl["numbers"]["max_error"]
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four_device_runs():
    """One process with four virtual CPU devices drives the clean run, each
    fault and the control, so the programs compile once for all the tests."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", _COLLECTIVES.format(root=str(ROOT))],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_collective_run_is_correct_when_nothing_is_broken(four_device_runs):
    assert four_device_runs["clean"] is True
    assert four_device_runs["program"] == 0.0


@pytest.mark.parametrize("fault", sorted(FAULTS["collectives"]))
def test_collective_fault_is_caught(four_device_runs, fault):
    assert four_device_runs[fault] is False


def test_collective_control_reads_far_above_the_program(four_device_runs):
    assert four_device_runs["control"] > 1e-3
