"""Integration tests that need >1 device: run progs in subprocesses.

Each prog sets XLA_FLAGS=--xla_force_host_platform_device_count (8, or 4) before
importing jax, which must happen in a fresh process (the main pytest
process keeps 1 device so smoke tests see the default environment).
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

PROGS = pathlib.Path(__file__).parent / "progs"
SRC = pathlib.Path(__file__).parent.parent / "src"


def run_prog(name: str, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{SRC}:{env.get('PYTHONPATH', '')}"
    env.pop("XLA_FLAGS", None)  # the prog sets its own
    proc = subprocess.run(
        [sys.executable, str(PROGS / name)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert proc.returncode == 0, (
        f"{name} failed (rc={proc.returncode})\n--- stdout ---\n{proc.stdout}"
        f"\n--- stderr ---\n{proc.stderr}"
    )
    assert "ALL OK" in proc.stdout, f"{name} did not complete:\n{proc.stdout}"
    return proc.stdout


def test_collective_schedules_8dev():
    run_prog("collectives_prog.py")


def test_summa_fcl_overlap_8dev():
    run_prog("gemm_prog.py")


def test_dp_compressed_training_and_elastic_8dev():
    run_prog("dp_train_prog.py")


def test_dryrun_plumbing_every_family_8dev():
    run_prog("dryrun_smoke_prog.py")


COLLECTIVES = ("broadcast", "all_reduce", "all_gather", "reduce_scatter", "barrier")
SCHEDULES = ("native", "chain", "pipelined", "tree")


@pytest.fixture(scope="module")
def collective_op_names():
    """``{"<op>.<schedule>": [op_name of each collective op]}`` from the
    compiled HLO of every public collective on 4 host devices."""
    return json.loads(run_prog("schedule_scopes_prog.py", timeout=300).splitlines()[0])


@pytest.mark.parametrize("schedule", SCHEDULES)
@pytest.mark.parametrize("op", COLLECTIVES)
def test_collective_ops_carry_their_schedule_scope(collective_op_names, op, schedule):
    names = collective_op_names[f"{op}.{schedule}"]
    assert names, f"{op} under {schedule} compiled to no collective op"
    for name in names:
        assert f"/schedules.{op}.{schedule}/" in name, name
