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
SOFTWARE = ("chain", "pipelined", "tree")
N_SCOPES = 4  # host devices of schedule_scopes_prog.py


@pytest.fixture(scope="module")
def scopes_prog_lines():
    """The two JSON lines of ``schedule_scopes_prog.py`` (4 host devices)."""
    out = run_prog("schedule_scopes_prog.py", timeout=300).splitlines()
    return json.loads(out[0]), json.loads(out[1])


@pytest.fixture(scope="module")
def collective_op_names(scopes_prog_lines):
    """``{"<op>.<schedule>": [op_name of each collective op]}`` from the
    compiled HLO of every public collective on 4 host devices."""
    return scopes_prog_lines[0]


@pytest.fixture(scope="module")
def permute_pairs(scopes_prog_lines):
    """``{"<op>.<schedule>[.root<r>]": [(src, dst) pairs of each permute]}``."""
    return scopes_prog_lines[1]


@pytest.mark.parametrize("schedule", SCHEDULES)
@pytest.mark.parametrize("op", COLLECTIVES)
def test_collective_ops_carry_their_schedule_scope(collective_op_names, op, schedule):
    names = collective_op_names[f"{op}.{schedule}"]
    assert names, f"{op} under {schedule} compiled to no collective op"
    for name in names:
        assert f"/schedules.{op}.{schedule}/" in name, name


def paper_permutes(schedule: str, root: int) -> list[list[list[int]]]:
    """The unicasts ``broadcast_ops`` emits, grouped as the schedule's permutes:
    one per chain hop (per chunk when pipelined), one per tree stage."""
    from repro.core.noc.program import ProgramBuilder
    from repro.core.schedules import broadcast_ops
    from repro.core.topology import Coord, Mesh2D

    b = ProgramBuilder(Mesh2D(N_SCOPES, 1))
    broadcast_ops(b, [Coord(i, 0) for i in range(N_SCOPES)], root=root, nbytes=4096,
                  schedule=schedule, chunks=2)
    hops = [(op.start, [op.src[0], op.dst[0]]) for op in b.build().ops]
    if schedule == "tree":
        return [[pair for t, pair in hops if t == stage] for stage in sorted({t for t, _ in hops})]
    return [[pair] for _, pair in hops]


def as_multiset(permutes):
    return sorted(sorted(pairs) for pairs in permutes)


@pytest.mark.parametrize("root", range(N_SCOPES))
@pytest.mark.parametrize("schedule", SOFTWARE)
def test_software_broadcast_sends_only_the_papers_hops(permute_pairs, schedule, root):
    want = paper_permutes(schedule, root)
    sizes = {"chain": [1] * 3, "pipelined": [1] * 6, "tree": [1, 2]}[schedule]
    assert sorted(map(len, want)) == sizes  # one pair per hop; 2^i at tree stage i
    assert as_multiset(permute_pairs[f"broadcast.{schedule}.root{root}"]) == as_multiset(want)


RING = [[i, (i + 1) % N_SCOPES] for i in range(N_SCOPES)]


def xor_pairs(mask: int) -> list[list[int]]:
    return [[i, i ^ mask] for i in range(N_SCOPES)]


REDUCTION_PERMUTES = {
    "all_reduce.chain": [RING] * 3,
    "all_reduce.pipelined": [RING] * 6,
    "all_reduce.tree": [xor_pairs(1), xor_pairs(2)],
    "reduce_scatter.chain": [RING] * 3,
    "reduce_scatter.pipelined": [RING] * 3,
    "reduce_scatter.tree": [RING] * 3,
}


@pytest.mark.parametrize("case", sorted(REDUCTION_PERMUTES))
def test_software_reductions_keep_every_pair(permute_pairs, case):
    assert as_multiset(permute_pairs[case]) == as_multiset(REDUCTION_PERMUTES[case])
