"""The collective runner's calls move nothing from the host, and its loop
programs still take the trip count at run time.

One process with four virtual CPU devices sets up the collective cell at a
small size, runs its window with host-to-device transfers disallowed, and
lowers each case's program twice: with the trip count the window passes (on
the chips since set-up) and with it as a Python int, as a call from the host
would pass it.
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

from chipbench import bench
from chipbench.tests import small

ROOT = pathlib.Path(__file__).resolve().parents[2]

_SCRIPT = """
import json, re, sys
sys.path[:0] = [{root!r}, {root!r} + "/src"]
import jax
from chipbench.tests import small

OPCODE = re.compile(r"^\\s*(?:ROOT )?\\S+ = .*? ([a-z][a-z0-9-]*)\\(")
ENTRY_PARAM = re.compile(r"^\\s*\\S+ = (\\S+) parameter\\((\\d)\\)")


def shape(hlo):
    lines = hlo.splitlines()
    entry = lines[next(i for i, l in enumerate(lines) if l.startswith("ENTRY")):]
    params = dict(reversed(m.groups()) for m in map(ENTRY_PARAM.match, entry) if m)
    return {{"opcodes": [m.group(1) for m in map(OPCODE.match, lines) if m],
             "params": params}}


cell = small.collectives_cell()
runner = cell.runner().Runner(cell, small.SEED, jax.devices()[:4], jax, {{}})
runner.setup()
out = {{"cases": {{}}}}
try:
    with jax.transfer_guard_host_to_device("disallow"):
        out["window"] = runner.window(0.2)["attempted"]
except Exception as e:  # the refused transfer, reported to the test
    out["window"] = f"{{type(e).__name__}}: {{e}}"
for case in runner.cases:
    fn, state = runner.fns[case], runner.state[case]
    out["cases"][case] = {{
        "window": shape(fn.lower(state, runner.k_on_chips).as_text(dialect="hlo")),
        "host_int": shape(fn.lower(state, runner.k).as_text(dialect="hlo")),
    }}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", _SCRIPT.format(root=str(ROOT))],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


is_collective = bench.load_cell(small.COLLECTIVES).runner().is_collective


def _cases():
    tr = small.collectives_cell().traffic
    return [f"{op}/{s}/{kib}KiB" for op in tr["ops"] for s in tr["schedules"]
            for kib in tr["sizes_kib"]]


def test_window_moves_nothing_from_the_host(four_devices):
    """Every call of the window runs with host-to-device transfers disallowed:
    the trip count was put on the chips at set-up, not passed from the host."""
    cases = len(_cases())
    assert isinstance(four_devices["window"], int), four_devices["window"]
    assert four_devices["window"] >= cases and four_devices["window"] % cases == 0


@pytest.mark.parametrize("case", _cases())
def test_loop_takes_the_trip_count_at_run_time(four_devices, case):
    """The program the window calls takes the trip count as an ``s32[]``
    parameter and holds the same instructions, its ``while`` and
    collectives among them, as with the count passed as a Python int: the
    count is not a constant the compiler could unroll the loop by."""
    got = four_devices["cases"][case]
    assert got["window"]["params"] == {"0": got["window"]["params"]["0"], "1": "s32[]"}
    assert got["window"] == got["host_int"]
    assert "while" in got["window"]["opcodes"]
    assert any(is_collective(op) for op in got["window"]["opcodes"])
