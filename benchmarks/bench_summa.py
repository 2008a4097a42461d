"""Figure 9a: SUMMA GEMM comm vs comp across mesh sizes + JAX execution.

The analytical part reproduces the paper's scaling study (4x4 .. 256x256);
the execution part runs the actual shard_map SUMMA on host devices via a
subprocess (8 virtual CPU devices), timing native vs software schedules;
a failed subprocess fails the module.
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

from repro.core.noc import model as m
from repro.core.noc.params import PAPER_GEMM

_SRC = pathlib.Path(__file__).resolve().parents[1] / "src"

_EXEC_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, time
import jax, jax.numpy as jnp
from repro.core.summa import summa_sharded

mesh = jax.make_mesh((2, 2), ("row", "col"), devices=jax.devices()[:4],
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
A = jax.random.normal(jax.random.PRNGKey(0), (256, 256), jnp.float32)
B = jax.random.normal(jax.random.PRNGKey(1), (256, 256), jnp.float32)
out = {}
for sched in ("native", "chain", "pipelined", "tree", "ring"):
    with jax.set_mesh(mesh):
        fn = jax.jit(lambda a, b: summa_sharded(a, b, mesh, "row", "col", schedule=sched))
        fn(A, B).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(20):
            r = fn(A, B)
        r.block_until_ready()
        out[sched] = (time.perf_counter() - t0) / 20 * 1e6
print("JSON:" + json.dumps(out))
"""


def rows():
    p = PAPER_GEMM
    out = []
    for pt in m.summa_sweep(p):
        out.append((f"summa_s{pt.mesh}_tcomm_sw", pt.t_comm_sw / 1e3, pt.sw_bound))
        out.append((f"summa_s{pt.mesh}_tcomm_hw", pt.t_comm_hw / 1e3, pt.hw_bound))
        out.append((f"summa_s{pt.mesh}_tcomp", pt.t_comp / 1e3, ""))
        out.append((f"summa_s{pt.mesh}_speedup", 0.0, round(pt.speedup, 2)))
    # execute the real shard_map SUMMA on virtual host devices, in a child
    # pinned to the CPU: on a chip machine the parent may hold the chip
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{_SRC}:{env.get('PYTHONPATH', '')}"
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _EXEC_SNIPPET],
                          capture_output=True, text=True, timeout=600, env=env)
    line = [l for l in proc.stdout.splitlines() if l.startswith("JSON:")]
    if proc.returncode != 0 or not line:
        raise RuntimeError(f"SUMMA child failed (rc={proc.returncode}): "
                           f"{proc.stderr[-300:]}")
    for sched, us in json.loads(line[0][5:]).items():
        out.append((f"summa_exec_2x2_{sched}", round(us, 1), ""))
    return out
