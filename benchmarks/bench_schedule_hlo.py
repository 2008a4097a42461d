"""HW-vs-SW collective schedules measured on COMPILED HLO (8 host devices).

The paper's central comparison — in-network collectives vs optimized
software schedules — reproduced at the XLA level: for a fixed tensor, each
schedule is lowered over an 8-way axis and its compiled collective traffic
is summed (launch/roofline.collective_bytes).  Native lowers to a single
fabric collective; the software schedules lower to collective-permute
chains with strictly more traffic and steps.
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

_SRC = pathlib.Path(__file__).resolve().parents[1] / "src"

_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.core import schedules as sched
from repro.launch.roofline import collective_bytes

mesh = jax.make_mesh((8,), ("x",), axis_types=(jax.sharding.AxisType.Auto,))
x = jax.ShapeDtypeStruct((8 * 512, 128), jnp.float32)  # 2 MiB global
out = {}
with jax.set_mesh(mesh):
    for op, fn in {
        "broadcast": lambda s: lambda v: sched.broadcast(v, "x", schedule=s, chunks=4),
        "all_reduce": lambda s: lambda v: sched.all_reduce(v, "x", schedule=s),
        "all_gather": lambda s: lambda v: sched.all_gather(v, "x", schedule=s)[None],
        "reduce_scatter": lambda s: lambda v: sched.reduce_scatter(v, "x", schedule=s),
    }.items():
        for s in ("native", "chain", "pipelined", "tree"):
            if op == "all_gather" and s == "pipelined":
                continue
            body = fn(s)
            mapped = partial(jax.shard_map, mesh=mesh, in_specs=(P("x", None),),
                             out_specs=P("x", None) if op != "all_gather" else P("x", None, None),
                             check_vma=False)(body)
            try:
                hlo = jax.jit(mapped).lower(x).compile().as_text()
                out[f"{op}_{s}"] = sum(collective_bytes(hlo).values())
            except Exception as e:
                out[f"{op}_{s}"] = f"fail:{e}"
print("JSON:" + json.dumps(out))
"""


def rows():
    # a child on 8 virtual CPU devices, pinned to the CPU: on a chip machine
    # the parent may hold the chip
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{_SRC}:{env.get('PYTHONPATH', '')}"
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _SNIPPET],
                          capture_output=True, text=True, timeout=900, env=env)
    line = [l for l in proc.stdout.splitlines() if l.startswith("JSON:")]
    if proc.returncode != 0 or not line:
        raise RuntimeError(f"schedule HLO child failed (rc={proc.returncode}): "
                           f"{proc.stderr[-300:]}")
    data = json.loads(line[0][5:])
    natives = {}
    for k, v in data.items():
        if isinstance(v, (int, float)):
            op = k.rsplit("_", 1)[0]
            if k.endswith("_native"):
                natives[op] = v
    out = []
    for k, v in sorted(data.items()):
        if isinstance(v, str):
            out.append((f"hlo_{k}", 0.0, v))
            continue
        op = k.rsplit("_", 1)[0]
        ratio = round(v / natives[op], 2) if natives.get(op) else ""
        out.append((f"hlo_{k}_bytes_per_dev", 0.0, f"{v} ({ratio}x native)"))
    return out
