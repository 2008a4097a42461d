"""The op_name of every collective op each public collective compiles to
(4 host devices), printed as one JSON line: {"<op>.<schedule>": [op_name, ...]}.

Run by tests/test_multidevice.py in a subprocess so the main pytest process
keeps a single device.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import json
import re

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import schedules as sched

N = 4
OPS = {
    "broadcast": lambda x, s: sched.broadcast(x, "x", root=1, schedule=s, chunks=2),
    "all_reduce": lambda x, s: sched.all_reduce(x, "x", schedule=s),
    "all_gather": lambda x, s: sched.all_gather(x, "x", schedule=s),
    "reduce_scatter": lambda x, s: sched.reduce_scatter(x, "x", schedule=s),
    "barrier": lambda x, s: x + sched.barrier("x", schedule=s),
}
COLLECTIVE = re.compile(r"= .*? (all-reduce|all-gather|reduce-scatter|collective-permute"
                        r"|all-to-all)(-start|-done)?\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def collective_op_names(op: str, schedule: str) -> list[str]:
    mesh = jax.make_mesh((N,), ("x",))
    fn = jax.jit(jax.shard_map(lambda x: OPS[op](x, schedule), mesh=mesh,
                               in_specs=P("x"), out_specs=P("x"), check_vma=False))
    text = fn.lower(jnp.zeros((N * 8, 16), jnp.float32)).compile().as_text()
    names = []
    for line in text.splitlines():
        if COLLECTIVE.search(line):
            m = OP_NAME.search(line)
            names.append(m.group(1) if m else "")
    return names


if __name__ == "__main__":
    out = {f"{op}.{s}": collective_op_names(op, s) for op in OPS for s in sched.SCHEDULES}
    print(json.dumps(out))
    print("ALL OK")
