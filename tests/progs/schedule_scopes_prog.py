"""What each public collective compiles to on 4 host devices, as two JSON lines.

Line 1, {"<op>.<schedule>": [op_name, ...]}: the op_name of every collective op.
Line 2, {"<op>.<schedule>[.root<r>]": [[[src, dst], ...], ...]}: the
source_target_pairs of every collective-permute, for the software broadcasts
under every root and for the software all_reduce and reduce_scatter.

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
SOFTWARE = ("chain", "pipelined", "tree")
OPS = {
    "broadcast": lambda x, s, root=1: sched.broadcast(x, "x", root=root, schedule=s, chunks=2),
    "all_reduce": lambda x, s: sched.all_reduce(x, "x", schedule=s),
    "all_gather": lambda x, s: sched.all_gather(x, "x", schedule=s),
    "reduce_scatter": lambda x, s: sched.reduce_scatter(x, "x", schedule=s),
    "barrier": lambda x, s: x + sched.barrier("x", schedule=s),
}
COLLECTIVE = re.compile(r"= .*? (all-reduce|all-gather|reduce-scatter|collective-permute"
                        r"|all-to-all)(-start|-done)?\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')
PAIRS = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)*)\}")


def compiled_text(body) -> str:
    mesh = jax.make_mesh((N,), ("x",))
    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("x"), out_specs=P("x"),
                               check_vma=False))
    return fn.lower(jnp.zeros((N * 8, 16), jnp.float32)).compile().as_text()


def collective_op_names(text: str) -> list[str]:
    names = []
    for line in text.splitlines():
        if COLLECTIVE.search(line):
            m = OP_NAME.search(line)
            names.append(m.group(1) if m else "")
    return names


def permute_pairs(text: str) -> list[list[list[int]]]:
    """The (src, dst) pairs of each collective-permute, in program order."""
    out = []
    for line in text.splitlines():
        m = PAIRS.search(line)
        if m and "collective-permute" in line:
            out.append([[int(a), int(b)] for a, b in re.findall(r"(\d+),(\d+)", m.group(1))])
    return out


if __name__ == "__main__":
    texts = {f"{op}.{s}": compiled_text(lambda x, op=op, s=s: OPS[op](x, s))
             for op in OPS for s in sched.SCHEDULES}
    print(json.dumps({k: collective_op_names(t) for k, t in texts.items()}))
    pairs = {f"broadcast.{s}.root{r}": permute_pairs(compiled_text(
        lambda x, s=s, r=r: OPS["broadcast"](x, s, root=r))) for s in SOFTWARE for r in range(N)}
    pairs.update({f"{op}.{s}": permute_pairs(texts[f"{op}.{s}"])
                  for op in ("all_reduce", "reduce_scatter") for s in SOFTWARE})
    print(json.dumps(pairs))
    print("ALL OK")
