"""Multi-device validation of every collective schedule (8 host devices).

Run by tests/test_multidevice.py in a subprocess so the main pytest process
keeps a single device.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import schedules as sched

N = 8
mesh = jax.make_mesh((N,), ("x",))


def run_spmd(fn, *args, in_specs, out_specs):
    return jax.jit(
        partial(jax.shard_map, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                check_vma=False)(fn)
    )(*args)


def check_broadcast():
    x = jnp.arange(N * 4 * 6, dtype=jnp.float32).reshape(N * 4, 6)  # integers: exact
    for schedule in ("native", "chain", "pipelined", "tree"):
        for root in range(N):
            out = run_spmd(
                lambda xs: sched.broadcast(xs, "x", root=root, schedule=schedule, chunks=2),
                x, in_specs=(P("x", None),), out_specs=P("x", None))
            expected = np.tile(np.asarray(x).reshape(N, 4, 6)[root], (N, 1, 1)).reshape(N * 4, 6)
            np.testing.assert_array_equal(np.asarray(out), expected,
                                          err_msg=f"broadcast {schedule} root={root}")
    for schedule in ("chain", "pipelined", "tree"):  # their permute pairs need a static root
        try:
            run_spmd(lambda xs, r: sched.broadcast(xs, "x", root=r[0], schedule=schedule,
                                                   chunks=2),
                     x, jnp.ones((N,), jnp.int32), in_specs=(P("x", None), P("x")),
                     out_specs=P("x", None))
        except TypeError as e:
            assert "static integer root" in str(e), e
        else:
            raise AssertionError(f"broadcast {schedule} took a traced root")
    print("broadcast ok")


def check_all_reduce():
    x = jax.random.normal(jax.random.PRNGKey(0), (N * 4, 6))
    expected = np.tile(np.asarray(x).reshape(N, 4, 6).sum(0), (N, 1, 1)).reshape(N * 4, 6)
    for schedule in ("native", "chain", "pipelined", "tree"):
        out = run_spmd(lambda xs: sched.all_reduce(xs, "x", schedule=schedule),
                       x, in_specs=(P("x", None),), out_specs=P("x", None))
        np.testing.assert_allclose(np.asarray(out), expected, rtol=2e-5, atol=1e-5,
                                   err_msg=f"all_reduce {schedule}")
    print("all_reduce ok")


def check_all_gather():
    x = jax.random.normal(jax.random.PRNGKey(1), (N * 2, 5))
    expected = np.tile(np.asarray(x), (N, 1, 1)).reshape(N, N * 2, 5)
    for schedule in ("native", "chain", "tree"):
        out = run_spmd(lambda xs: sched.all_gather(xs, "x", schedule=schedule)[None],
                       x, in_specs=(P("x", None),), out_specs=P("x", None, None))
        np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-6,
                                   err_msg=f"all_gather {schedule}")
    print("all_gather ok")


def check_reduce_scatter():
    x = jax.random.normal(jax.random.PRNGKey(2), (N, N * 2, 5))  # one (N*2,5) per dev
    full = np.asarray(x).sum(0)
    for schedule in ("native", "chain"):
        out = run_spmd(lambda xs: sched.reduce_scatter(xs[0], "x", schedule=schedule),
                       x, in_specs=(P("x", None, None),), out_specs=P("x", None))
        np.testing.assert_allclose(np.asarray(out), full, rtol=2e-5, atol=1e-5,
                                   err_msg=f"reduce_scatter {schedule}")
    print("reduce_scatter ok")


def check_barrier():
    for schedule in ("native", "tree"):
        out = run_spmd(lambda xs: (sched.barrier("x", schedule=schedule) * 0 + xs).sum()[None],
                       jnp.ones((N,)), in_specs=(P("x"),), out_specs=P("x"))
        assert out.shape == (N,)
    print("barrier ok")


if __name__ == "__main__":
    check_broadcast()
    check_all_reduce()
    check_all_gather()
    check_reduce_scatter()
    check_barrier()
    print("ALL OK")
