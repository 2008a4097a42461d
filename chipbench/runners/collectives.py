"""Runner of ``kind: "collectives"`` mixes: the paper's schedules on a device mesh.

Each case (op, schedule, size) is one jitted ``shard_map`` program over the
mesh axis whose body is a loop, with a trip count passed at run time, of
``calls_per_case`` applications of the op from ``repro.core.schedules``;
each application takes the previous one's output.  The trip count is put on
the chips once, at set-up, so a call moves nothing from the host.  The
window cycles the cases round-robin, one program call at a time, each
waited for, and every call carries its case's buffers into the next.  ``collective_us`` is the
window over all collective applications completed.

After the window every case's buffers are compared, exactly, with the plain
reference (``reference/collectives.py``): the payload is integers, which
every schedule sums without rounding.
"""

from __future__ import annotations

import collections
import time

import numpy as np

from chipbench.clock import span
from chipbench.generate import payload

# The control: the reference with bfloat16 payload and sums.
CONTROL = "bf16"
COLLECTIVE_OPS = ("all-reduce", "reduce-scatter", "collective-permute", "all-gather",
                  "all-to-all")


def is_collective(op_name: str) -> bool:
    return any(k in op_name for k in COLLECTIVE_OPS)


def compare(prog: dict, ref: dict) -> dict:
    """Largest |program - reference| over the largest |reference|, any case."""
    worst = 0.0
    for case, want in ref["outputs"].items():
        got = np.asarray(prog["outputs"][case], np.float64)
        scale = max(float(np.max(np.abs(want))), 1e-30)
        worst = max(worst, float(np.max(np.abs(got - want))) / scale)
    return {"max_error": worst}


class Runner:
    def __init__(self, cell, seed: int, devices, jax, limits: dict):
        self.cell, self.seed, self.devices, self.jax = cell, seed, devices, jax
        self.cfg, self.traffic, self.limits = cell.config, cell.traffic, limits
        self.failed = 0
        self.wrap_op = None  # a test's fault, planted under the timed op

    def _op(self, op: str):
        from repro.core import schedules as sched

        tr, n = self.traffic, len(self.devices)
        inv = 1.0 / n
        fns = {
            "broadcast": lambda x, axis, s: sched.broadcast(x, axis, root=tr["root"],
                                                            schedule=s, chunks=tr["chunks"]),
            "all_reduce": lambda x, axis, s: sched.all_reduce(x, axis, schedule=s) * inv,
            "reduce_scatter": lambda x, axis, s: sched.reduce_scatter(x, axis, schedule=s) * inv,
        }
        fn = fns[op]
        if self.wrap_op is not None:
            fn = self.wrap_op(op, fn)
        if op == "reduce_scatter":  # back to the buffer's size, so the loop can go on
            return lambda x, axis, s: self.jax.numpy.tile(fn(x, axis, s), (n, 1))
        return fn

    def setup(self) -> None:
        jax = self.jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        tr, cfg = self.traffic, self.cfg
        n, axis = len(self.devices), cfg["mesh_axis"]
        self.mesh = jax.make_mesh((n,), (axis,), devices=self.devices,
                                  axis_types=(jax.sharding.AxisType.Auto,))
        sharding = NamedSharding(self.mesh, P(axis))
        self.k = int(tr["calls_per_case"])
        self.k_on_chips = jax.device_put(np.int32(self.k), NamedSharding(self.mesh, P()))
        self.cases, self.fns, self.state, self.inputs = [], {}, {}, {}
        for op in tr["ops"]:
            for schedule in tr["schedules"]:
                for kib in tr["sizes_kib"]:
                    case = f"{op}/{schedule}/{kib}KiB"
                    rows = kib * 1024 // (4 * tr["cols"])
                    host = payload(self.seed, len(self.cases), (n * rows, tr["cols"]),
                                   tr["payload_range"])
                    self.inputs[case] = host.reshape(n, rows, tr["cols"])
                    self.state[case] = jax.device_put(host, sharding)
                    self.fns[case] = self._program(op, schedule, axis)
                    self.cases.append(case)
        self.calls = collections.Counter()
        self.case_s = collections.Counter()
        for case in self.cases:  # compile and run each once
            self._call(case)

    def _program(self, op, schedule, axis):
        jax = self.jax
        from jax.sharding import PartitionSpec as P

        fn = self._op(op)

        def loop(x, k):
            return jax.lax.fori_loop(0, k, lambda _, y: fn(y, axis, schedule), x)

        mapped = jax.shard_map(loop, mesh=self.mesh, in_specs=(P(axis), P()),
                               out_specs=P(axis), check_vma=False)
        return jax.jit(mapped, donate_argnums=0)

    def _call(self, case: str) -> None:
        t0 = time.perf_counter()
        with span("call"):
            self.state[case] = self.fns[case](self.state[case], self.k_on_chips)
            self.state[case].block_until_ready()
        self.case_s[case] += time.perf_counter() - t0
        self.calls[case] += 1

    def window(self, seconds: float, trace_dir=None) -> dict:
        jax = self.jax
        rounds = self.traffic["trace_rounds"] if trace_dir is not None else None
        self.case_s.clear()
        calls_before = sum(self.calls.values())
        if trace_dir is not None:
            jax.profiler.start_trace(str(trace_dir))
        t0 = time.perf_counter()
        done = 0
        with span("window"):
            while True:
                with span("round"):
                    for case in self.cases:
                        self._call(case)
                done += 1
                elapsed = time.perf_counter() - t0
                if (rounds is not None and done >= rounds) or (rounds is None and elapsed >= seconds):
                    break
        if trace_dir is not None:
            jax.profiler.stop_trace()
        calls = sum(self.calls.values()) - calls_before
        applications = calls * self.k
        per_case = {c: 1e6 * self.case_s[c] / (done * self.k) for c in self.cases}
        return {
            "attempted": calls,
            "failed": self.failed,
            "collective_us": 1e6 * elapsed / applications,
            "applications": applications,
            "is_collective": is_collective,
            "notes": [f"window: {done} rounds of {len(self.cases)} cases, {calls} calls of "
                      f"{self.k} applications in {elapsed} s",
                      "generator lateness: none, a closed loop (each call waits for the last)"]
                     + [f"case {c}: {us} us per application (host clock, dispatch included)"
                        for c, us in per_case.items()],
        }

    def release(self) -> None:
        self.readings = {"outputs": {c: np.asarray(a).reshape(self.inputs[c].shape)
                                     for c, a in self.state.items()}}
        for a in self.state.values():
            a.delete()
        self.state = None

    def reference(self, precision: str = "exact") -> dict:
        ref = self.cell.reference()
        root = self.traffic["root"]
        return {"outputs": {c: ref.settled(c.split("/")[0], x, root, precision)
                            for c, x in self.inputs.items()}}

    def check(self) -> dict:
        numbers = compare(self.readings, self.reference())
        return {k: {"value": v, "limit": self.limits[k]} for k, v in numbers.items()}
