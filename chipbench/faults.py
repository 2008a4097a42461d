"""Faults planted under a runner's timed path, for the tests and the study.

Each fault is a hook called with the runner before its set-up; it wraps the
callable the window drives, so everything else of a run stays as it is.  A
run with a fault planted has to come out ``correct: false``.

- ``no_exchange``: the collective leaves out the exchange between chips.
- ``half_batch``: half of each buffer is left out of the collective.
- ``altered``: one element of an answer is altered where it is produced.
"""

from __future__ import annotations


def _collective(kind):
    def hook(runner):
        runner.wrap_op = lambda op, fn: _broken(kind, op, fn)
    return hook


def _broken(kind, op, fn):
    import jax
    import jax.numpy as jnp

    def no_exchange(x, axis, schedule):
        if op == "reduce_scatter":  # the own chunk, not summed with the others'
            n = jax.lax.axis_size(axis)
            rows = x.shape[0] // n
            return jax.lax.dynamic_slice_in_dim(x, jax.lax.axis_index(axis) * rows, rows)
        return x

    def half(x, axis, schedule):
        rows = x.shape[0] // 2
        if op == "reduce_scatter":
            return fn(jnp.concatenate([x[:rows], jnp.zeros_like(x[rows:])]), axis, schedule)
        return jnp.concatenate([fn(x[:rows], axis, schedule), x[rows:]])

    def altered(x, axis, schedule):
        out = fn(x, axis, schedule)
        return out.at[(0,) * out.ndim].add(1.0)

    return {"no_exchange": no_exchange, "half_batch": half, "altered": altered}[kind]


FAULTS = {
    "collectives": {k: _collective(k) for k in ("no_exchange", "half_batch", "altered")},
}
