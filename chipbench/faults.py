"""Faults planted under a runner's timed path, for the tests and the study.

Each fault is a hook called with the runner before its set-up; it wraps the
callable the window drives, so everything else of a run stays as it is.  A
run with a fault planted has to come out ``correct: false``.

- ``no_exchange``: the collective leaves out the exchange between chips.
- ``half_batch``: half of each buffer is left out of the collective.
- ``altered``: one element of an answer is altered where it is produced.

Of ``kind: "train"`` runs:

- ``frozen``: the step returns its state unchanged (the loss is still
  computed, on copies of the state).
- ``half_batch``: half of each batch is left out, the mean taken over the rest.
- ``padded_vocab``: the program holds 256 more vocabulary rows than the
  model has, and its softmax counts them.
"""

from __future__ import annotations

import dataclasses


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


def _frozen(step):
    import jax
    import jax.numpy as jnp

    def frozen(params, opt, batch, err):
        copy = lambda tree: jax.tree.map(jnp.copy, tree)
        _, _, _, metrics = step(copy(params), copy(opt), batch, err)
        return params, opt, err, metrics

    return frozen


def _half_batch(step):
    def half_batch(params, opt, batch, err):
        return step(params, opt, {k: v[: v.shape[0] // 2] for k, v in batch.items()}, err)

    return half_batch


def _train_step(wrap):
    def hook(runner):
        runner.wrap_step = wrap
    return hook


def _padded_vocab(runner):
    runner.wrap_model_config = lambda cfg: dataclasses.replace(cfg, vocab=cfg.padded_vocab + 1)


FAULTS = {
    "collectives": {k: _collective(k) for k in ("no_exchange", "half_batch", "altered")},
    "train": {"frozen": _train_step(_frozen), "half_batch": _train_step(_half_batch),
              "padded_vocab": _padded_vocab},
}
