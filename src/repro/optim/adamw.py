"""AdamW with optional ZeRO-1 optimizer-state sharding.

States are a pytree mirroring params.  With ``zero1=True`` the specs shard
each state leaf's dim 0 over the DP axes when divisible — optimizer memory
drops by the DP degree; the update still runs under pjit, XLA inserting
the reduce-scatter/all-gather pair (in-network reduction + multicast, in
the paper's vocabulary).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.sharding import constrain


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0


def adamw_init(params):
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {
        "step": jnp.zeros((), jnp.int32),
        "m": jax.tree.map(zeros, params),
        "v": jax.tree.map(zeros, params),
    }


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(tree)))


def adamw_update(params, grads, state, cfg: AdamWConfig, lr_scale=1.0,
                 update_specs=None):
    """Returns (new_params, new_state, metrics).

    ``update_specs`` (a PartitionSpec tree matching params, normally the
    ZeRO-1 opt-state specs): constrains the f32 update intermediates to the
    DP-sharded layout, so the whole optimizer step runs on 1/DP of each
    tensor and only the final bf16 params are all-gathered — the ZeRO-1
    update semantics, not just ZeRO-1 storage.
    """
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.grad_clip / (gnorm + 1e-9))
    step = state["step"] + 1
    b1c = 1.0 - cfg.b1 ** step.astype(jnp.float32)
    b2c = 1.0 - cfg.b2 ** step.astype(jnp.float32)
    lr = cfg.lr * lr_scale

    def upd(p, g, m, v, spec):
        shard = partial(constrain, spec=spec)
        g = shard(g.astype(jnp.float32) * scale)
        m = cfg.b1 * m + (1 - cfg.b1) * g
        v = cfg.b2 * v + (1 - cfg.b2) * jnp.square(g)
        mhat = m / b1c
        vhat = v / b2c
        new_p = shard(p.astype(jnp.float32)) - lr * (
            mhat / (jnp.sqrt(vhat) + cfg.eps)
            + cfg.weight_decay * shard(p.astype(jnp.float32)))
        return new_p.astype(p.dtype), m, v

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(state["m"])
    flat_v = jax.tree.leaves(state["v"])
    if update_specs is None:
        flat_s = [None] * len(flat_p)
    else:
        flat_s = jax.tree.leaves(update_specs)  # PartitionSpec is a leaf
    out = [upd(p, g, m, v, s) for p, g, m, v, s in
           zip(flat_p, flat_g, flat_m, flat_v, flat_s)]
    new_params = jax.tree.unflatten(treedef, [o[0] for o in out])
    new_state = {
        "step": step,
        "m": jax.tree.unflatten(treedef, [o[1] for o in out]),
        "v": jax.tree.unflatten(treedef, [o[2] for o in out]),
    }
    return new_params, new_state, {"grad_norm": gnorm, "lr": lr}


def opt_state_specs(param_specs, param_shapes, batch_axes=("data",),
                    zero1: bool = True, axis_sizes: dict | None = None):
    """Sharding specs for the optimizer state (ZeRO-1 over the DP axes).

    ``param_shapes``: pytree of arrays or ShapeDtypeStructs matching
    ``param_specs`` — dim 0 is only sharded when divisible by the DP degree.
    """
    dp = 1
    for a in batch_axes:
        dp *= (axis_sizes or {}).get(a, 1)

    def zspec(spec: P, shape) -> P:
        if not zero1 or dp <= 1:
            return spec
        dims = shape.shape if hasattr(shape, "shape") else tuple(shape)
        parts = list(spec) + [None] * (len(dims) - len(spec))
        # shard the LARGEST unsharded divisible dim over DP.  Choosing by
        # size (not position) keeps the sharding decision independent of the
        # stacked layer count, so the dry-run's reduced-depth lowerings see
        # the same collective structure as the full model.
        best, best_size = None, 0
        for i, (p, dim) in enumerate(zip(parts, dims)):
            if p is None and dim % dp == 0 and dim > best_size:
                best, best_size = i, dim
        if best is None:
            return spec
        parts[best] = batch_axes
        return P(*parts)

    m_specs = jax.tree.map(zspec, param_specs, param_shapes,
                           is_leaf=lambda x: isinstance(x, P))
    return {"step": P(), "m": m_specs, "v": m_specs}
