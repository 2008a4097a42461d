"""Sharding constraints shared by the model and optimizer layers."""

from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import PartitionSpec as P


def constrain(x, spec: Optional[P]):
    """Apply a sharding constraint when a mesh is in scope; no-op otherwise.

    Under a mesh a spec the mesh cannot honour raises, rather than leaving
    the tensor unsharded without a word.
    """
    if spec is None or jax.sharding.get_abstract_mesh().empty:
        return x
    return jax.lax.with_sharding_constraint(x, spec)
