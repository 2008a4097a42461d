"""Plain reference of the collective mix: NumPy, one member's buffer per row.

Each case applies one operation ``f`` to the members' buffers over and over
(the timed loop carries its output into its next call), so what a case must
hold after any number of calls is ``f`` applied until it no longer changes
the buffers: once for broadcast and all-reduce, twice for reduce-scatter.

- broadcast: every member gets the root's buffer;
- all_reduce: every member gets the sum of all buffers, times 1/n;
- reduce_scatter: member i gets row block i of the sum, times 1/n, repeated
  n times to its buffer's size.

``precision="exact"`` sums in float64, which is exact for the payload's
integers; ``"bf16"`` is the control: inputs and every partial sum rounded to
bfloat16.
"""

from __future__ import annotations

import numpy as np

APPLICATIONS = 3  # f∘f∘f equals f∘f for every op here, and f∘f equals f for two


def _rounder(precision: str):
    if precision == "exact":
        return lambda a: np.asarray(a, np.float64)
    if precision == "bf16":
        import ml_dtypes

        return lambda a: np.asarray(a, np.float32).astype(ml_dtypes.bfloat16).astype(np.float64)
    raise ValueError(f"unknown precision {precision!r}")


def _sum(members, rnd):
    acc = rnd(members[0])
    for m in members[1:]:
        acc = rnd(acc + rnd(m))
    return acc


def apply(op: str, x: np.ndarray, root: int, precision: str = "exact") -> np.ndarray:
    """One application of ``op`` to x of shape (members, rows, cols)."""
    rnd = _rounder(precision)
    n = x.shape[0]
    if op == "broadcast":
        return np.stack([rnd(x[root])] * n)
    total = _sum(list(x), rnd) / n
    if op == "all_reduce":
        return np.stack([total] * n)
    if op == "reduce_scatter":
        blocks = np.split(total, n, axis=0)
        return np.stack([np.concatenate([b] * n, axis=0) for b in blocks])
    raise ValueError(f"unknown op {op!r}")


def settled(op: str, x: np.ndarray, root: int, precision: str = "exact") -> np.ndarray:
    for _ in range(APPLICATIONS):
        x = apply(op, x, root, precision)
    return x
