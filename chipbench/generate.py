"""The traffic generator: every mix under ``traffic/`` is parameters for this."""

from __future__ import annotations

import numpy as np


def payload(seed: int, case: int, shape, value_range: int) -> np.ndarray:
    """Integers in [-value_range, value_range) held as float32: sums of a few
    of them are exact in float32 and not in bfloat16."""
    rng = np.random.Generator(np.random.Philox(key=seed, counter=[0, 0, 1, case]))
    return rng.integers(-value_range, value_range, size=shape).astype(np.float32)
