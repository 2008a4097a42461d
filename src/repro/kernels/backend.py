"""Which backend the Pallas kernels run on.

Every kernel takes ``interpret=None`` by default, which resolves through
``default_interpret()``: compiled to Mosaic when JAX's default backend is
a TPU, and run by the Pallas interpreter otherwise (the CPU test suite).
Passing ``interpret=False`` forces the Mosaic lowering, which is how the
compile tests target a described TPU from a CPU-only host.
"""

from __future__ import annotations

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def default_interpret() -> bool:
    return not on_tpu()
