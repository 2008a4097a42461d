"""Public jit'd entry points for the Pallas kernels.

The backend choice (Mosaic on a TPU, the interpreter elsewhere) lives in
``kernels/backend.py``; ``kernels/widths.py`` holds each kernel at the
widths of the models it serves.
"""

from __future__ import annotations

from repro.kernels.backend import default_interpret, on_tpu  # noqa: F401
from repro.kernels.flash_attention import flash_attention  # noqa: F401
from repro.kernels.gemm import gemm  # noqa: F401
from repro.kernels.reduce_nway import reduce_nway  # noqa: F401
from repro.kernels.rglru import rglru_scan  # noqa: F401
from repro.kernels.rwkv6 import wkv  # noqa: F401
