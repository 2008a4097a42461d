"""The Pallas kernels at the widths of the models they serve.

One table for every check that runs a kernel at full width: the chip smoke
run (compiled, against ``kernels/ref.py``), the compile tests for a
described v5e (``jax.eval_shape`` of the inputs), and the interpret-mode
tests at the shapes Mosaic once refused.

Widths: Qwen1.5-0.5B attention (16 heads x 2048 x 64) and MLP GEMM
(1024 x 1024 @ 1024 x 2816), RecurrentGemma-2B RG-LRU (width 2560), RWKV6-3B
WKV (40 heads x 64), and the paper's N-way router reduction over 4 inputs of
8192 (5 for the bitwise AND).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gemm import gemm
from repro.kernels.reduce_nway import reduce_nway
from repro.kernels.rglru import rglru_scan
from repro.kernels.rwkv6 import wkv


@dataclasses.dataclass(frozen=True)
class KernelCase:
    kernel: Callable
    reference: Callable
    make_inputs: Callable  # PRNG key -> list of input arrays
    # max |kernel - reference| / max |reference| allowed under Mosaic: bf16
    # inputs with f32 inside and a bf16 output carry one bf16 rounding
    # (2^-8); f32 elementwise scans are exact to f32 rounding; WKV allows for
    # bf16 MXU passes of its f32 chunk matmuls.
    tol: float


def _normal(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def _attention(key):
    return [_normal(k, (16, 2048, 64), jnp.bfloat16) for k in jax.random.split(key, 3)]


def _gemm(key):
    ka, kb = jax.random.split(key)
    return [_normal(ka, (1024, 1024), jnp.bfloat16, 1 / 32),
            _normal(kb, (1024, 2816), jnp.bfloat16)]


def _rglru(key):
    ka, kb = jax.random.split(key)
    return [jax.nn.sigmoid(_normal(ka, (2, 1024, 2560))), _normal(kb, (2, 1024, 2560))]


def _wkv(key):
    kr, kk, kv, kw, ku = jax.random.split(key, 5)
    shape = (40, 256, 64)
    return [_normal(kr, shape, scale=0.5), _normal(kk, shape, scale=0.5),
            _normal(kv, shape, scale=0.5),
            -jnp.exp(jnp.clip(_normal(kw, shape) - 2.0, -8, 1)),  # log-decay <= 0
            _normal(ku, (40, 64), scale=0.5)]


def _reduce(key):
    return [_normal(key, (4, 8192))]


def _reduce_bits(key):
    return [jax.random.randint(key, (5, 8192), -2 ** 31, 2 ** 31 - 1, jnp.int32)]


def _reduce_case(op: str, make_inputs, tol: float) -> KernelCase:
    return KernelCase(partial(reduce_nway, op=op), partial(ref.reduce_nway_ref, op=op),
                      make_inputs, tol)


MODEL_WIDTH = {
    "flash_attention": KernelCase(flash_attention, ref.flash_attention_ref, _attention, 1e-2),
    "gemm": KernelCase(gemm, ref.gemm_ref, _gemm, 1e-2),
    "rglru_scan": KernelCase(rglru_scan, ref.rglru_scan_ref, _rglru, 1e-4),
    "wkv": KernelCase(wkv, ref.wkv_ref, _wkv, 2e-2),
    "reduce_nway_add": _reduce_case("add", _reduce, 1e-6),
    "reduce_nway_max": _reduce_case("max", _reduce, 0.0),
    "reduce_nway_and": _reduce_case("and", _reduce_bits, 0.0),
}
