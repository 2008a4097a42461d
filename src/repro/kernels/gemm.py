"""MXU-tiled GEMM with an accumulate-into-output epilogue (the DCA analogue).

The paper's DCA lets the network reduce partial results using the tile's
own FPUs.  The TPU-native equivalent at kernel level: a GEMM whose epilogue
*accumulates into an existing output buffer*, so partial products arriving
from peers (e.g. the per-step blocks of a SUMMA iteration or the shards of
a tensor-parallel contraction) are reduced by the consumer's MXU/VPU with
no separate reduction pass.

Grid: (M/bm, N/bn, K/bk); the K dimension iterates sequentially per (i, j)
tile (TPU grid minor-to-major order), carrying an f32 VMEM accumulator.
Block shapes default to MXU-aligned (128, 128, 128).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import default_interpret


def _gemm_kernel(a_ref, b_ref, c_ref, o_ref, acc_ref, *, nk: int, accumulate: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        if accumulate:
            acc_ref[...] = c_ref[...].astype(jnp.float32)
        else:
            acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...].astype(jnp.float32),
        b_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "accumulate", "interpret"))
def gemm(a, b, c=None, *, bm: int = 128, bn: int = 128, bk: int = 128,
         accumulate: bool = False, interpret: bool | None = None):
    """C = A @ B  (+ C_in if accumulate).  Shapes must tile evenly."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (
        f"({M},{N},{K}) not tiled by ({bm},{bn},{bk})")
    if c is None:
        c = jnp.zeros((M, N), a.dtype)
    nk = K // bk
    kernel = functools.partial(_gemm_kernel, nk=nk, accumulate=accumulate)
    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=default_interpret() if interpret is None else interpret,
    )(a, b, c)
