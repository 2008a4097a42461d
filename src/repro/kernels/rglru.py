"""Chunked RG-LRU linear-recurrence kernel.

h_t = a_t * h_{t-1} + b_t, evaluated chunk-by-chunk: the grid's sequential
chunk dimension carries the boundary state in VMEM scratch; within a chunk
the recurrence is unrolled log-depth (Hillis-Steele over the affine maps
(a, b), each level one sublane roll) with the cumulative products held in
registers.  This is the TPU-shaped replacement for a length-S sequential
scan: HBM traffic is one read of (a, b) and one write of h, and the
sequential dependency is only across S/chunk grid steps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import default_interpret


def _rglru_kernel(a_ref, b_ref, o_ref, h_ref, *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[0].astype(jnp.float32)  # (chunk, width)
    b = b_ref[0].astype(jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
    # inclusive scan of (a, b) under (al, bl) . (ar, br) = (al*ar, ar*bl + br);
    # rows that the roll wraps around take the identity (1, 0).
    shift = 1
    while shift < chunk:
        live = row >= shift
        a_prev = jnp.where(live, pltpu.roll(a, shift, 0), 1.0)
        b_prev = jnp.where(live, pltpu.roll(b, shift, 0), 0.0)
        b = a * b_prev + b
        a = a * a_prev
        shift *= 2
    # inject boundary state: h_t += (prod a_{1..t}) * h_boundary
    hh = b + a * h_ref[...]
    h_ref[...] = hh[chunk - 1:]
    o_ref[0] = hh.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rglru_scan(a, b, *, chunk: int = 128, interpret: bool | None = None):
    """a, b: (B, S, W) -> h: (B, S, W) with h_t = a_t h_{t-1} + b_t."""
    B, S, W = a.shape
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    return pl.pallas_call(
        functools.partial(_rglru_kernel, chunk=chunk),
        grid=(B, S // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, W), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, W), lambda b, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, W), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, W), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, W), jnp.float32)],
        interpret=default_interpret() if interpret is None else interpret,
    )(a, b)
