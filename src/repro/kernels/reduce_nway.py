"""N-way elementwise reduction kernel (the parallel-reduction-router analogue).

The paper's output arbiter reduces packets from up to 5 input directions in
parallel (Section 3.1.3).  The TPU analogue reduces N input streams tile by
tile in VMEM with the VPU: inputs (N, M) -> output (M), with the op chosen
by opcode, mirroring the router's computation blocks:

  * ``add``  — the wide DCA reduction,
  * ``max``  — an alternative arithmetic block,
  * ``and``  — the LsbAnd barrier primitive (integer inputs).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import default_interpret

OPS = ("add", "max", "and")


def _reduce_kernel(x_ref, o_ref, *, op: str):
    x = x_ref[...]  # (N, bs) -> o_ref (1, bs)
    if op == "add":
        o_ref[...] = jnp.sum(x.astype(jnp.float32), axis=0,
                             keepdims=True).astype(o_ref.dtype)
    elif op == "max":
        o_ref[...] = jnp.max(x, axis=0, keepdims=True)
    elif op == "and":
        def body(i, acc):
            return acc & x_ref[pl.ds(i, 1), :]
        o_ref[...] = jax.lax.fori_loop(1, x.shape[0], body, x_ref[pl.ds(0, 1), :])


@functools.partial(jax.jit, static_argnames=("op", "bs", "interpret"))
def reduce_nway(x, *, op: str = "add", bs: int = 512, interpret: bool | None = None):
    """x: (N, M) -> (M,). M must be a multiple of the 2-D tile minor 128."""
    assert op in OPS, op
    N, M = x.shape
    bs = min(bs, M)
    assert M % bs == 0, (M, bs)
    return pl.pallas_call(
        functools.partial(_reduce_kernel, op=op),
        grid=(M // bs,),
        in_specs=[pl.BlockSpec((N, bs), lambda i: (0, i))],
        # a 2-D (1, M) output: a 1-D (bs,) block does not match XLA's tiled
        # layout of a 1-D f32 array on the TPU
        out_specs=pl.BlockSpec((1, bs), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, M), x.dtype),
        interpret=default_interpret() if interpret is None else interpret,
    )(x)[0]
