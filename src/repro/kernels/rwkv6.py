"""Chunked RWKV-6 WKV kernel (data-dependent-decay linear attention).

One (batch*head) slice per grid row; the chunk dimension iterates
sequentially carrying the (hd x hd) state in VMEM scratch.  Within a chunk
everything is MXU matmuls: the intra-chunk term is a masked (c x c)
attention-like product, the inter-chunk term a (c, hd) x (hd, hd) matmul —
the same formulation as models/rwkv6.chunked_wkv, specialized per head.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import default_interpret


def _row_cumsum(x):
    """Inclusive prefix sum down the rows (Hillis-Steele, one sublane roll per
    level): Mosaic has no cumsum lowering."""
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    shift = 1
    while shift < x.shape[0]:
        x = x + jnp.where(row >= shift, pltpu.roll(x, shift, 0), 0.0)
        shift *= 2
    return x


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, s_ref, *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0].astype(jnp.float32)    # (c, hd)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)  # log-decay, <= 0
    u = u_ref[0].astype(jnp.float32)    # (1, hd) bonus

    cum = _row_cumsum(lw)
    p_excl = cum - lw
    c, hd = r.shape
    A = cum[c - 1:]                                    # (1, hd) chunk decay

    state = s_ref[...]
    r_dec = r * jnp.exp(p_excl)
    out_inter = r_dec @ state                          # (c, hd)
    att = (r * jnp.exp(p_excl)) @ (k * jnp.exp(-cum)).T
    mask = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0) > \
        jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    att = jnp.where(mask, att, 0.0)
    out_intra = att @ v
    diag = jnp.sum(r * (u * k), axis=-1, keepdims=True)
    out = out_inter + out_intra + diag * v
    k_dec = k * jnp.exp(A - cum)
    # exp(A) as an (hd, 1) column scales the state's rows; a diagonal pick
    # and a lane reduction stand in for the (1, hd) -> (hd, 1) relayout
    eye = jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 0) == \
        jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 1)
    decay = jnp.sum(jnp.where(eye, jnp.exp(A), 0.0), axis=1, keepdims=True)
    s_ref[...] = decay * state + k_dec.T @ v
    o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv(r, k, v, logw, u, *, chunk: int = 64, interpret: bool | None = None):
    """r,k,v,logw: (BH, S, hd); u: (BH, hd). Returns out (BH, S, hd)."""
    BH, S, hd = r.shape
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    return pl.pallas_call(
        functools.partial(_wkv_kernel, chunk=chunk),
        grid=(BH, S // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            # u rides as (BH, 1, hd): a (1, hd) block of a (BH, hd) array
            # breaks the TPU rule that the second-minor block dim is a
            # multiple of 8 or the whole dim.
            pl.BlockSpec((1, 1, hd), lambda b, c: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), r.dtype),
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=default_interpret() if interpret is None else interpret,
    )(r, k, v, logw, u[:, None, :])
