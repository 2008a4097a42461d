"""Block-wise (flash) causal attention forward kernel with sliding window.

Grid: (batch*heads, n_q_blocks, n_kv_blocks); the kv dimension iterates
sequentially per (bh, qi) tile carrying running max / normalizer / output
accumulator in VMEM scratch (the standard online-softmax recurrence).
Causal and out-of-window kv blocks are skipped via ``pl.when``, so the
sliding-window archs (gemma3 local layers, recurrentgemma) pay only
O(S * window) compute.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import default_interpret

NEG_INF = -2.0e38


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  bq: int, bkv: int, nkv: int, window: int, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # block-level skip: causal (k block entirely after q block) and window
    q_start, k_start = qi * bq, ki * bkv
    causal_live = k_start <= q_start + bq - 1
    window_live = (window <= 0) or (k_start + bkv - 1 >= q_start - window + 1)
    # window_live depends only on static ints when window is static

    @pl.when(causal_live & window_live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale      # (bq, d)
        k = k_ref[0].astype(jnp.float32)              # (bkv, d)
        v = v_ref[0].astype(jnp.float32)              # (bkv, d)
        s = q @ k.T                                   # (bq, bkv)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        mask = k_pos <= q_pos
        if window > 0:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + p @ v
        m_ref[...] = m_new

    @pl.when(ki == nkv - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bq", "bkv", "window", "interpret"))
def flash_attention(q, k, v, *, window: int = 0, bq: int = 128, bkv: int = 128,
                    interpret: bool | None = None):
    """Causal (optionally windowed) attention.

    q, k, v: (BH, S, d) with matching S (self-attention).  Returns (BH, S, d).
    """
    BH, S, d = q.shape
    bq, bkv = min(bq, S), min(bkv, S)
    assert S % bq == 0 and S % bkv == 0, (S, bq, bkv)
    nq, nkv = S // bq, S // bkv
    scale = 1.0 / (d ** 0.5)
    kernel = functools.partial(_flash_kernel, bq=bq, bkv=bkv, nkv=nkv,
                               window=window, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=default_interpret() if interpret is None else interpret,
    )(q, k, v)
