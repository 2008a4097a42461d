"""Plain reference of a dense decoder LM of the Qwen2 architecture, and of its
first training steps under AdamW.

float32 ``jax.numpy``, every matrix product at precision ``HIGHEST``; it
imports nothing of the program under test.  The model is the one HF's
``modeling_qwen2`` describes for the configuration's published keys:

  x = embed[tokens]
  each layer:  h = x + attn(rms(x; norm1)),  x = h + mlp(rms(h; norm2))
  attn(x):     q, k, v = x Wq + bq, x Wk + bk, x Wv + bv, split into heads of
               hidden_size / num_attention_heads; key and value heads repeated
               to the query heads; RoPE (rotate-half, base rope_theta) on q
               and k; softmax(q k^T / sqrt(head_dim)) over the causal past;
               the heads' outputs through Wo
  mlp(x):      (silu(x Wgate) * x Wup) Wdown
  rms(x; s):   x / sqrt(mean(x^2) + rms_norm_eps) * (1 + s)
  loss:        mean over all tokens of logsumexp(logits) - logits[label],
               logits = rms(x; final_norm) embed^T (tied head)

The optimizer (the traffic's ``optimizer``): gradients clipped to a global
norm of ``grad_clip``; AdamW with bias correction; the learning rate of step
t (from 1) is ``lr * warmup_cosine(t - 1)``, rising linearly from 0 over
``warmup`` steps, then a cosine to ``min_lr_ratio`` at ``total_steps``;
decoupled weight decay on every parameter as held.

Departures from the published description, none of which changes a result:
- each RMSNorm weight is held as an offset s from 1 (the weight is 1 + s), so
  weight decay pulls the weight towards 1; HF holds the weight itself;
- it runs in blocks so that it fits one chip: one sequence at a time, each
  layer under ``jax.checkpoint``, attention by blocks of queries and the loss
  by blocks of positions.

``precision="fp8"`` is the control: every matrix product's two operands are
rounded to float8 e4m3 with one scale per operand (the largest magnitude to
448), the products and everything else as above; gradients pass the rounding
straight through.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
PRECISIONS = ("exact", "fp8")


class Dims(NamedTuple):
    """What the jitted functions take as static: shapes, constants, blocks."""
    heads: int
    kv_heads: int
    head_dim: int
    eps: float
    theta: float
    tied: bool
    q_block: int
    loss_block: int
    precision: str


def dims_of(cfg: dict, seq_len: int, precision: str) -> Dims:
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r}; have {PRECISIONS}")
    h = cfg["num_attention_heads"]
    return Dims(heads=h, kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg["hidden_size"] // h, eps=float(cfg["rms_norm_eps"]),
                theta=float(cfg["rope_theta"]), tied=bool(cfg["tie_word_embeddings"]),
                q_block=_block(512, seq_len), loss_block=_block(512, seq_len),
                precision=precision)


def _block(size: int, seq_len: int) -> int:
    size = min(size, seq_len)
    if seq_len % size:
        raise ValueError(f"a block of {size} does not divide the sequence of {seq_len}")
    return size


def _fp8(x):
    scale = jnp.max(jnp.abs(x)) / E4M3_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(spec, a, b, dims: Dims):
    if dims.precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, s, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * (1.0 + s)


def _rope(x, theta):
    """x: (S, heads, head_dim); HF's rotate-half form."""
    S, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    rot = jnp.concatenate([-x[..., hd // 2:], x[..., : hd // 2]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def _attention(p, x, dims: Dims):
    S = x.shape[0]
    H, Hkv, hd = dims.heads, dims.kv_heads, dims.head_dim
    q = (_mm("sd,de->se", x, p["wq"], dims) + p["bq"]).reshape(S, H, hd)
    k = (_mm("sd,de->se", x, p["wk"], dims) + p["bk"]).reshape(S, Hkv, hd)
    v = (_mm("sd,de->se", x, p["wv"], dims) + p["bv"]).reshape(S, Hkv, hd)
    q, k = _rope(q, dims.theta), _rope(k, dims.theta)
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    qb = dims.q_block

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=0)
        logits = _mm("qhd,khd->hqk", qi, k, dims) / math.sqrt(hd)
        rows = i * qb + jnp.arange(qb)
        causal = jnp.arange(S)[None, :] <= rows[:, None]
        w = jax.nn.softmax(jnp.where(causal[None], logits, -jnp.inf), axis=-1)
        return _mm("hqk,khd->qhd", w, v, dims)

    out = jax.lax.map(jax.checkpoint(block), jnp.arange(S // qb))
    return _mm("se,ed->sd", out.reshape(S, H * hd), p["wo"], dims)


def _layer(x, p, dims: Dims):
    h = x + _attention(p, _rms(x, p["norm1"], dims.eps), dims)
    y = _rms(h, p["norm2"], dims.eps)
    g = _mm("sd,df->sf", y, p["w_gate"], dims)
    u = _mm("sd,df->sf", y, p["w_up"], dims)
    return h + _mm("sf,fd->sd", jax.nn.silu(g) * u, p["w_down"], dims)


def _loss_sum(params, tokens, labels, dims: Dims):
    """Sum over one sequence's positions of the next-token loss."""
    layer = jax.checkpoint(functools.partial(_layer, dims=dims))
    x, _ = jax.lax.scan(lambda x, p: (layer(x, p), None), params["embed"][tokens],
                        params["layers"])
    x = _rms(x, params["final_norm"], dims.eps)
    head = params["embed"] if dims.tied else params["lm_head"]
    rb = dims.loss_block

    def block(i):
        xi = jax.lax.dynamic_slice_in_dim(x, i * rb, rb, axis=0)
        yi = jax.lax.dynamic_slice_in_dim(labels, i * rb, rb, axis=0)
        logits = _mm("sd,vd->sv", xi, head, dims)
        gold = jnp.take_along_axis(logits, yi[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)

    return jnp.sum(jax.lax.map(jax.checkpoint(block), jnp.arange(x.shape[0] // rb)))


@functools.partial(jax.jit, static_argnums=4, donate_argnums=0)
def _accumulate(acc, params, tokens, labels, dims: Dims):
    """(loss sum, gradient sum) of one more sequence added to ``acc``."""
    loss, grads = jax.value_and_grad(_loss_sum)(params, tokens, labels, dims)
    return jax.tree.map(jnp.add, acc, (loss, grads))


def lr_scale(step, warmup: int, total: int, min_ratio: float):
    """Multiplier of the learning rate at optimizer step ``step`` (from 0)."""
    step = jnp.asarray(step, jnp.float32)
    warm = jnp.minimum(step / max(warmup, 1), 1.0)
    frac = jnp.clip((step - warmup) / max(total - warmup, 1), 0.0, 1.0)
    return warm * (min_ratio + (1 - min_ratio) * 0.5 * (1 + jnp.cos(jnp.pi * frac)))


@functools.partial(jax.jit, static_argnums=(5, 6), donate_argnums=(0, 1, 2))
def _adamw(params, m, v, grads, t, hp: tuple, n_tokens: int):
    lr, b1, b2, eps, wd, clip, warmup, total, min_ratio = hp
    grads = jax.tree.map(lambda g: g / n_tokens, grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    c = jnp.minimum(1.0, clip / (gnorm + 1e-9))
    tf = t.astype(jnp.float32)
    rate = lr * lr_scale(t - 1, warmup, total, min_ratio)
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g * c, m, grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * jnp.square(g * c), v, grads)
    params = jax.tree.map(
        lambda p, m, v: p - rate * ((m / (1 - b1 ** tf)) / (jnp.sqrt(v / (1 - b2 ** tf)) + eps)
                                    + wd * p),
        params, m, v)
    return params, m, v


def _hyper(opt: dict) -> tuple:
    return (float(opt["lr"]), float(opt["b1"]), float(opt["b2"]), float(opt["eps"]),
            float(opt["weight_decay"]), float(opt["grad_clip"]), int(opt["warmup"]),
            int(opt["total_steps"]), float(opt["min_lr_ratio"]))


def train(weights, batches, opt: dict, cfg: dict, precision: str = "exact") -> dict:
    """The first ``len(batches)`` steps from ``weights`` (a tree of arrays as
    laid out here: ``embed``, ``final_norm``, ``layers`` of stacked per-layer
    arrays; any float type, taken to float32).

    Returns host arrays: ``losses`` (mean loss of each step), ``grad1`` (the
    first step's gradient as the optimizer takes it, clipped), ``change``
    (parameters after the last step less ``weights``)."""
    seq_len = batches[0]["tokens"].shape[1]
    dims = dims_of(cfg, seq_len, precision)
    hp = _hyper(opt)
    f32 = lambda a: jnp.array(a, jnp.float32, copy=True)  # ``_adamw`` donates it
    params = jax.tree.map(f32, weights)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, grad1 = [], None
    for t, batch in enumerate(batches, start=1):
        tokens, labels = np.asarray(batch["tokens"]), np.asarray(batch["labels"])
        acc = (jnp.zeros(()), jax.tree.map(jnp.zeros_like, params))
        for row in range(tokens.shape[0]):
            acc = _accumulate(acc, params, jnp.asarray(tokens[row]), jnp.asarray(labels[row]), dims)
        n = int(tokens.size)
        losses.append(float(acc[0]) / n)
        params, m, v = _adamw(params, m, v, acc[1], jnp.asarray(t, jnp.int32), hp, n)
        if t == 1:
            grad1 = jax.device_get(jax.tree.map(lambda a: a / (1 - hp[1]), m))
        del acc
    change = jax.device_get(jax.tree.map(lambda p, w: p - w.astype(jnp.float32), params, weights))
    return {"losses": losses, "grad1": grad1, "change": change}
