"""Shared building blocks: config, sharding policy, norms, embeddings, loss."""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.sharding import constrain  # noqa: F401  (the model modules import it from here)


# ---------------------------------------------------------------------------
# Configuration
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """One record per assigned architecture (see src/repro/configs)."""

    name: str
    family: str                    # transformer | rglru_hybrid | rwkv6 | whisper
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int

    head_dim: int = 0              # 0 -> d_model // n_heads
    # attention pattern
    attn_window: int = 0           # 0 -> full attention; >0 -> sliding window
    local_global_ratio: int = 0    # gemma3: N local layers per 1 global
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    # MoE
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    # hybrid (recurrentgemma): pattern of blocks, e.g. ("rec", "rec", "attn")
    block_pattern: tuple[str, ...] = ()
    lru_width: int = 0             # 0 -> d_model
    conv_width: int = 4
    # rwkv
    rwkv_head_size: int = 64
    # whisper
    encoder_layers: int = 0
    encoder_len: int = 1500        # precomputed conv-frontend frames (stub)
    # attention materialization: 0 = full (S x S) logits; >0 = blockwise
    # over query chunks of this size (flash-style memory behaviour at the
    # XLA level; the Pallas kernel is the TPU fast path)
    attn_q_chunk: int = 0
    # keep the (S x S) logits in bf16 (halves attention HBM traffic; the
    # softmax max-shift keeps it stable) — §Perf lever
    attn_bf16_logits: bool = False
    # shard the token dim over the model axis inside the expert-parallel
    # MoE dispatch even without sequence parallelism (otherwise every model
    # rank routes the same replicated tokens -> esize x redundant expert
    # FLOPs after the all_to_all).  Default ON (§Perf confirmed: phi
    # prefill compute term 4.6x down, 6ND/HLO 0.06 -> 0.27).
    moe_token_shard: bool = True
    # numerics
    param_dtype: Any = jnp.bfloat16
    compute_dtype: Any = jnp.bfloat16
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    # loss
    loss_chunk: int = 1024         # sequence chunk for the vocab projection
    remat: bool = True
    # scan_layers=True compiles O(1)-size HLO (production); the dry-run
    # lowers with scan_layers=False (unrolled) because XLA cost_analysis
    # counts loop bodies once — unrolling makes the roofline FLOP/byte
    # accounting exact.
    scan_layers: bool = True

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // max(1, self.n_kv_heads)

    @property
    def padded_vocab(self) -> int:
        """Vocab padded to a multiple of 256 so it shards over the TP axis."""
        return ((self.vocab + 255) // 256) * 256

    @property
    def n_params(self) -> int:
        """Approximate parameter count (reported, and used for 6ND)."""
        d, f, L, v = self.d_model, self.d_ff, self.n_layers, self.padded_vocab
        hd = self.head_dim
        if self.family == "rwkv6":
            per_layer = 4 * d * d + d * d + 2 * d * f + 6 * d * 32 * 2  # tmix+ffn+lora-ish
        elif self.family == "rglru_hybrid":
            rec = 2 * d * (self.lru_width or d) + (self.lru_width or d) * d
            attn = d * (self.n_heads * hd) + 2 * d * (self.n_kv_heads * hd) + (self.n_heads * hd) * d
            mlp = 3 * d * f
            n_attn = sum(1 for i in range(L) if self._block_kind(i) == "attn")
            per_layer = 0  # computed below
            total = (L - n_attn) * (rec + mlp) + n_attn * (attn + mlp)
            return total + 2 * v * d
        else:
            attn = d * (self.n_heads * hd) + 2 * d * (self.n_kv_heads * hd) + (self.n_heads * hd) * d
            if self.n_experts:
                mlp = self.n_experts * 3 * d * f + d * self.n_experts
            else:
                mlp = 3 * d * f
            per_layer = attn + mlp
        total = L * per_layer + 2 * v * d
        if self.family == "whisper":
            total += self.encoder_layers * (2 * attn + 2 * d * f + d * f)
        return total

    @property
    def n_active_params(self) -> int:
        """Active params per token (MoE: top_k experts only)."""
        if not self.n_experts:
            return self.n_params
        d, f, L = self.d_model, self.d_ff, self.n_layers
        hd = self.head_dim
        attn = d * (self.n_heads * hd) + 2 * d * (self.n_kv_heads * hd) + (self.n_heads * hd) * d
        mlp = self.top_k * 3 * d * f + d * self.n_experts
        return L * (attn + mlp) + 2 * self.padded_vocab * d

    def _block_kind(self, i: int) -> str:
        if not self.block_pattern:
            return "attn"
        return self.block_pattern[i % len(self.block_pattern)]


# ---------------------------------------------------------------------------
# Sharding policy
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShardingPolicy:
    """Maps logical tensor dimensions to mesh axes.

    ``batch_axes`` collect DP axes (('pod','data') on the multi-pod mesh);
    ``model_axis`` is the TP/EP axis.  ``divisible`` guards: a dimension is
    only sharded if the axis size divides it (e.g. 4 KV heads or 8 whisper
    heads do NOT shard over a 16-wide model axis -> replicate; recorded in
    DESIGN.md §Arch-applicability).
    """

    batch_axes: tuple[str, ...] = ("data",)
    model_axis: Optional[str] = "model"
    mesh_axis_sizes: dict[str, int] = dataclasses.field(default_factory=dict)
    # Sequence parallelism (Megatron-style): between blocks, activations are
    # sharded on the sequence dim over ``seq_axis`` — GSPMD inserts the
    # all-gather before attention/MLP and the reduce-scatter after (the
    # multicast/reduction pair, in the paper's vocabulary).  Cuts the
    # per-device remat-saved activation footprint by the TP degree.
    seq_axis: Optional[str] = None
    # Decode-path fix: constrain in-flight q/k/v to the KV-cache layout so
    # GSPMD never round-trips the cache through a replicated layout
    # ("involuntary full rematerialization").  §Perf measures the win.
    align_decode_cache: bool = False

    def kv_dims(self, n_kv: int, head_dim: int):
        """(kv_spec, hd_spec) for cache dims: prefer kv heads, else head_dim."""
        kv = self._model_if_divisible(n_kv)
        if kv is not None:
            return kv, None
        return None, self._model_if_divisible(head_dim)

    def _model_if_divisible(self, dim: int):
        if self.model_axis is None:
            return None
        size = self.mesh_axis_sizes.get(self.model_axis, 1)
        return self.model_axis if dim % size == 0 else None

    # -- parameter specs --
    def w_col(self, out_dim: int) -> P:         # (d_in, d_out) column parallel
        return P(None, self._model_if_divisible(out_dim))

    def w_row(self, in_dim: int) -> P:          # (d_in, d_out) row parallel
        return P(self._model_if_divisible(in_dim), None)

    def w_expert_col(self, n_experts: int, out_dim: int) -> P:
        e = self._model_if_divisible(n_experts)
        return P(e, None, None if e else self._model_if_divisible(out_dim))

    def w_expert_row(self, n_experts: int, in_dim: int) -> P:
        e = self._model_if_divisible(n_experts)
        return P(e, None if e else self._model_if_divisible(in_dim), None)

    def embed(self, vocab: int) -> P:
        return P(self._model_if_divisible(vocab), None)

    def none(self) -> P:
        return P()

    # -- activation specs --
    def act_bsd(self) -> P:                     # (batch, seq, d)
        return P(self.batch_axes or None, self.seq_axis, None)

    def act_bshd(self, n_heads: int) -> P:      # (batch, seq, heads, head_dim)
        return P(self.batch_axes or None, None, self._model_if_divisible(n_heads), None)

    def act_bsf(self, d_ff: int) -> P:          # (batch, seq, d_ff)
        return P(self.batch_axes or None, None, self._model_if_divisible(d_ff))

    def act_bsv(self, vocab: int) -> P:         # (batch, seq, vocab)
        return P(self.batch_axes or None, None, self._model_if_divisible(vocab))

    def kv_cache(self, n_kv: int) -> P:         # (layers, batch, seq, kv, hd)
        return P(None, self.batch_axes or None, None, self._model_if_divisible(n_kv), None)


REPLICATED = ShardingPolicy(batch_axes=(), model_axis=None)


# ---------------------------------------------------------------------------
# Primitives
# ---------------------------------------------------------------------------


def rms_norm(x, scale, eps: float = 1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(dtype)


def layer_norm(x, scale, bias, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    out = (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias
    return out.astype(dtype)


def dense_init(key, shape, dtype, scale: float | None = None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = (scale if scale is not None else 1.0) / jnp.sqrt(fan_in)
    return (jax.random.normal(key, shape) * std).astype(dtype)


def embed_init(key, vocab, d, dtype):
    return (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)


def chunked_cross_entropy(hidden, embed_out, labels, cfg: ModelConfig,
                          policy: ShardingPolicy = REPLICATED):
    """Cross-entropy without materializing the full (B, S, V) logits.

    Scans the sequence in ``cfg.loss_chunk`` chunks; the vocab projection
    stays sharded over the model axis and only a (B, chunk, V) slab exists
    at a time.  This is one of the beyond-paper memory-term optimizations
    (EXPERIMENTS.md §Perf).
    """
    B, S, D = hidden.shape
    chunk = min(cfg.loss_chunk, S)
    n_chunks = S // chunk
    rem = S - n_chunks * chunk

    def chunk_loss(h, y):
        logits = jnp.einsum("bsd,vd->bsv", h.astype(jnp.float32),
                            embed_out.astype(jnp.float32))
        logits = constrain(logits, policy.act_bsv(embed_out.shape[0]))
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
        valid = (y >= 0).astype(jnp.float32)
        return jnp.sum((logz - gold) * valid), jnp.sum(valid)

    if n_chunks > 0:
        hs = hidden[:, : n_chunks * chunk].reshape(B, n_chunks, chunk, D)
        ys = labels[:, : n_chunks * chunk].reshape(B, n_chunks, chunk)

        if cfg.scan_layers:
            def body(carry, xs):
                h, y = xs
                l, n = chunk_loss(h, y)
                return (carry[0] + l, carry[1] + n), None

            (total, count), _ = jax.lax.scan(
                body, (jnp.zeros(()), jnp.zeros(())),
                (hs.swapaxes(0, 1), ys.swapaxes(0, 1)))
        else:
            total, count = jnp.zeros(()), jnp.zeros(())
            for i in range(n_chunks):
                l, n = chunk_loss(hs[:, i], ys[:, i])
                total, count = total + l, count + n
    else:
        total, count = jnp.zeros(()), jnp.zeros(())
    if rem:
        l, n = chunk_loss(hidden[:, n_chunks * chunk:], labels[:, n_chunks * chunk:])
        total, count = total + l, count + n
    return total / jnp.maximum(count, 1.0)


def maybe_remat(fn, enabled: bool):
    return jax.checkpoint(fn) if enabled else fn
