"""Step functions + input specs + shardings for every (arch x shape) cell.

``build_cell(cfg, shape_name, mesh)`` returns (step_fn, input_specs,
in_shardings, donate) ready for ``jax.jit(...).lower(...)`` — the dry-run
contract.  Inputs are ShapeDtypeStructs only (weak-type-correct, shardable,
no allocation).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import axis_sizes, batch_axes
from repro.launch.shapes import SHAPES, ShapeCell, applicable
from repro.models import get_family
from repro.models.common import ModelConfig, ShardingPolicy
from repro.optim import AdamWConfig, adamw_update
from repro.optim.adamw import opt_state_specs


def make_policy(cfg: ModelConfig, mesh, *, shard_batch: bool = True,
                seq_parallel: bool = False,
                align_decode_cache: bool = False) -> ShardingPolicy:
    return ShardingPolicy(
        batch_axes=batch_axes(mesh) if shard_batch else (),
        model_axis="model",
        mesh_axis_sizes=axis_sizes(mesh),
        seq_axis="model" if seq_parallel else None,
        align_decode_cache=align_decode_cache,
    )


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _param_shapes(cfg: ModelConfig):
    fam = get_family(cfg)
    return jax.eval_shape(lambda: fam.init(jax.random.PRNGKey(0), cfg))


def _ns(mesh, spec_tree):
    def conv(s):
        if s is None:
            return None
        return NamedSharding(mesh, s if isinstance(s, P) else P())

    return jax.tree.map(conv, spec_tree,
                        is_leaf=lambda x: isinstance(x, P) or x is None)


def _kv_dim_specs(policy: ShardingPolicy, cfg: ModelConfig):
    """(kv_spec, hd_spec) for cache dims: prefer kv heads, else head_dim."""
    kv = policy._model_if_divisible(cfg.n_kv_heads)
    if kv is not None:
        return kv, None
    return None, policy._model_if_divisible(cfg.head_dim)


# ---------------------------------------------------------------------------
# Cache shape/spec builders per family
# ---------------------------------------------------------------------------


def cache_specs(cfg: ModelConfig, cell: ShapeCell, policy: ShardingPolicy,
                long_ctx: bool):
    """Returns (cache ShapeDtypeStruct tree, cache PartitionSpec tree)."""
    fam = get_family(cfg)
    B, S = cell.global_batch, cell.seq_len
    bspec = policy.batch_axes or None
    seq_spec = "data" if long_ctx else None  # sequence-shard the 500k cache
    if cfg.family == "transformer":
        kv_s, hd_s = _kv_dim_specs(policy, cfg)
        shape = (cfg.n_layers, B, S, cfg.n_kv_heads, cfg.head_dim)
        sds = jax.tree.map(lambda _: _sds(shape, cfg.compute_dtype), fam.KVCache(0, 0))
        spec = jax.tree.map(lambda _: P(None, bspec, seq_spec, kv_s, hd_s),
                            fam.KVCache(0, 0))
        return sds, spec
    if cfg.family == "whisper":
        kv_s, hd_s = _kv_dim_specs(policy, cfg)
        kv_shape = (cfg.n_layers, B, S, cfg.n_kv_heads, cfg.head_dim)
        mem_shape = (B, cfg.encoder_len, cfg.d_model)
        from repro.models.whisper import WhisperCache
        from repro.models.attention import KVCache

        sds = WhisperCache(
            self_kv=KVCache(k=_sds(kv_shape, cfg.compute_dtype),
                            v=_sds(kv_shape, cfg.compute_dtype)),
            memory=_sds(mem_shape, cfg.compute_dtype))
        spec = WhisperCache(
            self_kv=KVCache(k=P(None, bspec, seq_spec, kv_s, hd_s),
                            v=P(None, bspec, seq_spec, kv_s, hd_s)),
            memory=P(bspec, None, None))
        return sds, spec
    if cfg.family == "rwkv6":
        from repro.models.rwkv6 import RwkvCache, _heads

        H, hd = _heads(cfg)
        sds = RwkvCache(
            state=_sds((cfg.n_layers, B, H, hd, hd), jnp.float32),
            shift=_sds((cfg.n_layers, B, 2, cfg.d_model), cfg.compute_dtype))
        spec = RwkvCache(
            state=P(None, bspec, None, None, None),
            shift=P(None, bspec, None, policy._model_if_divisible(cfg.d_model)))
        return sds, spec
    if cfg.family == "rglru_hybrid":
        from repro.models.rglru import HybridCache, _kinds, _lru_width
        from repro.models.attention import KVCache

        w = _lru_width(cfg)
        window = max(1, min(cfg.attn_window or S, S))
        w_spec = policy._model_if_divisible(w)
        kv_s, hd_s = _kv_dim_specs(policy, cfg)
        rec_h, conv, attn = [], [], []
        rec_h_s, conv_s, attn_s = [], [], []
        for kind in _kinds(cfg):
            if kind == "rec":
                rec_h.append(_sds((B, w), jnp.float32))
                conv.append(_sds((B, cfg.conv_width - 1, w), cfg.compute_dtype))
                attn.append(None)
                rec_h_s.append(P(bspec, w_spec))
                conv_s.append(P(bspec, None, w_spec))
                attn_s.append(None)
            else:
                rec_h.append(None)
                conv.append(None)
                attn.append(KVCache(
                    k=_sds((B, window, cfg.n_kv_heads, cfg.head_dim), cfg.compute_dtype),
                    v=_sds((B, window, cfg.n_kv_heads, cfg.head_dim), cfg.compute_dtype)))
                rec_h_s.append(None)
                conv_s.append(None)
                attn_s.append(KVCache(k=P(bspec, None, kv_s, hd_s),
                                      v=P(bspec, None, kv_s, hd_s)))
        return (HybridCache(rec_h, conv, attn), HybridCache(rec_h_s, conv_s, attn_s))
    raise ValueError(cfg.family)


# ---------------------------------------------------------------------------
# Cell builder
# ---------------------------------------------------------------------------


def build_cell(cfg: ModelConfig, shape_name: str, mesh,
               adamw: AdamWConfig = AdamWConfig(), zero1: bool = True,
               seq_parallel: bool | None = None,
               align_decode_cache: bool = True,
               microbatches: int = 1):
    """Returns dict(step_fn, specs, in_shardings, donate, kind)."""
    ok, why = applicable(cfg, shape_name)
    if not ok:
        raise ValueError(f"{cfg.name} x {shape_name} skipped: {why}")
    cell = SHAPES[shape_name]
    fam = get_family(cfg)
    long_ctx = shape_name == "long_500k"
    if seq_parallel is None:
        # Sequence parallelism on for training AND prefill by default
        # (confirmed §Perf win: phi prefill max-term 2x, compute 6.8x);
        # decode has seq_len 1 per step.
        seq_parallel = cell.kind in ("train", "prefill") and cell.seq_len > 1024
    policy = make_policy(cfg, mesh, shard_batch=not long_ctx,
                         seq_parallel=seq_parallel,
                         align_decode_cache=align_decode_cache)
    p_specs = fam.param_specs(cfg, policy)
    p_shapes = _param_shapes(cfg)
    bspec = policy.batch_axes or None
    B, S = cell.global_batch, cell.seq_len

    if cell.kind == "train":
        o_specs = opt_state_specs(p_specs, p_shapes, batch_axes=batch_axes(mesh),
                                  zero1=zero1, axis_sizes=axis_sizes(mesh))
        batch_sds = {"tokens": _sds((B, S), jnp.int32),
                     "labels": _sds((B, S), jnp.int32)}
        batch_spec = {"tokens": P(bspec, None), "labels": P(bspec, None)}
        if cfg.family == "whisper":
            batch_sds["frames"] = _sds((B, cfg.encoder_len, cfg.d_model),
                                       cfg.compute_dtype)
            batch_spec["frames"] = P(bspec, None, None)
        opt_sds = jax.eval_shape(
            lambda p: {"step": jnp.zeros((), jnp.int32),
                       "m": jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), p),
                       "v": jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), p)},
            p_shapes)

        from repro.sharding import constrain as _constrain

        grad_specs = o_specs["m"]  # ZeRO sharding for the f32 accumulator

        def shard_grads(g):
            # ZeRO-2-style: the f32 grad accumulator lives DP-sharded (each
            # microbatch's grads are reduce-scattered into it), so its
            # footprint matches the opt states instead of the full model.
            return jax.tree.map(lambda x, s: _constrain(x, s), g, grad_specs,
                                is_leaf=lambda x: x is None)

        def train_step(params, opt_state, batch):
            if microbatches > 1:
                # gradient accumulation: peak activation memory drops by the
                # microbatch count; DP sync still happens once per step
                split = jax.tree.map(
                    lambda x: x.reshape((microbatches, x.shape[0] // microbatches)
                                        + x.shape[1:]), batch)

                def micro(carry, mb):
                    l, g = jax.value_and_grad(
                        lambda p: fam.loss_fn(p, mb, cfg, policy))(params)
                    acc = jax.tree.map(jnp.add, carry[1], g)
                    return (carry[0] + l, shard_grads(acc)), None

                zero = shard_grads(jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params))
                (loss, grads), _ = jax.lax.scan(micro, (jnp.zeros(()), zero), split)
                inv = 1.0 / microbatches
                loss = loss * inv
                grads = jax.tree.map(lambda g: g * inv, grads)
            else:
                loss, grads = jax.value_and_grad(
                    lambda p: fam.loss_fn(p, batch, cfg, policy))(params)
            params, opt_state, metrics = adamw_update(
                params, grads, opt_state, adamw,
                update_specs=grad_specs if zero1 else None)
            metrics["loss"] = loss
            return params, opt_state, metrics

        return {
            "step_fn": train_step,
            "specs": (p_shapes, opt_sds, batch_sds),
            "in_shardings": (_ns(mesh, p_specs), _ns(mesh, o_specs),
                             _ns(mesh, batch_spec)),
            # outputs alias the donated inputs: pin the same layouts so the
            # compiler never inserts a gather to satisfy an unconstrained
            # output (it would break aliasing too)
            "out_shardings": (_ns(mesh, p_specs), _ns(mesh, o_specs), None),
            "donate": (0, 1),
            "kind": "train",
        }

    if cell.kind == "prefill":
        tok_sds = _sds((B, S), jnp.int32)

        if cfg.family == "whisper":
            batch_sds = {"frames": _sds((B, cfg.encoder_len, cfg.d_model),
                                        cfg.compute_dtype),
                         "tokens": tok_sds}
            batch_spec = {"frames": P(bspec, None, None), "tokens": P(bspec, None)}

            def prefill_step(params, batch):
                return fam.prefill(params, batch, cfg, policy, max_len=S)

            return {"step_fn": prefill_step,
                    "specs": (p_shapes, batch_sds),
                    "in_shardings": (_ns(mesh, p_specs), _ns(mesh, batch_spec)),
                    "donate": (), "kind": "prefill"}

        def prefill_step(params, tokens):
            return fam.prefill(params, tokens, cfg, policy, max_len=S)

        return {"step_fn": prefill_step,
                "specs": (p_shapes, tok_sds),
                "in_shardings": (_ns(mesh, p_specs),
                                 NamedSharding(mesh, P(bspec, None))),
                "donate": (), "kind": "prefill"}

    # decode
    cache_sds, cache_spec = cache_specs(cfg, cell, policy, long_ctx)
    tok_sds = _sds((B, 1), jnp.int32)
    pos_sds = _sds((), jnp.int32)

    def decode_step(params, cache, tokens, pos):
        return fam.decode_step(params, cache, tokens, pos, cfg, policy)

    return {"step_fn": decode_step,
            "specs": (p_shapes, cache_sds, tok_sds, pos_sds),
            "in_shardings": (_ns(mesh, p_specs), _ns(mesh, cache_spec),
                             NamedSharding(mesh, P(bspec, None)),
                             NamedSharding(mesh, P())),
            "out_shardings": (None, _ns(mesh, cache_spec)),
            "donate": (1,),
            "kind": "decode"}


def input_specs(cfg: ModelConfig, shape_name: str, mesh):
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    return build_cell(cfg, shape_name, mesh)["specs"]
