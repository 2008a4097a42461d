"""Architecture registry: one module per assigned architecture.

``get_config(arch_id)`` returns the full assigned configuration;
``get_smoke_config(arch_id)`` returns a reduced same-family configuration
for CPU smoke tests (small layers/width, few experts, tiny vocab);
``scaled_config(arch_id, scale)`` picks one of the three by name for the
serve and train entry points.
"""

from __future__ import annotations

import dataclasses
import importlib

ARCH_IDS = [
    "phi3_5_moe",
    "moonshot_v1_16b",
    "yi_6b",
    "qwen1_5_0_5b",
    "glm4_9b",
    "gemma3_12b",
    "chameleon_34b",
    "whisper_base",
    "recurrentgemma_2b",
    "rwkv6_3b",
]

# canonical external names -> module ids
ALIASES = {
    "phi3.5-moe-42b-a6.6b": "phi3_5_moe",
    "moonshot-v1-16b-a3b": "moonshot_v1_16b",
    "yi-6b": "yi_6b",
    "qwen1.5-0.5b": "qwen1_5_0_5b",
    "glm4-9b": "glm4_9b",
    "gemma3-12b": "gemma3_12b",
    "chameleon-34b": "chameleon_34b",
    "whisper-base": "whisper_base",
    "recurrentgemma-2b": "recurrentgemma_2b",
    "rwkv6-3b": "rwkv6_3b",
}


def _module(arch_id: str):
    arch_id = ALIASES.get(arch_id, arch_id)
    if arch_id not in ARCH_IDS:
        raise KeyError(f"unknown arch {arch_id!r}; have {ARCH_IDS}")
    return importlib.import_module(f"repro.configs.{arch_id}")


def get_config(arch_id: str):
    return _module(arch_id).CONFIG


def get_smoke_config(arch_id: str):
    return _module(arch_id).smoke_config()


def all_configs():
    return {a: get_config(a) for a in ARCH_IDS}


SCALES = ("smoke", "100m", "full")


def scaled_config(arch_id: str, scale: str):
    """``full``: the published widths; ``smoke``: the CPU smoke config;
    ``100m``: a same-family ~100M-parameter reduction."""
    if scale == "full":
        return get_config(arch_id)
    if scale == "smoke":
        return get_smoke_config(arch_id)
    if scale == "100m":
        import jax.numpy as jnp

        cfg = get_smoke_config(arch_id)
        return dataclasses.replace(
            cfg, n_layers=8, d_model=512, n_heads=8,
            n_kv_heads=min(8, max(1, cfg.n_kv_heads)), head_dim=64,
            d_ff=2048, vocab=32768, loss_chunk=256,
            param_dtype=jnp.float32, compute_dtype=jnp.float32, remat=False)
    raise ValueError(f"unknown scale {scale!r}; have {SCALES}")
