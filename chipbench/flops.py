"""Model FLOPs of a dense decoder LM's training step, from the configuration's shapes.

Counted: 2 FLOPs per matrix parameter per token (attention projections, the
gated MLP's three matrices and the output head over the vocabulary), and the
attention products Q K^T and P V at their causal half (on average a query
sees ``seq_len / 2`` keys); then x3 for the forward and backward passes.
Not counted: the embedding gather (a lookup, not a product), biases, norms,
softmax and other elementwise work, and anything ``remat`` recomputes.
"""

from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Matrix parameters a token passes through once, the head included."""
    d, f, L = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_hidden_layers"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    return L * (attn + 3 * d * f) + cfg["vocab_size"] * d


def attention_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Q K^T and P V of every layer, forward only, at the causal half."""
    h, L = cfg["num_attention_heads"], cfg["num_hidden_layers"]
    hd = cfg["hidden_size"] // h
    return L * 2 * 2 * h * hd * seq_len / 2


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    return 3 * (2 * matmul_params(cfg) + attention_flops_per_token(cfg, seq_len))


def train_step_flops(cfg: dict, batch: int, seq_len: int) -> float:
    return batch * seq_len * train_flops_per_token(cfg, seq_len)
