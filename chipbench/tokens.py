"""The token generator: every ``kind: "train"`` mix under ``traffic/`` is parameters for this.

A first-order Markov stream over the configuration's whole vocabulary: each
sequence starts at a token drawn uniformly, and each next token is one of
``branching`` fixed successors of the current one, drawn uniformly.  A model
that learns the chain falls far below the uniform entropy, so the loss has
somewhere to go.  Step ``k`` of seed ``s`` is the same batch in every run,
and no two steps share a row.  The arithmetic is that of
``repro.data.pipeline.SyntheticLMSource``, kept here with the yardstick.
"""

from __future__ import annotations

import numpy as np


def markov_batch(seed: int, step: int, batch: int, seq_len: int, vocab: int,
                 branching: int) -> dict:
    """``{"tokens", "labels"}``, int32 (batch, seq_len); labels are the
    tokens shifted by one."""
    rng = np.random.Generator(np.random.Philox(key=seed, counter=[0, 0, 0, step]))
    toks = np.empty((batch, seq_len + 1), np.int32)
    toks[:, 0] = rng.integers(0, vocab, size=batch)
    for t in range(seq_len):
        j = rng.integers(0, branching, size=batch)
        prev = toks[:, t].astype(np.int64)
        toks[:, t + 1] = (prev * 2654435761 + j * 40503 + 17) % vocab
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:].copy()}
