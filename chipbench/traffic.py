"""Seeded token streams for every traffic mix.

``SyntheticSource`` is a copy of the ``arith`` rows of the program's
generator (``repro.data.pipeline.SyntheticSource``), kept here so that no
change to the program can change the benchmark's inputs.  Rows are
arithmetic runs (next token = previous + stride mod vocab) from a seeded
start and stride, so every seed gives rows of the same sizes and only the
token ids differ.
"""

from __future__ import annotations

import numpy as np


class SyntheticSource:
    """Deterministic rows: the same (seed, step) gives the same
    (batch, seq_len + 1) int32 array."""

    def __init__(self, batch: int, seq_len: int, vocab: int, seed: int):
        self.batch, self.seq_len, self.vocab, self.seed = batch, seq_len, vocab, seed

    def batch_at(self, step: int) -> np.ndarray:
        rng = np.random.default_rng((self.seed, 0, step))
        start = rng.integers(0, self.vocab, size=(self.batch, 1))
        stride = rng.integers(1, 4, size=(self.batch, 1))
        t = np.arange(self.seq_len + 1)[None, :]
        return ((start + stride * t) % self.vocab).astype(np.int32)


def seed32(seed: int) -> int:
    """Fold a seed of any size into 31 bits for ``jax.random.PRNGKey``."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0]) & 0x7FFFFFFF
