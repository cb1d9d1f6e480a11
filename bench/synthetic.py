"""Seeded token rows for the benchmark's training cells.

A copy of the program's ``SyntheticLM`` generator, kept here so that a
change to the program cannot change what the benchmark feeds it: Zipf(1.3)
token ids with a bigram signal on every even position, one row per
sequence, each row drawn from ``SeedSequence([seed, row])``.  The same
seed gives the same rows, and every row differs from every other.
"""
from __future__ import annotations

import numpy as np


class SyntheticLM:
    def __init__(self, vocab_size: int, seq_len: int, seed: int = 0):
        self.vocab_size = vocab_size
        self.seq_len = seq_len
        self.seed = seed
        self.position = 0

    def next_batch(self, batch: int) -> np.ndarray:
        out = np.empty((batch, self.seq_len), np.int32)
        for b in range(batch):
            rng = np.random.default_rng(
                np.random.SeedSequence([self.seed, self.position + b]))
            z = rng.zipf(1.3, size=self.seq_len).astype(np.int64)
            toks = (z - 1) % self.vocab_size
            toks[1::2] = (toks[:-1:2] * 31 + 7) % self.vocab_size
            out[b] = toks.astype(np.int32)
        self.position += batch
        return out
