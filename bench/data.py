"""The fine-tune job's token stream: a noisy-permutation language.

Token ``t+1`` is ``perm[token_t]`` with probability ``1 - noise``, else
uniform over the vocabulary; each row starts at a uniform token.  A model can
learn it, so training moves the loss as real fine-tuning does.  The
permutation is drawn from the seed, and each step's rows from ``(seed,
step)``, so step ``i`` is the same batch whichever step a run starts from.
"""
from __future__ import annotations

from typing import Dict, Iterator

import numpy as np


class NoisyPermutation:
    def __init__(self, vocab: int, seq_len: int, rows: int, seed: int,
                 noise: float):
        self.vocab, self.seq_len, self.rows = vocab, seq_len, rows
        self.seed, self.noise = int(seed), float(noise)
        self.perm = np.random.default_rng((self.seed, 0x5EED)).permutation(
            vocab).astype(np.int32)

    def batch(self, step: int) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng((self.seed, int(step)))
        B, S = self.rows, self.seq_len
        toks = np.empty((B, S + 1), np.int32)
        toks[:, 0] = rng.integers(0, self.vocab, B)
        flip = rng.random((B, S)) < self.noise
        rand = rng.integers(0, self.vocab, (B, S), dtype=np.int32)
        for t in range(S):
            toks[:, t + 1] = np.where(flip[:, t], rand[:, t],
                                      self.perm[toks[:, t]])
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:].copy()}

    def __call__(self, start: int) -> Iterator[Dict[str, np.ndarray]]:
        """Batches of steps ``start, start + 1, ...``."""
        step = int(start)
        while True:
            yield self.batch(step)
            step += 1
