"""The benchmark's traffic generator for training cells: token sequences
from a fixed sparse random Markov chain (a copy of the program's
``BigramSource``, kept here so that no change to the program can change the
traffic it is measured on).

Each token has ``successors`` seeded candidate next tokens drawn uniformly
from the vocabulary, with softmax(normal / temperature) probabilities.  Batch
``step`` depends only on (seed, step), so the generator runs inside the
training loop exactly as a real input pipeline does: one call per step, on the
host, while the device works on the previous step.
"""

from __future__ import annotations

import numpy as np


class BigramSource:
    def __init__(self, vocab: int, seed: int, *, successors: int = 16,
                 temperature: float = 0.5):
        self.vocab = vocab
        self.seed = seed
        rng = np.random.default_rng(np.random.Philox(key=seed))
        k = min(successors, vocab)
        self.next = rng.integers(0, vocab, (vocab, k), dtype=np.int32)
        logits = rng.normal(size=(vocab, k)) / temperature
        p = np.exp(logits - logits.max(1, keepdims=True))
        self.cum = np.cumsum(p / p.sum(1, keepdims=True), axis=1)
        self.cum[:, -1] = 1.0  # no rounding gap past the last candidate

    def batch(self, step: int, batch: int, seq: int) -> dict[str, np.ndarray]:
        """``{"tokens", "labels"}``, each (batch, seq) int32; labels are the
        tokens shifted by one."""
        rng = np.random.default_rng(
            np.random.Philox(key=self.seed + 1, counter=[step, 0, 0, 0]))
        toks = np.empty((batch, seq + 1), np.int32)
        toks[:, 0] = rng.integers(0, self.vocab, batch)
        u = rng.random((batch, seq))
        for t in range(seq):
            cur = toks[:, t]
            pick = (self.cum[cur] > u[:, t:t + 1]).argmax(1)
            toks[:, t + 1] = self.next[cur, pick]
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:].copy()}
