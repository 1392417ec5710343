"""Deterministic, shardable synthetic data pipeline.

Two sources:

* :class:`BigramSource` — sequences from a fixed sparse random Markov chain,
  so a language model has real structure to learn (training-loss benchmarks
  and the convergence examples need a learnable task, not noise);
* :class:`SyntheticBatches` — uniform tokens + gaussian frontend embeddings,
  shaped per architecture (used for throughput work where content is
  irrelevant).

Determinism: batch t of worker w depends only on (seed, t, w), so any worker
can be restarted independently — the property real distributed input
pipelines need.  Generation is host-side numpy (Philox counters), then
device_put with the batch sharding.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import numpy as np

from repro.configs.base import InputShape, ModelConfig

SUCCESSORS = 16  # candidate next tokens per token in BigramSource


@dataclass
class BigramSource:
    """Each token has ``SUCCESSORS`` seeded candidate next tokens, drawn
    uniformly from the vocab, with softmax(normal / temperature)
    probabilities.  Host memory and per-token sampling cost are
    O(vocab * SUCCESSORS), so the source works at real vocab sizes."""

    vocab: int
    seed: int = 0
    temperature: float = 0.5

    def __post_init__(self):
        rng = np.random.default_rng(np.random.Philox(key=self.seed))
        k = min(SUCCESSORS, self.vocab)
        self.next = rng.integers(0, self.vocab, (self.vocab, k), dtype=np.int32)
        logits = rng.normal(size=(self.vocab, k)) / self.temperature
        p = np.exp(logits - logits.max(1, keepdims=True))
        self.cum = np.cumsum(p / p.sum(1, keepdims=True), axis=1)
        self.cum[:, -1] = 1.0  # no rounding gap past the last candidate

    def batch(self, step: int, batch: int, seq: int, worker: int = 0) -> dict[str, np.ndarray]:
        rng = np.random.default_rng(np.random.Philox(key=self.seed + 1, counter=[step, worker, 0, 0]))
        toks = np.empty((batch, seq + 1), np.int32)
        toks[:, 0] = rng.integers(0, self.vocab, batch)
        u = rng.random((batch, seq))
        for t in range(seq):
            cur = toks[:, t]
            pick = (self.cum[cur] > u[:, t : t + 1]).argmax(1)
            toks[:, t + 1] = self.next[cur, pick]
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:].copy()}


@dataclass
class SyntheticBatches:
    cfg: ModelConfig
    shape: InputShape
    seed: int = 0

    def batch(self, step: int) -> dict[str, np.ndarray]:
        cfg, shape = self.cfg, self.shape
        B, S = shape.global_batch, shape.seq_len
        rng = np.random.default_rng(np.random.Philox(key=self.seed, counter=[step, 0, 0, 0]))
        out: dict[str, np.ndarray] = {}
        S_text = S
        if cfg.modality == "vision":
            S_vis = int(S * cfg.vision_fraction)
            S_text = S - S_vis
            out["patches"] = rng.normal(size=(B, S_vis, cfg.d_model)).astype(np.float32)
        if cfg.is_encoder_decoder:
            S_enc = max(1, S // cfg.encoder_ratio)
            out["frames"] = rng.normal(size=(B, S_enc, cfg.d_model)).astype(np.float32)
        out["tokens"] = rng.integers(0, cfg.vocab, (B, S_text)).astype(np.int32)
        if shape.kind == "train":
            out["labels"] = rng.integers(0, cfg.vocab, (B, S_text)).astype(np.int32)
        return out
