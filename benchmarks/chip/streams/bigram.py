"""Seeded token rows with learnable bigram structure: a copy of the
repository's ``SyntheticLM`` stream, kept here so that a change to the
program cannot move the yardstick.

A fixed permutation of the vocabulary is the "true" next token, and each
position is replaced by a uniform draw with probability ``noise``; step
``s`` of seed ``seed`` is always the same rows.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np

try:  # host spans in the profiler's trace; absent outside JAX, never fatal
    from jax.profiler import TraceAnnotation
except ImportError:  # pragma: no cover
    from contextlib import nullcontext as TraceAnnotation  # type: ignore

INPUT_SPAN = "bench.input"


class BigramStream:
    """``batch(step, rows)`` returns ``{"tokens", "labels"}`` of shape
    ``(rows, seq_len)``, a pure function of ``(seed, step, rows)``."""

    def __init__(self, vocab: int, seq_len: int, seed: int, noise: float = 0.3):
        self.vocab = int(vocab)
        self.seq_len = int(seq_len)
        self.seed = int(seed)
        self.noise = float(noise)
        self.rule = np.random.default_rng(self.seed).permutation(self.vocab)

    def batch(self, step: int, batch_size: int) -> Dict[str, np.ndarray]:
        with TraceAnnotation(INPUT_SPAN):
            rng = np.random.default_rng((self.seed, int(step)))
            toks = np.empty((batch_size, self.seq_len + 1), np.int32)
            toks[:, 0] = rng.integers(0, self.vocab, batch_size)
            for t in range(1, self.seq_len + 1):
                nxt = self.rule[toks[:, t - 1]]
                corrupt = rng.random(batch_size) < self.noise
                nxt = np.where(corrupt, rng.integers(0, self.vocab, batch_size), nxt)
                toks[:, t] = nxt
            return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def make(params: Dict[str, Any], vocab: int, seq_len: int, seed: int) -> BigramStream:
    """The stream a mix's ``stream`` entry describes."""
    return BigramStream(vocab, seq_len, seed, noise=params["noise"])
