"""The one traffic generator: token rows for a training job, from the
traffic file's parameters and the run's seed.

Rows follow an order-1 Markov chain over the vocabulary: each token has
``branching`` successors, drawn from the seed, and each step picks one
of them uniformly (the structure of the program's SyntheticLM, made
here on the host with numpy so the benchmark owns its inputs). So a loss
can fall well below log(vocab), every row differs, and the batch of
(seed, step) is the same wherever and whenever it is asked for.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

# Keeps the successor table's stream apart from the rows' streams.
_TABLE = 0x7AB1E


class MarkovTokens:
    """Batch source with the interface the program's ``DataPipeline``
    reads: ``batch(step, batch_size, seq_len, shard, num_shards)``."""

    def __init__(self, vocab: int, seed: int, branching: int):
        if seed < 0:
            raise ValueError(f"seed must be a whole number, got {seed}")
        self.vocab, self.seed, self.branching = vocab, seed, branching
        rng = np.random.default_rng([seed, _TABLE])
        self.succ = rng.integers(0, vocab, size=(vocab, branching),
                                 dtype=np.int32)

    def rows(self, step: int, first: int, count: int,
             seq_len: int) -> np.ndarray:
        """Global rows ``first .. first+count-1`` of step ``step``:
        int32 (count, seq_len + 1)."""
        starts = np.empty((count,), np.int32)
        choices = np.empty((count, seq_len + 1), np.int32)
        for i in range(count):
            rng = np.random.default_rng([self.seed, step, first + i])
            starts[i] = rng.integers(0, self.vocab)
            choices[i] = rng.integers(0, self.branching, size=seq_len + 1)
        out = np.empty((count, seq_len + 1), np.int32)
        tok = starts
        for t in range(seq_len + 1):
            tok = self.succ[tok, choices[:, t]]
            out[:, t] = tok
        return out

    def batch(self, step: int, batch_size: int, seq_len: int,
              shard: int = 0, num_shards: int = 1) -> Dict[str, np.ndarray]:
        rows = self.rows(step, shard * batch_size, batch_size, seq_len)
        return {"tokens": rows[:, :-1], "labels": rows[:, 1:]}


def source(traffic: dict, vocab: int, seed: int) -> MarkovTokens:
    tokens = traffic["tokens"]
    if tokens["kind"] != "markov":
        raise ValueError(f"unknown token kind {tokens['kind']!r}")
    return MarkovTokens(vocab, seed, tokens["branching"])
