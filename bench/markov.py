"""Token streams drawn from the seed: an order-k Markov source with noise.

The source of ``repro.data.synthetic.SyntheticLM``: each token is a fixed
random function of the previous ``order`` tokens (a sum of per-lag table
entries, modulo the vocabulary), replaced by a uniform draw with
probability ``noise``. Here all rows are drawn at once from one generator
seeded by ``seed``, so a run's whole feed is one host pass at set-up.
"""
from __future__ import annotations

import numpy as np


def markov_rows(seed: int, rows: int, length: int, vocab: int,
                order: int = 2, noise: float = 0.1) -> np.ndarray:
    """(rows, length) int32 tokens in [0, vocab)."""
    rng = np.random.default_rng([seed, rows, length, vocab])
    table = rng.integers(0, vocab, size=(order, vocab))
    toks = np.zeros((rows, length), np.int64)
    toks[:, :order] = rng.integers(0, vocab, size=(rows, order))
    for t in range(order, length):
        det = np.zeros(rows, np.int64)
        for k in range(order):
            det += table[k][toks[:, t - 1 - k]]
        det %= vocab
        rand = rng.integers(0, vocab, size=rows)
        toks[:, t] = np.where(rng.random(rows) < noise, rand, det)
    return toks.astype(np.int32)


def feed(traffic: dict, vocab: int, seed: int) -> np.ndarray:
    """A cell's whole feed: (distinct_steps, batch x grad_accum, seq + 1)."""
    rows = traffic["batch"] * traffic["grad_accum"]
    toks = markov_rows(seed, traffic["distinct_steps"] * rows, traffic["seq"] + 1,
                       vocab, **traffic["source"])
    return toks.reshape(traffic["distinct_steps"], rows, traffic["seq"] + 1)
