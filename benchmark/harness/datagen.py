"""Seeded input distributions shared by the model adapters."""

from __future__ import annotations

import functools

import numpy as np


@functools.lru_cache(maxsize=8)
def _zipf_cdf(n: int, s: float):
    mass = np.arange(1, n + 1, dtype=np.float64) ** (-s)
    cdf = np.cumsum(mass)
    return cdf / cdf[-1]


def zipf_ids(rng, shape, vocab: int, s: float = 1.1, first: int = 0):
    """Ids in [first, vocab) from a truncated Zipf(s), by inverse CDF
    (np.random.zipf is unbounded; truncating it by rejection is biased).
    Copied from `paddle_tpu.streaming.zipf_ids` / `bench.py` `_zipf_ids`:
    real text and real click logs are Zipf-distributed, so embedding
    gathers are skewed as theirs are and a unigram model has something
    to learn. Rank 0 is the most frequent id. Every shipped mix draws at
    s = 1.1."""
    cdf = _zipf_cdf(int(vocab) - int(first), float(s))
    u = rng.random_sample(int(np.prod(shape)))
    ids = np.searchsorted(cdf, u, side="left").astype(np.int64) + int(first)
    return ids.reshape(shape)


def distinct_positions(rng, rows: int, length: int, k: int):
    """`k` distinct sorted positions in [0, length) for each row."""
    return np.sort(np.argsort(rng.random_sample((rows, length)), axis=1)[:, :k],
                   axis=1).astype(np.int64)
