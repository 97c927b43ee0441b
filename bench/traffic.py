"""The one generator of the benchmark's traffic.

A mix is a data file, ``bench/traffic/<mix>.json``, whose ``kind`` names
the driver that runs it (``bench/drivers/<kind>.py``) and whose other keys
are that kind's parameters.  Everything a run sends is drawn here from the
run's ``--seed``, one independent stream per (purpose, index), so the same
seed always gives the same inputs and item ``i`` does not depend on how
many items came before it.
"""

from __future__ import annotations

import numpy as np


def rng(seed: int, *stream) -> np.random.Generator:
    """The generator of one stream of one seed."""
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, *stream])


# stream ids
TOKENS, ARRIVALS, LENGTHS, SAMPLE, PROMPTS = range(5)


def zipf_tokens(g: np.random.Generator, shape, vocab: int) -> np.ndarray:
    """Token ids with a Zipf-like (alpha about 1) frequency skew, as natural
    text has: inverse CDF of a log-uniform draw."""
    u = g.random(shape)
    return np.minimum((np.exp(u * np.log(vocab)) - 1).astype(np.int64),
                      vocab - 1).astype(np.int32)


def token_batch(seed: int, index: int, batch: int, seq: int,
                vocab: int) -> np.ndarray:
    """Batch ``index`` of ``batch`` rows of ``seq`` tokens; every row of
    every batch is drawn afresh."""
    return zipf_tokens(rng(seed, TOKENS, index), (batch, seq), vocab)


def lognormal_lengths(g: np.random.Generator, n: int, median: float,
                      sigma: float, lo: int, hi: int) -> np.ndarray:
    """Heavy-tailed lengths: lognormal around ``median``, clipped."""
    x = np.exp(np.log(median) + sigma * g.standard_normal(n))
    return np.clip(np.round(x), lo, hi).astype(np.int64)


def sample(seed: int, n_items: int, k: int, always=()) -> list:
    """``k`` item indices out of ``n_items`` drawn from the seed, with the
    indices in ``always`` kept."""
    keep = [i for i in always if 0 <= i < n_items]
    rest = [i for i in range(n_items) if i not in keep]
    g = rng(seed, SAMPLE, n_items)
    extra = g.permutation(len(rest))[:max(k - len(keep), 0)]
    return sorted(keep + [rest[i] for i in extra])
