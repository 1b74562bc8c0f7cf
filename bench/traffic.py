"""The one traffic generator: what a mix file (``bench/mixes/<mix>.json``)
asks for, drawn from the run's seed.

A mix is data.  Its ``loop`` names the window loop that drives the system
(``bench/loops/<loop>.py``: ``run`` and ``warm_sizes``), and its other keys
are parameters that this module turns into requests:

- arrivals (``rate_per_s``, ``gap_seed``, ``burst``): due times of a
  Poisson process.  Every seed gets the same inter-arrival gaps (drawn from
  ``gap_seed``) in an order of its own, so the offered work is the same and
  only its order and the queries change.  ``burst`` > 1 makes each arrival
  a burst of that many requests due at once, at ``rate_per_s / burst``
  bursts a second.
- queries (``query_pool``, ``cluster_skew``): the pool of query vectors a
  window cycles through, drawn from the seed out of the configuration's
  mixture, with the clusters weighted as Zipf(``cluster_skew``); 0, the
  default, is uniform.
- batches (``batch``, ``k``, ``min_batch``, ``max_batch``): what each
  request asks for and the engine's buckets.

A mix that changes only these numbers is a new data file; one that needs a
new kind of request (upserts, filters) adds a loop file of its own.
"""
from __future__ import annotations

import numpy as np

from bench import data


def poisson_due(rate: float, seconds: float, gap_seed: int, seed: int,
                burst: int = 1):
    """Due times (s from the window's start) of ``round(rate * seconds)``
    requests: bursts of ``burst`` requests at exponential gaps of mean
    ``burst / rate`` drawn from ``gap_seed``, put in an order drawn from
    ``seed``."""
    burst = max(1, int(burst))
    n = max(1, int(round(rate * seconds / burst)))
    gaps = np.random.default_rng(gap_seed).exponential(burst / rate, n)
    order = np.random.default_rng(
        np.random.SeedSequence([int(seed), 7])).permutation(n)
    return np.repeat(np.cumsum(gaps[order]), burst)


def due_times(mix: dict, seconds: float, seed: int):
    return poisson_due(float(mix["rate_per_s"]), seconds,
                       int(mix["gap_seed"]), seed, int(mix.get("burst", 1)))


def queries(cfg: dict, mix: dict, seed: int):
    """[query_pool, d] query vectors on the device: drawn from ``seed`` out
    of the configuration's mixture (its centres from ``corpus_seed``)."""
    return data.clustered_vectors(
        int(mix["query_pool"]), int(cfg["d"]), int(cfg["corpus_seed"]), seed,
        2, n_clusters=int(cfg["clusters"]), spread=float(cfg["spread"]),
        skew=float(mix.get("cluster_skew", 0.0)))


def percentile(values, q: float) -> float:
    """The ``q``-th percentile of all values (linear between ranks)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def pad_rows(mix: dict, flush_sizes) -> tuple[int, int]:
    """(pad rows, rows sent to the index) of a window's flushes, by the
    engine's buckets: a flush of m splits into ``max_batch`` chunks and each
    pads to the next pow2 at least ``min_batch``."""
    lo, hi = int(mix["min_batch"]), int(mix["max_batch"])
    pad = sent = 0
    for m in flush_sizes:
        for s in range(0, m, hi):
            c = min(hi, m - s)
            b = min(hi, max(lo, 1 << (c - 1).bit_length()))
            pad += b - c
            sent += b
    return pad, sent


class Flushes:
    """What a serving loop records: the answers by request id, when each
    came back, and each flush's size, start (s from the window's start) and
    duration."""

    def __init__(self):
        self.answers, self.done_t = {}, {}
        self.sizes, self.start_s, self.flush_s = [], [], []

    def add(self, out: dict, m: int, start: float, end: float) -> None:
        for rid, ans in out.items():
            self.answers[rid] = ans
            self.done_t[rid] = end
        self.sizes.append(m)
        self.start_s.append(start)
        self.flush_s.append(end - start)

    def result(self, **kw) -> dict:
        return dict(answers=self.answers, flush_sizes=self.sizes,
                    flush_start_s=self.start_s, flush_s=self.flush_s, **kw)
