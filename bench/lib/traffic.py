"""Open-loop request schedules from a traffic mix's parameters and a seed.

A mix is a data file (``bench/traffic/<name>.json``) read by this one
generator:

  rate_per_s      arrivals per second; each arrival opens a session
  k, b            the session's ``search(q, k, b=b)``
  more_k          0, or the page size of one ``more(sid, more_k)`` that is
                  due ``think`` seconds after the search's page returned
  think_mean_s    mean of the exponential think time
  warmup_sessions sessions run one by one before the window, on queries
                  the window never sends

Every seed gets the same work: the gaps between arrivals and the think
times are the same set of exponential quantiles, and the sessions' queries
the same held-out queries, each in an order drawn from the seed.  So the
arrivals are a Poisson process conditioned on its count, and two seeds
differ in order, not in load.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Schedule:
    due_s: np.ndarray      # [n] arrival times from the window's start, ascending
    query: np.ndarray      # [n] indices into the queries
    think_s: np.ndarray    # [n] think time before the session's more()
    warmup: np.ndarray     # query indices of the warm-up sessions

    @property
    def n_queries(self) -> int:
        return len(self.warmup) + len(self.query)


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def exp_quantiles(n: int, mean: float) -> np.ndarray:
    """The n stratified quantiles of an exponential distribution."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) * mean


def schedule(mix: dict, seed: int, seconds: float) -> Schedule:
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    gaps = _rng(seed, 10).permutation(exp_quantiles(n, 1.0))
    due = seconds * np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) / gaps.sum()
    think = _rng(seed, 11).permutation(exp_quantiles(n, mix.get("think_mean_s", 0.0)))
    n_warm = int(mix.get("warmup_sessions", 0))
    query = _rng(seed, 12).permutation(np.arange(n_warm, n_warm + n))
    return Schedule(due_s=due, query=query, think_s=think, warmup=np.arange(n_warm))
