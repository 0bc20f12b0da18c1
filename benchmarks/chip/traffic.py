"""One generator for every traffic mix; a mix is a data file in `mixes/`.

Two kinds of mix:

- ``open_loop``: Poisson arrivals at a fixed rate, queries drawn by the
  test log's popularity. The arrival times are one fixed Poisson sample
  path (the exponential law's quantiles in one fixed order), the same for
  every seed: a tail percentile over a window is set by a few bursts, so
  a path drawn per seed would make the seed change the work. The seed
  orders the queries, a fixed multiset (the popularity law's quantiles)
  of its own data.
- ``refit_rotate``: topic rotation drift (K topics, the hot topic boosted
  by 1 + 15 * strength, dwelling `dwell` windows). Refit i of a cycle
  solves for epoch i's window, blended with the training prior as a
  re-tiering controller does, and probes the new generation with a batch
  drawn from that window. Topics and probes are drawn from one fixed
  seed over the corpus's fixed layout (`corpus.py`), so every seed
  refits the same drift over its own ids.
"""
from __future__ import annotations

import dataclasses

import numpy as np


ARRIVAL_PATH = 20050789      # seed of the one arrival path all runs share
DRIFT = 20050791             # seed of the one drift all runs share


def stream(seed: int, name: int) -> np.random.Generator:
    """A generator for one purpose, independent of the data's stream."""
    return np.random.default_rng([seed, name])


def normalize(p: np.ndarray) -> np.ndarray:
    p = np.asarray(p, np.float64)
    return p / p.sum()


@dataclasses.dataclass
class OpenLoop:
    arrivals: np.ndarray      # f64 [n] seconds after the window opens
    query_ids: np.ndarray     # int64 [n] ids into the unique-query list


def open_loop(mix: dict, weights: np.ndarray, seed: int,
              seconds: float) -> OpenLoop:
    rate = float(mix["rate_qps"])
    n = max(1, int(round(rate * seconds)))
    u = (np.arange(n) + 0.5) / n
    gaps = np.random.default_rng(ARRIVAL_PATH).permutation(-np.log1p(-u))
    gaps *= seconds / gaps.sum()
    arrivals = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    cdf = np.cumsum(normalize(weights))
    picks = np.searchsorted(cdf, u * cdf[-1], side="right")
    picks = np.minimum(picks, len(cdf) - 1)
    return OpenLoop(arrivals=arrivals,
                    query_ids=stream(seed, 1).permutation(picks))


@dataclasses.dataclass
class RefitStep:
    weights: np.ndarray       # f64 [Nq] what the refit solves for
    window: np.ndarray        # f64 [Nq] the drift window's distribution
    probe_ids: np.ndarray     # int64 [probe_batch]


def refit_rotate(mix: dict, test_weights: np.ndarray,
                 train_weights: np.ndarray) -> list[RefitStep]:
    """One cycle of refits, one per topic epoch."""
    k, dwell = int(mix["topics"]), int(mix["dwell"])
    boost = 1.0 + 15.0 * float(mix["strength"])
    lam = float(mix["blend_prior"])
    p0 = normalize(test_weights)
    prior = normalize(train_weights)
    rng = np.random.default_rng(DRIFT)
    topic = rng.integers(0, k, size=len(p0))
    steps = []
    for epoch in range(int(mix["cycle"])):
        t = epoch * dwell
        window = normalize(p0 * np.where(topic == (t // dwell) % k, boost,
                                         1.0))
        probe = rng.choice(len(window), size=int(mix["probe_batch"]),
                           p=window)
        steps.append(RefitStep(weights=(1.0 - lam) * window + lam * prior,
                               window=window, probe_ids=probe))
    return steps
