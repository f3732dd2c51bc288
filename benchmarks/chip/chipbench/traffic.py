"""The one traffic generator: an open-loop arrival schedule from a mix's
parameters and the seed.

A mix (``traffic/<name>.json``) gives:

* ``arrivals``: ``poisson``, the only arrival process so far;
* ``rate_per_s``: the offered load. Exactly ``round(rate * seconds)``
  requests arrive in a window, at the order statistics of uniform draws
  over it (a Poisson process given its count), so every seed offers the
  same amount of work in another order;
* ``zipf_s``: the skew of users, ``P(rank r) ~ r**-s`` over the resident
  population, with ranks mapped to user ids by a permutation drawn from
  the seed;
* ``candidates``: C per request;
* ``in_interest_share``: the share of candidates drawn from one of the
  user's interest categories (the rest from any category), each item
  uniform within its category;
* ``max_burst``: the most requests the front end hands over in one call.

Every request carries host NumPy arrays only: its candidates' item and
category ids, a binary context per candidate, and the user's recent
window of ``short_len`` behaviours.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


@dataclasses.dataclass
class Schedule:
    due: np.ndarray           # (n,) seconds from the window's start
    users: np.ndarray         # (n,) user ids
    cand_items: np.ndarray    # (n, C) int32
    cand_cats: np.ndarray     # (n, C) int32
    ctx: np.ndarray           # (n, C, ctx_dim) float32

    def __len__(self) -> int:
        return len(self.due)


def zipf_users(rng, n: int, population: int, s: float) -> np.ndarray:
    ranks = np.arange(1, population + 1, dtype=np.float64)
    cdf = np.cumsum(ranks ** -s)
    cdf /= cdf[-1]
    r = np.minimum(np.searchsorted(cdf, rng.random(n), side="right"),
                   population - 1)
    perm = rng.permutation(population)
    return perm[r].astype(np.int64)


def schedule(mix: dict, cfg: dict, seconds: float, seed: int,
             interests: np.ndarray, stream: int = 1) -> Schedule:
    """The window's requests. ``interests`` (population, K) holds each
    user's interest categories."""
    if mix.get("arrivals", "poisson") != "poisson":
        raise ValueError(f"arrivals {mix['arrivals']!r}: only 'poisson'")
    rng = rng_for(seed, stream)
    n = int(round(mix["rate_per_s"] * seconds))
    due = np.sort(rng.random(n)) * seconds
    population = interests.shape[0]
    users = zipf_users(rng, n, population, mix["zipf_s"])
    C = mix["candidates"]
    own = interests[users[:, None],
                    rng.integers(0, interests.shape[1], (n, C))]
    any_cat = rng.integers(0, cfg["n_cats"], (n, C))
    cats = np.where(rng.random((n, C)) < mix["in_interest_share"], own,
                    any_cat)
    per_cat = cfg["n_items"] // cfg["n_cats"]
    items = cats * per_cat + rng.integers(0, per_cat, (n, C))
    ctx = rng.integers(0, 2, (n, C, cfg["ctx_dim"])).astype(np.float32)
    return Schedule(due, users, items.astype(np.int32),
                    cats.astype(np.int32), ctx)


def bursts(due: np.ndarray, now: float, start: int, max_burst: int) -> int:
    """End index of the burst handed over at ``now``: every request from
    ``start`` that is due by then, at most ``max_burst``."""
    end = int(np.searchsorted(due, now, side="right"))
    return min(end, start + max_burst)
