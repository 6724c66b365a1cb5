"""Seeded draws that the traffic generators share: named length
distributions and arrival processes, each an entry of a traffic file.

The amount of work of a seed is fixed: a phase of ``T`` seconds at rate ``r``
gets exactly ``round(r T)`` arrivals (a Poisson process conditioned on its
count: sorted uniform times), and lengths are stratified over their
distribution (one draw from each of ``n`` equal-probability strata, in
seeded order), so two seeds differ in order and spacing, not in load.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

_NORMAL = NormalDist()


def _ppf(dist: dict, u: np.ndarray) -> np.ndarray:
    """Inverse CDF of the named distribution at ``u`` in (0, 1), before
    clipping."""
    name = dist["dist"]
    if name == "fixed":
        return np.full(u.shape, float(dist["value"]))
    if name == "uniform":
        return dist["min"] + u * (dist["max"] + 1 - dist["min"]) - 0.5
    if name == "loguniform":
        lo, hi = math.log(dist["min"]), math.log(dist["max"])
        return np.exp(lo + u * (hi - lo))
    if name == "lognormal":
        z = np.array([_NORMAL.inv_cdf(float(x)) for x in u])
        return np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    raise ValueError(f"unknown length distribution {name!r}")


def lengths(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` whole lengths from ``dist``, stratified, clipped to its
    ``min``..``max``."""
    if n == 0:
        return np.zeros(0, np.int64)
    u = (rng.permutation(n) + rng.uniform(0.0, 1.0, n)) / n
    u = np.clip(u, 1e-9, 1 - 1e-9)
    x = np.rint(_ppf(dist, u)).astype(np.int64)
    lo = dist.get("min", dist.get("value"))
    hi = dist.get("max", dist.get("value"))
    return np.clip(x, lo, hi)


def arrivals(process: dict, t0: float, t1: float,
             rng: np.random.Generator) -> np.ndarray:
    """Sorted arrival times in ``[t0, t1)`` for the named process.

    ``poisson``: ``round(rate (t1 - t0))`` uniform times. With ``bursts``
    (``{"every_s", "for_s", "factor"}``) the rate is ``factor`` times higher
    during the first ``for_s`` of every ``every_s``, and lowered outside so
    that the mean stays ``rate_per_s``; times are drawn by inverting the
    cumulative rate, so the count stays fixed."""
    if process["process"] != "poisson":
        raise ValueError(f"unknown arrival process {process['process']!r}")
    n = int(round(process["rate_per_s"] * (t1 - t0)))
    u = np.sort(rng.uniform(0.0, 1.0, n))
    bursts = process.get("bursts")
    if not bursts:
        return t0 + u * (t1 - t0)
    every, on, k = bursts["every_s"], bursts["for_s"], bursts["factor"]
    share_on = k * on / (k * on + (every - on))   # of a period's arrivals
    # piecewise-linear cumulative share over one period, then whole periods
    grid = np.arange(0.0, (t1 - t0) + every, every)
    ts = np.concatenate([[g, g + on] for g in grid] + [[grid[-1] + every]])
    cum = np.concatenate([[i, i + share_on] for i in range(len(grid))]
                         + [[len(grid)]])
    total = np.interp(t1 - t0, ts, cum)
    return t0 + np.interp(u * total, cum, ts)


def residual(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """Remaining lengths of ``n`` requests found in service at a random
    instant of a steady state: a length-biased draw from ``dist`` times a
    uniform share, at least 1. Used to start a run near its steady state
    instead of ramping for several service times."""
    if n == 0:
        return np.zeros(0, np.int64)
    cand = lengths(dist, 8 * n, rng).astype(np.float64)
    pick = rng.choice(cand, size=n, replace=False, p=cand / cand.sum())
    return np.maximum(1, np.ceil(pick * rng.uniform(0.0, 1.0, n))
                      ).astype(np.int64)
