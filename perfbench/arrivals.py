"""Open-loop arrival schedule.

Exponential inter-arrival gaps, as ``repro.serving.chaos.arrival_trace``
draws them (``t += rng.exponential(mean_gap)``), with one change for
steadiness: the gaps are the ``n`` midpoint quantiles of the exponential
distribution, and the seed only permutes them.  Every seed then offers the
same set of gaps, the same mean rate and the same span, in another order,
so runs on different seeds differ by the order of arrivals and not by how
much work arrives.
"""
from __future__ import annotations

import numpy as np


def poisson_schedule(rate_per_s: float, seconds: float, seed: int
                     ) -> np.ndarray:
    """Due times (seconds from the window's start) of ``round(rate *
    seconds)`` requests, increasing, the last one at ``seconds``."""
    n = int(round(rate_per_s * seconds))
    if n < 1:
        raise ValueError(f"rate {rate_per_s}/s over {seconds} s offers no "
                         "request")
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    rng = np.random.default_rng([int(seed) & (2**63 - 1), 0x5E4E])
    gaps = gaps[rng.permutation(n)]
    return np.cumsum(gaps) * (seconds / gaps.sum())
