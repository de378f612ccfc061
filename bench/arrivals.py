"""Open-loop arrival times and per-task sizes with the same work for
every seed.

A seeded Poisson process (as the program's ``core.workload.ArrivalTrace``
draws) puts a different number of tasks into a window for each seed,
and even with the same set of gaps and sizes for every seed, their
order decides how they cluster, and a queue turns that into its tail.
Here the window is cut into blocks of ``m = round(rate * block_s)``
tasks, and every block gets the same set of gaps, the quantiles
``(i + 1/2) / m`` of the exponential law scaled to a mean of
``1 / rate``, and the same set of sizes, each in the seed's order. Only
the blocks that end inside the window are kept, so every seed gets the
same number of tasks and the same work in every stretch of the window.
"""
from __future__ import annotations

import numpy as np


def open_loop(rate: float, seconds: float, lo: float, hi: float,
              g: np.random.Generator, block_s: float
              ) -> tuple[np.ndarray, np.ndarray]:
    """Ascending arrival times in ``[0, seconds)``, the first at 0, and
    one size in ``[lo, hi]`` per task: block ``k`` spans ``[k m / rate,
    (k + 1) m / rate)``."""
    m = max(1, int(round(rate * block_s)))
    blocks = max(1, int(rate * seconds / m + 1e-9))
    gaps = -np.log1p(-(np.arange(m) + 0.5) / m)
    gaps *= m / (rate * gaps.sum())
    steps = lo + (hi - lo) * (np.arange(m) + 0.5) / m
    seq = np.concatenate([g.permutation(gaps) for _ in range(blocks)])
    times = np.concatenate(([0.0], np.cumsum(seq[:-1])))
    sizes = np.concatenate([g.permutation(steps) for _ in range(blocks)])
    keep = times < seconds
    return times[keep], sizes[keep]
