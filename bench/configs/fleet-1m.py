"""Plain reference of the ``fleet-1m`` configuration: the paper's §VI-A
stage-1 greedy over the whole fleet on the host, in float64. Imports
nothing of the program under test.

A client is eligible when each of its first nine criteria is at least
the task's threshold (Eq. 8d). Eligible clients are taken in order of
overall score (Eq. 6, the sum of the criteria) over cost, highest first,
ties to the lower row, and the scan stops at the first client whose cost
exceeds what is left of the budget (the remaining budget folded left,
one subtraction per pick). The answer is the picked rows in pool order
with their summed overall score and cost, summed in pool order.

``dtype`` selects the arithmetic; the benchmark's control runs this same
greedy in float32.
"""
from __future__ import annotations

import numpy as np

THRESHOLDED = 9


class Greedy:
    """One eligibility mask and ratio order shared by every task with the
    same thresholds; each budget is then one left fold over it."""

    def __init__(self, scores: np.ndarray, costs: np.ndarray,
                 thresholds: np.ndarray, dtype=np.float64):
        s = np.asarray(scores, dtype)
        self.dtype = dtype
        self.overall = s @ np.ones(s.shape[1], dtype)
        self.costs = np.asarray(costs, dtype)
        th = np.asarray(thresholds, dtype)[:THRESHOLDED]
        valid = np.flatnonzero(np.all(s[:, :THRESHOLDED] >= th, axis=1))
        ratio = self.overall[valid] / np.maximum(self.costs[valid],
                                                 dtype(1e-12))
        self.order = valid[np.argsort(-ratio, kind="stable")]
        self.oc = self.costs[self.order]

    def select(self, budget: float) -> tuple[np.ndarray, float, float]:
        """``(rows in pool order, total score, total cost)``."""
        rem = np.subtract.accumulate(
            np.concatenate(([self.dtype(budget)], self.oc)))[:-1]
        unaff = self.oc > rem
        k = int(np.argmax(unaff)) if unaff.any() else self.oc.size
        rows = np.sort(self.order[:k])
        return (rows, float(self.overall[rows].sum(dtype=self.dtype)),
                float(self.costs[rows].sum(dtype=self.dtype)))
