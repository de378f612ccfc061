"""Batched selection / scheduling engine over ``ClientPoolState`` arrays.

This module is the array-native hot path behind the control plane:

- ``greedy_knapsack``        — Stage-1 greedy (Eq. 12) as argsort +
  cumulative-sum prefix instead of a per-client Python loop. Bit-exact
  against ``selection.select_greedy_legacy`` (the remaining-budget
  sequence is reproduced with ``np.subtract.accumulate``, so even float
  rounding matches the sequential loop).
- ``greedy_knapsack_batch``  — the same greedy jit+vmapped over many
  concurrent ``TaskRequest`` budgets/threshold masks (multi-tenant
  serving: one argsort per task, one fused scan, no Python per client).
- ``mkp_pseudo_utility``     — the Toyoda scarcity-weighted scoring of
  *all* MKP candidates at once (shared with ``mkp.solve_mkp_greedy`` so
  the two paths cannot drift).
- ``solve_mkp_greedy_jax``   — the MKP greedy loop as a
  ``lax.while_loop`` whose per-iteration ``(n_items, n_knapsacks)``
  utility update runs through ``kernels.ops.mkp_utility`` (Pallas on
  TPU, jnp reference on CPU, interpret mode for tests).

Data flow: callers hold a ``ClientPoolState``; every function here takes
plain arrays (columns of that state) and returns arrays/masks, so it is
jit/vmap friendly and never materializes ``ClientProfile`` objects.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.segmented_topk import method as topk_method
from . import spans

_EPS = 1e-12


# ---------------------------------------------------------------------------
# Stage 1: vectorized greedy knapsack
# ---------------------------------------------------------------------------

def greedy_order(scores: np.ndarray, costs: np.ndarray) -> np.ndarray:
    """Non-increasing score/cost ratio order (stable, like the legacy)."""
    ratio = np.asarray(scores, np.float64) / np.maximum(
        np.asarray(costs, np.float64), _EPS)
    return np.argsort(-ratio, kind="stable")


def greedy_knapsack(scores: np.ndarray, costs: np.ndarray, budget: float,
                    skip_unaffordable: bool = False
                    ) -> tuple[np.ndarray, float, float]:
    """Vectorized greedy (§VI-A). Returns ``(chosen, total_score,
    total_cost)`` with ``chosen`` positions in pick order — identical to
    the legacy Python loop on any input.

    Paper-faithful mode (``skip_unaffordable=False``): the scan stops at
    the first client whose cost exceeds the remaining budget, i.e. the
    selection is the longest affordable prefix of the ratio order. The
    remaining-budget sequence ``b - c0 - c1 - ...`` is evaluated with
    left-fold rounding (``np.subtract.accumulate``) so float behavior
    matches the sequential loop exactly.

    The skip variant keeps scanning for cheaper clients; that is an
    inherently sequential recurrence, run here over the presorted cost
    array with a suffix-min early exit.
    """
    scores = np.asarray(scores, dtype=np.float64)
    costs = np.asarray(costs, dtype=np.float64)
    order = greedy_order(scores, costs)
    oc = costs[order]
    n = oc.size
    if n == 0:
        return order[:0], 0.0, 0.0
    if not skip_unaffordable:
        # remaining[t] = budget - c0 - ... - c_{t-1}, folded left to right
        rem = np.subtract.accumulate(
            np.concatenate(([float(budget)], oc)))[:-1]
        unaff = oc > rem
        k = int(np.argmax(unaff)) if unaff.any() else n
        chosen = order[:k]
        return chosen, float(scores[chosen].sum()), float(costs[chosen].sum())
    # skip mode: sequential over the sorted order, but bail out as soon as
    # nothing further down can fit (suffix minimum of cost).
    sufmin = np.minimum.accumulate(oc[::-1])[::-1]
    remaining = float(budget)
    taken = np.zeros(n, dtype=bool)
    for t in range(n):
        if sufmin[t] > remaining:
            break
        c = oc[t]
        if c <= remaining:
            taken[t] = True
            remaining -= c
    chosen = order[taken]
    return chosen, float(scores[chosen].sum()), float(costs[chosen].sum())


@functools.partial(jax.jit, static_argnames=("skip_unaffordable",))
def _greedy_batch_jax(scores, costs, budgets, valid, skip_unaffordable):
    """(T,) budgets x (T, n) validity -> (T, n) selection masks + totals."""

    def one(budget, vmask):
        ratio = jnp.where(vmask, scores / jnp.maximum(costs, _EPS), -jnp.inf)
        order = jnp.argsort(-ratio, stable=True)
        # invalid clients sort last; infinite cost makes them hard stops
        oc = jnp.where(vmask[order], costs[order], jnp.inf)

        def step(carry, c):
            remaining, stopped = carry
            fits = (c <= remaining) & jnp.logical_not(stopped)
            if not skip_unaffordable:
                stopped = stopped | (c > remaining)
            remaining = remaining - jnp.where(fits, c, 0.0)
            return (remaining, stopped), fits

        init = (jnp.asarray(budget, scores.dtype), jnp.asarray(False))
        _, taken = jax.lax.scan(step, init, oc)
        return jnp.zeros_like(vmask).at[order].set(taken)

    masks = jax.vmap(one)(budgets, valid)
    return masks, masks @ scores, masks @ costs


def greedy_knapsack_batch(scores: np.ndarray, costs: np.ndarray,
                          budgets: np.ndarray,
                          valid: np.ndarray | None = None,
                          skip_unaffordable: bool = False,
                          backend: str = "auto"):
    """Batched Stage-1 greedy for multi-tenant serving.

    Every concurrent task shares the client pool, hence the score/cost
    ratio *order*: the batch reduces to ONE argsort plus a ``(T, n)``
    masked cumulative sum — per-task work is O(n), not O(n log n), and
    fully vectorized over tasks. ``backend="jax"`` instead runs the
    jit+vmap scan (`_greedy_batch_jax`), the path that makes sense on
    TPU; ``"auto"`` picks jax on TPU and numpy elsewhere.

    Args:
      scores, costs: (n,) shared client pool columns.
      budgets: (T,) one budget per concurrent task.
      valid: optional (T, n) per-task eligibility (threshold masks).

    Returns ``(masks, total_scores, total_costs)`` with shapes
    ``(T, n), (T,), (T,)`` as numpy arrays. With the numpy backend,
    selections are bit-exact against running the single-task greedy per
    task over its valid clients; the jax backend computes in float32
    (ratio ties / rounding may differ at the margin).
    """
    if backend == "auto":
        backend = "jax" if jax.default_backend() == "tpu" else "numpy"
    if backend == "jax":
        scores = jnp.asarray(scores)
        costs = jnp.asarray(costs)
        budgets = jnp.atleast_1d(jnp.asarray(budgets))
        if valid is None:
            valid = jnp.ones((budgets.shape[0], scores.shape[0]), dtype=bool)
        else:
            valid = jnp.asarray(valid, dtype=bool)
        masks, ts, tc = _greedy_batch_jax(scores, costs, budgets, valid,
                                          bool(skip_unaffordable))
        return np.asarray(masks), np.asarray(ts), np.asarray(tc)

    scores = np.asarray(scores, dtype=np.float64)
    costs = np.asarray(costs, dtype=np.float64)
    budgets = np.atleast_1d(np.asarray(budgets, dtype=np.float64))
    T, n = budgets.shape[0], scores.shape[0]
    if valid is None:
        valid = np.ones((T, n), dtype=bool)
    else:
        valid = np.asarray(valid, dtype=bool)
    if skip_unaffordable:
        # sequential recurrence per task; no shared-prefix shortcut
        masks = np.zeros((T, n), dtype=bool)
        for t in range(T):
            cols = np.flatnonzero(valid[t])
            chosen, _, _ = greedy_knapsack(scores[cols], costs[cols],
                                           budgets[t], skip_unaffordable=True)
            masks[t, cols[chosen]] = True
        return masks, masks @ scores, masks @ costs
    order = greedy_order(scores, costs)
    oc = costs[order]                                  # (n,)
    ov = valid[:, order]                               # (T, n)
    # Reproduce the single-task greedy's left-fold remaining-budget
    # sequence per row (budget - c0 - c1 - ..., rounded at every step;
    # invalid clients subtract exactly 0.0), so selections are bit-exact
    # against greedy_knapsack even when partial sums round differently
    # than a cumsum-vs-budget comparison would.
    rem = np.subtract.accumulate(
        np.concatenate([budgets[:, None], np.where(ov, oc, 0.0)], axis=1),
        axis=1)[:, :-1]                                # (T, n) before each pick
    viol = ov & (oc > rem)
    first = np.where(viol.any(axis=1), viol.argmax(axis=1), n)
    take = ov & (np.arange(n) < first[:, None])
    masks = np.zeros((T, n), dtype=bool)
    masks[:, order] = take
    return masks, masks @ scores, masks @ costs


# ---------------------------------------------------------------------------
# Stage 1 at fleet scale: hierarchical two-level greedy
# ---------------------------------------------------------------------------

def _flat_pool_greedy(pool, budget: float, thresholds
                      ) -> tuple[np.ndarray, float, float, int]:
    """Host flat path over a ``ClientPoolState``: threshold mask ->
    greedy over kept rows -> global row indices in pick order."""
    mask = pool.threshold_mask(thresholds)
    rows_kept = np.flatnonzero(mask)
    chosen, ts, tc = greedy_knapsack(pool.overall[rows_kept],
                                     pool.costs[rows_kept], budget)
    return rows_kept[chosen], ts, tc, int(rows_kept.size)


def hierarchical_greedy_knapsack(pool, budget: float,
                                 thresholds: np.ndarray | None = None,
                                 *, mirror=None, shard_cap: int | None = None,
                                 interpret: bool | None = None,
                                 stats: dict | None = None
                                 ) -> tuple[np.ndarray, float, float, int]:
    """Two-level Stage-1 greedy over the device pool mirror (fleet
    scale: 1M–10M clients; see ``docs/scaling.md``).

    Level 1 (device, f32): eligibility mask + score/cost ratios over the
    ``(S, C)`` sharded mirror, then a per-shard top-``F`` frontier via
    the ``segmented_topk`` kernel — O(n) streaming work, no full-pool
    argsort. Level 2 (host, f64): the exact paper greedy over the
    ``<= S*F`` surviving candidates, re-ranked with the host pool's f64
    scores/costs and the flat path's stable tie-break (ratio ties break
    toward the lower global row). The frontier escalates (``F *= 2``)
    whenever a clipped shard could still contribute — i.e. the budget
    scan consumed a clipped shard's entire frontier, or never hit a
    stop — so on termination the result provably matches the flat
    greedy on the f32-frontier candidate set (membership itself is
    decided in f32; see docs for the near-tie caveat).

    Degenerate budgets that would select a large fraction of the pool
    (frontier ~ pool) fall back to the flat host path.

    Returns ``(rows, total_score, total_cost, n_valid)`` with ``rows``
    global pool rows in pick order. ``stats``, if given, is filled with
    the task's counters: ``path`` ("frontier" | "flat-fallback"),
    ``shards``, the last pass's ``frontier`` (F) and ``candidates``,
    and, summed over the level-1 passes, ``passes``, ``escalations``
    and ``frontier_slots`` (S·F); ``picks`` and ``n_valid`` are those
    of the answer. The steps run in ``stage1.mask``,
    ``stage1.frontier`` and ``stage1.merge`` spans
    (:mod:`repro.core.spans`).
    """
    if mirror is None:
        mirror = pool.device_mirror(shard_cap=shard_cap)
    else:
        mirror.sync(pool)
    with spans.span("stage1.mask"):
        valid = mirror.valid_mask(thresholds)
        counts, cost_sum = mirror.shard_stats(valid)
    n_valid = int(counts.sum())
    if stats is None:
        stats = {}
    stats.update(path="frontier", frontier=0, escalations=0,
                 candidates=0, shards=mirror.num_shards, passes=0,
                 frontier_slots=0, picks=0, n_valid=n_valid)
    if n_valid == 0:
        return np.zeros(0, np.int64), 0.0, 0.0, 0
    S = mirror.num_shards
    max_count = int(counts.max())
    budget = float(budget)
    # Frontier sizing: expected picks if the budget were spent at the
    # mean valid cost, spread over shards, with 4x headroom for skew.
    k_est = budget / max(cost_sum / n_valid, _EPS)
    if k_est >= 0.5 * n_valid:
        stats["path"] = "flat-fallback"
        rows, ts, tc, n_kept = _flat_pool_greedy(pool, budget, thresholds)
        stats.update(picks=int(rows.size), n_valid=n_kept)
        return rows, ts, tc, n_kept
    F = int(min(max_count, max(32, 1 << int(np.ceil(
        np.log2(4.0 * k_est / S + 8.0))))))
    while True:
        stats["frontier"] = F
        stats["passes"] += 1
        stats["frontier_slots"] += S * F
        with spans.span("stage1.frontier", F=F, shards=S,
                        method=topk_method(F, mirror.shard_cap)) as sp:
            vals, rows = mirror.frontier(mirror.masked_ratio(valid), F,
                                         interpret=interpret)
            cand = rows[np.isfinite(vals)]
            stats["candidates"] = int(cand.size)
            spans.annotate(sp, candidates=int(cand.size))
        with spans.span("stage1.merge") as sp:
            # Host-precision merge: exact greedy over the candidate set.
            # overall_score on the gathered rows only — identical per-row
            # values to pool.overall, without forcing the pool-wide O(n)
            # cache rebuild after every churn event.
            from .criteria import overall_score
            sc = overall_score(pool.scores[cand])
            cs = pool.costs[cand]
            ratio = sc / np.maximum(cs, _EPS)
            pos = np.lexsort((cand, -ratio))  # ratio desc, row asc on ties
            cand_s, oc = cand[pos], cs[pos]
            rem = np.subtract.accumulate(
                np.concatenate(([budget], oc)))[:-1]
            unaff = oc > rem
            stopped = bool(unaff.any())
            k = int(np.argmax(unaff)) if stopped else oc.size
            # Escalate iff a clipped shard could still change the answer:
            # its whole frontier fed the consumed prefix (selection + the
            # stopping client), or the scan never stopped at all.
            escalate = False
            clipped = counts > F
            if clipped.any() and F < max_count:
                prefix = cand_s[: k + 1] if stopped else cand_s
                contrib = np.bincount(prefix // mirror.shard_cap,
                                      minlength=S)
                suspect = clipped & (contrib >= F) if stopped else clipped
                escalate = bool(suspect.any())
            spans.annotate(sp, escalate=int(escalate))
        if escalate:
            F = min(2 * F, max_count)
            stats["escalations"] += 1
            continue
        chosen = cand_s[:k]
        stats["picks"] = int(k)
        return (chosen, float(sc[pos][:k].sum()), float(oc[:k].sum()),
                n_valid)


def hierarchical_greedy_knapsack_batch(pool, budgets: np.ndarray,
                                       thresholds_list,
                                       *, mirror=None,
                                       shard_cap: int | None = None,
                                       interpret: bool | None = None):
    """Batched :func:`hierarchical_greedy_knapsack` for multi-tenant
    sweeps: one mirror sync serves every task; each task then runs its
    own frontier + host merge (per-task thresholds make the device mask
    task-specific, so there is no shared argsort to amortize — the
    shared work is the mirror itself).

    ``thresholds_list``: per-task thresholds (or ``None``), length T.
    Returns a list of ``(rows, total_score, total_cost, n_valid)``.
    The sync runs in a ``stage1.sync`` span, each task in a
    ``stage1.task`` span whose arguments are its ``stats`` counters.
    """
    with spans.span("stage1.sync"):
        if mirror is None:
            mirror = pool.device_mirror(shard_cap=shard_cap)
        else:
            mirror.sync(pool)
    budgets = np.atleast_1d(np.asarray(budgets, dtype=np.float64))
    batch = spans.current_batch()
    out = []
    for i, (b, th) in enumerate(zip(budgets, thresholds_list)):
        stats: dict = {}
        with spans.span("stage1.task", batch=batch, index=i) as sp:
            out.append(hierarchical_greedy_knapsack(
                pool, float(b), th, mirror=mirror, interpret=interpret,
                stats=stats))
            spans.annotate(sp, path=int(stats["path"] != "frontier"),
                           passes=stats["passes"],
                           escalations=stats["escalations"],
                           picks=stats["picks"], n_valid=stats["n_valid"])
    return out


# ---------------------------------------------------------------------------
# Stage 2: vectorized Toyoda pseudo-utility (MKP inner loop)
# ---------------------------------------------------------------------------

def mkp_pseudo_utility(values: np.ndarray, weights: np.ndarray,
                       residual: np.ndarray, selectable: np.ndarray
                       ) -> tuple[np.ndarray, np.ndarray]:
    """Scarcity-weighted utility of *all* candidates at once.

    ``util_j = v_j / (w_j · scarcity)`` with ``scarcity = 1/residual``;
    items that don't fit (or aren't selectable) score ``-inf``. This is
    the single source of truth for the greedy MKP scoring — both
    ``mkp.solve_mkp_greedy`` (numpy) and the jax/Pallas path call the
    same formula.
    """
    scarcity = 1.0 / np.maximum(residual, _EPS)
    penalty = weights @ scarcity
    util = values / np.maximum(penalty, _EPS)
    fits = selectable & np.all(weights <= residual + _EPS, axis=1)
    return np.where(fits, util, -np.inf), fits


def mkp_pseudo_utility_jax(values, weights, residual, selectable,
                           interpret: bool | None = None):
    """Accelerator path of :func:`mkp_pseudo_utility` (Pallas on TPU,
    jnp reference otherwise; ``interpret=True`` forces the kernel in
    interpreter mode for CPU testing)."""
    from ..kernels import ops
    return ops.mkp_utility(values, weights, residual, selectable,
                           interpret=interpret)


@functools.partial(jax.jit, static_argnames=("max_size", "interpret"))
def _mkp_greedy_jax(values, weights, capacities, max_size, interpret):
    from ..kernels import ops
    n, m = weights.shape

    def cond(state):
        _, _, count, cont = state
        return cont & (count < max_size)

    def body(state):
        used, in_sel, count, _ = state
        residual = capacities - used
        util = ops.mkp_utility(values, weights, residual,
                               jnp.logical_not(in_sel), interpret=interpret)
        j = jnp.argmax(util)
        ok = jnp.isfinite(util[j])
        in_sel = in_sel.at[j].set(in_sel[j] | ok)
        used = used + jnp.where(ok, weights[j], 0.0)
        return used, in_sel, count + ok.astype(jnp.int32), ok

    init = (jnp.zeros(m, values.dtype), jnp.zeros(n, dtype=bool),
            jnp.asarray(0, jnp.int32), jnp.asarray(True))
    used, in_sel, _, _ = jax.lax.while_loop(cond, body, init)
    return in_sel, used


def solve_mkp_greedy_jax(values, weights, capacities,
                         max_size: int | None = None,
                         interpret: bool | None = None
                         ) -> tuple[np.ndarray, np.ndarray]:
    """Toyoda greedy as a jit'd ``while_loop``; the per-iteration utility
    update is the Pallas kernel (TPU) / jnp reference (CPU).

    Returns ``(selection_mask (n,), used (m,))``. Matches the greedy
    phase of ``mkp.solve_mkp_greedy`` (``local_search=False``) up to
    float32 utility ties.
    """
    values = jnp.asarray(values)
    weights = jnp.asarray(weights)
    capacities = jnp.asarray(capacities)
    ms = int(values.shape[0] if max_size is None else max_size)
    in_sel, used = _mkp_greedy_jax(values, weights, capacities, ms,
                                   interpret)
    return np.asarray(in_sel), np.asarray(used)
