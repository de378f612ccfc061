"""Traffic kind ``open_select``: tasks arrive open loop and wait for
stage 1 over a fleet.

At each step every task that is due and not yet served goes to one
``FLServiceProvider.select_pools_batch`` call, the way the service's
intake batches its queue. A task's latency runs from its due time to
the return of the call that served it, so a call that runs long delays
the tasks that arrive under it. Tasks due inside the window are served
to the end, up to a minute past its close.

``select_tasks_per_s`` is every task due in the window over the time
from the window's start to the return of the call that served the last
of them: all the work, and all the time it took. Offered above what
stage 1 sustains, the queue never empties, so that is the rate stage 1
completes tasks at. The tail of the tasks' latencies goes to the
per-layer counters (``wait_p95_ms``): above capacity it grows all
through the run.

Correctness: every served task's picks and totals against the
configuration's plain host greedy, and every task due in the window
answered.
"""
from __future__ import annotations

import time

import numpy as np

from bench import arrivals, datagen, harness
from bench.harness import BENCH

GRACE_S = 60.0
STALL_S = 0.4           # a wait, or a call's time a task, this long is a stall


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int, session):
        self.config, self.traffic = config, traffic
        self.seed, self.session = seed, session
        self.ref = harness.load_module(
            BENCH / "configs" / f"{config['name']}.py",
            f"bench_ref_{config['name'].replace('-', '_')}")

    def _task(self, budget: float, i):
        from repro.core import TaskRequest
        f = self.config["fleet"]
        return TaskRequest(budget=budget, n_star=f["n_star"],
                           thresholds=self.thresholds,
                           subset_size=f["subset_size"],
                           subset_delta=f["subset_delta"],
                           x_star=f["x_star"],
                           seed=datagen.sub_seed(self.seed, "task", i))

    def _budget(self, frac: float) -> float:
        return round(float(frac) * self.total_cost, 1)

    def setup(self) -> None:
        from repro.core import ClientPoolState, FLServiceProvider
        f = self.config["fleet"]
        self.scores, hists, self.costs = datagen.fleet(
            f["clients"], f["classes"], datagen.rng(self.seed, "fleet"))
        self.total_cost = float(self.costs.sum())
        self.thresholds = np.full(f["thresholded_criteria"],
                                  f["threshold"])
        pool = ClientPoolState(np.arange(f["clients"], dtype=np.int64),
                               self.scores, hists, self.costs)
        self.provider = FLServiceProvider(pool)
        # every frontier size the budgets can ask for, and the doubled
        # ones an escalation can: budgets up to four times the largest
        lo, hi = self.traffic["budget_frac"]
        warm = np.geomspace(lo, 4 * hi, self.traffic["warm_budgets"])
        self.provider.select_pools_batch(
            [self._task(self._budget(x), -1) for x in warm])

    def window(self, seconds: float) -> dict:
        tr = self.traffic
        g = datagen.rng(self.seed, "arrivals")
        times, fracs = arrivals.open_loop(tr["rate_per_s"], seconds,
                                          *tr["budget_frac"], g,
                                          tr["block_s"])
        tasks = [self._task(self._budget(x), i) for i, x in enumerate(fracs)]
        served: dict[int, tuple[float, object]] = {}
        calls: list[tuple[float, float, int, float]] = []
        wake_late = 0.0
        late_wakes: list[tuple[float, float]] = []
        nxt = 0                         # first task not yet due
        t0 = time.perf_counter()
        while len(served) < len(tasks):
            now = time.perf_counter() - t0
            if now > seconds + GRACE_S:
                break
            while nxt < len(tasks) and times[nxt] <= now:
                nxt += 1
            due = [j for j in range(nxt) if j not in served]
            if not due:
                with self.session.span("bench.wait"):
                    time.sleep(max(0.0, times[nxt] - now))
                late = time.perf_counter() - t0 - times[nxt]
                wake_late = max(wake_late, late)
                if late > STALL_S:
                    late_wakes.append((times[nxt], late))
                continue
            cpu = time.thread_time()
            with self.session.span("bench.select"):
                res = self.provider.select_pools_batch(
                    [tasks[j] for j in due])
            ret = time.perf_counter() - t0
            calls.append((now, ret, len(due), time.thread_time() - cpu))
            for j, r in zip(due, res):
                served[j] = (ret, r)
        lat = [served[j][0] - times[j] for j in sorted(served)]
        self.latencies = lat                # in arrival order
        self.times, self.fracs, self.calls = times, fracs, calls
        infeasible = sum(1 for _, r in served.values() if not r.feasible)
        self.tasks, self.served = tasks, served
        unserved = len(tasks) - len(served)
        mirror = self.provider.pool_state.device_mirror()
        last = max((c[1] for c in calls), default=float("inf"))
        p95 = 1e3 * float(np.percentile(lat, 95))
        return {
            "attempted": len(tasks), "failed": unserved + infeasible,
            "e2e": {"select_tasks_per_s": len(served) / last},
            "samples": {"select_tasks_per_s": len(served),
                        "wait_p95_ms": len(lat)},
            "info": {"calls": len(calls),
                     "tasks_per_call_max": max((c[2] for c in calls),
                                               default=0),
                     "generator_wake_late_s": wake_late,
                     # where the host stood still: late wake-ups, and
                     # calls that took that long a task, with the
                     # thread's CPU seconds in them
                     "late_wakes": late_wakes[:8],
                     "long_calls": [c for c in calls
                                    if c[1] - c[0] > STALL_S * c[2]][:8],
                     "latency_p50_ms": 1e3 * float(np.percentile(lat, 50)),
                     "latency_p95_ms": p95,
                     "last_return_after_close_s": last - seconds,
                     "picks_mean": float(np.mean(
                         [len(r.selected) for _, r in served.values()]))},
            "counters": {"tasks": len(served), "calls": len(calls),
                         "wait_p95_ms": p95,
                         "shards": mirror.num_shards,
                         "shard_width": mirror.shard_cap},
        }

    def release(self) -> None:
        self.provider = None

    def answers(self) -> dict:
        """What the timed path answered: per served task, ``(rows in pool
        order, total score, total cost)``."""
        return {j: (np.asarray(r.selected, np.int64), r.total_score,
                    r.total_cost) for j, (_, r) in self.served.items()}

    def check(self) -> list[tuple[str, float, float]]:
        return self.compare(self.answers())

    def compare(self, answers: dict) -> list[tuple[str, float, float]]:
        """``answers`` (as ``answers()`` gives them) against the plain
        host greedy: tasks unanswered, tasks whose picks differ, and the
        worst relative gap of the totals."""
        limits = self.config["check"]["limits"]
        ref = self.ref.Greedy(self.scores, self.costs, self.thresholds)
        mismatched, gap = 0, 0.0
        for j, (rows_got, score_got, cost_got) in answers.items():
            rows, score, cost = ref.select(self.tasks[j].budget)
            if not np.array_equal(rows_got, rows):
                mismatched += 1
            for a, b in ((score_got, score), (cost_got, cost)):
                gap = max(gap, abs(a - b) / max(abs(b), 1e-300))
        return [("unanswered", float(len(self.tasks) - len(answers)), 0.0),
                ("pick_mismatch", float(mismatched), 0.0),
                ("total_gap", gap, limits["total_gap"])]

    def control_answers(self) -> dict:
        """The control in the program's place: the plain greedy in
        float32, the next precision down, over the same served tasks."""
        low = self.ref.Greedy(self.scores, self.costs, self.thresholds,
                              dtype=np.float32)
        return {j: low.select(self.tasks[j].budget) for j in self.served}


def build(config: dict, traffic: dict, seed: int, session) -> Cell:
    return Cell(config, traffic, seed, session)


def readings(config: dict, traffic: dict, seeds: list[int], n_control: int,
             session, seconds: float):
    """Per seed, one row of the numbers compared: the program against the
    plain reference over a window of ``seconds`` at the cell's own load,
    and on the first ``n_control`` seeds the float32 control through the
    same comparison."""
    for n, s in enumerate(seeds):
        t = time.perf_counter()
        cell = build(config, traffic, s, session)
        cell.setup()
        res = cell.window(seconds)
        cell.release()
        row = {"seed": s, "tasks": res["attempted"], **res["e2e"],
               "program": {k: v for k, v, _ in cell.check()}}
        if n < n_control:
            row["control"] = {k: v for k, v, _ in
                              cell.compare(cell.control_answers())}
        row["seconds"] = time.perf_counter() - t
        yield row
