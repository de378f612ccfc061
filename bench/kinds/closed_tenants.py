"""Traffic kind ``closed_tenants``: several requesters' training tasks
served side by side by one ``ServiceScheduler`` over one client pool, in
a closed loop (each tenant's next chunk is dispatched when its previous
one is collected).

Set-up makes the data and the client pool from the seed, submits every
tenant and serves sweeps until every tenant has finished the periods
in which the evaluation rounds (every ``eval_every`` rounds) fall at
each phase they take within a period: one, where a period's rounds are
a multiple of ``eval_every``. Every period schedules the same subsets
(the configuration fixes the partition's shape, and no client is
suspended), so those periods meet every (rounds per segment, padded
client count) that the round-chunk programs are called with, on each
tenant's own chip: the window compiles nothing. The window then calls
``ServiceScheduler.sweep`` until it closes.

Correctness: the first chunk of every tenant (set-up's first sweep, the
window's own call) is replayed by the configuration's plain reference
from the same seed, and every schedule stage 2 produced is held to the
paper's §VII guarantees.
"""
from __future__ import annotations

import math
import time

import numpy as np

from bench import datagen, harness
from bench.harness import BENCH


def p95(values) -> float:
    """95th percentile; NaN where there is no sample."""
    if not len(values):
        return float("nan")
    return float(np.percentile(np.asarray(values, np.float64), 95))


def leaf_gaps(prog: dict, ref: dict, base_p: dict, base_r: dict):
    """Per leaf, the gap between the program's and the reference's norm
    of the parameters' change, against the larger of that leaf's
    reference norm and the median leaf's. Leaves whose reference change
    is under a thousandth of the median leaf's read NaN: they move by
    round-off alone."""
    import jax
    pl = jax.tree_util.tree_leaves(prog)
    rl = jax.tree_util.tree_leaves(ref)
    bp = jax.tree_util.tree_leaves(base_p)
    br = jax.tree_util.tree_leaves(base_r)
    pn = [float(np.linalg.norm((a - b).astype(np.float64)))
          for a, b in zip(pl, bp)]
    rn = [float(np.linalg.norm((a - b).astype(np.float64)))
          for a, b in zip(rl, br)]
    med = float(np.median(rn))
    return [abs(p - r) / max(r, med) if r >= 1e-3 * med else float("nan")
            for p, r in zip(pn, rn)]


def schedule_violations(pool_ids, subsets, x_star: int) -> int:
    """Clients of the period's pool that no subset holds (coverage),
    clients held more than ``x_star`` times (bounded participation) and
    subset members outside the pool, §VII."""
    counts: dict[int, int] = {}
    for s in subsets:
        for c in s:
            counts[int(c)] = counts.get(int(c), 0) + 1
    pool = {int(c) for c in pool_ids}
    return (sum(1 for c in pool if counts.get(c, 0) < 1)
            + sum(1 for v in counts.values() if v > x_star)
            + sum(1 for c in counts if c not in pool))


def round_flags(state):
    """Per round of ``state.rounds``: the returned flags and quality
    values the task's reputation tracker recorded for its clients."""
    hist = {}
    for cid in state.tracker.client_ids:
        r = state.tracker.records[int(cid)]
        hist[int(cid)] = (r.b_rounds.copy(), r.q_rounds.copy())
    seen: dict[int, int] = {}
    out = []
    for ev in state.rounds:
        b, q = [], []
        for c in ev.subset:
            j = seen.get(c, 0)
            seen[c] = j + 1
            b.append(hist[c][0][j] > 0)
            q.append(hist[c][1][j])
        out.append((np.array(b), np.array(q, np.float32)))
    return out


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int, session):
        self.config, self.traffic = config, traffic
        self.seed, self.session = seed, session
        self.m, self.t = config["model"], config["training"]
        self.ref = harness.load_module(
            BENCH / "configs" / f"{config['name']}.py",
            f"bench_ref_{config['name'].replace('-', '_')}")

    # -- set-up -------------------------------------------------------------
    def setup(self) -> None:
        self.build_tenants()
        self._sweep_setup()                 # stage 1, placement, chunk 1
        rounds = max(self.first_period.values())
        periods = math.lcm(rounds, self.t["eval_every"]) // rounds
        while any(self.scheduler.state(tid).period < periods
                  for tid in self.tids):
            self._sweep_setup()

    def build_tenants(self) -> None:
        """Data, client pool, provider, scheduler, and every tenant's
        trainer and task, submitted. Every trainer is built on one
        staged copy of the images; with ``devices`` over 1 in the
        traffic mix the scheduler places the tenants by its
        ``placement`` policy, and each trainer's ``place_on`` moves its
        state and data to its chip."""
        import jax
        from repro.core import (ClientPoolState, FLServiceProvider,
                                ServiceScheduler, TaskRequest)
        from repro.data.synthetic import ClassificationData
        from repro.fl.simulation import DeviceFLSim, SimConfig
        from repro.models.cnn import CNNConfig

        cfg, m, t, seed = self.config, self.m, self.t, self.seed
        pc = cfg["pool"]
        classes = m["num_classes"]
        n_train, n_test = pc["train_samples"], pc["test_samples"]
        labels, parts = datagen.partition_fixed(
            pc["client_classes"], pc["class_split"], n_train,
            datagen.rng(seed, "split"))
        test_labels = datagen.rng(seed, "test-labels").integers(
            0, classes, size=n_test).astype(np.int32)
        shape = dict(height=m["height"], width=m["width"],
                     channels=m["channels"], classes=classes)
        host_img = datagen.cifar_images(
            labels, datagen.rng(seed, "train-images"), **shape)
        host_test = datagen.cifar_images(
            test_labels, datagen.rng(seed, "test-images"), **shape)
        n_dev = int(self.traffic.get("devices", 1))
        devices = self.session.devices[:n_dev]
        if len(devices) < n_dev:
            raise ValueError(f"the traffic places tenants on {n_dev} "
                             f"devices, the cell has {len(devices)}")
        # staged once: every tenant trains on the same clients' data, as
        # tasks of one service over one pool do
        self.images = jax.device_put(host_img, devices[0])
        data = ClassificationData(self.images, labels, classes)
        test_img = jax.device_put(host_test, devices[0])
        scores, hists, costs = datagen.client_criteria(
            parts, labels, classes, datagen.rng(seed, "criteria"))
        self.labels, self.parts = labels, parts
        pool = ClientPoolState(np.arange(pc["clients"], dtype=np.int64),
                               scores, hists, costs)
        test = ClassificationData(test_img, test_labels, classes)

        schedules = self.schedules = []
        first = self.first_period = {}      # task -> rounds in period 0

        class RecordingProvider(FLServiceProvider):
            """The service's provider, keeping what stage 2 was given
            and what it returned, for the §VII check."""

            def schedule_period(self, pool_ids, task, rng,
                                policy_state=None):
                res = super().schedule_period(pool_ids, task, rng,
                                              policy_state=policy_state)
                schedules.append((list(pool_ids), task.x_star,
                                  [list(s) for s in res.subsets]))
                first.setdefault(id(task), len(res.subsets))
                return res

        model = CNNConfig(name=cfg["name"], height=m["height"],
                          width=m["width"], channels=m["channels"],
                          num_classes=classes, conv1=m["conv1"],
                          conv2=m["conv2"], hidden=m["hidden"],
                          dtype=m["dtype"])
        self.scheduler = ServiceScheduler(
            RecordingProvider(pool),
            max_inflight=self.traffic["max_inflight"], n_devices=n_dev,
            placement=self.traffic.get("placement"))
        self.sims, self.tids, self.seeds = [], [], []
        self.w0, self.w_first, self.n_first = [], {}, {}
        chunk = t["round_chunk"]
        for i in range(self.traffic["tenants"]):
            s = datagen.sub_seed(seed, "tenant", i)
            sim = DeviceFLSim(
                model, data, parts, test,
                SimConfig(batch_size=t["batch_size"],
                          local_steps=t["local_steps"],
                          local_lr=t["local_lr"], server_lr=t["server_lr"],
                          dropout_rate=t["dropout_rate"],
                          eval_every=t["eval_every"], seed=s),
                pad_subset_to=t["pad_subset_to"])
            self.w0.append(jax.tree_util.tree_map(np.asarray, sim.params))
            task = TaskRequest(
                budget=float(cfg["budget"]), n_star=pc["clients"],
                subset_size=t["subset_size"],
                subset_delta=t["subset_delta"], x_star=t["x_star"],
                rep_threshold=t["rep_threshold"],
                max_periods=10**9, round_chunk=chunk, max_rounds=10**9,
                seed=datagen.sub_seed(seed, "task", i))
            tid = self.scheduler.submit(
                task, sim, stop_fn=self._first_chunk(i, sim, task))
            self.sims.append(sim)
            self.tids.append(tid)
            self.seeds.append(s)

    def _first_chunk(self, i: int, sim, task):
        """Stop condition that never stops; at the last round of the
        first chunk (``round_chunk`` rounds, fewer where the first period
        is shorter) it keeps a host copy of the tenant's weights, before
        the next dispatch donates them."""
        def watch(metrics: dict) -> bool:
            if i not in self.w_first:
                n = min(self.t["round_chunk"], self.first_period[id(task)])
                if metrics["round"] == n - 1:
                    import jax
                    self.w_first[i] = jax.tree_util.tree_map(np.asarray,
                                                             sim.params)
                    self.n_first[i] = n
            return False
        return watch

    def _sweep_setup(self) -> None:
        self.scheduler.sweep()
        self.last_return = {tid: time.perf_counter() for tid in self.tids}

    # -- window ---------------------------------------------------------------
    def window(self, seconds: float) -> dict:
        sched = self.scheduler
        start = {tid: len(sched.state(tid).rounds) for tid in self.tids}
        counted = dict.fromkeys(self.tids, 0)
        last = dict(self.last_return)
        intervals: list[float] = []
        sweeps = 0
        t0 = time.perf_counter()
        t_end = t0 + seconds
        while True:
            with self.session.span("bench.sweep"):
                out = sched.sweep()
            now = time.perf_counter()
            if now > t_end:
                break
            sweeps += 1
            for tid, evs in out.items():
                intervals.extend([now - last[tid]] * len(evs))
                last[tid] = now
                counted[tid] += len(evs)
        updates = rounds = nonfinite = slots = 0
        for tid in self.tids:
            st = sched.state(tid)
            flags = round_flags(st)
            for j in range(start[tid], start[tid] + counted[tid]):
                updates += int(flags[j][0].sum())
                slots += len(st.rounds[j].subset)
                rounds += 1
                nonfinite += not np.isfinite(st.rounds[j].metrics["loss"])
        self.nonfinite = nonfinite
        m, t = self.m, self.t
        from bench import counts
        return {
            "attempted": rounds, "failed": nonfinite,
            "e2e": {"client_updates_per_s": updates / seconds,
                    "round_p95_ms": 1e3 * p95(intervals)},
            "samples": {"round_p95_ms": len(intervals),
                        "client_updates": updates, "rounds": rounds},
            "info": {"sweeps_in_window": sweeps,
                     "scheduled_client_slots": slots,
                     "tenants_per_device": self.tenants_per_device(),
                     "subset_sizes_per_period": self.period_shapes()},
            "counters": {
                "rounds": rounds, "updates": updates, "seconds": seconds,
                "train_flops_per_update": t["local_steps"] * t["batch_size"]
                * counts.cnn_train_flops_per_sample(m),
                "params": counts.cnn_params(m)},
        }

    def tenants_per_device(self) -> list[int]:
        placed = self.scheduler.placements()
        return [sum(placed[tid] == d for tid in self.tids)
                for d in range(self.scheduler.n_devices)]

    def period_shapes(self) -> list[list[int]]:
        """Every distinct list of subset sizes that stage 2 made for a
        period: one, where the configuration fixes the work."""
        return [list(x) for x in sorted({tuple(len(s) for s in subsets)
                                         for _, _, subsets in
                                         self.schedules})]

    def release(self) -> None:
        import jax
        for sim in self.sims:
            jax.block_until_ready(sim.params)
        self.first = self.program_first_chunks()
        self.scheduler = self.sims = None

    def program_first_chunks(self) -> list[dict]:
        """What the timed path produced in every tenant's first chunk:
        its rounds (index, clients, loss, returned flags, quality) and
        its weights before and after."""
        out = []
        for i, tid in enumerate(self.tids):
            st = self.scheduler.state(tid)
            n = self.n_first[i]
            out.append({
                "w0": self.w0[i], "w1": self.w_first[i],
                "rounds": [(ev.round_index, list(ev.subset),
                            float(ev.metrics["loss"]), b, q)
                           for ev, (b, q) in zip(st.rounds[:n],
                                                 round_flags(st)[:n])]})
        return out

    def reference_first_chunks(self, first: list[dict], **how) -> list[dict]:
        """The plain reference's replay of the same rounds from the same
        seeds; ``how`` passes its dtype, precision or planted fault."""
        import jax
        import jax.numpy as jnp
        out = []
        dtype = how.get("dtype", jnp.float32)
        for seed32, f in zip(self.seeds, first):
            w1, rounds = self.ref.replay(
                self.m, self.t, seed32, self.images, self.labels,
                self.parts, [(r, s) for r, s, *_ in f["rounds"]],
                self.t["pad_subset_to"], **how)
            w0 = jax.tree_util.tree_map(
                lambda x: np.asarray(x, np.float32),
                self.ref.init_params(self.m, seed32, dtype))
            out.append({"w0": w0, "w1": w1,
                        "rounds": [(r, s, lv, keep, q) for (r, s, *_), (
                            keep, q, lv) in zip(f["rounds"], rounds)]})
        return out

    def control_first_chunks(self, first: list[dict]) -> list[dict]:
        """The control: the plain reference one precision step below
        the program's (every model product's inputs in fp8, at the
        default precision), replaying the same rounds."""
        import jax
        return self.reference_first_chunks(
            first, precision=jax.lax.Precision.DEFAULT,
            round_to=self.config["check"]["control_round_to"])

    # -- check ----------------------------------------------------------------
    def check(self) -> list[tuple[str, float, float]]:
        limits = self.config["check"]["limits"]
        got = compare(self.first, self.reference_first_chunks(self.first))
        violations = sum(schedule_violations(p, s, x)
                         for p, x, s in self.schedules)
        return [*((n, got[n], float(lim)) for n, lim in limits.items()),
                ("dropout_mismatch", got["dropout_mismatch"], 0.0),
                ("schedule_violations", float(violations), 0.0),
                ("nonfinite_losses", float(self.nonfinite), 0.0)]


def tenant_gaps(g: dict, w: dict) -> dict:
    """One tenant's first chunk against the reference's: each round's
    relative loss gap, each round's worst client quality gap, each
    leaf's gap in the norm of the weights' change (``leaf_gaps``), and
    the returned flags that differ."""
    loss, q, mismatched = [], [], 0
    for (_, _, lg, bg, qg), (_, _, lw, bw, qw) in zip(g["rounds"],
                                                       w["rounds"]):
        loss.append(abs(lg - lw) / abs(lw))
        q.append(float(np.max(np.abs(qg - qw))))
        mismatched += int((np.asarray(bg) != np.asarray(bw)).sum())
    return {"loss": loss, "q": q,
            "update": leaf_gaps(g["w1"], w["w1"], g["w0"], w["w0"]),
            "dropout_mismatch": mismatched}


def compare(got: list[dict], want: list[dict]) -> dict:
    """The numbers compared, over every tenant's first chunk
    (``tenant_gaps``): the worst round's relative loss gap and the worst
    leaf's gap in the norm of the weights' change, over all tenants
    (``loss_gap``, ``update_gap``) and, for the weights, the median
    tenant's (``update_gap_med``); the worst client quality gap of the
    first round (``q_gap_first``); and the returned flags that differ.
    The medians and the first round are steady from seed to seed, where
    the worst tenant's later rounds swing (PERF.md §2)."""
    per = [tenant_gaps(g, w) for g, w in zip(got, want)]
    upd = [float(np.nanmax(t["update"])) for t in per]
    return {"loss_gap": max(max(t["loss"]) for t in per),
            "update_gap": max(upd),
            "update_gap_med": float(np.median(upd)),
            "q_gap_first": max(t["q"][0] for t in per),
            "dropout_mismatch": float(sum(t["dropout_mismatch"]
                                          for t in per))}


def build(config: dict, traffic: dict, seed: int, session) -> Cell:
    return Cell(config, traffic, seed, session)


def readings(config: dict, traffic: dict, seeds: list[int], n_control: int,
             session, seconds: float):
    """Per seed, one row of the numbers compared: every tenant's first
    chunk, driven through ``ServiceScheduler.sweep`` as set-up drives it,
    against the plain reference; on the first ``n_control`` seeds also
    the control (``Cell.control_first_chunks``, in the program's place)
    and each fault planted in the reference, through the same
    comparison, with each tenant's gaps (``tenant_gaps``) beside the
    numbers. ``seconds`` is unused: no window is needed."""
    import jax
    for n, s in enumerate(seeds):
        t = time.perf_counter()
        cell = build(config, traffic, s, session)
        cell.build_tenants()
        cell._sweep_setup()
        for sim in cell.sims:
            jax.block_until_ready(sim.params)
        first = cell.program_first_chunks()
        cell.scheduler = cell.sims = None
        ref = cell.reference_first_chunks(first)
        runs = {"program": first}
        if n < n_control:
            runs["control"] = cell.control_first_chunks(first)
            for fault in ("half", "flip"):
                runs[fault] = cell.reference_first_chunks(first, fault=fault)
        row = {"seed": s}
        for name, got in runs.items():
            row[name] = compare(got, ref)
            row[name]["tenants"] = [tenant_gaps(g, w)
                                    for g, w in zip(got, ref)]
        row["seconds"] = time.perf_counter() - t
        yield row
