"""Traffic kind ``closed_tenants``: several requesters' training tasks
served side by side by one ``ServiceScheduler`` over one client pool, in
a closed loop (each tenant's next chunk is dispatched when its previous
one is collected).

Set-up makes the data and the client pool from the seed, submits every
tenant, warms each tenant's round-chunk program for every (rounds per
segment, padded client count) its schedules can ask for, and serves
sweeps until every tenant is past its first period. The window then
calls ``ServiceScheduler.sweep`` until it closes.

Correctness: the first chunk of every tenant (set-up's first sweep, the
window's own call) is replayed by the configuration's plain reference
from the same seed, and every schedule stage 2 produced is held to the
paper's §VII guarantees.
"""
from __future__ import annotations

import time

import numpy as np

from bench import datagen, harness
from bench.harness import BENCH

def p95(values) -> float:
    return float(np.percentile(np.asarray(values, np.float64), 95))


def leaf_gap(prog: dict, ref: dict, base_p: dict, base_r: dict):
    """Worst leaf's gap between the program's and the reference's norm
    of the parameters' change, against the larger of that leaf's
    reference norm and the median leaf's. Leaves whose reference change
    is under a thousandth of the median leaf's are left out: they move
    by round-off alone."""
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
    gaps = [abs(p - r) / max(r, med) for p, r in zip(pn, rn)
            if r >= 1e-3 * med]
    return max(gaps) if gaps else float("nan")


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
        import jax
        import jax.numpy as jnp
        self.build_tenants()
        self._sweep_setup()                 # stage 1, placement, chunk 1
        self._warm(jax, jnp)
        while any(self.scheduler.state(tid).period < 1
                  for tid in self.tids):
            self._sweep_setup()

    def build_tenants(self) -> None:
        """Data, client pool, provider, scheduler, and every tenant's
        trainer and task, submitted."""
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
        g = datagen.rng(seed, "labels")
        labels = g.integers(0, classes, size=n_train + n_test).astype(
            np.int32)
        shape = dict(height=m["height"], width=m["width"],
                     channels=m["channels"], classes=classes)
        # staged once and shared: every tenant trains on the same clients'
        # data, as tasks of one service over one client pool do
        train_img = jax.device_put(datagen.cifar_images(
            labels[:n_train], datagen.rng(seed, "train-images"), **shape),
            self.session.devices[0])
        test_img = jax.device_put(datagen.cifar_images(
            labels[n_train:], datagen.rng(seed, "test-images"), **shape),
            self.session.devices[0])
        parts = datagen.partition_type2(labels[:n_train], pc["clients"],
                                        classes, datagen.rng(seed, "split"))
        scores, hists, costs = datagen.client_criteria(
            parts, labels[:n_train], classes, datagen.rng(seed, "criteria"))
        self.labels, self.parts, self.images = labels[:n_train], parts, \
            train_img
        pool = ClientPoolState(np.arange(pc["clients"], dtype=np.int64),
                               scores, hists, costs)
        data = ClassificationData(train_img, labels[:n_train], classes)
        test = ClassificationData(test_img, labels[n_train:], classes)

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
            max_inflight=self.traffic["max_inflight"])
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

    def _warm(self, jax, jnp) -> None:
        """Compile every tenant's chunk program for every (rounds per
        segment, padded client count) a schedule can ask for, on dummy
        weights on the tenant's own device."""
        t = self.t
        sizes = range(t["subset_size"] - t["subset_delta"],
                      t["subset_size"] + t["subset_delta"] + 1)
        for sim in self.sims:
            dev = next(iter(jax.tree_util.tree_leaves(sim.params)[0]
                            .devices()))
            dummy = jax.device_put(
                jax.tree_util.tree_map(jnp.zeros_like, sim.params), dev)
            for s in range(1, t["round_chunk"] + 1):
                for k in sorted({sim._k_pad(k) for k in sizes}):
                    sched = {"rows": jnp.zeros((s, k), jnp.int32),
                             "weights": jnp.zeros((s, k), jnp.float32),
                             "active": jnp.zeros((s, k), jnp.float32),
                             "round_ids": jnp.arange(s, dtype=jnp.int32)}
                    dummy, info = sim.chunk_fn(dummy, sim.data, sched,
                                               sim.base_key)
            jax.block_until_ready(dummy)

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
                     "scheduled_client_slots": slots},
            "counters": {
                "rounds": rounds, "updates": updates, "seconds": seconds,
                "train_flops_per_update": t["local_steps"] * t["batch_size"]
                * counts.cnn_train_flops_per_sample(m),
                "params": counts.cnn_params(m)},
        }

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

    # -- check ----------------------------------------------------------------
    def check(self) -> list[tuple[str, float, float]]:
        limits = self.config["check"]["limits"]
        got = compare(self.first, self.reference_first_chunks(self.first))
        violations = sum(schedule_violations(p, s, x)
                         for p, x, s in self.schedules)
        return [("loss_gap", got["loss_gap"], limits["loss_gap"]),
                ("update_gap", got["update_gap"], limits["update_gap"]),
                ("q_gap", got["q_gap"], limits["q_gap"]),
                ("dropout_mismatch", got["dropout_mismatch"], 0.0),
                ("schedule_violations", float(violations), 0.0),
                ("nonfinite_losses", float(self.nonfinite), 0.0)]


def compare(got: list[dict], want: list[dict]) -> dict:
    """The numbers compared, over every tenant's first chunk: the worst
    round's relative loss gap, the worst leaf's gap in the norm of the
    weights' change over the chunk (``leaf_gap``), the worst client's
    quality gap, and the returned flags that differ."""
    loss_gap = q_gap = upd_gap = 0.0
    mismatched = 0
    for g, w in zip(got, want):
        for (_, _, lg, bg, qg), (_, _, lw, bw, qw) in zip(g["rounds"],
                                                           w["rounds"]):
            loss_gap = max(loss_gap, abs(lg - lw) / abs(lw))
            mismatched += int((np.asarray(bg) != np.asarray(bw)).sum())
            q_gap = max(q_gap, float(np.max(np.abs(qg - qw))))
        upd_gap = max(upd_gap, leaf_gap(g["w1"], w["w1"], g["w0"], w["w0"]))
    return {"loss_gap": loss_gap, "update_gap": upd_gap, "q_gap": q_gap,
            "dropout_mismatch": float(mismatched)}


def build(config: dict, traffic: dict, seed: int, session) -> Cell:
    return Cell(config, traffic, seed, session)


def readings(config: dict, traffic: dict, seeds: list[int], n_control: int,
             session, seconds: float):
    """Per seed, one row of the numbers compared: every tenant's first
    chunk, driven through ``ServiceScheduler.sweep`` as set-up drives it,
    against the plain reference; on the first ``n_control`` seeds also
    the control (the reference in bfloat16 at default precision, in the
    program's place) and each fault planted in the reference, through
    the same comparison. ``seconds`` is unused: no window is needed."""
    import jax
    import jax.numpy as jnp
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
        row = {"seed": s, "program": compare(first, ref),
               "losses": [[r[2] for r in f["rounds"]] for f in first],
               "ref_losses": [[r[2] for r in f["rounds"]] for f in ref]}
        if n < n_control:
            row["control"] = compare(cell.reference_first_chunks(
                first, dtype=jnp.bfloat16,
                precision=jax.lax.Precision.DEFAULT), ref)
            for fault in ("half", "flip"):
                row[fault] = compare(
                    cell.reference_first_chunks(first, fault=fault), ref)
        row["seconds"] = time.perf_counter() - t
        yield row
