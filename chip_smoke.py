"""Bring-up check of the FL service on a TPU, through its served path.

Run from the root of a checkout:

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the cross-device phase only

One chip runs, in one process:

- ``stage1``  — a 1M-client pool (``ClientPoolState.random``) selected
  through ``FLServiceProvider.select_pool``, which routes a pool this size
  to the hierarchical plane (``segmented_topk`` Pallas frontier). Picks and
  totals must equal the flat host greedy. The batched greedy's float32
  device path is compared with its numpy path (differing picks reported)
  and must stay within budget.
- ``stage2``  — ``schedule_period`` over the selected pool; the paper's
  §VII coverage and bounded-participation guarantees must hold.
- ``train``   — the paper's CIFAR CNN at full width on seeded non-iid
  synthetic data, served ``TaskRequest`` -> ``ServiceScheduler`` ->
  ``DeviceFLSim`` -> ``RoundEvent`` over at least two periods. The round
  chunk's compiled HLO must hold a ``tpu_custom_call`` (the fused
  aggregation kernel), the kernel must match its ``ref.py`` oracle on one
  round's deltas, and losses must be finite.
- ``codec``   — the same served path with ``compression="topk:F+int8"``,
  and each codec kernel against its ``ref.py`` oracle at CIFAR-CNN width.

``--chips 4`` runs ``placement``: eight CIFAR-CNN tenants ``bin_pack``-placed
over four chips (each tenant's params must live on its device), and a
client-sharded round scan over ``make_host_mesh()`` compared with the same
rounds run unsharded on one chip.

Each phase prints one JSON line: its checks, ``compile_s`` (XLA backend
compile time, persistent-cache reads included) and ``steady_s`` (the
phase's wall time less ``compile_s``). The last line is
``{"ok": true, "device": {...}}``. Any failed check raises, and the script
exits non-zero without that line; it also exits non-zero when JAX finds no
TPU. Data and weights are made from ``--seed``.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

FLEET_CLIENTS = 1_000_000     # stage-1 pool: routes to the hierarchical plane
FLEET_BUDGET_FRAC = 0.005     # a selective budget: ~0.5% of the fleet's cost
FL_CLIENTS = 40               # training pool: 4 subsets of 10 +- 3 a period
TRAIN_SAMPLES, TEST_SAMPLES = 8000, 1000
ROUND_CHUNK = 4
TRAIN_ROUNDS = 12             # three chunks, at least two periods
CODEC_ROUNDS = 8
TOPK_FRAC = 0.001             # codec phase: k = ceil(F * P) ~ 1,071 of 1.07M
TENANTS = 8                   # --chips 4: tenants bin-packed over the chips


class SmokeFailure(RuntimeError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileClock:
    """Sums JAX's backend-compile durations (persistent-cache reads
    included) so each phase can report its compile seconds."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.total += duration


def run_phase(name: str, clock: CompileClock, fn, *args):
    c0, t0 = clock.total, time.perf_counter()
    info, *rest = fn(*args)
    wall = time.perf_counter() - t0
    comp = clock.total - c0
    line = {"phase": name, "ok": True, "compile_s": round(comp, 3),
            "steady_s": round(wall - comp, 3), **info}
    print(json.dumps(line), flush=True)
    return rest


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def phase_stage1(n_clients: int, seed: int):
    import jax
    import numpy as np
    from repro.core import (ClientPoolState, FLServiceProvider, TaskRequest,
                            device_pool, engine)
    from repro.kernels import ops

    pool = ClientPoolState.random(n_clients, 10, np.random.default_rng(seed))
    budget = round(FLEET_BUDGET_FRAC * float(pool.costs.sum()), 1)
    task = TaskRequest(budget=budget, n_star=1, thresholds=np.full(9, 0.05),
                       subset_size=10, subset_delta=3, x_star=3, seed=seed)
    provider = FLServiceProvider(pool)
    check(pool.n >= device_pool.HIERARCHICAL_MIN_N,
          "pool too small for the hierarchical plane")
    sel = provider.select_pool(task)
    rows, score, cost, _ = engine._flat_pool_greedy(pool, budget,
                                                    task.thresholds)
    check(sel.feasible, f"stage 1 infeasible: {sel.note}")
    check(np.array_equal(np.asarray(sel.selected), pool.client_ids[rows]),
          "hierarchical picks differ from the flat greedy")
    check(sel.total_score == score and sel.total_cost == cost,
          "hierarchical totals differ from the flat greedy")

    stats: dict = {}
    engine.hierarchical_greedy_knapsack(pool, budget, task.thresholds,
                                        stats=stats)
    check(stats["path"] == "frontier", f"took the {stats['path']} path")
    mirror = pool.device_mirror()
    ratio = mirror.masked_ratio(mirror.valid_mask(task.thresholds))
    hlo = jax.jit(lambda r: ops.segmented_topk(r, stats["frontier"])
                  ).lower(ratio).compile().as_text()
    on_tpu = jax.default_backend() == "tpu"
    check("tpu_custom_call" in hlo or not on_tpu,
          "segmented_topk is not a TPU kernel")

    budgets = budget * np.array([0.5, 1.0, 2.0, 4.0])
    valid = np.broadcast_to(pool.threshold_mask(task.thresholds),
                            (budgets.size, pool.n))
    m_auto, _, _ = engine.greedy_knapsack_batch(pool.overall, pool.costs,
                                                budgets, valid)
    m_np, _, _ = engine.greedy_knapsack_batch(pool.overall, pool.costs,
                                              budgets, valid, backend="numpy")
    spent = m_auto.astype(np.float64) @ pool.costs
    picks = m_auto.sum(axis=1)
    # float32 remaining-budget arithmetic: one f32 ulp of slack per pick
    slack = picks * np.spacing(budgets.astype(np.float32)).astype(np.float64)
    check(np.all(spent <= budgets + slack), "batched greedy overspent")
    info = {"clients": pool.n, "shards": stats["shards"],
            "frontier": stats["frontier"],
            "escalations": stats["escalations"], "picks": len(sel.selected),
            "hier_equals_flat": True,
            "batch_backend": "jax" if on_tpu else "numpy",
            "batch_picks": picks.tolist(),
            "batch_picks_differing_from_numpy":
                (m_auto != m_np).sum(axis=1).tolist()}
    return info, provider, sel, task


def phase_stage2(provider, sel, task, seed: int):
    import numpy as np
    from repro.core import fairness_report
    sched = provider.schedule_period(sel.selected, task,
                                     np.random.default_rng(seed))
    rep = fairness_report(sched, sel.selected, x_star=task.x_star)
    check(rep["coverage"], "§VII coverage violated")
    check(rep["bounded"], "§VII bounded participation violated")
    return ({"pool": len(sel.selected), "subsets": sched.num_rounds,
             "coverage": True, "bounded": True,
             "jain": round(rep["jain_index"], 6)},)


def build_tenant(seed: int, n_train: int, n_test: int, n_clients: int,
                 dropout: float = 0.05, compression: str | None = None,
                 mesh=None):
    """Seeded CIFAR-shaped non-iid data, its client pool, and a
    ``DeviceFLSim`` training the paper's CIFAR CNN at full width."""
    import numpy as np
    from repro.data.synthetic import make_classification_data
    from repro.fl.partition import partition_labels
    from repro.fl.simulation import DeviceFLSim, SimConfig, pool_from_partition
    from repro.models import cnn

    full = make_classification_data("cifar", n_train + n_test, seed=seed)
    data = full.subset(np.arange(n_train))
    test = full.subset(np.arange(n_train, n_train + n_test))
    parts = partition_labels(data.labels, n_clients, "type2",
                             data.num_classes, seed=seed)
    pool = pool_from_partition(data.labels, parts, data.num_classes,
                               seed=seed)
    sim = SimConfig(batch_size=16, local_steps=2, local_lr=0.05,
                    eval_every=ROUND_CHUNK, dropout_rate=dropout, seed=seed)
    simul = DeviceFLSim(cnn.CIFAR_CNN, data, parts, test, sim,
                        pad_subset_to=13, compression=compression, mesh=mesh)
    return pool, simul


def fl_task(n_clients: int, rounds: int, seed: int,
            compression: str | None = None):
    from repro.core import TaskRequest
    return TaskRequest(budget=1e9, n_star=n_clients, subset_size=10,
                       subset_delta=3, x_star=3, max_periods=100,
                       round_chunk=ROUND_CHUNK, max_rounds=rounds, seed=seed,
                       compression=compression)


def served_rounds(scheduler, tid, rounds: int):
    """The finished task's ``RoundEvent``s, checked: all rounds ran,
    over at least two periods, with finite losses."""
    events = scheduler.results()[tid].rounds
    check(len(events) == rounds, f"{len(events)} of {rounds} rounds ran")
    periods = len({e.period for e in events})
    check(periods >= 2, f"only {periods} period(s) ran")
    losses = [e.metrics["loss"] for e in events]
    check(all(math.isfinite(v) for v in losses), f"non-finite loss {losses}")
    return events, periods, losses


def chunk_hlo(simul, K: int) -> str:
    """Compiled HLO of the tenant's round chunk at ``ROUND_CHUNK`` rounds
    of ``K`` client slots."""
    import jax.numpy as jnp
    import numpy as np
    S = ROUND_CHUNK
    schedule = {"rows": jnp.asarray(np.tile(np.arange(K, dtype=np.int32),
                                            (S, 1))),
                "weights": jnp.full((S, K), 1.0 / K, jnp.float32),
                "active": jnp.ones((S, K), jnp.float32),
                "round_ids": jnp.arange(S, dtype=jnp.int32)}
    return simul.chunk_fn.lower(simul.params, simul.data, schedule,
                                simul.base_key).compile().as_text()


def round_deltas(simul, K: int):
    """One local step's deltas of ``K`` clients from the current params:
    the stacked ``(K, P)`` matrix the round's aggregation reads."""
    import jax
    import jax.numpy as jnp
    from repro.fl import device_data
    from repro.fl.round import flatten_stacked
    from repro.models import cnn
    rows = jnp.arange(K, dtype=jnp.int32)
    _, pos_u = device_data.sample_positions(simul.base_key, 0, K, 1,
                                            simul.sim.batch_size)
    batch = device_data.gather_batches(simul.data, rows, pos_u)

    def delta(b):
        step = jax.tree_util.tree_map(lambda x: x[0], b)
        g = jax.grad(lambda p: cnn.loss_fn(simul.cfg, p, step)[0])(
            simul.params)
        return jax.tree_util.tree_map(lambda x: simul.sim.local_lr * x, g)

    flat, _ = flatten_stacked(jax.vmap(delta)(batch))
    return flat


def phase_train(seed: int, n_train: int, n_test: int, n_clients: int,
                rounds: int):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import FLServiceProvider, ServiceScheduler
    from repro.kernels import ops, ref

    pool, simul = build_tenant(seed, n_train, n_test, n_clients)
    scheduler = ServiceScheduler(FLServiceProvider(pool))
    tid = scheduler.submit(fl_task(n_clients, rounds, seed), simul)
    scheduler.run()
    _, periods, losses = served_rounds(scheduler, tid, rounds)
    on_tpu = jax.default_backend() == "tpu"
    hlo = chunk_hlo(simul, 10)
    check("tpu_custom_call" in hlo or not on_tpu,
          "round chunk holds no TPU kernel")

    K = 10
    flat = round_deltas(simul, K)
    w = jnp.full((K,), 1.0 / K, jnp.float32)
    agg, dots, sq, asq = ops.fedavg_agg_quality(flat, w)
    ragg, rdots, rsq, rasq = ref.fedavg_agg_quality_ref(flat, w)
    q = np.asarray(dots / jnp.sqrt(sq * asq))
    rq = np.asarray(rdots / jnp.sqrt(rsq * rasq))
    # f32 tolerances: agg sums K=10 terms; the Gram terms sum P ~ 1e6
    scale = float(jnp.max(jnp.abs(ragg)))
    np.testing.assert_allclose(np.asarray(agg), np.asarray(ragg),
                               rtol=1e-5, atol=1e-6 * scale)
    np.testing.assert_allclose(np.asarray(sq), np.asarray(rsq), rtol=1e-4)
    np.testing.assert_allclose(float(asq), float(rasq), rtol=1e-4)
    np.testing.assert_allclose(q, rq, rtol=0, atol=1e-4)
    info = {"model": "cnn-cifar", "params": int(flat.shape[1]),
            "rounds": rounds, "periods": periods,
            "round_chunk": ROUND_CHUNK,
            "tpu_custom_calls_in_chunk": hlo.count("tpu_custom_call"),
            "loss_first": round(losses[0], 6),
            "loss_last": round(losses[-1], 6),
            "agg_max_abs_err": float(np.max(np.abs(np.asarray(agg - ragg)))),
            "q_max_abs_err": float(np.max(np.abs(q - rq))),
            "tolerance": {"agg_rtol": 1e-5, "agg_atol_of_max": 1e-6,
                          "gram_rtol": 1e-4, "q_atol": 1e-4}}
    return (info,)


def phase_codec(seed: int, n_train: int, n_test: int, n_clients: int,
                rounds: int):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import FLServiceProvider, ServiceScheduler
    from repro.kernels import ops, ref

    spec = f"topk:{TOPK_FRAC:g}+int8"
    pool, simul = build_tenant(seed, n_train, n_test, n_clients,
                               compression=spec)
    scheduler = ServiceScheduler(FLServiceProvider(pool))
    tid = scheduler.submit(fl_task(n_clients, rounds, seed, spec), simul)
    scheduler.run()
    events, periods, losses = served_rounds(scheduler, tid, rounds)
    check(all(e.metrics.get("bytes", 0) > 0 for e in events),
          "compressed rounds report no wire bytes")

    P = sum(x.size for x in jax.tree_util.tree_leaves(simul.params))
    K = 10
    x = jax.random.normal(jax.random.PRNGKey(seed), (K, P), jnp.float32)
    k = math.ceil(TOPK_FRAC * P)
    # against the oracle where the TPU's lax.top_k is exact ...
    w128 = min(P, 131072)
    vals, idx = ops.topk_sparsify(x[:, :w128], k)
    rvals, ridx = ref.topk_sparsify_ref(x[:, :w128], k)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(rvals))
    # ... and at full width against the exact host top-k (stable
    # argsort of |x|): there the TPU's lax.top_k is not exact
    xh = np.asarray(x)
    exact = np.argsort(-np.abs(xh), axis=1, kind="stable")[:, :k]
    vals, idx = ops.topk_sparsify(x, k)
    np.testing.assert_array_equal(np.asarray(idx), exact)
    np.testing.assert_array_equal(np.asarray(vals),
                                  np.take_along_axis(xh, exact, axis=1))
    _, lax_idx = ref.topk_sparsify_ref(x, k)
    lax_idx = np.asarray(lax_idx)
    lax_off = int((lax_idx != exact).sum())
    lax_set_off = int(sum(np.setdiff1d(a, b).size
                          for a, b in zip(lax_idx, exact)))
    w = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(seed + 1), (K,)))
    step_diff = 0
    for xq in (x, vals):       # CIFAR width, and the served top-k payload
        v, s = ops.quantize_i8(xq)
        rv, rs = ref.quantize_i8_ref(xq)
        step_diff = max(step_diff, int(np.max(np.abs(
            np.asarray(v, np.int32) - np.asarray(rv, np.int32)))))
        check(step_diff <= 1, f"int8 values {step_diff} steps off the "
                              f"oracle at {xq.shape}")
        np.testing.assert_allclose(np.asarray(s), np.asarray(rs), rtol=2e-7)
        np.testing.assert_allclose(np.asarray(ops.dequantize_i8(rv, rs)),
                                   np.asarray(ref.dequantize_i8_ref(rv, rs)),
                                   rtol=2e-7)
        got = ops.fedavg_agg_quality_i8(rv, rs, w)
        want = ref.fedavg_agg_quality_i8_ref(rv, rs, w)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-6)
    info = {"compression": spec, "topk_frac": TOPK_FRAC, "k": k,
            "params": P, "rounds": rounds, "periods": periods,
            "bytes_per_round": events[-1].metrics["bytes"],
            "loss_last": round(losses[-1], 6),
            "topk_equals_exact": True,
            "lax_top_k_entries_off_exact": lax_off,
            "lax_top_k_set_off_exact": lax_set_off,
            "int8_max_step_diff": step_diff}
    return (info,)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def phase_placement(seed: int, n_train: int, n_test: int, n_clients: int,
                    rounds: int, n_devices: int):
    import jax
    import numpy as np
    from repro.core import FLServiceProvider, ServiceScheduler
    from repro.launch.mesh import make_host_mesh

    devices = jax.devices()
    check(len(devices) == n_devices,
          f"{len(devices)} devices, expected {n_devices}")
    tenants = [build_tenant(seed + i, n_train, n_test, n_clients)
               for i in range(TENANTS)]
    scheduler = ServiceScheduler(FLServiceProvider(tenants[0][0]),
                                 n_devices=n_devices, placement="bin_pack")
    tids = [scheduler.submit(fl_task(n_clients, rounds, seed + i), simul)
            for i, (_, simul) in enumerate(tenants)]
    scheduler.run()
    placed = scheduler.placements()
    for tid, (_, simul) in zip(tids, tenants):
        served_rounds(scheduler, tid, rounds)
        want = {devices[placed[tid]]}
        homes = {d for leaf in jax.tree_util.tree_leaves(simul.params)
                 for d in leaf.devices()}
        check(homes == want, f"tenant {tid} params on {homes}, placed on "
                             f"{want}")
    check(set(placed.values()) == set(range(n_devices)),
          f"tenants did not cover every device: {placed}")

    # client-sharded scan over all chips vs the same rounds on one chip
    mesh = make_host_mesh()
    _, flat_sim = build_tenant(seed, n_train, n_test, n_clients, dropout=0.0)
    _, mesh_sim = build_tenant(seed, n_train, n_test, n_clients, dropout=0.0,
                               mesh=mesh)
    rng = np.random.default_rng(seed)
    subsets = [sorted(rng.choice(n_clients, 8, replace=False).tolist())
               for _ in range(ROUND_CHUNK)]
    weights = [np.full(8, 1.0 / 8) for _ in subsets]
    res_a = flat_sim.run_rounds(0, subsets, weights)
    res_b = mesh_sim.run_rounds(0, subsets, weights)
    for (ma, qa, meta), (mb, qb, metb) in zip(res_a, res_b):
        np.testing.assert_array_equal(ma, mb)
        np.testing.assert_allclose(qa, qb, rtol=1e-3, atol=1e-4)
        np.testing.assert_allclose(meta["loss"], metb["loss"], rtol=1e-3)
    max_err = 0.0
    for a, b in zip(jax.tree_util.tree_leaves(flat_sim.params),
                    jax.tree_util.tree_leaves(mesh_sim.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)
        max_err = max(max_err, float(np.max(np.abs(np.asarray(a)
                                                   - np.asarray(b)))))
    info = {"tenants": TENANTS, "placement": "bin_pack",
            "tenants_per_device": [list(placed.values()).count(d)
                                   for d in range(n_devices)],
            "params_on_assigned_device": True,
            "mesh": dict(mesh.shape), "sharded_rounds": len(subsets),
            "sharded_vs_unsharded_max_abs_param_err": max_err}
    return (info,)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the cross-device phase")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (jax.devices()[0] is a "
              f"{dev.platform!r} device); this check runs on the chip only",
              file=sys.stderr)
        return 2
    from repro.launch.cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    print(json.dumps({"phase": "device", "ok": True,
                      "platform": dev.platform, "kind": dev.device_kind,
                      "count": len(jax.devices()), "jax": jax.__version__,
                      "x64": bool(jax.config.jax_enable_x64),
                      "compile_cache": cache_dir}), flush=True)
    s = args.seed
    if args.chips == 4:
        run_phase("placement", clock, phase_placement, s, TRAIN_SAMPLES // 2,
                  TEST_SAMPLES, FL_CLIENTS, 2 * ROUND_CHUNK, 4)
    else:
        provider, sel, task = run_phase("stage1", clock, phase_stage1,
                                        FLEET_CLIENTS, s)
        run_phase("stage2", clock, phase_stage2, provider, sel, task, s)
        run_phase("train", clock, phase_train, s, TRAIN_SAMPLES,
                  TEST_SAMPLES, FL_CLIENTS, TRAIN_ROUNDS)
        run_phase("codec", clock, phase_codec, s, TRAIN_SAMPLES,
                  TEST_SAMPLES, FL_CLIENTS, CODEC_ROUNDS)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
