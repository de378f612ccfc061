"""Whole runs of each cell at a small size on the CPU, with the chip
check skipped: a sound run comes out correct, and each fault a cell can
have, planted under the timed path, comes out not correct."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.tests import small


@pytest.fixture
def fleet_small(monkeypatch):
    small.hierarchical_at_small_size(monkeypatch)


def failed_checks(line):
    return sorted(n for n, c in line["checks"].items()
                  if not c["value"] <= c["limit"])


def test_cifar_sound_run_is_correct():
    line = small.run_small("cifar-dense", seed=2**33 + 1)
    assert line["correct"], line["stderr"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"client_updates_per_s", "round_p95_ms",
                                    "setup_s"}
    assert list(line)[-2] == "checks"
    assert line["stderr"].strip().splitlines()[-1].startswith("check ")


def test_fleet_sound_run_is_correct(fleet_small):
    from repro.core import engine
    paths = []
    real = engine.hierarchical_greedy_knapsack

    def spy(*a, stats=None, **k):
        stats = {} if stats is None else stats
        out = real(*a, stats=stats, **k)
        paths.append(stats["path"])
        return out
    import pytest as _p
    mp = _p.MonkeyPatch()
    mp.setattr(engine, "hierarchical_greedy_knapsack", spy)
    try:
        line = small.run_small("fleet-select", seed=7)
    finally:
        mp.undo()
    assert line["correct"], line["stderr"]
    assert set(line["metrics"]) == {"select_tasks_per_s", "setup_s"}
    assert paths and set(paths) == {"frontier"}


# -- training faults -----------------------------------------------------------

def _scan_with(monkeypatch, **change):
    import repro.fl.simulation as simulation
    real = simulation.make_fl_rounds_scan

    def patched(loss_fn, **kw):
        if "loss" in change:
            loss_fn = change["loss"](loss_fn)
        kw.update(change.get("kw", {}))
        return real(loss_fn, **kw)
    monkeypatch.setattr(simulation, "make_fl_rounds_scan", patched)


def test_cifar_state_left_unchanged_is_caught(monkeypatch):
    _scan_with(monkeypatch, kw={"server_lr": 0.0})
    line = small.run_small("cifar-dense", seed=11)
    assert not line["correct"]
    assert "update_gap" in failed_checks(line)


def test_cifar_half_the_batch_is_caught(monkeypatch):
    def half(loss_fn):
        def f(p, b):
            n = b["labels"].shape[0] // 2
            return loss_fn(p, {k: v[:n] for k, v in b.items()})
        return f
    _scan_with(monkeypatch, loss=half)
    line = small.run_small("cifar-dense", seed=12)
    assert not line["correct"]
    assert "loss_gap" in failed_checks(line)


def test_cifar_altered_update_is_caught(monkeypatch):
    from repro.kernels import ops
    real = ops.fedavg_agg_quality

    def altered(updates, weights, **kw):
        return real(updates.at[0].multiply(-1.0), weights, **kw)
    monkeypatch.setattr(ops, "fedavg_agg_quality", altered)
    line = small.run_small("cifar-dense", seed=13)
    assert not line["correct"]
    assert {"update_gap", "update_gap_med", "q_gap_first"} & set(
        failed_checks(line))


def test_cifar_bfloat16_control_fails_the_run(monkeypatch):
    """The control, the plain reference in bfloat16 at default precision,
    put in the program's place: the run's own check finds it out."""
    real_load = harness.load_module

    def load(path, name):
        mod = real_load(path, name)
        if name.startswith("bench_kind_"):
            real_first = mod.Cell.program_first_chunks

            def control(self):
                return self.reference_first_chunks(
                    real_first(self), dtype=jnp.bfloat16,
                    precision=jax.lax.Precision.DEFAULT)
            monkeypatch.setattr(mod.Cell, "program_first_chunks", control)
        return mod
    monkeypatch.setattr(harness, "load_module", load)
    line = small.run_small("cifar-dense", seed=14)
    assert not line["correct"]
    assert {"loss_gap", "update_gap", "update_gap_med", "q_gap_first"} & set(
        failed_checks(line))


# -- stage-1 faults ------------------------------------------------------------

def _select_with(monkeypatch, change):
    from repro.core import FLServiceProvider
    real = FLServiceProvider.select_pools_batch

    @functools.wraps(real)
    def patched(self, tasks, rngs=None):
        return change(self, tasks, real(self, tasks, rngs))
    monkeypatch.setattr(FLServiceProvider, "select_pools_batch", patched)


def test_fleet_stale_answer_is_caught(monkeypatch, fleet_small):
    last = {}

    def stale(provider, tasks, res):
        prev = last.get("res")
        last["res"] = res
        return [prev[0]] * len(res) if prev else res
    _select_with(monkeypatch, stale)
    line = small.run_small("fleet-select", seed=21)
    assert not line["correct"]
    assert "pick_mismatch" in failed_checks(line)


def test_fleet_half_the_fleet_is_caught(monkeypatch, fleet_small):
    from repro.core import device_pool
    real = device_pool.DevicePoolState.valid_mask

    def half(self, thresholds):
        v = real(self, thresholds)
        keep = jnp.arange(v.shape[0])[:, None] < (v.shape[0] + 1) // 2
        return v & keep
    monkeypatch.setattr(device_pool.DevicePoolState, "valid_mask", half)
    line = small.run_small("fleet-select", seed=22)
    assert not line["correct"]
    assert "pick_mismatch" in failed_checks(line)


def test_fleet_altered_answer_is_caught(monkeypatch, fleet_small):
    def drop_last(provider, tasks, res):
        for r in res:
            r.selected = r.selected[:-1]
        return res
    _select_with(monkeypatch, drop_last)
    line = small.run_small("fleet-select", seed=23)
    assert not line["correct"]
    assert "pick_mismatch" in failed_checks(line)


def test_fleet_float32_control_fails_the_run(monkeypatch, fleet_small):
    """The control, the plain greedy in float32, answering in the place
    of ``select_pools_batch``: the run's own check finds it out."""
    ref = harness.load_module(harness.BENCH / "configs" / "fleet-1m.py",
                              "fleet_ref_control")
    greedy = {}

    def control(provider, tasks, res):
        pool = provider.pool_state
        for t, r in zip(tasks, res):
            th = tuple(t.thresholds)
            if th not in greedy:
                greedy[th] = ref.Greedy(pool.scores, pool.costs,
                                        np.asarray(th), dtype=np.float32)
            rows, r.total_score, r.total_cost = greedy[th].select(t.budget)
            r.selected = pool.client_ids[rows]
        return res
    _select_with(monkeypatch, control)
    line = small.run_small("fleet-select", seed=24)
    assert not line["correct"]
    assert "total_gap" in failed_checks(line)
