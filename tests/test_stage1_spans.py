"""Stage-1 spans on the profiler's clock (``repro.core.spans``): the
``stage1.*`` spans of ``select_pools_batch`` nest batch > task > step,
each ``stage1.task`` carries its call's ``stats`` counters, escalation
and the flat fallback show in them, and a selection traced is the same
selection untraced."""
import glob

import jax
import numpy as np
import pytest

from repro.core import FLServiceProvider, TaskRequest, device_pool, engine
from repro.core.pool import ClientPoolState

TH = np.full(9, 0.05)


def _traced(tmp_path, fn):
    """``fn()`` under ``jax.profiler.trace``; returns its result and the
    host ``stage1.*`` events as ``(name, start, end, args)``."""
    with jax.profiler.trace(str(tmp_path)):
        out = fn()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("stage1."):
                    events.append((e.name, e.start_ns,
                                   e.start_ns + e.duration_ns,
                                   {k: v for k, v in e.stats}))
    return out, sorted(events, key=lambda e: (e[1], -e[2]))


def _parent(ev, events):
    """The innermost other span that contains ``ev``."""
    inside = [e for e in events if e is not ev
              and e[1] <= ev[1] and ev[2] <= e[2]]
    return min(inside, key=lambda e: e[2] - e[1]) if inside else None


def _children(task, events):
    return [e for e in events if _parent(e, events) is task]


@pytest.fixture
def spy_stats(monkeypatch):
    """The ``stats`` dict of every ``hierarchical_greedy_knapsack`` call,
    in call order."""
    seen = []
    real = engine.hierarchical_greedy_knapsack

    def spy(*a, stats=None, **k):
        stats = {} if stats is None else stats
        out = real(*a, stats=stats, **k)
        seen.append((stats, out))
        return out
    monkeypatch.setattr(engine, "hierarchical_greedy_knapsack", spy)
    return seen


def _fleet(monkeypatch, n, seed):
    monkeypatch.setattr(device_pool, "HIERARCHICAL_MIN_N", 1000)
    monkeypatch.setattr(device_pool, "DEFAULT_SHARD_CAP", 512)
    return ClientPoolState.random(n, 10, np.random.default_rng(seed))


def _tasks(budgets):
    return [TaskRequest(budget=b, n_star=3, thresholds=TH, seed=i)
            for i, b in enumerate(budgets)]


def test_spans_nest_and_carry_the_task_counters(tmp_path, monkeypatch,
                                                spy_stats):
    pool = _fleet(monkeypatch, 6000, seed=31)
    sp = FLServiceProvider(pool)
    tasks = _tasks([50.0, 800.0, 8000.0, 10.0 * pool.n])
    _, events = _traced(tmp_path, lambda: sp.select_pools_batch(tasks))

    batch, = [e for e in events if e[0] == "stage1.batch"]
    assert batch[3]["tasks"] == 4
    sync, = [e for e in events if e[0] == "stage1.sync"]
    task_spans = [e for e in events if e[0] == "stage1.task"]
    results = [e for e in events if e[0] == "stage1.result"]
    assert _parent(sync, events) is batch
    assert len(task_spans) == len(results) == 4
    assert all(_parent(e, events) is batch for e in task_spans + results)
    assert [t[3]["index"] for t in task_spans] == [0, 1, 2, 3]
    assert {t[3]["batch"] for t in task_spans} == {batch[3]["batch"]}
    assert [r[3]["picks"] for r in results] \
        == [t[3]["picks"] for t in task_spans]

    assert len(spy_stats) == 4
    for task, (stats, (rows, _, _, n_valid)) in zip(task_spans, spy_stats):
        args = task[3]
        assert args["path"] == int(stats["path"] == "flat-fallback")
        for key in ("passes", "escalations", "picks", "n_valid"):
            assert args[key] == stats[key], key
        assert args["picks"] == rows.size and args["n_valid"] == n_valid
        kids = _children(task, events)
        fronts = [e for e in kids if e[0] == "stage1.frontier"]
        merges = [e for e in kids if e[0] == "stage1.merge"]
        assert [e[0] for e in kids if e[0] == "stage1.mask"] \
            == ["stage1.mask"]
        assert len(fronts) == len(merges) == stats["passes"]
        assert sum(f[3]["shards"] * f[3]["F"] for f in fronts) \
            == stats["frontier_slots"]
        if fronts:
            assert fronts[-1][3]["F"] == stats["frontier"]
            assert fronts[-1][3]["candidates"] == stats["candidates"]
            assert fronts[-1][3]["shards"] == stats["shards"] >= 2
            assert [m[3]["escalate"] for m in merges] \
                == [1] * stats["escalations"] + [0]
        assert all(_parent(e, events) is task for e in kids)

    # the select-everything budget takes the flat path: no frontier pass
    fallback = task_spans[3][3]
    assert fallback["path"] == 1 and fallback["passes"] == 0
    assert all(t[3]["path"] == 0 and t[3]["passes"] >= 1
               for t in task_spans[:3])


def test_forced_escalation_gives_two_passes(tmp_path, monkeypatch,
                                            spy_stats):
    # the best ratios crowded into shard 0, as in
    # test_escalation_still_exact; this budget floods its first frontier
    # once, and the doubled one suffices
    pool = _fleet(monkeypatch, 2000, seed=13)
    pool.costs[:256] = 1.0
    pool._overall = None
    sp = FLServiceProvider(pool)
    _, events = _traced(tmp_path,
                        lambda: sp.select_pools_batch(_tasks([50.0])))
    task, = [e for e in events if e[0] == "stage1.task"]
    assert task[3]["escalations"] == 1 and task[3]["passes"] == 2
    kids = _children(task, events)
    assert [e[0] for e in kids] == ["stage1.mask", "stage1.frontier",
                                    "stage1.merge", "stage1.frontier",
                                    "stage1.merge"]
    assert [e[3]["escalate"] for e in kids if e[0] == "stage1.merge"] \
        == [1, 0]
    f1, f2 = [e[3]["F"] for e in kids if e[0] == "stage1.frontier"]
    assert f2 == 2 * f1
    stats, _ = spy_stats[0]
    assert stats["frontier_slots"] == stats["shards"] * (f1 + f2)


def test_frontier_spans_name_the_selection_method(tmp_path, monkeypatch):
    pool = _fleet(monkeypatch, 6000, seed=33)
    sp = FLServiceProvider(pool)
    _, events = _traced(tmp_path, lambda: sp.select_pools_batch(
        _tasks([50.0, 800.0, 8000.0])))
    fronts = [e for e in events if e[0] == "stage1.frontier"]
    assert len(fronts) >= 3
    assert {f[3]["method"] for f in fronts} == {"threshold"}


def test_traced_selection_equals_untraced(tmp_path, monkeypatch):
    pool = _fleet(monkeypatch, 6000, seed=32)
    sp = FLServiceProvider(pool)
    tasks = _tasks([40.0, 900.0, 7000.0, 10.0 * pool.n])
    traced, events = _traced(tmp_path, lambda: sp.select_pools_batch(tasks))
    assert any(e[0] == "stage1.task" for e in events)
    plain = sp.select_pools_batch(tasks)
    for a, b in zip(traced, plain):
        assert a.selected == b.selected
        assert a.total_score == b.total_score
        assert a.total_cost == b.total_cost
        assert (a.feasible, a.note) == (b.feasible, b.note)
