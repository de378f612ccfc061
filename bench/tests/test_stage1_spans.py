"""The readers of the program's stage-1 spans (``bench/spans.py`` and the
``select_frontier_*``/``select_*merge*`` metrics): on hand-made traces,
on a whole traced run at a small size on the CPU, with and without the
program's spans."""
import io
import time
import types
from pathlib import Path

import pytest

from bench import harness, kernels, spans, trace
from bench.tests import small

DATA = Path(__file__).parent / "data"

DEV = "/device:TPU:0"
HOST = "/host:CPU"
NEW = ("select_frontier_ms", "select_merge_ms", "select_idle_merge_ms",
       "select_frontier_passes_per_task", "select_frontier_slots_per_pick")


def ev(plane, line, name, start, dur, **stats):
    return trace.Event(plane, line, name, float(start), float(dur), stats)


def host(name, start, end, **stats):
    return ev(HOST, "python3", name, start, end - start, **stats)


def two_tasks():
    """A 1000 ns window, one batch of two tasks. The device runs
    [160, 195), [205, 295) and [560, 690): its idle gap [295, 560)
    crosses the end of task 0's merge and the start of task 1, and the
    one from 690 to the window's end covers task 1's whole merge."""
    return [
        host("bench.window", 0, 1000),
        host("bench.select", 90, 910),
        host("stage1.batch", 100, 900, batch=0, tasks=2),
        host("stage1.sync", 110, 130),
        host("stage1.task", 150, 500, batch=0, index=0, path=0, passes=1,
             escalations=0, picks=10, n_valid=100),
        host("stage1.mask", 160, 200),
        host("stage1.frontier", 200, 300, F=4, shards=2, candidates=8),
        host("stage1.merge", 300, 420, escalate=0),
        host("stage1.result", 850, 870, picks=10),
        host("stage1.task", 520, 850, batch=0, index=1, path=0, passes=1,
             escalations=0, picks=6, n_valid=100),
        host("stage1.mask", 530, 560),
        host("stage1.frontier", 560, 700, F=8, shards=2, candidates=16),
        host("stage1.merge", 700, 800, escalate=0),
        host("stage1.mask", 1500, 1600),            # after the window
        ev(DEV, "XLA Ops", "fusion.1", 160, 35),
        ev(DEV, "XLA Ops", "segmented_topk.2", 205, 90),
        ev(DEV, "XLA Ops", "segmented_topk.3", 560, 130),
    ]


def ctx_of(events):
    return types.SimpleNamespace(
        events=events, counters={}, peaks=None,
        window_s=trace.window_s(events), busy_s=trace.busy_s(events))


def read(name, ctx):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py",
                               name).read(ctx)


def test_self_time_leaves_out_the_children():
    s = spans.stage1(two_tasks())
    assert len(s) == 11                          # not the late mask
    own = {(e.name, e.start_ns): sum(b - a for a, b in p)
           for e, p in spans.self_parts(s)}
    assert own[("stage1.batch", 100.0)] == 800 - 20 - 350 - 330 - 20
    assert own[("stage1.task", 150.0)] == 350 - 40 - 100 - 120
    assert own[("stage1.task", 520.0)] == 330 - 30 - 140 - 100
    assert own[("stage1.frontier", 200.0)] == 100
    assert spans.self_ns(spans.self_parts(s), "stage1.merge") == 220


def test_idle_gap_across_span_boundaries():
    events = two_tasks()
    parts = spans.self_parts(spans.stage1(events))
    assert trace.idle_gaps(events)[2:] == [(295.0, 560.0), (690.0, 1000.0)]
    assert spans.idle_ns(events, parts, "stage1.merge") == 120 + 100
    assert spans.idle_ns(events, parts, "stage1.task") == 10 + 80 + 10 + 50
    assert spans.idle_ns(events, parts, "stage1.frontier") == 5 + 5 + 10


def test_readers_on_two_tasks():
    ctx = ctx_of(two_tasks())
    assert read("select_frontier_ms", ctx) == pytest.approx(120e-6)
    assert read("select_merge_ms", ctx) == pytest.approx(110e-6)
    assert read("select_idle_merge_ms", ctx) == pytest.approx(110e-6)
    assert read("select_frontier_passes_per_task", ctx) == 1.0
    assert read("select_frontier_slots_per_pick", ctx) == (8 + 16) / 16


def test_readers_find_nothing_without_the_spans():
    bare = [e for e in two_tasks() if e.name != "stage1.task"]
    for name in NEW:
        assert read(name, ctx_of(bare)) is None, name
    host_only = [e for e in two_tasks() if e.plane == HOST]
    assert read("select_idle_merge_ms", ctx_of(host_only)) is None
    assert read("select_merge_ms", ctx_of(host_only)) \
        == pytest.approx(110e-6)


def test_share_starting_inside():
    ops = [ev(DEV, "XLA Ops", "a", 10, 30), ev(DEV, "XLA Ops", "b", 45, 10),
           ev(DEV, "XLA Ops", "c", 70, 60)]
    assert spans.share_starting_inside(ops, [(0, 20), (40, 50)]) \
        == pytest.approx(40 / 100)
    assert spans.share_starting_inside([], [(0, 1)]) is None


def _traced_small(monkeypatch, seed):
    """A whole traced run of ``fleet-select`` at a small size, as the
    benchmark defines it, and its events."""
    small.hierarchical_at_small_size(monkeypatch)
    kept = {}
    line = harness.run_cell(
        small.ROOT, "fleet-select", seed, 1.0, True, t0=time.perf_counter(),
        require_tpu=False, config=small.small_config("fleet-1m"),
        traffic=small.small_traffic("select-overload"),
        err=io.StringIO(),
        on_trace=lambda events: kept.update(events=events))
    return line, kept["events"]


def test_traced_run_reports_the_span_metrics(monkeypatch):
    line, events = _traced_small(monkeypatch, seed=2**33 + 5)
    assert line["correct"]
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # no TPU plane on the CPU: the idle share inside merges is not read
    assert set(NEW) - set(m) == {"select_idle_merge_ms"}
    assert m["select_frontier_ms"] > 0 and m["select_merge_ms"] > 0
    assert m["select_frontier_passes_per_task"] >= 1.0
    assert m["select_frontier_slots_per_pick"] >= 1.0
    s = spans.stage1(events)
    batches = spans.named(s, "stage1.batch")
    lo, hi = trace.window(events)
    sel = [e for e in trace.spans(events, "bench.select")
           if lo <= e.start_ns < hi]
    assert len(batches) == len(sel)
    assert sum(e.stats["tasks"] for e in batches) \
        == len(spans.named(s, "stage1.task"))


def test_traced_run_without_program_spans_leaves_them_out(monkeypatch):
    """A program that writes no ``stage1.*`` span, as before they were
    added: the run still ends, and the line leaves the metrics out."""
    from repro.core import spans as program_spans

    class Silent:
        def __init__(self, name, **args):
            pass

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        @staticmethod
        def is_enabled():
            return False
    monkeypatch.setattr(program_spans, "TraceAnnotation", Silent)
    line, events = _traced_small(monkeypatch, seed=2**33 + 6)
    assert line["correct"] and not spans.stage1(events)
    assert "select_call_ms" in line["metrics"]
    assert not set(NEW) & set(line["metrics"])


def _topk_in_frontier(events):
    lo, hi = trace.window(events)
    topk = [e for e in trace.device_ops(events) if lo <= e.start_ns < hi
            and kernels.is_kernel(e, kernels.SEGMENTED_TOPK)]
    parts = spans.self_parts(spans.stage1(events))
    return spans.share_starting_inside(
        topk, spans.intervals(parts, "stage1.frontier"))


def test_readers_on_a_recorded_tpu_trace():
    """The first two calls of a traced ``fleet-select`` window on one v5e
    chip: one task at F = 4,096, then two at F = 8,192 and 2,048. The
    sums are the spans' durations (the frontier and merge spans have no
    children), read off the slice by hand."""
    events = trace.read_dump(str(DATA / "stage1_slice.json"))
    ctx = ctx_of(events)
    frontier = 39_259_467 + 121_104_490 + 16_821_458
    merge = 7_873_269 + 130_517_788 + 3_623_660
    assert read("select_frontier_ms", ctx) == pytest.approx(
        1e-6 * frontier / 3)
    assert read("select_merge_ms", ctx) == pytest.approx(1e-6 * merge / 3)
    # the device is idle through every merge: nothing queued behind it
    assert read("select_idle_merge_ms", ctx) == pytest.approx(
        1e-6 * merge / 3)
    assert read("select_frontier_passes_per_task", ctx) == 1.0
    assert read("select_frontier_slots_per_pick", ctx) == pytest.approx(
        8 * (4096 + 8192 + 2048) / (5202 + 6724 + 3001))


def test_recorded_spans_share_the_device_clock_and_cover_the_call():
    events = trace.read_dump(str(DATA / "stage1_slice.json"))
    # every segmented_topk pass starts inside a stage1.frontier span
    assert _topk_in_frontier(events) == 1.0
    s = spans.stage1(events)
    parts = spans.self_parts(s)
    batch = sum(e.dur_ns for e in spans.named(s, "stage1.batch"))
    outer = sum(e.dur_ns for e in trace.spans(events, "bench.select"))
    assert batch == 50_546_256 + 278_220_526
    assert batch / outer >= 0.98
    own = spans.self_ns(parts, "stage1.batch") \
        + spans.self_ns(parts, "stage1.task")
    assert own == (50_546_256 - 8_680 - 49_929_756 - 483_930) \
        + (278_220_526 - 7_150 - 254_213_328 - 23_053_958 - 534_440
           - 222_270) \
        + (49_929_756 - 2_655_700 - 39_259_467 - 7_873_269) \
        + (254_213_328 - 2_357_580 - 121_104_490 - 130_517_788) \
        + (23_053_958 - 2_504_910 - 16_821_458 - 3_623_660)
    assert own / batch < 0.05


def test_recorded_trace_with_the_device_clock_ahead():
    """Another traced run's first two calls, where the profiler placed the
    device about 1.2 ms early against the host: the masked ratios the
    host dispatches inside ``stage1.frontier`` show on the device before
    the span opens, so the first ``segmented_topk`` pass of each call
    starts before it too. Span edges carry that much uncertainty."""
    events = trace.read_dump(str(DATA / "stage1_slice_offset.json"))
    fronts = spans.named(spans.stage1(events), "stage1.frontier")
    ratios = [e for e in trace.device_ops(events, line=trace.MODULES_LINE)
              if e.name.startswith("jit__masked_ratio")]
    early = [f.start_ns - r.start_ns for f, r in zip(fronts, ratios)]
    assert early == [23_094_728 - 21_908_636, 77_759_593 - 76_470_870]
    assert _topk_in_frontier(events) == pytest.approx(
        (6_810_942 + 2_067_676 + 30_890_940 + 15_445_484 + 7_723_252)
        / (27_244_775 + 6_810_942 + 2_067_676 + 61_782_943 + 30_890_940
           + 15_445_484 + 7_723_252))
    # the merges stay idle on the device all the same
    ctx = ctx_of(events)
    assert read("select_idle_merge_ms", ctx) == pytest.approx(
        read("select_merge_ms", ctx))
