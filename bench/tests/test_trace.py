"""The reduction from a trace to metrics, on small recorded traces."""
import types
from pathlib import Path

import pytest

from bench import harness, kernels, trace

DATA = Path(__file__).parent / "data"


def ev(plane, line, name, start, dur, **stats):
    return trace.Event(plane, line, name, float(start), float(dur), stats)


DEV = "/device:TPU:0"
HOST = "/host:CPU"


def small_trace():
    """A 1000 ns window: device ops busy over [100, 300) and [350, 400)
    (an overlap inside the first), and host spans around them."""
    return [
        ev(HOST, "python", "bench.window", 0, 1000),
        ev(HOST, "python", "bench.sweep", 50, 400),
        ev(HOST, "python", "bench.wait", 600, 300),
        ev(DEV, "XLA Modules", "jit_chunk_fn(7)", 100, 300),
        ev(DEV, "XLA Ops", "fusion.1", 100, 150),
        ev(DEV, "XLA Ops",
           "%fedavg_agg_quality.2 = (f32[1070794]{0:T(1024)}, "
           "f32[13,1]{1,0:T(8,128)}, f32[13,1]{1,0:T(8,128)}, "
           "f32[1,1]{1,0:T(1,128)}) custom-call(f32[13,1]{1,0:T(8,128)} "
           "%w, f32[13,1070794]{1,0:T(8,128)} %u), "
           "custom_call_target=\"tpu_custom_call\"", 200, 100),
        ev(DEV, "XLA Ops", "fusion.3", 350, 50),
        ev(DEV, "XLA Ops", "fusion.9", 1500, 50),     # after the window
    ]


def test_busy_is_the_union_inside_the_window():
    events = small_trace()
    assert trace.window(events) == (0.0, 1000.0)
    assert trace.busy_s(events) == pytest.approx(250e-9)
    assert trace.window_s(events) == pytest.approx(1000e-9)


def test_idle_gaps_and_their_spans():
    events = small_trace()
    gaps = trace.idle_gaps(events)
    assert gaps == [(0.0, 100.0), (300.0, 350.0), (400.0, 1000.0)]
    b = trace.breakdown(events)
    assert b["idle_gaps"][0] == ["bench.wait", pytest.approx(600e-9)]
    assert b["idle_gaps"][1] == ["bench.sweep", pytest.approx(100e-9)]
    names = [n for n, _ in b["device_ops"]]
    assert names[0] == "fusion.1" and "fusion.9" not in names
    assert "fedavg_agg_quality.2" in names


def test_program_time_and_kernel_shapes():
    events = small_trace()
    assert trace.module_seconds(events, "jit_chunk_fn") \
        == (pytest.approx(300e-9), 1)
    found = kernels.executions(events, kernels.FEDAVG_AGG_QUALITY)
    assert found == [(pytest.approx(100e-9), (13, 1070794))]


def test_metric_readers_on_the_small_trace():
    events = small_trace()
    ctx = types.SimpleNamespace(
        events=events, peaks={"flops_per_s": 197e12,
                              "hbm_bytes_per_s": 819e9},
        counters={"rounds": 3, "updates": 30, "seconds": 10.0,
                  "train_flops_per_update": 1e9, "tasks": 2},
        window_s=trace.window_s(events), busy_s=trace.busy_s(events))
    read = {}
    for name in ("idle_share.train", "chunk_device_ms.train", "train_mfu",
                 "fedavg_agg_roofline"):
        read[name] = harness.load_module(
            harness.BENCH / "metrics" / f"{name}.py", name).read(ctx)
    assert read["idle_share.train"] == pytest.approx(75.0)
    assert read["chunk_device_ms.train"] == pytest.approx(1e-4)
    assert read["train_mfu"] == pytest.approx(100 * 3e9 / 197e12)
    least = 59_964_624 / 819e9
    assert read["fedavg_agg_roofline"] == pytest.approx(
        100 * least / 100e-9)


def test_a_reader_that_finds_nothing_returns_nothing():
    events = [ev(HOST, "python", "bench.window", 0, 1000)]
    ctx = types.SimpleNamespace(events=events, peaks=None, counters={},
                                window_s=1e-6, busy_s=0.0)
    for name in ("idle_share.select", "select_call_ms", "select_wait_p95_ms",
                 "segmented_topk_roofline", "fedavg_agg_roofline",
                 "chunk_device_ms.train", "train_mfu"):
        mod = harness.load_module(harness.BENCH / "metrics" / f"{name}.py",
                                  name)
        assert mod.read(ctx) is None, name


def test_dump_round_trip(tmp_path):
    events = small_trace()
    trace.dump(events, str(tmp_path / "t.json"))
    assert trace.read_dump(str(tmp_path / "t.json")) == events


def test_segmented_topk_on_a_recorded_tpu_trace():
    """Two ``segmented_topk`` calls cut from a one-chip v5e trace of
    ``fleet-select``: F = 2,048 (two kernel passes) and F = 4,096 (three),
    with the fusions between passes left out of the kernel's time."""
    events = trace.read_dump(str(DATA / "fleet_select_slice.json"))
    calls = kernels.topk_calls(events, 8, 131_072)
    assert [c[1] for c in calls] == [(8, 131_072, 2048), (8, 131_072, 4096)]
    assert calls[0][0] == pytest.approx((12_607_912 + 1_576_143) * 1e-9)
    assert calls[1][0] == pytest.approx(
        (27_244_777 + 6_810_942 + 2_067_678) * 1e-9)
    ctx = types.SimpleNamespace(
        events=events, peaks={"flops_per_s": 197e12,
                              "hbm_bytes_per_s": 819e9},
        counters={"shards": 8, "shard_width": 131_072, "tasks": 2},
        window_s=trace.window_s(events), busy_s=trace.busy_s(events))
    share = harness.load_module(
        harness.BENCH / "metrics" / "segmented_topk_roofline.py",
        "segmented_topk_roofline").read(ctx)
    need = sum(8 * 131_072 * 4 + 8 * k * 8 for k in (2048, 4096)) / 819e9
    assert share == pytest.approx(100 * need / sum(c[0] for c in calls))
    call_ms = harness.load_module(
        harness.BENCH / "metrics" / "select_call_ms.py",
        "select_call_ms").read(ctx)
    assert call_ms == pytest.approx((52_378_937 + 142_320_482) * 1e-6 / 2)
    ctx.counters["wait_p95_ms"] = 1234.5
    assert harness.load_module(
        harness.BENCH / "metrics" / "select_wait_p95_ms.py",
        "select_wait_p95_ms").read(ctx) == 1234.5
