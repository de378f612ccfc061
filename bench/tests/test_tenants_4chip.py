"""The training cells at a small size on the CPU: a whole run of the
eight tenants bin-packed over four host devices (traffic
``tenants-8-binpack``, the four-chip cell PERF.md lists as next), the
control, seeds that train the same work, and the per-chip idle
reduction."""
import json
import os
import subprocess
import sys
import types

import jax
import pytest

from bench import harness, trace
from bench.tests import small
from bench.tests.small import ROOT

CELL = "cifar-tenants-4chip"


def four_chip_spec(tmp_path):
    """``BENCHMARK.json`` with the four-chip cell beside the one-chip
    training cell: the same configuration and metrics, the tenants
    placed over four chips."""
    spec = harness.read_json(ROOT / "BENCHMARK.json")
    one = harness.entry(spec["workloads"], "cifar-tenants", "workload")
    spec["workloads"].append(dict(one, name=CELL, chips=4,
                                  traffic="tenants-8-binpack"))
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "cifar-tenants" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    return path


WHOLE_RUN = """
import json, sys, time
from pathlib import Path
sys.path[:0] = [{root!r}, {src!r}]
from bench import harness, trace
from bench.tests import small
infos, busy = [], []
line = harness.run_cell(
    small.ROOT, {cell!r}, {seed}, 1.0, True, t0=time.perf_counter(),
    spec_file=Path({spec!r}), require_tpu=False,
    err=sys.stderr, info=infos.append,
    config=small.small_config("cnn-cifar"),
    traffic=small.small_traffic("tenants-8-binpack"),
    on_trace=lambda ev: busy.append(trace.device_busy_s(ev, 4)))
print(json.dumps({{"line": line, "info": infos[0]["info"],
                  "device_busy_s": busy[0]}}))
"""


def _kind():
    return harness.load_module(harness.BENCH / "kinds" / "closed_tenants.py",
                               "closed_tenants_4chip")


def test_whole_run_over_four_devices(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = WHOLE_RUN.format(root=str(ROOT), src=str(ROOT / "src"),
                            cell=CELL, seed=2**33 + 41,
                            spec=str(four_chip_spec(tmp_path)))
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    line, info = out["line"], out["info"]
    assert line["correct"], p.stderr[-4000:]
    assert line["device"]["count"] == 4
    assert line["attempted"] > 0 and line["failed"] == 0
    per_dev = info["tenants_per_device"]
    assert len(per_dev) == 4 and sum(per_dev) == 8
    assert sum(n > 0 for n in per_dev) > 1
    assert info["compile_requests_in_window"] == 0
    assert len(info["subset_sizes_per_period"]) == 1
    assert len(out["device_busy_s"]) == 4


def test_fp8_control_fails_the_run(monkeypatch):
    """The harness's wiring of the control: the plain reference with
    every model product's inputs in fp8, put in the program's place,
    reaches the run's own check and is found out there. At this CPU
    size the program computes in full float32, so this shows the
    plumbing, not that the limits separate the control on the chip
    (PERF.md §2 gives those readings)."""
    real_load = harness.load_module

    def load(path, name):
        mod = real_load(path, name)
        if name.startswith("bench_kind_"):
            real_first = mod.Cell.program_first_chunks

            def control(self):
                return self.control_first_chunks(real_first(self))
            monkeypatch.setattr(mod.Cell, "program_first_chunks", control)
        return mod
    monkeypatch.setattr(harness, "load_module", load)
    line = small.run_small("cifar-dense", seed=2**34 + 15)
    assert not line["correct"]
    assert {"loss_gap", "update_gap", "update_gap_med", "q_gap_first"} & {
        n for n, c in line["checks"].items()
        if not c["value"] <= c["limit"]}


def _shape(seed, calls):
    """Rounds, padded client slots and subset sizes a seed trains in
    set-up and a few sweeps."""
    del calls[:]
    cell = _kind().build(small.small_config("cnn-cifar"),
                         small.small_traffic("tenants-4-closed"), seed,
                         harness.Session(jax.devices()[:1], False))
    cell.setup()
    for _ in range(5):
        cell.scheduler.sweep()
    rounds = [[len(ev.subset) for ev in cell.scheduler.state(t).rounds]
              for t in cell.tids]
    return rounds, list(calls), cell.period_shapes()


def test_seeds_train_the_same_work(monkeypatch):
    from repro.fl import simulation
    calls = []
    real = simulation.DeviceFLSim._enqueue_segment

    def spy(self, start_round, subsets, *a, **k):
        calls.append((len(subsets), self._k_pad(max(len(s)
                                                    for s in subsets))))
        return real(self, start_round, subsets, *a, **k)
    monkeypatch.setattr(simulation.DeviceFLSim, "_enqueue_segment", spy)
    a = _shape(5, calls)
    b = _shape(2**40 + 77, calls)
    assert a[0] == b[0]                  # rounds and their subset sizes
    assert a[1] == b[1]                  # (rounds, padded slots) a call
    assert a[2] == b[2] and len(a[2]) == 1


@pytest.mark.parametrize("busy_ns, mean, most", [
    ((600, 600, 600, 600), 40.0, 40.0),
    ((1000, 500, 300, 0), 55.0, 100.0),
])
def test_idle_is_read_per_chip(busy_ns, mean, most):
    """Four chips over a 1000 ns window: each chip's own idle share is
    averaged, and a chip that ran nothing counts as wholly idle."""
    events = [trace.Event("/host:CPU", "python", "bench.window", 0.0,
                          1000.0, {})]
    for d, ns in enumerate(busy_ns):
        if ns:
            events.append(trace.Event(f"/device:TPU:{d}", "XLA Ops",
                                      "fusion.1", 0.0, float(ns), {}))
    per = trace.device_busy_s(events, 4)
    assert len(per) == 4
    ctx = types.SimpleNamespace(window_s=trace.window_s(events),
                                device_busy_s=per)
    read = {n: harness.load_module(harness.BENCH / "metrics" / f"{n}.py",
                                   n).read
            for n in ("idle_share.train", "train_device_idle_max")}
    assert read["idle_share.train"](ctx) == pytest.approx(mean)
    assert read["train_device_idle_max"](ctx) == pytest.approx(most)
