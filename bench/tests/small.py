"""The benchmark's cells at a size a CPU test run can hold: the same
kinds, references and checks, with small widths and pools."""
from __future__ import annotations

import copy
import io
import json
import time

from bench import harness

ROOT = harness.BENCH.parent


def small_config(name: str) -> dict:
    cfg = harness.read_json(ROOT / "bench" / "configs" / f"{name}.json")
    cfg = copy.deepcopy(cfg)
    if name == "cnn-cifar":
        cfg["model"].update(height=16, width=16, conv1=4, conv2=8,
                            hidden=16)
        cfg["pool"].update(clients=12, train_samples=1200,
                           test_samples=64,
                           client_classes=cfg["pool"]["client_classes"][:12])
        cfg["training"].update(subset_size=4, subset_delta=1,
                               pad_subset_to=5, batch_size=4)
    elif name == "fleet-1m":
        cfg["fleet"].update(clients=4096)
    return cfg


def hierarchical_at_small_size(monkeypatch) -> None:
    """Route a small fleet through the hierarchical plane, as a fleet
    of millions is routed."""
    from repro.core import device_pool
    monkeypatch.setattr(device_pool, "HIERARCHICAL_MIN_N", 1024)
    monkeypatch.setattr(device_pool, "DEFAULT_SHARD_CAP", 1024)


def small_traffic(name: str) -> dict:
    tr = copy.deepcopy(harness.read_json(
        ROOT / "bench" / "traffic" / f"{name}.json"))
    if tr["kind"] == "closed_tenants" and tr.get("devices", 1) == 1:
        tr["tenants"] = 2
    if tr["kind"] == "open_select":
        tr.update(rate_per_s=20.0, warm_budgets=4)
    return tr


# both cells as the benchmark defines them; ``cifar-dense`` is not in
# BENCHMARK.json until it has been proven on the chip (PERF.md)
SPEC = ROOT / "bench" / "tests" / "data" / "two_cells.json"


def run_small(workload: str, seed: int = 123, seconds: float = 1.0,
              trace: bool = False, config: dict | None = None,
              traffic: dict | None = None, on_trace=None,
              spec_file=SPEC) -> dict:
    spec = harness.read_json(spec_file)
    cell = harness.entry(spec["workloads"], workload, "workload")
    err = io.StringIO()
    line = harness.run_cell(
        ROOT, workload, seed, seconds, trace, t0=time.perf_counter(),
        spec_file=spec_file,
        require_tpu=False, err=err,
        config=config or small_config(cell["config"]),
        traffic=traffic or small_traffic(cell["traffic"]),
        on_trace=on_trace)
    line["stderr"] = err.getvalue()
    json.dumps(line)
    return line
