"""One run of one cell: set-up, the measured window, the check, the
result line. Driven by ``BENCHMARK.json``; nothing here knows a cell,
configuration, traffic mix or metric by name.

A cell's traffic mix (``bench/traffic/<traffic>.json``) names its kind,
a module ``bench/kinds/<kind>.py`` whose ``build(config, traffic, seed,
session)`` returns an object with

- ``setup()``: inputs, weights, warm-up of every shape the window uses;
- ``window(seconds) -> dict``: the measured window; returns
  ``attempted``, ``failed``, ``e2e`` (end-to-end metric values by name),
  ``samples`` (sample counts by metric) and ``counters`` (what the
  per-layer readers need);
- ``release()``: drops the program's state once the peak memory is read;
- ``check() -> [(name, value, limit)]``: the numbers compared with the
  plain reference; a run is correct when every value is at most its
  limit.

Each per-layer metric is read by ``bench/metrics/<name>.py``'s
``read(ctx)``, which returns ``None`` where it finds nothing to read.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
import types
from pathlib import Path

BENCH = Path(__file__).resolve().parent
CACHE_DIR = BENCH.parent / ".jax_cache"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def entry(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json has no {what} {name!r}")


def applies(metric: dict, cell: str, reported: set[str] | None = None
            ) -> bool:
    """Whether ``metric`` is reported in ``cell``: listed there, or, with
    no list, wherever its ``moves`` metric (if any) is reported."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if reported is not None and "moves" in metric:
        return metric["moves"] in reported
    return True


class CompileClock:
    """Counts JAX's compile requests and persistent-cache hits, and sums
    the seconds spent tracing, lowering, compiling and reading compiled
    programs from the cache, so a window can show that nothing compiled
    inside it and set-up can show where its time went."""

    DURATIONS = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration":
                     "lower_s",
                 "/jax/core/compile/backend_compile_duration": "compile_s",
                 "/jax/compilation_cache/cache_retrieval_time_sec":
                     "cache_read_s"}

    def __init__(self):
        import jax
        self.requests = 0
        self.cache_hits = 0
        self.seconds = dict.fromkeys(self.DURATIONS.values(), 0.0)
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def _on_dur(self, event: str, duration: float, **_) -> None:
        if event in self.DURATIONS:
            self.seconds[self.DURATIONS[event]] += duration

    def snapshot(self) -> tuple[int, int, float, dict]:
        return (self.requests, self.cache_hits, self.seconds["compile_s"],
                dict(self.seconds))


class Session:
    """What a cell gets from the harness: named host spans (written into
    the profiler's trace when tracing) and the devices it may use."""

    def __init__(self, devices, trace: bool):
        self.devices = devices
        self.trace = trace

    def span(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)


def enable_cache() -> str:
    """Persistent compilation cache at one fixed directory inside the
    checkout, every program kept. A ``JAX_COMPILATION_CACHE_DIR`` set
    outside is overridden: the program takes the directory given here,
    so two checkouts never share compiled programs."""
    import jax
    from repro.launch.cache import enable_compile_cache
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: an eviction limit set outside makes JAX read an access
    # time beside every entry, and one entry without it fails every write
    jax.config.update("jax_compilation_cache_max_size", -1)
    return where


def devices_for(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: jax.devices()[0] is a {devs[0].platform!r} "
                     f"device")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return devs[:chips]


def memory_peak(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, *, t0: float, require_tpu: bool = True,
             err=sys.stderr, info=None, config: dict | None = None,
             traffic: dict | None = None, on_trace=None,
             spec_file: Path | None = None) -> dict:
    """Run one cell once; returns the result line as a dict (the caller
    prints it). ``require_tpu=False`` lets a test drive a whole run on
    the CPU; no number such a run gives is a device number. ``config``,
    ``traffic`` and ``spec_file`` stand in for the cell's files and
    ``BENCHMARK.json`` (tests run cells at a small size); ``on_trace`` is
    handed the trace's events."""
    spec = read_json(spec_file or root / "BENCHMARK.json")
    cell = entry(spec["workloads"], workload, "workload")
    cfg_entry = entry(spec["configs"], cell["config"], "config")
    if config is None:
        config = read_json(root / cfg_entry["file"])
    if traffic is None:
        traffic = read_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    kind = load_module(BENCH / "kinds" / f"{traffic['kind']}.py",
                       f"bench_kind_{traffic['kind']}")

    devices = devices_for(int(cell["chips"]), require_tpu)
    import jax
    from bench import peaks as peaks_mod
    dev0 = devices[0]
    peaks = peaks_mod.lookup(dev0.device_kind) if require_tpu else None
    cache_dir = enable_cache()
    clock = CompileClock()
    session = Session(devices, trace)

    obj = kind.build(config, traffic, seed, session)
    obj.setup()
    setup_s = time.perf_counter() - t0
    before = clock.snapshot()

    tracedir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # benchmark spans only
            jax.profiler.start_trace(tracedir, profiler_options=opts)
        with session.span("bench.window"):
            res = obj.window(seconds)
        if trace:
            jax.profiler.stop_trace()
        after = clock.snapshot()
        mem = memory_peak(devices)
        obj.release()
        gc.collect()
        events = None
        if trace:
            from bench import trace as trace_mod
            events = trace_mod.load(trace_mod.find_xplane(tracedir))
            if on_trace is not None:
                on_trace(events)
    finally:
        if tracedir is not None:
            shutil.rmtree(tracedir, ignore_errors=True)

    checks = obj.check()
    window_compiles = after[0] - before[0]
    if info is not None:
        info({"info": {
            "workload": workload, "seed": seed, "seconds": seconds,
            "trace": trace, "compile_cache": cache_dir,
            "compile_requests_in_setup": before[0],
            "cache_hits_in_setup": before[1],
            "backend_compile_s_in_setup": before[2],
            **{f"{k}_in_setup": v for k, v in before[3].items()
               if k != "compile_s"},
            "compile_requests_in_window": window_compiles,
            "samples": res.get("samples", {}),
            **res.get("info", {})}})

    reported_e2e = {m["name"] for m in spec["end_to_end"]
                    if applies(m, workload)}
    metrics: dict[str, dict] = {}
    breakdown = None
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    if not trace:
        values = dict(res["e2e"], setup_s=setup_s)
        for m in spec["end_to_end"]:
            if m["name"] in reported_e2e:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        from bench import trace as trace_mod
        ctx = types.SimpleNamespace(
            events=events, counters=res.get("counters", {}),
            peaks=peaks, config=config, traffic=traffic,
            chips=len(devices), window_s=trace_mod.window_s(events),
            busy_s=trace_mod.busy_s(events),
            device_busy_s=trace_mod.device_busy_s(events, len(devices)))
        for m in spec["per_layer"]:
            if not applies(m, workload, reported_e2e):
                continue
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                                 f"bench_metric_{m['name']}")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = sum(ctx.device_busy_s) / len(devices)
        device["window_s"] = ctx.window_s
        breakdown = trace_mod.breakdown(events)

    correct = all(math.isfinite(v) and v <= lim for _, v, lim in checks)
    line = {"correct": correct, "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    for n, v, lim in checks:
        print(f"check {n} {v!r} limit {lim!r} "
              f"{'ok' if math.isfinite(v) and v <= lim else 'FAIL'}",
              file=err)
    err.flush()
    return line


def main(root: Path, workload: str, seed: int, seconds: float, trace: bool,
         *, t0: float) -> int:
    def info(obj):
        print(json.dumps(obj), flush=True)
    try:
        line = run_cell(root, workload, seed, seconds, trace, t0=t0,
                        info=info)
    except NoChip as e:
        print(f"bench: {e}; the benchmark runs on the chip only",
              file=sys.stderr)
        return 3
    print(json.dumps(line), flush=True)
    return 0
