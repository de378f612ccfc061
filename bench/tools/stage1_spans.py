"""One traced run of a stage-1 cell, as ``bench/run.py --trace 1`` makes
it, and where its time goes by the program's ``stage1.*`` spans: per
span its count, self time and the device's idle time inside it, the
idle time outside them, the frontier sizes asked for, and two checks
of the spans against the trace (the share of ``segmented_topk`` device
time that starts inside a ``stage1.frontier`` span; the share of the
``bench.select`` time the ``stage1.batch`` spans cover). Prints the
result line, writes the breakdown to ``<out>/stage1_spans.<seed>.json``
and the first few calls of the window, with their spans and device
operations, to ``<out>/stage1_slice.<seed>.json`` (``bench.trace.dump``).

    python bench/tools/stage1_spans.py --workload fleet-select --seed 5 --seconds 50 --out stage1_out
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

# as bench/run.py: the host's BLAS on one thread, before numpy loads
for _var in ("OPENBLAS_NUM_THREADS", "OMP_NUM_THREADS", "MKL_NUM_THREADS"):
    os.environ[_var] = "1"

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, kernels, spans, trace  # noqa: E402


def breakdown(events) -> dict:
    """Where the window's stage-1 time and device idle time go, by span."""
    lo, hi = trace.window(events)
    s = spans.stage1(events)
    parts = spans.self_parts(s)
    tasks = spans.named(s, "stage1.task")
    n = max(len(tasks), 1)
    has_device = bool(trace.device_planes(events))
    gaps = trace.idle_gaps(events) if has_device else []
    idle_total = sum(e - b for b, e in gaps)
    rows = {}
    for name in sorted({e.name for e in s}):
        evs = spans.named(s, name)
        own = spans.self_ns(parts, name)
        idle = spans.idle_ns(events, parts, name)
        rows[name] = {
            "count": len(evs),
            "ms_per_task": 1e-6 * sum(e.dur_ns for e in evs) / n,
            "self_ms_per_task": 1e-6 * own / n,
            "idle_ms_per_task": None if idle is None else 1e-6 * idle / n,
            "idle_share_of_window_idle":
                None if idle is None or not idle_total
                else idle / idle_total}
    bench_sel = [e for e in trace.spans(events, "bench.select")
                 if lo <= e.start_ns < hi]
    bench_wait = trace.union((e.start_ns, e.end_ns) for e in
                             trace.spans(events, "bench.wait")
                             if lo <= e.start_ns < hi)
    in_stage1 = trace.union(iv for _, p in parts for iv in p)
    batch_ns = sum(e.dur_ns for e in spans.named(s, "stage1.batch"))
    own_ns = spans.self_ns(parts, "stage1.batch") \
        + spans.self_ns(parts, "stage1.task")
    topk = [e for e in trace.device_ops(events)
            if lo <= e.start_ns < hi
            and kernels.is_kernel(e, kernels.SEGMENTED_TOPK)]
    fronts = spans.named(s, "stage1.frontier")
    # each frontier pass dispatches one masked-ratio program first: where
    # it shows on the device before the span opens, the device clock
    # runs ahead of the host's by at least that much
    ratios = sorted((e for e in trace.device_ops(events,
                                                 line=trace.MODULES_LINE)
                     if lo <= e.start_ns < hi
                     and e.name.startswith("jit__masked_ratio")),
                    key=lambda e: e.start_ns)
    ahead = sorted(1e-6 * (f.start_ns - r.start_ns) for f, r in
                   zip(sorted(fronts, key=lambda e: e.start_ns), ratios))
    last = max((e.end_ns for e in bench_sel), default=hi)
    return {
        "tasks": len(tasks),
        # as select_tasks_per_s counts them, on the trace's clock
        "tasks_per_s": len(tasks) / (1e-9 * (last - lo)),
        "window_s": 1e-9 * (hi - lo),
        "idle_s": 1e-9 * idle_total if has_device else None,
        "spans": rows,
        "idle_s_in_stage1_spans": 1e-9 * spans.overlap_ns(gaps, in_stage1),
        "idle_s_in_bench_wait": 1e-9 * spans.overlap_ns(gaps, bench_wait),
        "bench_select_ms_per_task":
            1e-6 * sum(e.dur_ns for e in bench_sel) / n,
        "batch_over_bench_select":
            batch_ns / max(sum(e.dur_ns for e in bench_sel), 1),
        "own_time_share_of_batch": own_ns / max(batch_ns, 1),
        "device_ahead_ms": {
            "pairs": len(ahead), "of": len(fronts),
            "min": ahead[0] if ahead else None,
            "median": ahead[len(ahead) // 2] if ahead else None,
            "max": ahead[-1] if ahead else None},
        "topk_time_starting_in_frontier": spans.share_starting_inside(
            topk, spans.intervals(parts, "stage1.frontier")),
        "frontier_F": sorted(collections.Counter(
            e.stats.get("F") for e in fronts).items()),
        "passes": sorted(collections.Counter(
            e.stats.get("passes") for e in tasks).items()),
        "paths": sorted(collections.Counter(
            e.stats.get("path") for e in tasks).items()),
        "picks": sum(e.stats.get("picks", 0) for e in tasks),
        "candidates": sum(e.stats.get("candidates", 0) for e in fronts),
        "slots": sum(e.stats.get("shards", 0) * e.stats.get("F", 0)
                     for e in fronts),
    }


def first_calls(events, calls: int) -> list:
    """The window's span, and the first ``calls`` ``bench.select`` spans
    with every host span and device operation that starts inside them."""
    lo, hi = trace.window(events)
    sel = sorted((e for e in trace.spans(events, "bench.select")
                  if lo <= e.start_ns < hi), key=lambda e: e.start_ns)
    sel = sel[:calls]
    if not sel:
        return []
    a, b = sel[0].start_ns, sel[-1].end_ns
    keep = [e for e in events if e.name == trace.WINDOW_SPAN]
    keep += [e for e in trace.spans(events, "bench.") + spans.stage1(events)
             if e.name != trace.WINDOW_SPAN and a <= e.start_ns < b]
    keep += [e for e in events if e.plane.startswith(trace.DEVICE_PREFIX)
             and e.line in (trace.OPS_LINE, trace.MODULES_LINE)
             and a <= e.start_ns < b]
    return keep


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default="stage1_out")
    ap.add_argument("--calls", type=int, default=2)
    args = ap.parse_args(argv)
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    kept = {}
    line = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                            True, t0=T0,
                            info=lambda o: print(json.dumps(o)),
                            on_trace=lambda ev: kept.update(events=ev))
    print(json.dumps(line), flush=True)
    events = kept["events"]
    with open(out / f"stage1_spans.{args.seed}.json", "w") as f:
        json.dump(breakdown(events), f, indent=1)
    trace.dump(first_calls(events, args.calls),
               str(out / f"stage1_slice.{args.seed}.json"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
