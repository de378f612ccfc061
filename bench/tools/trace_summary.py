"""One traced run of a cell, with a summary of its trace: which planes
and lines it holds, the most frequent and longest event names per line,
and the stats of a few events of each kernel. Writes the summary, and a
short slice of the trace's events that tests can replay, under
``--out``.

    python bench/tools/trace_summary.py --workload cifar-dense --seed 5 --seconds 3 --out chiprun_out
"""
from __future__ import annotations

import argparse
import collections
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, trace  # noqa: E402


def summarize(events) -> dict:
    lines = collections.defaultdict(list)
    for e in events:
        lines[(e.plane, e.line)].append(e)
    out = {}
    for (plane, line), evs in sorted(lines.items()):
        by = collections.Counter(e.name for e in evs)
        dur = collections.Counter()
        for e in evs:
            dur[e.name] += e.dur_ns
        out[f"{plane} | {line}"] = {
            "events": len(evs),
            "first_ns": min(e.start_ns for e in evs),
            "last_ns": max(e.end_ns for e in evs),
            "top_count": by.most_common(12),
            "top_time_ns": dur.most_common(12),
            "samples": [{"name": e.name, "start": e.start_ns,
                         "dur": e.dur_ns, "stats": e.stats}
                        for e in evs[:3]],
        }
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", default="chiprun_out")
    ap.add_argument("--slice", type=int, default=400)
    args = ap.parse_args(argv)
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    kept = {}

    def on_trace(events):
        kept["events"] = events
    line = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                            True, t0=time.perf_counter(),
                            info=lambda o: print(json.dumps(o)),
                            on_trace=on_trace)
    print(json.dumps(line), flush=True)
    events = kept["events"]
    with open(out / f"trace_summary.{args.workload}.json", "w") as f:
        json.dump(summarize(events), f, indent=1, default=str)
    lo, hi = trace.window(events)
    dev = [e for e in events if e.plane.startswith(trace.DEVICE_PREFIX)
           and lo <= e.start_ns < hi]
    mid = (lo + hi) / 2
    dev.sort(key=lambda e: abs(e.start_ns - mid))
    sl = dev[: args.slice]
    span = [e for e in trace.spans(events)
            if e.name != trace.WINDOW_SPAN and any(
                e.start_ns <= d.start_ns < e.end_ns for d in sl[:50])]
    win = [e for e in events if e.name == trace.WINDOW_SPAN]
    trace.dump(win + span + sorted(sl, key=lambda e: e.start_ns),
               str(out / f"trace_slice.{args.workload}.json"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
