"""Benchmark harness: one module per paper table/figure (+ roofline).

``python -m benchmarks.run [--only NAME]`` prints ``name,value,note`` CSV.
"""
from __future__ import annotations

import argparse
import importlib
import sys
import time

from repro.launch.cache import enable_compile_cache

BENCHES = [
    "bench_selection",        # Tables II/III
    "bench_selection_time",   # Fig. 3
    "bench_policies",         # ISSUE-5 pluggable-policy comparison
    "bench_subsets",          # Fig. 4 + fairness §VII
    "bench_training",         # Figs. 5/6 (reduced)
    "bench_round_time",       # ISSUE-2 device-resident round data plane
    "bench_service_multitask",  # ISSUE-3 multi-tenant service lifecycle
    "bench_faults",           # ISSUE-7 fault injection + mitigation
    "bench_workload",         # ISSUE-8 online workload harness (SLA)
    "bench_compression",      # ISSUE-9 compressed update plane (bytes/acc)
    "bench_placement",        # ISSUE-10 multi-device tenant placement
    "bench_roofline",         # §Roofline (from dry-run artifacts)
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names (default: all)")
    ap.add_argument("--skip", default=None)
    args = ap.parse_args()
    names = args.only.split(",") if args.only else list(BENCHES)
    if args.skip:
        names = [n for n in names if n not in set(args.skip.split(","))]

    enable_compile_cache()
    print("name,value,note")
    failures = 0
    for name in names:
        mod = importlib.import_module(f"benchmarks.{name}")
        t0 = time.time()

        def report(metric, value, note=""):
            print(f"{name}.{metric},{value},{note}", flush=True)

        try:
            mod.run(report)
            report("elapsed_s", round(time.time() - t0, 2))
        except Exception as e:  # keep the harness going
            failures += 1
            report("ERROR", 0.0, f"{type(e).__name__}: {e}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
