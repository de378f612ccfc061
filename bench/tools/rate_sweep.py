"""Find the highest task rate stage 1 sustains over the fleet, once, on
the chip: one set-up, then one open-loop window per rate and arrival
seed.

    python bench/tools/rate_sweep.py --workload fleet-select --rates 4 8 12 16 --seconds 25 --arrival-seeds 1 2

Per window it prints the tail and median latency, and the median latency
of the first and the last third of the window's tasks: where the last
third waits far longer than the first, the backlog grows and the rate
is past what the system sustains. ``--set`` overrides keys of the
traffic mix (a JSON object); ``--dump`` writes every window's arrivals,
sizes, calls and latencies to a JSON file.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--seed", type=int, default=2_100_000_001)
    ap.add_argument("--arrival-seeds", type=int, nargs="+")
    ap.add_argument("--set", default="{}")
    ap.add_argument("--dump")
    args = ap.parse_args(argv)
    spec = harness.read_json(ROOT / "BENCHMARK.json")
    cell = harness.entry(spec["workloads"], args.workload, "workload")
    cfg_e = harness.entry(spec["configs"], cell["config"], "config")
    config = harness.read_json(ROOT / cfg_e["file"])
    traffic = harness.read_json(harness.BENCH / "traffic"
                                / f"{cell['traffic']}.json")
    traffic.update(json.loads(args.set))
    kind = harness.load_module(harness.BENCH / "kinds"
                               / f"{traffic['kind']}.py", "kind")
    devices = harness.devices_for(int(cell["chips"]), require_tpu=True)
    harness.enable_cache()
    obj = kind.build(config, traffic, args.seed,
                     harness.Session(devices, trace=False))
    obj.setup()
    dump = []
    for rate in args.rates:
        traffic["rate_per_s"] = rate
        for s in args.arrival_seeds or [args.seed]:
            obj.seed = s                    # the arrivals' seed only
            res = obj.window(args.seconds)
            lat = np.asarray(obj.latencies) * 1e3
            third = max(1, lat.size // 3)
            print(json.dumps({
                "rate_per_s": rate, "arrival_seed": s,
                "tasks": res["attempted"], "failed": res["failed"],
                "p95_ms": float(np.percentile(lat, 95)), **res["e2e"],
                "p50_ms": float(np.median(lat)),
                "first_third_p50_ms": float(np.median(lat[:third])),
                "last_third_p50_ms": float(np.median(lat[-third:])),
                **res["info"]}), flush=True)
            dump.append({"rate_per_s": rate, "arrival_seed": s,
                         "times": obj.times.tolist(),
                         "fracs": obj.fracs.tolist(),
                         "calls": obj.calls, "latency_ms": lat.tolist()})
    if args.dump:
        Path(args.dump).parent.mkdir(parents=True, exist_ok=True)
        Path(args.dump).write_text(json.dumps(dump))
    return 0


if __name__ == "__main__":
    sys.exit(main())
