"""The readings the correctness limits are set from, on the chip at the
cells' own sizes, many seeds in one process:

    python bench/tools/readings.py --workload cifar-dense --seeds 12 --control 3
    python bench/tools/readings.py --workload fleet-select --seeds 12 --control 3

Per seed it prints one JSON line, as the traffic kind's ``readings``
hook makes it: the program against the plain reference (the lower
readings), and on the first ``--control`` seeds the control (the
reference in the next precision down, in the program's place) and any
fault the kind plants, through the same comparison. ``--seconds`` is the
window a kind that needs one serves at the cell's own load.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_200_000_000)
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)
    spec = harness.read_json(ROOT / "BENCHMARK.json")
    cell = harness.entry(spec["workloads"], args.workload, "workload")
    cfg_e = harness.entry(spec["configs"], cell["config"], "config")
    config = harness.read_json(ROOT / cfg_e["file"])
    traffic = harness.read_json(harness.BENCH / "traffic"
                                / f"{cell['traffic']}.json")
    kind = harness.load_module(harness.BENCH / "kinds"
                               / f"{traffic['kind']}.py", "kind")
    devices = harness.devices_for(int(cell["chips"]), require_tpu=True)
    harness.enable_cache()
    session = harness.Session(devices, trace=False)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    print(json.dumps({"device": devices[0].device_kind,
                      "seeds": seeds}), flush=True)
    for row in kind.readings(config, traffic, seeds, args.control, session,
                             args.seconds):
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
