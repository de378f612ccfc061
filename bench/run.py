"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. Exits non-zero, with no result line,
where JAX finds no TPU or fewer chips than the cell asks for.
"""
import time

T0 = time.perf_counter()          # set-up is timed from process start

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

# load from one process with few threads: the host's BLAS works on one
# thread, set before numpy is first imported
for _var in ("OPENBLAS_NUM_THREADS", "OMP_NUM_THREADS", "MKL_NUM_THREADS"):
    os.environ[_var] = "1"
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    return harness.main(ROOT, args.workload, args.seed, args.seconds,
                        bool(args.trace), t0=T0)


if __name__ == "__main__":
    sys.exit(main())
