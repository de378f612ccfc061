"""The benchmark of the FL service: one command runs one cell once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name from ``BENCHMARK.json``: the
configuration file, the traffic mix under ``bench/traffic/``, the traffic
kind that the mix names under ``bench/kinds/``, and one reader per
per-layer metric under ``bench/metrics/``.
"""
