"""The command refuses to run where it should: with no TPU, and in a
directory that holds only the benchmark's own files."""
import json
import os
import shutil
import subprocess
import sys

from bench.tests.small import ROOT

ARGS = ["--workload", "fleet-select", "--seed", str(2**31 + 5),
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(stdout):
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert "correct" not in obj


def test_no_tpu_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    _no_result(p.stdout)
    assert "TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    _no_result(p.stdout)
