"""The entry refuses to run where it cannot measure: without a TPU, and in a
directory that holds only the benchmark's own files."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

from slobench import spec

CELL = spec.load_benchmark()["workloads"][0]["name"]
ARGS = ["-m", "slobench.run", "--workload", CELL,
        "--seed", str(2 ** 31 + 3), "--seconds", "1", "--trace", "0"]


def run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def no_result(proc) -> bool:
    last = (proc.stdout.strip().splitlines() or [""])[-1]
    try:
        return "correct" not in json.loads(last)
    except ValueError:
        return True


def test_refuses_without_a_tpu():
    proc = run(spec.CHECKOUT)
    assert proc.returncode != 0
    assert no_result(proc)
    assert "no TPU" in proc.stderr


def test_refuses_outside_a_checkout(tmp_path):
    shutil.copy(spec.CHECKOUT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.HERE, tmp_path / "slobench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = run(tmp_path)
    assert proc.returncode != 0
    assert no_result(proc)
