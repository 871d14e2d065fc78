"""Without a TPU, or without the program beside it, the runner exits
non-zero and prints no result."""
import os
import shutil
import subprocess
import sys

import pytest

import tiny

ARGS = ["--workload", tiny.SERVE_CELL, "--seed", "2147483700", "--seconds", "1",
        "--trace", "0"]


@pytest.mark.parametrize("alone", [False, True], ids=["cpu", "bench-alone"])
def test_no_result(tmp_path, alone):
    root = tiny.ROOT
    if alone:
        shutil.copytree(tiny.BENCH, tmp_path / "bench",
                        ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copy(tiny.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
        root = tmp_path
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=root, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
