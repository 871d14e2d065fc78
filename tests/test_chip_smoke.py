"""``chip_smoke.py``: the rehearsal runs end to end, and nothing but a TPU
chip ever gets a result out of it; the compile-cache helper it shares
with the launchers keeps its cache where the next run finds it."""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_rehearsal_runs_both_phases(monkeypatch, capsys):
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    smoke = _load_smoke()
    assert smoke.main(["--rehearse", "--requests", "5"]) == 0
    out = capsys.readouterr().out
    assert "serve phase:" in out and "train phase:" in out
    assert '"ok"' not in out  # only a chip run reports a result


@pytest.mark.parametrize("alone", [False, True], ids=["cpu", "script-alone"])
def test_no_result_without_a_chip(tmp_path, alone):
    """On the CPU the script refuses before any work; copied out of the
    checkout it cannot even import the program.  Either way: a non-zero
    exit and no result line."""
    script = ROOT / "chip_smoke.py"
    if alone:
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, str(script)], cwd=script.parent, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_compile_cache_dir(monkeypatch):
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.enable() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == prev  # JAX's own read
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
