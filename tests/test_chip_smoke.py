"""``chip_smoke.py``'s phases on the CPU, and the compile-cache helper.

The script itself refuses to run without a TPU; its phase functions are
imported here and run at a tiny input size (the serving phase for one
second), the four-chip phase on the forced host devices of
``conftest.py``.  That rehearses the control flow and every check of the
script without the platform check.
"""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_paper_suite_phase(smoke):
    row = smoke.phase_paper_suite(n=32)
    assert row["bit_exact"] and row["launches"] == 8
    assert sum(row["per_sm_cycles"]) > 0


def test_serving_phase(smoke):
    row = smoke.phase_serving(duration_s=1.0)
    assert row["bit_exact"] and row["launches"] > 0
    assert row["window_errors"] == row["unresolved"] == 0


def test_four_chip_phase(smoke):
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 (forced) devices")
    row = smoke.phase_four_chips(n=32)
    assert row["bit_exact"] and row["n_devices"] == 4
    assert row["launches"] == 8


def test_refuses_without_a_tpu(smoke, capsys):
    """No accelerator: a non-zero exit and nothing on stdout."""
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    assert capsys.readouterr().out == ""


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          before[1])


def test_compile_cache_env_dir_holds_every_entry(tmp_path):
    """With the variable set, compiles land there and nowhere else."""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    default = compile_cache.DEFAULT_DIR
    had = sorted(default.iterdir()) if default.exists() else None
    code = ("import json, jax, jax.numpy as jnp\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "path = enable_compile_cache()\n"
            "jax.jit(lambda x: x * 2 + 1)(jnp.arange(8)).block_until_ready()\n"
            "print(json.dumps(path))\n")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300,
                         check=True)
    assert json.loads(out.stdout.splitlines()[-1]) == str(tmp_path)
    assert any(tmp_path.iterdir())
    assert (sorted(default.iterdir()) if default.exists() else None) == had
