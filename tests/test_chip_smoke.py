"""The chip smoke run's refusals and the compile-cache placement, on CPU.

``chip_smoke.py`` must never pass on a host without a TPU: it exits
non-zero, names the missing TPU, and prints no result line.  Copied alone
into an empty directory (no ``src/``) it fails as well.
"""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.runtime import compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("alone", [False, True], ids=["repo", "alone"])
def test_chip_smoke_refuses_without_tpu(tmp_path, alone):
    cwd = ROOT
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    if alone:
        shutil.copy(ROOT / "chip_smoke.py", tmp_path)
        cwd = tmp_path
        env.pop("PYTHONPATH", None)
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    if alone:
        assert "ModuleNotFoundError" in r.stderr
    else:
        assert "no TPU" in r.stderr and "'cpu'" in r.stderr


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_default_is_repo_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.enable_compile_cache() == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")


def test_compile_cache_env_sets_nothing(monkeypatch, restore_cache_dir,
                                        tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", None)
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None
