"""What the chip run relies on, checked on the CPU: the compile-cache
location, peaks keyed by the device JAX reports, and ``chip_smoke.py``
refusing to report a result without a TPU."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.core.roofline.hardware import TPU_V5E, chip_for_device_kind
from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_env_var_wins(monkeypatch, tmp_path,
                                    restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_cache_fallback_is_fixed_inside_checkout(monkeypatch,
                                                         restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.enable_compile_cache()
    assert first == compile_cache.enable_compile_cache()
    assert Path(first) == REPO / ".jax_cache"
    assert jax.config.jax_compilation_cache_dir == first
    ignored = (REPO / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored


def test_device_kind_lookup():
    assert chip_for_device_kind("TPU v5 lite") is TPU_V5E
    with pytest.raises(ValueError, match="no peaks recorded"):
        chip_for_device_kind("TPU v9 imaginary")
    with pytest.raises(ValueError):
        chip_for_device_kind("cpu")


def test_chip_smoke_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                         cwd=REPO, env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert '"ok"' not in out.stdout
