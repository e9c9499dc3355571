"""Process start-up (``repro.launch.runtime``): where compiled programs are
cached, and the refusal to fall back to the CPU."""

import os

import pytest

from conftest import run_jax_subprocess
from repro.launch import runtime

COMPILE = (
    "import os, jax, jax.numpy as jnp\n"
    "from repro.launch.runtime import enable_compile_cache\n"
    "path = enable_compile_cache()\n"
    "jax.jit(lambda x: x * 2 + 1)(jnp.arange(4.0)).block_until_ready()\n"
    "print('CACHE', path, len(os.listdir(path)))\n"
)


def test_compile_cache_goes_where_the_env_says(tmp_path):
    proc = run_jax_subprocess(
        COMPILE, env_extra={runtime.CACHE_ENV: str(tmp_path)})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert f"CACHE {tmp_path} " in proc.stdout
    assert os.listdir(tmp_path), "no compiled program was cached"


def test_compile_cache_default_is_fixed_and_git_ignored():
    proc = run_jax_subprocess(COMPILE)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert f"CACHE {runtime.DEFAULT_CACHE_DIR} " in proc.stdout
    assert os.listdir(runtime.DEFAULT_CACHE_DIR)
    root, name = os.path.split(runtime.DEFAULT_CACHE_DIR)
    with open(os.path.join(root, ".gitignore")) as f:
        assert f"{name}/" in f.read().split(), f"{name}/ is not git-ignored"


def test_require_tpu_admits_cpu_only_when_asked(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert runtime.require_tpu(allow_explicit_cpu=True).platform == "cpu"
    with pytest.raises(SystemExit, match="no TPU"):
        runtime.require_tpu(allow_explicit_cpu=False)
    monkeypatch.setenv("JAX_PLATFORMS", "")
    with pytest.raises(SystemExit, match="no TPU"):
        runtime.require_tpu(allow_explicit_cpu=True)
