"""Process start-up shared by the launchers and ``chip_smoke.py``.

* :func:`enable_compile_cache` — JAX's persistent compilation cache, placed
  from outside or at a fixed path.  When ``$JAX_COMPILATION_CACHE_DIR`` is
  set, JAX already reads it and no directory is set here.  Otherwise the
  cache lives in ``.jax_cache/`` at the root of the checkout (git-ignored):
  a fixed path, because the path is part of what a later run must find.
* :func:`require_tpu` — the device check: a run that finds no TPU stops
  instead of falling through to the CPU and interpret-mode kernels.
"""

from __future__ import annotations

import os

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..", ".jax_cache")
)


def enable_compile_cache() -> str:
    """Cache every compiled program; call before the first compile.
    Returns the cache directory."""
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def require_tpu(*, allow_explicit_cpu: bool):
    """The first device, which must be a TPU.

    ``allow_explicit_cpu`` admits the CPU only when ``JAX_PLATFORMS=cpu``
    asks for it (the test suite's interpret-mode runs); a CPU that JAX fell
    back to on its own is refused either way.
    """
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        return dev
    if allow_explicit_cpu and os.environ.get("JAX_PLATFORMS") == "cpu":
        return dev
    raise SystemExit(
        f"no TPU: JAX found {dev.platform} ({dev.device_kind}); set "
        f"JAX_PLATFORMS=cpu to run on the CPU with interpret-mode kernels"
    )
