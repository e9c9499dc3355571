"""Test-suite bootstrap.

* :func:`run_jax_subprocess` (also a fixture, ``jax_subprocess``) — run a
  python snippet or argv in a SUBPROCESS with a clean jax environment:
  ``JAX_PLATFORMS=cpu`` always (without it jax probes the TPU runtime on
  TPU-image hosts and spends minutes in GCP-metadata retries) and, for
  ``devices > 1``, ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
  set BEFORE jax initialises — the only way to fake a multi-device host.
  Sharding/dist tests use this instead of hand-rolling env plumbing.
* :func:`assert_trace_budget` — compile-count budgets for an Engine.
* :func:`assert_logits_match` / :func:`assert_trees_match` — comparisons
  between two differently shaped fp32 programs (paged vs dense, verify
  window vs sequential decode).  XLA promises no reduction order across
  shapes, so they agree to a stated tolerance, not bit for bit.
"""

from __future__ import annotations

import subprocess
import sys

import numpy as np
import pytest

# Two fp32 programs that reduce in different orders differ by a few ulps
# (2^-23) per reduction, compounding over the layer stack; observed gaps
# are ~1e-5 of the logit scale.  2^-12 of the scale (max(1, max|ref|)) is
# ~2000 ulps of headroom and still 16x below one bf16 rounding (2^-8).
FP32_REORDER_TOL = 2.0 ** -12


def assert_logits_match(ref, got, err_msg: str = "") -> None:
    """``got`` within :data:`FP32_REORDER_TOL` of ``ref``, and the same
    argmax wherever ``ref``'s top-2 margin exceeds twice the tolerance."""
    ref = np.asarray(ref, np.float64)
    got = np.asarray(got, np.float64)
    tol = FP32_REORDER_TOL * max(1.0, float(np.abs(ref).max()))
    np.testing.assert_allclose(got, ref, rtol=0, atol=tol, err_msg=err_msg)
    top2 = np.sort(ref, axis=-1)[..., -2:]
    decided = (top2[..., 1] - top2[..., 0]) > 2 * tol
    same = ref.argmax(-1) == got.argmax(-1)
    assert same[decided].all(), f"argmax differs at a decided position {err_msg}"


def assert_trees_match(ref_tree, got_tree) -> None:
    """Cache trees of two programs: float leaves within
    :data:`FP32_REORDER_TOL` of each leaf's scale; integer (int8-quantized)
    leaves within one quantization step, since a value that lands on a
    rounding boundary may round either way."""
    import jax

    for a, b in zip(jax.tree.leaves(ref_tree), jax.tree.leaves(got_tree)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape and a.dtype == b.dtype
        if np.issubdtype(a.dtype, np.integer):
            diff = np.abs(a.astype(np.int64) - b.astype(np.int64))
            assert diff.max(initial=0) <= 1
        else:
            a64 = a.astype(np.float64)
            tol = FP32_REORDER_TOL * max(1.0, float(np.abs(a64).max(initial=0)))
            np.testing.assert_allclose(b.astype(np.float64), a64, rtol=0,
                                       atol=tol)


def run_jax_subprocess(
    code: str | None = None,
    argv: list[str] | None = None,
    devices: int = 1,
    timeout: int = 900,
    env_extra: dict | None = None,
) -> subprocess.CompletedProcess:
    """Run ``python -c code`` (or ``python *argv``) with the repo on
    PYTHONPATH, jax forced onto CPU, and optionally ``devices`` fake host
    devices.  Returns the CompletedProcess (caller asserts on
    returncode/stdout)."""
    assert (code is None) != (argv is None), "pass exactly one of code/argv"
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"}
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    if env_extra:
        env.update(env_extra)
    cmd = [sys.executable] + (["-c", code] if code is not None else list(argv))
    return subprocess.run(
        cmd, capture_output=True, text=True, timeout=timeout, env=env, cwd=".",
    )


@pytest.fixture
def jax_subprocess():
    """Fixture handle on :func:`run_jax_subprocess` (multi-device CPU
    subprocess runner) for tests that prefer injection over import."""
    return run_jax_subprocess


@pytest.fixture
def assert_trace_budget():
    """Assert an Engine's retrace sentinel matches a documented program
    budget: ``check(engine, {"decode_chunk": 1, ...})``.  A *program* is a
    distinct abstract signature traced for that jitted entry point
    (``repro.analysis.retrace``); budgets pin the compile counts the serving
    PRs promised (DESIGN.md invariant catalogue).  Names absent from the
    budget are unconstrained; names in the budget but never traced count 0.
    """
    def check(engine, budget: dict) -> None:
        snap = engine.compiles.snapshot()
        got = {n: snap.get(n, {}).get("programs", 0) for n in budget}
        assert got == budget, (
            f"trace budget violated: expected {budget}, got {got}; "
            f"full snapshot: {snap}"
        )
    return check
