"""kanlint battery: rule unit tests on synthetic sources + the acceptance
CLI checks.

Acceptance contract (ISSUE): ``python -m repro.analysis --check src`` exits
non-zero when seeded with a planted violation from each rule family —
missing donation (KL101), host readback (KL102), VMEM-overflow cache entry
(KL201), unpinned cache-mutating entry point (KL105) — and exits zero on
the fixed tree.  The CLI tests below run each plant through the real
subprocess entry points (``repro.analysis`` and ``repro.launch.lint``),
the unit tests drive the rule modules in-process.
"""

from __future__ import annotations

import json
import textwrap

import numpy as np
import pytest

from repro.analysis import ast_rules, findings, run_check, sharding_audit
from repro.analysis.retrace import RetraceRegistry, counting

from conftest import run_jax_subprocess


def lint(src: str, path: str = "src/repro/serve/x.py"):
    return ast_rules.lint_source(textwrap.dedent(src), path)


def rules_of(fs) -> list[str]:
    return sorted(f.rule for f in fs)


# ---------------------------------------------------------------------------
# KL101 donation
# ---------------------------------------------------------------------------


def test_kl101_flags_undonated_cache_arg():
    fs = lint("""
        import jax
        step = jax.jit(lambda params, caches: caches)
    """)
    assert rules_of(fs) == ["KL101"]
    assert "caches" in fs[0].message and "donate_argnums" in fs[0].hint


def test_kl101_satisfied_by_donation_and_by_static():
    fs = lint("""
        import jax
        step = jax.jit(lambda params, caches: caches, donate_argnums=(1,))
        other = jax.jit(lambda params, tokens: tokens)
    """)
    assert fs == []


def test_kl101_sees_through_counting_wrapper():
    """The engine wraps every jitted callable in the retrace sentinel; the
    rule must resolve through it or it goes blind on its flagship target."""
    fs = lint("""
        import jax
        from repro.analysis.retrace import counting
        reg = object()
        step = jax.jit(counting(lambda params, caches: caches, "s", reg))
    """)
    assert rules_of(fs) == ["KL101"]


def test_kl101_pragma_waives_via_run_check_machinery():
    src = textwrap.dedent("""
        import jax
        step = jax.jit(   # kanlint: ignore[KL101]
            lambda params, caches: caches)
    """)
    fs = ast_rules.lint_source(src, "src/repro/serve/x.py")
    kept = findings.apply_pragmas(
        fs, {"src/repro/serve/x.py": findings.file_pragmas(src)})
    assert kept == []


def test_kl101_decorated_def_and_named_resolution():
    fs = lint("""
        import jax

        @jax.jit
        def step(params, pool):
            return pool

        def _impl(params, kv):
            return kv

        run = jax.jit(_impl)
    """)
    assert rules_of(fs) == ["KL101", "KL101"]


# ---------------------------------------------------------------------------
# KL102 host sync
# ---------------------------------------------------------------------------


def test_kl102_flags_readback_of_jitted_result():
    fs = lint("""
        import jax
        import numpy as np

        class E:
            def __init__(self):
                self._f = jax.jit(lambda x: x)

            def go(self, x):
                y = self._f(x)
                z = np.asarray(y)
                return z
    """)
    assert rules_of(fs) == ["KL102"]
    assert "device_get" in fs[0].hint


def test_kl102_device_get_is_sanctioned_and_returns_exempt():
    fs = lint("""
        import jax
        import numpy as np

        class E:
            def __init__(self):
                self._f = jax.jit(lambda x: x)

            def batched(self, x):
                y = self._f(x)
                a, b = jax.device_get((y, y))
                return np.asarray(y)
    """)
    assert fs == []


# ---------------------------------------------------------------------------
# KL103 float64 / KL104 purity
# ---------------------------------------------------------------------------


def test_kl103_float64_on_device_path_only():
    src = """
        import jax.numpy as jnp
        x = jnp.zeros((4,), dtype=jnp.float64)
    """
    assert rules_of(lint(src, "src/repro/models/x.py")) == ["KL103"]
    assert lint(src, "benchmarks/x.py") == []


def test_kl104_impure_calls_in_traced_function():
    fs = lint("""
        import jax
        import numpy as np
        import time

        @jax.jit
        def step(x):
            t = time.time()
            r = np.random.rand()
            return x + t + r

        def host_side():
            return time.time()
    """, path="benchmarks/x.py")
    assert rules_of(fs) == ["KL104", "KL104"]


# ---------------------------------------------------------------------------
# KL105 sharding audit
# ---------------------------------------------------------------------------


def test_kl105_public_cache_entry_point_needs_shard():
    src = textwrap.dedent("""
        def decode_step(params, tok, cache, pos):
            return cache

        def _private(params, cache):
            return cache

        def shard_ok(params, cache, shard=None):
            return cache
    """)
    fs = sharding_audit.audit_source(src, "src/repro/models/x.py")
    assert rules_of(fs) == ["KL105"]
    assert "decode_step" in fs[0].message
    # non-model paths are out of audit scope
    assert sharding_audit.audit_source(src, "src/repro/serve/x.py") == []


# ---------------------------------------------------------------------------
# KL2xx kernel-config validator
# ---------------------------------------------------------------------------


def test_kl201_vmem_overflow_and_contraction():
    from repro.analysis import kernel_configs as kc

    fs = kc.validate_tiles("fused", (256, 4096, 512), 256, 512, 4096, 8,
                           "float32", "tpu", None, origin="unit test")
    assert "KL201" in rules_of(fs)


def test_kl202_alignment_tpu_only():
    from repro.analysis import kernel_configs as kc

    fs = kc.validate_tiles("fused", (12, 64, 8), 64, 64, 128, 8,
                           "float32", "tpu", None, origin="unit test")
    assert "KL202" in rules_of(fs)
    fs_cpu = kc.validate_tiles("fused", (12, 64, 8), 64, 64, 128, 8,
                               "float32", "cpu", None, origin="unit test")
    assert "KL202" not in rules_of(fs_cpu)


def test_kl202_bk_is_a_lane_dim():
    """``bk`` is the lane dim of the x block: a multiple of 128 or all of K
    (bk=16 at K=512 is what Mosaic refused for the fused kernel)."""
    from repro.analysis import kernel_configs as kc

    fs = kc.validate_tiles("fused", (16, 256, 16), 16, 512, 1024, 8,
                           "bfloat16", "tpu", None, origin="unit test")
    assert "KL202" in rules_of(fs)
    for tiles, K in (((16, 256, 256), 512), ((16, 128, 64), 64)):
        fs = kc.validate_tiles("fused", tiles, 16, K, 1024, 8,
                               "bfloat16", "tpu", None, origin="unit test")
        assert "KL202" not in rules_of(fs), fs


def test_kl203_oversized_tile():
    from repro.analysis import kernel_configs as kc

    fs = kc.validate_tiles("fused", (512, 64, 8), 64, 64, 128, 8,
                           "float32", "cpu", None, origin="unit test")
    assert "KL203" in rules_of(fs)


def test_shipped_candidate_spaces_and_defaults_are_clean(monkeypatch):
    """The repo's own autotuner tables must validate — this is the static
    half of the acceptance criterion (point the cache env at nothing so a
    developer's local cache can't leak into the assertion)."""
    from repro.analysis import kernel_configs as kc
    from repro.kernels import autotune as tune

    monkeypatch.setenv(tune.CACHE_ENV, "/nonexistent/autotune.json")
    assert kc.validate_all() == []


# ---------------------------------------------------------------------------
# retrace sentinel unit
# ---------------------------------------------------------------------------


def test_retrace_registry_counts_distinct_abstract_signatures():
    import jax
    import jax.numpy as jnp

    reg = RetraceRegistry()
    f = jax.jit(counting(lambda x: x + 1, "f", reg))
    f(jnp.zeros((2,)))
    f(jnp.ones((2,)))              # same abstract signature: cache hit
    f(jnp.zeros((3,)))             # new shape: one more program
    snap = reg.snapshot()
    assert snap["f"]["programs"] == 2
    assert snap["f"]["traces"] == 2
    assert reg.programs("f") == 2
    out = np.asarray(f(jnp.zeros((2,))))
    np.testing.assert_array_equal(out, np.ones((2,)))
    assert reg.snapshot()["f"]["programs"] == 2   # still no retrace


# ---------------------------------------------------------------------------
# baseline machinery
# ---------------------------------------------------------------------------


def test_baseline_round_trip_is_line_independent(tmp_path):
    f1 = findings.Finding("KL101", "a.py", 10, "jit without donation", "fix")
    path = tmp_path / "base.json"
    findings.save_baseline(str(path), [f1])
    base = findings.load_baseline(str(path))
    moved = findings.Finding("KL101", "a.py", 99, "jit without donation", "fix")
    new, old = findings.split_baselined([moved], base)
    assert new == [] and old == [moved]
    assert findings.load_baseline(str(tmp_path / "missing.json")) == set()


# ---------------------------------------------------------------------------
# acceptance: CLI plants (subprocess, real entry points)
# ---------------------------------------------------------------------------

PLANTS = {
    "KL101": """
        import jax
        step = jax.jit(lambda params, caches: caches)
    """,
    "KL102": """
        import jax
        import numpy as np

        class E:
            def __init__(self):
                self._f = jax.jit(lambda x: x)

            def go(self, x):
                y = self._f(x)
                z = np.asarray(y)
                return z
    """,
    "KL105": """
        def decode_step(params, tok, cache, pos):
            return cache
    """,
}


def _check_cli(paths, tmp_path, extra=None, env_extra=None, module="repro.analysis"):
    argv = ["-m", module, "--check"] if module == "repro.analysis" else ["-m", module]
    argv += list(paths) + ["--baseline", str(tmp_path / "b.json")]
    argv += list(extra or [])
    return run_jax_subprocess(argv=argv, env_extra=env_extra)


@pytest.mark.parametrize("rule", sorted(PLANTS))
def test_cli_planted_violation_fails_check(rule, tmp_path):
    sub = "models" if rule == "KL105" else "serve"
    d = tmp_path / sub
    d.mkdir()
    (d / "planted.py").write_text(textwrap.dedent(PLANTS[rule]))
    r = _check_cli([str(d)], tmp_path, extra=["--no-kernel-validator"])
    assert r.returncode == 1, r.stdout + r.stderr
    assert rule in r.stdout


def test_cli_planted_vmem_cache_entry_fails_check(tmp_path):
    """KL201 plant: a hand-edited measurement-cache winner that fits the
    grid but oversubscribes VMEM (and the contraction budget) on TPU."""
    cache = tmp_path / "autotune.json"
    key = "fused|BS=256|K=512|N=4096|M=8|dtype=float32|backend=tpu"
    cache.write_text(json.dumps({key: {"tiles": [256, 4096, 512], "us": 1.0}}))
    empty = tmp_path / "empty"
    empty.mkdir()
    r = _check_cli([str(empty)], tmp_path,
                   env_extra={"KAN_SAS_AUTOTUNE_CACHE": str(cache)})
    assert r.returncode == 1, r.stdout + r.stderr
    assert "KL201" in r.stdout


def test_cli_fixed_tree_is_clean(tmp_path):
    """The acceptance zero: the repo's own ``src`` tree has no new findings
    (the checked-in baseline is empty, so nothing hides there either)."""
    r = run_jax_subprocess(
        argv=["-m", "repro.analysis", "--check", "src"],
        env_extra={"KAN_SAS_AUTOTUNE_CACHE": str(tmp_path / "no-cache.json")},
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 new finding(s)" in r.stdout
    assert json.load(open("kanlint.baseline.json"))["findings"] == []


def test_cli_update_baseline_round_trip(tmp_path):
    d = tmp_path / "serve"
    d.mkdir()
    (d / "planted.py").write_text(textwrap.dedent(PLANTS["KL101"]))
    r1 = _check_cli([str(d)], tmp_path,
                    extra=["--no-kernel-validator", "--update-baseline"])
    assert r1.returncode == 0 and "baseline updated" in r1.stdout
    r2 = _check_cli([str(d)], tmp_path, extra=["--no-kernel-validator"])
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert "1 baselined" in r2.stdout


def test_launch_lint_cli_smoke(tmp_path):
    """Launcher front door: per-rule summary + the same exit contract."""
    d = tmp_path / "serve"
    d.mkdir()
    (d / "clean.py").write_text("x = 1\n")
    r = _check_cli([str(d)], tmp_path, extra=["--no-kernel-validator"],
                   module="repro.launch.lint")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "[lint] scanned 1 files: 0 new finding(s) (none)" in r.stdout
    (d / "planted.py").write_text(textwrap.dedent(PLANTS["KL101"]))
    r = _check_cli([str(d)], tmp_path, extra=["--no-kernel-validator"],
                   module="repro.launch.lint")
    assert r.returncode == 1
    assert "KL101=1" in r.stdout


def test_run_check_in_process_matches_cli_contract(tmp_path, monkeypatch):
    """run_check is the single engine under both CLIs."""
    from repro.kernels import autotune as tune

    monkeypatch.setenv(tune.CACHE_ENV, str(tmp_path / "no-cache.json"))
    d = tmp_path / "serve"
    d.mkdir()
    (d / "planted.py").write_text(textwrap.dedent(PLANTS["KL101"]))
    rep = run_check([str(d)], baseline_path=str(tmp_path / "b.json"))
    assert [f.rule for f in rep["new"]] == ["KL101"]
    assert rep["files"] == 1 and rep["baselined"] == []
