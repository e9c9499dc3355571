"""Kernel-config validator (kanlint KL2xx).

The autotuner (``kernels/autotune.py``) is the single source of tile
configs for every Pallas kernel — candidate spaces, the measured DEFAULTS
table, and the JSON measurement cache.  A config that oversubscribes VMEM
or violates dtype tiling alignment does not fail *here* on the CPU
container (interpret mode executes anything); it fails on the first real
TPU run, long after the PR merged.  This validator makes those configs
fail **lint** instead:

* **KL201 VMEM budget** — the per-grid-step footprint screened by
  ``autotune.tile_vmem_bytes`` must fit the 16 MiB scoped VMEM, and the
  contraction width ``bk·unit`` must respect the shared ``_MAX_CONTRACT``
  budget (DESIGN.md §2/§2a).  The footprint model counts the pipelined
  blocks, the scratch accumulator and the basis temporaries; it does not
  see Mosaic's own spills and relayouts, so it only screens.  The
  authority on what compiles is ``tests/test_tpu_compile.py``, which
  AOT-compiles the served kernels for a v5e chip.
* **KL202 dtype tiling alignment** (TPU only) — batch tiles ``bb`` must be
  sublane-aligned for the dtype (fp32 8, bf16 16, int8 32), and ``bn``
  and ``bk`` (the lane dims of the output and ``x`` blocks) must be
  multiples of 128 or the whole ``N`` / ``K``.  Only kernels compiled for
  the TPU (``autotune.TPU_KERNELS``) are checked for it.
* **KL203 grid fit** — tiles must not exceed the minimally padded problem
  dims (an oversized tile means a grid that never covers its block).

Checked surfaces: every registered kernel's candidate space and resolved
defaults over a representative problem suite (registry:
``kernels/ops.py:KERNELS``), plus every entry of the measurement cache the
environment points at (``$KAN_SAS_AUTOTUNE_CACHE``) — a hand-edited or
stale cache entry is exactly as dangerous as a bad default.
"""

from __future__ import annotations

import os

import jax.numpy as jnp

from repro.analysis.findings import Finding
from repro.kernels import autotune as tune

LANE = 128                     # last-dim tiling granularity on TPU

# Representative problems (BS, K, N): serving prefill, decode, and a small
# shape near the alignment boundaries.  M/nnz come from the kernel registry.
PROBLEM_SUITE = [(256, 512, 1024), (8, 256, 1024), (64, 64, 128)]


def _autotune_relpath() -> str:
    path = tune.__file__
    rel = os.path.relpath(path)
    return rel.replace(os.sep, "/") if not rel.startswith("..") else path


def _line_of(marker: str) -> int:
    try:
        with open(tune.__file__) as fh:
            for i, line in enumerate(fh, start=1):
                if marker in line:
                    return i
    except OSError:
        pass
    return 1


def validate_tiles(
    kernel: str, tiles: tune.Tiles, BS: int, K: int, N: int, M: int,
    dtype, backend: str, nnz: int | None, *, origin: str,
    has_base: bool = True, out_bytes: int | None = None,
    path: str | None = None, line: int = 1,
) -> list[Finding]:
    """KL201/202/203 for one concrete tiling; ``origin`` names the config
    source (candidate space / defaults / cache entry) in the message."""
    path = path or _autotune_relpath()
    bb, bn, bk = tiles
    what = (f"{origin}: {kernel} tiles {bb}x{bn}x{bk} for "
            f"BS={BS} K={K} N={N} M={M} dtype={jnp.dtype(dtype).name} "
            f"backend={backend}")
    out: list[Finding] = []
    if min(bb, bn, bk) < 1:
        out.append(Finding("KL203", path, line, f"{what}: non-positive tile",
                           "tiles must be >= 1"))
        return out
    unit = tune._contract_unit(kernel, M, nnz)
    if bk * unit > tune._MAX_CONTRACT:
        out.append(Finding(
            "KL201", path, line,
            f"{what}: contraction width bk*{unit}={bk * unit} exceeds the "
            f"shared budget {tune._MAX_CONTRACT}",
            "shrink bk or widen the budget deliberately in autotune.py",
        ))
    if backend == "tpu":
        vmem = tune.tile_vmem_bytes(kernel, tiles, M, dtype,
                                    has_base=has_base, out_bytes=out_bytes)
        if vmem > tune.VMEM_BYTES:
            out.append(Finding(
                "KL201", path, line,
                f"{what}: tile VMEM footprint {vmem} B exceeds the "
                f"{tune.VMEM_BYTES} B scoped budget",
                "shrink bb/bn/bk until blocks, scratch and temporaries fit",
            ))
        sub = tune._SUBLANE.get(jnp.dtype(dtype).name, 8)
        if bb % sub:
            out.append(Finding(
                "KL202", path, line,
                f"{what}: bb={bb} violates the {jnp.dtype(dtype).name} "
                f"sublane granularity {sub}",
                f"round bb up to a multiple of {sub}",
            ))
        for name, t, whole in (("bn", bn, N), ("bk", bk, K)):
            if t % LANE and t != whole:
                out.append(Finding(
                    "KL202", path, line,
                    f"{what}: {name}={t} is a lane dim but neither a multiple "
                    f"of {LANE} nor the whole dim ({whole})",
                    f"use a multiple of {LANE} or the whole dim",
                ))
    sub = tune._SUBLANE.get(jnp.dtype(dtype).name, 8)
    lane = LANE if backend == "tpu" else 8
    if bb > tune._round_up(BS, sub) or bn > tune._round_up(N, lane) or bk > K:
        out.append(Finding(
            "KL203", path, line,
            f"{what}: tile exceeds the padded problem "
            f"({tune._round_up(BS, sub)}, {tune._round_up(N, lane)}, {K})",
            "clamp tiles to the padded problem dims (grid blocks must "
            "cover real work)",
        ))
    return out


def _registry() -> dict:
    from repro.kernels.ops import KERNELS
    return KERNELS


def validate_candidate_spaces() -> list[Finding]:
    """Every registered kernel's candidate space over the problem suite —
    bad candidates fail lint, never compile."""
    line = _line_of("def candidate_tiles")
    out: list[Finding] = []
    for kernel, spec in _registry().items():
        backends = ("tpu", "cpu") if kernel in tune.TPU_KERNELS else ("cpu",)
        for dtype in spec["dtypes"]:
            for backend in backends:
                for BS, K, N in PROBLEM_SUITE:
                    cands = tune.candidate_tiles(
                        kernel, BS, K, N, spec["M"], dtype, backend,
                        nnz=spec.get("nnz"),
                    )
                    for tiles in cands:
                        out.extend(validate_tiles(
                            kernel, tiles, BS, K, N, spec["M"], dtype,
                            backend, spec.get("nnz"),
                            origin="candidate space",
                            has_base=spec.get("base", True),
                            out_bytes=spec.get("out_bytes"), line=line,
                        ))
    return out


def validate_defaults() -> list[Finding]:
    """The DEFAULTS table as ``get_tiles`` actually resolves it (the
    problem-clamp is part of the contract being validated — ONE definition,
    ``autotune.clamp_default``)."""
    line = _line_of("DEFAULTS: ")
    out: list[Finding] = []
    reg = _registry()
    for (kernel, backend) in tune.DEFAULTS:
        spec = reg.get(kernel)
        if spec is None:
            out.append(Finding(
                "KL204", _autotune_relpath(), line,
                f"kernel '{kernel}' has DEFAULTS but is not registered in "
                f"kernels/ops.py:KERNELS",
                "add a registry entry (dtypes, M, base, out_bytes) so its "
                "configs get validated",
            ))
            continue
        for dtype in spec["dtypes"]:
            for BS, K, N in PROBLEM_SUITE:
                tiles = tune.clamp_default(kernel, backend, BS, K, N, dtype)
                out.extend(validate_tiles(
                    kernel, tiles, BS, K, N, spec["M"], dtype, backend,
                    spec.get("nnz"), origin="DEFAULTS",
                    has_base=spec.get("base", True),
                    out_bytes=spec.get("out_bytes"), line=line,
                ))
    return out


def validate_measurement_cache() -> list[Finding]:
    """Every entry of the measurement cache ``$KAN_SAS_AUTOTUNE_CACHE``
    names (none when unset): a hand-edited or stale winner reaches
    ``ops.py`` with zero compile-time checks, so it gets the same static
    validation as the in-repo tables."""
    cache = tune._load_cache()
    if not cache:
        return []
    path = os.path.relpath(tune.cache_path()).replace(os.sep, "/")
    reg = _registry()
    out: list[Finding] = []
    for key, entry in cache.items():
        tiles = tune._valid_tiles(entry)
        if tiles is None:
            out.append(Finding(
                "KL203", path, 1,
                f"cache entry {key!r}: malformed tiles {entry!r}",
                "delete the entry; get_tiles would ignore it anyway",
            ))
            continue
        try:
            kernel, rest = key.split("|", 1)
            kv = dict(p.split("=", 1) for p in rest.split("|"))
            BS, K, N, M = (int(kv[k]) for k in ("BS", "K", "N", "M"))
            dtype, backend = kv["dtype"], kv["backend"]
        except (ValueError, KeyError):
            out.append(Finding(
                "KL203", path, 1,
                f"cache entry {key!r}: unparseable problem key",
                "keys come from autotune.problem_key; delete foreign entries",
            ))
            continue
        spec = reg.get(kernel, {})
        out.extend(validate_tiles(
            kernel, tiles, BS, K, N, M, dtype, backend, spec.get("nnz"),
            origin="measurement cache", has_base=spec.get("base", True),
            out_bytes=spec.get("out_bytes"), path=path,
        ))
    return out


def validate_all() -> list[Finding]:
    return (
        validate_candidate_spaces()
        + validate_defaults()
        + validate_measurement_cache()
    )
