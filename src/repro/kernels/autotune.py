"""Tile autotuner for the KAN Pallas kernels (DESIGN.md §2).

The fused kernels tile the ``(BS, N, K)`` iteration space with
``(bb, bn, bk)`` blocks; the best tiling depends on the problem shape, the
dtype (sublane granularity), the backend — and the *kernel*: the dense-band
kernels (``fused``/``int8``) contract ``bk·M`` wide, the sparse N:M kernels
(``sparse``/``sparse_int8``) only ``bk·(P+1)`` wide, so their legal/useful
``bk`` range is ``M/(P+1)×`` larger under the same contraction-width budget.
Rather than hard-coding tiles everywhere, :func:`get_tiles` resolves
them in three steps:

1. the **measurement cache** — a JSON file of winners recorded by
   :func:`autotune`, read only when ``$KAN_SAS_AUTOTUNE_CACHE`` names it
   (unset: tiles come from committed code only, so two runs on one machine
   pick the same tiles);
2. the **in-repo defaults table**;
3. a **per-kernel shape heuristic** — clamp friendly tiles to the problem
   size so small problems don't pay for padding to 128.

Only the fused kernel is compiled for the TPU (``TPU_KERNELS``).  Its TPU
tiles put ``bk`` and ``bn`` on the lanes — each a multiple of 128 or the
whole ``K``/``N`` — and ``bb`` on the sublanes of the input dtype; every
tiling this module emits for it at kanformer-100m widths is AOT-compiled
for a v5e chip by ``tests/test_tpu_compile.py``, which is the authority
on what compiles (:func:`tile_vmem_bytes` is a screening model).  The
sparse and int8 kernels run in interpret mode only and have no TPU tiles.

:func:`autotune` times every candidate from :func:`candidate_tiles` with
the real kernel (interpret mode on CPU, compiled on TPU), records the
winner under the problem key when the cache file is named, and returns a
report row that ``benchmarks/kan_paths.py`` embeds in
``BENCH_kan_paths.json``.

Cache robustness: the JSON is written atomically (unique temp file +
``os.replace``) so concurrent processes — pytest-xdist, two engines warming
up — can race without corrupting it; readers get *copies* of the memoised
cache (mutating a result cannot poison later reads); a corrupt or
wrong-schema cache file silently falls back to defaults.
"""

from __future__ import annotations

import copy
import json
import os
import tempfile
import time
from typing import Callable

import jax

Tiles = tuple[int, int, int]

CACHE_ENV = "KAN_SAS_AUTOTUNE_CACHE"

# Sublane granularity per dtype (TPU tiling constraint: second-to-last dim).
_SUBLANE = {"float32": 8, "bfloat16": 16, "int8": 32, "int32": 8}

# Contraction-width budget per grid step for the interpret-mode kernels
# that lay the basis on a trailing axis (int8 contracts bk·M wide, sparse
# bk·nnz wide; the fused kernel contracts M separate bk-wide matmuls).
_MAX_CONTRACT = 1024

# Kernels compiled for the TPU; the rest run in interpret mode only.
TPU_KERNELS = ("fused",)

# Scoped VMEM a kernel may use on a v5e core without raising the limit.
VMEM_BYTES = 16 * 2**20

# (kernel, backend) -> (bb, bn, bk).  The TPU entry puts bk/bn on the
# lanes (multiples of 128); it and every candidate below compile for v5e
# at kanformer-100m widths (tests/test_tpu_compile.py).  The CPU entries
# were picked from interpret-mode timings in this container and only
# bound how many grid steps the interpreter walks.
DEFAULTS: dict[tuple[str, str], Tiles] = {
    ("fused", "tpu"): (128, 256, 256),
    ("fused", "cpu"): (64, 64, 8),
    ("int8", "cpu"): (64, 64, 8),
    ("sparse", "cpu"): (8, 256, 256),
    ("sparse_int8", "cpu"): (8, 256, 256),
}

# TPU candidate (bb, bn, bk) for the fused kernel, before clamping to the
# problem and screening with tile_vmem_bytes.
_TPU_CANDIDATES: tuple[Tiles, ...] = (
    (128, 256, 256), (256, 256, 256), (128, 512, 256), (256, 512, 256),
    (128, 256, 512),
)


def is_sparse_kernel(kernel: str) -> bool:
    return kernel.startswith("sparse")


def cache_path() -> str | None:
    """The measurement cache file, or None when ``$KAN_SAS_AUTOTUNE_CACHE``
    is unset (then nothing is read or written)."""
    return os.environ.get(CACHE_ENV) or None


# (path, mtime_ns) -> parsed cache; avoids a JSON parse per kernel call.
_mem_cache: dict[tuple[str, int], dict] = {}


def _load_cache() -> dict:
    """Parsed cache contents; always a fresh copy (callers may mutate)."""
    path = cache_path()
    if path is None:
        return {}
    try:
        mtime = os.stat(path).st_mtime_ns
    except OSError:
        return {}
    key = (path, mtime)
    if key not in _mem_cache:
        try:
            with open(path) as f:
                parsed = json.load(f)
        except (OSError, ValueError):
            return {}  # unreadable / corrupt (e.g. torn write): use defaults
        if not isinstance(parsed, dict):
            return {}
        _mem_cache.clear()     # at most one live entry
        _mem_cache[key] = parsed
    return copy.deepcopy(_mem_cache[key])


def _save_cache(cache: dict) -> None:
    """Atomic write: unique temp file in the target dir + ``os.replace``.

    A fixed temp name would let two concurrent writers interleave into the
    same file; ``mkstemp`` gives each writer its own, and ``os.replace`` is
    atomic on POSIX, so readers only ever see a complete JSON document.
    """
    path = cache_path()
    if path is None:
        return
    try:
        d = os.path.dirname(path) or "."
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=d, prefix=".autotune-", suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(cache, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
    except OSError:
        pass  # read-only FS: autotuning still works, it just doesn't persist


def problem_key(
    kernel: str, BS: int, K: int, N: int, M: int, dtype, backend: str
) -> str:
    return f"{kernel}|BS={BS}|K={K}|N={N}|M={M}|dtype={jax.numpy.dtype(dtype).name}|backend={backend}"


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _contract_unit(kernel: str, M: int, nnz: int | None) -> int:
    """Per-bk contraction width of one matmul: 1 for the fused kernel (M
    separate bk-wide matmuls), M for the int8 dense band, nnz = P+1 for
    the sparse kernels (default M//2 when the caller can't supply it)."""
    if is_sparse_kernel(kernel):
        return max(1, nnz if nnz is not None else M // 2)
    if kernel == "fused":
        return 1
    return M


def _check_backend(kernel: str, backend: str) -> None:
    if backend == "tpu" and kernel not in TPU_KERNELS:
        raise ValueError(
            f"kernel {kernel!r} is not compiled for the TPU (interpret mode "
            f"only); TPU kernels: {TPU_KERNELS}"
        )


def tile_vmem_bytes(
    kernel: str, tiles: Tiles, M: int, dtype, *,
    has_base: bool = True, out_bytes: int | None = None,
) -> int:
    """Screening model of one grid step's VMEM for a (bb, bn, bk) tiling.

    Counts the double-buffered pipelined blocks — x ``(bb, bk)``, coeff
    ``(bk, M, bn)`` with ``M`` padded to the sublane tile, optional base
    ``(bk, bn)``, out ``(bb, bn)`` — the fp32 scratch accumulator (the
    sparse and int8 kernels carry a second one), and for the fused kernel
    the ``(bb, bk)`` fp32 basis temporaries (``M`` band planes plus about
    12 for the Cox-de Boor triangle at P=3).  It does not see Mosaic's own
    spills or relayouts: the compile tests are the authority, and this
    model only keeps candidates the compiler would obviously refuse out of
    the space.
    """
    bb, bn, bk = tiles
    e = jax.numpy.dtype(dtype).itemsize
    oe = out_bytes if out_bytes is not None else e
    m_pad = _round_up(M, _SUBLANE.get(jax.numpy.dtype(dtype).name, 8))
    blocks = bb * bk * e + bk * m_pad * bn * e + bb * bn * oe
    if has_base:
        blocks += bk * bn * e
    scratch = bb * bn * 4
    if is_sparse_kernel(kernel) or "int8" in kernel:
        scratch += bb * bn * 4
    temps = (M + 12) * bb * bk * 4 if kernel == "fused" else 0
    return 2 * blocks + scratch + temps


def _tpu_clamp(tiles: Tiles, BS: int, K: int, N: int, dtype) -> Tiles:
    """TPU tiles clamped to one problem: ``bb`` stays sublane-aligned,
    ``bn``/``bk`` stay 128-multiples or become the whole ``N``/``K``."""
    sub = _SUBLANE.get(jax.numpy.dtype(dtype).name, 8)
    bb, bn, bk = tiles
    return _round_up(min(bb, _round_up(BS, sub)), sub), min(bn, N), min(bk, K)


def _heuristic(
    kernel: str, BS: int, K: int, N: int, M: int, dtype,
    nnz: int | None = None,
) -> Tiles:
    """Interpret-mode tiles clamped to the problem (padding-aware)."""
    sub = _SUBLANE.get(jax.numpy.dtype(dtype).name, 8)
    unit = _contract_unit(kernel, M, nnz)
    if is_sparse_kernel(kernel):
        # Decode-shaped: small batch tile; bk as wide as the contraction
        # budget allows (the narrow bk·(P+1) contraction is the whole point).
        bb = min(32, _round_up(BS, sub))
        bn = min(256, _round_up(N, 32))
        bk = max(1, min(K, _MAX_CONTRACT // unit))
        return bb, bn, bk
    bb = min(128, _round_up(BS, sub))
    bn = min(128, _round_up(N, 32))
    # contraction width bk*M near 128-512 keeps the work per step
    # moderate; clamp to K so tiny layers use one step.
    bk = max(1, min(K, max(1, 256 // M)))
    return bb, bn, bk


def candidate_tiles(
    kernel: str, BS: int, K: int, N: int, M: int, dtype=jax.numpy.float32,
    backend: str | None = None, nnz: int | None = None,
) -> list[Tiles]:
    """Deduplicated candidate (bb, bn, bk) tilings for one problem.

    TPU (fused only): ``_TPU_CANDIDATES`` clamped to the problem and
    screened by :func:`tile_vmem_bytes`.  Interpret mode: the ``bk`` range
    is capped by the contraction-width budget (``bk·unit <= 1024``), which
    is what lets the sparse kernels trade their narrower contraction for
    fewer, wider grid steps.
    """
    backend = backend or jax.default_backend()
    _check_backend(kernel, backend)
    if backend == "tpu":
        out: list[Tiles] = []
        for t in _TPU_CANDIDATES:
            t = _tpu_clamp(t, BS, K, N, dtype)
            if t not in out and tile_vmem_bytes(kernel, t, M, dtype) <= VMEM_BYTES:
                out.append(t)
        return out
    sub = _SUBLANE.get(jax.numpy.dtype(dtype).name, 8)
    unit = _contract_unit(kernel, M, nnz)
    if is_sparse_kernel(kernel):
        bbs = sorted({min(_round_up(b, sub), _round_up(BS, sub))
                      for b in (8, 16, 32)})
        bk_opts = (16, 32, 64, 128, 256)
    else:
        bbs = sorted({min(_round_up(b, sub), _round_up(BS, sub))
                      for b in (32, 64, 128, 256)})
        bk_opts = (4, 8, 16, 32, 64, 128)
    bns = sorted({min(_round_up(b, 8), _round_up(N, 8)) for b in (64, 128, 256)})
    bks = sorted({min(b, K) for b in bk_opts if b * unit <= _MAX_CONTRACT})
    out = []
    for bb in bbs:
        for bn in bns:
            for bk in bks:
                if (bb, bn, bk) not in out:
                    out.append((bb, bn, bk))
    return out


def _valid_tiles(hit) -> Tiles | None:
    """Schema-check one cache entry; malformed entries fall through to the
    defaults instead of raising."""
    if not isinstance(hit, dict):
        return None
    tiles = hit.get("tiles")
    if (
        isinstance(tiles, (list, tuple))
        and len(tiles) == 3
        and all(isinstance(t, int) and t > 0 for t in tiles)
    ):
        return tuple(tiles)  # type: ignore[return-value]
    return None


def clamp_default(
    kernel: str, backend: str, BS: int, K: int, N: int, dtype
) -> Tiles:
    """The DEFAULTS entry as resolved for one problem: clamped so small-K
    (or N just over the gate) shapes don't pay large padding multiples,
    then ``bb`` re-rounded UP to the dtype's sublane granularity — the
    decode-shaped sparse default (bb=8) is only aligned under fp32; under
    bf16/int8 the clamp itself must restore alignment.  This is the ONE
    definition both ``get_tiles`` and the kernel-config lint validate."""
    if backend == "tpu":
        return _tpu_clamp(DEFAULTS[(kernel, backend)], BS, K, N, dtype)
    sub = _SUBLANE.get(jax.numpy.dtype(dtype).name, 8)
    bb, bn, bk = DEFAULTS[(kernel, backend)]
    return (
        _round_up(min(bb, _round_up(BS, sub)), sub),
        min(bn, _round_up(N, 8)),
        min(bk, K),
    )


def get_tiles(
    kernel: str, BS: int, K: int, N: int, M: int,
    dtype=jax.numpy.float32, backend: str | None = None,
    nnz: int | None = None,
) -> Tiles:
    """Resolve tiles: measurement cache -> defaults table -> heuristic."""
    backend = backend or jax.default_backend()
    _check_backend(kernel, backend)
    key = problem_key(kernel, BS, K, N, M, dtype, backend)
    hit = _valid_tiles(_load_cache().get(key))
    if hit:
        return hit
    if backend == "tpu":
        return clamp_default(kernel, backend, BS, K, N, dtype)
    if (kernel, backend) in DEFAULTS:
        use = (
            BS <= 32 and N >= 128          # sparse defaults: decode-shaped,
            if is_sparse_kernel(kernel)    # apply in the regime measured
            else min(BS, N) >= 128
        )
        if use:
            return clamp_default(kernel, backend, BS, K, N, dtype)
    return _heuristic(kernel, BS, K, N, M, dtype, nnz)


def _time_call(fn: Callable[[], jax.Array], iters: int) -> float:
    out = fn()
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def record_winner(
    kernel: str, BS: int, K: int, N: int, M: int, dtype, backend: str,
    tiles: Tiles, us: float,
) -> str:
    """Write one measured winner into the cache (atomic, see _save_cache).

    For callers that time candidates themselves (e.g. the benchmark's
    interleaved fused-vs-sparse sweep) but still want ``get_tiles`` to hand
    the winner to every later ``ops.py`` call.  Returns the problem key.
    """
    key = problem_key(kernel, BS, K, N, M, dtype, backend)
    cache = _load_cache()
    cache[key] = {"tiles": list(tiles), "us": round(float(us), 1)}
    _save_cache(cache)
    return key


def autotune(
    kernel: str,
    run: Callable[[int, int, int], jax.Array],
    BS: int, K: int, N: int, M: int,
    dtype=jax.numpy.float32,
    backend: str | None = None,
    iters: int = 3,
    candidates: list[Tiles] | None = None,
    nnz: int | None = None,
) -> dict:
    """Time every candidate tiling of ``run(bb, bn, bk)``, cache the winner.

    Returns ``{"key", "tiles", "us", "candidates": {tiles_str: us}}`` —
    the report row the benchmark JSON embeds.
    """
    backend = backend or jax.default_backend()
    key = problem_key(kernel, BS, K, N, M, dtype, backend)
    cands = candidates or candidate_tiles(kernel, BS, K, N, M, dtype, backend, nnz)
    timings: dict[str, float] = {}
    best: Tiles | None = None
    best_us = float("inf")
    for tiles in cands:
        us = _time_call(lambda: run(*tiles), iters)
        timings["x".join(map(str, tiles))] = round(us, 1)
        if us < best_us:
            best, best_us = tiles, us
    cache = _load_cache()
    cache[key] = {"tiles": list(best), "us": round(best_us, 1)}
    _save_cache(cache)
    return {"key": key, "tiles": best, "us": best_us, "candidates": timings}
