"""Pallas TPU kernel: the integer-only KAN-SAs datapath (paper §III-B2, §V).

Implements the exact hardware pipeline of the paper in one fused kernel:

* integer Align + Compare (Eq. 5): ``u = (G+2P)(x_q - t_q0)``,
  ``k = u // 255``, ``addr = clip(u - 255k, 0, 255)`` — int32 arithmetic only;
* the uint8 ROM of Fig. 5, realised **without the ROM**: the table entries
  are by construction ``round(B_{0,P}(addr/(S-1) + c) · s)``, so the kernel
  evaluates that generating function directly with the shared compare-select
  Cox-de Boor code (:mod:`repro.kernels.common`) and rounds — bit-identical
  to the direct + inverted-address half-table fetch (verified by
  ``tests/test_kernels.py``), but O(P²) per element instead of the two
  O(S)-wide one-hot matmuls the previous revision used;
* the dense-band scatter (the M-to-N mux in reverse) shared with the
  floating-point kernel;
* int8 coefficient band, int32 accumulation (8-bit in / 32-bit out PEs of
  Table I). On a real TPU the int8 MXU path doubles throughput vs bf16;
* an optional **fused dequantisation epilogue**: the per-output-channel
  float multiply of [18] is applied to the int32 accumulator tile while it
  is still in VMEM, so the kernel emits the serving dtype directly and the
  int32 accumulator never touches HBM.

Without ``scale`` the raw int32 accumulator is returned (the bit-exact
contract the oracle tests check).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bspline import SplineGrid
from repro.kernels.common import (
    band_scatter,
    int8_compact_values_inblock,
)


def _int8_kernel(
    *refs, grid: SplineGrid, S: int, qmax: int, lut_scale: int, has_scale: bool,
):
    if has_scale:
        xq_ref, cq_ref, scale_ref, y_ref, acc_ref = refs
    else:
        xq_ref, cq_ref, y_ref, acc_ref = refs
        scale_ref = None
    M = grid.n_basis
    x_q = xq_ref[...].astype(jnp.int32)               # (bb, bk)

    # Integer Align + Compare (Eq. 5) + ROM-free fetch (shared with the
    # sparse int8 kernel): bit-identical to the uint8 half-table.
    bvals, k = int8_compact_values_inblock(x_q, grid, S, qmax, lut_scale)

    # Dense-band scatter (the M-to-N mux in reverse) + int32 MXU GEMM.
    band = band_scatter(bvals, k, M)                  # (bb, bk, M) int32
    bb, bk = x_q.shape
    acc = jnp.dot(
        band.reshape(bb, bk * M), cq_ref[...].astype(jnp.int32),
        preferred_element_type=jnp.int32,
    )

    kk = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = acc

    @pl.when(kk > 0)
    def _accumulate():
        acc_ref[...] = acc_ref[...] + acc

    @pl.when(kk == nk - 1)
    def _epilogue():
        total = acc_ref[...]
        if has_scale:
            # Fused dequant: one float multiply per output channel while the
            # accumulator tile is still in VMEM (paper [18]); the int32
            # accumulator never reaches HBM.
            y_ref[...] = (
                total.astype(jnp.float32) * scale_ref[...]
            ).astype(y_ref.dtype)
        else:
            y_ref[...] = total


@functools.partial(
    jax.jit,
    static_argnames=("grid", "bb", "bn", "bk", "qmax", "S", "lut_scale",
                     "out_dtype", "interpret"),
)
def kan_int8_gemm_pallas(
    x_q: jax.Array,
    coeff_q: jax.Array,
    grid: SplineGrid,
    scale: jax.Array | None = None,
    bb: int = 128,
    bn: int = 128,
    bk: int = 16,
    qmax: int = 255,
    S: int = 256,
    lut_scale: int | None = None,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """Integer fused KAN GEMM.

    ``x_q: (BS, K)`` uint8/int32 activations quantised over the extended
    domain; ``coeff_q: (K, M, N)`` int8; ``scale: (N,) float32 | None`` the
    per-output-channel dequant multiplier (typically
    ``coeff_scale / lut_scale``).

    Returns the int32 accumulator ``(BS, N)`` when ``scale is None``, else
    the dequantised ``(BS, N)`` in ``out_dtype`` (fused epilogue).
    """
    assert lut_scale is not None, (
        "pass lut_scale explicitly (resolve with "
        "repro.core.quantization.lut_value_scale OUTSIDE any jit trace)"
    )
    BS, K = x_q.shape
    Kc, M, N = coeff_q.shape
    assert Kc == K and M == grid.n_basis
    has_scale = scale is not None
    pb, pk, pn = -BS % bb, -K % bk, -N % bn
    xp = jnp.pad(x_q.astype(jnp.int32), ((0, pb), (0, pk)))
    cp = jnp.pad(coeff_q.astype(jnp.int8), ((0, pk), (0, 0), (0, pn)))
    c2 = cp.reshape((K + pk) * M, N + pn)
    gb, gn, gk = (BS + pb) // bb, (N + pn) // bn, (K + pk) // bk

    in_specs = [
        pl.BlockSpec((bb, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk * M, bn), lambda i, j, kk: (kk, j)),
    ]
    operands = [xp, c2]
    if has_scale:
        sp = jnp.pad(scale.astype(jnp.float32).reshape(1, N), ((0, 0), (0, pn)))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
        operands.append(sp)

    y = pl.pallas_call(
        functools.partial(
            _int8_kernel, grid=grid, S=S, qmax=qmax,
            lut_scale=lut_scale, has_scale=has_scale,
        ),
        grid=(gb, gn, gk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bb, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct(
            (BS + pb, N + pn), out_dtype if has_scale else jnp.int32
        ),
        scratch_shapes=[pltpu.VMEM((bb, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(*operands)
    return y[:BS, :N]


@functools.lru_cache(maxsize=32)
def _reference_lut(P: int, S: int, scale: int) -> np.ndarray:
    from repro.core.quantization import build_lut_u8

    return build_lut_u8(P, S, scale)


@functools.lru_cache(maxsize=8)
def _max_cardinal(P: int) -> float:
    from repro.core import bspline

    return float(bspline.cardinal_bspline(jnp.asarray((P + 1) / 2.0), P))


def resolve_lut_scale(lut_u8, grid: SplineGrid, S: int) -> int:
    """The ROM-free kernel reproduces ``build_lut_u8(P, S, scale)``; infer
    ``scale`` from a concrete table (and verify the table matches — any
    other table is rejected).  A traced table (inside an enclosing jit)
    cannot be inspected: the caller must pass ``lut_scale`` explicitly
    (``ops.kan_int8_gemm(..., lut_scale=...)``) for non-default scales.
    """
    from repro.core.quantization import lut_value_scale

    default = lut_value_scale(grid.P)
    try:
        concrete = np.asarray(lut_u8)
    except Exception:
        return default  # traced: default-scale contract
    # Infer: the table max is round(max(B_{0,P}) * scale).
    inferred = int(round(float(concrete.max()) / _max_cardinal(grid.P)))
    for scale in dict.fromkeys((default, inferred, inferred - 1, inferred + 1)):
        if scale > 0 and np.array_equal(concrete, _reference_lut(grid.P, S, scale)):
            return scale
    raise ValueError(
        "kan_int8_gemm computes the build_lut_u8 ROM in-kernel; the given "
        "table matches no integer value scale — arbitrary LUT tables are "
        "not supported"
    )
