"""Pallas TPU kernel: fused KAN GEMM — the KAN-SAs array itself (paper §III-IV).

Computes the **whole** KAN layer of Eq. 1 in one kernel:

``Y[b, n] = sum_{j,m} B_m(x[b, j]) * C[j, m, n]  +  sum_j ReLU(x[b, j]) * Wb[j, n]``

**without ever materialising the B-spline activation matrix
``B : (BS, K*(G+P))`` in HBM**, and without a second GEMM for the base term.

This is the TPU rendering of the paper's two architectural moves:

* the B-spline unit "directly streams its values into the systolic array"
  (§III-A): here, each grid step evaluates the basis *in VMEM/registers*
  from the raw ``x`` tile;
* the N:M vector PE with its M-to-N multiplexer (§IV-B): the multiplexer
  becomes a branch-free compare-select that places the compact values into
  the dense band (:func:`repro.kernels.common.band_planes`).

The base term ``w_b · ReLU(x)`` of Eq. 1 rides along as an **epilogue
contraction on the same x tile**: the tile is already resident in VMEM for
the spline evaluation, so the base GEMM costs zero extra HBM reads of ``x``
and no second kernel launch.  HBM traffic drops from ``X + B + C + Y``
(dense-B baseline, plus another ``X + Wb + Y`` for a separate base GEMM) to
``X + C + Wb + Y`` — see DESIGN.md §2 for the roofline accounting.

TPU layout.  The contraction dim ``bk`` sits on the lanes of the ``x``
tile, so it is a multiple of 128 or the whole ``K``.  The basis is built as
``M`` unrolled ``(bb, bk)`` planes — never a trailing ``(..., M)`` axis,
which Mosaic would pad to 128 lanes (16x the VMEM for ``M = 8``) and then
relayout to flatten.  The coefficient block is ``(bk, M, bn)`` straight
from the ``(K, M, N)`` parameter (no HBM transpose); plane ``m`` contracts
with its ``[:, m, :]`` slice, so the layer is ``M + 1`` MXU matmuls of
``(bb, bk) @ (bk, bn)`` per grid step.

Accumulation is float32 in a VMEM scratch tile regardless of the input
dtype (bf16 inputs hit the MXU in bf16 but never round the partial sums);
the output tile is written once, on the last contraction step.

Grid: ``(BS/bb, N/bn, K/bk)`` with the contraction dim innermost; the
accumulator stays resident in VMEM across the ``K`` sweep (standard Pallas
matmul revisiting pattern).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bspline import SplineGrid
from repro.kernels.common import band_planes


def _fused_kernel(*refs, grid: SplineGrid, has_base: bool):
    if has_base:
        x_ref, c_ref, bw_ref, y_ref, acc_ref = refs
    else:
        x_ref, c_ref, y_ref, acc_ref = refs
        bw_ref = None
    x = x_ref[...]                                    # (bb, bk)
    acc = None
    if has_base:
        # Base-term epilogue (Eq. 1): the x tile is already in VMEM — one
        # extra MXU contraction, zero extra HBM traffic for x.
        xb = jnp.maximum(x, jnp.zeros((), x.dtype))   # ReLU in input dtype
        acc = jnp.dot(
            xb.astype(bw_ref.dtype), bw_ref[...],
            preferred_element_type=jnp.float32,
        )
    # B tile: M planes in VMEM only, never HBM; plane m meets C[:, m, :].
    for m, plane in enumerate(band_planes(x, grid)):
        part = jnp.dot(
            plane.astype(c_ref.dtype), c_ref[:, m, :],
            preferred_element_type=jnp.float32,
        )
        acc = part if acc is None else acc + part

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
        y_ref[...] = acc_ref[...].astype(y_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("grid", "bb", "bn", "bk", "interpret")
)
def kan_fused_gemm_pallas(
    x: jax.Array,
    coeff: jax.Array,
    grid: SplineGrid,
    base_w: jax.Array | None = None,
    bb: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Fused KAN layer. ``x: (BS, K)``, ``coeff: (K, M, N)``,
    ``base_w: (K, N) | None`` -> ``(BS, N)`` in ``x.dtype``.

    When ``base_w`` is given the base term ``ReLU(x) @ base_w`` is fused
    into the kernel epilogue — spline + base in a single ``pallas_call``.
    Compiled for TPU, ``bk``/``bn`` are multiples of 128 or the whole
    ``K``/``N`` and ``bb`` a multiple of the dtype's sublane count (the
    autotuner's TPU tiles obey this); interpret mode takes any tiling.
    Inputs are padded to block multiples (padded features carry zero
    coefficients/base weights, hence contribute nothing).
    """
    BS, K = x.shape
    Kc, M, N = coeff.shape
    assert Kc == K and M == grid.n_basis
    has_base = base_w is not None
    pb, pk, pn = -BS % bb, -K % bk, -N % bn
    xp = jnp.pad(x, ((0, pb), (0, pk)), constant_values=grid.x_min)
    cp = jnp.pad(coeff, ((0, pk), (0, 0), (0, pn)))
    gb, gn, gk = (BS + pb) // bb, (N + pn) // bn, (K + pk) // bk

    in_specs = [
        pl.BlockSpec((bb, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, M, bn), lambda i, j, kk: (kk, 0, j)),
    ]
    operands = [xp, cp]
    if has_base:
        assert base_w.shape == (K, N), (base_w.shape, (K, N))
        bwp = jnp.pad(base_w.astype(coeff.dtype), ((0, pk), (0, pn)))
        in_specs.append(pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)))
        operands.append(bwp)

    y = pl.pallas_call(
        functools.partial(_fused_kernel, grid=grid, has_base=has_base),
        grid=(gb, gn, gk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bb, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((BS + pb, N + pn), x.dtype),
        scratch_shapes=[pltpu.VMEM((bb, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(*operands)
    return y[:BS, :N]
