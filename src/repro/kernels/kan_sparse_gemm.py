"""Pallas TPU kernel: the compact N:M sparse spline GEMM (paper §IV-A/B).

The fused kernel (`kan_fused_gemm.py`) converts the B-spline's structured
N:M sparsity into *dense* MXU work: the ``P+1`` non-zero values are
scattered into the full ``M = G+P`` band and contracted ``bk·M`` wide, so
every tile pays ``M/(P+1)×`` more MACs — and streams ``M/(P+1)×`` more
coefficient rows — than the useful work requires.  That is exactly the
utilization gap the paper's N:M vector PE closes in hardware (§IV-A: 100%
vs ~30% for the conventional array).

This kernel is the software analogue of that PE.  Per input it contracts
only the ``P+1`` non-zero basis values against a *gathered* ``(P+1, N)``
slice of the coefficient tensor (the M-to-N multiplexer run forward,
``kernels/common.py: gather_coeff_slabs``), so

* MACs drop ``(G+P)/(P+1)×`` (2× at the default G=5/P=3, 3.25× for
  MNIST-KAN's G=10);
* the coefficient stream shrinks by the same factor: only the slab rows
  live inputs touch cross the memory boundary (exact at BS=1 decode — see
  DESIGN.md §2a for the accounting and the crossover vs the fused kernel).

Because the gathered slabs differ per batch row, the contraction is a
*batched* matvec ``(bb, 1, bk·(P+1)) @ (bb, bk·(P+1), bn)`` rather than one
shared GEMM — VPU-shaped work, which is precisely right for the
memory-bound small-batch/decode regime this kernel targets (the fused
kernel stays the large-batch path, where the MXU-aligned dense band wins).

Both variants follow the fused kernels' structure: grid
``(BS/bb, N/bn, K/bk)`` with the contraction innermost, fp32 (int32)
accumulation in a VMEM scratch tile, the base term ``ReLU(x) @ Wb`` (the
per-channel dequant multiply, for int8) fused as an epilogue on the
already-resident tile — one ``pallas_call`` per layer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bspline import SplineGrid
from repro.kernels.common import (
    compact_basis_inblock,
    gather_coeff_slabs,
    int8_compact_values_inblock,
)


def _slab_contract(vals: jax.Array, slabs: jax.Array, acc_dtype) -> jax.Array:
    """Batched sparse contraction: ``(bb, bk, P+1) x (bb, bk, P+1, bn) ->
    (bb, bn)`` — each row contracts its own gathered slabs, ``bk·(P+1)``
    wide instead of the dense ``bk·M``."""
    bb = vals.shape[0]
    W = vals.shape[1] * vals.shape[2]                 # bk * (P+1)
    bn = slabs.shape[-1]
    out = jax.lax.dot_general(
        vals.reshape(bb, 1, W),
        slabs.reshape(bb, W, bn),
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=acc_dtype,
    )
    return out[:, 0, :]


def _sparse_kernel(*refs, grid: SplineGrid, has_base: bool):
    if has_base:
        x_ref, c_ref, bw_ref, y_ref, acc_ref = refs
    else:
        x_ref, c_ref, y_ref, acc_ref = refs
        bw_ref = None
    x = x_ref[...]                                    # (bb, bk)
    vals, k = compact_basis_inblock(x, grid)          # f32 (bb, bk, P+1), i32
    c = c_ref[...]                                    # (bk, M, bn)

    # The N:M vector PE: gather each input's (P+1, bn) coefficient slab and
    # contract only the non-zero lanes — no dense band, no zero MACs.
    slabs = gather_coeff_slabs(c, k, grid.P)          # (bb, bk, P+1, bn)
    acc = _slab_contract(vals.astype(c.dtype), slabs, jnp.float32)

    if has_base:
        # Base-term epilogue (Eq. 1), same as the fused kernel: the x tile
        # is already in VMEM — one extra contraction, no extra HBM reads.
        xb = jnp.maximum(x, jnp.zeros((), x.dtype))
        acc = acc + jnp.dot(
            xb.astype(bw_ref.dtype), bw_ref[...],
            preferred_element_type=jnp.float32,
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
        y_ref[...] = acc_ref[...].astype(y_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("grid", "bb", "bn", "bk", "interpret")
)
def kan_sparse_gemm_pallas(
    x: jax.Array,
    coeff: jax.Array,
    grid: SplineGrid,
    base_w: jax.Array | None = None,
    bb: int = 8,
    bn: int = 128,
    bk: int = 32,
    interpret: bool = False,
) -> jax.Array:
    """Sparse KAN layer. ``x: (BS, K)``, ``coeff: (K, M, N)``,
    ``base_w: (K, N) | None`` -> ``(BS, N)`` in ``x.dtype``.

    Numerically matches :func:`kan_fused_gemm_pallas` (same basis values,
    same fp32 accumulation; only the zero MACs are skipped).  Default tiles
    are decode-shaped: small ``bb``, wide ``bk`` (the sparse contraction is
    only ``bk·(P+1)`` wide, so a big ``bk`` keeps the per-step work dense).
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
        functools.partial(_sparse_kernel, grid=grid, has_base=has_base),
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


def _sparse_int8_kernel(
    *refs, grid: SplineGrid, S: int, qmax: int, lut_scale: int, has_scale: bool,
):
    if has_scale:
        xq_ref, cq_ref, scale_ref, y_ref, acc_ref = refs
    else:
        xq_ref, cq_ref, y_ref, acc_ref = refs
        scale_ref = None
    x_q = xq_ref[...].astype(jnp.int32)               # (bb, bk)

    # Shared integer Align/Compare + ROM-free fetch (bit-identical to the
    # dense-band int8 kernel), then the N:M gather instead of band scatter.
    bvals, k = int8_compact_values_inblock(x_q, grid, S, qmax, lut_scale)
    c = cq_ref[...].astype(jnp.int32)                 # (bk, M, bn)
    slabs = gather_coeff_slabs(c, k, grid.P)          # (bb, bk, P+1, bn)
    acc = _slab_contract(bvals, slabs, jnp.int32)

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
            # Fused dequant epilogue, same as the dense-band int8 kernel.
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
def kan_sparse_int8_gemm_pallas(
    x_q: jax.Array,
    coeff_q: jax.Array,
    grid: SplineGrid,
    scale: jax.Array | None = None,
    bb: int = 8,
    bn: int = 128,
    bk: int = 32,
    qmax: int = 255,
    S: int = 256,
    lut_scale: int | None = None,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """Integer sparse KAN GEMM — the N:M vector PE on the int8 datapath.

    Same contract as ``kan_int8_gemm_pallas`` (and bit-identical to it:
    identical integer address math, identical ROM values, int32
    accumulation — only the zero multiplies are skipped): returns the int32
    accumulator when ``scale is None``, else the dequantised ``out_dtype``
    via the fused epilogue.
    """
    assert lut_scale is not None, "pass lut_scale explicitly (see ops.py)"
    BS, K = x_q.shape
    Kc, M, N = coeff_q.shape
    assert Kc == K and M == grid.n_basis
    has_scale = scale is not None
    pb, pk, pn = -BS % bb, -K % bk, -N % bn
    xp = jnp.pad(x_q.astype(jnp.int32), ((0, pb), (0, pk)))
    cp = jnp.pad(coeff_q.astype(jnp.int8), ((0, pk), (0, 0), (0, pn)))
    gb, gn, gk = (BS + pb) // bb, (N + pn) // bn, (K + pk) // bk

    in_specs = [
        pl.BlockSpec((bb, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, M, bn), lambda i, j, kk: (kk, 0, j)),
    ]
    operands = [xp, cp]
    if has_scale:
        sp = jnp.pad(scale.astype(jnp.float32).reshape(1, N), ((0, 0), (0, pn)))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
        operands.append(sp)

    y = pl.pallas_call(
        functools.partial(
            _sparse_int8_kernel, grid=grid, S=S, qmax=qmax,
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
