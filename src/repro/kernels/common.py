"""In-kernel building blocks shared by the KAN Pallas kernels.

The floating-point (`kan_fused_gemm`), integer (`kan_int8_gemm`) and
sparse (`kan_sparse_gemm`) datapaths share these pieces of the paper's
architecture rendered as branch-free vector code:

* the B-spline unit (§III-A): evaluate the ``P+1`` non-zero cardinal
  B-spline values for a tile of inputs entirely in VMEM/registers
  (:func:`cardinal_planes`, one Cox-de Boor for every kernel);
* the M-to-N multiplexer run in reverse (§IV-B): place those values into
  the dense ``M = G+P`` band with compare-selects — no gathers, no
  scatters.  :func:`band_planes` keeps the basis index unrolled (one
  ``(bb, bk)`` plane per basis function, the layout the TPU fused kernel
  compiles with); :func:`band_scatter` lays it out on a trailing axis
  (the int8 kernel, interpret mode);
* the M-to-N multiplexer run *forward* (§IV-B, the N:M vector PE of the
  sparse kernels): gather, per input, the ``(P+1, N)`` coefficient slab its
  non-zero basis values touch (:func:`gather_coeff_slabs`);
* the integer Align/Compare units + ROM-free table fetch of the int8
  datapath (Eq. 5), shared by the dense-band and sparse int8 kernels
  (:func:`int8_compact_values_inblock`).

Only the fused kernel's path (:func:`band_planes`) is compiled for the TPU
(``tests/test_tpu_compile.py``).  The trailing-axis helpers pad their
``P+1`` / ``M`` minor axis to 128 lanes under Mosaic, and
:func:`gather_coeff_slabs` is a 4-D gather Mosaic does not lower; the
kernels built on them run in interpret mode.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.bspline import SplineGrid


def cardinal_planes(xa: jax.Array, P: int) -> list[jax.Array]:
    """Cardinal B-spline values ``B_{0,P}(xa + (P - i))`` for ``i = 0..P``.

    ``xa`` is the in-interval offset in ``[0, 1)``; returns ``P+1`` arrays
    of ``xa.shape`` ordered by ascending basis index.  Runs the Cox-de Boor
    triangle as a list of planes, one per knot segment: since
    ``u_i = xa + (P-i)`` lies in ``[P-i, P-i+1)``, the degree-0 coefficient
    vector for point ``i`` is the indicator ``e_{P-i}``.  Keeping the basis
    axis as a Python list (never a trailing array axis) lets a TPU kernel
    keep ``xa``'s own lane layout for every temporary.
    """
    out = []
    for i in range(P + 1):
        u = xa + float(P - i)
        b = [
            jnp.where((u >= float(s)) & (u < float(s + 1)), 1.0, 0.0).astype(xa.dtype)
            for s in range(P + 1)
        ]
        for p in range(1, P + 1):
            b = [
                (u - float(s)) / float(p) * b[s]
                + (float(s + p + 1) - u) / float(p) * b[s + 1]
                for s in range(P + 1 - p)
            ]
        out.append(b[0])
    return out


def cardinal_values_inblock(xa: jax.Array, P: int) -> jax.Array:
    """:func:`cardinal_planes` stacked on a trailing ``(P+1,)`` axis."""
    return jnp.stack(cardinal_planes(xa, P), axis=-1)


def _align_inblock(x: jax.Array, grid: SplineGrid) -> tuple[jax.Array, jax.Array]:
    """Align + Compare units: in-interval offset ``xa`` (f32) and interval
    index ``k``, identical math to :func:`repro.core.bspline.compact_basis`.
    Evaluation runs in float32 regardless of ``x.dtype`` (the Cox-de Boor
    triangle loses too much in bf16)."""
    P = grid.P
    xf = x.astype(jnp.float32)
    z = (xf - jnp.float32(grid.t0)) / jnp.float32(grid.delta)
    k = jnp.clip(jnp.floor(z).astype(jnp.int32), P, grid.n_basis - 1)
    xa = jnp.clip(z - k.astype(jnp.float32), 0.0, 1.0)
    return xa, k


def band_planes(x: jax.Array, grid: SplineGrid) -> list[jax.Array]:
    """All ``M = G+P`` dense basis planes ``B_m(x)``, each of ``x.shape``
    (float32) — the B-spline unit and the M-to-N multiplexer in reverse,
    with the basis index unrolled instead of laid out on an array axis.

    Plane ``m`` holds ``vals[i]`` where ``m == k - P + i`` and zero
    elsewhere: compare-selects, no gathers, and every temporary keeps
    ``x``'s ``(rows, lanes)`` layout.
    """
    xa, k = _align_inblock(x, grid)
    vals = cardinal_planes(xa, grid.P)
    base = k - grid.P
    planes = []
    for m in range(grid.n_basis):
        plane = jnp.zeros(x.shape, jnp.float32)
        for i, v in enumerate(vals):
            plane = plane + jnp.where(base == m - i, v, 0.0)
        planes.append(plane)
    return planes


def compact_basis_inblock(
    x: jax.Array, grid: SplineGrid
) -> tuple[jax.Array, jax.Array]:
    """Exact compact N:M evaluation as branch-free vector code.

    Returns ``vals: x.shape + (P+1,)`` (ascending basis index) and the
    interval index ``k``.  Identical math to
    :func:`repro.core.bspline.compact_basis`; callers cast the values to
    the contraction dtype.
    """
    xa, k = _align_inblock(x, grid)
    return cardinal_values_inblock(xa, grid.P), k


def band_scatter(vals: jax.Array, k: jax.Array, M: int) -> jax.Array:
    """The M-to-N multiplexer in reverse (paper §IV-B).

    Places compact values ``vals: (..., P+1)`` (ascending basis index, the
    window ``B_{k-P} .. B_k``) into the dense band ``(..., M)`` with
    compare-selects — structured N:M sparsity becomes an MXU-aligned dense
    tile without gathers.  Works for any dtype (float or int).
    """
    P = vals.shape[-1] - 1
    m_iota = jax.lax.broadcasted_iota(jnp.int32, k.shape + (M,), k.ndim)
    rel = m_iota - (k[..., None] - P)                 # (..., M)
    zero = jnp.zeros((), vals.dtype)
    band = jnp.zeros(k.shape + (M,), vals.dtype)
    for i in range(P + 1):
        band = band + jnp.where(rel == i, vals[..., i][..., None], zero)
    return band


def gather_coeff_slabs(c: jax.Array, k: jax.Array, P: int) -> jax.Array:
    """The M-to-N multiplexer run *forward* (paper §IV-B, the N:M vector PE).

    ``c: (bk, M, bn)`` coefficient block, ``k: (bb, bk)`` interval indices in
    ``[P, M-1]`` -> ``(bb, bk, P+1, bn)``: per input, the coefficient slab
    ``C[j, k-P .. k, :]`` its ``P+1`` non-zero basis values touch (ascending
    basis index, matching :func:`cardinal_values_inblock`).  This is the
    select-by-``k`` that lets the sparse kernels contract only ``bk·(P+1)``
    wide instead of the dense ``bk·M`` band.

    Lowered as one batched gather; XLA fuses the broadcast into it, so no
    ``(bb, bk, M, bn)`` temporary is materialised.  In interpret mode these
    are plain XLA ops; compiling on TPU needs Mosaic dynamic-gather support
    (the sparse kernels are decode-shape kernels — small ``bb·bk`` — by
    design, see DESIGN.md §2a).
    """
    bb, bk = k.shape
    offs = jax.lax.broadcasted_iota(jnp.int32, k.shape + (P + 1,), k.ndim)
    idx = (k[..., None] - P) + offs                   # (bb, bk, P+1) in [0, M-1]
    cb = jnp.broadcast_to(c[None], (bb,) + c.shape)   # fused into the gather
    return jnp.take_along_axis(cb, idx[..., None], axis=2, mode="clip")


def int8_compact_values_inblock(
    x_q: jax.Array, grid: SplineGrid, S: int, qmax: int, lut_scale: int
) -> tuple[jax.Array, jax.Array]:
    """Integer Align + Compare units (paper Eq. 5) + ROM-free table fetch.

    ``x_q: (...,) int32`` activations quantised over the extended domain ->
    ``(bvals: (..., P+1) int32, k: (...,) int32)``.  The uint8 table entries
    are by construction ``round(B_{0,P}(addr/(S-1) + c) · lut_scale)``, so
    the generating function is evaluated directly with the shared
    compare-select Cox-de Boor code — bit-identical to the direct +
    inverted-address half-table fetch (tested), no O(S) one-hot matmuls.
    Shared by the dense-band (``kan_int8_gemm``) and sparse
    (``kan_sparse_gemm``) integer kernels.
    """
    P, M = grid.P, grid.n_basis
    u = (grid.G + 2 * P) * x_q
    k = jnp.clip(u // qmax, P, M - 1)
    addr = jnp.clip(u - qmax * k, 0, qmax)
    addr = (addr * (S - 1)) // qmax
    xa_q = addr.astype(jnp.float32) / jnp.float32(S - 1)
    vals = cardinal_values_inblock(xa_q, P)           # f32 (..., P+1)
    bvals = jnp.clip(
        jnp.round(vals * jnp.float32(lut_scale)), 0.0, 255.0
    ).astype(jnp.int32)
    return bvals, k
