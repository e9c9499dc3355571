"""KAN layers as GEMM workloads (paper §II-A, Eq. 1, Fig. 1c).

``KANLayer(x) = sum_j phi_j(x_j) + w_b · b(x)`` with ``phi`` parameterised in
the B-spline basis: ``phi(x) = sum_m c_m B_m(x)``. The ``w_i`` scales of
Eq. 1 are absorbed into the coefficients (paper §II-A: "at inference time,
they can be absorbed in the functions"); the base nonlinearity ``b`` is ReLU
(paper: "It is typically a SiLU but we replace it with a ReLU").

Forward paths (selectable, all numerically cross-checked in tests):

* ``dense``   — materialise the full ``B : (BS, K, G+P)`` activation tensor via
  exact Cox-de Boor and contract with XLA. This is the *conventional SA*
  baseline of the paper (the scalar-PE array chewing through zeros) and the
  differentiable training path.
* ``compact`` — the N:M form: only the ``P+1`` non-zero values are produced and
  the matching coefficient slabs are *gathered* per input (the paper's
  M-to-N multiplexer). Wins on TPU in the small-batch/decode regime.
* ``lut``     — tabulated evaluation (paper Fig. 5) scattered dense; inference.
* ``fused``   — Pallas kernel: B tile built on the fly in VMEM, MXU contraction
  (the paper's B-spline unit streaming straight into the systolic array).
  Spline AND base term execute in a single ``pallas_call`` (the base GEMM is
  a kernel epilogue on the already-resident x tile).  Requires
  ``repro.kernels``; CPU tests run it with ``interpret=True``.
* ``sparse``  — Pallas kernel: the paper's N:M vector PE (§IV-A/B). Each input
  contracts only its ``P+1`` non-zero values against a *gathered*
  ``(P+1, N)`` coefficient slab — ``(G+P)/(P+1)×`` fewer MACs and
  coefficient reads than ``fused`` (DESIGN.md §2a).
* ``auto``    — :func:`resolve_inference_method`: ``fused`` on TPU,
  ``compact`` off-TPU (interpret-mode Pallas is correct but slow on CPU).
  ``sparse`` runs in interpret mode only (its slab gather does not lower
  to Mosaic).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bspline
from repro.core.bspline import SplineGrid

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class KANLayerConfig:
    in_dim: int
    out_dim: int
    grid: SplineGrid = SplineGrid()
    base: bool = True           # include the w_b · ReLU(x) term of Eq. 1
    noise_scale: float = 0.1    # init scale for spline coefficients
    lut_size: int = 256         # paper: 8-bit address -> 256 entries


def init_kan_layer(key: jax.Array, cfg: KANLayerConfig, dtype=jnp.float32) -> Params:
    """Coefficients ``(K, M, N)`` + base weight ``(K, N)``."""
    k_coef, k_base = jax.random.split(key)
    M = cfg.grid.n_basis
    coeff = cfg.noise_scale * jax.random.normal(
        k_coef, (cfg.in_dim, M, cfg.out_dim), dtype
    ) / math.sqrt(cfg.in_dim * (cfg.grid.P + 1))
    params: Params = {"coeff": coeff}
    if cfg.base:
        params["base_w"] = jax.random.normal(
            k_base, (cfg.in_dim, cfg.out_dim), dtype
        ) * math.sqrt(2.0 / cfg.in_dim)
    return params


def _base_term(params: Params, x: jax.Array) -> jax.Array:
    if "base_w" not in params:
        return jnp.zeros(x.shape[:-1] + (params["coeff"].shape[-1],), x.dtype)
    return jax.nn.relu(x) @ params["base_w"]


def kan_layer_dense(params: Params, x: jax.Array, grid: SplineGrid) -> jax.Array:
    """Conventional-SA baseline: dense B materialisation + GEMM (Fig. 1c)."""
    B = bspline.cox_de_boor_dense(x, grid)            # (..., K, M)
    y = jnp.einsum("...km,kmn->...n", B, params["coeff"])
    return y + _base_term(params, x)


def kan_layer_compact(params: Params, x: jax.Array, grid: SplineGrid) -> jax.Array:
    """N:M sparsity-aware path (paper §IV): compute only the P+1 non-zero
    values and gather their coefficients — no multiplications with zero.

    The coefficient-slab gather ``C[j, k-P+i, :]`` is the software analogue of
    the paper's M-to-N multiplexer (select-by-``k``). It moves
    ``BS·K·(P+1)·N`` coefficient elements, so on TPU it wins over the dense
    panel (``K·M·N``) exactly in the small-batch/decode regime — see DESIGN.md.
    """
    vals, k = bspline.compact_basis(x, grid)          # (..., K, P+1), (..., K)
    coeff = params["coeff"]                           # (K, M, N)
    K = coeff.shape[0]
    m_idx = k[..., None] - grid.P + jnp.arange(grid.P + 1, dtype=k.dtype)
    flat_m = m_idx.reshape(-1, K, grid.P + 1)         # (BSf, K, P+1)
    coeff_b = jnp.broadcast_to(coeff, flat_m.shape[:1] + coeff.shape)
    slabs = jnp.take_along_axis(                      # (BSf, K, P+1, N)
        coeff_b, flat_m[..., None].astype(jnp.int32), axis=2, mode="clip"
    )
    vals_f = vals.reshape(-1, K, grid.P + 1)
    y = jnp.einsum("bki,bkin->bn", vals_f, slabs)
    y = y.reshape(x.shape[:-1] + (coeff.shape[-1],))
    return y + _base_term(params, x)


def kan_layer_lut(
    params: Params, x: jax.Array, grid: SplineGrid, lut: jax.Array
) -> jax.Array:
    """Tabulated inference path (paper Fig. 5) — dense scatter + GEMM."""
    B = bspline.lut_basis_dense(x, grid, lut)
    y = jnp.einsum("...km,kmn->...n", B, params["coeff"])
    return y + _base_term(params, x)


def resolve_inference_method(backend: str | None = None) -> str:
    """The serving path per backend (DESIGN.md §2a).

    On TPU: the ``fused`` kernel at every row count (one kernel per layer,
    B never in HBM — DESIGN.md §2).  The ``sparse`` N:M kernel is not on
    the TPU path: its per-input coefficient-slab gather is a 4-D gather
    Mosaic does not lower, and the gather-free rendering of it is the
    fused kernel's dense band.  Off-TPU: ``compact`` (interpret-mode
    Pallas is correct on CPU but orders of magnitude slower than the XLA
    gather path).

    ``$KAN_SAS_INFERENCE_METHOD`` names a method to use instead, e.g.
    ``fused`` to run the TPU kernel in interpret mode on a CPU; naming
    ``sparse`` on a TPU is an error.
    """
    import os

    backend = backend or jax.default_backend()
    method = os.environ.get("KAN_SAS_INFERENCE_METHOD") or (
        "fused" if backend == "tpu" else "compact"
    )
    if backend == "tpu" and method == "sparse":
        raise ValueError("the sparse kernel is not compiled for the TPU")
    return method


def kan_layer_apply(
    params: Params,
    x: jax.Array,
    grid: SplineGrid,
    method: str = "dense",
    lut: jax.Array | None = None,
) -> jax.Array:
    if method == "auto":
        method = resolve_inference_method()
    if method == "dense":
        return kan_layer_dense(params, x, grid)
    if method == "compact":
        return kan_layer_compact(params, x, grid)
    if method == "lut":
        if lut is None:
            lut = jnp.asarray(bspline.build_lut(grid.P))
        return kan_layer_lut(params, x, grid, lut)
    if method == "fused":
        from repro.kernels import ops as kops

        # Spline + base in ONE pallas_call: the base term is an epilogue
        # contraction on the x tile already resident in VMEM.
        return kops.kan_fused_gemm(
            x, params["coeff"], grid, base_w=params.get("base_w")
        )
    if method == "sparse":
        from repro.kernels import ops as kops

        # The N:M vector PE: P+1-wide gathered-slab contraction, base term
        # fused as the same epilogue — one pallas_call per layer.
        return kops.kan_sparse_gemm(
            x, params["coeff"], grid, base_w=params.get("base_w")
        )
    raise ValueError(f"unknown method {method!r}")


# ---------------------------------------------------------------------------
# KAN stacks (MLP-style) and ConvKAN — the paper's application workloads.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KANNetConfig:
    """A KAN MLP: e.g. MNIST-KAN is ``layers=[784, 64, 10], G=10, P=3``."""

    layers: tuple[int, ...]
    G: int = 5
    P: int = 3
    x_min: float = -1.0
    x_max: float = 1.0
    base: bool = True
    layer_norm: bool = True  # keep activations in-domain between layers

    def grid(self) -> SplineGrid:
        return SplineGrid(self.x_min, self.x_max, self.G, self.P)


def init_kan_net(key: jax.Array, cfg: KANNetConfig, dtype=jnp.float32) -> list[Params]:
    keys = jax.random.split(key, len(cfg.layers) - 1)
    return [
        init_kan_layer(
            k,
            KANLayerConfig(cfg.layers[i], cfg.layers[i + 1], cfg.grid(), base=cfg.base),
            dtype,
        )
        for i, k in enumerate(keys)
    ]


def _tanh_norm(h: jax.Array) -> jax.Array:
    """Map intermediate activations back into the spline domain.

    KAN reference impls keep activations in the grid range either by grid
    updates (training-time) or normalisation; we use a smooth tanh squash,
    which keeps the LUT/int8 paths' clipping honest.
    """
    return jnp.tanh(h)


def kan_net_apply(
    params: list[Params],
    x: jax.Array,
    cfg: KANNetConfig,
    method: str = "dense",
    lut: jax.Array | None = None,
) -> jax.Array:
    g = cfg.grid()
    h = x
    for i, p in enumerate(params):
        if i > 0 and cfg.layer_norm:
            h = _tanh_norm(h)
        h = kan_layer_apply(p, h, g, method=method, lut=lut)
    return h


# ---------------------------------------------------------------------------
# ConvKAN (ResKAN18 building block): scalar conv filter weights replaced by
# splines; realised as im2col + KANLayer (paper §V-C, refs [16],[29],[32]).
# ---------------------------------------------------------------------------


def im2col(x: jax.Array, kh: int, kw: int, stride: int = 1, pad: int = 0) -> jax.Array:
    """(B, H, W, C) -> (B, Ho, Wo, kh*kw*C) patches."""
    if pad:
        x = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    B, H, W, C = x.shape
    Ho = (H - kh) // stride + 1
    Wo = (W - kw) // stride + 1
    patches = jax.lax.conv_general_dilated_patches(
        x.transpose(0, 3, 1, 2), (kh, kw), (stride, stride), "VALID"
    )  # (B, C*kh*kw, Ho, Wo)
    return patches.transpose(0, 2, 3, 1).reshape(B, Ho, Wo, C * kh * kw)


def conv_kan_apply(
    params: Params,
    x: jax.Array,
    grid: SplineGrid,
    kh: int = 3,
    kw: int = 3,
    stride: int = 1,
    pad: int = 1,
    method: str = "dense",
) -> jax.Array:
    """ConvKAN layer: each filter tap is a learnable spline."""
    patches = im2col(x, kh, kw, stride, pad)       # (B, Ho, Wo, kh*kw*C)
    B, Ho, Wo, Kin = patches.shape
    y = kan_layer_apply(params, patches.reshape(-1, Kin), grid, method=method)
    return y.reshape(B, Ho, Wo, -1)


def kan_layer_flops(BS: int, K: int, N: int, grid: SplineGrid) -> dict[str, float]:
    """Useful vs dense FLOP accounting (paper §IV-A utilisation argument)."""
    M, Nnz = grid.n_basis, grid.n_nonzero
    return {
        "dense_macs": float(BS * K * M * N),
        "useful_macs": float(BS * K * Nnz * N),
        "density": Nnz / M,
    }
