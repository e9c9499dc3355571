"""Composable decoder blocks.

A model is a *pattern* of blocks (DESIGN.md §3): a repeating unit scanned
``n_repeats`` times (stacked params, O(1) HLO in depth) plus optional
prologue/epilogue blocks and *shared* blocks (zamba2's shared attention:
one parameter set invoked at several depths).

Block kinds: ``attn_mlp``, ``attn_moe``, ``mamba2``, ``mlstm``, ``slstm``,
``attn_kan`` (the paper's technique as an FFN replacement), and the windowed
variants via ``AttnConfig.window``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import kan_layer as KL
from repro.core.bspline import SplineGrid
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S
from repro.models import xlstm as X
from repro.models.layers import ParamCtx


@dataclasses.dataclass(frozen=True)
class BlockCfg:
    kind: str
    attn: A.AttnConfig | None = None
    d_ff: int = 0                       # dense (SwiGLU) FFN hidden size
    moe: M.MoEConfig | None = None
    mamba: S.Mamba2Config | None = None
    xlstm: X.XLSTMConfig | None = None
    kan_grid: SplineGrid | None = None  # attn_kan
    kan_ff: int = 0
    shared_id: int | None = None        # reference into the model's shared set


def _mlp_init(ctx: ParamCtx, d: int, ff: int) -> dict:
    return {
        "wi": ctx.make((d, ff), ("embed", "ffn")),
        "wg": ctx.make((d, ff), ("embed", "ffn")),
        "wo": ctx.make((ff, d), ("ffn", "embed")),
    }


def _mlp(params: dict, x: jax.Array) -> jax.Array:
    h = x @ params["wi"].astype(x.dtype)
    g = x @ params["wg"].astype(x.dtype)
    return (jax.nn.silu(g) * h) @ params["wo"].astype(x.dtype)


def _kan_ffn_init(ctx: ParamCtx, d: int, ff: int, grid: SplineGrid) -> dict:
    """KAN FFN: two spline layers d -> ff -> d (the paper's technique as a
    first-class FFN replacement; coefficients carry the basis axis)."""
    M_ = grid.n_basis
    return {
        "c1": ctx.make((d, M_, ff), ("embed", None, "ffn"), scale=0.02),
        "b1": ctx.make((d, ff), ("embed", "ffn"), scale=0.02),
        "c2": ctx.make((ff, M_, d), ("ffn", None, "embed"), scale=0.02),
        "b2": ctx.make((ff, d), ("ffn", "embed"), scale=0.02),
    }


def _kan_ffn(
    params: dict, x: jax.Array, grid: SplineGrid, method: str = "dense",
    shard=None,
) -> jax.Array:
    """Two spline layers d -> ff -> d.

    ``method="dense"`` is the differentiable training path; inference
    callers (prefill/decode) pass ``method="auto"``, which resolves per
    backend (``KL.resolve_inference_method``): the fused kernel on TPU,
    ``compact`` elsewhere.  On a multi-device mesh (``shard``) the FFN runs
    per device (:func:`_kan_ffn_per_device`).
    """
    lead = x.shape[:-1]
    xf = jnp.tanh(x.reshape(-1, x.shape[-1]))   # squash into the spline domain
    if shard is not None and shard.n_devices > 1:
        y = _kan_ffn_per_device(params, xf, grid, method, shard)
    else:
        y = _kan_ffn_rows(params, xf, grid, method)
    return y.reshape(lead + (y.shape[-1],)).astype(x.dtype)


def _kan_ffn_rows(params: dict, xf: jax.Array, grid: SplineGrid,
                  method: str) -> jax.Array:
    h = KL.kan_layer_apply(
        {"coeff": params["c1"], "base_w": params["b1"]}, xf, grid, method
    )
    h = jnp.tanh(h)
    return KL.kan_layer_apply(
        {"coeff": params["c2"], "base_w": params["b2"]}, h, grid, method
    )


def _kan_ffn_per_device(params: dict, xf: jax.Array, grid: SplineGrid,
                        method: str, shard) -> jax.Array:
    """The FFN under ``shard.kernel_map``, Megatron style: the Pallas
    kernels are opaque to GSPMD, which refuses to partition them.  ``c1``/
    ``b1`` split on their ffn columns and ``c2``/``b2`` on their ffn rows,
    as the parameter shardings already place them; one psum over that mesh
    axis joins the partial outputs.  Rows split over the data axes."""
    m = shard.spec(("ffn",), params["c1"].shape[-1:])[0]
    r = shard.row_axis(xf.shape[0])

    def body(x, c1, b1, c2, b2):
        y = _kan_ffn_rows({"c1": c1, "b1": b1, "c2": c2, "b2": b2}, x, grid,
                          method)
        return y if m is None else jax.lax.psum(y, m)

    return shard.kernel_map(
        body,
        in_specs=(P(r, None), P(None, None, m), P(None, m), P(m, None, None),
                  P(m, None)),
        out_specs=P(r, None),
    )(xf, params["c1"], params["b1"], params["c2"], params["b2"])


def block_init(ctx: ParamCtx, d_model: int, blk: BlockCfg) -> dict:
    p: dict = {"ln1": L.rmsnorm_init(ctx, d_model)}
    if blk.kind in ("attn_mlp", "attn_moe", "attn_kan"):
        p["attn"] = A.attn_init(ctx, blk.attn)
        p["ln2"] = L.rmsnorm_init(ctx, d_model)
        if blk.kind == "attn_mlp":
            p["mlp"] = _mlp_init(ctx, d_model, blk.d_ff)
        elif blk.kind == "attn_moe":
            p["moe"] = M.moe_init(ctx, blk.moe)
        else:
            p["kan"] = _kan_ffn_init(ctx, d_model, blk.kan_ff, blk.kan_grid)
    elif blk.kind == "mamba2":
        p["mamba"] = S.mamba2_init(ctx, blk.mamba)
    elif blk.kind == "mlstm":
        p["mlstm"] = X.mlstm_init(ctx, blk.xlstm)
    elif blk.kind == "slstm":
        p["slstm"] = X.slstm_init(ctx, blk.xlstm)
    else:
        raise ValueError(blk.kind)
    return p


def block_apply(
    params: dict,
    blk: BlockCfg,
    x: jax.Array,
    *,
    positions: jax.Array | None = None,
    chunk: int = 1024,
) -> tuple[jax.Array, dict]:
    """Pre-norm residual application; returns (x, aux_losses)."""
    aux: dict = {}
    h = L.rmsnorm(params["ln1"], x)
    if blk.kind in ("attn_mlp", "attn_moe", "attn_kan"):
        x = x + A.attn_forward(params["attn"], blk.attn, h, positions=positions, chunk=chunk)
        h2 = L.rmsnorm(params["ln2"], x)
        if blk.kind == "attn_mlp":
            x = x + _mlp(params["mlp"], h2)
        elif blk.kind == "attn_moe":
            y, aux = M.moe_forward(params["moe"], blk.moe, h2)
            x = x + y
        else:
            x = x + _kan_ffn(params["kan"], h2, blk.kan_grid)
    elif blk.kind == "mamba2":
        x = x + S.mamba2_forward(params["mamba"], blk.mamba, h)
    elif blk.kind == "mlstm":
        x = x + X.mlstm_forward(params["mlstm"], blk.xlstm, h)
    elif blk.kind == "slstm":
        x = x + X.slstm_forward(params["slstm"], blk.xlstm, h)
    return x, aux


# ----------------------------- decode support -------------------------------


def block_init_cache(
    blk: BlockCfg, batch: int, max_seq: int, dtype
) -> dict:
    """Per-block decode state (KV cache / SSM state / LSTM state).

    Windowed attention allocates a ``window``-slot ring buffer; kv_quant
    stores int8 values + per-(token, kv-head) fp32 scales."""
    if blk.kind in ("attn_mlp", "attn_moe", "attn_kan"):
        c = blk.attn
        if c.kv_lora_rank is not None:
            return {
                "ckv": jnp.zeros(
                    (batch, max_seq, c.kv_lora_rank + c.qk_rope_dim), dtype
                )
            }
        S_ = c.cache_len(max_seq)
        kv_dtype = jnp.int8 if c.kv_quant else dtype
        cache = {
            "k": jnp.zeros((batch, S_, c.n_kv_heads, c.head_dim), kv_dtype),
            "v": jnp.zeros((batch, S_, c.n_kv_heads, c.head_dim), kv_dtype),
        }
        if c.kv_quant:
            cache["k_scale"] = jnp.zeros((batch, S_, c.n_kv_heads), jnp.float32)
            cache["v_scale"] = jnp.zeros((batch, S_, c.n_kv_heads), jnp.float32)
        return cache
    if blk.kind == "mamba2":
        return S.mamba2_init_state(blk.mamba, batch, dtype)
    if blk.kind == "mlstm":
        return X.mlstm_init_state(blk.xlstm, batch, dtype)
    if blk.kind == "slstm":
        return X.slstm_init_state(blk.xlstm, batch, dtype)
    raise ValueError(blk.kind)


def block_supports_paging(blk: BlockCfg) -> bool:
    """Paged KV (DESIGN.md §3b) covers full-attention GQA layers: windowed
    ring buffers already bound their cache to ``window`` slots, MLA latents
    and SSM/LSTM states are per-sequence (not per-token) — none of them
    strand per-token HBM the way a dense ``max_seq`` KV row does."""
    return (
        blk.kind in ("attn_mlp", "attn_moe", "attn_kan")
        and blk.attn.kv_lora_rank is None
        and blk.attn.window is None
    )


def block_init_paged_cache(
    blk: BlockCfg, n_blocks: int, block_size: int, dtype
) -> dict:
    """Pool-shaped decode cache: ``(n_blocks, block_size, ...)`` leaves in
    place of :func:`block_init_cache`'s ``(batch, max_seq, ...)`` rows.
    Physical block 0 is the engine's reserved sentinel (``serve/kv_pool.py``).
    """
    if not block_supports_paging(blk):
        raise NotImplementedError(
            f"paged KV cache: unsupported block kind {blk.kind!r} "
            "(full-attention GQA layers only)"
        )
    c = blk.attn
    kv_dtype = jnp.int8 if c.kv_quant else dtype
    cache = {
        "k": jnp.zeros((n_blocks, block_size, c.n_kv_heads, c.head_dim), kv_dtype),
        "v": jnp.zeros((n_blocks, block_size, c.n_kv_heads, c.head_dim), kv_dtype),
    }
    if c.kv_quant:
        cache["k_scale"] = jnp.zeros((n_blocks, block_size, c.n_kv_heads), jnp.float32)
        cache["v_scale"] = jnp.zeros((n_blocks, block_size, c.n_kv_heads), jnp.float32)
    return cache


def block_prefill(
    params: dict,
    blk: BlockCfg,
    x: jax.Array,                  # (B, T, d)
    *,
    positions: jax.Array | None = None,
    max_seq: int,
    chunk: int = 1024,
    shard=None,                    # optional ShardingCtx (mesh serving)
) -> tuple[jax.Array, dict]:
    """Forward + decode-cache production (KV padded to ``max_seq``).

    With ``shard`` the produced cache leaves are constraint-pinned to the
    shardings their logical axes derive (``block_cache_axes``), so a jitted
    sharded prefill hands decode a distributed cache, not a gathered one."""
    B, T, _ = x.shape
    h = L.rmsnorm(params["ln1"], x)
    if blk.kind in ("attn_mlp", "attn_moe", "attn_kan"):
        c = blk.attn
        y, kv = A.attn_forward(
            params["attn"], c, h, positions=positions, chunk=chunk,
            return_cache=True,
        )
        if "k" in kv:  # GQA path: ring placement + optional int8
            S_ = c.cache_len(max_seq)
            if c.window and S_ < T:
                # ring semantics: slot s holds the latest position p < T with
                # p % S_ == s (matches decode's slot = pos % window)
                s_idx = jnp.arange(S_)
                p_s = (T - 1) - ((T - 1 - s_idx) % S_)
                kv = jax.tree.map(lambda a: a[:, p_s], kv)
            elif S_ > T:
                kv = jax.tree.map(
                    lambda a: jnp.pad(
                        a, ((0, 0), (0, S_ - T)) + ((0, 0),) * (a.ndim - 2)
                    ),
                    kv,
                )
            if c.kv_quant:
                kq, ks = A._kv_quantize(kv["k"])
                vq, vs = A._kv_quantize(kv["v"])
                cache = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
            else:
                cache = kv
        else:  # MLA latent cache
            pad = max_seq - T
            cache = jax.tree.map(
                lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0))), kv
            )
        x = x + y
        h2 = L.rmsnorm(params["ln2"], x)
        if blk.kind == "attn_mlp":
            x = x + _mlp(params["mlp"], h2)
        elif blk.kind == "attn_moe":
            y2, _ = M.moe_forward(params["moe"], blk.moe, h2)
            x = x + y2
        else:
            # inference path: fused Pallas kernel on TPU, compact elsewhere
            x = x + _kan_ffn(params["kan"], h2, blk.kan_grid, "auto", shard)
        if shard is not None:
            cache = shard.constrain_tree(cache, block_cache_axes(blk))
        return x, cache
    if blk.kind == "mamba2":
        y, st = S.mamba2_forward(params["mamba"], blk.mamba, h, return_state=True)
    elif blk.kind == "mlstm":
        y, st = X.mlstm_forward(params["mlstm"], blk.xlstm, h, return_state=True)
    elif blk.kind == "slstm":
        y, st = X.slstm_forward(params["slstm"], blk.xlstm, h, return_state=True)
    else:
        raise ValueError(blk.kind)
    if shard is not None:
        st = shard.constrain_tree(st, block_cache_axes(blk))
    return x + y, st


def block_prefill_paged(
    params: dict,
    blk: BlockCfg,
    x: jax.Array,                  # (B, Ts, d) — uncached suffix tokens only
    *,
    positions: jax.Array,          # (B, Ts) absolute positions
    cache: dict,                   # pool leaves (n_blocks, bs, ...)
    table: jax.Array,              # (B, n_logical)
    lengths: jax.Array,            # (B,) true total prompt lengths
    start: jax.Array,              # scalar: first uncached position
    chunk: int = 1024,
    view_blocks: int | None = None,
    shard=None,                    # optional ShardingCtx (mesh serving)
) -> tuple[jax.Array, dict]:
    """Suffix prefill writing K/V straight into pool blocks — the paged
    counterpart of :func:`block_prefill` (which pads a private cache row to
    ``max_seq`` for splicing).  Prefix-cache hits enter with ``start > 0``
    and skip the cached positions entirely."""
    if not block_supports_paging(blk):
        raise NotImplementedError(f"paged prefill: unsupported kind {blk.kind!r}")
    h = L.rmsnorm(params["ln1"], x)
    y, cache = A.attn_prefill_paged(
        params["attn"], blk.attn, h, positions, cache, table, lengths, start,
        chunk=chunk, view_blocks=view_blocks, shard=shard,
    )
    x = x + y
    h2 = L.rmsnorm(params["ln2"], x)
    if blk.kind == "attn_mlp":
        x = x + _mlp(params["mlp"], h2)
    elif blk.kind == "attn_moe":
        y2, _ = M.moe_forward(params["moe"], blk.moe, h2)
        x = x + y2
    else:
        # same batch-regime-aware inference path as block_prefill — row
        # counts differ (suffix only), but every KAN method is row-wise
        x = x + _kan_ffn(params["kan"], h2, blk.kan_grid, "auto", shard)
    return x, cache


def block_paged_cache_axes(blk: BlockCfg) -> dict:
    """Logical axes of the pool-shaped cache (mirrors
    :func:`block_init_paged_cache`): the batch axis is gone — sharding can
    split the pool along ``kv_blocks`` (the paged analogue of
    ``seq_cache``) or the head axes."""
    from repro.models.layers import Axes

    assert block_supports_paging(blk)
    axes = {
        "k": Axes(("kv_blocks", None, "kv_heads", "head_dim")),
        "v": Axes(("kv_blocks", None, "kv_heads", "head_dim")),
    }
    if blk.attn.kv_quant:
        axes["k_scale"] = Axes(("kv_blocks", None, "kv_heads"))
        axes["v_scale"] = Axes(("kv_blocks", None, "kv_heads"))
    return axes


def block_cache_axes(blk: BlockCfg) -> dict:
    """Logical axes of the decode state (mirrors block_init_cache).

    ``seq_cache`` lets long-context decode shard the KV cache's sequence dim
    on the data axis when the batch cannot occupy it (long_500k, B=1).
    """
    from repro.models.layers import Axes

    if blk.kind in ("attn_mlp", "attn_moe", "attn_kan"):
        if blk.attn.kv_lora_rank is not None:
            return {"ckv": Axes(("batch", "seq_cache", "kv_lora"))}
        axes = {
            "k": Axes(("batch", "seq_cache", "kv_heads", "head_dim")),
            "v": Axes(("batch", "seq_cache", "kv_heads", "head_dim")),
        }
        if blk.attn.kv_quant:
            axes["k_scale"] = Axes(("batch", "seq_cache", "kv_heads"))
            axes["v_scale"] = Axes(("batch", "seq_cache", "kv_heads"))
        return axes
    if blk.kind == "mamba2":
        return {
            "ssm": Axes(("batch", "heads", "state", "head_dim")),
            "conv": Axes(("batch", None, "ffn")),
        }
    if blk.kind == "mlstm":
        return {
            "C": Axes(("batch", "heads", "head_dim", "head_dim")),
            "n": Axes(("batch", "heads", "head_dim")),
            "m": Axes(("batch", "heads")),
            "conv": Axes(("batch", None, "ffn")),
        }
    if blk.kind == "slstm":
        # sLSTM gates are per-unit: all four state tensors are (B, H, hd)
        ax = Axes(("batch", "heads", "head_dim"))
        return {"c": ax, "n": ax, "h": ax, "m": ax}
    raise ValueError(blk.kind)


def block_decode_step(
    params: dict,
    blk: BlockCfg,
    x: jax.Array,               # (B, 1, d)
    cache: dict,
    pos: jax.Array,             # (B,)
    table: jax.Array | None = None,   # (B, n_logical): paged block table
    shard=None,                 # optional ShardingCtx (mesh serving)
    layer=None,                 # index into the unit's stacked leaves
) -> tuple[jax.Array, dict]:
    """One decode step of one block.

    With ``layer`` set, ``cache`` holds the unit's stacked leaves (a
    leading layers axis), carried through the decode layer scan, and this
    block is layer ``layer`` of them.  GQA attention (full or windowed,
    dense or paged, bf16 or int8) writes its token into the stacked K/V in
    place and reads its own layer back; blocks whose state is per sequence
    (MLA's latent, mamba2, xLSTM) slice their layer, step it and put it
    back."""
    gqa = (blk.kind in ("attn_mlp", "attn_moe", "attn_kan")
           and blk.attn.kv_lora_rank is None)
    if layer is not None and not gqa:
        one = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False),
            cache)
        x, one = block_decode_step(params, blk, x, one, pos, table, shard)
        return x, jax.tree.map(
            lambda a, o: jax.lax.dynamic_update_index_in_dim(a, o, layer, 0),
            cache, one)
    h = L.rmsnorm(params["ln1"], x)
    if table is not None and not block_supports_paging(blk):
        raise NotImplementedError(f"paged decode: unsupported kind {blk.kind!r}")
    if blk.kind in ("attn_mlp", "attn_moe", "attn_kan"):
        c = blk.attn
        if table is not None:
            y, cache = A.attn_decode_step_paged(
                params["attn"], c, h, cache, table, pos, shard=shard,
                layer=layer,
            )
        elif c.kv_lora_rank is not None:
            # shard threads into the step itself (the latent is pinned at
            # the write, like the GQA paths) — no caller-side special case
            y, ckv = A.mla_decode_step(
                params["attn"], c, h, cache["ckv"], pos, shard=shard
            )
            cache = {"ckv": ckv}
        else:
            y, cache = A.attn_decode_step(
                params["attn"], c, h, cache, pos, shard=shard, layer=layer
            )
        x = x + y
        h2 = L.rmsnorm(params["ln2"], x)
        if blk.kind == "attn_mlp":
            x = x + _mlp(params["mlp"], h2)
        elif blk.kind == "attn_moe":
            y2, _ = M.moe_forward(params["moe"], blk.moe, h2)
            x = x + y2
        else:
            # inference path: fused Pallas kernel on TPU, compact elsewhere
            x = x + _kan_ffn(params["kan"], h2, blk.kan_grid, "auto", shard)
        return x, cache
    if blk.kind == "mamba2":
        y, cache = S.mamba2_decode_step(params["mamba"], blk.mamba, h, cache)
    elif blk.kind == "mlstm":
        y, cache = X.mlstm_decode_step(params["mlstm"], blk.xlstm, h, cache)
    elif blk.kind == "slstm":
        y, cache = X.slstm_decode_step(params["slstm"], blk.xlstm, h, cache)
    else:
        raise ValueError(blk.kind)
    if shard is not None:
        cache = shard.constrain_tree(cache, block_cache_axes(blk))
    return x + y, cache


def block_verify_window(
    params: dict,
    blk: BlockCfg,
    x: jax.Array,               # (B, W, d) — last accepted token + k drafts
    cache: dict,
    pos: jax.Array,             # (B,) window start positions
    table: jax.Array | None = None,   # (B, n_logical): paged block table
    shard=None,                 # optional ShardingCtx (mesh serving)
) -> tuple[jax.Array, dict]:
    """Speculative verify: :func:`block_decode_step` for a W-token window in
    one batch-shaped pass.  Restricted to the paged-capable block set (full
    attention GQA) — ring buffers and recurrent states are inherently
    sequential in the window dimension.  The FFN sees ``B·W`` rows, so
    ``method="auto"`` resolves to the *fused* kernel regime on TPU — the
    shape conversion speculative decoding exists to buy (DESIGN.md §9)."""
    if not block_supports_paging(blk):
        raise NotImplementedError(
            f"speculative verify: unsupported kind {blk.kind!r} "
            "(full-attention GQA layers only)"
        )
    h = L.rmsnorm(params["ln1"], x)
    c = blk.attn
    if table is not None:
        y, cache = A.attn_verify_window_paged(
            params["attn"], c, h, cache, table, pos, shard=shard
        )
    else:
        y, cache = A.attn_verify_window(
            params["attn"], c, h, cache, pos, shard=shard
        )
    x = x + y
    h2 = L.rmsnorm(params["ln2"], x)
    if blk.kind == "attn_mlp":
        x = x + _mlp(params["mlp"], h2)
    elif blk.kind == "attn_moe":
        y2, _ = M.moe_forward(params["moe"], blk.moe, h2)
        x = x + y2
    else:
        x = x + _kan_ffn(params["kan"], h2, blk.kan_grid, "auto", shard)
    return x, cache
