"""Attention: GQA/MQA, MLA (DeepSeek-V2), sliding-window, flash-style
chunked softmax, and cached decode steps.

The training/prefill path uses a memory-efficient blockwise attention
(online softmax over KV chunks under ``lax.scan``) so 32k-token prefill
compiles with bounded live memory — no TPU kernel required for the dry-run
(and cost_analysis stays complete; see DESIGN.md §7).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import layers as L
from repro.models.layers import ParamCtx

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    window: int | None = None        # sliding-window size (gemma3 local layers)
    rope_theta: float = 10000.0
    qk_norm: bool = False            # gemma3-style RMS q/k norm
    # MLA (deepseek-v2): when kv_lora_rank is set, K/V come from a shared
    # compressed latent that is also what the serving cache stores.
    kv_lora_rank: int | None = None
    qk_rope_dim: int = 64
    # Serving-memory features (DESIGN.md §4):
    # * windowed layers allocate a RING BUFFER of `window` slots instead of
    #   max_seq (gemma3 local layers: 32x cache cut at 32k);
    # * kv_quant stores the cache in int8 with a per-(token, kv-head) fp32
    #   scale (2x over bf16; what makes qwen1.5-32b decode_32k fit 16 GB).
    kv_quant: bool = False
    # Sequence-parallel attention (SecPerf iteration 5, prefill/train): shard
    # the QUERY sequence over the given spec (e.g. (("data",), "model", None,
    # None)) and replicate K/V over the model axis. The right call when
    # heads/kv_heads cannot shard (paligemma MQA: kv=1, 8 heads vs model=16)
    # — each shard attends its query block against full (tiny) KV instead of
    # all-reducing (B,H,T,T) score partials.
    sp_spec: tuple | None = None

    def cache_len(self, max_seq: int) -> int:
        return min(max_seq, self.window) if self.window else max_seq


def attn_init(ctx: ParamCtx, cfg: AttnConfig) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if cfg.kv_lora_rank is None:
        p = {
            "wq": ctx.make((d, h, hd), ("embed", "heads", "head_dim")),
            "wk": ctx.make((d, kv, hd), ("embed", "kv_heads", "head_dim")),
            "wv": ctx.make((d, kv, hd), ("embed", "kv_heads", "head_dim")),
            "wo": ctx.make((h, hd, d), ("heads", "head_dim", "embed")),
        }
        if cfg.qkv_bias:
            p["bq"] = ctx.make((h, hd), ("heads", "head_dim"), init="zeros")
            p["bk"] = ctx.make((kv, hd), ("kv_heads", "head_dim"), init="zeros")
            p["bv"] = ctx.make((kv, hd), ("kv_heads", "head_dim"), init="zeros")
    else:
        r, rope = cfg.kv_lora_rank, cfg.qk_rope_dim
        nope = hd  # qk_nope dim == head_dim (v_head_dim == head_dim too)
        p = {
            "wq": ctx.make((d, h, nope + rope), ("embed", "heads", "head_dim")),
            "w_dkv": ctx.make((d, r + rope), ("embed", "kv_lora")),
            "w_uk": ctx.make((r, h, nope), ("kv_lora", "heads", "head_dim")),
            "w_uv": ctx.make((r, h, hd), ("kv_lora", "heads", "head_dim")),
            "wo": ctx.make((h, hd, d), ("heads", "head_dim", "embed")),
            "kv_norm": ctx.make((r,), ("kv_lora",), init="ones"),
        }
    if cfg.qk_norm:
        p["q_norm"] = ctx.make((hd,), ("head_dim",), init="ones")
        p["k_norm"] = ctx.make((hd,), ("head_dim",), init="ones")
    return p


def _qk_rmsnorm(x: jax.Array, scale: jax.Array) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + 1e-6) * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Blockwise (flash-style) attention.
# ---------------------------------------------------------------------------


def flash_attention(
    q: jax.Array,                  # (B, Tq, H, D)
    k: jax.Array,                  # (B, Tk, KV, D)
    v: jax.Array,                  # (B, Tk, KV, D)
    *,
    causal: bool = True,
    window: int | None = None,
    q_offset: int = 0,             # absolute position of q[0] (decode/prefill)
    chunk: int = 1024,
) -> jax.Array:
    """Online-softmax attention over KV chunks; O(Tq·chunk) live memory.

    K and V may have different head dims (MLA: K carries nope+rope, V not).
    """
    B, Tq, H, D = q.shape
    Tk, KV = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    rep = H // KV
    scale = 1.0 / math.sqrt(D)
    chunk = min(chunk, Tk)
    n_chunks = math.ceil(Tk / chunk)
    pad = n_chunks * chunk - Tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(B, n_chunks, chunk, KV, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, chunk, KV, Dv).transpose(1, 0, 2, 3, 4)

    q_pos = q_offset + jnp.arange(Tq)
    qh = q.reshape(B, Tq, KV, rep, D)

    def step(carry, inp):
        m, l, acc = carry
        ci, (kb, vb) = inp
        kv_pos = ci * chunk + jnp.arange(chunk)
        s = jnp.einsum("btgrd,bsgd->bgrts", qh, kb) * scale   # (B,KV,rep,Tq,chunk)
        mask = kv_pos[None, :] <= Tk - 1  # drop padded keys
        if causal:
            mask = mask & (kv_pos[None, :] <= q_pos[:, None])
        if window is not None:
            mask = mask & (kv_pos[None, :] > q_pos[:, None] - window)
        s = jnp.where(mask[None, None, None], s.astype(jnp.float32), NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bgrts,bsgd->bgrtd", p.astype(vb.dtype), vb
        ).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, rep, Tq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, rep, Tq), jnp.float32)
    a0 = jnp.zeros((B, KV, rep, Tq, Dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0), (jnp.arange(n_chunks), (kc, vc))
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Tq, H, Dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# Full attention blocks (projections + rotary + flash) and decode steps.
# ---------------------------------------------------------------------------


def _project_qkv(
    params: dict, cfg: AttnConfig, x: jax.Array, positions: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """QKV projection + bias + q/k norm + rotary at absolute ``positions``
    — the per-position math shared by full prefill (:func:`attn_forward`),
    paged suffix prefill (:func:`attn_prefill_paged`) and decode steps.
    One definition is what makes the three paths agree bit-for-bit on every
    K/V value (the paged bit-identity contract, DESIGN.md §3b)."""
    q = jnp.einsum("btd,dhk->bthk", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("btd,dhk->bthk", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("btd,dhk->bthk", x, params["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    if cfg.qk_norm:
        q = _qk_rmsnorm(q, params["q_norm"])
        k = _qk_rmsnorm(k, params["k_norm"])
    cos, sin = L.rotary_embedding(positions, cfg.head_dim, cfg.rope_theta, x.dtype)
    q = L.apply_rotary(q, cos, sin)
    k = L.apply_rotary(k, cos, sin)
    return q, k, v


def attn_forward(
    params: dict,
    cfg: AttnConfig,
    x: jax.Array,                   # (B, T, d_model)
    *,
    positions: jax.Array | None = None,
    chunk: int = 1024,
    return_cache: bool = False,
):
    """Training/prefill attention. With ``return_cache``, also returns the
    post-rotary K/V (or the MLA latent) — exactly what the decode cache
    stores, so prefill fills caches for free."""
    B, T, _ = x.shape
    if positions is None:
        positions = jnp.arange(T)[None, :]
    if cfg.kv_lora_rank is not None:
        return _mla_forward(params, cfg, x, positions, chunk, return_cache)
    q, k, v = _project_qkv(params, cfg, x, positions)
    if cfg.sp_spec is not None:
        from jax.sharding import PartitionSpec as _P

        bspec = cfg.sp_spec[0]
        q = jax.lax.with_sharding_constraint(q, _P(*cfg.sp_spec))
        k = jax.lax.with_sharding_constraint(k, _P(bspec, None, None, None))
        v = jax.lax.with_sharding_constraint(v, _P(bspec, None, None, None))
    o = flash_attention(q, k, v, causal=True, window=cfg.window, chunk=chunk)
    if cfg.sp_spec is not None:
        from jax.sharding import PartitionSpec as _P

        o = jax.lax.with_sharding_constraint(o, _P(*cfg.sp_spec))
    y = jnp.einsum("bthk,hkd->btd", o, params["wo"].astype(x.dtype))
    if return_cache:
        return y, {"k": k, "v": v}
    return y


def _mla_forward(params, cfg: AttnConfig, x, positions, chunk, return_cache=False):
    """DeepSeek-V2 Multi-head Latent Attention (training/prefill)."""
    B, T, _ = x.shape
    hd, rope = cfg.head_dim, cfg.qk_rope_dim
    q = jnp.einsum("btd,dhk->bthk", x, params["wq"].astype(x.dtype))
    q_nope, q_rope = q[..., :hd], q[..., hd:]
    ckv = x @ params["w_dkv"].astype(x.dtype)          # (B, T, r + rope)
    c_kv, k_rope_raw = ckv[..., : cfg.kv_lora_rank], ckv[..., cfg.kv_lora_rank:]
    c_kv = _qk_rmsnorm(c_kv, params["kv_norm"])
    k_nope = jnp.einsum("btr,rhk->bthk", c_kv, params["w_uk"].astype(x.dtype))
    v = jnp.einsum("btr,rhk->bthk", c_kv, params["w_uv"].astype(x.dtype))
    cos, sin = L.rotary_embedding(positions, rope, cfg.rope_theta, x.dtype)
    q_rope = L.apply_rotary(q_rope, cos, sin)
    k_rope = L.apply_rotary(k_rope_raw[..., None, :], cos, sin)
    k_rope1 = k_rope[..., 0, :]                        # (B, T, rope), shared
    k_rope = jnp.broadcast_to(k_rope, k_nope.shape[:-1] + (rope,))
    q_full = jnp.concatenate([q_nope, q_rope], -1)
    k_full = jnp.concatenate([k_nope, k_rope], -1)
    o = flash_attention(q_full, k_full, v, causal=True, chunk=chunk)
    y = jnp.einsum("bthk,hkd->btd", o, params["wo"].astype(x.dtype))
    if return_cache:
        # cache stores the *unnormalised* latent + rotated rope key, matching
        # mla_decode_step's layout
        ckv_cache = jnp.concatenate([ckv[..., : cfg.kv_lora_rank], k_rope1], -1)
        return y, {"ckv": ckv_cache}
    return y


def _kv_quantize(k: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(..., KV, D) -> int8 values + per-(..., KV) fp32 scale."""
    scale = jnp.maximum(jnp.max(jnp.abs(k.astype(jnp.float32)), -1), 1e-6) / 127.0
    q = jnp.clip(jnp.round(k.astype(jnp.float32) / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def _write_cache(cache: dict, name: str, val: jax.Array, slot: jax.Array,
                 quant: bool, layer=None):
    """Write one token's K or V into the (ring) cache at ``slot``.

    Two regimes (EXPERIMENTS.md §Perf iterations 1 & 4):

    * **synchronized decode** (scalar ``slot`` — every sequence at the same
      position, the common serving case): ``dynamic_update_slice`` — GSPMD
      keeps it fully local under any cache sharding (no collectives);
    * **ragged decode** (per-batch ``slot``, continuous batching): indexed
      scatter. GSPMD's scatter partitioning all-gathers a batch-sharded
      operand (measured: 7.06 GB/step on gemma3 decode_32k), so ragged mode
      costs collectives — the engine uses synchronized buckets by default.

    Both replace the original masked-arithmetic update, which materialised
    two cache-sized temporaries (+13 GB/device on qwen1.5-32b decode_32k).
    With ``layer`` the leaves are the unit's stacked caches ``(R, B, S,
    ...)`` and the token lands at ``[layer, b, slot]``, in place.
    """
    if quant:
        qv, sc = _kv_quantize(val)                            # (B,1,KV,D)
        cache[name] = _put_token(cache[name], qv, slot, layer)
        cache[name + "_scale"] = _put_token(
            cache[name + "_scale"], sc, slot, layer)
    else:
        cache[name] = _put_token(
            cache[name], val.astype(cache[name].dtype), slot, layer)
    return cache


def _put_token(a: jax.Array, u: jax.Array, slot: jax.Array, layer) -> jax.Array:
    """``a[(layer,) b, slot[b]] = u[b, 0]`` for one-token values ``u (B, 1,
    ...)``: a dynamic update slice for a scalar ``slot``, a scatter for a
    per-row one."""
    lead = () if layer is None else (layer,)
    if slot.ndim == 0:
        return jax.lax.dynamic_update_slice(
            a, u.reshape((1,) * len(lead) + u.shape),
            lead + (0, slot) + (0,) * (u.ndim - 2))
    return a.at[lead + (jnp.arange(u.shape[0]), slot)].set(u[:, 0])


def _layer_view(cache: dict, layer) -> dict:
    """Layer ``layer`` of a stacked cache tree: the ``(B, S, ...)`` leaves
    one block attends over."""
    return {k: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
            for k, a in cache.items()}


# Logical axes of each GQA cache-dict leaf, for with_sharding_constraint
# under an optional ShardingCtx (``repro.dist.sharding``, duck-typed so the
# models package stays import-free of the dist package): dense rows (and
# gathered paged VIEWS) carry (batch, seq_cache, ...), pools carry
# (kv_blocks, ...) — the same names ``blocks.block_cache_axes``/
# ``block_paged_cache_axes`` export.  ``models/lm.py`` reuses these tables
# for its pool/view constraints — ONE definition per layout.
DENSE_CACHE_AXES = {
    "k": ("batch", "seq_cache", "kv_heads", "head_dim"),
    "v": ("batch", "seq_cache", "kv_heads", "head_dim"),
    "k_scale": ("batch", "seq_cache", "kv_heads"),
    "v_scale": ("batch", "seq_cache", "kv_heads"),
}
POOL_CACHE_AXES = {
    "k": ("kv_blocks", None, "kv_heads", "head_dim"),
    "v": ("kv_blocks", None, "kv_heads", "head_dim"),
    "k_scale": ("kv_blocks", None, "kv_heads"),
    "v_scale": ("kv_blocks", None, "kv_heads"),
}


def _constrain_cache(cache: dict, shard, paged: bool,
                     stacked: bool = False) -> dict:
    """Pin freshly written cache leaves to their logical-axes shardings so
    GSPMD keeps KV distributed through decode updates (no-op without a
    ``shard`` ctx, and bit-identical under a 1-device mesh).  ``stacked``
    leaves carry the unit's leading 'layers' axis."""
    if shard is None:
        return cache
    table = POOL_CACHE_AXES if paged else DENSE_CACHE_AXES
    lead = ("layers",) if stacked else ()
    return {k: shard.constrain(v, lead + table[k]) for k, v in cache.items()}


def _read_cache(cache: dict, name: str, quant: bool, dtype):
    if quant:
        return (
            cache[name].astype(jnp.float32) * cache[name + "_scale"][..., None]
        ).astype(dtype)
    return cache[name].astype(dtype)


def attn_decode_step(
    params: dict,
    cfg: AttnConfig,
    x: jax.Array,                   # (B, 1, d_model)
    cache: dict,                    # {"k","v"[, "k_scale","v_scale"]}
    pos: jax.Array,                 # (B,) current absolute position
    shard=None,                     # optional ShardingCtx (mesh serving)
    layer=None,                     # index into stacked (R, B, S, ...) leaves
) -> tuple[jax.Array, dict]:
    """One-token decode against a pre-filled KV cache.

    Windowed layers use a ring buffer: slot = pos % window. Rotary is applied
    *before* caching, so scores never need absolute slot positions; validity
    is "slot written", which is within-window by construction.

    ``pos`` may be scalar (synchronized decode — collective-free cache
    writes) or per-batch ``(B,)`` (ragged/continuous batching).  With a
    ``shard`` ctx the updated cache leaves are constraint-pinned to their
    logical-axes shardings (kv_heads on ``model``, batch on ``data``).

    With ``layer`` the leaves are the unit's stacked caches, carried
    through the decode layer scan: the token is written at ``[layer, b,
    slot]`` in place and layer ``layer`` is read back for the attention,
    so no cache-sized slice or stacked output is built per layer.
    """
    B = x.shape[0]
    S = cache["k"].shape[1 if layer is None else 2]
    pos_b = jnp.broadcast_to(pos, (B,))          # per-batch view for masks
    q, k, v = _project_qkv(params, cfg, x, pos_b[:, None])
    slot = pos % S if cfg.window else pos
    cache = dict(cache)
    cache = _write_cache(cache, "k", k, slot, cfg.kv_quant, layer)
    cache = _write_cache(cache, "v", v, slot, cfg.kv_quant, layer)
    cache = _constrain_cache(cache, shard, paged=False,
                             stacked=layer is not None)
    view = cache if layer is None else _layer_view(cache, layer)
    y = _cache_attend(params, cfg, x, view, q, pos_b)
    return y, cache


def _cache_attend(
    params: dict,
    cfg: AttnConfig,
    x: jax.Array,                   # (B, 1, d_model)
    cache: dict,                    # (B, S, ...) leaves — dense OR paged view
    q: jax.Array,                   # (B, 1, H, D) post-rotary query
    pos_b: jax.Array,               # (B,)
) -> jax.Array:
    """The decode attention *read*: one-shot softmax (fp caches) or chunked
    flash-decode with fused dequant (int8 caches) over a ``(B, S, ...)``
    cache tree.  Shared verbatim by the dense contiguous cache and the
    paged path (which first materialises the logical view with the
    block-table gather) — running the identical program on bit-identical
    values is what makes paged decode bit-equal to dense decode."""
    B = x.shape[0]
    S = cache["k"].shape[1]
    H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    rep = H // KV
    qh = q.reshape(B, KV, rep, D)
    if not cfg.kv_quant:
        # One-shot attention read: decode scores are only (B,KV,rep,S) —
        # small — and a single einsum + softmax lets GSPMD run the
        # distributed-softmax pattern when the cache is seq-sharded
        # (SecPerf iteration 4). Chunking is only needed to bound the
        # dequantisation temp of int8 caches (below).
        ck = cache["k"].astype(x.dtype)
        cv = cache["v"].astype(x.dtype)
        s = jnp.einsum("bgrd,bsgd->bgrs", qh, ck) / math.sqrt(D)
        kv_slot = jnp.arange(S)[None, :]
        if cfg.window:
            mask = (kv_slot <= pos_b[:, None]) | (pos_b[:, None] >= S)
        else:
            mask = kv_slot <= pos_b[:, None]
        s = jnp.where(mask[:, None, None], s.astype(jnp.float32), NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bgrs,bsgd->bgrd", p.astype(cv.dtype), cv)
        o = o.reshape(B, 1, H, D).astype(x.dtype)
        return jnp.einsum("bthk,hkd->btd", o, params["wo"].astype(x.dtype))
    # int8 cache: flash-decode chunks bound the dequant temp
    # (EXPERIMENTS.md SecPerf iteration 1: -21 GB on qwen1.5-32b decode_32k)
    chunk = min(8192, S)
    n_chunks = (S + chunk - 1) // chunk
    assert S % chunk == 0 or n_chunks == 1, "cache length is chunk-aligned"

    def read_chunk(name, ci):
        raw = jax.lax.dynamic_slice_in_dim(cache[name], ci * chunk, chunk, 1)
        if cfg.kv_quant:
            sc = jax.lax.dynamic_slice_in_dim(
                cache[name + "_scale"], ci * chunk, chunk, 1
            )
            return (raw.astype(jnp.float32) * sc[..., None]).astype(x.dtype)
        return raw.astype(x.dtype)

    def step(carry, ci):
        m_p, l_p, acc_p = carry
        kb = read_chunk("k", ci)                              # (B,chunk,KV,D)
        vb = read_chunk("v", ci)
        s = jnp.einsum("bgrd,bsgd->bgrs", qh, kb) / math.sqrt(D)
        kv_slot = ci * chunk + jnp.arange(chunk)[None, :]
        if cfg.window:
            mask = (kv_slot <= pos_b[:, None]) | (pos_b[:, None] >= S)
        else:
            mask = kv_slot <= pos_b[:, None]
        s = jnp.where(mask[:, None, None], s.astype(jnp.float32), NEG_INF)
        m_new = jnp.maximum(m_p, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_p - m_new)
        l_new = l_p * corr + p.sum(-1)
        acc = acc_p * corr[..., None] + jnp.einsum(
            "bgrs,bsgd->bgrd", p.astype(vb.dtype), vb
        ).astype(jnp.float32)
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, KV, rep), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, rep), jnp.float32)
    a0 = jnp.zeros((B, KV, rep, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), jnp.arange(n_chunks))
    o = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(x.dtype)
    o = o.reshape(B, 1, H, D)
    return jnp.einsum("bthk,hkd->btd", o, params["wo"].astype(x.dtype))


# ---------------------------------------------------------------------------
# Paged KV cache (DESIGN.md §3b): pool + block-table addressing.
#
# Layout: each cache leaf becomes a POOL ``(n_blocks, block_size, ...)``
# shared by all batch rows; a per-row block table ``(B, n_logical)`` maps
# logical block l of row b to a physical block (``n_logical * block_size ==
# max_seq``).  Physical block 0 is the reserved sentinel: unassigned table
# entries point at it and out-of-coverage writes are redirected into it —
# its contents are finite garbage that the causal mask annihilates exactly
# (``exp(NEG_INF - m) == 0.0`` in fp32), so reads through it can never
# perturb live rows.  The read path gathers the logical ``(B, max_seq,
# ...)`` view (Pallas block-table gather on TPU, ``jnp.take`` elsewhere —
# ``kernels/paged_gather.py``) and then runs the UNCHANGED dense math
# (:func:`_cache_attend` / :func:`flash_attention`), which is what makes
# paged serving bit-identical to the dense contiguous cache.
# ---------------------------------------------------------------------------


def gather_pool(
    pool: jax.Array, table: jax.Array, names: tuple, shard=None,
    stacked: bool = False,
) -> jax.Array:
    """One pool leaf's logical view ``(B, L·bs, ...)`` through the
    block-table gather kernel (``stacked``: a leading layers axis, gathered
    per layer).  ``names`` are the leaf's :data:`POOL_CACHE_AXES`.

    On a multi-device mesh the kernel runs per device under
    ``shard.kernel_map``: table rows split like the view's batch axis, the
    pool's trailing axes keep their sharding (``kv_heads`` on ``model``),
    and every device sees all blocks (a block axis sharded over the data
    axes is gathered first)."""
    from repro.kernels.paged_gather import gather_blocks

    def gather(p, t):
        if stacked:
            return jax.vmap(lambda q: gather_blocks(q, t))(p)
        return gather_blocks(p, t)

    if shard is None or shard.n_devices == 1:
        return gather(pool, table)
    lead = (None,) if stacked else ()
    rest = tuple(shard.spec(names, pool.shape[len(lead):]))[1:]
    r = shard.row_axis(table.shape[0])
    return shard.kernel_map(
        gather, in_specs=(P(*lead, None, *rest), P(r, None)),
        out_specs=P(*lead, r, *rest),
    )(pool, table)


def paged_view(cache: dict, table: jax.Array, shard=None) -> dict:
    """Materialise the logical contiguous view of a paged cache tree:
    pools ``(n_blocks, bs, ...)`` + table ``(B, L)`` -> ``(B, L·bs, ...)``
    leaves, gathered with the block-table kernel."""
    return {
        name: gather_pool(pool, table, POOL_CACHE_AXES[name], shard)
        for name, pool in cache.items()
    }


def paged_route(
    table: jax.Array,               # (B, L) block table
    positions: jax.Array,           # (B, T) absolute cache positions
    block_size: int,
    valid: jax.Array | None = None, # extra (B, T) mask (e.g. pad positions)
) -> tuple[jax.Array, jax.Array]:
    """THE block-table write routing: absolute positions -> ``(phys, off)``
    scatter targets.  Positions past the table span — and any caller-masked
    positions — are redirected to the sentinel block 0.  Every paged write
    path (per-token, prefill span, shadow-chunk writeback) routes through
    this one definition, because the sentinel-redirect invariant is what
    the paged bit-identity contract stands on."""
    L = table.shape[1]
    lb = jnp.minimum(positions // block_size, L - 1)
    ok = positions < L * block_size
    if valid is not None:
        ok = ok & valid
    phys = jnp.where(ok, jnp.take_along_axis(table, lb, axis=1), 0)
    return phys, positions % block_size


def _paged_write_token(
    cache: dict, name: str, val: jax.Array, table: jax.Array,
    pos_b: jax.Array, quant: bool, layer=None,
) -> dict:
    """Write one decode token's K or V into its pool block: the T=1 case of
    :func:`paged_write_span` (per-row start ``pos_b``, every position
    real).  ``lengths = pos_b + 1`` makes the span's pad mask vacuous while
    keeping its out-of-coverage sentinel redirect — one definition of the
    write routing the bit-identity contract depends on."""
    return paged_write_span(cache, name, val, table, pos_b, pos_b + 1, quant,
                            layer)


def paged_write_span(
    cache: dict, name: str, val: jax.Array, table: jax.Array,
    start: jax.Array, lengths: jax.Array, quant: bool, layer=None,
) -> dict:
    """Scatter a span of K or V into pool blocks.

    ``val (B, T, KV, D)`` holds positions ``start + t`` (``start`` scalar —
    grouped admission prefill — or per-row ``(B,)`` — decode steps); rows
    are right-padded — positions ``>= lengths[b]`` are redirected to the
    sentinel block so pad K/V never lands in a real block (the dense path
    keeps pad KV in its private row, where causality hides it; a shared
    pool has no private rows, so pads must be discarded at write time).
    The same redirect absorbs positions past the table span: fixed-shape
    chunks overrun finished rows, and retired slots' table rows are reset
    to sentinel — duplicate sentinel writes are unordered but the sentinel
    is never attendable.  With ``layer`` the pools are the unit's stacked
    pools ``(R, n_blocks, bs, ...)`` and the span lands in layer ``layer``.
    """
    pool = cache[name]
    B, T = val.shape[:2]
    bs = pool.shape[1 if layer is None else 2]
    starts = jnp.reshape(jnp.asarray(start), (-1, 1))      # scalar or (B,)
    positions = jnp.broadcast_to(starts + jnp.arange(T)[None, :], (B, T))
    phys, off = paged_route(table, positions, bs,
                            valid=positions < lengths[:, None])
    at = (phys, off) if layer is None else (layer, phys, off)
    if quant:
        qv, sc = _kv_quantize(val)
        cache[name] = pool.at[at].set(qv)
        cache[name + "_scale"] = cache[name + "_scale"].at[at].set(sc)
    else:
        cache[name] = pool.at[at].set(val.astype(pool.dtype))
    return cache


def attn_decode_step_paged(
    params: dict,
    cfg: AttnConfig,
    x: jax.Array,                   # (B, 1, d_model)
    cache: dict,                    # POOL leaves (n_blocks, bs, ...)
    table: jax.Array,               # (B, n_logical) int32 block table
    pos: jax.Array,                 # (B,) absolute positions
    shard=None,                     # optional ShardingCtx (mesh serving)
    layer=None,                     # index into stacked (R, n_blocks, ...) pools
) -> tuple[jax.Array, dict]:
    """One-token decode against the paged pool: identical QKV math, writes
    routed through the block table, then :func:`_cache_attend` on the
    gathered logical view — bit-identical to :func:`attn_decode_step` on
    the dense contiguous cache (tested in ``tests/test_kv_pool.py``).
    With ``layer`` the pools are the unit's stacked pools, written in place
    at ``layer`` and gathered from that layer alone."""
    assert cfg.window is None and cfg.kv_lora_rank is None, (
        "paged KV supports full-attention GQA layers only"
    )
    B = x.shape[0]
    pos_b = jnp.broadcast_to(pos, (B,))
    q, k, v = _project_qkv(params, cfg, x, pos_b[:, None])
    cache = dict(cache)
    cache = _paged_write_token(cache, "k", k, table, pos_b, cfg.kv_quant, layer)
    cache = _paged_write_token(cache, "v", v, table, pos_b, cfg.kv_quant, layer)
    cache = _constrain_cache(cache, shard, paged=True, stacked=layer is not None)
    pools = cache if layer is None else _layer_view(cache, layer)
    y = _cache_attend(params, cfg, x, paged_view(pools, table, shard), q, pos_b)
    return y, cache


def attn_prefill_paged(
    params: dict,
    cfg: AttnConfig,
    x: jax.Array,                   # (B, Ts, d_model) — the UNCACHED suffix
    positions: jax.Array,           # (B, Ts) absolute positions (start + t)
    cache: dict,                    # POOL leaves (n_blocks, bs, ...)
    table: jax.Array,               # (B, n_logical)
    lengths: jax.Array,             # (B,) true total prompt lengths
    start: jax.Array,               # scalar: first uncached position
    chunk: int = 1024,
    view_blocks: int | None = None, # static: table columns the attention
                                    # view needs (covers start + T); None =
                                    # all (the full max_seq view)
    shard=None,                     # optional ShardingCtx (mesh serving)
) -> tuple[jax.Array, dict]:
    """Suffix prefill into pool blocks: the prefix-cache hit path computes
    only positions ``start..len-1`` (a prefix hit makes ``start > 0``).

    Attention runs over the logical view with the freshly computed span
    **overlaid raw** (``dynamic_update_slice`` at ``start``): positions
    ``< start`` come from reused blocks (bit-equal to a full prefill's
    values by induction), the suffix attends its own raw K/V exactly as a
    full prefill would — including under ``kv_quant``, where the pool
    stores int8 but prefill attention must see raw values to stay
    bit-identical to the dense path (which only quantizes at cache-store
    time).  Chunks beyond a query's causal range are exact no-ops in the
    online softmax (``corr == exp(0) == 1``), so the view's ``max_seq``
    length vs. the dense path's padded prompt length cannot change a single
    bit.
    """
    assert cfg.window is None and cfg.kv_lora_rank is None, (
        "paged KV supports full-attention GQA layers only"
    )
    q, k, v = _project_qkv(params, cfg, x, positions)
    cache = dict(cache)
    cache = paged_write_span(cache, "k", k, table, start, lengths, cfg.kv_quant)
    cache = paged_write_span(cache, "v", v, table, start, lengths, cfg.kv_quant)
    cache = _constrain_cache(cache, shard, paged=True)
    # The view only needs the causally reachable range (<= start + T): any
    # chunk past the last query position is an exact online-softmax no-op,
    # so truncating to a static block count changes no bits but cuts the
    # flash sweep from max_seq to ~the padded prompt length — the same
    # work the dense prefill does.
    view = paged_view(cache, table if view_blocks is None
                      else table[:, :view_blocks], shard)
    ck = _read_cache(view, "k", cfg.kv_quant, x.dtype)
    cv = _read_cache(view, "v", cfg.kv_quant, x.dtype)
    ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), start, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), start, axis=1)
    o = flash_attention(q, ck, cv, causal=True, q_offset=start, chunk=chunk)
    y = jnp.einsum("bthk,hkd->btd", o, params["wo"].astype(x.dtype))
    return y, cache


# ---------------------------------------------------------------------------
# Speculative verify windows (DESIGN.md §9): score W = k+1 draft positions in
# ONE batch-shaped pass against the live decode cache.  The whole point of
# speculative decoding here is shape conversion — k sequential decode steps
# (rows = B, the memory-bound regime) become one pass with B·W rows
# (the fused-kernel regime) — so these entry points must NOT be a scan of
# decode steps.  Bit-identity with the sequential path instead rests on the
# same per-element-reduction argument the batch dimension already relies on:
# the window axis ``t`` is carried as a pure batch axis through every einsum
# (contractions stay over ``d`` / ``s`` with identical per-element lengths),
# so position j of a window computes exactly the arrays decode step j would.
# ---------------------------------------------------------------------------


def _write_cache_span(
    cache: dict, name: str, val: jax.Array, positions: jax.Array, quant: bool
) -> dict:
    """Scatter a (B, W) span of K or V into a dense ``(B, S, ...)`` cache at
    per-row absolute ``positions``.  The W-token generalisation of
    :func:`_write_cache`'s ragged branch; positions ``>= S`` drop (jax
    scatter out-of-bounds semantics), mirroring the sentinel redirect of the
    paged span write — fixed-shape windows may overrun ``max_seq`` on rows
    that retire this window."""
    b_idx = jnp.arange(val.shape[0])[:, None]
    if quant:
        qv, sc = _kv_quantize(val)                            # (B,W,KV,D)
        cache[name] = cache[name].at[b_idx, positions].set(qv)
        cache[name + "_scale"] = (
            cache[name + "_scale"].at[b_idx, positions].set(sc)
        )
    else:
        cache[name] = cache[name].at[b_idx, positions].set(
            val.astype(cache[name].dtype)
        )
    return cache


def _cache_attend_window(
    params: dict,
    cfg: AttnConfig,
    x: jax.Array,                   # (B, W, d_model)
    cache: dict,                    # (B, S, ...) leaves — dense OR paged view
    q: jax.Array,                   # (B, W, H, D) post-rotary queries
    pos_b: jax.Array,               # (B,) window start positions
) -> jax.Array:
    """The verify-window attention *read*: :func:`_cache_attend` with the
    window axis rode along as a batch axis.  Query j (absolute position
    ``pos_b + j``) masks ``kv_slot <= pos_b + j`` — its own freshly written
    slot included, exactly like the sequential step — and every reduction
    (q·k over ``d``, softmax over ``S``, p·v over ``s``) keeps the decode
    path's per-element operand length, so each window position reproduces
    the sequential step's bits."""
    B, W = q.shape[:2]
    S = cache["k"].shape[1]
    H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    rep = H // KV
    qh = q.reshape(B, W, KV, rep, D)
    q_pos = pos_b[:, None] + jnp.arange(W)[None, :]           # (B, W)
    if not cfg.kv_quant:
        ck = cache["k"].astype(x.dtype)
        cv = cache["v"].astype(x.dtype)
        s = jnp.einsum("btgrd,bsgd->btgrs", qh, ck) / math.sqrt(D)
        mask = jnp.arange(S)[None, None, :] <= q_pos[:, :, None]   # (B,W,S)
        s = jnp.where(mask[:, :, None, None, :], s.astype(jnp.float32), NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("btgrs,bsgd->btgrd", p.astype(cv.dtype), cv)
        o = o.reshape(B, W, H, D).astype(x.dtype)
        return jnp.einsum("bthk,hkd->btd", o, params["wo"].astype(x.dtype))
    # int8 cache: the chunked flash-decode sweep with a W axis in the carry
    chunk = min(8192, S)
    n_chunks = (S + chunk - 1) // chunk
    assert S % chunk == 0 or n_chunks == 1, "cache length is chunk-aligned"

    def read_chunk(name, ci):
        raw = jax.lax.dynamic_slice_in_dim(cache[name], ci * chunk, chunk, 1)
        sc = jax.lax.dynamic_slice_in_dim(
            cache[name + "_scale"], ci * chunk, chunk, 1
        )
        return (raw.astype(jnp.float32) * sc[..., None]).astype(x.dtype)

    def step(carry, ci):
        m_p, l_p, acc_p = carry
        kb = read_chunk("k", ci)                              # (B,chunk,KV,D)
        vb = read_chunk("v", ci)
        s = jnp.einsum("btgrd,bsgd->btgrs", qh, kb) / math.sqrt(D)
        kv_slot = ci * chunk + jnp.arange(chunk)
        mask = kv_slot[None, None, :] <= q_pos[:, :, None]
        s = jnp.where(mask[:, :, None, None, :], s.astype(jnp.float32), NEG_INF)
        m_new = jnp.maximum(m_p, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_p - m_new)
        l_new = l_p * corr + p.sum(-1)
        acc = acc_p * corr[..., None] + jnp.einsum(
            "btgrs,bsgd->btgrd", p.astype(vb.dtype), vb
        ).astype(jnp.float32)
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, W, KV, rep), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, W, KV, rep), jnp.float32)
    a0 = jnp.zeros((B, W, KV, rep, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), jnp.arange(n_chunks))
    o = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(x.dtype)
    o = o.reshape(B, W, H, D)
    return jnp.einsum("bthk,hkd->btd", o, params["wo"].astype(x.dtype))


def attn_verify_window(
    params: dict,
    cfg: AttnConfig,
    x: jax.Array,                   # (B, W, d_model) — last token + k drafts
    cache: dict,                    # dense (B, S, ...) leaves
    pos: jax.Array,                 # (B,) window start (= next write slot)
    shard=None,
) -> tuple[jax.Array, dict]:
    """W-token verify against the dense cache: write all W post-rotary K/V
    spans (quantized when ``kv_quant`` — the sequential step also attends
    its own freshly *quantized* write, so verify must too), then attend with
    per-query causal masks.  Rejected positions leave garbage K/V at slots
    ``>= pos + m``; the next window rewrites every such slot before any
    query can reach it (its start ``pos'`` satisfies ``pos' + k >= pos + k``
    and causality bounds reads at ``pos' + j``), so no rollback is needed."""
    assert cfg.window is None and cfg.kv_lora_rank is None, (
        "speculative verify supports full-attention GQA layers only"
    )
    B, W, _ = x.shape
    pos_b = jnp.broadcast_to(pos, (B,))
    positions = pos_b[:, None] + jnp.arange(W)[None, :]       # (B, W)
    q, k, v = _project_qkv(params, cfg, x, positions)
    cache = dict(cache)
    cache = _write_cache_span(cache, "k", k, positions, cfg.kv_quant)
    cache = _write_cache_span(cache, "v", v, positions, cfg.kv_quant)
    cache = _constrain_cache(cache, shard, paged=False)
    y = _cache_attend_window(params, cfg, x, cache, q, pos_b)
    return y, cache


def attn_verify_window_paged(
    params: dict,
    cfg: AttnConfig,
    x: jax.Array,                   # (B, W, d_model)
    cache: dict,                    # POOL leaves (n_blocks, bs, ...)
    table: jax.Array,               # (B, n_logical)
    pos: jax.Array,                 # (B,)
    shard=None,
) -> tuple[jax.Array, dict]:
    """W-token verify against the paged pool: span writes routed through the
    block table (admission caps prefix reuse at ``(len-1)//bs`` full blocks,
    so window writes can never land in a refcounted shared block — rejected
    tokens only dirty request-exclusive blocks, which the engine trims from
    coverage instead of CoW-copying), then the identical window attention
    on the gathered logical view."""
    assert cfg.window is None and cfg.kv_lora_rank is None, (
        "paged KV supports full-attention GQA layers only"
    )
    B, W, _ = x.shape
    pos_b = jnp.broadcast_to(pos, (B,))
    positions = pos_b[:, None] + jnp.arange(W)[None, :]
    q, k, v = _project_qkv(params, cfg, x, positions)
    cache = dict(cache)
    cache = paged_write_span(cache, "k", k, table, pos_b, pos_b + W, cfg.kv_quant)
    cache = paged_write_span(cache, "v", v, table, pos_b, pos_b + W, cfg.kv_quant)
    cache = _constrain_cache(cache, shard, paged=True)
    y = _cache_attend_window(params, cfg, x, paged_view(cache, table, shard), q, pos_b)
    return y, cache


def mla_decode_step(
    params: dict,
    cfg: AttnConfig,
    x: jax.Array,                   # (B, 1, d_model)
    cache_ckv: jax.Array,           # (B, S, r + rope) — the compressed latent
    pos: jax.Array,
    shard=None,                     # optional ShardingCtx (mesh serving)
) -> tuple[jax.Array, jax.Array]:
    """MLA decode: the cache stores only the (r + rope)-dim latent — the
    memory win that makes DeepSeek-V2 serving cheap.  Like every other
    cache-mutating entry point (kanlint KL105), the freshly written latent
    is pinned to its logical axes under a mesh so GSPMD can't gather it."""
    B = x.shape[0]
    S = cache_ckv.shape[1]
    pos_b = jnp.broadcast_to(pos, (B,))
    r, rope, hd = cfg.kv_lora_rank, cfg.qk_rope_dim, cfg.head_dim
    q = jnp.einsum("btd,dhk->bthk", x, params["wq"].astype(x.dtype))
    q_nope, q_rope = q[..., :hd], q[..., hd:]
    ckv_new = x @ params["w_dkv"].astype(x.dtype)             # (B, 1, r+rope)
    cos, sin = L.rotary_embedding(pos_b[:, None], rope, cfg.rope_theta, x.dtype)
    q_rope = L.apply_rotary(q_rope, cos, sin)
    rotated = L.apply_rotary(ckv_new[..., None, r:], cos, sin)[..., 0, :]
    ckv_new = jnp.concatenate([ckv_new[..., :r], rotated], -1)
    if pos.ndim == 0:  # synchronized decode: collective-free DUS
        cache_ckv = jax.lax.dynamic_update_slice_in_dim(
            cache_ckv, ckv_new.astype(cache_ckv.dtype), pos, axis=1)
    else:
        cache_ckv = cache_ckv.at[jnp.arange(B), pos_b].set(
            ckv_new[:, 0].astype(cache_ckv.dtype)
        )
    if shard is not None:
        cache_ckv = shard.constrain(
            cache_ckv, ("batch", "seq_cache", "kv_lora")
        )

    c_kv = _qk_rmsnorm(cache_ckv[..., :r], params["kv_norm"])  # (B, S, r)
    k_rope = cache_ckv[..., r:]                                # (B, S, rope)
    # Absorbed-weight trick: score = q_nope·(W_uk c) + q_rope·k_rope
    q_abs = jnp.einsum("bthk,rhk->bthr", q_nope, params["w_uk"].astype(x.dtype))
    s = jnp.einsum("bhr,bsr->bhs", q_abs[:, 0], c_kv)
    s = s + jnp.einsum("bhk,bsk->bhs", q_rope[:, 0], k_rope)
    s = s / math.sqrt(hd + rope)
    mask = jnp.arange(S)[None, :] <= pos_b[:, None]
    s = jnp.where(mask[:, None], s.astype(jnp.float32), NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    o_lat = jnp.einsum("bhs,bsr->bhr", p, c_kv)                # (B, H, r)
    o = jnp.einsum("bhr,rhk->bhk", o_lat, params["w_uv"].astype(x.dtype))
    y = jnp.einsum("bhk,hkd->bd", o, params["wo"].astype(x.dtype))[:, None]
    return y, cache_ckv
