"""Decoder LM: pattern-of-blocks with scan-over-repeats.

The model is ``prologue + unit * n_repeats + epilogue`` (DESIGN.md §3);
the unit's parameters are stacked along a leading 'layers' axis and driven
by ``lax.scan``, so the HLO is O(unit length), not O(depth) — this is what
makes 64-layer × 512-device dry-runs compile fast.

Shared blocks (zamba2): parameters created once under ``params["shared"]``,
closed over inside the scan body (loop-invariant), invoked wherever the unit
references their ``shared_id``.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from repro.models import attention as A
from repro.models import blocks as B
from repro.models import layers as L
from repro.models.blocks import BlockCfg
from repro.models.layers import ParamCtx


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    d_model: int
    vocab: int
    unit: tuple[BlockCfg, ...]
    n_repeats: int
    prologue: tuple[BlockCfg, ...] = ()
    epilogue: tuple[BlockCfg, ...] = ()
    shared: tuple[BlockCfg, ...] = ()
    input_kind: str = "tokens"          # "tokens" | "embeddings" | "mixed"
    n_prefix: int = 0                   # mixed: image/audio prefix length
    max_seq: int = 8192
    remat: str = "unit"                 # "none" | "unit"
    attn_chunk: int = 1024
    logit_softcap: float | None = None
    # scan_layers=False python-loops the unit repeats instead of lax.scan.
    # Production uses scan (O(1) HLO); the cost-faithful dry-run uses the
    # loop mode because XLA's cost_analysis counts while bodies ONCE
    # (see launch/dryrun.py --costmode and EXPERIMENTS.md §Roofline).
    scan_layers: bool = True

    @property
    def n_layers(self) -> int:
        return (
            len(self.prologue)
            + len(self.unit) * self.n_repeats
            + len(self.epilogue)
        )


def model_init(ctx: ParamCtx, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    params: dict = {
        "embed": L.embed_init(ctx, cfg.vocab, d),
        "final_ln": L.rmsnorm_init(ctx, d),
    }
    if cfg.prologue:
        params["prologue"] = [B.block_init(ctx, d, b) for b in cfg.prologue]
    params["unit"] = [
        ctx.stacked(cfg.n_repeats, functools.partial(B.block_init, d_model=d, blk=b))
        for b in cfg.unit
    ]
    if cfg.epilogue:
        params["epilogue"] = [B.block_init(ctx, d, b) for b in cfg.epilogue]
    if cfg.shared:
        params["shared"] = [B.block_init(ctx, d, b) for b in cfg.shared]
    return params


def init_params(key: jax.Array, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    return model_init(ParamCtx("init", key, dtype), cfg)


def abstract_params(cfg: ModelConfig, dtype=jnp.float32) -> dict:
    return model_init(ParamCtx("abstract", dtype=dtype), cfg)


def param_axes(cfg: ModelConfig) -> dict:
    return model_init(ParamCtx("axes"), cfg)


# ---------------------------------------------------------------------------
# Forward (training / prefill).
# ---------------------------------------------------------------------------


def _embed_inputs(params: dict, cfg: ModelConfig, inputs: dict, dtype) -> jax.Array:
    d = cfg.d_model
    scale = math.sqrt(d)
    if cfg.input_kind == "tokens":
        return L.embed_lookup(params["embed"], inputs["tokens"], dtype) * scale
    if cfg.input_kind == "embeddings":
        # audio/vision backbone-only: the modality frontend is a stub; the
        # harness provides precomputed frame/patch embeddings (brief §shapes).
        return inputs["embeddings"].astype(dtype)
    if cfg.input_kind == "mixed":
        txt = L.embed_lookup(params["embed"], inputs["tokens"], dtype) * scale
        return jnp.concatenate([inputs["prefix_embeddings"].astype(dtype), txt], axis=1)
    raise ValueError(cfg.input_kind)


def _apply_block_by_ref(params_blk, blk: BlockCfg, shared_params, x, positions, chunk):
    if blk.shared_id is not None:
        return B.block_apply(
            shared_params[blk.shared_id], blk, x, positions=positions, chunk=chunk
        )
    return B.block_apply(params_blk, blk, x, positions=positions, chunk=chunk)


def forward(
    params: dict, cfg: ModelConfig, inputs: dict, compute_dtype=jnp.bfloat16,
    shard=None,
) -> tuple[jax.Array, dict]:
    """-> (logits (B, T, vocab) fp32, aux losses).

    ``shard`` (optional ``repro.dist.sharding.ShardingCtx``): pins the
    activations' batch axis to the data mesh axes; parameters are expected
    to arrive committed to their own shardings (``ShardingCtx.place_params``).
    """
    h = _embed_inputs(params, cfg, inputs, compute_dtype)
    if shard is not None:
        h = shard.constrain(h, ("batch", None, "embed"))
    T = h.shape[1]
    positions = jnp.arange(T)[None, :]
    shared = params.get("shared", [])
    aux_total: dict = {}

    def add_aux(aux):
        for k, v in aux.items():
            aux_total[k] = aux_total.get(k, 0.0) + v

    for p_blk, blk in zip(params.get("prologue", []), cfg.prologue):
        h, aux = B.block_apply(p_blk, blk, h, positions=positions, chunk=cfg.attn_chunk)
        add_aux(aux)

    def unit_body(h_carry, rep_params):
        aux_rep: dict = {}
        for i, blk in enumerate(cfg.unit):
            h_carry, aux = _apply_block_by_ref(
                rep_params[i], blk, shared, h_carry, positions, cfg.attn_chunk
            )
            for k, v in aux.items():
                aux_rep[k] = aux_rep.get(k, 0.0) + v
        # pad aux to a fixed structure for scan
        keys = ("moe_lb_loss", "moe_z_loss", "moe_drop_frac")
        aux_vec = jnp.stack([jnp.asarray(aux_rep.get(k, 0.0), jnp.float32) for k in keys])
        return h_carry, aux_vec

    body = unit_body
    if cfg.remat == "unit":
        body = jax.checkpoint(unit_body, prevent_cse=False)
    if cfg.scan_layers:
        h, aux_vecs = jax.lax.scan(body, h, params["unit"])
    else:
        vecs = []
        for r in range(cfg.n_repeats):
            rep = jax.tree.map(lambda a: a[r], params["unit"])
            h, av = body(h, rep)
            vecs.append(av)
        aux_vecs = jnp.stack(vecs)
    for i, k in enumerate(("moe_lb_loss", "moe_z_loss", "moe_drop_frac")):
        s = aux_vecs[:, i].sum()
        add_aux({k: s})

    for p_blk, blk in zip(params.get("epilogue", []), cfg.epilogue):
        h, aux = B.block_apply(p_blk, blk, h, positions=positions, chunk=cfg.attn_chunk)
        add_aux(aux)

    h = L.rmsnorm(params["final_ln"], h)
    logits = L.unembed_logits(params["embed"], h)
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        logits = c * jnp.tanh(logits / c)
    if shard is not None:
        logits = shard.constrain(logits, ("batch", None, "vocab"))
    return logits, aux_total


def lm_loss(
    params: dict,
    cfg: ModelConfig,
    batch: dict,
    compute_dtype=jnp.bfloat16,
    aux_weight: float = 0.01,
) -> tuple[jax.Array, dict]:
    """Next-token cross-entropy (+ MoE aux). ``batch['labels']`` aligns with
    the *token* positions (prefix positions carry label -100 = masked)."""
    logits, aux = forward(params, cfg, batch, compute_dtype)
    labels = batch["labels"]
    if cfg.input_kind == "mixed":
        pad = jnp.full(labels.shape[:1] + (cfg.n_prefix,), -100, labels.dtype)
        labels = jnp.concatenate([pad, labels], axis=1)
    mask = (labels >= 0).astype(jnp.float32)
    labels_c = jnp.clip(labels, 0, cfg.vocab - 1)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels_c[..., None], axis=-1)[..., 0]
    loss = (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    metrics = {"loss": loss, "ntokens": mask.sum()}
    total = loss
    for k in ("moe_lb_loss", "moe_z_loss"):
        if k in aux:
            total = total + aux_weight * aux[k]
            metrics[k] = aux[k]
    if "moe_drop_frac" in aux:
        metrics["moe_drop_frac"] = aux["moe_drop_frac"]
    metrics["total_loss"] = total
    return total, metrics


# ---------------------------------------------------------------------------
# Decode (serving).
# ---------------------------------------------------------------------------


def init_caches(cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.bfloat16) -> dict:
    caches: dict = {}
    if cfg.prologue:
        caches["prologue"] = [
            B.block_init_cache(b, batch, max_seq, dtype) for b in cfg.prologue
        ]
    unit_caches = []
    for blk in cfg.unit:
        one = B.block_init_cache(blk, batch, max_seq, dtype)
        unit_caches.append(
            jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (cfg.n_repeats,) + a.shape).copy()
                if hasattr(a, "shape")
                else a,
                one,
            )
        )
    caches["unit"] = unit_caches
    if cfg.epilogue:
        caches["epilogue"] = [
            B.block_init_cache(b, batch, max_seq, dtype) for b in cfg.epilogue
        ]
    return caches


def model_supports_paging(cfg: ModelConfig) -> bool:
    """Every block must hold a full-attention GQA KV cache (DESIGN.md §3b)."""
    blks = cfg.prologue + cfg.unit + cfg.epilogue + cfg.shared
    return all(B.block_supports_paging(b) for b in blks)


def model_supports_speculative(cfg: ModelConfig) -> bool:
    """Speculative verify needs every block to accept a W-token window in
    one batch-shaped pass — the same full-attention GQA condition paging
    needs (ring buffers and recurrent states are sequential in the window
    dim), plus token inputs (the drafter re-embeds accepted tokens)."""
    return model_supports_paging(cfg) and cfg.input_kind == "tokens"


def model_kv_quant(cfg: ModelConfig) -> bool:
    """True if any attention block stores an int8-quantized KV cache."""
    blks = cfg.prologue + cfg.unit + cfg.epilogue + cfg.shared
    return any(b.attn is not None and b.attn.kv_quant for b in blks)


def init_paged_caches(
    cfg: ModelConfig, n_blocks: int, block_size: int, dtype=jnp.bfloat16
) -> dict:
    """Pool-shaped caches: one ``(n_blocks, block_size, ...)`` pool per
    layer, all layers addressed by the SAME physical block id (vLLM-style —
    one allocation covers a token's KV across the whole depth).  Structure
    mirrors :func:`init_caches` (unit pools stacked on the layers axis) so
    the decode scan machinery is unchanged."""
    if not model_supports_paging(cfg):
        raise NotImplementedError(
            f"{cfg.name}: paged KV needs full-attention GQA blocks throughout"
        )
    caches: dict = {}
    if cfg.prologue:
        caches["prologue"] = [
            B.block_init_paged_cache(b, n_blocks, block_size, dtype)
            for b in cfg.prologue
        ]
    unit_caches = []
    for blk in cfg.unit:
        one = B.block_init_paged_cache(blk, n_blocks, block_size, dtype)
        unit_caches.append(
            jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (cfg.n_repeats,) + a.shape).copy(),
                one,
            )
        )
    caches["unit"] = unit_caches
    if cfg.epilogue:
        caches["epilogue"] = [
            B.block_init_paged_cache(b, n_blocks, block_size, dtype)
            for b in cfg.epilogue
        ]
    return caches


def paged_cache_axes(cfg: ModelConfig) -> dict:
    """Logical-axes tree mirroring :func:`init_paged_caches` (the paged
    counterpart of :func:`cache_axes`): pools carry ``kv_blocks`` where the
    dense rows carried ``batch``/``seq_cache``."""
    from repro.models.layers import Axes

    axes: dict = {}
    if cfg.prologue:
        axes["prologue"] = [B.block_paged_cache_axes(b) for b in cfg.prologue]
    axes["unit"] = [
        jax.tree.map(
            lambda a: Axes(("layers",) + a.names),
            B.block_paged_cache_axes(b),
            is_leaf=lambda x: isinstance(x, Axes),
        )
        for b in cfg.unit
    ]
    if cfg.epilogue:
        axes["epilogue"] = [B.block_paged_cache_axes(b) for b in cfg.epilogue]
    return axes


def _leaf_names(table: dict, key: str, stacked: bool) -> tuple:
    """Logical axes of one pool/view leaf: attention's per-layout tables
    (``DENSE_CACHE_AXES`` for gathered views, ``POOL_CACHE_AXES`` for
    pools — one definition per layout), plus the stacked unit caches'
    leading 'layers' axis."""
    return (("layers",) if stacked else ()) + table[key]


def _map_paged_leaves(caches: dict, fn) -> dict:
    """Apply ``fn(key, leaf, stacked)`` over a paged-cache tree: unit pools
    carry a leading layers axis (``stacked=True``), prologue/epilogue don't."""
    out: dict = {}
    if "prologue" in caches:
        out["prologue"] = [
            {k: fn(k, a, False) for k, a in c.items()} for c in caches["prologue"]
        ]
    out["unit"] = [
        {k: fn(k, a, True) for k, a in c.items()} for c in caches["unit"]
    ]
    if "epilogue" in caches:
        out["epilogue"] = [
            {k: fn(k, a, False) for k, a in c.items()} for c in caches["epilogue"]
        ]
    return out


def paged_views(caches: dict, table: jax.Array, shard=None) -> dict:
    """Gather the logical dense view of every pool leaf: the result tree is
    shaped exactly like :func:`init_caches` (batch = table rows, seq =
    n_logical·block_size), so the UNCHANGED dense decode program runs on it.

    This is the engine's "shadow" read path (DESIGN.md §3b): gather ONCE
    per decode chunk, run the dense scan on the view, write the chunk's
    span back with :func:`writeback_paged_chunk` — amortizing the gather
    over ``chunk_steps`` instead of paying it every token.  The transient
    view costs ``slots x max_seq`` per layer (the dense *decode-batch*
    footprint; the pool remains the only persistent KV store).  With
    ``shard`` the gathered view is constraint-pinned to the dense cache
    shardings (batch on ``data``, kv_heads on ``model``)."""
    def leaf(key, pool, stacked):
        v = A.gather_pool(pool, table, A.POOL_CACHE_AXES[key], shard, stacked)
        if shard is not None:
            v = shard.constrain(v, _leaf_names(A.DENSE_CACHE_AXES, key, stacked))
        return v

    return _map_paged_leaves(caches, leaf)


def writeback_paged_chunk(
    caches: dict, view: dict, table: jax.Array, pos0: jax.Array, steps: int,
    shard=None,
) -> dict:
    """Scatter a finished chunk's writes from the dense shadow ``view``
    back into the pools.

    The dense scan wrote rows only at positions ``pos0[b] .. pos0[b] +
    steps - 1`` (latched rows rewrite their frozen slot; untouched
    positions in that window still hold the gathered pool values, so
    copying them back is an exact no-op).  Out-of-span positions (chunk
    overrun past ``max_seq``) are redirected to the sentinel block,
    mirroring the per-step write path."""

    from repro.models.attention import paged_route

    def write(pool, v):
        bs = pool.shape[1]
        B, S = v.shape[:2]
        positions = pos0[:, None] + jnp.arange(steps)[None, :]   # (B, steps)
        pos_cl = jnp.minimum(positions, S - 1)                   # view read idx
        rest = v.ndim - 2
        idx = pos_cl.reshape((B, steps) + (1,) * rest)
        vals = jnp.take_along_axis(v, idx, axis=1)               # (B,steps,...)
        phys, off = paged_route(table, positions, bs)
        return pool.at[phys, off].set(vals.astype(pool.dtype))

    def leaf(key, pool, v, stacked):
        out = jax.vmap(write)(pool, v) if stacked else write(pool, v)
        if shard is not None:
            out = shard.constrain(out, _leaf_names(A.POOL_CACHE_AXES, key, stacked))
        return out

    pooled = _map_paged_leaves(caches, lambda k, a, s: (k, a, s))
    return jax.tree.map(
        lambda ps, v: leaf(ps[0], ps[1], v, ps[2]),
        pooled, view,
        is_leaf=lambda x: isinstance(x, tuple),
    )


def copy_paged_blocks(caches: dict, srcs, dsts, shard=None) -> dict:
    """Device-side copy of physical blocks ``srcs[i] -> dsts[i]`` in every
    pool leaf — the data half of copy-on-write
    (``kv_pool.BlockPool.copy_on_write`` rebinds the table; this copies the
    KV payload).  The whole batch of copies lowers to ONE gather + ONE
    scatter per leaf, so an admission wave's CoW copies cost two dispatches
    per leaf instead of ``2n`` dynamic slices (the ROADMAP "sharded
    prefix-cache block copies" note: under a mesh the batched scatter keeps
    the pool's ``kv_blocks`` sharding with a single collective round).

    ``srcs``/``dsts`` are length-``n`` int32 vectors (traced OK — one jitted
    program serves every same-``n`` wave; callers bucket by wave size).
    ``dsts`` must be pairwise distinct: duplicate scatter targets apply in
    unspecified order.  The pool allocator guarantees this — freshly
    CoW-allocated blocks are unique by construction."""
    srcs = jnp.reshape(jnp.asarray(srcs, jnp.int32), (-1,))
    dsts = jnp.reshape(jnp.asarray(dsts, jnp.int32), (-1,))

    def copy_leaf(key, pool, stacked: bool):
        # unit pools carry a leading layers axis, so their block axis is 1;
        # prologue/epilogue pools index blocks at axis 0
        ax = 1 if stacked else 0
        blks = jnp.take(pool, srcs, axis=ax)
        out = pool.at[:, dsts].set(blks) if stacked else pool.at[dsts].set(blks)
        if shard is not None:
            out = shard.constrain(out, _leaf_names(A.POOL_CACHE_AXES, key, stacked))
        return out

    return _map_paged_leaves(caches, copy_leaf)


def copy_paged_block(caches: dict, src, dst, shard=None) -> dict:
    """Single-pair :func:`copy_paged_blocks` (kept for the fork/beam-search
    CoW primitive's call sites and tests)."""
    return copy_paged_blocks(
        caches, jnp.reshape(jnp.asarray(src, jnp.int32), (1,)),
        jnp.reshape(jnp.asarray(dst, jnp.int32), (1,)), shard,
    )


def prefill(
    params: dict,
    cfg: ModelConfig,
    inputs: dict,
    max_seq: int,
    compute_dtype=jnp.bfloat16,
    shard=None,
) -> tuple[jax.Array, dict]:
    """Inference prefill: full-sequence forward that also fills the decode
    caches (the ``prefill_32k`` workload). Returns (logits, caches).

    ``shard`` (optional ``ShardingCtx``) pins every produced cache leaf to
    its logical-axes sharding (kv_heads on ``model``, batch/seq on the data
    axes), so a sharded serve program hands decode a distributed cache."""
    h = _embed_inputs(params, cfg, inputs, compute_dtype)
    if shard is not None:
        h = shard.constrain(h, ("batch", None, "embed"))
    T = h.shape[1]
    positions = jnp.arange(T)[None, :]
    shared = params.get("shared", [])
    caches: dict = {}

    if cfg.prologue:
        pcs = []
        for p_blk, blk in zip(params["prologue"], cfg.prologue):
            h, c = B.block_prefill(
                p_blk, blk, h, positions=positions, max_seq=max_seq,
                chunk=cfg.attn_chunk, shard=shard,
            )
            pcs.append(c)
        caches["prologue"] = pcs

    def unit_body(h_carry, rep_params):
        new_caches = []
        for i, blk in enumerate(cfg.unit):
            p = shared[blk.shared_id] if blk.shared_id is not None else rep_params[i]
            h_carry, c = B.block_prefill(
                p, blk, h_carry, positions=positions, max_seq=max_seq,
                chunk=cfg.attn_chunk, shard=shard,
            )
            new_caches.append(c)
        return h_carry, new_caches

    if cfg.scan_layers:
        h, unit_caches = jax.lax.scan(unit_body, h, params["unit"])
    else:
        reps = []
        for r in range(cfg.n_repeats):
            rep = jax.tree.map(lambda a: a[r], params["unit"])
            h, cs = unit_body(h, rep)
            reps.append(cs)
        unit_caches = jax.tree.map(lambda *xs: jnp.stack(xs), *reps)
    caches["unit"] = unit_caches

    if cfg.epilogue:
        ecs = []
        for p_blk, blk in zip(params["epilogue"], cfg.epilogue):
            h, c = B.block_prefill(
                p_blk, blk, h, positions=positions, max_seq=max_seq,
                chunk=cfg.attn_chunk, shard=shard,
            )
            ecs.append(c)
        caches["epilogue"] = ecs

    h = L.rmsnorm(params["final_ln"], h)
    logits = L.unembed_logits(params["embed"], h)
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        logits = c * jnp.tanh(logits / c)
    return logits, caches


def cache_axes(cfg: ModelConfig) -> dict:
    """Logical-axes tree mirroring :func:`init_caches` (stacked unit caches
    get a leading 'layers' axis)."""
    from repro.models.layers import Axes

    axes: dict = {}
    if cfg.prologue:
        axes["prologue"] = [B.block_cache_axes(b) for b in cfg.prologue]
    axes["unit"] = [
        jax.tree.map(
            lambda a: Axes(("layers",) + a.names),
            B.block_cache_axes(b),
            is_leaf=lambda x: isinstance(x, Axes),
        )
        for b in cfg.unit
    ]
    if cfg.epilogue:
        axes["epilogue"] = [B.block_cache_axes(b) for b in cfg.epilogue]
    return axes


def abstract_caches(cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.bfloat16):
    return jax.eval_shape(lambda: init_caches(cfg, batch, max_seq, dtype))


def insert_cache_slot(cfg: ModelConfig, caches: dict, one: dict, slot,
                      shard=None) -> dict:
    """Write a batch-1 cache tree into batch row ``slot`` of a live cache.

    ``one`` must mirror ``caches`` structurally with batch size 1 (both
    built for the same ``max_seq``, e.g. by :func:`prefill` vs
    :func:`init_caches`).  The batch axis of each leaf is located by name
    via :func:`cache_axes` — stacked unit caches carry a leading 'layers'
    axis, so the batch axis is not a fixed position.  ``slot`` may be a
    traced scalar: the write lowers to one dynamic_update_slice per leaf,
    so a single jitted program serves every slot.
    """
    axes = cache_axes(cfg)

    def put(big, small, ax):
        b_axis = ax.names.index("batch")
        out = jax.lax.dynamic_update_slice_in_dim(
            big, small.astype(big.dtype), slot, axis=b_axis
        )
        if shard is not None:
            out = shard.constrain(out, ax.names)
        return out

    return jax.tree.map(put, caches, one, axes)


def prefill_into_slot(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array,           # (1, T_pad) int32, right-padded prompt
    length: jax.Array,           # scalar int32: true prompt length (>= 1)
    slot,                        # scalar int32: target batch row
    caches: dict,
    max_seq: int,
    compute_dtype=jnp.bfloat16,
    shard=None,
) -> tuple[jax.Array, dict]:
    """Prefill ONE request and splice its KV into slot ``slot`` of a live
    batch cache — the cache-insert primitive continuous batching needs to
    swap a finished row for a queued request between decode chunks.

    Returns ``(last_logits (vocab,) fp32, caches)`` where ``last_logits``
    is taken at the request's own last real token (position ``length-1``;
    causal attention makes it independent of the right-padding, which is
    what keeps slot-admitted generations bit-identical to solo
    :class:`~repro.serve.engine.Engine` ``generate`` calls).  Jit callers
    retrace once per padded prompt length ``T_pad`` (bucket prompts to
    bound compiles); ``length`` and ``slot`` stay traced.  Thin k=1 wrapper
    over :func:`prefill_into_slots` — the serve loop uses the grouped form
    because slots free in bursts at chunk boundaries.
    """
    last, caches = prefill_into_slots(
        params, cfg, tokens,
        jnp.reshape(jnp.asarray(length, jnp.int32), (1,)),
        jnp.reshape(jnp.asarray(slot, jnp.int32), (1,)),
        caches, max_seq, compute_dtype, shard,
    )
    return last[0], caches


def prefill_into_slots(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array,           # (k, T_pad) int32, right-padded prompts
    lengths: jax.Array,          # (k,) int32 true prompt lengths
    slots: jax.Array,            # (k,) int32 target batch rows
    caches: dict,
    max_seq: int,
    compute_dtype=jnp.bfloat16,
    shard=None,
) -> tuple[jax.Array, dict]:
    """Batched :func:`prefill_into_slot`: ONE prefill dispatch admits ``k``
    queued requests at once (k is static — jit callers retrace per
    ``(k, T_pad)`` admission-group shape).  Continuous batching frees slots
    in bursts at chunk boundaries, so grouped admission amortizes the
    prefill dispatch overhead that dominates one-at-a-time slot refills.
    Row independence of prefill makes each admitted row bit-identical to
    its batch-1 admission.  Returns ``(last_logits (k, vocab), caches)``.
    """
    k = tokens.shape[0]
    logits, many = prefill(
        params, cfg, {"tokens": tokens}, max_seq, compute_dtype, shard
    )
    last = jnp.take_along_axis(
        logits, (lengths - 1)[:, None, None], axis=1
    )[:, 0]
    axes = cache_axes(cfg)
    for i in range(k):
        one = jax.tree.map(
            lambda a, ax: jax.lax.dynamic_slice_in_dim(
                a, i, 1, axis=ax.names.index("batch")
            ),
            many, axes,
        )
        caches = insert_cache_slot(cfg, caches, one, slots[i], shard)
    return last, caches


def prefill_into_pages(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array,           # (k, Ts_pad) int32, right-padded SUFFIX tokens
    lengths: jax.Array,          # (k,) int32 true TOTAL prompt lengths
    tables: jax.Array,           # (k, n_logical) int32 block tables
    caches: dict,                # paged pools (init_paged_caches)
    start,                       # scalar int32: first uncached position
    compute_dtype=jnp.bfloat16,
    view_blocks: int | None = None,   # STATIC attention-view truncation:
                                      # table columns covering start + Ts
                                      # (bit-identical — see attn_prefill_paged)
    shard=None,
) -> tuple[jax.Array, dict]:
    """Paged admission prefill: compute ONLY the uncached suffix (positions
    ``start .. len-1``; a prefix-cache hit makes ``start > 0``) and scatter
    its K/V into the pool blocks mapped by ``tables``.

    The paged counterpart of :func:`prefill_into_slots` — no private cache
    row is built or spliced; blocks are written in place.  Returns
    ``(last_logits (k, vocab), caches)`` with ``last_logits`` taken at each
    request's last real token (row ``lengths - 1 - start`` of the suffix).
    Jit callers retrace once per ``(k, Ts_pad)`` group shape; ``lengths``,
    ``tables`` and ``start`` stay traced (admission groups bucket by
    ``(start, Ts_pad)``).  Bit-identity to the dense path: suffix K/V and
    logits are computed by the same per-position math
    (``attention._project_qkv`` / ``flash_attention`` with exact no-op
    masked chunks — see ``attn_prefill_paged``), and under ``kv_quant`` the
    engine forces ``start = 0`` so prefill attention sees raw values
    exactly like the dense path.
    """
    k, Ts = tokens.shape
    start = jnp.asarray(start, jnp.int32)
    h = _embed_inputs(params, cfg, {"tokens": tokens}, compute_dtype)
    positions = start + jnp.arange(Ts)[None, :]
    shared = params.get("shared", [])
    new_caches: dict = {}

    def apply(p_blk, blk, h, cache):
        p = shared[blk.shared_id] if blk.shared_id is not None else p_blk
        return B.block_prefill_paged(
            p, blk, h, positions=positions, cache=cache, table=tables,
            lengths=lengths, start=start, chunk=cfg.attn_chunk,
            view_blocks=view_blocks, shard=shard,
        )

    if cfg.prologue:
        pcs = []
        for p_blk, blk, c in zip(params["prologue"], cfg.prologue, caches["prologue"]):
            h, c2 = apply(p_blk, blk, h, c)
            pcs.append(c2)
        new_caches["prologue"] = pcs

    def unit_body(h_carry, xs):
        rep_params, rep_caches = xs
        new_rep = []
        for i, blk in enumerate(cfg.unit):
            h_carry, c2 = apply(rep_params[i], blk, h_carry, rep_caches[i])
            new_rep.append(c2)
        return h_carry, new_rep

    if cfg.scan_layers:
        h, new_unit = jax.lax.scan(unit_body, h, (params["unit"], caches["unit"]))
    else:
        reps = []
        for r in range(cfg.n_repeats):
            rep_p = jax.tree.map(lambda a: a[r], params["unit"])
            rep_c = jax.tree.map(lambda a: a[r], caches["unit"])
            h, nc = unit_body(h, (rep_p, rep_c))
            reps.append(nc)
        new_unit = jax.tree.map(lambda *xs: jnp.stack(xs), *reps)
    new_caches["unit"] = new_unit

    if cfg.epilogue:
        ecs = []
        for p_blk, blk, c in zip(params["epilogue"], cfg.epilogue, caches["epilogue"]):
            h, c2 = apply(p_blk, blk, h, c)
            ecs.append(c2)
        new_caches["epilogue"] = ecs

    h = L.rmsnorm(params["final_ln"], h)
    logits = L.unembed_logits(params["embed"], h)
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        logits = c * jnp.tanh(logits / c)
    last = jnp.take_along_axis(
        logits, (lengths - 1 - start)[:, None, None], axis=1
    )[:, 0]
    return last, new_caches


def decode_step(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array,           # (B, 1) int32 (or (B,1,d) embeddings)
    caches: dict,
    pos: jax.Array,              # (B,)
    compute_dtype=jnp.bfloat16,
    table: jax.Array | None = None,   # (B, n_logical): paged block tables
    shard=None,
) -> tuple[jax.Array, dict]:
    """One decode step for the whole model -> (logits (B, vocab), caches).

    With ``table`` set, ``caches`` holds paged pools
    (:func:`init_paged_caches`) and every block reads/writes through the
    block table (DESIGN.md §3b); the same physical block id addresses every
    layer's pool.  ``shard`` (optional ``ShardingCtx``) keeps the updated
    cache leaves pinned to their mesh shardings step over step."""
    d = cfg.d_model
    if cfg.input_kind == "tokens" or cfg.input_kind == "mixed":
        h = L.embed_lookup(params["embed"], tokens, compute_dtype) * math.sqrt(d)
    else:
        h = tokens.astype(compute_dtype)
        if h.ndim == 2:  # allow (B, d)
            h = h[:, None]
    shared = params.get("shared", [])
    new_caches: dict = {}

    if cfg.prologue:
        ncs = []
        for p_blk, blk, c in zip(params["prologue"], cfg.prologue, caches["prologue"]):
            h, c2 = B.block_decode_step(p_blk, blk, h, c, pos, table, shard)
            ncs.append(c2)
        new_caches["prologue"] = ncs

    # The unit's stacked caches are the layer scan's carry: each block
    # writes its token at [layer, ...] in place and reads its own layer,
    # so no per-layer cache slice goes in and no stacked copy comes out.
    def unit_body(carry, xs):
        h_c, unit_c = carry
        layer, rep_params = xs
        unit_c = list(unit_c)
        for i, blk in enumerate(cfg.unit):
            p = shared[blk.shared_id] if blk.shared_id is not None else rep_params[i]
            h_c, unit_c[i] = B.block_decode_step(
                p, blk, h_c, unit_c[i], pos, table, shard, layer
            )
        return (h_c, unit_c), None

    new_unit = list(caches["unit"])
    if cfg.scan_layers:
        (h, new_unit), _ = jax.lax.scan(
            unit_body, (h, new_unit),
            (jnp.arange(cfg.n_repeats), params["unit"]),
        )
    else:
        for r in range(cfg.n_repeats):
            rep_p = jax.tree.map(lambda a: a[r], params["unit"])
            (h, new_unit), _ = unit_body((h, new_unit), (r, rep_p))
    new_caches["unit"] = new_unit

    if cfg.epilogue:
        ncs = []
        for p_blk, blk, c in zip(params["epilogue"], cfg.epilogue, caches["epilogue"]):
            h, c2 = B.block_decode_step(p_blk, blk, h, c, pos, table, shard)
            ncs.append(c2)
        new_caches["epilogue"] = ncs

    h = L.rmsnorm(params["final_ln"], h)
    logits = L.unembed_logits(params["embed"], h)[:, 0]
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        logits = c * jnp.tanh(logits / c)
    return logits, new_caches


def verify_window(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array,           # (B, W) int32: last accepted token + k drafts
    caches: dict,
    pos: jax.Array,              # (B,) window start positions
    compute_dtype=jnp.bfloat16,
    table: jax.Array | None = None,   # (B, n_logical): paged block tables
    shard=None,
) -> tuple[jax.Array, dict]:
    """Speculative verification: score all ``W = k + 1`` window positions of
    every row in ONE pass -> ``(logits (B, W, vocab) fp32, caches)``.

    ``logits[:, j]`` is the next-token distribution after the token at
    absolute position ``pos + j`` — exactly what ``decode_step`` would
    return at step ``j`` of a sequential chunk, provided the window prefix
    matches the sequential stream (the acceptance rule's induction,
    ``serve/speculative.py``).  Structure mirrors :func:`decode_step`
    (scan-over-repeats on the same stacked caches) with
    :func:`~repro.models.blocks.block_verify_window` per block; the per-row
    accepted length is applied by the CALLER — the model writes all W
    positions and the engine's rollback invariants make rejected writes
    unobservable (DESIGN.md §9)."""
    if not model_supports_speculative(cfg):
        raise NotImplementedError(
            f"{cfg.name}: speculative verify needs token-input full-attention "
            "GQA blocks throughout"
        )
    d = cfg.d_model
    h = L.embed_lookup(params["embed"], tokens, compute_dtype) * math.sqrt(d)
    shared = params.get("shared", [])
    new_caches: dict = {}

    if cfg.prologue:
        ncs = []
        for p_blk, blk, c in zip(params["prologue"], cfg.prologue, caches["prologue"]):
            h, c2 = B.block_verify_window(p_blk, blk, h, c, pos, table, shard)
            ncs.append(c2)
        new_caches["prologue"] = ncs

    def unit_body(carry, xs):
        h_c = carry
        rep_params, rep_caches = xs
        new_rep = []
        for i, blk in enumerate(cfg.unit):
            p = shared[blk.shared_id] if blk.shared_id is not None else rep_params[i]
            h_c, c2 = B.block_verify_window(
                p, blk, h_c, rep_caches[i], pos, table, shard
            )
            new_rep.append(c2)
        return h_c, new_rep

    if cfg.scan_layers:
        h, new_unit = jax.lax.scan(unit_body, h, (params["unit"], caches["unit"]))
    else:
        reps = []
        for r in range(cfg.n_repeats):
            rep_p = jax.tree.map(lambda a: a[r], params["unit"])
            rep_c = jax.tree.map(lambda a: a[r], caches["unit"])
            h, nc = unit_body(h, (rep_p, rep_c))
            reps.append(nc)
        new_unit = jax.tree.map(lambda *xs: jnp.stack(xs), *reps)
    new_caches["unit"] = new_unit

    if cfg.epilogue:
        ncs = []
        for p_blk, blk, c in zip(params["epilogue"], cfg.epilogue, caches["epilogue"]):
            h, c2 = B.block_verify_window(p_blk, blk, h, c, pos, table, shard)
            ncs.append(c2)
        new_caches["epilogue"] = ncs

    h = L.rmsnorm(params["final_ln"], h)
    logits = L.unembed_logits(params["embed"], h)
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        logits = c * jnp.tanh(logits / c)
    return logits, new_caches
