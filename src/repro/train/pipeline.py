"""GPipe-style pipeline parallelism across the ``pod`` mesh axis.

Rationale (DESIGN.md §4): inter-pod ICI is the slowest link in a multi-pod
system, and pipeline-stage handoff (one activation tensor per microbatch,
point-to-point) is the cheapest traffic to put there — DP gradients
all-reduce 2x params per step, PP moves M x (mb x T x d) activations.

Mechanics: the layer stack (a uniform unit, ``n_repeats`` deep) is split
into S = pod-size stages; stage parameters are stacked on a leading axis
sharded over ``pod``, so inside ``jax.shard_map`` (manual over {pod}, auto
over data/model — TP/DP still handled by GSPMD) each pod sees only its own
stage. The classic looped schedule runs M + S - 1 ticks; activations hop
stages via ``ppermute``; the last stage accumulates the loss, and a psum
over ``pod`` makes the result provably pod-invariant. Backward is pure
autodiff through the loop (GPipe activation stashing).

Embedding/unembedding run on every stage and are masked — wasted FLOPs of
one embed+logits per tick, the standard simple-GPipe tradeoff (noted in
EXPERIMENTS.md); production would dedicate them to stages 0/S-1.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.models import blocks as B
from repro.models import layers as L
from repro.models import lm


def stage_params(params: dict, n_stages: int) -> dict:
    """Reshape stacked unit params (R, ...) -> (S, R/S, ...)."""
    def resh(a):
        return a.reshape((n_stages, a.shape[0] // n_stages) + a.shape[1:])

    out = dict(params)
    out["unit"] = [jax.tree.map(resh, p) for p in params["unit"]]
    return out


def make_pp_loss(model_cfg, n_stages: int, microbatches: int, mesh,
                 compute_dtype=jnp.bfloat16):
    """-> loss_fn(params_staged, batch) running the pipelined forward.

    Requires: uniform single-block unit, no prologue/epilogue/shared,
    n_repeats % n_stages == 0, global_batch % microbatches == 0.
    """
    cfg = model_cfg
    assert len(cfg.unit) == 1 and not cfg.prologue and not cfg.epilogue
    assert cfg.n_repeats % n_stages == 0
    blk = cfg.unit[0]

    def body(unit_local, embed_p, ln_p, tokens, labels):
        # unit_local: (1, R/S, ...) — my stage's slice (leading pod dim)
        unit_local = jax.tree.map(lambda a: a[0], unit_local)
        s = jax.lax.axis_index("pod")
        M = microbatches
        Bm, T = tokens.shape[1], tokens.shape[2]
        d = cfg.d_model
        state = jnp.zeros((Bm, T, d), compute_dtype)
        loss_sum = jnp.zeros((), jnp.float32)
        tok_sum = jnp.zeros((), jnp.float32)

        def stage_apply(h):
            def unit_body(h_c, rep_params):
                h_c, _ = B.block_apply(
                    rep_params, blk, h_c, positions=jnp.arange(T)[None, :],
                    chunk=cfg.attn_chunk,
                )
                return h_c, None

            h, _ = jax.lax.scan(unit_body, h, unit_local)
            return h

        for t in range(M + n_stages - 1):
            mb_in = min(t, M - 1)
            mb_out = t - (n_stages - 1)
            inject = L.embed_lookup(embed_p, tokens[mb_in], compute_dtype) * \
                math.sqrt(d)
            x = jnp.where(s == 0, inject, state)
            x = stage_apply(x)
            # last stage: loss for microbatch mb_out (if valid)
            h = L.rmsnorm(ln_p, x)
            logits = L.unembed_logits(embed_p, h)
            lbl = labels[max(0, min(mb_out, M - 1))]
            mask = (lbl >= 0).astype(jnp.float32)
            lbl_c = jnp.clip(lbl, 0, cfg.vocab - 1)
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, lbl_c[..., None], axis=-1)[..., 0]
            valid = jnp.logical_and(s == n_stages - 1, 0 <= mb_out)
            loss_sum += jnp.where(valid, (nll * mask).sum(), 0.0)
            tok_sum += jnp.where(valid, mask.sum(), 0.0)
            # hop activations to the next stage
            state = jax.lax.ppermute(
                x, "pod", [(i, i + 1) for i in range(n_stages - 1)]
            )
        loss_sum = jax.lax.psum(loss_sum, "pod")
        tok_sum = jax.lax.psum(tok_sum, "pod")
        return loss_sum / jnp.maximum(tok_sum, 1.0)

    smapped = shard_map(
        body,
        mesh=mesh,
        # pytree-prefix specs: the stage-stacked unit tree is pod-sharded on
        # its leading axis; everything else is pod-replicated (data/model
        # sharding stays with GSPMD — only {pod} is manual here).
        in_specs=(P("pod"), P(), P(), P(), P()),
        out_specs=P(),
        axis_names={"pod"},
        check_vma=False,
    )

    def loss_fn(params_staged, batch):
        tokens, labels = batch["tokens"], batch["labels"]
        Bg, T = tokens.shape
        mb = Bg // microbatches
        tok_m = tokens.reshape(microbatches, mb, T)
        lbl_m = labels.reshape(microbatches, mb, T)
        unit0 = params_staged["unit"][0]
        return smapped(
            unit0, params_staged["embed"], params_staged["final_ln"],
            tok_m, lbl_m,
        )

    return loss_fn
