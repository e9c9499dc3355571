"""Speculative decoding: a cheap KAN drafter + fused batch verification.

Decode is the regime where the paper's kernels are starved: one token per
step means ``rows = B`` — the memory-bound regime (DESIGN.md §2a).  Speculative decoding converts ``k`` sequential target decode steps
into (a) ``k`` steps of a much cheaper *drafter* and (b) ONE verification
pass scoring all ``W = k + 1`` window positions — batch-shaped work
(``rows = B·W``) that resolves to the fused KAN kernel on TPU
(``KL.resolve_inference_method``).  The drafter here is a *shrunken KAN*:
the first ``draft_layers`` repeats of the target's own scanned unit
(parameter slices — no second checkpoint), optionally int8 fake-quantized
(KANtize: KANs tolerate aggressive low-bit compression).

Determinism contract (the engine's bit-identity invariant, PR 3): at window
position ``j`` the verifier samples the *target* token ``t_j`` from the
target logits with the request's OWN chain key ``kt_j`` — the exact key the
sequential engine would use for that emission — and accepts the drafter's
``d_j`` iff ``d_j == t_j``.  The emitted stream is therefore always the
target chain's samples (greedy: argmax; temperature > 0: the same
per-row ``categorical`` draws), so speculative output is bit-identical to
non-speculative output *by construction*; drafter quality moves only the
acceptance rate (throughput), never a token.  This is the exact-match
specialization of standard rejection sampling: for temperature > 0 it
keeps the target distribution trivially (the emissions ARE target samples)
at the cost of rejecting token-equal-but-differently-sampled proposals —
the price of bitwise reproducibility across ``spec_k`` settings.

Cache lockstep (DESIGN.md §9): the drafter keeps its own small dense cache
``(slots, max_seq)`` over ``draft_layers`` layers.  The draft loop writes
``tok, d_0..d_{k-1}`` at ``pos..pos+k-1``; positions up to ``pos' - 1``
(the accepted prefix) hold exactly the emitted stream's KV, and garbage
beyond is overwritten by the next window before any causal mask can expose
it — the same rollback-free argument the target cache uses.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models import lm


def sample_tokens(
    logits: jax.Array, step_keys: jax.Array, temperature: float
) -> jax.Array:
    """Per-row sampling: ``logits (R, vocab)``, ``step_keys (R, 2)`` — one
    key per row.  THE sampling definition shared by the sequential engine,
    the draft loop, and the verifier: per-row vmap makes each row's draw a
    pure function of (its key, its logits), so the same row samples the
    same token at any batch shape — the property the acceptance rule's
    bit-identity argument stands on."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.vmap(
        lambda k, lg: jax.random.categorical(k, lg / temperature)
    )(step_keys, logits).astype(jnp.int32)


def split_chain(keys: jax.Array, n: int) -> tuple[jax.Array, jax.Array]:
    """Advance every row's PRNG chain ``n`` steps up front.

    ``keys (B, 2)`` -> ``(kts (B, n, 2), chains (B, n + 1, 2))`` where
    ``kts[:, j]`` is the sampling key of the chain's ``j``-th split and
    ``chains[:, j]`` is the carry after ``j`` splits (``chains[:, 0] ==
    keys``).  Matches the sequential body — ``pairs = vmap(split)(keys);
    keys, kt = pairs[:, 0], pairs[:, 1]`` — split for split, so a window
    that emits ``m`` tokens resumes from ``chains[:, m]`` holding exactly
    the key the sequential engine would carry (key splitting is integer
    hashing — no float reassociation to worry about)."""

    def step(carry, _):
        pairs = jax.vmap(jax.random.split)(carry)
        return pairs[:, 0], (pairs[:, 1], pairs[:, 0])

    _, (kts, tails) = jax.lax.scan(step, keys, None, length=n)
    chains = jnp.concatenate([keys[None], tails], axis=0)   # (n+1, B, 2)
    return jnp.swapaxes(kts, 0, 1), jnp.swapaxes(chains, 0, 1)


def accept_window(
    draft: jax.Array,       # (B, k) drafter proposals
    target: jax.Array,      # (B, k+1) target-chain samples t_0..t_k
    eos_hit: jax.Array,     # (B,) latched rows emit nothing
    eos_id,                 # traced scalar; -1 never matches
    pad_id,                 # traced scalar
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Longest-matching-prefix acceptance with EOS latching.

    Window position ``j`` emits iff every prior draft matched
    (``j <= n_acc`` — the bonus token ``t_{n_acc}`` always rides along), no
    earlier window position emitted EOS, and the row wasn't already
    latched.  Returns ``(emitted (B, W), m (B,), eos_new (B,))``:
    ``emitted[:, :m]`` is the (contiguous) accepted stream — always a run
    of target-chain samples, possibly ending in EOS — and positions
    ``>= m`` carry ``pad_id``.  The sequential engine emits exactly the
    same tokens: it too keeps sampling the target chain until EOS/budget,
    and its post-EOS pads match our padding (``finalize`` pads outputs to
    budget either way)."""
    k = draft.shape[1]
    W = k + 1
    match = (draft == target[:, :k]).astype(jnp.int32)
    n_acc = jnp.cumprod(match, axis=1).sum(axis=1)          # leading matches
    j = jnp.arange(W)[None, :]
    in_prefix = j <= n_acc[:, None]                         # (B, W)
    is_eos = (target == eos_id) & in_prefix
    eos_cum = jnp.cumsum(is_eos.astype(jnp.int32), axis=1)
    eos_before = (eos_cum - is_eos.astype(jnp.int32)) > 0   # strictly earlier
    real = in_prefix & ~eos_before & ~eos_hit[:, None]
    m = real.sum(axis=1).astype(jnp.int32)
    emitted = jnp.where(real, target, pad_id)
    eos_new = eos_hit | (real & (target == eos_id)).any(axis=1)
    return emitted, m, eos_new


def draft_propose(
    dparams: dict,
    dcfg,                    # drafter ModelConfig
    k: int,                  # static: proposals per window
    tok: jax.Array,          # (B, 1) last emitted token
    caches: dict,            # drafter dense caches (slots, max_seq, ...)
    pos: jax.Array,          # (B,) window start positions
    keys: jax.Array,         # (B, 2) the request chain (NOT consumed here)
    eos_hit: jax.Array,      # (B,) latched rows freeze position
    temperature: float,
    compute_dtype,
    shard=None,
) -> tuple[jax.Array, dict]:
    """Propose ``k`` tokens per row: a fixed-shape scan of drafter decode
    steps, sampling with the SAME chain keys the verifier will use for the
    target — when drafter logits agree with target logits (argmax, or the
    categorical draw under a shared key), the proposal matches and is
    accepted.  The chain itself is not consumed: the verifier re-derives it
    and advances the carry by exactly the number of emissions.  Latched
    rows keep their position frozen (they only overwrite their own dead
    slot).  Returns ``(draft (B, k) int32, caches)``."""
    kts, _ = split_chain(keys, k)                            # (B, k, 2)

    def body(carry, kt):
        tok_c, caches_c, pos_c = carry
        lg, caches_c = lm.decode_step(
            dparams, dcfg, tok_c, caches_c, pos_c, compute_dtype, None, shard
        )
        nxt = sample_tokens(lg, kt, temperature)
        pos_c = jnp.where(eos_hit, pos_c, pos_c + 1)
        return (nxt[:, None], caches_c, pos_c), nxt

    (_, caches, _), drafts = jax.lax.scan(
        body, (tok, caches, pos), jnp.swapaxes(kts, 0, 1)
    )
    return jnp.swapaxes(drafts, 0, 1), caches                # (B, k)


def _fake_quant_int8(a: jax.Array) -> jax.Array:
    """Symmetric per-output-channel int8 round-trip (KANtize-style weight
    compression for the drafter).  Values are stored back in the original
    dtype — the CPU-honest stand-in for an int8 weight store; an actual
    int8 GEMM is a kernels/ concern.  Drafter numerics only ever move the
    acceptance rate, never an emitted token, so this needs no error
    budget."""
    if a.ndim < 2 or not jnp.issubdtype(a.dtype, jnp.floating):
        return a
    scale = jnp.maximum(
        jnp.max(jnp.abs(a.astype(jnp.float32)), axis=-1, keepdims=True), 1e-8
    ) / 127.0
    q = jnp.clip(jnp.round(a.astype(jnp.float32) / scale), -127, 127)
    return (q * scale).astype(a.dtype)


@dataclasses.dataclass
class DraftModel:
    """A drafter derived from (or supplied alongside) the target checkpoint.

    ``from_target`` builds the shrunken-KAN drafter: the first
    ``n_layers`` repeats of the target's scanned unit — parameter *slices*
    of the stacked unit leaves, so the drafter shares every non-unit tensor
    (embed/unembed, final_ln, prologue/epilogue) with the target by
    aliasing and adds only ``n_layers / n_repeats`` of the unit weights
    when quantization is off.  Its dense KV cache costs
    ``n_layers / n_repeats`` of one dense target cache — the HBM price of
    speculation (DESIGN.md §9)."""

    params: dict
    cfg: object              # drafter ModelConfig
    n_layers: int
    quant: bool = False

    @classmethod
    def from_target(cls, params: dict, cfg, n_layers: int = 1,
                    quant: bool = False) -> "DraftModel":
        if not (1 <= n_layers <= cfg.n_repeats):
            raise ValueError(
                f"draft_layers must be in [1, {cfg.n_repeats}], got {n_layers}"
            )
        if not lm.model_supports_speculative(cfg):
            raise NotImplementedError(
                f"{cfg.name}: speculative drafter needs token-input "
                "full-attention GQA blocks throughout"
            )
        dcfg = dataclasses.replace(
            cfg, name=f"{cfg.name}-draft{n_layers}", n_repeats=n_layers
        )
        dparams = dict(params)                  # alias non-unit leaves
        unit = [
            jax.tree.map(lambda a: a[:n_layers], blk_params)
            for blk_params in params["unit"]
        ]
        if quant:
            unit = jax.tree.map(_fake_quant_int8, unit)
        dparams["unit"] = unit
        return cls(params=dparams, cfg=dcfg, n_layers=n_layers, quant=quant)
