"""Batched serving engine: prefill + decode with KV caches.

The engine jits one ``prefill`` per (batch, seq) bucket and ONE
scan-over-steps decode program per batch shape: the whole generation after
prefill is a single compiled ``jax.lax.scan`` (``max_new_tokens`` static),
so a request costs two XLA dispatches instead of ``max_new_tokens`` Python
round-trips.

Two serving drivers share that program:

* ``serve_requests`` — static bucketing: requests are length-sorted into
  fixed batches and each bucket drains to ``max_new_tokens`` (finished rows
  keep decoding into dead slots — the idle-PE problem in software);
* ``serve_continuous`` — true continuous batching: a slot table
  (``serve/scheduler.py``) runs fixed-shape jitted decode *chunks*
  (``chunk_steps``-long scans with per-row EOS latching) and swaps finished
  slots for queued requests between chunks via
  ``lm.prefill_into_slots`` — queued requests' KV is prefilled and spliced into
  a live batch cache row.  With ``ServeConfig.paged`` the dense per-slot
  cache rows become a block pool with per-request block tables, prefix
  caching, and preemption-with-recompute (DESIGN.md §3b) — same outputs,
  bit for bit.

Padding is **right**-padding with per-request start offsets: real tokens
sit at positions ``0..len-1``, causal attention means no real token ever
attends a pad, each request samples from the logits at its *own* last real
position, and decode starts ragged at ``pos_b = len_b`` (overwriting pad
cache slots before they become attendable).

Sampling is **per-row**: each row's PRNG key chain is derived from its
*request id* (``fold_in(PRNGKey(seed), request_id)``, then one split per
emitted token), never from its batch position — so even ``temperature >
0`` generation is bit-invariant to batch-mates, padding, and scheduling
(static vs continuous).  An earlier revision drew all rows' noise from one
batch-wide key, making sampled outputs depend on bucket composition.

EOS (``ServeConfig.eos_id >= 0``) latches per row: the EOS token itself is
emitted, every later step of that row emits ``pad_id`` and the row's
position freezes (its cache stops growing).  ``eos_id = -1`` (default)
never matches a real token id, so the same compiled program reproduces the
never-stop behavior exactly.  Under both greedy and sampled decoding a
request's full ``max_new``-token output (EOS, then pads) is bit-identical
between a solo ``generate`` call and any scheduling of
``serve_requests``/``serve_continuous`` (regression-tested).

Caveat: ragged decode into *windowed* (ring-buffer) attention layers can
still attend stale pad slots once a row's position wraps the window; the
KAN serving configs use full attention, where the invariance is exact.
SSM/LSTM block states are sequential and not pad-invariant under any
padding scheme; equal-length buckets avoid padding entirely.

Mesh-native serving (``ServeConfig.mesh``, DESIGN.md §4): the engine
places params and KV (dense rows or the paged block pool) on
``NamedSharding``s derived from their logical axes and threads a
``ShardingCtx`` through every jitted program, so decode chunks, slot
insertion, paged gather/writeback, and prefix-cache block copies stay
distributed across devices.  Host bookkeeping (scheduler, BlockPool,
PrefixCache) never sees device counts.  On one device the mesh path is
bit-identical to ``mesh=None``; across devices token outputs still match
(greedy and sampled) — only logits can differ in the last ulp, because
partitioned contractions reorder fp32 partial sums.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.retrace import RetraceRegistry, counting
from repro.models import lm
from repro.serve import speculative
from repro.serve.kv_pool import BlockPool, blocks_for, worst_case_blocks
from repro.serve.prefix_cache import PrefixCache
from repro.serve.scheduler import ContinuousScheduler


@dataclasses.dataclass
class ServeConfig:
    max_seq: int = 512
    max_new_tokens: int = 64
    temperature: float = 0.0
    eos_id: int = -1             # -1: never stops early
    pad_id: int = 0              # emitted after a row latches on EOS
    compute_dtype: str = "float32"
    decode_impl: str = "scan"    # "scan" (one compiled program) | "loop"
    # Paged KV cache (DESIGN.md §3b): carve the cache into fixed-size
    # blocks bound to requests on demand (serve/kv_pool.py), dedup shared
    # prompt prefixes (serve/prefix_cache.py), and preempt-with-recompute
    # on pool exhaustion.  Affects serve_continuous only; bit-identical to
    # the dense path.
    paged: bool = False
    block_size: int = 16         # must divide max_seq
    pool_blocks: int | None = None   # physical blocks incl. sentinel;
                                     # None -> slots·(max_seq/block_size)+1
                                     # (dense-equivalent capacity)
    prefix_caching: bool = True  # auto-disabled under int8 KV quant (the
                                 # dense path attends RAW prefill K/V;
                                 # reused blocks could only supply
                                 # dequantized values — bit-identity first)
    # Decode read path: "shadow" gathers the dense view ONCE per chunk,
    # runs the unchanged dense scan on it, and writes the chunk's span back
    # to the pools (gather amortized over chunk_steps; transient
    # slots x max_seq view).  "step" reads/writes through the block table
    # every token — the shape a fused TPU paged-attention kernel runs, and
    # the path with no transient view.  Both are bit-identical (tested).
    paged_read: str = "shadow"
    # Mesh-native serving (DESIGN.md §4): a jax.sharding.Mesh with
    # ("data", "model") axes (launch/mesh.py).  Parameters, dense cache
    # rows, and the paged block pool are placed on NamedShardings derived
    # from their logical axes (dist/sharding.py: kv_heads on "model",
    # slots/blocks on "data"), and every jitted serve program threads a
    # ShardingCtx so cache updates never silently gather to one device.
    # None (default) keeps the single-device engine — byte-for-byte the
    # pre-mesh behavior; a 1-device mesh compiles the same math and is
    # bit-identical to it.  All host-side bookkeeping (scheduler,
    # BlockPool, PrefixCache) is device-count-agnostic.
    mesh: object | None = None
    # Speculative decoding (DESIGN.md §9, serve/speculative.py): a shrunken
    # KAN drafter proposes spec_k tokens per window and ONE fused
    # verification pass scores all spec_k + 1 positions — batch-shaped work
    # that resolves to the fused kernel path instead of spec_k + 1 starved
    # single-token decode dispatches.  Outputs stay bit-identical to
    # spec_k = 0 (greedy AND temperature > 0): the verifier samples the
    # target chain at every window position with the request's own PRNG
    # chain and only ever emits those samples.  serve_continuous only.
    spec_k: int = 0              # drafts per window; 0 disables speculation
    draft: object | None = None  # a speculative.DraftModel; None derives one
                                 # from the target checkpoint at engine init
    draft_layers: int = 1        # derived drafter: leading unit repeats kept
    draft_quant: bool = False    # derived drafter: int8 fake-quant weights


class Engine:
    def __init__(self, params, model_cfg, serve_cfg: ServeConfig):
        self.model = model_cfg
        self.cfg = serve_cfg
        self._dt = jnp.float32 if serve_cfg.compute_dtype == "float32" else jnp.bfloat16
        self.last_serve_stats: dict | None = None
        self._last_pool = None      # paged-mode introspection (tests/bench)
        self._last_prefix = None

        # Mesh-native serving (ServeConfig.mesh): derive the parameter
        # shardings once, commit the params to them, and thread a
        # ShardingCtx through every jitted program below.  shard=None keeps
        # the single-device engine byte-identical to the pre-mesh code.
        if serve_cfg.mesh is not None:
            from repro.dist.sharding import ShardingCtx, shard_tree

            self.shard = ShardingCtx(serve_cfg.mesh)
            self._pshard = self.shard.param_shardings(model_cfg)
            params = shard_tree(params, self._pshard)
        else:
            self.shard = None
            self._pshard = None
        self.params = params
        self._cache_init_progs: dict = {}   # (kind, *shape) -> jitted init
        shard = self.shard
        # Retrace sentinel (repro.analysis.retrace): every jitted program
        # below is wrapped with counting() BEFORE jit, so each compilation
        # records (name, abstract signature).  The serving drivers export
        # the snapshot as last_serve_stats["compiles"], and the retrace
        # regression tests assert the documented budgets (one decode-chunk
        # program per chunk config, one prefill program per (group, bucket),
        # EOS sweeps add zero traces).
        self.compiles = RetraceRegistry()
        _count = lambda fn, name: counting(fn, name, self.compiles)  # noqa: E731

        def _jit(fn, *, param_argnum=None, **kw):
            """jit that pins the params argument to its sharding tree when a
            mesh is configured (in_shardings; other args stay inferred-from-
            commitment: None leaves = UNSPECIFIED).  Compiling the entry
            points with explicit in_shardings is what guarantees admission
            prefill never silently gathers the params to one device."""
            if shard is not None and param_argnum is not None:
                n_args = kw.pop("n_args")
                in_sh = [None] * n_args
                in_sh[param_argnum] = self._pshard
                kw["in_shardings"] = tuple(in_sh)
            else:
                kw.pop("n_args", None)
            return jax.jit(fn, **kw)

        self._prefill = _jit(
            _count(lambda p, inputs: lm.prefill(
                p, self.model, inputs, self.cfg.max_seq, self._dt, shard
            ), "prefill"),
            param_argnum=0, n_args=2,
        )
        self._decode = jax.jit(
            _count(lambda p, tok, caches, pos: lm.decode_step(
                p, self.model, tok, caches, pos, self._dt, None, shard
            ), "decode"),
            donate_argnums=(2,),   # caches update in place
        )
        # scan decode: the whole generation (or one continuous-batching
        # chunk) is one compiled program; retraces per static step count
        self._decode_scan = jax.jit(
            _count(self._scan_impl, "decode_chunk"),
            static_argnums=(0,), donate_argnums=(3,),
        )
        # continuous batching: prefill an admission *group* of k queued
        # requests in ONE dispatch and splice them into their slots
        # (retraces once per (k, padded prompt length) group shape — slots
        # free in bursts at chunk boundaries, so k-batching amortizes the
        # prefill dispatch overhead that dominates one-at-a-time refills)
        self._prefill_insert = _jit(
            _count(lambda p, toks, lengths, slots, caches:
                   lm.prefill_into_slots(
                       p, self.model, toks, lengths, slots, caches,
                       self.cfg.max_seq, self._dt, shard,
                   ), "prefill_insert"),
            param_argnum=0, n_args=5,
            donate_argnums=(4,),
        )
        # paged admission: suffix prefill scattered straight into pool
        # blocks.  view_blocks is STATIC (it truncates the attention view
        # to the causally reachable blocks — same flash sweep the dense
        # prefill does), so callers retrace per (group size, padded suffix
        # length, view blocks); the prefix start offset stays traced.
        self._prefill_pages = jax.jit(
            _count(lambda p, toks, lengths, tables, caches, start, view_blocks:
                   lm.prefill_into_pages(
                       p, self.model, toks, lengths, tables, caches, start,
                       self._dt, view_blocks, shard,
                   ), "prefill_pages"),
            donate_argnums=(4,), static_argnums=(6,),
        )
        # per-row key derivation + first-token sampling, shared by generate
        # and slot admission (jitted: the eager vmap path costs ms per call)
        self._keys_first = jax.jit(_count(self._keys_first_impl, "keys_first"))
        # paged "shadow" read path: per-chunk view gather + span writeback.
        # The gather's input pools are re-read by the writeback at the end
        # of the same chunk, so they must NOT be donated here:
        self._gather_views = jax.jit(   # kanlint: ignore[KL101]
            _count(lambda caches, table: lm.paged_views(caches, table, shard),
                   "gather_views")
        )
        # The view (argnum 1) is dead after its span is written back, but
        # its slot-shaped leaves (slots, max_seq, ...) can never alias the
        # pool-shaped outputs (n_blocks, bs, ...), so donating it buys
        # nothing and makes XLA warn about unusable donations every compile
        self._writeback_chunk = jax.jit(   # kanlint: ignore[KL101]
            _count(lambda caches, view, table, pos0, steps:
                   lm.writeback_paged_chunk(
                       caches, view, table, pos0, steps, shard),
                   "writeback_chunk"),
            static_argnums=(4,),
            donate_argnums=(0,),           # pools update in place
        )
        # ---- speculative decoding (DESIGN.md §9, serve/speculative.py) ----
        if serve_cfg.spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {serve_cfg.spec_k}")
        if serve_cfg.spec_k >= 1:
            self.draft = (
                serve_cfg.draft
                if serve_cfg.draft is not None
                else speculative.DraftModel.from_target(
                    self.params, model_cfg,
                    n_layers=serve_cfg.draft_layers,
                    quant=serve_cfg.draft_quant,
                )
            )
        else:
            self.draft = None
        # drafter weights are EXPLICIT jit arguments everywhere below —
        # closing over them would bake the whole drafter into each program
        # as XLA constants (re-staged per trace, resident per executable)
        self._draft_chunk = jax.jit(
            _count(self._draft_impl, "draft_chunk"),
            static_argnums=(0,), donate_argnums=(3,),
        )
        self._verify = _jit(
            _count(self._verify_impl, "verify_window"),
            param_argnum=0, n_args=10,
            donate_argnums=(3,),           # target caches update in place
        )
        # drafter admission prefill: the drafter keeps a dense per-slot
        # cache even under the paged target (its whole cache costs
        # draft_layers / n_repeats of ONE dense target cache), so admission
        # always prefills the FULL prompt into its row — prefix-cache hits
        # only skip target-side compute
        self._draft_prefill = jax.jit(
            _count(lambda p, toks, lengths, slots, draft_caches:
                   lm.prefill_into_slots(
                       p, self.draft.cfg, toks, lengths, slots, draft_caches,
                       self.cfg.max_seq, self._dt, shard,
                   ), "draft_prefill"),
            donate_argnums=(4,),
        )

        # retrace-sentinel name -> jitted program, for lowered()
        self._programs = {
            "prefill": self._prefill, "decode": self._decode,
            "decode_chunk": self._decode_scan,
            "prefill_insert": self._prefill_insert,
            "prefill_pages": self._prefill_pages,
            "keys_first": self._keys_first,
            "gather_views": self._gather_views,
            "writeback_chunk": self._writeback_chunk,
            "draft_chunk": self._draft_chunk, "verify_window": self._verify,
            "draft_prefill": self._draft_prefill,
        }

    def lowered(self, name: str) -> list:
        """Every program compiled so far under retrace-sentinel ``name``,
        lowered again from the abstract arguments it was traced with
        (``.compile().as_text()`` shows what ran, e.g. ``tpu_custom_call``
        for the Pallas kernels)."""
        prog = self._programs[name]
        return [prog.lower(*a, **kw) for a, kw in self.compiles.calls(name)]

    # ------------------------------------------------------------------
    # cache construction: on a mesh the trees are built under jit with
    # explicit out_shardings (dist/sharding.py derives them from the
    # cache_axes / paged_cache_axes trees), so the KV store is born
    # distributed; without a mesh this is the eager pre-mesh path.
    # ------------------------------------------------------------------

    def _make_dense_caches(self, slots: int):
        if self.shard is None:
            return lm.init_caches(self.model, slots, self.cfg.max_seq, self._dt)
        prog = self._cache_init_progs.get(("dense", slots))
        if prog is None:
            sh = self.shard.cache_shardings(
                self.model, slots, self.cfg.max_seq, self._dt
            )
            prog = jax.jit(
                counting(lambda: lm.init_caches(
                    self.model, slots, self.cfg.max_seq, self._dt
                ), "cache_init", self.compiles),
                out_shardings=sh,
            )
            self._cache_init_progs[("dense", slots)] = prog
        return prog()

    def _make_paged_caches(self, pool_blocks: int, block_size: int):
        if self.shard is None:
            return lm.init_paged_caches(
                self.model, pool_blocks, block_size, self._dt
            )
        key = ("paged", pool_blocks, block_size)
        prog = self._cache_init_progs.get(key)
        if prog is None:
            sh = self.shard.paged_cache_shardings(
                self.model, pool_blocks, block_size, self._dt
            )
            prog = jax.jit(
                counting(lambda: lm.init_paged_caches(
                    self.model, pool_blocks, block_size, self._dt
                ), "cache_init", self.compiles),
                out_shardings=sh,
            )
            self._cache_init_progs[key] = prog
        return prog()

    # ------------------------------------------------------------------
    # per-row PRNG: key chain = fold_in(base, request_id), split per token
    # ------------------------------------------------------------------

    @staticmethod
    def _row_key_pairs(base_key, request_ids: jax.Array) -> jax.Array:
        """(B,) request ids -> (B, 2, 2): [:, 0] the carried chain key,
        [:, 1] the first sampling key.  vmap of split == per-row split, so
        a solo call and any batched call agree bit-for-bit."""
        return jax.vmap(
            lambda r: jax.random.split(jax.random.fold_in(base_key, r))
        )(request_ids.astype(jnp.int32))

    def _keys_first_impl(self, base_key, request_ids, last_logits):
        """-> (carry keys (B, 2), first sampled token (B,)): each row's key
        chain and its first token, from the logits at its last real prompt
        position.  One definition serves solo ``generate`` and continuous
        slot admission, so the two are bit-identical by construction."""
        pairs = self._row_key_pairs(base_key, request_ids)
        return pairs[:, 0], self._sample(last_logits, pairs[:, 1])

    def _sample(self, logits: jax.Array, step_keys: jax.Array) -> jax.Array:
        """logits (B, vocab), step_keys (B, 2) — one key per row.  Delegates
        to the ONE sampling definition (speculative.sample_tokens) shared
        with the draft loop and the verifier, so the speculative acceptance
        rule compares like with like, bit for bit."""
        return speculative.sample_tokens(logits, step_keys,
                                         self.cfg.temperature)

    def _validate_request(self, rid, prompt_len: int, max_new: int) -> None:
        """Per-request admission validation (clear errors instead of a
        deep-in-trace assert): the prompt plus its token budget must fit
        the engine's ``max_seq``."""
        if max_new < 1:
            raise ValueError(
                f"request {rid}: max_new must be >= 1, got {max_new}"
            )
        if prompt_len < 1:
            raise ValueError(
                f"request {rid}: empty prompt (prompt_len={prompt_len})"
            )
        if prompt_len + max_new > self.cfg.max_seq:
            raise ValueError(
                f"request {rid}: prompt_len {prompt_len} + max_new {max_new} "
                f"= {prompt_len + max_new} exceeds max_seq {self.cfg.max_seq}"
            )

    def _scan_impl(self, steps, params, tok0, caches, pos0, keys0, eos_hit0,
                   eos_id, pad_id, table=None):
        """(steps static) scan body == one loop iteration of the unrolled
        decode, so scan and loop are bit-identical (tested).

        Per-row EOS latching: once row b emits ``eos_id`` every later step
        emits ``pad_id`` and (when ``pos`` is per-row) its position
        freezes.  ``eos_id``/``pad_id`` are traced scalars — one compiled
        program serves every eos choice, and ``eos_id = -1`` never matches
        a sampled token (ids are >= 0), reproducing never-stop exactly.
        Returns ``(toks (steps, B), tok_last, caches, pos, keys, eos_hit)``
        — the full carry, so continuous batching can resume the next chunk
        where this one left off.
        """

        def body(carry, _):
            tok, caches, pos, keys, eos_hit = carry
            lg, caches = lm.decode_step(
                params, self.model, tok, caches, pos, self._dt, table,
                self.shard,
            )
            pairs = jax.vmap(jax.random.split)(keys)
            keys, kt = pairs[:, 0], pairs[:, 1]
            nxt = self._sample(lg, kt)
            emitted = jnp.where(eos_hit, pad_id, nxt)
            eos_new = eos_hit | (nxt == eos_id)
            if pos.ndim == 0:      # synchronized scalar-position decode
                pos = pos + 1
            else:                  # ragged/continuous: latched rows freeze
                pos = jnp.where(eos_hit, pos, pos + 1)
            return (emitted[:, None], caches, pos, keys, eos_new), emitted

        (tok, caches, pos, keys, eos_hit), toks = jax.lax.scan(
            body, (tok0, caches, pos0, keys0, eos_hit0), None, length=steps
        )
        return toks, tok, caches, pos, keys, eos_hit   # toks: (steps, B)

    # ------------------------------------------------------------------
    # speculative decoding (DESIGN.md §9)
    # ------------------------------------------------------------------

    def _draft_impl(self, k, dparams, tok, draft_caches, pos, keys, eos_hit):
        """One draft window: ``k`` (static) cheap drafter decode steps
        proposing candidate tokens per live row, sampling with the same
        chain keys the verifier will replay against the target."""
        return speculative.draft_propose(
            dparams, self.draft.cfg, k, tok, draft_caches, pos, keys,
            eos_hit, self.cfg.temperature, self._dt, self.shard,
        )

    def _verify_impl(self, params, tok, draft, caches, pos, keys, eos_hit,
                     eos_id, pad_id, table=None):
        """Fused verification: score all ``W = k + 1`` window positions in
        ONE target forward (``lm.verify_window`` — B·W rows resolve to the
        fused kernel path), sample the target chain at every position with
        the request's own key chain, and accept the longest matching draft
        prefix plus the bonus token.  Returns ``(emitted (B, W), m (B,),
        tok', caches, pos', keys', eos')`` — the full decode carry advanced
        by exactly the ``m`` accepted emissions, bitwise the state the
        sequential chunk would carry after ``m`` steps."""
        B, k = draft.shape
        W = k + 1
        x = jnp.concatenate([tok, draft], axis=1)            # (B, W)
        logits, caches = lm.verify_window(
            params, self.model, x, caches, pos, self._dt, table, self.shard
        )
        kts, chains = speculative.split_chain(keys, W)
        t = speculative.sample_tokens(
            logits.reshape(B * W, -1), kts.reshape(B * W, 2),
            self.cfg.temperature,
        ).reshape(B, W)
        emitted, m, eos_new = speculative.accept_window(
            draft, t, eos_hit, eos_id, pad_id
        )
        # resume the chain after exactly m splits; tok' = last real emission
        keys_new = jnp.take_along_axis(chains, m[:, None, None], axis=1)[:, 0]
        last = jnp.take_along_axis(emitted, jnp.maximum(m - 1, 0)[:, None], 1)
        tok_new = jnp.where((m > 0)[:, None], last, tok)
        pos_new = pos + m
        return emitted, m, tok_new, caches, pos_new, keys_new, eos_new

    def generate(
        self,
        prompts: np.ndarray,
        seed: int = 0,
        lengths: np.ndarray | None = None,
        request_ids: np.ndarray | None = None,
        max_new: int | None = None,
        eos_id: int | None = None,
    ) -> np.ndarray:
        """prompts: (B, T_prompt) int32 -> (B, max_new) int32.

        ``lengths`` (optional, (B,)): true prompt lengths for right-padded
        prompts.  Each row then samples from the logits at its own last real
        token and decodes from its own start offset — generation is
        invariant to batch-mates and padding (module docstring).  Without
        ``lengths`` every row is taken as full-length (synchronized decode,
        collective-free scalar-position cache writes).

        ``request_ids`` (optional, (B,)): per-row sampling identity; rows
        with the same id draw the same noise in any batch (defaults to
        ``arange(B)``).  ``max_new``/``eos_id`` override the config values
        per call (``max_new`` retraces the scan; ``eos_id`` does not).
        Rows that emit ``eos_id`` latch: the output carries the EOS token
        followed by ``pad_id`` up to the fixed ``max_new`` length.
        """
        B, T = prompts.shape
        max_new = self.cfg.max_new_tokens if max_new is None else int(max_new)
        eos = self.cfg.eos_id if eos_id is None else int(eos_id)
        rids = (
            np.arange(B, dtype=np.int32)
            if request_ids is None
            else np.asarray(request_ids, np.int32)
        )
        assert rids.shape == (B,)
        # per-request validation (was a bare deep-in-trace assert): each
        # row's true prompt length + budget must fit max_seq
        row_lens = np.full((B,), T) if lengths is None else np.asarray(lengths)
        for b in range(B):
            self._validate_request(int(rids[b]), int(row_lens[b]), max_new)
        if T > self.cfg.max_seq:
            raise ValueError(
                f"padded prompt length {T} exceeds max_seq {self.cfg.max_seq}"
            )
        logits, caches = self._prefill(self.params, {"tokens": jnp.asarray(prompts)})
        if lengths is None:
            last = logits[:, T - 1]
            # synchronized decode (scalar position): collective-free writes
            # — unless EOS can latch rows at different steps, which needs
            # per-row frozen positions
            pos = (
                jnp.asarray(T, jnp.int32)
                if eos < 0
                else jnp.full((B,), T, jnp.int32)
            )
        else:
            lengths = np.asarray(lengths, np.int32)
            assert lengths.shape == (B,), (lengths.shape, B)
            assert lengths.min() >= 1 and lengths.max() <= T
            last = jnp.take_along_axis(
                logits, jnp.asarray(lengths - 1)[:, None, None], axis=1
            )[:, 0]
            # ragged decode: per-row start offsets; each row's first write
            # lands at slot len_b, overwriting the pad K/V before any mask
            # ever exposes it
            pos = jnp.asarray(lengths, jnp.int32)
        keys, tok0 = self._keys_first(
            jax.random.PRNGKey(seed), jnp.asarray(rids), last
        )
        tok = tok0[:, None]
        eos_hit = tok[:, 0] == eos          # eos = -1 never matches
        eos_a, pad_a = jnp.int32(eos), jnp.int32(self.cfg.pad_id)
        steps = max_new - 1
        if self.cfg.decode_impl == "scan":
            toks, _, _, _, _, _ = self._decode_scan(
                steps, self.params, tok, caches, pos, keys, eos_hit,
                eos_a, pad_a,
            )
            out = jnp.concatenate([tok, toks.T], axis=1)
        else:  # python-loop reference (one dispatch per step), mirrors body
            outs = [tok]
            for _ in range(steps):
                lg, caches = self._decode(self.params, tok, caches, pos)
                pairs = jax.vmap(jax.random.split)(keys)
                keys, kt = pairs[:, 0], pairs[:, 1]
                nxt = self._sample(lg, kt)
                emitted = jnp.where(eos_hit, pad_a, nxt)
                if pos.ndim == 0:
                    pos = pos + 1
                else:
                    pos = jnp.where(eos_hit, pos, pos + 1)
                eos_hit = eos_hit | (nxt == eos_a)
                tok = emitted[:, None]
                outs.append(tok)
            out = jnp.concatenate(outs, axis=1)
        return np.asarray(out)

    def serve_requests(
        self, requests: list[np.ndarray], batch_size: int = 8, seed: int = 0
    ) -> list[np.ndarray]:
        """Bucket requests BY LENGTH into fixed batches (pad with copies) and
        drain bucket by bucket — the *static* batched-serving driver.
        Length-sorting means each bucket pads to its own max prompt length,
        not the global max.  Mixed-length buckets RIGHT-pad and thread the
        true lengths through ``generate``; per-row sampling keys are derived
        from each request's index in ``requests``, so outputs (greedy OR
        sampled) never depend on batch-mates or padding.  Finished (EOS)
        rows latch but their slots are NOT recycled — see
        :meth:`serve_continuous` for that."""
        order = sorted(range(len(requests)), key=lambda i: requests[i].shape[0])
        results: list[np.ndarray | None] = [None] * len(requests)
        t0 = time.perf_counter()
        buckets: list[dict] = []
        for start in range(0, len(order), batch_size):
            idxs = order[start : start + batch_size]
            bucket = [requests[i] for i in idxs]
            T = max(r.shape[0] for r in bucket)
            lens = np.asarray([r.shape[0] for r in bucket], np.int32)
            rids = np.asarray(idxs, np.int32)
            padded = np.stack(
                [np.pad(r, (0, T - r.shape[0]), constant_values=0) for r in bucket]
            )
            while padded.shape[0] < batch_size:
                padded = np.concatenate([padded, padded[-1:]], axis=0)
                lens = np.concatenate([lens, lens[-1:]], axis=0)
                rids = np.concatenate([rids, rids[-1:]], axis=0)
            gen = self.generate(
                padded.astype(np.int32), seed=seed,
                lengths=None if bool((lens == T).all()) else lens,
                request_ids=rids,
            )
            for j, i in enumerate(idxs):
                results[i] = gen[j]
            # a request "completes" when its bucket drains — the latency
            # accounting the serving benchmark compares against continuous
            buckets.append({
                "request_ids": idxs,
                "rows": int(padded.shape[0]),
                "done_s": time.perf_counter() - t0,
            })
        self.last_serve_stats = {
            "wall_s": time.perf_counter() - t0,
            "buckets": buckets,
            "request_latency_s": [
                next(b["done_s"] for b in buckets if i in b["request_ids"])
                for i in range(len(requests))
            ],
            "compiles": self.compiles.snapshot(),
        }
        return results  # type: ignore[return-value]

    # ------------------------------------------------------------------
    # continuous batching
    # ------------------------------------------------------------------

    def serve_continuous(
        self,
        requests: list[np.ndarray],
        slots: int = 8,
        chunk_steps: int = 8,
        seed: int = 0,
        max_new: int | list[int] | None = None,
        prompt_pad_multiple: int = 8,
    ) -> list[np.ndarray]:
        """True continuous batching: a ``slots``-row decode batch whose rows
        are recycled the moment a request finishes (EOS latch or token
        budget), instead of draining with the bucket.

        The loop alternates two fixed-shape jitted programs: a decode
        *chunk* (``chunk_steps`` scan steps over all slots, per-row EOS
        latching/frozen positions for dead rows) and ``lm.prefill_into_slots``
        (one queued request prefilled at a bucketed prompt length and its
        KV spliced into the freed row).  Between chunks the host scheduler
        (``serve/scheduler.py``) retires finished slots and admits from the
        FIFO queue.  Recompile boundaries: one trace per ``chunk_steps``
        value and one per padded prompt length (``prompt_pad_multiple``
        buckets them).

        ``max_new``: per-request (list) or global token budgets; default
        ``cfg.max_new_tokens``.  Each request's output has exactly its
        budget's length, padded with ``pad_id`` after EOS — bit-identical
        to a solo :meth:`generate` call with the same ``request_id`` (its
        index in ``requests``), for greedy AND sampled decoding.

        With ``cfg.paged`` (DESIGN.md §3b) the KV cache is a block pool
        (``serve/kv_pool.py``) instead of dense per-slot rows: admission is
        allocation-aware (a request only enters a slot when its prompt's
        blocks are available), shared prompt prefixes reuse cached blocks
        (``serve/prefix_cache.py`` — prefill computes only the uncached
        suffix), and pool exhaustion first evicts LRU prefix entries, then
        preempts the youngest running request (freed blocks, requeued at
        the queue head, restarted from scratch on re-admission —
        recompute regenerates the identical token stream).  All of it holds
        the same contract: outputs stay bit-identical to solo
        :meth:`generate`.

        Sets ``self.last_serve_stats`` (scheduler counters incl.
        ``n_preemptions``, per-request latency, wall time; paged mode adds
        a ``"paged"`` sub-dict with pool/prefix counters and
        prefill-tokens-saved) for the serving benchmarks.
        """
        n = len(requests)
        if max_new is None:
            budgets = [self.cfg.max_new_tokens] * n
        elif isinstance(max_new, int):
            budgets = [max_new] * n
        else:
            budgets = [int(m) for m in max_new]
            assert len(budgets) == n
        eos, pad = self.cfg.eos_id, self.cfg.pad_id
        for rid, (r, m) in enumerate(zip(requests, budgets)):
            self._validate_request(rid, int(r.shape[0]), m)
        assert chunk_steps >= 1 and slots >= 1
        spec_k = self.cfg.spec_k
        spec = spec_k >= 1
        W = spec_k + 1              # verify-window width (drafts + bonus)
        # device write span per decode round: a spec window writes
        # pos..pos+W-1 (tok + k drafts), a plain chunk pos..pos+chunk_steps-1
        steps_cov = W if spec else chunk_steps

        sched = ContinuousScheduler(slots, range(n))
        paged = self.cfg.paged
        if paged:
            if self.cfg.paged_read not in ("shadow", "step"):
                raise ValueError(
                    f"paged_read must be 'shadow' or 'step', "
                    f"got {self.cfg.paged_read!r}"
                )
            bs_blk = self.cfg.block_size
            if bs_blk < 1 or self.cfg.max_seq % bs_blk:
                raise ValueError(
                    f"block_size {bs_blk} must divide max_seq {self.cfg.max_seq}"
                )
            n_logical = self.cfg.max_seq // bs_blk
            pool_blocks = self.cfg.pool_blocks
            if pool_blocks is None:
                # default: dense-equivalent capacity (+ the sentinel)
                pool_blocks = slots * n_logical + 1
            if pool_blocks < 2:
                raise ValueError(
                    f"pool_blocks must be >= 2 (the reserved sentinel plus "
                    f"at least one usable block), got {pool_blocks}"
                )
            pool = BlockPool(pool_blocks, bs_blk)
            # paged admission validation: any single request must fit an
            # otherwise-empty pool, so preemption can always make progress
            for rid, (r, m) in enumerate(zip(requests, budgets)):
                need = worst_case_blocks(
                    int(r.shape[0]), m, chunk_steps, bs_blk, self.cfg.max_seq,
                    spec_k=spec_k,
                )
                if need > pool.usable:
                    raise ValueError(
                        f"request {rid}: worst-case footprint {need} blocks "
                        f"(prompt {r.shape[0]} + max_new {m}, block_size "
                        f"{bs_blk}) exceeds the pool's {pool.usable} usable "
                        f"blocks — raise pool_blocks or shrink the request"
                    )
            kv_quant = lm.model_kv_quant(self.model)
            # prefix reuse is OFF under int8 KV quant (ServeConfig note)
            prefix = (
                PrefixCache(bs_blk)
                if self.cfg.prefix_caching and not kv_quant else None
            )
            caches = self._make_paged_caches(pool_blocks, bs_blk)
            tables = np.zeros((slots, n_logical), np.int32)  # 0 == sentinel
            tables_dev = {"arr": None, "dirty": True}  # upload-once per change
            covered = np.zeros((slots,), np.int64)     # blocks bound per slot
            slot_rid = np.full((slots,), -1, np.int64)
            prefill_tok = {"computed": 0, "saved": 0}
            key_chains: dict[int, list] = {}   # rid -> immutable hash chain
                                               # (deferred admissions re-probe
                                               # without re-hashing)
        else:
            prefix = None
            caches = self._make_dense_caches(slots)
        # drafter KV: always dense per-slot rows, draft_layers deep — its
        # whole footprint is draft_layers / n_repeats of ONE dense target
        # cache, the HBM cost of speculation (DESIGN.md §9)
        draft_caches = (
            lm.init_caches(self.draft.cfg, slots, self.cfg.max_seq, self._dt)
            if spec else None
        )
        spec_stats = {"windows": 0, "proposed": 0, "accepted": 0, "emitted": 0}
        # host mirrors of the per-slot device state fed to each chunk
        tok = np.zeros((slots, 1), np.int32)
        pos = np.zeros((slots,), np.int32)
        keys = np.zeros((slots, 2), np.uint32)
        eos_hit = np.ones((slots,), bool)      # empty slots stay latched
        base = jax.random.PRNGKey(seed)
        bufs: list[list[int]] = [[] for _ in range(n)]
        outputs: list[np.ndarray | None] = [None] * n
        t0 = time.perf_counter()
        latency = [0.0] * n

        def finalize(rid: int) -> None:
            got = bufs[rid][: budgets[rid]]
            out = np.full((budgets[rid],), pad, np.int32)
            out[: len(got)] = got
            outputs[rid] = out
            latency[rid] = time.perf_counter() - t0

        def activate_group(pairs, lens, last):
            """Shared admission tail (dense AND paged): derive per-request
            key chains + first tokens from the prefill logits, then either
            activate each slot or retire it on the spot (budget-1 request,
            or the very first token hit EOS).  One definition keeps the two
            admission paths in bitwise lockstep."""
            rids_a = jnp.asarray(np.asarray([rid for _, rid in pairs], np.int32))
            # one batched device->host transfer for both results (two bare
            # np.asarray calls here were two serial syncs — kanlint KL102)
            kcs, firsts = jax.device_get(self._keys_first(base, rids_a, last))
            for j, (b, rid) in enumerate(pairs):
                first = int(firsts[j])
                bufs[rid].append(first)
                hit = eos >= 0 and first == eos
                if sched.confirm_admit(b, rid, int(lens[j]),
                                       budgets[rid] - 1, hit):
                    finalize(rid)       # done at admission: the freed slot
                    sched.retire(b)     # is refilled by the next round
                    if paged:
                        release_slot_blocks(b)
                    eos_hit[b] = True
                else:
                    tok[b, 0] = first
                    pos[b] = int(lens[j])
                    keys[b] = kcs[j]
                    eos_hit[b] = False

        def admit_all():
            nonlocal caches, draft_caches
            while True:
                ready = sched.admit_ready()
                if not ready:
                    return
                # one prefill dispatch per (padded length) admission group
                groups: dict[int, list[tuple[int, int]]] = {}
                for b, rid in ready:
                    L = requests[rid].shape[0]
                    # clamp: padding past L is causally invisible, but the
                    # prefilled cache must still fit the (slots, max_seq)
                    # live cache it is spliced into
                    t_pad = min(
                        -(-L // prompt_pad_multiple) * prompt_pad_multiple,
                        self.cfg.max_seq,
                    )
                    groups.setdefault(t_pad, []).append((b, rid))
                for t_pad, grp in sorted(groups.items()):
                    slots_a = np.asarray([b for b, _ in grp], np.int32)
                    lens = np.asarray(
                        [requests[rid].shape[0] for _, rid in grp], np.int32
                    )
                    padded = np.stack([
                        np.pad(requests[rid], (0, t_pad - requests[rid].shape[0]))
                        for _, rid in grp
                    ]).astype(np.int32)
                    last, caches = self._prefill_insert(
                        self.params, padded, lens, slots_a, caches
                    )
                    if spec:
                        # drafter cache row enters lockstep here: admission
                        # overwrites the whole row, so slot recycling and
                        # preemption-with-recompute can never leak a prior
                        # occupant's drafter KV into a new request
                        _, draft_caches = self._draft_prefill(
                            self.draft.params, padded, lens, slots_a,
                            draft_caches,
                        )
                    activate_group(grp, lens, last)

        # ---------------------- paged-mode machinery ----------------------

        def release_slot_blocks(b: int) -> None:
            """Drop slot b's block bindings (retire or preempt): the pool
            drops the request's refs (prefix-cache-held blocks survive) and
            the table row resets to the sentinel so the fixed-shape chunk's
            writes for this dead row land in the trash block."""
            pool.release_request(int(slot_rid[b]))
            tables[b, :] = 0
            tables_dev["dirty"] = True
            covered[b] = 0
            slot_rid[b] = -1

        def free_up(need: int, protect_slot: int | None) -> bool:
            """Make ``need`` blocks free: first evict LRU prefix-cache
            entries, then preempt the youngest live request (requeued at
            the queue head; its re-run regenerates the same tokens —
            preemption-with-recompute).  Returns False once ``protect_slot``
            itself was preempted (the caller stops extending it)."""
            while pool.free_count() < need:
                if prefix is not None and prefix.evict_lru(pool) is not None:
                    continue
                victim = sched.youngest_live_slot()
                assert victim is not None, "pool exhausted with no live rows"
                rid_v = sched.preempt(victim)
                bufs[rid_v] = []          # restart from scratch on re-admit
                release_slot_blocks(victim)
                eos_hit[victim] = True
                if victim == protect_slot:
                    return False
            return True

        def admit_all_paged():
            nonlocal caches, draft_caches
            while True:
                ready = sched.admit_ready()
                if not ready:
                    return
                # bind blocks per request; group dispatches by
                # (prefix start, padded suffix length)
                groups: dict[tuple[int, int], list] = {}
                deferred: list[int] = []
                for b, rid in ready:
                    toks_r = requests[rid]
                    L = toks_r.shape[0]
                    if prefix is not None:
                        n_hit, hit_blocks, keys_r = prefix.match(
                            toks_r, key_chains.get(rid)
                        )
                        key_chains[rid] = keys_r
                    else:
                        n_hit, hit_blocks, keys_r = 0, [], []
                    start = n_hit * bs_blk
                    n_new = blocks_for(L, bs_blk) - n_hit
                    # share FIRST: a matched cache-only block must not be
                    # evicted while we free room for the fresh suffix blocks
                    pool.share(rid, hit_blocks)
                    ok = pool.free_count() >= n_new
                    while not ok and prefix is not None:
                        if prefix.evict_lru(pool) is None:
                            break
                        ok = pool.free_count() >= n_new
                    if not ok:
                        # admission never preempts (that would thrash);
                        # blocks free as running requests retire
                        pool.release_request(rid)
                        deferred.append(rid)
                        continue
                    row = hit_blocks + pool.alloc(rid, n_new)
                    if prefix is not None:
                        prefix.record_admission(n_hit, L)
                    tables[b, :] = 0
                    tables[b, : len(row)] = row
                    tables_dev["dirty"] = True
                    covered[b] = len(row)
                    slot_rid[b] = rid
                    prefill_tok["saved"] += start
                    prefill_tok["computed"] += L - start
                    t_pad = min(
                        -(-(L - start) // prompt_pad_multiple) * prompt_pad_multiple,
                        self.cfg.max_seq - start,
                    )
                    groups.setdefault((start, t_pad), []).append(
                        (b, rid, L, keys_r)
                    )
                for (start, t_pad), grp in sorted(groups.items()):
                    lens = np.asarray([L for _, _, L, _ in grp], np.int32)
                    suffix = np.stack([
                        np.pad(requests[rid][start:], (0, t_pad - (L - start)))
                        for _, rid, L, _ in grp
                    ]).astype(np.int32)
                    tbls = jnp.asarray(tables[[b for b, *_ in grp]])
                    last, caches = self._prefill_pages(
                        self.params, suffix, jnp.asarray(lens), tbls, caches,
                        jnp.int32(start), blocks_for(start + t_pad, bs_blk),
                    )
                    if spec:
                        # the drafter has no paged pool and no prefix cache:
                        # prefill its dense row with the FULL prompt (target
                        # prefix hits only skip target-side compute).
                        # start + t_pad is group-constant, so one dispatch
                        full = np.stack([
                            np.pad(requests[rid], (0, start + t_pad - L))
                            for _, rid, L, _ in grp
                        ]).astype(np.int32)
                        slots_a = np.asarray([b for b, *_ in grp], np.int32)
                        _, draft_caches = self._draft_prefill(
                            self.draft.params, full, lens, slots_a,
                            draft_caches,
                        )
                    # register the freshly computed full prompt blocks so
                    # later admissions can reuse them (first writer wins)
                    if prefix is not None:
                        for b, rid, L, keys_r in grp:
                            for i, key in enumerate(keys_r):
                                if prefix.insert(key, int(tables[b, i])):
                                    pool.cache_ref(int(tables[b, i]))
                    activate_group([(b, rid) for b, rid, _, _ in grp],
                                   lens, last)
                if deferred:
                    # head-of-queue, original order: they re-admit first
                    for rid in reversed(deferred):
                        sched.queue.push_front(rid)
                    return

        def ensure_chunk_coverage():
            """Before a chunk, every live row's table must cover the full
            ``chunk_steps`` of writes (fixed-shape scans advance positions
            regardless of remaining budget; writes past ``max_seq`` are
            sentinel-redirected device-side).  Pool exhaustion here is what
            triggers eviction / preempt-youngest."""
            for b in list(sched.table.live_slots()):
                s = sched.table.slots[b]
                if not s.occupied or s.eos_hit:
                    continue   # preempted/retired meanwhile
                want = blocks_for(
                    min(int(pos[b]) + steps_cov, self.cfg.max_seq), bs_blk
                )
                need = int(want - covered[b])
                if need <= 0:
                    continue
                if not free_up(need, protect_slot=b):
                    continue   # b itself was preempted
                fresh = pool.alloc(int(slot_rid[b]), need)
                tables[b, int(covered[b]): int(covered[b]) + need] = fresh
                tables_dev["dirty"] = True
                covered[b] += need

        eos_a, pad_a = jnp.int32(eos), jnp.int32(pad)
        while True:
            admit_all_paged() if paged else admit_all()
            sched.check_invariants()
            if paged:
                ensure_chunk_coverage()
            if not sched.can_run_chunk():
                if paged and sched.has_work():
                    continue   # everything preempted: re-admit and retry
                break
            if paged and tables_dev["dirty"]:
                tables_dev["arr"] = jnp.asarray(tables)
                tables_dev["dirty"] = False
            if spec:
                # one window: k drafter steps, then ONE fused verify pass
                pos0 = jnp.asarray(pos)
                tok0 = jnp.asarray(tok)
                keys0 = jnp.asarray(keys)
                eos0 = jnp.asarray(eos_hit)
                draft, draft_caches = self._draft_chunk(
                    spec_k, self.draft.params, tok0, draft_caches, pos0,
                    keys0, eos0,
                )
                if paged and self.cfg.paged_read == "shadow":
                    view = self._gather_views(caches, tables_dev["arr"])
                    emitted_d, m_d, tok_l, view, pos_l, keys_l, eos_l = (
                        self._verify(
                            self.params, tok0, draft, view, pos0, keys0,
                            eos0, eos_a, pad_a, None,
                        )
                    )
                    caches = self._writeback_chunk(
                        caches, view, tables_dev["arr"], pos0, W
                    )
                else:
                    emitted_d, m_d, tok_l, caches, pos_l, keys_l, eos_l = (
                        self._verify(
                            self.params, tok0, draft, caches, pos0, keys0,
                            eos0, eos_a, pad_a,
                            tables_dev["arr"] if paged else None,
                        )
                    )
                emitted_h, m_h, tok, pos, keys, eos_hit = [
                    np.array(a) for a in jax.device_get(
                        (emitted_d, m_d, tok_l, pos_l, keys_l, eos_l)
                    )
                ]
                # emitted rows carry no post-EOS pads inside m (accept_window
                # truncates at EOS), so useful == n_keep — no eos_steps pass
                spec_stats["windows"] += 1
                for b, rid, n_keep, finished in sched.complete_spec_window(
                    W, m_h, eos_hit
                ):
                    spec_stats["proposed"] += spec_k
                    spec_stats["accepted"] += max(0, min(int(m_h[b]) - 1,
                                                         spec_k))
                    spec_stats["emitted"] += n_keep
                    bufs[rid].extend(int(t) for t in emitted_h[b, :n_keep])
                    if finished:
                        finalize(rid)
                        sched.retire(b)
                        if paged:
                            release_slot_blocks(b)
                        eos_hit[b] = True
                if paged:
                    # roll back rejected coverage: blocks past the accepted
                    # frontier are request-exclusive FRESH blocks (admission
                    # caps prefix reuse below blocks_for(pos')), so the trim
                    # frees them outright — no CoW, no device copy
                    for b in sched.table.live_slots():
                        keep = blocks_for(int(pos[b]), bs_blk)
                        if keep < covered[b]:
                            pool.trim_request(int(slot_rid[b]), keep)
                            tables[b, keep:] = 0
                            tables_dev["dirty"] = True
                            covered[b] = keep
                continue
            if paged and self.cfg.paged_read == "shadow":
                # gather once per chunk, dense-scan the view, write the
                # chunk's span back — per-step decode cost equals dense
                pos0 = jnp.asarray(pos)
                view = self._gather_views(caches, tables_dev["arr"])
                toks, tok_l, view, pos_l, keys_l, eos_l = self._decode_scan(
                    chunk_steps, self.params, jnp.asarray(tok), view,
                    pos0, jnp.asarray(keys), jnp.asarray(eos_hit),
                    eos_a, pad_a, None,
                )
                caches = self._writeback_chunk(
                    caches, view, tables_dev["arr"], pos0, chunk_steps
                )
            else:
                toks, tok_l, caches, pos_l, keys_l, eos_l = self._decode_scan(
                    chunk_steps, self.params, jnp.asarray(tok), caches,
                    jnp.asarray(pos), jnp.asarray(keys), jnp.asarray(eos_hit),
                    eos_a, pad_a, tables_dev["arr"] if paged else None,
                )
            # one device->host transfer; np.array copies because the host
            # mirrors are written by admission/retirement below
            toks, tok, pos, keys, eos_hit = [
                np.array(a)
                for a in jax.device_get((toks, tok_l, pos_l, keys_l, eos_l))
            ]
            if eos >= 0:
                # first in-chunk EOS emission per slot (chunk_steps if
                # none): post-EOS pads count as waste in the utilization
                hits = toks == eos
                eos_steps = np.where(
                    hits.any(axis=0), hits.argmax(axis=0), chunk_steps
                )
            else:
                eos_steps = None
            for b, rid, n_keep, finished in sched.complete_chunk(
                chunk_steps, eos_hit, eos_steps
            ):
                bufs[rid].extend(int(t) for t in toks[:n_keep, b])
                if finished:
                    finalize(rid)
                    sched.retire(b)
                    if paged:
                        release_slot_blocks(b)
                    eos_hit[b] = True

        sched.check_invariants()
        assert all(o is not None for o in outputs)
        self.last_serve_stats = {
            **sched.stats(),
            "wall_s": time.perf_counter() - t0,
            "request_latency_s": latency,
            "useful_tokens": int(sum(budget_used(bufs[i], budgets[i], eos)
                                     for i in range(n))),
            "mesh_shape": dict(self.shard.mesh.shape) if self.shard else None,
            "compiles": self.compiles.snapshot(),
        }
        if spec:
            self.last_serve_stats["spec"] = {
                "spec_k": spec_k,
                "draft_layers": self.draft.n_layers,
                "draft_quant": self.draft.quant,
                "windows": spec_stats["windows"],
                "proposed_drafts": spec_stats["proposed"],
                "accepted_drafts": spec_stats["accepted"],
                "acceptance_rate": (
                    spec_stats["accepted"] / max(spec_stats["proposed"], 1)
                ),
                "emitted_tokens": spec_stats["emitted"],
            }
        if paged:
            # after drain every block is free or prefix-cache-held (rc 1):
            # leaked blocks / unbalanced refcounts fail loudly here, and the
            # equivalence battery asserts the exported counters besides
            pool.check_balanced(n_live_requests=0)
            self.last_serve_stats["paged"] = {
                **pool.stats(),
                **(prefix.stats() if prefix is not None else
                   {"prefix_caching": False}),
                "prefill_tokens_computed": prefill_tok["computed"],
                "prefill_tokens_saved": prefill_tok["saved"],
            }
            self._last_pool = pool          # test introspection handles
            self._last_prefix = prefix
        return outputs  # type: ignore[return-value]


def budget_used(buf: list[int], budget: int, eos: int) -> int:
    """Tokens a request actually *used*: up to and including its EOS, else
    its full budget (serving-benchmark accounting)."""
    toks = buf[:budget]
    if eos >= 0 and eos in toks:
        return toks.index(eos) + 1
    return len(toks)
