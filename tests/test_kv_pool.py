"""Paged KV-cache subsystem: pool/prefix-cache units, gather kernel,
copy-on-write, and paged-vs-dense bit-identity at the model level.

The serving-level equivalence battery (paged ``serve_continuous`` vs solo
``generate`` under random schedules, preemption, and shared prefixes) lives
in ``tests/test_continuous_serving.py``; this module drives the layers
underneath it directly:

* ``serve/kv_pool.py`` — free list, refcounts, ownership, CoW, watermark,
  balanced-after-drain invariants (host-only, no jax);
* ``serve/prefix_cache.py`` — chained block hashing, hit capping, LRU
  eviction with pool cooperation, stale-entry removal;
* ``kernels/paged_gather.py`` — the Pallas block-table gather
  (interpret mode) bit-equal to the ``jnp.take`` fallback;
* ``models/lm.py`` paged paths — ``prefill_into_pages`` / paged
  ``decode_step`` bit-identical to the dense contiguous cache, and
  ``copy_paged_block`` as the CoW data mover.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import assert_logits_match
from repro import configs
from repro.kernels.paged_gather import gather_blocks
from repro.models import lm
from repro.serve.engine import Engine, ServeConfig
from repro.serve.kv_pool import BlockPool, blocks_for, worst_case_blocks
from repro.serve.prefix_cache import PrefixCache, block_keys


# ---------------------------------------------------------------------------
# BlockPool (host-only)
# ---------------------------------------------------------------------------


def test_block_pool_alloc_release_cycle_and_watermark():
    p = BlockPool(6, block_size=4)           # 5 usable, block 0 sentinel
    a = p.alloc(rid=0, n=3)
    assert len(a) == 3 and 0 not in a
    assert p.in_use() == 3 and p.free_count() == 2
    b = p.alloc(rid=1, n=2)
    assert not set(a) & set(b)
    assert p.watermark == 5
    with pytest.raises(MemoryError):
        p.alloc(rid=2, n=1)
    assert p.release_request(0) == a          # all freed (sole refs)
    assert p.in_use() == 2 and p.watermark == 5
    p.release_request(1)
    p.check_balanced(n_live_requests=0)


def test_block_pool_sharing_and_refcounts():
    p = BlockPool(8, block_size=2)
    a = p.alloc(rid=0, n=2)
    p.share(rid=1, blocks=a)                  # prefix hit: rc -> 2
    assert all(p.refcount(x) == 2 for x in a)
    assert p.release_request(0) == []         # request 1 still holds them
    assert all(p.refcount(x) == 1 for x in a)
    assert sorted(p.release_request(1)) == sorted(a)
    p.check_balanced(0)


def test_block_pool_cache_refs_and_cache_only():
    p = BlockPool(8, block_size=2)
    (blk,) = p.alloc(rid=0, n=1)
    p.cache_ref(blk)
    assert p.refcount(blk) == 2 and not p.cache_only(blk)
    p.release_request(0)
    assert p.cache_only(blk)                  # cache is now the sole holder
    assert p.cache_unref(blk)                 # ... and dropping it frees
    p.check_balanced(0)


def test_block_pool_copy_on_write():
    p = BlockPool(8, block_size=2)
    a = p.alloc(rid=0, n=2)
    assert p.copy_on_write(rid=0, logical=0) is None      # exclusive: no-op
    p.share(rid=1, blocks=a)
    res = p.copy_on_write(rid=1, logical=1)
    assert res is not None
    src, dst = res
    assert src == a[1] and dst not in a
    assert p.owned_blocks(1) == [a[0], dst]
    assert p.refcount(src) == 1 and p.refcount(dst) == 1
    assert p.n_cow == 1
    p.release_request(0), p.release_request(1)
    p.check_balanced(0)


def test_block_pool_detects_leak():
    p = BlockPool(4, block_size=2)
    p.alloc(rid=0, n=1)
    with pytest.raises(AssertionError):
        p.check_balanced(n_live_requests=0)   # rid 0 never released


def test_block_count_helpers():
    assert blocks_for(0, 4) == 0 and blocks_for(1, 4) == 1
    assert blocks_for(4, 4) == 1 and blocks_for(5, 4) == 2
    # prompt 10 + ceil(7/4)*4=8 decode positions -> 18 -> 5 blocks of 4
    assert worst_case_blocks(10, 8, 4, 4, max_seq=48) == 5
    # clamped by max_seq: writes past it are sentinel-redirected
    assert worst_case_blocks(10, 8, 4, 4, max_seq=12) == 3


# ---------------------------------------------------------------------------
# PrefixCache (host-only)
# ---------------------------------------------------------------------------


def test_prefix_chained_keys_position_dependence():
    t = np.arange(8, dtype=np.int32)
    keys = block_keys(t, 4)
    assert len(keys) == 2
    # same token block at a different chain position hashes differently
    t2 = np.concatenate([t[4:], t[:4]])
    assert block_keys(t2, 4)[0] != keys[1]
    # partial trailing block is never keyed
    assert len(block_keys(t[:7], 4)) == 1


def test_prefix_match_caps_last_full_block():
    """The last prompt token is always recomputed: a fully cached prompt
    still returns at most (len-1)//bs blocks, so sampling logits exist and
    decode writes stay out of shared blocks (no serving-path CoW)."""
    c = PrefixCache(4)
    t = np.arange(8, dtype=np.int32)
    keys = block_keys(t, 4)
    c.insert(keys[0], 5), c.insert(keys[1], 6)
    n_hit, blocks, _ = c.match(t)             # 8 tokens: cap = (8-1)//4 = 1
    assert n_hit == 1 and blocks == [5]
    n_hit, blocks, _ = c.match(np.arange(9, dtype=np.int32))  # cap = 2
    assert n_hit == 2 and blocks == [5, 6]


def test_prefix_stats_count_once_per_bound_admission():
    """match() records nothing (deferred admissions re-probe every loop
    iteration); record_admission counts one probe outcome, and only blocks
    actually probed count — the chain stops at the first miss and capped
    keys are never consulted."""
    c = PrefixCache(4)
    t = np.arange(9, dtype=np.int32)          # cap = 2 full blocks
    c.match(t), c.match(t)                    # retries: no stats
    assert c.lookups == 0 and c.hit_blocks == 0 and c.miss_blocks == 0
    c.record_admission(n_hit=0, n_tokens=9)   # cold probe: one miss
    c.record_admission(n_hit=2, n_tokens=9)   # full hit: no miss
    assert (c.lookups, c.hit_blocks, c.miss_blocks) == (2, 2, 1)
    assert c.stats()["prefix_block_hit_rate"] == 2 / 3
    c.record_admission(n_hit=0, n_tokens=4)   # cap 0: nothing probed
    assert c.miss_blocks == 1


def test_prefix_eviction_lru_with_pool():
    pool = BlockPool(8, block_size=4)
    c = PrefixCache(4)
    t = np.arange(12, dtype=np.int32)
    keys = block_keys(t, 4)
    blks = pool.alloc(rid=0, n=3)
    for k, b in zip(keys, blks):
        assert c.insert(k, b)
        pool.cache_ref(b)
    pool.release_request(0)
    # touch keys[0] so keys[1] becomes LRU
    c.match(t[:5])
    freed = c.evict_lru(pool)
    assert freed == blks[1] and len(c) == 2
    # a live request's block is skipped by eviction
    pool.share(rid=9, blocks=[blks[0]])
    assert c.evict_lru(pool) == blks[2]
    assert c.evict_lru(pool) is None          # blks[0] still request-held
    # stale-hit safety: evicted entries are gone from the map
    n_hit, blocks, _ = c.match(t)
    assert n_hit == 1 and blocks == [blks[0]]
    pool.release_request(9)


# ---------------------------------------------------------------------------
# gather kernel + device-side paged primitives
# ---------------------------------------------------------------------------


def test_gather_blocks_pallas_matches_take():
    rs = np.random.RandomState(0)
    for shape in [(9, 4, 3, 5), (9, 4, 3)]:   # KV pools and scale pools
        pool = jnp.asarray(rs.randn(*shape).astype(np.float32))
        tbl = jnp.asarray(rs.randint(0, 9, (3, 5)), jnp.int32)
        ref = gather_blocks(pool, tbl, method="take")
        pal = gather_blocks(pool, tbl, method="interpret")
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(pal))
        assert ref.shape == (3, 5 * 4) + shape[2:]
    # int8 pools gather bit-exactly too
    pool8 = jnp.asarray(rs.randint(-127, 128, (9, 4, 3, 5)), jnp.int8)
    tbl = jnp.asarray(rs.randint(0, 9, (2, 4)), jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(gather_blocks(pool8, tbl, method="take")),
        np.asarray(gather_blocks(pool8, tbl, method="interpret")),
    )


def _arch():
    return configs.get_reduced("qwen1.5-0.5b")


_PARAMS = None


def _params(model):
    global _PARAMS
    if _PARAMS is None:
        _PARAMS = lm.init_params(jax.random.PRNGKey(0), model)
    return _PARAMS


def test_copy_paged_block_moves_every_leaf():
    model = _arch().model
    caches = lm.init_paged_caches(model, n_blocks=5, block_size=4,
                                  dtype=jnp.float32)
    # scribble into block 2 of every pool leaf
    caches = jax.tree.map(
        lambda a: a.at[(slice(None), 2) if a.ndim == 5 else (2,)].set(1.25),
        caches,
    )
    out = lm.copy_paged_block(caches, src=2, dst=4)
    for leaf in jax.tree.leaves(out):
        blk_ax = 1 if leaf.ndim == 5 else 0   # unit pools: leading layers
        got = np.asarray(jnp.take(leaf, 4, axis=blk_ax))
        np.testing.assert_array_equal(got, np.full_like(got, 1.25))
        # source block intact
        src = np.asarray(jnp.take(leaf, 2, axis=blk_ax))
        np.testing.assert_array_equal(src, np.full_like(src, 1.25))


def test_paged_decode_bit_equal_dense():
    """Scattered random block tables + paged decode == dense contiguous
    cache across several steps (the tentpole contract at the model level):
    different programs, so logits agree to the fp32 reorder tolerance and
    greedy tokens wherever the margin decides them."""
    model = _arch().model
    params = _params(model)
    B, max_seq, bs = 2, 32, 4
    nlog = max_seq // bs
    rs = np.random.RandomState(3)
    T = 7
    toks = rs.randint(0, model.vocab, (B, T)).astype(np.int32)
    logits_d, caches_d = lm.prefill(
        params, model, {"tokens": jnp.asarray(toks)}, max_seq, jnp.float32
    )
    n_blocks = 2 * B * nlog + 1
    pools = lm.init_paged_caches(model, n_blocks, bs, jnp.float32)
    perm = rs.permutation(np.arange(1, n_blocks))[: B * nlog]
    tables = jnp.asarray(perm.reshape(B, nlog).astype(np.int32))
    lengths = jnp.full((B,), T, jnp.int32)
    last_p, pools = lm.prefill_into_pages(
        params, model, jnp.asarray(toks), lengths, tables, pools, 0,
        jnp.float32,
    )
    assert_logits_match(logits_d[:, T - 1], last_p)
    tok = jnp.argmax(last_p, -1).astype(jnp.int32)[:, None]
    pos = jnp.full((B,), T, jnp.int32)
    for _ in range(5):
        lg_d, caches_d = lm.decode_step(
            params, model, tok, caches_d, pos, jnp.float32
        )
        lg_p, pools = lm.decode_step(
            params, model, tok, pools, pos, jnp.float32, table=tables
        )
        assert_logits_match(lg_d, lg_p)
        tok = jnp.argmax(lg_p, -1).astype(jnp.int32)[:, None]
        pos = pos + 1


def test_prefix_hit_suffix_prefill_bit_equal():
    """prefill_into_pages with start > 0 (reusing another request's prefix
    blocks) returns the last-token logits of a dense full prefill, to the
    fp32 reorder tolerance."""
    model = _arch().model
    params = _params(model)
    max_seq, bs = 32, 4
    nlog = max_seq // bs
    rs = np.random.RandomState(5)
    T = 10
    toks = rs.randint(0, model.vocab, (1, T)).astype(np.int32)
    n_blocks = 3 * nlog + 1
    pools = lm.init_paged_caches(model, n_blocks, bs, jnp.float32)
    tabA = jnp.asarray(np.arange(1, nlog + 1, dtype=np.int32))[None]
    _, pools = lm.prefill_into_pages(
        params, model, jnp.asarray(toks), jnp.asarray([T], jnp.int32),
        tabA, pools, 0, jnp.float32,
    )
    # request B shares the first 2 full blocks (8 tokens), new suffix
    toksB = toks.copy()
    toksB[:, 8:] = rs.randint(0, model.vocab, (1, T - 8))
    dense_logits, _ = lm.prefill(
        params, model, {"tokens": jnp.asarray(toksB)}, max_seq, jnp.float32
    )
    tabB = np.arange(nlog + 1, 2 * nlog + 1, dtype=np.int32)
    tabB[:2] = [1, 2]                          # reuse A's prefix blocks
    lastB, pools = lm.prefill_into_pages(
        params, model, jnp.asarray(toksB[:, 8:]), jnp.asarray([T], jnp.int32),
        jnp.asarray(tabB)[None], pools, 8, jnp.float32,
    )
    assert_logits_match(dense_logits[:, T - 1], lastB)


def test_paged_rejects_unsupported_blocks():
    arch = configs.get_reduced("gemma3-12b")   # windowed local layers
    with pytest.raises(NotImplementedError):
        lm.init_paged_caches(arch.model, 8, 4, jnp.float32)


# ---------------------------------------------------------------------------
# admission validation (satellite: ValueError instead of deep assert)
# ---------------------------------------------------------------------------


def test_generate_validation_names_request_and_lengths():
    model = _arch().model
    eng = Engine(_params(model), model, ServeConfig(max_seq=16, max_new_tokens=8))
    big = np.arange(12, dtype=np.int32)[None]
    with pytest.raises(ValueError, match=r"request 7: prompt_len 12 \+ max_new 8"):
        eng.generate(big, request_ids=np.asarray([7]))
    with pytest.raises(ValueError, match="max_new must be >= 1"):
        eng.generate(big[:, :4], max_new=0)


def test_serve_continuous_validation_names_request():
    model = _arch().model
    eng = Engine(_params(model), model, ServeConfig(max_seq=16, max_new_tokens=4))
    ok = np.arange(4, dtype=np.int32)
    bad = np.arange(14, dtype=np.int32)
    with pytest.raises(ValueError, match="request 1: prompt_len 14"):
        eng.serve_continuous([ok, bad], slots=1, chunk_steps=2)
    with pytest.raises(ValueError, match="request 0: max_new"):
        eng.serve_continuous([ok], slots=1, chunk_steps=2, max_new=[0])


def test_paged_pool_too_small_is_a_clear_error():
    model = _arch().model
    eng = Engine(
        _params(model), model,
        ServeConfig(max_seq=32, max_new_tokens=8, paged=True, block_size=4,
                    pool_blocks=3),
    )
    with pytest.raises(ValueError, match="worst-case footprint"):
        eng.serve_continuous([np.arange(10, dtype=np.int32)], slots=1,
                             chunk_steps=4)
    eng2 = Engine(
        _params(model), model,
        ServeConfig(max_seq=30, max_new_tokens=4, paged=True, block_size=4),
    )
    with pytest.raises(ValueError, match="must divide max_seq"):
        eng2.serve_continuous([np.arange(4, dtype=np.int32)], slots=1,
                              chunk_steps=2)
    eng3 = Engine(
        _params(model), model,
        ServeConfig(max_seq=32, max_new_tokens=4, paged=True, block_size=4,
                    pool_blocks=1),
    )
    with pytest.raises(ValueError, match="pool_blocks must be >= 2"):
        eng3.serve_continuous([np.arange(4, dtype=np.int32)], slots=1,
                              chunk_steps=2)
