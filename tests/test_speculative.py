"""Speculative-decoding battery (DESIGN.md §9, serve/speculative.py).

The contract under test: ``ServeConfig(spec_k >= 1)`` is a pure
*throughput* knob — for every request, speculative outputs (greedy AND
temperature > 0) are bit-identical to the non-speculative engine and to a
solo ``Engine.generate`` call, across dense/paged x prefix on/off x int8-KV
on/off, across drafter quality (a full-depth drafter accepts everything; a
garbage drafter rejects everything), and across scheduler pressure (EOS
mid-window, slot recycling, preemption-with-recompute with a live draft
cache).

Also unit-covers the subsystem's pieces (split_chain / accept_window /
DraftModel / trim_request / complete_spec_window / worst_case_blocks), the
batched prefix-block copies (satellite: ``lm.copy_paged_blocks``), the
retrace budget (no recompiles after warmup), the kanlint drafter-cache
donation rule, and the CLI flag validation (invalid ``--spec-k`` => rc 2).

Property tests honor the ``tests/conftest.py`` hypothesis fallback shim.
"""

from __future__ import annotations

import textwrap

import hypothesis
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.analysis import ast_rules
from repro.models import lm
from repro.serve import speculative as sp
from repro.serve.engine import Engine, ServeConfig
from repro.serve.kv_pool import BlockPool, blocks_for, worst_case_blocks
from repro.serve.scheduler import ContinuousScheduler
from repro.serve.speculative import DraftModel

from conftest import assert_logits_match, assert_trees_match, run_jax_subprocess

MAX_NEW = 6


@pytest.fixture(autouse=True, scope="module")
def _fresh_jax_caches():
    """This module runs near the end of the tier-1 suite, after hundreds of
    compiles have accumulated in-process; XLA's CPU backend has been seen
    to segfault on the NEXT compile in that state.  Start from clean
    compilation caches (earlier modules are already done; worst case a
    later reuse recompiles)."""
    import gc

    jax.clear_caches()
    gc.collect()
    yield

# lazy singletons (hypothesis fallback shim: no fixtures in property tests);
# engines are memoized per config because every Engine re-jits its programs
_ARCH = None
_PARAMS = None
_ENGINES: dict = {}


def arch_params():
    global _ARCH, _PARAMS
    if _ARCH is None:
        _ARCH = configs.get_reduced("kanformer-100m")
        _PARAMS = lm.init_params(jax.random.PRNGKey(0), _ARCH.model)
    return _ARCH, _PARAMS


def get_engine(spec_k=0, temp=0.0, paged=False, paged_read="shadow",
               prefix=True, pool_blocks=None, draft_layers=1,
               draft_quant=False, quant_kv=False, draft=None) -> Engine:
    key = (spec_k, temp, paged, paged_read, prefix, pool_blocks,
           draft_layers, draft_quant, quant_kv, id(draft))
    if key not in _ENGINES:
        arch, params = arch_params()
        model = arch.model
        if quant_kv:
            from repro.configs.common import enable_kv_quant
            model = enable_kv_quant(arch).model
        _ENGINES[key] = Engine(params, model, ServeConfig(
            max_seq=48, max_new_tokens=MAX_NEW, temperature=temp,
            paged=paged, block_size=8, pool_blocks=pool_blocks,
            paged_read=paged_read, prefix_caching=prefix,
            spec_k=spec_k, draft_layers=draft_layers,
            draft_quant=draft_quant, draft=draft,
        ))
    return _ENGINES[key]


RS = np.random.RandomState(11)
POOL = [RS.randint(1, 500, L).astype(np.int32) for L in (4, 5, 7, 9, 12, 14)]

_SOLO_MEMO: dict = {}


def solo(req: np.ndarray, rid: int, max_new: int, eos: int,
         temp: float = 0.0) -> np.ndarray:
    """Isolated single-request generation with the request's OWN sampling
    identity — the oracle every scheduling (speculative or not) must hit
    bit-for-bit."""
    key = (req.tobytes(), rid, max_new, eos, temp)
    if key not in _SOLO_MEMO:
        _SOLO_MEMO[key] = get_engine(temp=temp).generate(
            req[None].astype(np.int32), seed=0,
            request_ids=np.asarray([rid], np.int32),
            max_new=max_new, eos_id=eos,
        )[0]
    return _SOLO_MEMO[key]


def assert_matches_solo(outs, reqs, budgets=None, eos=-1, temp=0.0):
    budgets = budgets or [MAX_NEW] * len(reqs)
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(
            solo(r, i, budgets[i], eos, temp), outs[i],
            err_msg=f"request {i} diverged from solo generate",
        )


# ---------------------------------------------------------------------------
# unit: PRNG chain splitting
# ---------------------------------------------------------------------------


def test_split_chain_matches_sequential_splits():
    keys = jax.vmap(jax.random.split)(
        jnp.stack([jax.random.PRNGKey(s) for s in (3, 7, 11)])
    )[:, 0]
    kts, chains = sp.split_chain(keys, 4)
    assert kts.shape == (3, 4, 2) and chains.shape == (3, 5, 2)
    # replay the sequential engine body split for split
    carry = keys
    for j in range(4):
        np.testing.assert_array_equal(np.asarray(chains[:, j]),
                                      np.asarray(carry))
        pairs = jax.vmap(jax.random.split)(carry)
        carry, kt = pairs[:, 0], pairs[:, 1]
        np.testing.assert_array_equal(np.asarray(kts[:, j]), np.asarray(kt))
    np.testing.assert_array_equal(np.asarray(chains[:, 4]), np.asarray(carry))


# ---------------------------------------------------------------------------
# unit: acceptance math
# ---------------------------------------------------------------------------


def test_accept_window_prefix_and_bonus():
    draft = jnp.asarray([[5, 6, 7], [5, 9, 7], [1, 2, 3]])
    target = jnp.asarray([[5, 6, 7, 8], [5, 6, 7, 8], [9, 9, 9, 9]])
    emitted, m, eos_new = sp.accept_window(
        draft, target, jnp.asarray([False] * 3), jnp.int32(-1), jnp.int32(0))
    np.testing.assert_array_equal(np.asarray(m), [4, 2, 1])
    np.testing.assert_array_equal(
        np.asarray(emitted),
        [[5, 6, 7, 8], [5, 6, 0, 0], [9, 0, 0, 0]])
    assert not np.asarray(eos_new).any()


def test_accept_window_eos_truncates_and_latches():
    draft = jnp.asarray([[5, 77, 7]])
    target = jnp.asarray([[5, 77, 7, 8]])
    emitted, m, eos_new = sp.accept_window(
        draft, target, jnp.asarray([False]), jnp.int32(77), jnp.int32(0))
    # EOS at window position 1 is EMITTED, later accepted positions pad
    np.testing.assert_array_equal(np.asarray(emitted), [[5, 77, 0, 0]])
    np.testing.assert_array_equal(np.asarray(m), [2])
    assert bool(np.asarray(eos_new)[0])
    # eos at the bonus position: full window emits
    e2, m2, eos2 = sp.accept_window(
        jnp.asarray([[5, 6, 7]]), jnp.asarray([[5, 6, 7, 77]]),
        jnp.asarray([False]), jnp.int32(77), jnp.int32(0))
    np.testing.assert_array_equal(np.asarray(m2), [4])
    assert bool(np.asarray(eos2)[0])


def test_accept_window_latched_row_emits_nothing():
    emitted, m, eos_new = sp.accept_window(
        jnp.asarray([[5, 6, 7]]), jnp.asarray([[5, 6, 7, 8]]),
        jnp.asarray([True]), jnp.int32(-1), jnp.int32(9))
    np.testing.assert_array_equal(np.asarray(emitted), [[9, 9, 9, 9]])
    np.testing.assert_array_equal(np.asarray(m), [0])
    assert bool(np.asarray(eos_new)[0])   # stays latched


# ---------------------------------------------------------------------------
# unit: DraftModel derivation
# ---------------------------------------------------------------------------


def test_draft_model_slices_unit_and_aliases_the_rest():
    arch, params = arch_params()
    d = DraftModel.from_target(params, arch.model, n_layers=1)
    assert d.cfg.n_repeats == 1 and arch.model.n_repeats == 2
    for blk_full, blk_draft in zip(params["unit"], d.params["unit"]):
        for a, b in zip(jax.tree.leaves(blk_full), jax.tree.leaves(blk_draft)):
            assert b.shape[0] == 1 and a.shape[1:] == b.shape[1:]
    # non-unit leaves are ALIASED, not copied (no extra HBM)
    assert d.params["embed"] is params["embed"]


def test_draft_model_validates_layers_and_arch():
    arch, params = arch_params()
    with pytest.raises(ValueError):
        DraftModel.from_target(params, arch.model, n_layers=0)
    with pytest.raises(ValueError):
        DraftModel.from_target(params, arch.model,
                               n_layers=arch.model.n_repeats + 1)


def test_draft_model_quant_roundtrips_without_touching_target():
    arch, params = arch_params()
    before = jax.tree.map(lambda a: np.asarray(a).copy(), params["unit"])
    d = DraftModel.from_target(params, arch.model, n_layers=1, quant=True)
    assert d.quant
    # target unit leaves untouched
    for blk_b, blk_p in zip(before, params["unit"]):
        for a, b in zip(jax.tree.leaves(blk_b), jax.tree.leaves(blk_p)):
            np.testing.assert_array_equal(a, np.asarray(b))
    # quantized drafter leaves take at most 255 distinct scaled levels per
    # output channel and stay within rounding error of the originals
    leaf = jax.tree.leaves(d.params["unit"][0])[0]
    src = jax.tree.leaves(params["unit"][0])[0][:1]
    err = np.abs(np.asarray(leaf, np.float32) - np.asarray(src, np.float32))
    scale = np.abs(np.asarray(src, np.float32)).max(axis=-1, keepdims=True)
    assert (err <= scale / 127.0 * 0.5 + 1e-7).all()


# ---------------------------------------------------------------------------
# unit: pool trim + worst-case bound + scheduler window accounting
# ---------------------------------------------------------------------------


def test_worst_case_blocks_spec_bound():
    # spec windows can write past the chunk bound: start at the last live
    # position and lay down spec_k drafts
    assert worst_case_blocks(4, 8, 4, 8, 64, spec_k=0) == \
        worst_case_blocks(4, 8, 4, 8, 64)
    assert worst_case_blocks(4, 8, 4, 8, 64, spec_k=3) == \
        blocks_for(4 + 8 - 1 + 3, 8)
    # clamped by max_seq like the chunk bound
    assert worst_case_blocks(4, 8, 4, 8, 16, spec_k=8) == blocks_for(16, 8)


def test_trim_request_releases_only_fresh_tail():
    pool = BlockPool(10, 8)
    got = pool.alloc(0, 5)
    freed = pool.trim_request(0, 2)
    assert freed == got[2:] and pool.owned_blocks(0) == got[:2]
    assert pool.free_count() == pool.usable - 2
    pool.release_request(0)
    pool.check_balanced(0)


def test_trim_request_refuses_shared_and_cached_blocks():
    pool = BlockPool(10, 8)
    blocks = pool.alloc(0, 2)
    pool.cache_ref(blocks[1])          # prefix cache holds the tail block
    with pytest.raises(AssertionError):
        pool.trim_request(0, 1)


def test_complete_spec_window_variable_emissions():
    sched = ContinuousScheduler(2, range(2))
    for b, rid in sched.admit_ready():
        sched.confirm_admit(b, rid, pos=4, remaining=5, eos_hit=False)
    out = sched.complete_spec_window(4, emitted_counts=[3, 7],
                                     eos_hits=[False, False])
    # row 0 keeps its 3 emissions; row 1 overshoots the budget: clamped to
    # remaining=5 and retired
    assert out == [(0, 0, 3, False), (1, 1, 5, True)]
    assert sched.table.slots[0].remaining == 2
    assert sched.total_token_steps == 8          # window capacity charged
    assert sched.useful_token_steps == 8         # 3 + 5 kept


# ---------------------------------------------------------------------------
# model-level: fused verify == sequential decode, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("quant_kv", [False, True])
def test_verify_window_logits_match_sequential_decode(quant_kv):
    """The batch-axis invariance the acceptance rule stands on: scoring W
    window positions in one fused forward gives the logits (and caches) of
    W sequential decode_step calls.  They are different programs, so the
    match is to the fp32 reorder tolerance, with equal greedy tokens
    wherever the margin decides them."""
    arch, params = arch_params()
    model = arch.model
    if quant_kv:
        from repro.configs.common import enable_kv_quant
        model = enable_kv_quant(arch).model
    toks = jnp.asarray(np.stack([POOL[2][:7], POOL[5][:7]]), jnp.int32)
    W = 4
    window = jnp.asarray(
        np.random.RandomState(5).randint(1, 500, (2, W)), jnp.int32)
    _, seq_caches = lm.prefill(params, model, {"tokens": toks}, 48,
                               jnp.float32)
    _, ver_caches = lm.prefill(params, model, {"tokens": toks}, 48,
                               jnp.float32)
    pos = jnp.asarray([7, 7], jnp.int32)
    seq_logits = []
    p = pos
    for j in range(W):
        lg, seq_caches = lm.decode_step(
            params, model, window[:, j:j + 1], seq_caches, p, jnp.float32)
        seq_logits.append(lg)
        p = p + 1
    ver_logits, ver_caches = lm.verify_window(
        params, model, window, ver_caches, pos, jnp.float32)
    assert ver_logits.shape == (2, W, model.vocab)
    for j in range(W):
        assert_logits_match(
            seq_logits[j], ver_logits[:, j],
            err_msg=f"window position {j} diverged (quant_kv={quant_kv})")
    assert_trees_match(seq_caches, ver_caches)


# ---------------------------------------------------------------------------
# engine-level bit-identity (the acceptance sweep)
# ---------------------------------------------------------------------------


@hypothesis.settings(max_examples=6, deadline=None)
@hypothesis.given(
    order_seed=st.integers(0, 10_000),
    n_requests=st.integers(1, 5),
    slots=st.integers(1, 3),
    spec_k=st.integers(1, 3),
    paged=st.booleans(),
    paged_read=st.sampled_from(["shadow", "step"]),
    prefix=st.booleans(),
    temp=st.sampled_from([0.0, 0.7]),
    eos_pos=st.integers(-1, MAX_NEW - 1),
    budget_seed=st.integers(0, 10_000),
)
def test_property_speculative_bit_identity(order_seed, n_requests, slots,
                                           spec_k, paged, paged_read, prefix,
                                           temp, eos_pos, budget_seed):
    """Random request sets x random (spec_k, dense/paged, shadow/step,
    prefix on/off, greedy/sampled, EOS placement, budgets): every output is
    bit-identical to the isolated non-speculative generation, and the paged
    pool drains balanced."""
    rs = np.random.RandomState(order_seed)
    reqs = [POOL[rs.randint(len(POOL))] for _ in range(n_requests)]
    bs = np.random.RandomState(budget_seed)
    budgets = [int(bs.randint(1, MAX_NEW + 1)) for _ in range(n_requests)]
    if eos_pos >= 0:
        probe = solo(reqs[0], 0, MAX_NEW, -1, temp)
        eos = int(probe[min(eos_pos, budgets[0] - 1)])
    else:
        eos = -1
    eng = get_engine(spec_k=spec_k, temp=temp, paged=paged,
                     paged_read=paged_read, prefix=prefix)
    old = eng.cfg.eos_id
    eng.cfg.eos_id = eos               # traced arg — no retrace
    try:
        outs = eng.serve_continuous(reqs, slots=slots, chunk_steps=4,
                                    seed=0, max_new=budgets)
    finally:
        eng.cfg.eos_id = old
    assert eng.last_serve_stats["n_served"] == n_requests
    stats = eng.last_serve_stats["spec"]
    assert 0.0 <= stats["acceptance_rate"] <= 1.0
    assert stats["spec_k"] == spec_k
    assert_matches_solo(outs, reqs, budgets, eos, temp)
    if paged:
        eng._last_pool.check_balanced(0)


def test_speculative_matches_non_speculative_engine():
    """spec_k is a pure throughput knob: same outputs as the spec_k=0
    continuous engine under the same scheduling shape."""
    reqs = [POOL[0], POOL[2], POOL[5], POOL[1], POOL[3]]
    base = get_engine().serve_continuous(reqs, slots=2, chunk_steps=3, seed=0)
    outs = get_engine(spec_k=2).serve_continuous(
        reqs, slots=2, chunk_steps=3, seed=0)
    for a, b in zip(base, outs):
        np.testing.assert_array_equal(a, b)


def test_full_depth_drafter_accepts_everything():
    """draft_layers == n_repeats makes the drafter the target: every draft
    matches its verified token, so acceptance is exactly 1.0 (and the
    emitted stream is still the target chain's)."""
    arch, _ = arch_params()
    for temp in (0.0, 0.7):
        eng = get_engine(spec_k=2, temp=temp,
                         draft_layers=arch.model.n_repeats)
        outs = eng.serve_continuous(list(POOL), slots=3, chunk_steps=4,
                                    seed=0)
        assert eng.last_serve_stats["spec"]["acceptance_rate"] == 1.0
        assert_matches_solo(outs, POOL, temp=temp)


def _zero_drafter() -> DraftModel:
    arch, params = arch_params()
    d = DraftModel.from_target(params, arch.model, n_layers=1)
    dparams = dict(d.params)
    dparams["embed"] = {"table": jnp.zeros_like(params["embed"]["table"])}
    return DraftModel(params=dparams, cfg=d.cfg, n_layers=1)


_ZERO_DRAFTER = None


def test_garbage_drafter_rejects_everything_but_stays_exact():
    """The worst-case drafter (all-zero logits proposes token 0 forever):
    every draft is rejected, every window emits exactly one bonus token,
    the paged trim rolls back the whole rejected span each window — and
    outputs still match solo bit for bit."""
    global _ZERO_DRAFTER
    if _ZERO_DRAFTER is None:
        _ZERO_DRAFTER = _zero_drafter()
    for paged in (False, True):
        eng = get_engine(spec_k=3, paged=paged, draft=_ZERO_DRAFTER)
        outs = eng.serve_continuous(list(POOL), slots=3, chunk_steps=4,
                                    seed=0)
        stats = eng.last_serve_stats["spec"]
        assert stats["acceptance_rate"] == 0.0
        # admission prefill emits each request's first token; the remaining
        # budget is all window emissions, one bonus token per window
        assert stats["emitted_tokens"] == (MAX_NEW - 1) * len(POOL)
        assert_matches_solo(outs, POOL)
        if paged:
            eng._last_pool.check_balanced(0)


def test_quant_kv_speculative_matches_quant_solo():
    """int8 KV quant target: the window write-then-dequantized-attend path
    must reproduce the sequential quantized decode bitwise."""
    reqs = [POOL[0], POOL[3], POOL[4], POOL[5]]
    qsolo = get_engine(quant_kv=True).generate(
        np.stack([np.pad(r, (0, 14 - len(r))) for r in reqs]).astype(np.int32),
        seed=0, lengths=np.asarray([len(r) for r in reqs], np.int32),
        request_ids=np.arange(len(reqs), dtype=np.int32),
    )
    for paged in (False, True):
        eng = get_engine(spec_k=2, paged=paged, quant_kv=True)
        outs = eng.serve_continuous(reqs, slots=2, chunk_steps=4, seed=0)
        for i in range(len(reqs)):
            np.testing.assert_array_equal(qsolo[i], outs[i])


# ---------------------------------------------------------------------------
# scheduler edge cases the draft loop stresses (ISSUE satellite)
# ---------------------------------------------------------------------------


def test_eos_mid_draft_window_latches_and_pads():
    """EOS emitted inside a window: the row latches at the accepted
    position, the rest of the window pads, and the final output equals the
    sequential EOS semantics exactly."""
    probe = solo(POOL[2], 0, MAX_NEW, -1)
    eos = int(probe[2])                # fires mid-stream, mid-window
    eng = get_engine(spec_k=3)
    old = eng.cfg.eos_id
    eng.cfg.eos_id = eos
    try:
        outs = eng.serve_continuous([POOL[2], POOL[4], POOL[0]],
                                    slots=3, chunk_steps=4, seed=0)
    finally:
        eng.cfg.eos_id = old
    assert_matches_solo(outs, [POOL[2], POOL[4], POOL[0]], eos=eos)


def test_slot_recycled_between_windows():
    """More requests than slots + tiny budgets: slots recycle constantly,
    each admission must re-seed BOTH the target and drafter cache rows
    (lockstep across recycling)."""
    reqs = [POOL[i % len(POOL)] for i in range(7)]
    budgets = [2, 5, 1, 6, 3, 2, 4]
    eng = get_engine(spec_k=2)
    outs = eng.serve_continuous(reqs, slots=2, chunk_steps=4, seed=0,
                                max_new=budgets)
    assert_matches_solo(outs, reqs, budgets)


def test_preemption_with_live_draft_cache():
    """Pool sized to force preempt-youngest while drafts are in flight:
    the preempted request restarts from scratch (target AND drafter rows
    re-prefilled) and still produces the identical stream."""
    reqs = [POOL[i % len(POOL)] for i in range(8)]
    eng = get_engine(spec_k=3, paged=True, pool_blocks=8)
    outs = eng.serve_continuous(reqs, slots=4, chunk_steps=4, seed=0)
    assert eng.last_serve_stats["n_preemptions"] > 0, (
        "pool was not tight enough to force preemption — shrink pool_blocks")
    assert_matches_solo(outs, reqs)
    eng._last_pool.check_balanced(0)


# ---------------------------------------------------------------------------
# retrace budget: speculative serving compiles a fixed program set
# ---------------------------------------------------------------------------


def test_speculative_retrace_budget_no_programs_after_warmup():
    eng = get_engine(spec_k=2, paged=True)
    reqs = [POOL[0], POOL[2], POOL[5], POOL[3]]
    eng.serve_continuous(reqs, slots=2, chunk_steps=4, seed=0)
    warm = {n: s["programs"]
            for n, s in eng.compiles.snapshot().items()}
    assert warm.get("draft_chunk", 0) >= 1
    assert warm.get("verify_window", 0) >= 1
    assert warm.get("draft_prefill", 0) >= 1
    eng.serve_continuous(reqs, slots=2, chunk_steps=4, seed=0)
    after = {n: s["programs"] for n, s in eng.compiles.snapshot().items()}
    retraced = {n: after[n] - warm.get(n, 0)
                for n in after if after[n] != warm.get(n, 0)}
    assert retraced == {}, f"programs_after_warmup: {retraced}"


# ---------------------------------------------------------------------------
# satellite: batched prefix-block copies
# ---------------------------------------------------------------------------


def test_copy_paged_blocks_matches_sequential_singles():
    arch, params = arch_params()
    caches_a = lm.init_paged_caches(arch.model, 12, 8, jnp.float32)
    # fill with recognizable values
    caches_a = jax.tree.map(
        lambda a: jnp.arange(a.size, dtype=a.dtype).reshape(a.shape)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, caches_a)
    caches_b = jax.tree.map(lambda a: a, caches_a)
    srcs, dsts = [1, 3, 5], [7, 8, 9]
    out_a = lm.copy_paged_blocks(caches_a, srcs, dsts)
    out_b = caches_b
    for s, d in zip(srcs, dsts):
        out_b = lm.copy_paged_block(out_b, s, d)
    for a, b in zip(jax.tree.leaves(out_a), jax.tree.leaves(out_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# satellite: kanlint covers the drafter-cache donation pattern
# ---------------------------------------------------------------------------


def _lint(src: str):
    return ast_rules.lint_source(textwrap.dedent(src),
                                 "src/repro/serve/x.py")


def test_kl101_flags_undonated_draft_caches():
    fs = _lint("""
        import jax
        step = jax.jit(lambda dparams, draft_caches: draft_caches)
    """)
    assert sorted(f.rule for f in fs) == ["KL101"]
    assert "draft_caches" in fs[0].message


def test_kl101_draft_caches_donation_satisfies():
    fs = _lint("""
        import jax
        step = jax.jit(lambda dparams, draft_caches: draft_caches,
                       donate_argnums=(1,))
    """)
    assert fs == []


# ---------------------------------------------------------------------------
# satellite: CLI flag validation (subprocess; invalid spec-k => rc 2)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("argv", [
    ["-m", "repro.launch.serve", "--arch", "kanformer-100m",
     "--engine", "continuous", "--spec-k", "-1"],
    ["-m", "repro.launch.serve", "--arch", "kanformer-100m",
     "--engine", "static", "--spec-k", "2"],
    ["-m", "repro.launch.serve", "--arch", "kanformer-100m",
     "--engine", "continuous", "--spec-k", "2", "--draft-layers", "99"],
    ["examples/serve_kan.py", "--spec-k", "-1"],
    ["examples/serve_kan.py", "--engine", "static", "--spec-k", "2"],
])
def test_cli_invalid_spec_flags_exit_2(argv):
    res = run_jax_subprocess(argv=argv)
    assert res.returncode == 2, (res.returncode, res.stderr[-500:])


def test_engine_rejects_negative_spec_k():
    arch, params = arch_params()
    with pytest.raises(ValueError):
        Engine(params, arch.model,
               ServeConfig(max_seq=48, max_new_tokens=4, spec_k=-1))
