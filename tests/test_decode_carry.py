"""The decode step carries the unit's stacked caches through its layer scan.

``lm.decode_step`` threads the stacked cache leaves ``(R, B, S, ...)``
through the layer ``lax.scan`` as carry: each GQA layer writes its token at
``[layer, b, pos[b]]`` in place and reads its own layer back, and blocks
with per-sequence state slice their layer, step it and put it back.  Two
contracts:

* **bit-identity** — logits and every cache leaf equal, bit for bit, those
  of the layer scan that takes each layer's cache as ``xs`` and stacks the
  updated ones as ``ys`` (the frozen oracle below), over ragged positions,
  for every block kind and both layer-loop modes;
* **structure** — the engine's compiled decode chunk builds no
  cache-shaped ``broadcast``, ``copy`` or ``dynamic-update-slice`` of the
  stacked caches: the per-token write is the only op that produces them.
"""

import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs import gemma3_12b, xlstm_1_3b, zamba2_1_2b
from repro.configs.common import enable_kv_quant
from repro.models import blocks as B
from repro.models import layers as L
from repro.models import lm
from repro.serve.engine import Engine, ServeConfig


def decode_step_xs_ys(params, cfg, tokens, caches, pos, compute_dtype,
                      table=None):
    """The decode step as it was when each layer's cache slice entered the
    layer scan as ``xs`` and the updated slices left it as stacked ``ys``:
    the oracle the carried-cache step must reproduce bit for bit."""
    d = cfg.d_model
    if cfg.input_kind == "tokens" or cfg.input_kind == "mixed":
        h = L.embed_lookup(params["embed"], tokens, compute_dtype) * math.sqrt(d)
    else:
        h = tokens.astype(compute_dtype)
    shared = params.get("shared", [])
    new_caches: dict = {}

    if cfg.prologue:
        ncs = []
        for p_blk, blk, c in zip(params["prologue"], cfg.prologue, caches["prologue"]):
            h, c2 = B.block_decode_step(p_blk, blk, h, c, pos, table)
            ncs.append(c2)
        new_caches["prologue"] = ncs

    def unit_body(carry, xs):
        h_c = carry
        rep_params, rep_caches = xs
        new_rep = []
        for i, blk in enumerate(cfg.unit):
            p = shared[blk.shared_id] if blk.shared_id is not None else rep_params[i]
            h_c, c2 = B.block_decode_step(p, blk, h_c, rep_caches[i], pos, table)
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
            h, c2 = B.block_decode_step(p_blk, blk, h, c, pos, table)
            ncs.append(c2)
        new_caches["epilogue"] = ncs

    h = L.rmsnorm(params["final_ln"], h)
    logits = L.unembed_logits(params["embed"], h)[:, 0]
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        logits = c * jnp.tanh(logits / c)
    return logits, new_caches


def _qwen():
    return configs.get_reduced("qwen1.5-0.5b").model


# name -> (model config, paged)
CASES = {
    "kanformer": (configs.get_reduced("kanformer-100m").model, False),
    "qwen": (_qwen(), False),
    "kv_quant": (enable_kv_quant(configs.get_reduced("qwen1.5-0.5b")).model,
                 False),
    # 5 ring-buffer layers (window 8) and one full layer per repeat
    "window": (gemma3_12b.build(n_repeats=2, d_model=64, n_heads=4, n_kv=2,
                                d_ff=128, vocab=512, head_dim=16,
                                window=8).model, False),
    # mamba2 state blocks + a shared attention block, mamba2 epilogue
    "hybrid_mamba2": (zamba2_1_2b.build(n_repeats=2, mamba_per_unit=2, tail=1,
                                        d_model=64, n_heads=4, n_kv=4,
                                        d_ff=128, vocab=512,
                                        d_state=16).model, False),
    "xlstm": (xlstm_1_3b.build(n_repeats=2, mlstm_per_unit=2, d_model=64,
                               n_heads=2, vocab=512).model, False),
    # MLA latent cache in the unit, dense prologue
    "mla": (configs.get_reduced("deepseek-v2-lite").model, False),
    "no_scan": (dataclasses.replace(_qwen(), scan_layers=False), False),
    "paged_step": (_qwen(), True),
}

BATCH, MAX_SEQ, BLOCK, STEPS = 3, 16, 4, 3


def _random_like(key, tree):
    """Random finite values of each leaf's dtype and shape (int8 leaves in
    the quantizer's range, scales positive)."""
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, a in zip(keys, leaves):
        if a.dtype == jnp.int8:
            out.append(jax.random.randint(k, a.shape, -127, 128, jnp.int32)
                       .astype(jnp.int8))
        else:
            v = jax.random.uniform(k, a.shape, jnp.float32, 0.01, 0.5)
            out.append(v.astype(a.dtype))
    return jax.tree.unflatten(treedef, out)


def _setup(name):
    model, paged = CASES[name]
    params = lm.init_params(jax.random.PRNGKey(1), model)
    if paged:
        n_logical = MAX_SEQ // BLOCK
        n_blocks = BATCH * n_logical + 1
        caches = lm.init_paged_caches(model, n_blocks, BLOCK, jnp.bfloat16)
        # rows own disjoint shuffled blocks; block 0 stays the sentinel
        ids = np.random.RandomState(0).permutation(np.arange(1, n_blocks))
        table = jnp.asarray(ids.reshape(BATCH, n_logical), jnp.int32)
    else:
        caches = lm.init_caches(model, BATCH, MAX_SEQ, jnp.bfloat16)
        table = None
    caches = _random_like(jax.random.PRNGKey(2), caches)
    return model, params, caches, table


@pytest.mark.parametrize("name", sorted(CASES))
def test_carried_decode_step_bit_identical_to_xs_ys(name):
    model, params, caches, table = _setup(name)
    # ragged rows; under the window the last row's ring has wrapped
    pos = jnp.asarray([1, 6, 10], jnp.int32)
    new = jax.jit(lambda p, t, c, q: lm.decode_step(
        p, model, t, c, q, jnp.bfloat16, table))
    old = jax.jit(lambda p, t, c, q: decode_step_xs_ys(
        p, model, t, c, q, jnp.bfloat16, table))
    c_new = c_old = caches
    toks = jax.random.randint(jax.random.PRNGKey(3), (STEPS, BATCH, 1), 0,
                              model.vocab)
    for s in range(STEPS):
        lg_new, c_new = new(params, toks[s], c_new, pos + s)
        lg_old, c_old = old(params, toks[s], c_old, pos + s)
        np.testing.assert_array_equal(np.asarray(lg_new), np.asarray(lg_old))
        flat_new = jax.tree_util.tree_flatten_with_path(c_new)[0]
        flat_old = jax.tree.leaves(c_old)
        assert len(flat_new) == len(flat_old)
        for (path, a), b in zip(flat_new, flat_old):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"step {s} {jax.tree_util.keystr(path)}")


@pytest.mark.parametrize("name", ["qwen", "no_scan"])
def test_carried_decode_step_synchronized_position(name):
    """A scalar position (synchronized decode) writes through the dynamic
    update slice at ``[layer, 0, pos]``; still bit-equal to the oracle."""
    model, params, caches, _ = _setup(name)
    tok = jnp.asarray([[5], [7], [9]], jnp.int32)
    pos = jnp.asarray(4, jnp.int32)
    lg_new, c_new = jax.jit(lambda p, c: lm.decode_step(
        p, model, tok, c, pos, jnp.bfloat16))(params, caches)
    lg_old, c_old = jax.jit(lambda p, c: decode_step_xs_ys(
        p, model, tok, c, pos, jnp.bfloat16))(params, caches)
    np.testing.assert_array_equal(np.asarray(lg_new), np.asarray(lg_old))
    for a, b in zip(jax.tree.leaves(c_new), jax.tree.leaves(c_old)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


_CACHE_COPY = re.compile(
    r"=\s*\w+\[(?P<dims>[\d,]+)\]\S*\s+(?P<op>broadcast|copy|dynamic-update-slice)\(")


@pytest.mark.parametrize("arch", ["kanformer-100m", "qwen1.5-0.5b"])
def test_decode_chunk_builds_no_stacked_cache_copies(arch):
    """The engine's decode chunk on the paged shadow path (the default read
    path: the dense step runs on the gathered view) compiles without a
    broadcast, copy or dynamic-update-slice whose result has the stacked
    view's shape: no per-layer slice goes in, no stacked output comes out."""
    model = configs.get_reduced(arch).model
    params = lm.init_params(jax.random.PRNGKey(0), model)
    max_seq, slots = 48, 2
    eng = Engine(params, model, ServeConfig(
        max_seq=max_seq, max_new_tokens=4, paged=True, block_size=4,
        compute_dtype="bfloat16"))
    assert eng.cfg.paged_read == "shadow"
    rs = np.random.RandomState(0)
    reqs = [rs.randint(0, 100, n).astype(np.int32) for n in (5, 9, 7)]
    eng.serve_continuous(reqs, slots=slots, chunk_steps=2, seed=0)
    (low,) = eng.lowered("decode_chunk")
    hlo = low.compile().as_text()
    attn = model.unit[0].attn
    stacked = (model.n_repeats, slots, max_seq, attn.n_kv_heads, attn.head_dim)
    dims = ",".join(map(str, stacked))
    assert re.search(rf"\[{dims}\]", hlo), "the stacked view is in the program"
    found = [m.group("op") for m in _CACHE_COPY.finditer(hlo)
             if m.group("dims") == dims]
    assert not found, found
