"""int8 KV-cache decode (serving-memory feature) vs bf16-cache reference,
plus the paged carry-over: int8 pool blocks must read bit-equal to the
int8 dense cache (KANtize's low-bit treatment survives the paged layout)."""

import jax
import jax.numpy as jnp
import numpy as np

from conftest import assert_logits_match
from repro import configs
from repro.configs.common import enable_kv_quant
from repro.models import lm
from repro.serve.engine import Engine, ServeConfig


def test_kv_quant_decode_close_to_fp():
    base = configs.get_reduced("qwen1.5-0.5b")
    quant = enable_kv_quant(base)
    params = lm.init_params(jax.random.PRNGKey(0), base.model)
    B, T = 2, 12
    rs = np.random.RandomState(0)
    toks = jnp.asarray(rs.randint(0, base.model.vocab, (B, T)), jnp.int32)

    def teacher_force(model):
        caches = lm.init_caches(model, B, T, dtype=jnp.float32)
        outs = []
        for t in range(T):
            lg, caches = lm.decode_step(
                params, model, toks[:, t : t + 1], caches,
                jnp.asarray(t, jnp.int32), jnp.float32,
            )
            outs.append(lg)
        return jnp.stack(outs, 1)

    fp = teacher_force(base.model)
    q8 = teacher_force(quant.model)
    # int8 cache: logits close; top-1 prediction nearly always identical
    rel = float(jnp.abs(fp - q8).max() / (jnp.abs(fp).max() + 1e-9))
    agree = float((jnp.argmax(fp, -1) == jnp.argmax(q8, -1)).mean())
    assert rel < 0.1, rel
    assert agree > 0.9, agree


def test_paged_quant_decode_bit_equal_dense_quant():
    """Quantized paged reads == quantized dense reads: the pool stores the
    same int8 values + fp32 scales the dense cache stores (identical
    per-(token, kv-head) quantization), the gather is pure data movement,
    and the chunked dequant flash-decode runs unchanged on the gathered
    view.  The two are different programs, so logits agree to the fp32
    reorder tolerance."""
    quant = enable_kv_quant(configs.get_reduced("qwen1.5-0.5b"))
    model = quant.model
    params = lm.init_params(jax.random.PRNGKey(0), model)
    B, max_seq, bs = 2, 24, 4
    nlog = max_seq // bs
    rs = np.random.RandomState(7)
    T = 6
    toks = rs.randint(0, model.vocab, (B, T)).astype(np.int32)
    logits_d, caches_d = lm.prefill(
        params, model, {"tokens": jnp.asarray(toks)}, max_seq, jnp.float32
    )
    n_blocks = 2 * B * nlog + 1
    pools = lm.init_paged_caches(model, n_blocks, bs, jnp.float32)
    perm = rs.permutation(np.arange(1, n_blocks))[: B * nlog]
    tables = jnp.asarray(perm.reshape(B, nlog).astype(np.int32))
    last_p, pools = lm.prefill_into_pages(
        params, model, jnp.asarray(toks), jnp.full((B,), T, jnp.int32),
        tables, pools, 0, jnp.float32,
    )
    # prefill attention sees RAW K/V on both paths; stored blocks are int8
    assert_logits_match(logits_d[:, T - 1], last_p)
    assert pools["unit"][0]["k"].dtype == jnp.int8
    tok = jnp.argmax(last_p, -1).astype(jnp.int32)[:, None]
    pos = jnp.full((B,), T, jnp.int32)
    for _ in range(4):
        lg_d, caches_d = lm.decode_step(
            params, model, tok, caches_d, pos, jnp.float32
        )
        lg_p, pools = lm.decode_step(
            params, model, tok, pools, pos, jnp.float32, table=tables
        )
        assert_logits_match(lg_d, lg_p)
        tok = jnp.argmax(lg_p, -1).astype(jnp.int32)[:, None]
        pos = pos + 1


def test_paged_quant_serving_bit_equal_dense_and_solo():
    """End-to-end: int8-cache paged serve_continuous == int8 dense
    serve_continuous == int8 solo generate (prefix reuse auto-disables
    under quant — reused blocks could only supply dequantized prefill
    values, and bit-identity wins)."""
    quant = enable_kv_quant(configs.get_reduced("qwen1.5-0.5b"))
    model = quant.model
    params = lm.init_params(jax.random.PRNGKey(0), model)
    rs = np.random.RandomState(2)
    reqs = [rs.randint(0, model.vocab, L).astype(np.int32)
            for L in (5, 9, 9, 12)]
    dense = Engine(params, model, ServeConfig(max_seq=32, max_new_tokens=5))
    paged = Engine(params, model,
                   ServeConfig(max_seq=32, max_new_tokens=5, paged=True,
                               block_size=4, pool_blocks=20))
    out_d = dense.serve_continuous(reqs, slots=2, chunk_steps=2, seed=0)
    out_p = paged.serve_continuous(reqs, slots=2, chunk_steps=2, seed=0)
    for i, r in enumerate(reqs):
        ref = dense.generate(r[None].astype(np.int32), seed=0,
                             request_ids=np.asarray([i]))[0]
        np.testing.assert_array_equal(ref, out_d[i])
        np.testing.assert_array_equal(ref, out_p[i])
    assert paged.last_serve_stats["paged"].get("prefix_caching") is False


def test_ring_buffer_matches_full_cache():
    """Windowed ring cache must reproduce full-cache attention exactly when
    the window covers the whole history."""
    import dataclasses

    base = configs.get_reduced("gemma3-12b")  # has local window=8 layers
    model = base.model
    params = lm.init_params(jax.random.PRNGKey(1), model)
    B, T = 1, 8  # history <= window: ring == full
    rs = np.random.RandomState(1)
    toks = jnp.asarray(rs.randint(0, model.vocab, (B, T)), jnp.int32)
    logits_full, _ = lm.forward(params, model, {"tokens": toks}, jnp.float32)
    caches = lm.init_caches(model, B, T, dtype=jnp.float32)
    outs = []
    for t in range(T):
        lg, caches = lm.decode_step(
            params, model, toks[:, t : t + 1], caches,
            jnp.asarray(t, jnp.int32), jnp.float32,
        )
        outs.append(lg)
    dec = jnp.stack(outs, 1)
    np.testing.assert_allclose(
        np.asarray(dec), np.asarray(logits_full), rtol=3e-4, atol=3e-4
    )
