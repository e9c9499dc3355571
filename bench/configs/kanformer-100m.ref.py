"""Plain float32 reference of kanformer-100m (``kanformer-100m.json``).

A pre-norm decoder (``harness.refops.decoder_logits``) whose FFN is two
B-spline KAN layers, ``d -> kan_intermediate_size -> d``: the input is
squashed by tanh into the grid's domain, and each layer computes

    y[n] = sum_{k, m} B_m(x[k]) C[k, m, n] + sum_k relu(x[k]) W[k, n]

(the paper's Eq. 1 with a ReLU base term), with tanh between the two.
"""

import jax
import jax.numpy as jnp

from harness import refops as R


def _kan(x, c, w, cfg, lowp):
    lo, hi = cfg["kan_grid_range"]
    b = R.bspline_basis(x, lo, hi, cfg["kan_grid_size"], cfg["kan_spline_order"])
    return (R.mm("tkm,kmn->tn", b, c, lowp)
            + R.mm("tk,kn->tn", jax.nn.relu(x), w, lowp))


def ffn(h, lw, cfg, lowp=False):
    u = jnp.tanh(_kan(jnp.tanh(h), lw["c1"], lw["b1"], cfg, lowp))
    return _kan(u, lw["c2"], lw["b2"], cfg, lowp)


def init_weights(key, cfg):
    d, L, V = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    H, KV, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    ff = cfg["kan_intermediate_size"]
    M = cfg["kan_grid_size"] + cfg["kan_spline_order"]
    g = cfg["init"]["kan_gain"]
    k0, k1 = jax.random.split(key)
    layers = R.normal(k1, {
        "wq": ((L, d, H, hd), d ** -0.5), "wk": ((L, d, KV, hd), d ** -0.5),
        "wv": ((L, d, KV, hd), d ** -0.5), "wo": ((L, H, hd, d), (H * hd) ** -0.5),
        "c1": ((L, d, M, ff), g * (2 / d) ** 0.5), "b1": ((L, d, ff), g * d ** -0.5),
        "c2": ((L, ff, M, d), g * (2 / ff) ** 0.5), "b2": ((L, ff, d), g * ff ** -0.5),
    })
    layers["attn_norm"] = jnp.ones((L, d), jnp.float32)
    layers["ffn_norm"] = jnp.ones((L, d), jnp.float32)
    return {
        "embed": cfg["init"]["embed_std"] * jax.random.normal(k0, (V, d), jnp.float32),
        "final_norm": jnp.full((d,), cfg["init"]["final_norm"], jnp.float32),
        "layers": layers,
    }


def logits(w, cfg, tokens, rows, lowp=False):
    return R.decoder_logits(w, cfg, tokens, rows,
                            lambda h, lw, lowp: ffn(h, lw, cfg, lowp), lowp)


def ffn_flops(cfg):
    """Per token: both KAN layers, ``M = G + P`` basis planes and the base
    term each, ``2·K·(M + 1)·N``."""
    d, ff = cfg["hidden_size"], cfg["kan_intermediate_size"]
    m1 = cfg["kan_grid_size"] + cfg["kan_spline_order"] + 1
    return 2 * d * m1 * ff + 2 * ff * m1 * d
