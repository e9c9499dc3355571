"""Plain float32 reference of Qwen1.5-0.5B (``qwen1.5-0.5b.json``).

The Qwen2 architecture: a pre-norm decoder (``harness.refops.
decoder_logits``) with biases on the query, key and value projections,
rotary embeddings at ``rope_theta``, a SwiGLU FFN
``down(silu(gate(h)) * up(h))``, RMSNorm, and the token embedding tied to
the output head.  The embedding is not scaled (``embedding_multiplier``
1.0).
"""

import jax
import jax.numpy as jnp

from harness import refops as R


def ffn(h, lw, lowp=False):
    g = R.mm("td,df->tf", h, lw["w_gate"], lowp)
    u = R.mm("td,df->tf", h, lw["w_up"], lowp)
    return R.mm("tf,fd->td", jax.nn.silu(g) * u, lw["w_down"], lowp)


def init_weights(key, cfg):
    d, L, V = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    H, KV, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    ff, bstd = cfg["intermediate_size"], cfg["init"]["qkv_bias_std"]
    k0, k1 = jax.random.split(key)
    layers = R.normal(k1, {
        "wq": ((L, d, H, hd), d ** -0.5), "wk": ((L, d, KV, hd), d ** -0.5),
        "wv": ((L, d, KV, hd), d ** -0.5), "wo": ((L, H, hd, d), (H * hd) ** -0.5),
        "bq": ((L, H, hd), bstd), "bk": ((L, KV, hd), bstd), "bv": ((L, KV, hd), bstd),
        "w_gate": ((L, d, ff), d ** -0.5), "w_up": ((L, d, ff), d ** -0.5),
        "w_down": ((L, ff, d), ff ** -0.5),
    })
    layers["attn_norm"] = jnp.ones((L, d), jnp.float32)
    layers["ffn_norm"] = jnp.ones((L, d), jnp.float32)
    return {
        "embed": cfg["init"]["embed_std"] * jax.random.normal(k0, (V, d), jnp.float32),
        "final_norm": jnp.full((d,), cfg["init"]["final_norm"], jnp.float32),
        "layers": layers,
    }


def logits(w, cfg, tokens, rows, lowp=False):
    return R.decoder_logits(w, cfg, tokens, rows, ffn, lowp)


def layer_flops(cfg):
    """Operations of all layers, ``(per position, per attended context
    position)``: attention (``refops.gqa_flops``) and the FFN's gate, up
    and down projections."""
    pos, ctx = R.gqa_flops(cfg)
    L = cfg["num_hidden_layers"]
    return L * (pos + 3 * 2 * cfg["hidden_size"] * cfg["intermediate_size"]), L * ctx
