"""Plain float32 building blocks of the references in ``bench/configs``.

Written from the architectures' published equations in straightforward
``jax.numpy``; nothing here comes from the program.  Every contraction
goes through :func:`mm`, which runs at ``Precision.HIGHEST`` (float32 on
the MXU) or, for the control, rounds both operands to float8 (e4m3, one
scale per tensor) first: the precision step below the bfloat16 that the
configurations serve in.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def fp8_round(x: jax.Array) -> jax.Array:
    """``x`` rounded to float8 e4m3 under one scale for the whole tensor
    (its largest magnitude maps to the format's largest value)."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def mm(eq: str, a: jax.Array, b: jax.Array, lowp: bool = False) -> jax.Array:
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if lowp:
        a, b = fp8_round(a), fp8_round(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def rmsnorm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding of ``x`` (T, H, D) at positions 0..T-1, rotating
    the first half of each head against the second (``rotate_half``)."""
    T, _, D = x.shape
    half = D // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv        # (T, half)
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def causal_attention(q, k, v, lowp: bool = False) -> jax.Array:
    """Softmax attention of ``q`` (T, H, D) over ``k``/``v`` (T, KV, D),
    each query seeing keys at its own position and before."""
    T, H, D = q.shape
    rep = H // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = mm("thd,shd->hts", q, k, lowp) / jnp.sqrt(jnp.float32(D))
    mask = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return mm("hts,shd->thd", p, v, lowp)


def bspline_basis(x: jax.Array, x_min: float, x_max: float, G: int,
                  P: int) -> jax.Array:
    """The ``G + P`` B-splines of degree ``P`` on the uniform grid of ``G``
    intervals over ``[x_min, x_max]``, extended by ``P`` intervals on each
    side, at ``x`` clipped to ``[x_min, x_max]``: ``x.shape + (G + P,)``.

    ``B_m(x) = N_P((x - t_m) / delta)`` with ``t_m = x_min + (m - P)
    delta`` and the cardinal B-spline ``N_0 = 1`` on ``[0, 1)``,
    ``N_p(u) = (u N_{p-1}(u) + (p + 1 - u) N_{p-1}(u - 1)) / p``.  The
    spline is continuous for ``P >= 1``, so ``x = x_max`` needs no rule of
    its own."""
    delta = (x_max - x_min) / G
    x = jnp.clip(x, x_min, x_max)
    t = x_min + (jnp.arange(G + P, dtype=jnp.float32) - P) * delta
    u = (x[..., None] - t) / delta                               # (..., M)

    def card(u, p):
        if p == 0:
            return jnp.where((u >= 0) & (u < 1), 1.0, 0.0)
        return (u * card(u, p - 1) + (p + 1 - u) * card(u - 1, p - 1)) / p

    return card(u, P)


def decoder_logits(w: dict, cfg: dict, tokens: jax.Array, rows: jax.Array,
                   ffn, lowp: bool = False) -> jax.Array:
    """Logits ``(len(rows), vocab)`` of a pre-norm decoder at the positions
    ``rows`` of ``tokens`` (T,): the token embedding times
    ``embedding_multiplier``; per layer ``x + attn(rmsnorm(x))`` then
    ``x + ffn(rmsnorm(x))``; a final rmsnorm and the tied embedding as the
    output head.  Layers run one at a time (``lax.scan``) so that only one
    layer's activations are live."""
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    x = w["embed"][tokens].astype(jnp.float32) * float(cfg["embedding_multiplier"])

    def layer(x, lw):
        h = rmsnorm(x, lw["attn_norm"], eps)
        q = mm("td,dhk->thk", h, lw["wq"], lowp)
        k = mm("td,dhk->thk", h, lw["wk"], lowp)
        v = mm("td,dhk->thk", h, lw["wv"], lowp)
        if "bq" in lw:
            q, k, v = q + lw["bq"], k + lw["bk"], v + lw["bv"]
        o = causal_attention(rope(q, theta), rope(k, theta), v, lowp)
        x = x + mm("thk,hkd->td", o, lw["wo"], lowp)
        return x + ffn(rmsnorm(x, lw["ffn_norm"], eps), lw, lowp), None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    h = rmsnorm(x[rows], w["final_norm"], eps)
    return mm("rd,vd->rv", h, w["embed"], lowp)


def normal(key: jax.Array, shapes: dict) -> dict:
    """``{name: std * N(0, 1)}`` for ``shapes = {name: (shape, std)}``,
    one key per name."""
    keys = jax.random.split(key, len(shapes))
    return {n: std * jax.random.normal(k, shape, jnp.float32)
            for k, (n, (shape, std)) in zip(keys, sorted(shapes.items()))}
