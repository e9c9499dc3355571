#!/usr/bin/env python3
"""Smoke run of the serving path on a TPU.

    python3 chip_smoke.py [--seed N]          # one chip
    python3 chip_smoke.py --four-chips        # the 2x2 mesh phase only

Serves 8 requests through the code path of ``python -m repro.launch.serve
--arch kanformer-100m --full --engine continuous --paged``: kanformer-100m
at its published widths (d=512, 8 layers, KAN FFN 512->1024->512 with G=5,
P=3, vocab 32000), weights from ``--seed``, 4 slots, bf16 activations and
KV cache, prompts of 73-256 tokens behind one shared 64-token prefix, 32
greedy tokens each.  Prefill runs the fused KAN kernel at 4x256 rows,
decode at 4 rows, and the second admission wave hits the prefix cache.
Then it checks:

* the tokens against a plain float32 reference (``lm.forward``, dense KAN
  path, fp32 matmuls), teacher-forced on the engine's own prompts and
  outputs: wherever the reference's top-2 logit margin exceeds the bf16
  tolerance of :func:`logit_tolerance`, the engine's token is the
  reference's argmax;
* that the compiled prefill, paged-gather and decode programs contain
  ``tpu_custom_call``, i.e. the Pallas kernels ran;
* that serving the same requests again compiles nothing new and returns
  the same tokens;
* the KAN FFN as the model serves it, with every layer's weights at the
  served row counts (the first prefill wave and the decode batch), against
  float32: each fused-kernel layer output within the bf16 bound of
  :func:`kan_layer_reference`, and controls that must break that bound (a
  shifted basis plane in either direction, a zeroed FFN);
* the paged gather of random pools at the served pool shape, equal to a
  plain ``jnp.take`` of the same blocks (and unequal for a rotated table).

With random weights the residual stream is dominated by the tied
embedding (scaled by sqrt(d)), so the greedy tokens mostly repeat the last
prompt token and the token check alone cannot see the FFN; the FFN and
gather checks carry the numerics.

``--four-chips`` runs only the mesh phase: the same requests on a 2x2
(data, model) mesh and on a 1x1 engine in one process, each held to the
token check, their tokens compared up to the first position the tolerance
cannot decide, and the FFN and gather checks on the mesh, with one more
control: the FFN with its psum dropped.

Without a TPU it exits non-zero before any phase.  On success the last
line of stdout is ``{"ok": true, "device": {...}}``; a failed check exits
non-zero without it.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from unittest import mock

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import bspline  # noqa: E402
from repro.core import kan_layer as KL  # noqa: E402
from repro.dist.sharding import shard_tree  # noqa: E402
from repro.launch import runtime  # noqa: E402
from repro.launch import serve as serve_cli  # noqa: E402
from repro.models import blocks, lm  # noqa: E402

ARCH = "kanformer-100m"
SLOTS = 4
BLOCK_SIZE = 16


@dataclasses.dataclass(frozen=True)
class Workload:
    full: bool            # published widths (else the reduced config)
    prompt_lens: tuple
    prefix_len: int       # tokens every prompt shares
    max_new: int


# Wave one (the first 4 slots) pads to one 256-token prefill group; wave
# two hits the 4 cached prefix blocks and pads to one 16-token suffix group.
SMOKE = Workload(full=True, prompt_lens=(256, 251, 253, 249, 73, 75, 77, 80),
                 prefix_len=64, max_new=32)
# bfloat16 keeps 8 significant bits: unit roundoff 2^-8; float32 2^-24.
BF16_UNIT_ROUNDOFF = 2.0 ** -8
FP32_UNIT_ROUNDOFF = 2.0 ** -24
# Hoeffding: independent mean-zero errors |e_i| <= c_i sum to more than
# λ·sqrt(Σ c_i²) with probability at most 2·exp(-λ²/2), 2.5e-14 at λ = 8.
HOEFFDING_LAMBDA = 8.0
HIGHEST = jax.lax.Precision.HIGHEST
KERNEL_PROGRAMS = ("prefill_pages", "gather_views", "decode_chunk")


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def serve_args(wl: Workload, mesh: str | None, seed: int) -> argparse.Namespace:
    """The launcher's flags for this workload."""
    argv = ["--arch", ARCH, "--engine", "continuous", "--paged",
            "--batch", str(SLOTS), "--block-size", str(BLOCK_SIZE),
            "--prompt-len", str(max(wl.prompt_lens)),
            "--max-new", str(wl.max_new), "--seed", str(seed)]
    if wl.full:
        argv.append("--full")
    if mesh is not None:
        argv += ["--mesh", mesh]
    return serve_cli.build_parser().parse_args(argv)


def setup(wl: Workload, mesh: str | None, seed: int):
    """Launcher flags, model config and the workload's prompts."""
    args = serve_args(wl, mesh, seed)
    model = serve_cli.pick_config(args.arch, args.full).model
    prompts = make_prompts(model.vocab, wl.prompt_lens, wl.prefix_len, seed)
    return args, model, prompts


def make_prompts(vocab: int, lens, prefix_len: int, seed: int) -> list[np.ndarray]:
    """Prompts of the given lengths that share one ``prefix_len`` prefix."""
    rs = np.random.RandomState(seed)
    prefix = rs.randint(0, vocab, prefix_len)
    return [
        np.concatenate([prefix, rs.randint(0, vocab, n - prefix_len)]).astype(np.int32)
        for n in lens
    ]


def logit_tolerance(ref_logits: np.ndarray, n_layers: int) -> np.ndarray:
    """Per-position bound on ``|bf16 engine logit - fp32 logit|``.

    Each layer rounds the residual stream to bf16 about four times
    (attention output, KAN hidden, KAN output, residual add), plus the
    embedding and the final norm: ``R = 4·n_layers + 2`` roundings, each a
    relative error of at most ``u = 2^-8``.  Taken as independent, they
    grow like ``sqrt(R)·u``; logit errors then scale with the spread of the
    logits (their standard deviation over the vocabulary).  A factor 4 of
    headroom covers the bf16 KV cache and accumulation order.
    """
    rel = 4.0 * np.sqrt(4 * n_layers + 2) * BF16_UNIT_ROUNDOFF
    return rel * ref_logits.std(axis=-1)


def reference_logits(params, model, prompts, outputs) -> np.ndarray:
    """fp32 logits that predict each output token, teacher-forced on the
    prompt and the engine's earlier outputs: ``(n, max_new, vocab)``."""
    n = len(prompts)
    T = max(len(p) + len(o) for p, o in zip(prompts, outputs))
    seqs = np.zeros((n, T), np.int32)
    idx = np.zeros((n, len(outputs[0])), np.int32)
    for i, (p, o) in enumerate(zip(prompts, outputs)):
        seqs[i, : len(p) + len(o)] = np.concatenate([p, o])
        idx[i] = len(p) - 1 + np.arange(len(o))

    def fwd(params, seqs, idx):
        logits, _ = lm.forward(params, model, {"tokens": seqs}, jnp.float32)
        return logits[jnp.arange(n)[:, None], idx]

    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(fwd)(params, seqs, idx))


def reference_check(params, model, prompts, outputs) -> dict:
    """Engine tokens vs the fp32 reference at every decidable position."""
    logits = reference_logits(params, model, prompts, outputs)
    out = np.stack(outputs)
    top2 = np.sort(np.partition(logits, -2, axis=-1)[..., -2:], axis=-1)
    margin = top2[..., 1] - top2[..., 0]
    tol = logit_tolerance(logits, model.n_repeats * len(model.unit))
    decided = margin > tol
    ref_tok = logits.argmax(-1)
    return {
        "positions": int(out.size),
        "decided": int(decided.sum()),
        "mismatches_decided": int((decided & (out != ref_tok)).sum()),
        "agreement_all": float((out == ref_tok).mean()),
        "tol_mean": float(tol.mean()),
        "finite": bool(np.isfinite(logits).all()),
        "decided_mask": decided,
    }


def assert_reference(rep: dict, label: str) -> None:
    print(f"[smoke] reference {label}: tolerance {rep['tol_mean']:.4f} "
          f"(mean over positions), {rep['decided']}/{rep['positions']} "
          f"positions decided, {rep['mismatches_decided']} mismatches there; "
          f"agreement at all positions {rep['agreement_all']:.4f}")
    check(rep["finite"], f"{label}: reference logits not finite")
    check(rep["decided"] * 4 >= rep["positions"],
          f"{label}: under a quarter of the positions decidable")
    check(rep["mismatches_decided"] == 0,
          f"{label}: {rep['mismatches_decided']} tokens differ from the "
          f"reference beyond the bf16 tolerance")


def kan_ffn_layers(params, model):
    """Every layer's KAN FFN parameters ``{c1, b1, c2, b2}`` and the grid."""
    (blk,) = model.unit
    stacked = params["unit"][0]["kan"]
    layers = [jax.tree.map(lambda a, i=i: a[i], stacked)
              for i in range(model.n_repeats)]
    return layers, blk.kan_grid


def ffn_rows(wl: Workload) -> tuple[int, int]:
    """The FFN's row counts in the first prefill wave and in decode."""
    return SLOTS * max(wl.prompt_lens), SLOTS


def kan_layer_reference(coeff, base_w, x, grid, parts: int = 1):
    """float32 output of one KAN layer on the bf16 input ``x``, and the
    elementwise bound on how far the served kernel's output may lie from it.

    The kernel sees the same bf16 ``x``, builds the basis in float32 and
    contracts on the MXU, which may round each float32 operand to bf16: at
    most ``γ = 2u + u²`` relative error per product, ``u = 2^-8``.  Taken
    as independent and mean-zero, these errors sum to at most
    ``λ·γ·sqrt(Σ term²)`` (Hoeffding, ``λ = 8``).  Rounding the output to
    bf16 adds ``u·|y|``; float32 accumulation, in the kernel and here, at
    most ``2·n·2^-24·Σ|term|`` over the ``n = K·(M+1)`` terms.  With
    ``parts > 1`` the layer is summed as that many partial sums over
    contiguous slices of K, each rounded to bf16 before they are added (the
    psum of the FFN's split on a mesh): ``u·(Σ|partial| + |y|)`` more.
    """
    xf = x.astype(jnp.float32)
    B = bspline.cox_de_boor_dense(xf, grid)                   # (rows, K, M)
    r = jax.nn.relu(xf)

    def contract(B, r, C, W, ks=slice(None)):
        return (jnp.einsum("rkm,kmn->rn", B[:, ks], C[ks], precision=HIGHEST)
                + jnp.matmul(r[:, ks], W[ks], precision=HIGHEST))

    y = contract(B, r, coeff, base_w)
    sq = contract(B * B, r * r, coeff * coeff, base_w * base_w)
    ab = contract(B, r, jnp.abs(coeff), jnp.abs(base_w))
    K, M = coeff.shape[:2]
    u = BF16_UNIT_ROUNDOFF
    bound = (HOEFFDING_LAMBDA * (2 * u + u * u) * jnp.sqrt(sq) + u * jnp.abs(y)
             + 2 * K * (M + 1) * FP32_UNIT_ROUNDOFF * ab)
    if parts > 1:
        w = K // parts
        split = sum(jnp.abs(contract(B, r, coeff, base_w, slice(i * w, (i + 1) * w)))
                    for i in range(parts))
        bound = bound + u * (split + jnp.abs(y))
    return y, (1 + u) * bound


def bound_ratio(y, ref) -> float:
    """Largest ``|y - y_ref| / bound`` over the elements: at most 1 passes."""
    y, y_ref, bound = (np.asarray(a, np.float32) for a in jax.device_get((y, *ref)))
    err = np.abs(y - y_ref)
    return float(np.max(err / np.maximum(bound, np.finfo(np.float32).tiny)))


def _model_parts(shard, ff: int) -> int:
    """How many devices the FFN's hidden axis is split over."""
    if shard is None:
        return 1
    axes = shard.spec(("ffn",), (ff,))[0]
    axes = () if axes is None else (axes,) if isinstance(axes, str) else axes
    return int(np.prod([shard.mesh.shape[a] for a in axes]))


def ffn_check(model, params, rows_list, seed: int, shard=None,
              shard_params=None) -> dict:
    """The KAN FFN as the model serves it (``blocks._kan_ffn``, bf16,
    ``method="auto"``) for every layer's weights at each row count, against
    :func:`kan_layer_reference`, teacher-forced per layer: the up layer on
    ``tanh(x)``, the down layer on ``tanh`` of the served up layer's output.

    With ``shard`` the FFN runs on that mesh with ``shard_params`` (the
    same weights, placed by the engine), and the up layer and the
    reference on one device with ``params``.  Returns the worst ratio per
    direction and, per control, the smallest ratio over the row counts; a
    control has to exceed 1 at each of them.
    """
    layers, grid = kan_ffn_layers(params, model)
    served = layers if shard is None else kan_ffn_layers(shard_params, model)[0]
    parts = _model_parts(shard, layers[0]["c1"].shape[-1])
    up = jax.jit(lambda p, x: KL.kan_layer_apply(
        {"coeff": p["c1"], "base_w": p["b1"]}, x, grid, "auto"))
    ref = jax.jit(kan_layer_reference, static_argnames=("grid", "parts"))

    def served_ffn():   # the psum control traces its own copy
        return jax.jit(lambda p, x: blocks._kan_ffn(p, x, grid, "auto", shard))

    ffn = served_ffn()

    def roll(p, name):
        return {**p, name: jnp.roll(p[name], 1, axis=1)}

    rep = {"up": 0.0, "down": 0.0, "controls": {}}

    def control(name, ratio):
        rep["controls"][name] = min(rep["controls"].get(name, np.inf), ratio)

    key = jax.random.PRNGKey(seed)
    for rows in rows_list:
        x = jax.random.normal(jax.random.fold_in(key, rows),
                              (rows, model.d_model)).astype(jnp.bfloat16)
        x1 = jnp.tanh(x)      # the FFN squashes its input into the grid
        for i, (p, ps) in enumerate(zip(layers, served)):
            h = up(p, x1)
            ref_up = ref(p["c1"], p["b1"], x1, grid=grid)
            ref_down = ref(p["c2"], p["b2"], jnp.tanh(h), grid=grid, parts=parts)
            rep["up"] = max(rep["up"], bound_ratio(h, ref_up))
            rep["down"] = max(rep["down"], bound_ratio(ffn(ps, x), ref_down))
            if i:
                continue
            control("wrong plane up", bound_ratio(up(roll(p, "c1"), x1), ref_up))
            control("wrong plane down", bound_ratio(ffn(roll(ps, "c2"), x), ref_down))
            zero = {**ps, "c2": jnp.zeros_like(ps["c2"]),
                    "b2": jnp.zeros_like(ps["b2"])}
            control("zeroed FFN", bound_ratio(ffn(zero, x), ref_down))
            if parts > 1:
                with mock.patch.object(jax.lax, "psum", lambda y, *a, **k: y):
                    y = served_ffn()(ps, x)
                control("dropped psum", bound_ratio(y, ref_down))
    return rep


def assert_ffn(rep: dict, rows_list, n_layers: int, label: str) -> None:
    ctl = ", ".join(f"{k} {v:.3f}" for k, v in rep["controls"].items())
    print(f"[smoke] KAN FFN {label}: worst |err|/bound over {n_layers} layers "
          f"x rows {tuple(rows_list)}: up {rep['up']:.4f}, down "
          f"{rep['down']:.4f} (pass <= 1); controls (must exceed 1): {ctl}")
    check(rep["up"] <= 1 and rep["down"] <= 1,
          f"{label}: the KAN FFN is outside its bf16 bound")
    bad = [k for k, v in rep["controls"].items() if not v > 1]
    check(not bad, f"{label}: the FFN check cannot tell apart: {bad}")


def gather_check(eng, model, seed: int) -> None:
    """The paged gather (``lm.paged_views``, the engine's gather program)
    of random pools at the served pool shape, on the engine's mesh, equal
    to ``jnp.take`` of the same blocks; a rotated table must differ."""
    paged = eng.last_serve_stats["paged"]
    n_blocks, bs = paged["pool_blocks"], paged["block_size"]
    n_logical = eng.cfg.max_seq // bs
    dt = jnp.bfloat16
    abstract = jax.eval_shape(lambda: lm.init_paged_caches(model, n_blocks, bs, dt))
    leaves, tree = jax.tree.flatten(abstract)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    pools = jax.tree.unflatten(tree, [
        jax.random.normal(k, a.shape).astype(a.dtype) for k, a in zip(keys, leaves)])
    if eng.shard is not None:
        pools = shard_tree(pools, eng.shard.paged_cache_shardings(model, n_blocks, bs, dt))
    table = np.random.RandomState(seed).randint(
        1, n_blocks, (SLOTS, n_logical)).astype(np.int32)
    views = jax.jit(lambda c, t: lm.paged_views(c, t, eng.shard))(pools, table)

    def expected(tbl):
        def leaf(path, pool):   # unit pools carry a leading layers axis
            a = 1 if path[0].key == "unit" else 0
            g = jnp.take(pool, tbl, axis=a)        # (..., slots, L, bs, ...)
            return g.reshape(g.shape[:a + 1] + (-1,) + g.shape[a + 3:])
        return jax.tree_util.tree_map_with_path(leaf, pools)

    def same(t1, t2):
        return all(a.shape == b.shape and np.asarray(a).tobytes() == np.asarray(b).tobytes()
                   for a, b in zip(jax.tree.leaves(t1), jax.tree.leaves(t2)))

    ok, ctl = same(views, expected(table)), same(views, expected(np.roll(table, 1, 1)))
    shapes = sorted({a.shape for a in leaves})
    print(f"[smoke] paged gather: {len(leaves)} pools {shapes}, table "
          f"{table.shape}: equal to jnp.take {ok}; rotated-table control equal {ctl}")
    check(ok, "the paged gather differs from jnp.take")
    check(not ctl, "the gather check cannot tell a rotated table apart")


def serve_once(eng, args, prompts) -> tuple[list[np.ndarray], float]:
    t0 = time.perf_counter()
    outs = serve_cli.serve(eng, args, prompts)
    return outs, time.perf_counter() - t0


def programs(eng) -> int:
    return sum(v["programs"] for v in eng.compiles.snapshot().values())


def serve_phase(args, model, prompts) -> tuple[object, list[np.ndarray]]:
    """Build the engine, serve cold then warm, check shapes and replay."""
    eng = serve_cli.build_engine(args, model)
    outs, cold_s = serve_once(eng, args, prompts)
    n_prog = programs(eng)
    outs2, warm_s = serve_once(eng, args, prompts)
    stats = eng.last_serve_stats
    paged = stats["paged"]
    n_tok = sum(len(o) for o in outs)
    print(f"[smoke] serve cold: {len(prompts)} requests, {n_tok} tokens, "
          f"{cold_s:.3f} s incl. compiling {n_prog} programs")
    print(f"[smoke] serve warm: {warm_s:.3f} s, new programs after warmup "
          f"{programs(eng) - n_prog}, prefix_hit_blocks "
          f"{paged['prefix_hit_blocks']}, prefill_tokens_saved "
          f"{paged['prefill_tokens_saved']}, mesh {stats['mesh_shape']}")
    check(all(o.shape == (args.max_new,) for o in outs), "output shapes")
    check(all(((o >= 0) & (o < model.vocab)).all() for o in outs),
          "token ids out of range")
    check(programs(eng) == n_prog, "the warm pass compiled new programs")
    check(all((a == b).all() for a, b in zip(outs, outs2)),
          "the warm pass returned different tokens")
    check(paged["prefix_hit_blocks"] > 0, "no prefix-cache hits")
    return eng, outs


def assert_kernels(eng) -> None:
    """The compiled prefill, paged-gather and decode programs hold the
    Pallas kernels."""
    for name in KERNEL_PROGRAMS:
        lows = eng.lowered(name)
        check(bool(lows), f"no {name} program was compiled")
        texts = [low.compile().as_text() for low in lows]
        n = sum("tpu_custom_call" in t for t in texts)
        print(f"[smoke] {name}: {n}/{len(texts)} compiled programs contain "
              f"tpu_custom_call")
        check(n == len(texts), f"{name} compiled without the Pallas kernels")


def one_chip(seed: int, wl: Workload = SMOKE) -> None:
    args, model, prompts = setup(wl, None, seed)
    eng, outs = serve_phase(args, model, prompts)
    assert_kernels(eng)
    assert_reference(reference_check(eng.params, model, prompts, outs), "1 chip")
    rows = ffn_rows(wl)
    assert_ffn(ffn_check(model, eng.params, rows, seed), rows,
               model.n_repeats, "1 chip")
    gather_check(eng, model, seed)


def four_chips(seed: int, wl: Workload = SMOKE) -> None:
    check(len(jax.devices()) == 4, f"--four-chips needs 4 devices, "
                                   f"found {len(jax.devices())}")
    runs = {}
    for mesh in ("1x1", "2x2"):
        args, model, prompts = setup(wl, mesh, seed)
        runs[mesh] = serve_phase(args, model, prompts)
    (eng1, outs1), (eng4, outs4) = runs["1x1"], runs["2x2"]
    reps = {}
    for label, outs in (("1x1", outs1), ("2x2", outs4)):
        reps[label] = reference_check(eng1.params, model, prompts, outs)
        assert_reference(reps[label], label)
    # Up to the first position the tolerance cannot decide, both meshes
    # must emit the same tokens (after it, their contexts may differ).
    agree = 0
    for a, b, dec in zip(outs1, outs4, reps["1x1"]["decided_mask"]):
        k = int(np.argmin(dec)) if not dec.all() else len(dec)
        check((a[:k] == b[:k]).all(), "2x2 and 1x1 tokens differ at a "
                                      "decided position")
        agree += int((a == b).all())
    print(f"[smoke] 2x2 vs 1x1: decided prefixes equal; {agree}/{len(outs1)} "
          f"requests identical end to end")
    rows = ffn_rows(wl)
    assert_ffn(ffn_check(model, eng1.params, rows, seed, shard=eng4.shard,
                         shard_params=eng4.params), rows, model.n_repeats, "2x2")
    gather_check(eng4, model, seed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 mesh phase (needs 4 chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    runtime.enable_compile_cache()
    dev = runtime.require_tpu(allow_explicit_cpu=False)
    print(f"[smoke] device {dev.platform} {dev.device_kind} "
          f"x{len(jax.devices())}, jax {jax.__version__}")
    t0 = time.perf_counter()
    try:
        (four_chips if args.four_chips else one_chip)(args.seed)
    except SmokeFailure as e:
        print(f"[smoke] FAIL: {e}", file=sys.stderr)
        return 1
    print(f"[smoke] all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
