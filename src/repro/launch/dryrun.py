import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (brief: deliverable (e)).

Lowers + compiles every (architecture x input-shape x mesh) cell against the
production mesh with 512 placeholder host devices, and records:

* ``memory_analysis`` (per-device argument/output/temp/peak bytes — proves
  the cell fits a 16 GB v5e chip),
* ``cost_analysis`` (per-device HLO FLOPs + bytes accessed — §Roofline),
* collective bytes parsed from the optimized HLO (all-gather / all-reduce /
  reduce-scatter / all-to-all / collective-permute), per op class.

Artifacts land in ``artifacts/dryrun/<arch>__<shape>__<mesh>.json``; the
roofline table (benchmarks/roofline.py) and EXPERIMENTS.md §Dry-run read
them. Usage:

    python -m repro.launch.dryrun --arch qwen1.5-0.5b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all [--jobs 3]     # orchestrates subprocesses
"""

import argparse
import json
import re
import subprocess
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.configs import common as C
from repro.dist import sharding as SH
from repro.launch.mesh import make_production_mesh
from repro.models import lm
from repro.optim import adamw
from repro.train import step as train_step_lib

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "artifacts", "dryrun")
ART_DIR = os.path.abspath(os.path.join(os.getcwd(), "artifacts", "dryrun"))

COLLECTIVE_RE = re.compile(
    r"=\s+(?:\([^)]*\)|(?P<ty>[a-z0-9]+)\[(?P<dims>[0-9,]*)\][^ ]*)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
TUPLE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
    "s8": 1, "u8": 1, "s16": 2, "u16": 2, "pred": 1, "s64": 8, "u64": 8,
}


def collective_bytes(hlo_text: str) -> dict:
    """Sum result bytes per collective class from optimized HLO.

    Async pairs appear as op-start/op-done; only `-start` (or the sync form)
    lines carry the `(...)` operand list matched here, so nothing double
    counts. Tuple-shaped results count every element."""
    out: dict[str, float] = {}
    for line in hlo_text.splitlines():
        m = re.search(
            r"=\s+(.+?)\s+(all-reduce-start|all-gather-start|reduce-scatter|"
            r"all-to-all|collective-permute-start|all-reduce|all-gather|"
            r"collective-permute)\(", line)
        if not m:
            continue
        shape_str, op = m.group(1), m.group(2)
        op = op.replace("-start", "")
        nbytes = 0
        for ty, dims in TUPLE_RE.findall(shape_str):
            if ty not in DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * DTYPE_BYTES[ty]
        out[op] = out.get(op, 0.0) + float(nbytes)
    out["total"] = float(sum(v for k, v in out.items() if k != "total"))
    return out


def input_specs(arch_name: str, shape_name: str, mesh) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell
    (brief: MULTI-POD DRY-RUN step 2) — weak-type-correct, shardable, no
    device allocation."""
    arch = configs.get_config(arch_name)
    model = arch.model
    cell = C.SHAPES[shape_name]
    B, S = cell.global_batch, cell.seq_len
    bspec = SH.batch_spec(mesh, B)
    bsh = NamedSharding(mesh, bspec)

    def tok(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=NamedSharding(
            mesh, P(*( [bspec[0]] + [None] * (len(shape) - 1) ))))

    if cell.mode in ("train", "prefill"):
        if model.input_kind == "tokens":
            return {"tokens": tok((B, S)), "labels": tok((B, S))}
        if model.input_kind == "embeddings":
            emb = jax.ShapeDtypeStruct(
                (B, S, model.d_model), jnp.bfloat16,
                sharding=NamedSharding(mesh, P(bspec[0], None, None)),
            )
            return {"embeddings": emb, "labels": tok((B, S))}
        # mixed (paligemma): n_prefix patch embeddings + text tokens
        tt = S - model.n_prefix
        emb = jax.ShapeDtypeStruct(
            (B, model.n_prefix, model.d_model), jnp.bfloat16,
            sharding=NamedSharding(mesh, P(bspec[0], None, None)),
        )
        return {"prefix_embeddings": emb, "tokens": tok((B, tt)),
                "labels": tok((B, tt))}
    # decode: one token + positions (caches built separately)
    if model.input_kind == "embeddings":
        tok_in = jax.ShapeDtypeStruct(
            (B, 1, model.d_model), jnp.bfloat16,
            sharding=NamedSharding(mesh, P(bspec[0], None, None)))
    else:
        tok_in = tok((B, 1))
    # synchronized decode: scalar position (collective-free cache writes —
    # EXPERIMENTS.md SecPerf iteration 4); ragged (B,) positions remain
    # supported for continuous batching.
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P()))
    return {"tokens": tok_in, "pos": pos}


# archs whose bf16 KV cache exceeds 16 GB/chip on the single pod: serve with
# the int8 KV-quant cache (see DESIGN.md §4 / EXPERIMENTS.md §Dry-run).
KV_QUANT_DECODE = {"qwen1.5-32b"}


def _accum_steps(global_batch: int, seq: int, mesh) -> int:
    """Grad-accum so one microbatch is <= ~8k tokens per device."""
    dp = 1
    for ax in ("pod", "data"):
        if ax in mesh.shape:
            dp *= mesh.shape[ax]
    per_dev = max(1, global_batch // dp)
    micro = max(1, 8192 // seq)
    return max(1, per_dev // micro)


def apply_variant(arch_name: str, model, variant: str, costmode: bool):
    """Per-arch beyond-baseline optimisation bundles (the SecPerf hillclimb
    variants). 'baseline' = paper-faithful/production default."""
    import dataclasses as _dc

    if variant == "baseline":
        return model
    if arch_name == "paligemma-3b":
        # hillclimb: sequence-parallel attention (MQA kv=1 cannot head-shard)
        bspec = ("pod", "data") if "pod" in [a for a in ("pod",)] else ("data",)
        bspec = ("data",)  # single-pod hillclimb cell
        def fix(b):
            if b.attn is not None:
                return _dc.replace(b, attn=_dc.replace(
                    b.attn, sp_spec=(bspec, "model", None, None)))
            return b
        return _dc.replace(model, unit=tuple(fix(b) for b in model.unit))
    if arch_name == "xlstm-1.3b":
        # hillclimb: chunked-parallel mLSTM (tests prove exact equivalence)
        def fix(b):
            if b.xlstm is not None:
                return _dc.replace(b, xlstm=_dc.replace(
                    b.xlstm, mlstm_impl="chunked", chunk=256,
                    scan_unroll=costmode))
            return b
        return _dc.replace(model, unit=tuple(fix(b) for b in model.unit))
    return model


def run_cell(
    arch_name: str, shape_name: str, mesh_kind: str, costmode: bool = False,
    variant: str = "baseline",
) -> dict:
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    arch = configs.get_config(arch_name)
    cell = C.SHAPES[shape_name]
    if cell.mode == "decode" and arch_name in KV_QUANT_DECODE:
        arch = C.enable_kv_quant(arch)
    model = apply_variant(arch_name, arch.model, variant, costmode)
    if costmode:
        return run_cell_cost(arch_name, model, cell, mesh, mesh_kind)
    result = {
        "arch": arch_name, "shape": shape_name, "mesh": mesh_kind,
        "mode": cell.mode, "seq_len": cell.seq_len,
        "global_batch": cell.global_batch,
        "n_devices": int(mesh.devices.size),
    }

    axes = lm.param_axes(model)
    # bf16 compute params everywhere; training keeps an fp32 ZeRO-sharded
    # master copy in the optimizer state (SecPerf iteration 2)
    pdtype = jnp.bfloat16
    abs_params = lm.abstract_params(model, dtype=pdtype)
    pshard = SH.tree_shardings(axes, abs_params, mesh)
    params_in = SH.with_sharded_leaves(abs_params, pshard)
    import math
    n_params = sum(math.prod(l.shape) for l in jax.tree.leaves(abs_params))
    result["n_params"] = n_params

    inputs = input_specs(arch_name, shape_name, mesh)

    with mesh:
        if cell.mode == "train":
            accum = _accum_steps(cell.global_batch, cell.seq_len, mesh)
            result["accum_steps"] = accum
            opt_cfg = adamw.AdamWConfig(master_weights=True)
            tstep = train_step_lib.make_train_step(
                model, opt_cfg, compute_dtype=jnp.bfloat16, accum_steps=accum
            )
            abs_opt = jax.eval_shape(
                lambda p: adamw.init_state(p, master_weights=True), abs_params
            )
            opt_m_sh = SH.tree_zero_shardings(axes, abs_params, mesh)
            opt_shard = {
                "m": opt_m_sh, "v": opt_m_sh, "master": opt_m_sh,
                "step": NamedSharding(mesh, P()),
            }
            opt_in = SH.with_sharded_leaves(abs_opt, opt_shard)
            lowered = jax.jit(
                tstep,
                out_shardings=(pshard, opt_shard, None),
                donate_argnums=(0, 1),
            ).lower(params_in, opt_in, inputs)
        elif cell.mode == "prefill":
            cax = lm.cache_axes(model)
            abs_caches = lm.abstract_caches(
                model, cell.global_batch, cell.seq_len, jnp.bfloat16
            )
            cache_shard = SH.tree_shardings(cax, abs_caches, mesh)

            def prefill_fn(p, inp):
                return lm.prefill(p, model, inp, cell.seq_len, jnp.bfloat16)

            lowered = jax.jit(
                prefill_fn, out_shardings=(None, cache_shard)
            ).lower(params_in, inputs)
        else:  # decode
            cax = lm.cache_axes(model)
            abs_caches = lm.abstract_caches(
                model, cell.global_batch, cell.seq_len, jnp.bfloat16
            )
            cache_shard = SH.tree_shardings(cax, abs_caches, mesh)
            caches_in = SH.with_sharded_leaves(abs_caches, cache_shard)

            def serve_step(p, tok, caches, pos):
                return lm.decode_step(p, model, tok, caches, pos, jnp.bfloat16)

            lowered = jax.jit(
                serve_step, out_shardings=(None, cache_shard),
                donate_argnums=(2,),   # caches update in place
            ).lower(params_in, inputs["tokens"], caches_in, inputs["pos"])

        result["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        result["compile_s"] = round(time.time() - t1, 2)

    ma = compiled.memory_analysis()
    result["memory"] = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "peak_bytes": int(ma.peak_memory_in_bytes),
    }
    ca = compiled.cost_analysis()
    result["cost"] = {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
    }
    result["collectives"] = collective_bytes(compiled.as_text())
    result["total_s"] = round(time.time() - t0, 2)
    return result



def _lower_costfaithful(model, cell, mesh, arch_name, n_rep):
    """Lower one cost-faithful variant: python-looped unit (n_rep repeats),
    no inner attention chunking (FLOP-equivalent), accum=1, remat as prod."""
    import dataclasses as _dc

    mvar = _dc.replace(
        model, scan_layers=False, n_repeats=n_rep,
        attn_chunk=max(cell.seq_len, 1),
    )
    axes = lm.param_axes(mvar)
    pdtype = jnp.bfloat16
    abs_params = lm.abstract_params(mvar, dtype=pdtype)
    pshard = SH.tree_shardings(axes, abs_params, mesh)
    params_in = SH.with_sharded_leaves(abs_params, pshard)
    inputs = input_specs(arch_name, cell.name, mesh)
    with mesh:
        if cell.mode == "train":
            opt_cfg = adamw.AdamWConfig(master_weights=True)
            tstep = train_step_lib.make_train_step(
                mvar, opt_cfg, compute_dtype=jnp.bfloat16, accum_steps=1
            )
            abs_opt = jax.eval_shape(
                lambda p: adamw.init_state(p, master_weights=True), abs_params
            )
            opt_m_sh = SH.tree_zero_shardings(axes, abs_params, mesh)
            opt_shard = {"m": opt_m_sh, "v": opt_m_sh, "master": opt_m_sh,
                         "step": NamedSharding(mesh, P())}
            opt_in = SH.with_sharded_leaves(abs_opt, opt_shard)
            lowered = jax.jit(
                tstep, out_shardings=(pshard, opt_shard, None)
            ).lower(params_in, opt_in, inputs)
        elif cell.mode == "prefill":
            cax = lm.cache_axes(mvar)
            abs_caches = lm.abstract_caches(
                mvar, cell.global_batch, cell.seq_len, jnp.bfloat16)
            cache_shard = SH.tree_shardings(cax, abs_caches, mesh)
            lowered = jax.jit(
                lambda p, inp: lm.prefill(p, mvar, inp, cell.seq_len, jnp.bfloat16),
                out_shardings=(None, cache_shard),
            ).lower(params_in, inputs)
        else:
            cax = lm.cache_axes(mvar)
            abs_caches = lm.abstract_caches(
                mvar, cell.global_batch, cell.seq_len, jnp.bfloat16)
            cache_shard = SH.tree_shardings(cax, abs_caches, mesh)
            caches_in = SH.with_sharded_leaves(abs_caches, cache_shard)
            lowered = jax.jit(
                lambda p, tok, cc, pos: lm.decode_step(
                    p, mvar, tok, cc, pos, jnp.bfloat16),
                out_shardings=(None, cache_shard),
            ).lower(params_in, inputs["tokens"], caches_in, inputs["pos"])
        compiled = lowered.compile()
    ca = compiled.cost_analysis()
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "collectives": collective_bytes(compiled.as_text()),
    }


def run_cell_cost(arch_name, model, cell, mesh, mesh_kind) -> dict:
    """Cost-faithful per-device costs (EXPERIMENTS.md SecRoofline).

    XLA cost_analysis counts while-loop bodies once; here all loops are
    eliminated (python-looped unit at R'=1,2 with linear extrapolation to
    the true depth; attention unchunked — FLOP/byte-equivalent; the SSD
    inner scan carries only O(state) ops) except the xLSTM time recurrence,
    which gets an analytic adder (models/costs.py). Gradient accumulation is
    folded analytically (x accum of the accum=1 step)."""
    from repro.models import costs as costs_lib

    t0 = time.time()
    f1 = _lower_costfaithful(model, cell, mesh, arch_name, 1)
    f2 = _lower_costfaithful(model, cell, mesh, arch_name, 2)
    R = model.n_repeats
    # NOTE: the cost graph uses accum=1, which already covers the FULL
    # per-device batch in one microbatch — token-identical to the production
    # accum>1 graph, so no scaling is applied (validated: fwd flops match
    # the analytic 2ND+attention within 2%).

    def extrap(a, b):
        return a + (R - 1) * (b - a)

    out = {
        "arch": arch_name, "shape": cell.name, "mesh": mesh_kind,
        "mode": cell.mode,
        "flops": extrap(f1["flops"], f2["flops"]),
        "bytes_accessed": extrap(f1["bytes_accessed"], f2["bytes_accessed"]),
        "collectives": {},
    }
    keys = set(f1["collectives"]) | set(f2["collectives"])
    for k in keys:
        out["collectives"][k] = extrap(
            f1["collectives"].get(k, 0.0), f2["collectives"].get(k, 0.0)
        )
    # per-device batch/tokens for the adder (costs are per-device programs)
    dp = 1
    for ax in ("pod", "data"):
        if ax in mesh.shape:
            dp *= mesh.shape[ax]
    per_dev_batch = max(1, cell.global_batch // dp)
    T = cell.seq_len if cell.mode != "decode" else 1
    adders = costs_lib.recurrent_adders(model, per_dev_batch, T, cell.mode)
    out["recurrent_adder"] = adders
    out["flops"] += adders["flops"]
    out["bytes_accessed"] += adders["bytes"]
    # reference quantities for the useful-compute ratio
    global_tokens = cell.global_batch * (cell.seq_len if cell.mode != "decode" else 1)
    out["model_flops_global"] = costs_lib.model_flops(
        model, global_tokens, cell.mode)
    out["n_active_params"] = costs_lib.n_active_params(model)
    out["total_s"] = round(time.time() - t0, 2)
    return out


def cells_for(arch_name: str) -> list[str]:
    return configs.get_config(arch_name).cells()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--costmode", action="store_true")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--jobs", type=int, default=3)
    ap.add_argument("--include-kanformer", action="store_true", default=True)
    args = ap.parse_args()
    os.makedirs(ART_DIR, exist_ok=True)

    if args.all:
        # The parent only lists cells and spawns children: it builds no
        # mesh and touches no device, so each child owns its own backend.
        jobs = []
        archs = list(configs.ASSIGNED) + (
            ["kanformer-100m"] if args.include_kanformer else []
        )
        for arch in archs:
            for shape in cells_for(arch):
                for mesh in ("single", "multi"):
                    out = os.path.join(
                        ART_DIR, f"{arch}__{shape}__{mesh}.json".replace("/", "_")
                    )
                    if not os.path.exists(out):
                        jobs.append((arch, shape, mesh, out, False))
                    # cost-faithful companion (single-pod only: SecRoofline)
                    outc = os.path.join(
                        ART_DIR, f"{arch}__{shape}__{mesh}__cost.json".replace("/", "_")
                    )
                    if mesh == "single" and not os.path.exists(outc):
                        jobs.append((arch, shape, mesh, outc, True))
        print(f"{len(jobs)} cells to run, {args.jobs} workers")
        running: list[tuple[subprocess.Popen, tuple]] = []
        failed = []
        while jobs or running:
            while jobs and len(running) < args.jobs:
                arch, shape, mesh, out, cost = jobs.pop(0)
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--mesh", mesh] + (
                       ["--costmode"] if cost else [])
                p = subprocess.Popen(
                    cmd, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE
                )
                running.append((p, (arch, shape, mesh, out)))
                print(f"[start] {arch} {shape} {mesh}")
            time.sleep(2)
            still = []
            for p, meta in running:
                if p.poll() is None:
                    still.append((p, meta))
                else:
                    ok = p.returncode == 0 and os.path.exists(meta[3])
                    print(f"[{'done' if ok else 'FAIL'}] {meta[0]} {meta[1]} {meta[2]}")
                    if not ok:
                        err = p.stderr.read().decode()[-2000:]
                        failed.append((meta, err))
                        print(err[-800:])
            running = still
        print(f"finished; {len(failed)} failures")
        for meta, err in failed:
            print("FAILED:", meta[:3])
        sys.exit(1 if failed else 0)

    # single-cell mode
    assert args.arch and args.shape
    try:
        result = run_cell(args.arch, args.shape, args.mesh,
                          costmode=args.costmode, variant=args.variant)
    except Exception:
        traceback.print_exc()
        sys.exit(1)
    suffix = ("__cost" if args.costmode else "") + (
        f"__{args.variant}" if args.variant != "baseline" else "")
    out = os.path.join(
        ART_DIR,
        f"{args.arch}__{args.shape}__{args.mesh}{suffix}.json".replace("/", "_"),
    )
    with open(out, "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps({k: v for k, v in result.items() if k != "collectives"}))
    print("wrote", out)


if __name__ == "__main__":
    main()
