"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Batched request serving (prefill + decode with KV caches) on the host mesh;
the production-mesh serve_step is exercised by the dry-run decode cells.

``--engine static`` drains length-sorted fixed buckets
(``Engine.serve_requests``); ``--engine continuous`` runs the slot-recycling
continuous-batching loop (``Engine.serve_continuous``) and reports its slot
utilization.  ``--paged`` (continuous only) switches the KV cache to the
paged block pool with prefix caching and preemption (DESIGN.md §3b);
``--block-size``/``--pool-blocks`` shape the pool.  ``--mesh DxM`` serves
on a (data, model) host mesh (DESIGN.md §4: params/KV sharded, outputs
identical to the single-device engine).  ``--spec-k K`` (continuous only)
turns on speculative decoding: a shrunken-KAN drafter (``--draft-layers``,
optionally ``--draft-quant``) proposes K tokens per window and one fused
verify pass scores them — outputs stay bit-identical to ``--spec-k 0``
(DESIGN.md §9).  Reduced (CPU-runnable) shapes are the default; ``--full``
selects the full production config.

The launcher needs a TPU; it runs on the CPU (Pallas kernels in interpret
mode) only when ``JAX_PLATFORMS=cpu`` asks for it.  Compiled programs are
kept in JAX's persistent cache (``launch/runtime.py``).  ``chip_smoke.py``
drives the same :func:`build_engine` / :func:`serve` path.
"""

from __future__ import annotations

import argparse
import sys
import time

import jax
import numpy as np

from repro import configs
from repro.launch.mesh import make_host_mesh, parse_mesh_shape
from repro.launch.runtime import enable_compile_cache, require_tpu
from repro.models import lm
from repro.serve.engine import Engine, ServeConfig


# Activations and KV cache are served in bf16 (params stay fp32), the dtype
# the TPU kernels are compiled and checked for (tests/test_tpu_compile.py,
# chip_smoke.py).
SERVED_DTYPE = "bfloat16"


def pick_config(arch: str, full: bool):
    """Reduced shapes by default; ``--full`` opts into the production
    config.  (The previous ``--reduced`` flag was ``store_true`` with
    ``default=True`` — impossible to turn off.)"""
    return configs.get_config(arch) if full else configs.get_reduced(arch)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=configs.list_configs())
    ap.add_argument("--full", action="store_true",
                    help="use the full production config (default: reduced)")
    ap.add_argument("--engine", choices=("static", "continuous"),
                    default="static")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4,
                    help="static: bucket size; continuous: slot count")
    ap.add_argument("--chunk-steps", type=int, default=8,
                    help="continuous: decode steps per jitted chunk")
    ap.add_argument("--paged", action="store_true",
                    help="continuous: paged KV cache (block pool + prefix "
                         "caching + preemption; DESIGN.md §3b)")
    ap.add_argument("--block-size", type=int, default=8,
                    help="paged: tokens per KV block (must divide max_seq)")
    ap.add_argument("--pool-blocks", type=int, default=None,
                    help="paged: physical blocks incl. the sentinel "
                         "(default: dense-equivalent capacity)")
    ap.add_argument("--mesh", type=str, default=None, metavar="DxM",
                    help="serve on a (data, model) host mesh, e.g. 2x4 "
                         "(requires that many host devices; force with "
                         "XLA_FLAGS=--xla_force_host_platform_device_count). "
                         "Default: single-device engine")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="continuous: speculative decoding — drafts per "
                         "verify window (0 disables; DESIGN.md §9). Outputs "
                         "stay bit-identical to --spec-k 0")
    ap.add_argument("--draft-layers", type=int, default=1,
                    help="speculative: leading unit repeats kept in the "
                         "derived drafter (1..n_repeats)")
    ap.add_argument("--draft-quant", action="store_true",
                    help="speculative: int8 fake-quantize the drafter "
                         "weights (KANtize-style)")
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--eos-id", type=int, default=-1)
    ap.add_argument("--seed", type=int, default=0)
    return ap


def build_engine(args, model) -> Engine:
    """Params from ``--seed`` and the engine the flags describe.  Raises
    ``ValueError`` for a ``--mesh`` the host cannot hold."""
    params = lm.init_params(jax.random.PRNGKey(args.seed), model)
    max_seq = args.prompt_len + args.max_new + 8
    if args.paged:   # the paged pool addresses whole blocks
        max_seq = -(-max_seq // args.block_size) * args.block_size
    mesh = None
    if args.mesh is not None:
        mesh = make_host_mesh(parse_mesh_shape(args.mesh))
        print(f"[serve] mesh={dict(mesh.shape)} over {mesh.size} "
              f"of {len(jax.devices())} host devices")
    return Engine(
        params, model,
        ServeConfig(max_seq=max_seq,
                    max_new_tokens=args.max_new, temperature=args.temperature,
                    eos_id=args.eos_id, compute_dtype=SERVED_DTYPE,
                    paged=args.paged, block_size=args.block_size,
                    pool_blocks=args.pool_blocks, mesh=mesh,
                    spec_k=args.spec_k, draft_layers=args.draft_layers,
                    draft_quant=args.draft_quant),
    )


def serve(eng: Engine, args, requests: list[np.ndarray]) -> list[np.ndarray]:
    """Run ``requests`` through the engine ``--engine`` names."""
    if args.engine == "continuous":
        return eng.serve_continuous(
            requests, slots=args.batch, chunk_steps=args.chunk_steps,
            seed=args.seed,
        )
    return eng.serve_requests(requests, batch_size=args.batch, seed=args.seed)


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)

    arch = pick_config(args.arch, args.full)
    model = arch.model
    if model.input_kind != "tokens":
        print(f"[serve] {args.arch} is {model.input_kind}-input; serving the "
              f"token path is exercised via mixed/embeddings archs in tests")
    if args.paged and args.engine != "continuous":
        print("[serve] --paged requires --engine continuous", file=sys.stderr)
        return 2
    if args.spec_k < 0:
        print(f"[serve] --spec-k must be >= 0, got {args.spec_k}",
              file=sys.stderr)
        return 2
    if args.spec_k > 0 and args.engine != "continuous":
        print("[serve] --spec-k requires --engine continuous", file=sys.stderr)
        return 2
    if not (1 <= args.draft_layers <= model.n_repeats):
        print(f"[serve] --draft-layers must be in [1, {model.n_repeats}] "
              f"for {args.arch}, got {args.draft_layers}", file=sys.stderr)
        return 2
    if args.spec_k > 0 and not lm.model_supports_speculative(model):
        print(f"[serve] {args.arch} does not support speculative decoding "
              f"(needs token-input full-attention GQA blocks)",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    require_tpu(allow_explicit_cpu=True)
    try:
        eng = build_engine(args, model)
    except ValueError as e:
        print(f"[serve] {e}", file=sys.stderr)
        return 2
    rs = np.random.RandomState(args.seed)
    reqs = [
        rs.randint(0, model.vocab, rs.randint(4, args.prompt_len + 1)).astype(np.int32)
        for _ in range(args.requests)
    ]
    t0 = time.time()
    outs = serve(eng, args, reqs)
    dt = time.time() - t0
    total_new = sum(len(o) for o in outs)
    print(f"[serve:{args.engine}] {len(reqs)} requests, {total_new} tokens "
          f"in {dt:.2f}s ({total_new/dt:.1f} tok/s) on {jax.default_backend()}")
    if args.engine == "continuous" and eng.last_serve_stats:
        s = eng.last_serve_stats
        print(f"[serve:continuous] slot_utilization="
              f"{s['mean_slot_utilization']:.3f} chunks={s['chunks_run']} "
              f"served={s['n_served']}/{s['n_submitted']}")
        if args.paged:
            p = s["paged"]
            print(f"[serve:paged] block_size={p['block_size']} "
                  f"blocks_watermark={p['blocks_in_use_watermark']}"
                  f"/{p['pool_blocks'] - 1} "
                  f"prefix_hit_blocks={p.get('prefix_hit_blocks', 0)} "
                  f"prefill_tokens_saved={p['prefill_tokens_saved']} "
                  f"preemptions={s['n_preemptions']}")
        if args.spec_k > 0:
            sp = s["spec"]
            print(f"[serve:spec] k={sp['spec_k']} "
                  f"draft_layers={sp['draft_layers']} "
                  f"windows={sp['windows']} "
                  f"acceptance_rate={sp['acceptance_rate']:.3f} "
                  f"emitted={sp['emitted_tokens']}")
    print("sample output ids:", outs[0][:10].tolist())
    return 0


if __name__ == "__main__":
    sys.exit(main())
