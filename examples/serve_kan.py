"""Batched serving demo: serve a small kanformer with batched requests
through the prefill+decode engine.

    PYTHONPATH=src python examples/serve_kan.py                      # static
    PYTHONPATH=src python examples/serve_kan.py --engine continuous  # slots
    PYTHONPATH=src python examples/serve_kan.py --engine continuous \\
        --shared-prefix                                   # paged + prefix hits

``--engine static`` drains length-sorted fixed buckets;
``--engine continuous`` recycles batch slots the moment a request finishes
(EOS or budget) — the software analogue of the paper's never-idle PEs.
``--shared-prefix`` switches to the paged KV cache and builds a
system-prompt-heavy workload (every request shares a long prefix, unique
short suffixes): the prefix cache prefillls the shared blocks once and
every later admission reuses them, so the demo prints how many prefill
tokens the block pool saved (DESIGN.md §3b).  ``--mesh DxM`` serves on a
(data, model) host mesh with sharded params and KV (DESIGN.md §4).
"""

import argparse
import time

import jax
import numpy as np

from repro import configs
from repro.core.kan_layer import resolve_inference_method
from repro.models import lm
from repro.serve.engine import Engine, ServeConfig


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--engine", choices=("static", "continuous"),
                    default="static")
    ap.add_argument("--chunk-steps", type=int, default=4)
    ap.add_argument("--shared-prefix", action="store_true",
                    help="paged-KV demo: one shared system prompt + unique "
                         "suffixes, exercising prefix-cache hits end to end")
    ap.add_argument("--mesh", type=str, default=None, metavar="DxM",
                    help="serve on a (data, model) host mesh, e.g. 1x2 "
                         "(DESIGN.md §4); default: single device")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: drafts per verify window "
                         "(0 disables; outputs bit-identical either way — "
                         "DESIGN.md §9)")
    ap.add_argument("--draft-layers", type=int, default=1,
                    help="speculative: unit repeats kept in the drafter")
    args = ap.parse_args(argv)
    if args.shared_prefix and args.engine != "continuous":
        ap.error("--shared-prefix needs --engine continuous (paged KV)")
    if args.spec_k < 0:
        ap.error(f"--spec-k must be >= 0, got {args.spec_k}")
    if args.spec_k > 0 and args.engine != "continuous":
        ap.error("--spec-k needs --engine continuous")

    mesh = None
    if args.mesh is not None:
        from repro.launch.mesh import make_host_mesh, parse_mesh_shape

        try:
            mesh = make_host_mesh(parse_mesh_shape(args.mesh))
        except ValueError as e:
            ap.error(str(e))
        print(f"mesh={dict(mesh.shape)} over {mesh.size} host devices "
              f"(params + KV sharded; same outputs as single-device)")
    arch = configs.get_reduced("kanformer-100m")
    params = lm.init_params(jax.random.PRNGKey(0), arch.model)
    eng = Engine(params, arch.model,
                 ServeConfig(max_seq=96, max_new_tokens=16,
                             paged=args.shared_prefix, block_size=8,
                             mesh=mesh, spec_k=args.spec_k,
                             draft_layers=args.draft_layers))
    rs = np.random.RandomState(0)
    if args.shared_prefix:
        # system-prompt-heavy workload: 32 shared tokens, 3-8 unique ones
        system = rs.randint(0, arch.model.vocab, 32).astype(np.int32)
        requests = [
            np.concatenate([
                system,
                rs.randint(0, arch.model.vocab, rs.randint(3, 9)).astype(np.int32),
            ])
            for _ in range(12)
        ]
    else:
        requests = [
            rs.randint(0, arch.model.vocab, rs.randint(4, 24)).astype(np.int32)
            for _ in range(12)
        ]
    print(f"backend={jax.default_backend()} engine={args.engine} "
          f"kan_method={resolve_inference_method()} "
          f"decode=scan (one compiled program per generation/chunk)")
    t0 = time.time()
    if args.engine == "continuous":
        outs = eng.serve_continuous(requests, slots=4,
                                    chunk_steps=args.chunk_steps)
    else:
        outs = eng.serve_requests(requests, batch_size=4)
    dt = time.time() - t0
    n_tok = sum(len(o) for o in outs)
    print(f"served {len(requests)} requests / {n_tok} new tokens in {dt:.2f}s "
          f"({n_tok/dt:.1f} tok/s, {jax.default_backend()})")
    if args.engine == "continuous" and eng.last_serve_stats:
        print(f"mean_slot_utilization="
              f"{eng.last_serve_stats['mean_slot_utilization']:.3f}")
        if args.shared_prefix:
            p = eng.last_serve_stats["paged"]
            total = p["prefill_tokens_computed"] + p["prefill_tokens_saved"]
            print(f"paged: prefix_hit_blocks={p['prefix_hit_blocks']} "
                  f"prefill_tokens_saved={p['prefill_tokens_saved']}/{total} "
                  f"blocks_watermark={p['blocks_in_use_watermark']}"
                  f"/{p['pool_blocks'] - 1}")
        if args.spec_k > 0:
            sp = eng.last_serve_stats["spec"]
            print(f"speculative: k={sp['spec_k']} "
                  f"draft_layers={sp['draft_layers']} "
                  f"acceptance_rate={sp['acceptance_rate']:.3f} "
                  f"windows={sp['windows']}")
    for i, o in enumerate(outs[:3]):
        print(f"  req{i} prompt_len={len(requests[i])} -> {o[:8].tolist()}...")


if __name__ == "__main__":
    main()
