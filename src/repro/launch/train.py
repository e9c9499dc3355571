"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Production posture (DESIGN.md §4):
* sharded via the logical-axis rules onto whatever mesh the host offers
  (the production mesh shape is exercised by the dry-run);
* checkpoint/restart: atomic async checkpoints every ``--ckpt-every`` steps,
  auto-resume from the latest valid one, checkpoint-on-SIGTERM/SIGINT
  (pre-emption handling), bounded retry around the step;
* deterministic data: batch = f(seed, step), so restarts never skip/replay.
"""

from __future__ import annotations

import argparse
import signal
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.checkpoint import store
from repro.data import pipeline as dp
from repro.dist import sharding as SH
from repro.launch.mesh import make_host_mesh
from repro.launch.runtime import enable_compile_cache, require_tpu
from repro.models import lm
from repro.optim import adamw
from repro.train import step as train_step_lib


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=configs.list_configs())
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-size config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--compute-dtype", default="float32")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--max-retries", type=int, default=2)
    args = ap.parse_args(argv)

    enable_compile_cache()
    require_tpu(allow_explicit_cpu=True)
    arch = configs.get_reduced(args.arch) if args.reduced else configs.get_config(args.arch)
    model = arch.model
    mesh = make_host_mesh()
    cdtype = jnp.float32 if args.compute_dtype == "float32" else jnp.bfloat16

    opt_cfg = adamw.AdamWConfig(lr=args.lr, total_steps=args.steps, warmup_steps=min(20, args.steps // 10 + 1))
    tstep = train_step_lib.make_train_step(
        model, opt_cfg, compute_dtype=cdtype, accum_steps=args.accum
    )

    axes = lm.param_axes(model)
    params = lm.init_params(jax.random.PRNGKey(args.seed), model)
    pshard = SH.tree_shardings(axes, jax.eval_shape(lambda: params), mesh)
    params = jax.tree.map(jax.device_put, params, pshard)
    opt_state = adamw.init_state(params)

    start_step = 0
    ckpt = store.AsyncCheckpointer(args.ckpt_dir) if args.ckpt_dir else None
    if ckpt is not None:
        latest = store.latest_step(args.ckpt_dir)
        if latest is not None:
            (params, opt_state), mf = store.restore(
                args.ckpt_dir, latest, (params, opt_state)
            )
            start_step = mf["step"]
            print(f"[resume] restored step {start_step} from {args.ckpt_dir}")

    stop = {"flag": False}

    def _on_signal(signum, frame):
        print(f"[signal] {signum}: checkpoint-and-exit requested")
        stop["flag"] = True

    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)

    data_cfg = dp.LMDataConfig(
        vocab=model.vocab, seq_len=args.seq, global_batch=args.batch, seed=args.seed
    )
    jit_step = jax.jit(tstep, donate_argnums=(0, 1))
    t_start = time.time()
    losses = []
    step_i = start_step
    while step_i < args.steps and not stop["flag"]:
        batch = dp.lm_batch(data_cfg, step_i)
        if model.input_kind == "embeddings":
            rs = np.random.RandomState(step_i)
            batch = {
                "embeddings": jnp.asarray(
                    rs.normal(size=(args.batch, args.seq, model.d_model)).astype(np.float32)
                ),
                "labels": batch["labels"],
            }
        elif model.input_kind == "mixed":
            rs = np.random.RandomState(step_i)
            batch = {
                "prefix_embeddings": jnp.asarray(
                    rs.normal(size=(args.batch, model.n_prefix, model.d_model)).astype(np.float32)
                ),
                "tokens": batch["tokens"],
                "labels": batch["labels"],
            }
        for attempt in range(args.max_retries + 1):
            try:
                params, opt_state, metrics = jit_step(params, opt_state, batch)
                break
            except Exception as e:  # bounded retry (transient failures)
                if attempt == args.max_retries:
                    raise
                print(f"[retry] step {step_i} attempt {attempt + 1}: {e}")
        step_i += 1
        loss = float(metrics["loss"])
        losses.append(loss)
        if step_i % args.log_every == 0 or step_i == args.steps:
            dt = time.time() - t_start
            tok_s = step_i * args.batch * args.seq / max(dt, 1e-9)
            print(
                f"step {step_i:5d} loss {loss:.4f} "
                f"gnorm {float(metrics['grad_norm']):.3f} "
                f"lr {float(metrics['lr']):.2e} tok/s {tok_s:,.0f}"
            )
        if ckpt is not None and (step_i % args.ckpt_every == 0 or stop["flag"]):
            ckpt.save_async(step_i, (params, opt_state), {"loss": loss})
    if ckpt is not None:
        ckpt.save_async(step_i, (params, opt_state), {"loss": losses[-1] if losses else None})
        ckpt.wait()
    first = float(np.mean(losses[:10])) if len(losses) >= 10 else (losses[0] if losses else float("nan"))
    last = float(np.mean(losses[-10:])) if losses else float("nan")
    print(f"[done] steps={step_i} loss {first:.4f} -> {last:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
