"""Mesh-serving rehearsal on the CPU: 1-device engine vs an 8-fake-CPU-device
(2x4 data x model) mesh — tok/s plus **per-device param + KV-pool bytes**
(``BENCH_shard.json``, written by ``benchmarks/run.py``).

This is a CPU-only rehearsal, on every machine: its child process is
forced onto the CPU (``JAX_PLATFORMS=cpu``) and never touches a chip, and
every row and the report carry the platform the child measured
(``platform``).  Its tok/s is host-CPU time, never a device metric; the
four-chip mesh is measured by ``chip_smoke.py --four-chips``.

The point on a CPU host is the MEMORY column, not the speed column: the
8 fake devices share one physical CPU, so the sharded engine pays real
collective/reshard overhead while gaining zero parallel FLOPs — tok/s
ratio < 1 is expected here and is exactly the resharding cost DESIGN.md §4
tabulates.  What the mesh buys is the per-device footprint: params shard
``model``-axis dimensions 4-way and the paged block pool shards blocks
2-way / kv_heads 4-way, so each device holds a fraction of the weights and
of the KV pool — the capacity lever that lets one serving process span
chips whose HBM a replicated model would blow.

Runs in a SUBPROCESS because ``--xla_force_host_platform_device_count``
must be set before jax initialises (the harness process already holds a
1-device jax).  Timings are interleaved best-of-repeats (host wall clock
swings 2-3x); byte counts are exact (summed ``addressable_shards`` on
device 0).

``$KAN_SAS_BENCH_SMOKE=1`` shrinks request count/budgets for CI.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap


def _smoke() -> bool:
    return os.environ.get("KAN_SAS_BENCH_SMOKE", "") not in ("", "0")


_SCRIPT = textwrap.dedent(
    """
    import json, os, time
    import jax, numpy as np
    from repro import configs
    from repro.models import lm
    from repro.serve.engine import Engine, ServeConfig
    from repro.launch.mesh import make_host_mesh

    smoke = os.environ.get("KAN_SAS_BENCH_SMOKE", "") not in ("", "0")
    n_requests, max_new, reps = (8, 6, 2) if smoke else (16, 24, 3)
    slots, chunk_steps, bs = 4, 4, 8
    arch = configs.get_reduced("kanformer-100m")
    max_seq = 48 if smoke else 80
    pool_blocks = slots * (max_seq // bs) + 2   # even: the data axis divides

    rs = np.random.RandomState(0)
    requests = [
        rs.randint(0, arch.model.vocab, rs.randint(4, 13)).astype(np.int32)
        for _ in range(n_requests)
    ]
    params = lm.init_params(jax.random.PRNGKey(0), arch.model)

    def bytes_on_dev0(tree):
        dev = jax.devices()[0]
        return int(sum(
            s.data.nbytes
            for leaf in jax.tree.leaves(tree)
            for s in leaf.addressable_shards if s.device == dev
        ))

    def build(mesh):
        return Engine(params, arch.model, ServeConfig(
            max_seq=max_seq, max_new_tokens=max_new, paged=True,
            block_size=bs, pool_blocks=pool_blocks, mesh=mesh))

    engines = {
        "1x1": build(None),                    # today's single-device engine
        "2x4": build(make_host_mesh((2, 4))),  # data=2 x model=4 mesh
    }

    def serve(eng):
        eng.serve_continuous(list(requests), slots=slots,
                             chunk_steps=chunk_steps, seed=0)
        return dict(eng.last_serve_stats)

    rows = {}
    for name, eng in engines.items():
        serve(eng)                             # warm every jitted shape
    stats = {name: None for name in engines}
    for _ in range(reps):                      # interleaved best-of-repeats
        for name, eng in engines.items():
            s = serve(eng)
            if stats[name] is None or s["wall_s"] < stats[name]["wall_s"]:
                stats[name] = s
    for name, eng in engines.items():
        s = stats[name]
        pool = eng._make_paged_caches(pool_blocks, bs)
        rows[name] = {
            "mesh_shape": s["mesh_shape"],
            "devices": eng.shard.n_devices if eng.shard else 1,
            "wall_s": s["wall_s"],
            "useful_tokens": s["useful_tokens"],
            "tokens_per_s": s["useful_tokens"] / s["wall_s"],
            "params_bytes_per_device": bytes_on_dev0(eng.params),
            "pool_bytes_per_device": bytes_on_dev0(pool),
        }
        del pool
    rows["platform"] = jax.devices()[0].platform
    print("RESULT " + json.dumps(rows))
    """
)


def run() -> list[tuple[str, float, str]]:
    env = {
        "PYTHONPATH": "src", "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    }
    if _smoke():
        env["KAN_SAS_BENCH_SMOKE"] = "1"
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True,
        timeout=1800, env=env, cwd=os.path.join(os.path.dirname(__file__), ".."),
    )
    if proc.returncode != 0:
        raise RuntimeError(f"shard_bench subprocess failed:\n{proc.stderr[-3000:]}")
    line = next(l for l in proc.stdout.splitlines() if l.startswith("RESULT "))
    rows = json.loads(line[len("RESULT "):])
    platform = rows.pop("platform")

    one, sharded = rows["1x1"], rows["2x4"]
    param_cut = one["params_bytes_per_device"] / sharded["params_bytes_per_device"]
    pool_cut = one["pool_bytes_per_device"] / sharded["pool_bytes_per_device"]
    rep = {
        "platform": platform,
        "workload": {"arch": "kanformer-100m (reduced)", "paged": True,
                     "smoke": _smoke()},
        "meshes": rows,
        "params_bytes_cut_per_device": param_cut,
        "pool_bytes_cut_per_device": pool_cut,
        "tokens_per_s_ratio": sharded["tokens_per_s"] / one["tokens_per_s"],
        "note": "8 fake devices share one CPU: the ratio prices collective "
                "overhead with zero parallel-FLOP gain; the bytes columns "
                "are the capacity win (DESIGN.md §4).",
    }
    run.last_report = rep  # type: ignore[attr-defined]
    return [
        ("shard.1x1", one["wall_s"] * 1e6,
         f"platform={platform} tok/s={one['tokens_per_s']:.1f} "
         f"param_B/dev={one['params_bytes_per_device']}"),
        ("shard.2x4", sharded["wall_s"] * 1e6,
         f"platform={platform} tok/s={sharded['tokens_per_s']:.1f} "
         f"param_B/dev={sharded['params_bytes_per_device']}"),
        ("shard.cut", 0.0,
         f"param_bytes/dev x{param_cut:.2f}, pool_bytes/dev x{pool_cut:.2f}, "
         f"tok/s x{rep['tokens_per_s_ratio']:.2f}"),
    ]
