"""Pipeline parallelism (GPipe over the pod axis): pipelined loss must equal
the plain loss. Runs in a subprocess with 8 fake devices (pod=2)."""

import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import configs
    from repro.launch.mesh import auto_mesh
    from repro.models import lm
    from repro.train import pipeline as PP

    mesh = auto_mesh((2, 2, 2), ("pod", "data", "model"))
    arch = configs.get_reduced("qwen1.5-0.5b")
    model = arch.model   # 2 repeats -> 2 stages x 1
    params = lm.init_params(jax.random.PRNGKey(0), model)
    rs = np.random.RandomState(0)
    B, T = 8, 16
    batch = {
        "tokens": jnp.asarray(rs.randint(0, model.vocab, (B, T)), jnp.int32),
        "labels": jnp.asarray(rs.randint(0, model.vocab, (B, T)), jnp.int32),
    }
    ref_loss, _ = lm.lm_loss(params, model, batch, jnp.float32)

    staged = PP.stage_params(params, 2)
    staged["unit"] = [jax.device_put(
        p, jax.tree.map(lambda _: NamedSharding(mesh, P("pod")), p))
        for p in staged["unit"]]
    loss_fn = PP.make_pp_loss(model, n_stages=2, microbatches=4, mesh=mesh,
                              compute_dtype=jnp.float32)
    with mesh:
        pp_loss = jax.jit(loss_fn)(staged, batch)
        # gradients flow through the pipeline (ppermute + scan autodiff)
        g = jax.jit(jax.grad(lambda p, b: loss_fn(p, b)))(staged, batch)
    print("ref", float(ref_loss), "pp", float(pp_loss))
    assert abs(float(ref_loss) - float(pp_loss)) < 2e-3, (ref_loss, pp_loss)
    leaves = jax.tree.leaves(g)
    assert all(bool(jnp.all(jnp.isfinite(l))) for l in leaves)
    gn = sum(float(jnp.sum(l.astype(jnp.float32)**2)) for l in leaves) ** 0.5
    assert gn > 0
    print("PP_OK grad_norm", gn)
    """
)


def test_pipeline_matches_plain_loss():
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, timeout=900,
        # JAX_PLATFORMS=cpu: the script fakes host devices; without it jax
        # may probe a TPU runtime (slow metadata retries on TPU-image hosts)
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu"}, cwd=".",
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "PP_OK" in proc.stdout, proc.stdout
