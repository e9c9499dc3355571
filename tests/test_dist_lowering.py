"""Distributed lowering integration tests.

Runs in a SUBPROCESS with 8 fake host devices (XLA_FLAGS must be set before
jax initialises — exactly the dry-run pattern) and lowers reduced configs on
a (2, 2, 2) pod/data/model mesh: proves the sharding rules produce valid,
divisible PartitionSpecs and the train/prefill/decode graphs compile with
collectives.
"""

import textwrap

from conftest import run_jax_subprocess

SCRIPT = textwrap.dedent(
    """
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import configs
    from repro.dist import sharding as SH
    from repro.launch.mesh import auto_mesh
    from repro.models import lm
    from repro.optim import adamw
    from repro.train import step as train_step_lib

    mesh = auto_mesh((2, 2, 2), ("pod", "data", "model"))
    for arch_name in ["qwen2.5-3b", "olmoe-1b-7b", "zamba2-1.2b",
                      "deepseek-v2-lite", "xlstm-1.3b", "kanformer-100m"]:
        arch = configs.get_reduced(arch_name)
        model = arch.model
        axes = lm.param_axes(model)
        absp = lm.abstract_params(model)
        psh = SH.tree_shardings(axes, absp, mesh)
        params_in = SH.with_sharded_leaves(absp, psh)
        B, S = 8, 16
        tokens = jax.ShapeDtypeStruct((B, S), jnp.int32,
            sharding=NamedSharding(mesh, P(("pod", "data"), None)))
        if model.input_kind == "tokens":
            inputs = {"tokens": tokens, "labels": tokens}
        elif model.input_kind == "embeddings":
            inputs = {"embeddings": jax.ShapeDtypeStruct((B, S, model.d_model),
                jnp.bfloat16, sharding=NamedSharding(mesh, P(("pod","data"), None, None))),
                "labels": tokens}
        else:
            tt = S - model.n_prefix
            tok2 = jax.ShapeDtypeStruct((B, tt), jnp.int32,
                sharding=NamedSharding(mesh, P(("pod", "data"), None)))
            inputs = {"prefix_embeddings": jax.ShapeDtypeStruct(
                (B, model.n_prefix, model.d_model), jnp.bfloat16,
                sharding=NamedSharding(mesh, P(("pod","data"), None, None))),
                "tokens": tok2, "labels": tok2}
        # train step
        tstep = train_step_lib.make_train_step(
            model, adamw.AdamWConfig(), compute_dtype=jnp.bfloat16, accum_steps=2)
        abs_opt = jax.eval_shape(adamw.init_state, absp)
        osh = {"m": SH.tree_zero_shardings(axes, absp, mesh),
               "v": SH.tree_zero_shardings(axes, absp, mesh),
               "step": NamedSharding(mesh, P())}
        opt_in = SH.with_sharded_leaves(abs_opt, osh)
        with mesh:
            c = jax.jit(tstep, out_shardings=(psh, osh, None)).lower(
                params_in, opt_in, inputs).compile()
            assert c.cost_analysis()["flops"] > 0
            # decode step
            cax = lm.cache_axes(model)
            absc = lm.abstract_caches(model, B, S, jnp.bfloat16)
            csh = SH.tree_shardings(cax, absc, mesh)
            caches_in = SH.with_sharded_leaves(absc, csh)
            if model.input_kind == "embeddings":
                tok1 = jax.ShapeDtypeStruct((B, 1, model.d_model), jnp.bfloat16,
                    sharding=NamedSharding(mesh, P(("pod","data"), None, None)))
            else:
                tok1 = jax.ShapeDtypeStruct((B, 1), jnp.int32,
                    sharding=NamedSharding(mesh, P(("pod", "data"), None)))
            pos = jax.ShapeDtypeStruct((B,), jnp.int32,
                sharding=NamedSharding(mesh, P(("pod", "data"))))
            d = jax.jit(lambda p, t, cc, po: lm.decode_step(p, model, t, cc, po, jnp.bfloat16),
                        out_shardings=(None, csh)).lower(
                params_in, tok1, caches_in, pos).compile()
        txt = c.as_text()
        has_coll = ("all-reduce" in txt) or ("all-gather" in txt) or ("reduce-scatter" in txt)
        assert has_coll, arch_name + ": no collectives in sharded train step?"
        print("OK", arch_name)
    print("ALL_OK")
    """
)


def test_multiaxis_lowering_subprocess():
    proc = run_jax_subprocess(SCRIPT, devices=8, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "ALL_OK" in proc.stdout
