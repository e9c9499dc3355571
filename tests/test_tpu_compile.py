"""AOT compiles of the served TPU kernels for a described v5e chip.

Interpret mode runs any tiling; Mosaic does not.  These tests compile, for
one chip of a ``v5e:2x2`` topology described here (no chip attached), every
Pallas kernel on the served TPU path at kanformer-100m's widths:

* the fused KAN kernel (``kernels/kan_fused_gemm.py``) in both FFN
  directions (512->1024, 1024->512) at prefill rows (4x256) and decode rows
  (4, 8), with the tiles ``autotune.get_tiles`` resolves for the TPU, for
  bf16 and fp32 activations against fp32 coefficients;
* every TPU candidate tiling ``autotune.candidate_tiles`` emits there;
* the paged block gather (``kernels/paged_gather.py``) at the pool shape
  the one-chip smoke run serves, per layer and vmapped over the stacked
  layers;
* one full-model decode step on the 2x2 (data, model) mesh of all four
  chips, where the kernels run under ``shard_map``, and the paged gather
  there, with the bytes of pool it all-gathers;
* the engine's decode chunk on one chip at 16 slots of 3120 tokens, whose
  step and layer loops write one token per layer into the carried view
  and build no copy of it.

The sparse N:M kernel is not on the TPU path (``resolve_inference_method``
never returns it there), so it is not compiled here.  The topology is
described in a module fixture: only the worker that runs this file loads
the TPU compiler.
"""

import math
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import kanformer_100m
from repro.kernels import autotune
from repro.kernels.kan_fused_gemm import kan_fused_gemm_pallas
from repro.kernels.paged_gather import gather_blocks_pallas

MODEL = kanformer_100m.config().model
BLK = MODEL.unit[0]
D, FF, GRID = MODEL.d_model, BLK.kan_ff, BLK.kan_grid
DIRECTIONS = {"up": (D, FF), "down": (FF, D)}
PREFILL_ROWS, DECODE_ROWS = 4 * 256, (4, 8)
# Paged pool of the one-chip smoke run: 4 slots x 19 blocks + sentinel,
# 16-token blocks, 8 kv heads of 64.
POOL, TABLE = (77, 16, 8, 64), (4, 19)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    # A compile for a described chip cannot be read back from the
    # persistent cache; keep it out of the cache.
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_fused(sharding, rows, K, N, x_dtype, tiles):
    bb, bn, bk = tiles
    fn = jax.jit(lambda x, c, w: kan_fused_gemm_pallas(
        x, c, GRID, base_w=w, bb=bb, bn=bn, bk=bk))
    return fn.lower(
        _sds((rows, K), x_dtype, sharding),
        _sds((K, GRID.n_basis, N), jnp.float32, sharding),
        _sds((K, N), jnp.float32, sharding),
    ).compile()


@pytest.mark.parametrize("x_dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("direction", sorted(DIRECTIONS))
@pytest.mark.parametrize("rows", (PREFILL_ROWS,) + DECODE_ROWS)
def test_fused_kernel_compiles_at_served_tiles(one_chip, rows, direction,
                                               x_dtype):
    K, N = DIRECTIONS[direction]
    tiles = autotune.get_tiles("fused", rows, K, N, GRID.n_basis,
                               jnp.dtype(x_dtype), "tpu")
    compiled = _compile_fused(one_chip, rows, K, N, jnp.dtype(x_dtype), tiles)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("direction", sorted(DIRECTIONS))
@pytest.mark.parametrize("rows", (PREFILL_ROWS, DECODE_ROWS[-1]))
def test_every_tpu_candidate_tiling_compiles(one_chip, rows, direction):
    K, N = DIRECTIONS[direction]
    cands = autotune.candidate_tiles("fused", rows, K, N, GRID.n_basis,
                                     jnp.bfloat16, "tpu")
    assert cands
    for tiles in cands:
        _compile_fused(one_chip, rows, K, N, jnp.bfloat16, tiles)


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("stacked", [False, True], ids=["layer", "stacked"])
def test_paged_gather_compiles_at_pool_shape(one_chip, stacked, kv_dtype):
    pool = ((MODEL.n_repeats,) if stacked else ()) + POOL
    if stacked:
        fn = jax.jit(lambda p, t: jax.vmap(
            lambda q: gather_blocks_pallas(q, t))(p))
    else:
        fn = jax.jit(gather_blocks_pallas)
    compiled = fn.lower(
        _sds(pool, jnp.dtype(kv_dtype), one_chip),
        _sds(TABLE, jnp.int32, one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_mesh_decode_step_keeps_kan_coefficients_sharded(topo, monkeypatch):
    """One decode step of the full model on a 2x2 (data, model) mesh of
    four v5e chips.  Mosaic kernels cannot be partitioned by GSPMD, so the
    model runs them under ``shard_map``; the compiled program holds the
    kernels and all-gathers no KAN coefficient block ``(..., 8, ff|d)``."""
    import re

    from repro.dist.sharding import ShardingCtx, with_sharded_leaves
    from repro.launch.mesh import auto_mesh
    from repro.models import lm

    # The code under test picks its kernels from the backend it runs on;
    # here it must take its TPU branches while compiling for the TPU.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ctx = ShardingCtx(auto_mesh((2, 2), ("data", "model"),
                                devices=topo.devices))
    slots, max_seq = 4, 304
    params = with_sharded_leaves(lm.abstract_params(MODEL),
                                 ctx.param_shardings(MODEL))
    caches = with_sharded_leaves(
        lm.abstract_caches(MODEL, slots, max_seq, jnp.bfloat16),
        ctx.cache_shardings(MODEL, slots, max_seq, jnp.bfloat16))
    rows = ctx.rows(slots)
    compiled = jax.jit(
        lambda p, t, c, pos: lm.decode_step(p, MODEL, t, c, pos,
                                            jnp.bfloat16, None, ctx),
        donate_argnums=(2,),
    ).lower(
        params, jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=rows),
        caches, jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=rows),
    ).compile()
    txt = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in txt
    coeff = re.compile(rf",{GRID.n_basis},({FF}|{D})\]")
    gathers = [ln for ln in txt.splitlines()
               if re.search(r"all-gather(-start)?\(", ln)]
    assert not [ln for ln in gathers if coeff.search(ln.split("all-gather")[0])]


def _all_gather_bytes(hlo: str) -> int:
    """Bytes of the results of every all-gather in a compiled program."""
    import re

    size = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "s8": 1}
    total = 0
    for ln in hlo.splitlines():
        m = re.search(r"=\s*(.*?)\s*all-gather(-start)?\(", ln)
        if m:
            dt, dims = re.findall(r"(\w+)\[([\d,]*)\]", m.group(1))[-1]
            total += size[dt] * math.prod(int(d) for d in dims.split(",") if d)
    return total


@pytest.mark.parametrize("pool_blocks", [POOL[0], POOL[0] + 1])
def test_mesh_paged_gather_all_gathers_the_pool(topo, monkeypatch, pool_blocks):
    """The paged gather on the 2x2 mesh runs under ``shard_map`` with the
    pool's block axis whole on every device.  A pool whose block count
    the data axis divides is stored split over it; each gather then
    all-gathers every layer's whole pool, k and v, to every device (the
    bytes below).  The smoke run's odd pool is stored whole on every data
    shard and gathers nothing."""
    from repro.dist.sharding import ShardingCtx, with_sharded_leaves
    from repro.launch.mesh import auto_mesh
    from repro.models import lm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ctx = ShardingCtx(auto_mesh((2, 2), ("data", "model"),
                                devices=topo.devices))
    bs, dt = POOL[1], jnp.bfloat16
    caches = with_sharded_leaves(
        jax.eval_shape(lambda: lm.init_paged_caches(MODEL, pool_blocks, bs, dt)),
        ctx.paged_cache_shardings(MODEL, pool_blocks, bs, dt))
    compiled = jax.jit(lambda c, t: lm.paged_views(c, t, ctx)).lower(
        caches, jax.ShapeDtypeStruct(TABLE, jnp.int32, sharding=ctx.rows(TABLE[0])),
    ).compile()
    txt = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in txt
    kv_heads_per_device = POOL[2] // 2
    whole_pools = 2 * math.prod(
        (MODEL.n_repeats, pool_blocks, bs, kv_heads_per_device, POOL[3])) * 2
    expect = whole_pools if pool_blocks % 2 == 0 else 0
    assert _all_gather_bytes(txt) == expect


def _loop_ops_of_shapes(hlo: str, shapes: set) -> list:
    """``(op, fused root op)`` of every unfused instruction outside the
    entry computation (the bodies of the step and layer loops) whose result
    has one of ``shapes``; parameters, tuples and bitcasts move nothing."""
    import re

    comps = re.split(r"\n(?=(?:ENTRY )?%\S+ \()", hlo)
    roots = {}
    for c in comps:
        m = re.search(r"ROOT %\S+ = \S+ ([\w-]+)\(", c)
        roots[c.split(" (")[0].lstrip("%")] = m.group(1) if m else None
    found = []
    for c in comps:
        name = c.split(" (")[0]
        if name.startswith("ENTRY") or "fused_computation" in name or name.endswith("_fusion"):
            continue
        for ln in c.splitlines()[1:]:
            m = re.match(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* ([\w-]+)\(", ln)
            if not m or m.group(1) not in shapes or m.group(2) in (
                    "get-tuple-element", "parameter", "tuple", "bitcast"):
                continue
            calls = re.search(r"calls=%([^,\s]+)", ln)
            found.append((m.group(2), roots.get(calls.group(1)) if calls else None))
    return found


def test_decode_chunk_writes_one_token_per_layer(one_chip, monkeypatch):
    """The engine's decode chunk (8 steps) at the size of the served prefill
    workload: 16 slots, 3120 positions, kanformer-100m's 8 layers of 8 kv
    heads of 64.  The stacked caches are the layer loop's carry, so inside
    the loops the only instruction that produces the view, or one layer of
    it, is the in-place scatter of each layer's token; the layer the
    attention reads is a dynamic slice fused into its reductions."""
    from repro.models import lm
    from repro.serve.engine import Engine, ServeConfig

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    slots, max_seq = 16, 3120
    sds = lambda a: _sds(a.shape, a.dtype, one_chip)  # noqa: E731
    params = jax.tree.map(sds, lm.abstract_params(MODEL))
    caches = jax.tree.map(
        sds, lm.abstract_caches(MODEL, slots, max_seq, jnp.bfloat16))
    eng = Engine(params, MODEL, ServeConfig(max_seq=max_seq,
                                            compute_dtype="bfloat16"))
    i32 = lambda *s: _sds(s, jnp.int32, one_chip)  # noqa: E731
    hlo = eng._decode_scan.lower(
        8, params, i32(slots, 1), caches, i32(slots),
        _sds((slots, 2), jnp.uint32, one_chip),
        _sds((slots,), jnp.bool_, one_chip), i32(), i32(),
    ).compile().as_text()
    view = caches["unit"][0]["k"].shape
    shapes = {",".join(map(str, view)), ",".join(map(str, view[1:]))}
    assert _loop_ops_of_shapes(hlo, shapes) == [("fusion", "scatter")] * 2
