"""dist/sharding.py edge cases the mesh serving path now hits.

These are pure spec-derivation tests: ``spec_for``/``zero_spec``/
``batch_spec`` only read ``mesh.shape``, so a stub mesh object is enough —
no fake-device subprocess needed (the end-to-end distribution proofs live
in ``tests/test_mesh_serving.py``).
"""

import jax
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import (
    ShardingCtx,
    batch_spec,
    spec_for,
    zero_spec,
)
from repro.launch.mesh import auto_mesh
from repro.models.layers import Axes


class StubMesh:
    """Only what the spec rules read: an axis-name -> size mapping."""

    def __init__(self, **shape):
        self.shape = shape


MESH = StubMesh(data=2, model=4)
POD_MESH = StubMesh(pod=2, data=2, model=2)


def _axes(*names):
    return Axes(tuple(names))


# ---------------------------------------------------------------------------
# spec_for: model-axis divisibility fallback
# ---------------------------------------------------------------------------


def test_spec_for_full_replication_when_nothing_divides_model():
    """No dimension divisible by the model axis -> the model axis is simply
    not placed (full replication on the tensor-parallel axis); the data
    axes may still find a home."""
    spec = spec_for(_axes("embed", "ffn"), (5, 7), MESH)
    assert spec == P(None, None)
    # with a divisible batch the data axis still lands
    spec = spec_for(_axes("batch", "ffn"), (6, 7), MESH)
    assert spec == P("data", None)


def test_spec_for_model_priority_falls_through_on_divisibility():
    """ffn outranks heads, but when ffn doesn't divide the model axis the
    next priority (heads) takes it — per-tensor fallback, not global."""
    spec = spec_for(_axes("ffn", "heads"), (6, 8), MESH)
    assert spec == P(None, "model")


def test_spec_for_cache_batch1_falls_through_to_seq_cache():
    """B=1 long-context decode: the batch can't occupy the data axes, so
    the KV cache's seq_cache dimension takes them instead."""
    names = _axes("batch", "seq_cache", "kv_heads", "head_dim")
    spec = spec_for(names, (1, 64, 4, 16), MESH)
    assert spec == P(None, "data", "model", None)
    # and when the batch CAN take data, seq_cache stays unsharded
    spec = spec_for(names, (8, 64, 4, 16), MESH)
    assert spec == P("data", None, "model", None)


def test_spec_for_paged_pool_blocks_take_data():
    """Paged pools carry no batch/seq_cache: the kv_blocks axis absorbs the
    data axes (each DP shard holds a slice of the physical pool) while
    kv_heads still takes model."""
    names = _axes("kv_blocks", None, "kv_heads", "head_dim")
    spec = spec_for(names, (16, 8, 4, 16), MESH)
    assert spec == P("data", None, "model", None)
    # odd pool (the engine's default slots*n_logical+1 sizing): replicate
    spec = spec_for(names, (17, 8, 4, 16), MESH)
    assert spec == P(None, None, "model", None)
    # pod+data both land when the block count divides their product
    spec = spec_for(names, (16, 8, 4, 16), POD_MESH)
    assert spec == P(("pod", "data"), None, "model", None)


# ---------------------------------------------------------------------------
# zero_spec
# ---------------------------------------------------------------------------


def test_zero_spec_on_fully_sharded_spec_is_identity():
    """Every dimension already carries a mesh axis -> ZeRO has nowhere to
    put the data axes; the spec must come back unchanged (not error, not
    double-place an axis)."""
    base = P("data", "model")
    assert zero_spec(base, (8, 8), MESH) == base


def test_zero_spec_skips_used_data_axes():
    """A spec already using 'data' must not get it a second time."""
    base = P("data", None)
    assert zero_spec(base, (8, 8), MESH) == base


def test_zero_spec_adds_data_to_first_divisible_replicated_dim():
    base = P(None, "model")
    assert zero_spec(base, (7, 8), MESH) == P(None, "model")   # 7 % 2 != 0
    assert zero_spec(base, (8, 8), MESH) == P("data", "model")


# ---------------------------------------------------------------------------
# batch_spec
# ---------------------------------------------------------------------------


def test_batch_spec_batch1_replicates():
    """B=1 decode: nothing divides, the row arrays replicate (the cache's
    seq_cache dim is where the data axes go instead — see above)."""
    assert batch_spec(MESH, 1) == P(None)
    assert batch_spec(MESH, 8) == P("data")
    assert batch_spec(POD_MESH, 4) == P(("pod", "data"))
    # batch 2 on a pod mesh: the full (pod, data)=4 doesn't divide, the
    # largest single axis that does takes it
    assert batch_spec(POD_MESH, 2) == P("data")


# ---------------------------------------------------------------------------
# ShardingCtx (real 1-device mesh: the degenerate everything-replicates ctx)
# ---------------------------------------------------------------------------


def test_make_host_mesh_default_and_shapes():
    """make_host_mesh: default keeps the historical (1, n) all-model shape;
    an explicit (data, model) shape is validated against the host's device
    count (the old version force-shaped (1, n) and made host data
    parallelism impossible)."""
    import pytest

    from repro.launch.mesh import make_host_mesh, parse_mesh_shape

    n = len(jax.devices())
    mesh = make_host_mesh()
    assert dict(mesh.shape) == {"data": 1, "model": n}
    mesh = make_host_mesh((1, 1))
    assert dict(mesh.shape) == {"data": 1, "model": 1}
    with pytest.raises(ValueError, match="too few"):
        make_host_mesh((n + 1, n + 1))
    with pytest.raises(ValueError, match="positive"):
        make_host_mesh((0, 1))
    assert parse_mesh_shape("2x4") == (2, 4)
    assert parse_mesh_shape("1X1") == (1, 1)
    with pytest.raises(ValueError):
        parse_mesh_shape("2x")
    with pytest.raises(ValueError):
        parse_mesh_shape("8")


def test_sharding_ctx_single_device_degrades_to_replication():
    mesh = auto_mesh((1, 1), ("data", "model"))
    ctx = ShardingCtx(mesh)
    assert ctx.n_devices == 1
    # size-1 mesh axes divide everything, so specs still NAME them — but
    # the resulting sharding is functionally full replication
    assert ctx.named(("batch", "seq_cache", "kv_heads", "head_dim"),
                     (4, 32, 4, 16)).is_fully_replicated
    assert ctx.rows(4).is_fully_replicated
    assert ctx.replicated().is_fully_replicated
    # constrain is a no-op passthrough shape-wise
    import jax.numpy as jnp

    x = jnp.ones((4, 8))
    y = ctx.constrain(x, ("batch", "embed"))
    assert y.shape == x.shape
