"""Logical-axis -> PartitionSpec rules (DESIGN.md §4).

Parameters, optimizer states and decode caches all carry an :class:`Axes`
leaf naming the *logical* role of every dimension (``embed``, ``ffn``,
``heads``, ``vocab``, ``batch``, ``seq_cache``, ...).  This module turns
those names into ``PartitionSpec``s for a concrete mesh:

* the tensor-parallel ``model`` mesh axis goes to the highest-priority
  logical axis (vocab > experts > ffn > heads > kv_heads > kv_lora > embed)
  whose size divides the mesh axis — the standard Megatron-style placement
  (shard the widest, most parallel dimension; fall back when it doesn't
  divide);
* the data-parallel axes (``pod`` + ``data``) go to ``batch``; when the
  batch cannot occupy them (long-context decode with B=1), the KV cache's
  ``seq_cache`` dimension takes them instead;
* ``layers`` (the scan-over-repeats stacking axis) and anonymous ``None``
  axes are never sharded;
* :func:`zero_spec` adds the data axes to an otherwise-replicated dimension
  — ZeRO-style optimizer-state sharding on top of the parameter spec;
* the paged KV pool's ``kv_blocks`` axis takes the data axes (pools carry
  no ``batch``/``seq_cache``), while ``kv_heads`` still takes ``model`` —
  each DP shard holds a slice of the physical block pool;
* :class:`ShardingCtx` bundles a mesh with these rules so serving call
  sites (``models/lm.py``, ``serve/engine.py``) stop re-deriving specs.

Every rule degrades to replication when divisibility fails, so the same
model code lowers on a 1-device host mesh and a 512-chip production mesh.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.models.layers import Axes

# Highest-priority first: which logical axis takes the tensor-parallel mesh
# axis.  vocab first (embedding/unembed are the largest matrices), then the
# expert and FFN dims (pure column/row parallelism), then attention heads.
MODEL_AXIS_PRIORITY = (
    "vocab", "experts", "ffn", "heads", "kv_heads", "kv_lora", "embed",
)

# Mesh axes that carry data parallelism, outermost first.
DATA_MESH_AXES = ("pod", "data")

# Logical axes that may absorb the data-parallel mesh axes, in order of
# preference.  ``kv_blocks`` is the paged KV pool's block axis (the paged
# analogue of a dense cache's slots × sequence): pools have no ``batch``
# or ``seq_cache`` dimension, so the block axis takes the data axes —
# each DP shard holds a slice of the physical block pool while the
# ``model`` axis splits ``kv_heads`` exactly as it does dense rows.
BATCH_AXIS_PRIORITY = ("batch", "seq_cache", "kv_blocks")


def _mesh_axis_size(mesh, name: str) -> int:
    return int(mesh.shape[name]) if name in mesh.shape else 0


def _data_axis_combos(mesh) -> list[tuple[str, ...]]:
    """Candidate data-axis assignments, largest first: ("pod","data") ->
    ("data",) -> ("pod",)."""
    present = tuple(a for a in DATA_MESH_AXES if a in mesh.shape)
    combos: list[tuple[str, ...]] = []
    if len(present) > 1:
        combos.append(present)
    for a in present[::-1] if len(present) > 1 else present:
        combos.append((a,))
    # dedupe, preserve order
    seen: set[tuple[str, ...]] = set()
    out = []
    for c in combos:
        if c not in seen:
            seen.add(c)
            out.append(c)
    return out


def _combo_size(mesh, combo: tuple[str, ...]) -> int:
    return math.prod(_mesh_axis_size(mesh, a) for a in combo)


def spec_for(axes: Axes, shape: tuple[int, ...], mesh) -> PartitionSpec:
    """PartitionSpec for one tensor from its logical axes + shape.

    Divisibility fallback: a mesh axis is only assigned to a dimension whose
    size it divides; otherwise the next candidate dimension (or replication)
    is used.  Each mesh axis is used at most once per spec.
    """
    assert len(axes.names) == len(shape), (axes, shape)
    entries: list = [None] * len(shape)

    # --- tensor parallelism: the "model" mesh axis -----------------------
    msize = _mesh_axis_size(mesh, "model")
    for logical in MODEL_AXIS_PRIORITY:
        placed = False
        for i, name in enumerate(axes.names):
            if name == logical and msize and shape[i] % msize == 0:
                entries[i] = "model"
                placed = True
                break
        if placed:
            break

    # --- data parallelism: batch (or seq_cache) takes pod+data -----------
    for logical in BATCH_AXIS_PRIORITY:
        placed = False
        for i, name in enumerate(axes.names):
            if name != logical or entries[i] is not None:
                continue
            for combo in _data_axis_combos(mesh):
                cs = _combo_size(mesh, combo)
                if cs and shape[i] % cs == 0:
                    entries[i] = combo if len(combo) > 1 else combo[0]
                    placed = True
                    break
            if placed:
                break
        if placed:
            break

    return PartitionSpec(*entries)


def zero_spec(base: PartitionSpec, shape: tuple[int, ...], mesh) -> PartitionSpec:
    """ZeRO: add the data-parallel axes to the first replicated dimension of
    ``base`` that they divide (optimizer m/v/master shards over DP ranks).

    Falls back to ``base`` unchanged when nothing divides — a 1-device host
    mesh then simply replicates, which is correct if wasteful.
    """
    entries = list(base) + [None] * (len(shape) - len(base))
    used = {a for e in entries for a in ((e,) if isinstance(e, str) else (e or ()))}
    for combo in _data_axis_combos(mesh):
        if any(a in used for a in combo):
            continue
        cs = _combo_size(mesh, combo)
        if not cs:
            continue
        for i, e in enumerate(entries):
            if e is None and shape[i] % cs == 0:
                entries[i] = combo if len(combo) > 1 else combo[0]
                return PartitionSpec(*entries)
    return PartitionSpec(*entries)


def batch_spec(mesh, batch: int) -> PartitionSpec:
    """Spec whose first entry shards the global batch over the data axes
    (largest divisible combination; None when nothing divides)."""
    for combo in _data_axis_combos(mesh):
        cs = _combo_size(mesh, combo)
        if cs and batch % cs == 0:
            return PartitionSpec(combo if len(combo) > 1 else combo[0])
    return PartitionSpec(None)


def _is_axes(x) -> bool:
    return isinstance(x, Axes)


def tree_shardings(axes_tree, abstract_tree, mesh):
    """NamedSharding tree: one leaf per (Axes, ShapeDtypeStruct) pair."""
    return jax.tree.map(
        lambda a, s: NamedSharding(mesh, spec_for(a, s.shape, mesh)),
        axes_tree,
        abstract_tree,
        is_leaf=_is_axes,
    )


def tree_zero_shardings(axes_tree, abstract_tree, mesh):
    """ZeRO-sharded variant of :func:`tree_shardings` (optimizer states)."""
    return jax.tree.map(
        lambda a, s: NamedSharding(
            mesh, zero_spec(spec_for(a, s.shape, mesh), s.shape, mesh)
        ),
        axes_tree,
        abstract_tree,
        is_leaf=_is_axes,
    )


@dataclasses.dataclass(frozen=True)
class ShardingCtx:
    """Mesh + spec derivation, bundled for the serving stack.

    Inference entry points (``models/lm.py`` ``prefill``/``decode_step``/
    the paged variants and ``serve/engine.py``) take an optional
    ``ShardingCtx`` instead of re-deriving PartitionSpecs at every call
    site: the ctx owns the mesh and turns logical axis names into
    ``NamedSharding``s / ``with_sharding_constraint``s on demand.  Every
    spec degrades to replication when divisibility fails, so a 1-device
    mesh ctx is a behavioral no-op (bit-identical programs) and the same
    serving code lowers on a laptop and a pod slice.
    """

    mesh: object                     # jax.sharding.Mesh

    @property
    def n_devices(self) -> int:
        return int(self.mesh.size)

    # -- spec derivation ------------------------------------------------

    def spec(self, names: tuple, shape: tuple[int, ...]) -> PartitionSpec:
        return spec_for(Axes(tuple(names)), tuple(shape), self.mesh)

    def named(self, names: tuple, shape: tuple[int, ...]) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(names, shape))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, PartitionSpec())

    def rows(self, batch: int) -> NamedSharding:
        """Sharding for a leading-batch host array (tokens, positions)."""
        return NamedSharding(self.mesh, batch_spec(self.mesh, batch))

    def row_axis(self, n: int):
        """The mesh axes ``n`` rows split over (None when nothing divides)."""
        return batch_spec(self.mesh, n)[0]

    def kernel_map(self, fn, in_specs, out_specs):
        """``fn`` run per device under ``shard_map``.  Pallas (Mosaic)
        kernels are opaque to GSPMD, which refuses to partition them, so
        the model wraps every kernel call on a multi-device mesh in one."""
        return jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    # -- constraints (used inside jitted model code) --------------------

    def constrain(self, x, names: tuple):
        """Pin one traced array to its logical-axes spec."""
        return jax.lax.with_sharding_constraint(x, self.named(names, x.shape))

    def constrain_tree(self, tree, axes_tree):
        """Pin a whole tree (caches, params) to its Axes tree's specs —
        the guard that keeps KV updates from silently gathering."""
        return jax.tree.map(
            lambda ax, a: jax.lax.with_sharding_constraint(
                a, NamedSharding(self.mesh, spec_for(ax, a.shape, self.mesh))
            ),
            axes_tree,
            tree,
            is_leaf=_is_axes,
        )

    # -- model-level sharding trees (lazy lm import: no cycle) ----------

    def param_shardings(self, model_cfg, dtype=jnp.float32):
        from repro.models import lm

        return tree_shardings(
            lm.param_axes(model_cfg), lm.abstract_params(model_cfg, dtype),
            self.mesh,
        )

    def place_params(self, model_cfg, params):
        """device_put the parameter tree onto its derived shardings."""
        return shard_tree(params, self.param_shardings(model_cfg))

    def cache_shardings(self, model_cfg, batch: int, max_seq: int,
                        dtype=jnp.bfloat16):
        from repro.models import lm

        return tree_shardings(
            lm.cache_axes(model_cfg),
            lm.abstract_caches(model_cfg, batch, max_seq, dtype),
            self.mesh,
        )

    def paged_cache_shardings(self, model_cfg, n_blocks: int,
                              block_size: int, dtype=jnp.bfloat16):
        from repro.models import lm

        abstract = jax.eval_shape(
            lambda: lm.init_paged_caches(model_cfg, n_blocks, block_size, dtype)
        )
        return tree_shardings(lm.paged_cache_axes(model_cfg), abstract, self.mesh)


def with_sharded_leaves(abstract_tree, sharding_tree):
    """Attach shardings to a ShapeDtypeStruct tree (jit.lower() inputs)."""
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        abstract_tree,
        sharding_tree,
    )


def shard_tree(tree, sharding_tree):
    """device_put every leaf onto its sharding (used by launchers)."""
    return jax.tree.map(jax.device_put, tree, sharding_tree)
