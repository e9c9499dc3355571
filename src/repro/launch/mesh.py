"""Mesh construction.

FUNCTIONS, not module-level constants — importing this module never
touches jax device state.  Every mesh in the repo (launchers, serving,
tests) comes from :func:`auto_mesh`, whose axes are ``AxisType.Auto``:
``jax.make_mesh`` without ``axis_types`` gives ``Explicit`` axes, under
which ``with_sharding_constraint`` (``dist/sharding.py: ShardingCtx``) and
GSPMD-propagated reshapes refuse to run."""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...], devices=None) -> Mesh:
    """A mesh with ``Auto`` axes over ``devices`` (default: this process's
    devices via ``jax.make_mesh``).  Pass ``devices`` to build a mesh from a
    described topology (``jax.experimental.topologies``) for AOT compiles."""
    types = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(tuple(shape), tuple(axes), axis_types=types)
    devs = np.asarray(devices)[: int(np.prod(shape))].reshape(shape)
    return Mesh(devs, tuple(axes), axis_types=types)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod (TPU v5e pod slice); 2x16x16 = 512 multi-pod.

    Axis roles (DESIGN.md §4): ``pod`` = data-parallel across pods (slow
    inter-pod links carry only gradient all-reduces / batch splits),
    ``data`` = in-pod DP + KV-cache seq sharding, ``model`` = TP/EP.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(shape: tuple[int, int] | None = None):
    """Host ``(data, model)`` mesh for CPU smoke/examples/serving.

    Default (``shape=None``) keeps the historical behavior: every host
    device lands on ``model`` (``(1, n)``).  That forced shape made data
    parallelism impossible on a host mesh — pass ``shape=(data, model)``
    to choose the split (e.g. ``(2, 4)`` on a forced-8-device host).  The
    requested mesh may use a subset of the host's devices, but its size
    must divide the device count (no stranded remainder)."""
    n = len(jax.devices())
    if shape is None:
        return auto_mesh((1, n), ("data", "model"))
    d, m = int(shape[0]), int(shape[1])
    if d < 1 or m < 1:
        raise ValueError(f"mesh shape must be positive, got {(d, m)}")
    if d * m > n or n % (d * m):
        raise ValueError(
            f"host mesh {d}x{m} needs {d * m} devices but the host offers "
            f"{n} ({'too few' if d * m > n else 'not divisible'}); force "
            f"more with XLA_FLAGS=--xla_force_host_platform_device_count=N"
        )
    return auto_mesh((d, m), ("data", "model"))


def parse_mesh_shape(s: str) -> tuple[int, int]:
    """``"2x4"`` -> ``(2, 4)`` — the ``--mesh dxm`` CLI flag format."""
    parts = s.lower().split("x")
    if len(parts) != 2:
        raise ValueError(f"--mesh expects DxM (e.g. 2x4), got {s!r}")
    try:
        d, m = int(parts[0]), int(parts[1])
    except ValueError:
        raise ValueError(f"--mesh expects integers DxM, got {s!r}") from None
    return d, m
