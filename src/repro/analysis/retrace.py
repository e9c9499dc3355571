"""Retrace sentinel: count compilations per (name, abstract signature).

``jax.jit`` silently retraces when an argument's abstract signature — shape,
dtype, pytree structure, or a static value — changes.  The serving engine's
whole performance story rests on *not* doing that mid-serve (PR 3: EOS
sweeps reuse the compiled decode chunk; admission prefill retraces once per
(group size, padded length) bucket).  This module makes those contracts
measurable:

* :func:`counting` wraps a python function *before* it is handed to
  ``jax.jit``.  The wrapper body executes only while jax is tracing (cache
  hits never re-enter python), so each execution is exactly one trace —
  i.e. one compiled program.  ``functools.wraps`` preserves the wrapped
  signature, so ``static_argnums``/``donate_argnums`` on the surrounding
  ``jit`` still resolve against the real parameters.
* :class:`RetraceRegistry` stores per-name signature->count maps and
  exports them as the ``last_serve_stats["compiles"]`` snapshot that the
  retrace regression tests (and ``BENCH_serve.json``) assert on.

The abstract signature is the pytree of ``dtype+shape`` strings for array
leaves (tracers included) and ``repr`` for static python values — the same
distinctions jit's own cache key draws, minus weak-type refinements.
"""

from __future__ import annotations

import functools

import jax


def _abstract_leaf(x) -> str:
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return f"{jax.numpy.dtype(x.dtype).name}{tuple(x.shape)}"
    return repr(x)


def abstract_signature(args: tuple, kwargs: dict) -> str:
    """Stable string key for one call's abstract signature (shapes/dtypes
    for arrays and tracers, ``repr`` for static values; pytree structure is
    part of the key because it is part of jit's)."""
    tree = jax.tree_util.tree_map(_abstract_leaf, (args, kwargs))
    return repr(tree)


def _abstract_arg(x):
    """A traced argument as a ``ShapeDtypeStruct`` (weak type kept); static
    python values pass through."""
    aval = getattr(x, "aval", None)
    if aval is None:
        return x
    return jax.ShapeDtypeStruct(aval.shape, aval.dtype, weak_type=aval.weak_type)


class RetraceRegistry:
    """Per-name trace counters.  One registry per Engine."""

    def __init__(self) -> None:
        self._traces: dict[str, dict[str, int]] = {}
        self._calls: dict[str, dict[str, tuple]] = {}

    def record(self, name: str, signature: str, call: tuple | None = None) -> None:
        sigs = self._traces.setdefault(name, {})
        sigs[signature] = sigs.get(signature, 0) + 1
        if call is not None:
            self._calls.setdefault(name, {})[signature] = call

    def calls(self, name: str) -> list[tuple]:
        """``(args, kwargs)`` per program traced under ``name``, arrays as
        ``ShapeDtypeStruct`` — enough to ``lower`` the program again."""
        return list(self._calls.get(name, {}).values())

    def programs(self, name: str) -> int:
        """Distinct abstract signatures traced under ``name`` — the number
        of compiled programs jit holds for it."""
        return len(self._traces.get(name, {}))

    def traces(self, name: str) -> int:
        """Total trace events under ``name`` (== programs unless something
        defeats jit's cache, e.g. a fresh wrapper per call)."""
        return sum(self._traces.get(name, {}).values())

    def snapshot(self) -> dict:
        """JSON-ready export: name -> {programs, traces, signatures}."""
        return {
            name: {
                "programs": len(sigs),
                "traces": sum(sigs.values()),
                "signatures": sorted(sigs),
            }
            for name, sigs in sorted(self._traces.items())
        }


def counting(fn, name: str, registry: RetraceRegistry):
    """Wrap ``fn`` so every *trace* (not every call) is recorded.

    Use as ``jax.jit(counting(fn, "decode_chunk", reg), ...)`` — the wrapper
    must sit INSIDE the jit: jit re-enters python only on cache miss, so the
    record call fires exactly once per compiled program.
    """

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        registry.record(
            name, abstract_signature(args, kwargs),
            jax.tree_util.tree_map(_abstract_arg, (args, kwargs)),
        )
        return fn(*args, **kwargs)

    return wrapped
