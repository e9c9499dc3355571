"""Faults planted in the timed path, to show that the correctness check
catches them: ``python3 bench/control.py --fault <name>`` on the chip, and
``bench/tests`` on the CPU.  Each is a context manager that patches the
program while engines are built and traced inside it.

* ``token``: every token altered where the engine samples it;
* ``kan_plane``: the KAN FFN contracts each basis plane with the next
  plane's coefficients (both layers), a fault of the fused kernel's band;
* ``kan_zero``: the KAN FFN returns zeros.
"""

from __future__ import annotations

import contextlib


def _patch(obj, name: str, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    return lambda: setattr(obj, name, old)


def _token():
    from repro.serve import speculative

    sample = speculative.sample_tokens

    def shifted(logits, keys, temperature):
        return (sample(logits, keys, temperature) + 1) % logits.shape[-1]

    return _patch(speculative, "sample_tokens", shifted)


def _kan_plane():
    import jax.numpy as jnp
    from repro.models import blocks

    rows = blocks._kan_ffn_rows

    def next_plane(params, xf, grid, method):
        p = dict(params, c1=jnp.roll(params["c1"], 1, axis=1),
                 c2=jnp.roll(params["c2"], 1, axis=1))
        return rows(p, xf, grid, method)

    return _patch(blocks, "_kan_ffn_rows", next_plane)


def _kan_zero():
    import jax.numpy as jnp
    from repro.models import blocks

    rows = blocks._kan_ffn_rows

    def zero(params, xf, grid, method):
        return jnp.zeros_like(rows(params, xf, grid, method))

    return _patch(blocks, "_kan_ffn_rows", zero)


FAULTS = {"token": _token, "kan_plane": _kan_plane, "kan_zero": _kan_zero}


@contextlib.contextmanager
def planted(name: str | None):
    """The fault ``name`` in place for the block's duration (``None``:
    none)."""
    undo = FAULTS[name]() if name else None
    try:
        yield
    finally:
        if undo:
            undo()
