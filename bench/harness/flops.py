"""Operations and bytes the work needs, from the configuration's shapes.

``model_flops`` counts a decoder's forward pass over a request's token
positions: its layers, as the configuration's reference counts them
(``layer_flops`` in ``<config>.ref.py``: per position and per attended
context position, summed over the layers), and the output head once per
generated token.  It is the yardstick of ``mfu``; it counts what the work
needs, not what an implementation happens to compute, so padding and
recomputed prefixes are not in it.
"""

from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "peaks.json")


def peaks(device_kind: str) -> dict:
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device {device_kind!r} in {PEAKS}")
    return table[device_kind]


def request_positions(prompt_len: int, cached: int, n_out: int) -> tuple[int, int]:
    """``(positions, sum of their contexts)`` a request runs through the
    model: the prompt positions after its cached prefix, then one position
    per generated token but the last (whose input is never fed back).  A
    position ``p`` attends to ``p + 1`` keys."""
    first, last = cached, prompt_len + n_out - 2          # inclusive
    n = last - first + 1
    return n, (first + 1 + last + 1) * n // 2


def model_flops(cfg: dict, ref, positions: int, context_sum: int,
                head_tokens: int) -> float:
    """``ref``: the configuration's reference module."""
    per_pos, per_ctx = ref.layer_flops(cfg)
    head = 2 * cfg["hidden_size"] * cfg["vocab_size"]
    return float(positions * per_pos + per_ctx * context_sum + head_tokens * head)
