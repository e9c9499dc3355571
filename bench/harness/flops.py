"""Operations and bytes the work needs, from the configuration's shapes.

``model_flops`` counts a decoder's forward pass per token position: the
attention projections, attention itself over the position's context, the
FFN (from the configuration's reference, ``ffn_flops``), and the output
head once per generated token.  It is the yardstick of ``mfu``; it counts
what the work needs, not what an implementation happens to compute, so
padding and recomputed prefixes are not in it.
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


def model_flops(cfg: dict, ffn_flops, positions: int, context_sum: int,
                head_tokens: int) -> float:
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    H, KV, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    per_pos = L * (2 * d * (H + 2 * KV) * hd + 2 * H * hd * d + ffn_flops(cfg))
    attn = L * 4 * H * hd * context_sum
    return float(positions * per_pos + attn + head_tokens * 2 * d * cfg["vocab_size"])
