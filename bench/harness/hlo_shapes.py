"""Shapes of an op as the device trace records it.

A device op event is named by its HLO instruction: ``%kan_fused_gemm_
pallas.16 = bf16[8192,1024]{1,0:T(8,128)(2,1)} custom-call(bf16[8192,512]
{...} %x, f32[512,8,1024]{...} %c, f32[512,1024]{...} %w),
custom_call_target="tpu_custom_call", ...``.  Up to the attributes, the
first array type is the result and the rest are the operands in order.
"""

from __future__ import annotations

import math
import re

ARRAY = re.compile(r"\b(pred|bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32|s64|u64|f8e4m3fn|f8e5m2)\[([0-9,]*)\]")
ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2,
            "f16": 2, "s16": 2, "u16": 2, "f32": 4, "s32": 4, "u32": 4,
            "f64": 8, "s64": 8, "u64": 8}


def arrays(text: str) -> list[tuple[str, tuple[int, ...]]]:
    return [(t, tuple(int(d) for d in dims.split(",") if d))
            for t, dims in ARRAY.findall(text)]


def nbytes(a: tuple[str, tuple[int, ...]]) -> int:
    return ITEMSIZE[a[0]] * math.prod(a[1])


def call_arrays(event) -> list[tuple[str, tuple[int, ...]]]:
    """``[result, operand, ...]`` of a Pallas kernel's custom call; empty
    for any other op."""
    text = event.name
    if 'custom_call_target="tpu_custom_call"' not in text:
        return []
    return arrays(text.split(", custom_call_target=", 1)[0])


def roofline_share(events, least_time) -> float | None:
    """``100 · Σ least_time(event) / Σ duration`` over the events whose
    shapes can be read; None if none can."""
    num = den = 0.0
    for e in events:
        if not e.dur_ns:
            continue
        t = least_time(call_arrays(e))
        if t is None:
            continue
        num += t
        den += e.dur_ns / 1e9
    return None if not den else 100.0 * num / den
