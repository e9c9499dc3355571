"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics and the ``breakdown`` read.

* busy time: the union of the intervals in which an operation runs on a
  device, per device, averaged over the devices used;
* the traced window: the harness's own span around the traced work;
* device op events, each named by its HLO instruction (shapes included);
* the longest idle gaps inside the window, each named by the innermost
  host span that covers its midpoint.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
# ``%name = <type> opcode(``: the opcode follows the result type, which
# ends in a layout ``}`` (or ``]`` / ``)`` without one) and a space
OPCODE = re.compile(r"[\]})] ([a-z][a-z0-9-]*)\(")
# ops that contain other ops: their time is their children's
CONTAINERS = {"while", "conditional", "call"}
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.traced"


@dataclasses.dataclass
class Event:
    name: str           # a device op's is its HLO instruction, shapes and all
    start_ns: float
    dur_ns: float

    @property
    def op(self) -> str:
        """A device op's instruction name (``kan_fused_gemm_pallas.16``)."""
        return self.name.split(" = ", 1)[0].lstrip("%")

    @property
    def opcode(self) -> str:
        """A device op's opcode (``custom-call``, ``fusion``, ``while``)."""
        m = OPCODE.search(self.name)
        return m.group(1) if m else ""


@dataclasses.dataclass
class Trace:
    window: tuple[float, float]          # ns, on the trace's clock
    device_ops: dict[int, list[Event]]   # device id -> op events in the window
    host_spans: list[Event]              # host events, every thread

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_intervals(self, dev: int) -> list[tuple[float, float]]:
        """The union of the op intervals of device ``dev``, clipped to the
        window, as sorted disjoint ``(start, end)`` pairs."""
        lo, hi = self.window
        iv = sorted((max(e.start_ns, lo), min(e.start_ns + e.dur_ns, hi))
                    for e in self.device_ops[dev])
        out: list[list[float]] = []
        for s, t in iv:
            if t <= s:
                continue
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], t)
            else:
                out.append([s, t])
        return [tuple(x) for x in out]

    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices that ran an op."""
        devs = [d for d, evs in self.device_ops.items() if evs]
        if not devs:
            return 0.0
        return sum(sum(t - s for s, t in self.busy_intervals(d))
                   for d in devs) / len(devs) / 1e9

    def ops(self, pred) -> list[Event]:
        """Every device op event, over all devices, for which ``pred``."""
        return [e for evs in self.device_ops.values() for e in evs if pred(e)]

    def top_ops(self, n: int = 10) -> list[list]:
        """``[[op, seconds], ...]``: device ops by summed time over all
        devices, largest first, named by instruction and result type; loops
        and calls, whose time is that of the ops inside them, left out."""
        tot: dict[str, float] = {}
        for e in self.ops(lambda e: e.opcode not in CONTAINERS):
            head = e.name.split("{", 1)[0]
            tot[head] = tot.get(head, 0.0) + e.dur_ns / 1e9
        return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def host_span_at(self, t: float) -> str:
        """The innermost (shortest) host event covering time ``t``."""
        best = None
        for e in self.host_spans:
            if e.start_ns <= t <= e.start_ns + e.dur_ns:
                if best is None or e.dur_ns < best.dur_ns:
                    best = e
        return best.name if best is not None else "(no host span)"

    def idle_gaps(self, n: int = 10) -> list[list]:
        """``[[host span, seconds], ...]``: the ``n`` longest stretches of the
        window in which the first device ran no op, longest first."""
        devs = sorted(d for d, evs in self.device_ops.items() if evs)
        if not devs:
            return []
        lo, hi = self.window
        gaps, t = [], lo
        for s, e in self.busy_intervals(devs[0]) + [(hi, hi)]:
            if s > t:
                gaps.append((s - t, t))
            t = max(t, e)
        gaps.sort(reverse=True)
        return [[self.host_span_at(t0 + g / 2), g / 1e9] for g, t0 in gaps[:n]]


def find_xplane(logdir: str) -> str:
    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return files[-1]


def load(path: str, window_span: str = WINDOW_SPAN) -> Trace:
    """Read the ``.xplane.pb`` at ``path`` (see :func:`reduce`)."""
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(path), window_span)


def reduce(pd, window_span: str = WINDOW_SPAN) -> Trace:
    """The device op events and host events of profile ``pd`` inside the
    window: the harness's ``window_span`` host event (the first one), else
    the extent of all device ops."""
    ops: dict[int, list[Event]] = {}
    host: list[Event] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = int(plane.name[len(DEVICE_PREFIX):].split()[0])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.setdefault(dev, []).extend(
                        Event(e.name, e.start_ns, e.duration_ns)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns, e.duration_ns)
                            for e in line.events)
    spans = [e for e in host if e.name == window_span]
    if spans:
        w = (spans[0].start_ns, spans[0].start_ns + spans[0].dur_ns)
    else:
        evs = [e for v in ops.values() for e in v]
        w = (min((e.start_ns for e in evs), default=0.0),
             max((e.start_ns + e.dur_ns for e in evs), default=0.0))
    lo, hi = w
    ops = {d: [e for e in v if e.start_ns < hi and e.start_ns + e.dur_ns > lo]
           for d, v in ops.items()}
    host = [e for e in host if e.start_ns < hi and e.start_ns + e.dur_ns > lo]
    return Trace(w, ops, host)
