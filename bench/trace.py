"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device
numbers: busy and idle time over the traced window, the device time of
each operation, and the longest idle gaps with the host span the
benchmark was in when each began.

The window is the benchmark's own host span ``bench.window``.  Device
operations are the events of the ``XLA Ops`` line of each TPU plane;
busy time is the union of their intervals inside the window, averaged
over the chips.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
# ops that only contain other ops (a scan's loop): counting them as busy
# would hide every gap inside the loop
CONTAINER_OPS = ("while", "conditional", "call")
MIN_GAP_NS = 1000


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float  # averaged over chips
    chips: int
    op_seconds: Dict[str, float]  # device time per op name, summed over chips
    op_counts: Dict[str, int]  # executions per op name in the window
    idle_gaps: List[Tuple[str, float]]  # longest first

    def top_ops(self, k: int = 10) -> List[Tuple[str, float]]:
        return sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:k]


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:") and name[len("/device:TPU:"):].isdigit()


def union_seconds(intervals: Iterable[Tuple[float, float]],
                  lo: float, hi: float) -> Tuple[float, List[Tuple[float, float]]]:
    """Length of the union of ``intervals`` clipped to ``[lo, hi]`` (same
    unit as the inputs) and the merged intervals."""
    clipped = sorted(
        (max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi
    )
    merged: List[List[float]] = []
    for a, b in clipped:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), [(a, b) for a, b in merged]


def opcode(name: str) -> str:
    """The HLO opcode of an ``XLA Ops`` event (``%fusion.3 = f32[8] fusion(...)``)."""
    m = re.search(r" ([a-z][a-z0-9\-]*)\(", name)
    return m.group(1) if m else ""


def short_name(name: str, width: int = 160) -> str:
    """An op's HLO text without layouts and callee names, cut to ``width``."""
    s = re.sub(r"\{[^{}]*\}", "", name)
    s = re.sub(r", calls=\S+", "", s)
    return s[:width]


def _span_at(spans: List[Tuple[float, float, str]], t: float) -> str:
    """Innermost benchmark span open at ``t`` (the shortest covering it)."""
    best: Optional[Tuple[float, str]] = None
    for a, b, name in spans:
        if a <= t < b and name != WINDOW_SPAN:
            if best is None or b - a < best[0]:
                best = (b - a, name)
    return best[1] if best else "outside spans"


def reduce_events(
    device: Dict[str, List[Tuple[float, float, str]]],
    spans: List[Tuple[float, float, str]],
    n_gaps: int = 10,
) -> TraceSummary:
    """The reduction on plain events (times in ns): ``device`` maps each
    chip's plane to its op events ``(start, end, name)``; ``spans``
    are the host spans ``(start, end, name)`` with one ``bench.window``.
    Container ops (:data:`CONTAINER_OPS`) are left out; each idle gap is
    named by the innermost span open at its midpoint."""
    windows = [(a, b) for a, b, name in spans if name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(windows)}")
    lo, hi = windows[0]
    busy_total, op_seconds, op_counts = 0.0, {}, {}
    gaps: List[Tuple[float, float]] = []
    for events in device.values():
        events = [e for e in events if opcode(e[2]) not in CONTAINER_OPS]
        busy, merged = union_seconds(((a, b) for a, b, _ in events), lo, hi)
        busy_total += busy
        for a, b, name in events:
            d = min(b, hi) - max(a, lo)
            if d > 0:
                op_seconds[name] = op_seconds.get(name, 0.0) + d * 1e-9
                op_counts[name] = op_counts.get(name, 0) + 1
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        gaps.extend(zip(edges[0::2], edges[1::2]))
    # gaps under MIN_GAP_NS between back-to-back ops are not idle time worth naming
    gaps = sorted((g for g in gaps if g[1] - g[0] >= MIN_GAP_NS),
                  key=lambda g: g[0] - g[1])
    chips = max(len(device), 1)
    return TraceSummary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_total * 1e-9 / chips,
        chips=len(device),
        op_seconds=op_seconds,
        op_counts=op_counts,
        idle_gaps=[(_span_at(spans, (a + b) / 2), (b - a) * 1e-9)
                   for a, b in gaps[:n_gaps]],
    )


def read_xplane(path: str):
    """``(device, spans)`` of :func:`reduce_events` from an xplane file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device: Dict[str, list] = {}
    spans: List[Tuple[float, float, str]] = []
    for plane in pd.planes:
        if _is_device_plane(plane.name):
            evs = device.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    evs.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                        )
    return device, spans


def reduce_xplane(path: str, n_gaps: int = 10) -> TraceSummary:
    device, spans = read_xplane(path)
    if not device:
        raise ValueError(f"no TPU device plane with an {OPS_LINE!r} line in {path}")
    return reduce_events(device, spans, n_gaps)
