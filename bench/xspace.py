"""What the program itself writes into a profiler trace, read from the
``.xplane.pb``:

* the name stack of every device op, which ``jax.named_scope`` fills: the
  ``tf_op`` stat of the op's event metadata
  (``jit(_run)/while/body/closed_call/snn.deliver/d3/jit(_take)/gather``);
* the program's host spans (``snn.*``) on every thread, with their counts
  (``bytes``).

``jax.profiler.ProfileData`` gives only each event's own stats, not those
of its metadata, and ``xplane_pb2`` ships with TensorFlow alone, so this
module decodes the protobuf wire format of the XSpace messages itself
(``tsl/profiler/protobuf/xplane.proto``; only the fields below).

    python3 bench/xspace.py <trace dir or .xplane.pb> [--steps N]

prints the span table: per ``snn.*`` span its count, total, self time
and bytes, and the device time per ``snn.*`` scope with the share of busy
time no scope names (``unscoped``).  Times are those of the window span
``bench.window`` where the trace has one, else of the whole trace.
"""
from __future__ import annotations

import argparse
import dataclasses
import glob
import os
import struct
import sys
from typing import Dict, Iterator, List, Optional, Tuple

if __package__ in (None, ""):  # run as a script: python3 bench/xspace.py
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace  # noqa: E402

PROGRAM_PREFIX = "snn."
UNSCOPED = "unscoped"
TF_OP = "tf_op"

# field numbers of xplane.proto
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_LINES, _PLANE_EVENT_MD, _PLANE_STAT_MD = 2, 3, 4, 5
_LINE_NAME, _LINE_TIMESTAMP_NS, _LINE_EVENTS = 2, 3, 4
_EVENT_MD_ID, _EVENT_OFFSET_PS, _EVENT_DURATION_PS, _EVENT_STATS = 1, 2, 3, 4
_MD_ID, _MD_NAME, _MD_STATS = 1, 2, 5
_STAT_MD_ID, _STAT_DOUBLE, _STAT_UINT64, _STAT_INT64 = 1, 2, 3, 4
_STAT_STR, _STAT_BYTES, _STAT_REF = 5, 6, 7
_MAP_VALUE = 2  # of a map entry; the key is 1


# -- the wire format ----------------------------------------------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, i
        shift += 7


def fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of one message: an int for varint and
    fixed-width fields, a ``memoryview`` for length-delimited ones."""
    mv = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = mv[i:i + size], i + size
        elif wire == 1:
            value, i = struct.unpack_from("<Q", buf, i)[0], i + 8
        elif wire == 5:
            value, i = struct.unpack_from("<I", buf, i)[0], i + 4
        else:
            raise ValueError(f"unsupported wire type {wire} (field {num})")
        yield num, value


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf, stat_names: Dict[int, str]) -> Tuple[str, object]:
    name, value = "", None
    for num, v in fields(buf):
        if num == _STAT_MD_ID:
            name = stat_names.get(v, "")
        elif num == _STAT_DOUBLE:
            value = struct.unpack("<d", struct.pack("<Q", v))[0]
        elif num == _STAT_UINT64:
            value = v
        elif num == _STAT_INT64:
            value = _signed(v)
        elif num in (_STAT_STR, _STAT_BYTES):
            value = bytes(v).decode("utf-8", "replace")
        elif num == _STAT_REF:
            value = stat_names.get(v, "")
    return name, value


@dataclasses.dataclass
class Event:
    start_ns: int
    end_ns: int
    name: str
    stats: Dict[str, object]


@dataclasses.dataclass
class Line:
    name: str
    events: List[Event]


@dataclasses.dataclass
class Plane:
    name: str
    lines: List[Line]


def _map_values(buf) -> Iterator[memoryview]:
    for num, v in fields(buf):
        if num == _MAP_VALUE:
            yield v


def _plane(buf, keep) -> Plane:
    name, raw_lines, raw_md, stat_names = "", [], [], {}
    for num, v in fields(buf):
        if num == _PLANE_NAME:
            name = bytes(v).decode()
        elif num == _PLANE_LINES:
            raw_lines.append(v)
        elif num == _PLANE_EVENT_MD:
            raw_md.append(v)
        elif num == _PLANE_STAT_MD:
            for md in _map_values(v):
                f = dict(fields(md))
                stat_names[f.get(_STAT_MD_ID, 0)] = bytes(
                    f.get(_MD_NAME, b"")).decode()
    metadata: Dict[int, Tuple[str, Dict[str, object]]] = {}
    for entry in raw_md:
        for md in _map_values(entry):
            mid, mname, mstats = 0, "", {}
            for f, x in fields(md):
                if f == _MD_ID:
                    mid = x
                elif f == _MD_NAME:
                    mname = bytes(x).decode("utf-8", "replace")
                elif f == _MD_STATS:
                    k, value = _stat(x, stat_names)
                    mstats[k] = value
            metadata[mid] = (mname, mstats)
    lines = []
    for raw in raw_lines:
        f, raw_events = {}, []
        for num, x in fields(raw):
            if num == _LINE_EVENTS:
                raw_events.append(x)
            else:
                f[num] = x
        lname = bytes(f.get(_LINE_NAME, b"")).decode()
        ts_ns = _signed(f.get(_LINE_TIMESTAMP_NS, 0))
        wanted = {mid for mid, (mname, _) in metadata.items()
                  if keep(name, lname, mname)}
        events = []
        for raw_ev in raw_events:
            # the metadata id comes first: skip unwanted events unparsed
            if raw_ev[0] == _EVENT_MD_ID << 3:
                mid, _ = _varint(raw_ev, 1)
                if mid not in wanted:
                    continue
            mid = offset_ps = duration_ps = 0
            stats = {}
            for num, x in fields(raw_ev):
                if num == _EVENT_MD_ID:
                    mid = x
                elif num == _EVENT_OFFSET_PS:
                    offset_ps = _signed(x)
                elif num == _EVENT_DURATION_PS:
                    duration_ps = _signed(x)
                elif num == _EVENT_STATS:
                    k, value = _stat(x, stat_names)
                    stats[k] = value
            if mid not in wanted:
                continue
            ename, md_stats = metadata[mid]
            # whole ns, as jax.profiler.ProfileData reads them
            start = ts_ns + offset_ps // 1000
            events.append(Event(start, start + duration_ps // 1000, ename,
                                {**md_stats, **stats}))
        lines.append(Line(lname, events))
    return Plane(name, lines)


def read_planes(path: str,
                keep=lambda plane, line, event: True) -> List[Plane]:
    """The planes of an ``.xplane.pb``, holding the events that
    ``keep(plane name, line name, event name)`` accepts.  An event's
    ``stats`` are those of its metadata (a device op's ``tf_op``) and its
    own (a span's ``bytes``)."""
    with open(path, "rb") as f:
        buf = f.read()
    return [_plane(v, keep) for num, v in fields(buf) if num == _SPACE_PLANES]


# -- the reduction ------------------------------------------------------------

def top_scope(name_stack: str) -> str:
    """The outermost ``snn.*`` component of an op's name stack, else
    :data:`UNSCOPED`."""
    for part in name_stack.split("/"):
        if part.startswith(PROGRAM_PREFIX):
            return part
    return UNSCOPED


@dataclasses.dataclass
class Span:
    thread: tuple  # (plane name, line index): each thread has a line
    start_ns: float
    end_ns: float
    name: str
    stats: Dict[str, object] = dataclasses.field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


@dataclasses.dataclass
class ProgramTrace:
    summary: trace.TraceSummary  # busy time, ops, idle gaps
    scope_seconds: Dict[str, float]  # device s per top-level scope
    spans: List[Span]  # the host spans that overlap the window

    @property
    def program_spans(self) -> Dict[str, List[float]]:
        """Seconds of each ``snn.*`` span, by name, in start order."""
        out: Dict[str, List[float]] = {}
        for s in sorted(self.spans, key=lambda s: s.start_ns):
            if s.name.startswith(PROGRAM_PREFIX):
                out.setdefault(s.name, []).append(s.seconds)
        return out


def scope_seconds(device: Dict[str, List[tuple]], lo: float,
                  hi: float) -> Dict[str, float]:
    """Device seconds per top-level scope: on each chip the union of the
    scope's op intervals in ``[lo, hi]`` (ns), averaged over the chips as
    busy time is.  ``device`` maps each chip to its ops ``(start, end, hlo
    text, name stack)``; container ops are left out."""
    out: Dict[str, float] = {}
    for events in device.values():
        by_scope: Dict[str, list] = {}
        for a, b, hlo, stack in events:
            if trace.opcode(hlo) not in trace.CONTAINER_OPS:
                by_scope.setdefault(top_scope(stack), []).append((a, b))
        for scope, intervals in by_scope.items():
            secs, _ = trace.union_seconds(intervals, lo, hi)
            out[scope] = out.get(scope, 0.0) + secs * 1e-9
    chips = max(len(device), 1)
    return {k: v / chips for k, v in out.items()}


def reduce_events(device: Dict[str, List[tuple]], spans: List[Span],
                  n_gaps: int = 10) -> ProgramTrace:
    """The reduction on plain events (ns): ``device`` maps each chip to its
    ops ``(start, end, hlo text, name stack)``, ``spans`` are the host
    spans of both prefixes with at most one ``bench.window``.

    Busy time, op times and idle gaps are ``trace.reduce_events``'s over
    the spans of the window's thread, so each gap is named by the
    innermost span of either prefix that the run loop was in; on a trace
    without ``snn.*`` spans every number is the same.  Without a window
    the whole trace is the window and the run loop is the thread of the
    first ``snn.chunk``."""
    windows = [s for s in spans if s.name == trace.WINDOW_SPAN]
    if len(windows) > 1:
        raise ValueError(f"expected one {trace.WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    if windows:
        lo, hi, loop = windows[0].start_ns, windows[0].end_ns, \
            windows[0].thread
    else:
        times = [t for evs in device.values() for e in evs for t in e[:2]]
        times += [t for s in spans for t in (s.start_ns, s.end_ns)]
        if not times:
            raise ValueError("the trace holds no device op and no span")
        lo, hi = min(times), max(times)
        chunks = [s for s in spans if s.name == "snn.chunk"]
        loop = min(chunks, key=lambda s: s.start_ns).thread if chunks \
            else None
        spans = spans + [Span(loop, lo, hi, trace.WINDOW_SPAN)]
    summary = trace.reduce_events(
        {chip: [e[:3] for e in evs] for chip, evs in device.items()},
        [(s.start_ns, s.end_ns, s.name) for s in spans
         if loop is None or s.thread == loop],
        n_gaps,
    )
    return ProgramTrace(
        summary=summary,
        scope_seconds=scope_seconds(device, lo, hi),
        spans=[s for s in spans if s.end_ns > lo and s.start_ns < hi
               and s.name != trace.WINDOW_SPAN],
    )


def _is_span(name: str) -> bool:
    return name.startswith((PROGRAM_PREFIX, trace.SPAN_PREFIX))


def read_xplane(path: str):
    """``(device, spans)`` of :func:`reduce_events` from an xplane file:
    the ``XLA Ops`` of each TPU plane with their name stacks, and every
    host span of either prefix with its stats."""
    def keep(plane, line, event):
        if plane.startswith("/host:"):
            return _is_span(event)
        return trace._is_device_plane(plane) and line == trace.OPS_LINE

    device: Dict[str, list] = {}
    spans: List[Span] = []
    for plane in read_planes(path, keep):
        if trace._is_device_plane(plane.name):
            evs = device.setdefault(plane.name, [])
            for line in plane.lines:
                # tf_op reads "<name stack>:<op type>", the type empty
                evs.extend((e.start_ns, e.end_ns, e.name,
                            str(e.stats.get(TF_OP, "")).rsplit(":", 1)[0])
                           for e in line.events)
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                spans.extend(Span((plane.name, i), e.start_ns, e.end_ns,
                                  e.name, e.stats) for e in line.events)
    return device, spans


def reduce_xplane(path: str, n_gaps: int = 10) -> ProgramTrace:
    device, spans = read_xplane(path)
    return reduce_events(device, spans, n_gaps)


# -- the span table -----------------------------------------------------------

def span_rows(spans: List[Span]) -> List[Tuple[str, int, float, float, int]]:
    """Per ``snn.*`` name: count, total s, self s (less the spans nested
    in it on its thread) and the sum of its ``bytes``."""
    rows: Dict[str, list] = {}
    for s in spans:
        if not s.name.startswith(PROGRAM_PREFIX):
            continue
        inner, _ = trace.union_seconds(
            [(c.start_ns, c.end_ns) for c in spans
             if c is not s and c.thread == s.thread
             and s.start_ns <= c.start_ns and c.end_ns <= s.end_ns],
            s.start_ns, s.end_ns)
        row = rows.setdefault(s.name, [0, 0.0, 0.0, 0])
        row[0] += 1
        row[1] += s.seconds
        row[2] += s.seconds - inner * 1e-9
        row[3] += int(s.stats.get("bytes", 0) or 0)
    return [(name, *row) for name, row in sorted(rows.items())]


def table(pt: ProgramTrace, steps: Optional[int] = None) -> str:
    """The span table and the device time per scope, as text."""
    out = [f"{'span':<22}{'count':>7}{'total s':>13}{'self s':>13}"
           f"{'bytes':>16}"]
    for name, count, total, own, nbytes in span_rows(pt.spans):
        out.append(f"{name:<22}{count:>7}{total:>13.6f}{own:>13.6f}"
                   f"{nbytes:>16}")
    busy = pt.summary.busy_s
    out.append(f"{'scope':<22}{'device s':>13}{'ms/step':>13}"
               f"{'of busy':>10}")
    for name, secs in sorted(pt.scope_seconds.items()):
        per_step = f"{1e3 * secs / steps:.6f}" if steps else "-"
        share = f"{100 * secs / busy:.4f}%" if busy > 0 else "-"
        out.append(f"{name:<22}{secs:>13.6f}{per_step:>13}{share:>10}")
    out.append(f"device busy {busy:.6f} s of {pt.summary.window_s:.6f} s "
               f"on {pt.summary.chips} chip(s)")
    return "\n".join(out)


def find_xplane(path: str) -> str:
    """``path`` itself, or the newest ``.xplane.pb`` under the directory."""
    if os.path.isfile(path):
        return path
    found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return max(found, key=os.path.getmtime)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", help="a trace directory or an .xplane.pb")
    ap.add_argument("--steps", type=int, default=None,
                    help="simulation steps in the window, for ms per step")
    args = ap.parse_args(argv)
    print(table(reduce_xplane(find_xplane(args.path)), args.steps))
    return 0


if __name__ == "__main__":
    sys.exit(main())
