"""From a ``jax.profiler`` trace to numbers: device-busy union, device time
inside a host span, exposed collective time, kernel sums, idle gaps by host
span.

A trace is reduced in two stages.  ``Trace.from_xplane`` reads the
``.xplane.pb`` with nothing but jax (``jax.profiler.ProfileData``) and keeps
what the metrics need: per chip the device's operation events (the ``XLA Ops``
line of each ``/device:TPU:<i>`` plane; an event there is named by the whole
text of its HLO instruction, of which the instruction's name is kept as
``name`` and the head of the rest as ``what``) and the benchmark's own host
spans (``bench.*``, written with ``jax.profiler.TraceAnnotation``), all on
the profiler's one clock, in nanoseconds.  Everything after that is arithmetic
on intervals, checked by ``selftest/test_trace_reduce.py`` on a trimmed trace
recorded on the chip (``selftest/trace_v5e.json``).

Device operations nest on the ops line (a ``while`` encloses its body), so
sums by name use self time, and "another operation runs" means a leaf event.
"""

from __future__ import annotations

import json
import re
from dataclasses import dataclass

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


@dataclass(frozen=True)
class Event:
    name: str      # a device operation's HLO instruction name, or a span's
    start: float   # ns on the profiler's clock
    end: float
    what: str = ""  # a device operation: result type and opcode, cut short

    @property
    def duration(self) -> float:
        return self.end - self.start


# --- interval arithmetic ---------------------------------------------------

def union(intervals) -> list:
    """Merge ``(start, end)`` pairs into disjoint sorted intervals."""
    merged = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [tuple(m) for m in merged]


def length(intervals) -> float:
    return sum(end - start for start, end in intervals)


def clip(intervals, t0: float, t1: float) -> list:
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if min(e, t1) > max(s, t0)]


def subtract(a, b) -> list:
    """The part of the disjoint sorted intervals ``a`` not covered by the
    disjoint sorted intervals ``b``."""
    out, j = [], 0
    for start, end in a:
        cursor = start
        while j < len(b) and b[j][1] <= cursor:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cursor:
                out.append((cursor, b[k][0]))
            cursor = max(cursor, b[k][1])
            k += 1
        if cursor < end:
            out.append((cursor, end))
    return out


def pairs(events) -> list:
    return [(e.start, e.end) for e in events]


# --- events ----------------------------------------------------------------

def within(events, t0: float, t1: float) -> list:
    """Events that start inside ``[t0, t1)``."""
    return [e for e in events if t0 <= e.start < t1]


def leaves(events) -> list:
    """Events that enclose no other event, sorted by start (events on one
    line are nested or disjoint, so the next one in order decides)."""
    ordered = sorted(events, key=lambda e: (e.start, -e.end))
    return [e for e, nxt in zip(ordered, ordered[1:] + [None])
            if nxt is None or nxt.start >= e.end]


def self_times(events) -> list:
    """``(event, self nanoseconds)``: an event's duration less the part its
    nested events cover."""
    ordered = sorted(events, key=lambda e: (e.start, -e.end))
    children = {id(e): [] for e in ordered}
    stack = []
    for e in ordered:
        while stack and stack[-1].end <= e.start:
            stack.pop()
        if stack and e.end <= stack[-1].end:
            children[id(stack[-1])].append((e.start, e.end))
        stack.append(e)
    return [(e, e.duration - length(union(children[id(e)])))
            for e in ordered]


def busy(ops, t0: float, t1: float) -> float:
    """Nanoseconds of ``[t0, t1)`` in which some operation runs."""
    return length(clip(union(pairs(ops)), t0, t1))


def top_by_name(ops, k: int = 10) -> list:
    """``[name: what, seconds]`` of the ``k`` instructions with most self
    time."""
    total, what = {}, {}
    for e, t in self_times(ops):
        total[e.name] = total.get(e.name, 0.0) + t
        what[e.name] = e.what
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[f"{name}: {what[name]}" if what[name] else name, t * 1e-9]
            for name, t in ranked]


def _opcode(e: Event) -> str:
    """``collective-permute-start`` of ``collective-permute-start.3``."""
    return e.name.split(".")[0]


def async_intervals(ops, kind: str) -> list:
    """Intervals of the collective ``kind``: each ``<kind>-done`` paired with
    the earliest ``<kind>-start`` still open, from the start's begin to the
    done's end; a synchronous ``<kind>`` event, and a start or a done whose
    partner the trace lacks, counts as it is."""
    out = pairs(e for e in ops if _opcode(e) == kind)
    open_starts = []
    for e in sorted(ops, key=lambda e: e.start):
        if _opcode(e) == kind + "-start":
            open_starts.append(e)
        elif _opcode(e) == kind + "-done":
            first = open_starts.pop(0) if open_starts else e
            out.append((first.start, e.end))
    return out + pairs(open_starts)


def exposed(ops, kind: str) -> float:
    """Nanoseconds of the collective ``kind``'s intervals during which no
    other leaf operation runs on the chip."""
    own = {kind, kind + "-start", kind + "-done"}
    others = union(pairs(e for e in leaves(ops) if _opcode(e) not in own))
    return length(subtract(union(async_intervals(ops, kind)), others))


def idle_gaps(ops, t0: float, t1: float) -> list:
    """The intervals of ``[t0, t1)`` in which no operation runs."""
    return subtract([(t0, t1)], clip(union(pairs(ops)), t0, t1))


def label_gaps(gaps, spans, k: int = 10) -> list:
    """``[span name, seconds]`` of the ``k`` longest gaps, each named by the
    benchmark span that covers most of it, the shortest such span where
    several do (``outside`` if none)."""
    out = []
    for start, end in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        cover = lambda s: min(end, s.end) - max(start, s.start)  # noqa: E731
        covering = [s for s in spans if cover(s) > 0]
        best = max(covering, key=lambda s: (round(cover(s)), -s.duration),
                   default=None)
        out.append([best.name if best else "outside", (end - start) * 1e-9])
    return out


# --- the reduced trace -----------------------------------------------------

_WHAT = re.compile(r"\{[^{}]*\}")   # layouts and tilings: noise to a reader


def _device_event(text: str, start: float, duration: float) -> Event:
    """``%fusion.3 = bf16[8,128]{1,0:T(8,128)} fusion(...), kind=kLoop`` ->
    name ``fusion.3``, what ``bf16[8,128] fusion(...`` cut to 96 characters."""
    name, _, rest = text.partition(" = ")
    return Event(name.lstrip("%"), start, start + duration,
                 _WHAT.sub("", rest)[:96])


class Trace:
    """Device operation events per chip and the benchmark's host spans."""

    def __init__(self, ops: dict, spans: list):
        self.ops = {int(c): sorted(v, key=lambda e: e.start)
                    for c, v in ops.items()}
        self.spans = sorted(spans, key=lambda e: e.start)

    # -- reading ----------------------------------------------------------
    @classmethod
    def from_xplane(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
        ops, spans, host_ops = {}, [], []
        for plane in data.planes:
            m = _DEVICE_PLANE.match(plane.name)
            if m:
                for line in plane.lines:
                    if line.name == _OPS_LINE:
                        ops[int(m.group(1))] = [
                            _device_event(e.name, e.start_ns, e.duration_ns)
                            for e in line.events]
            elif plane.name == "/host:CPU":
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(SPAN_PREFIX):
                            spans.append(Event(
                                e.name, e.start_ns,
                                e.start_ns + e.duration_ns))
                        elif line.name.startswith("tf_XLA") and \
                                not e.name.startswith(("Threadpool",
                                                       "end: ")):
                            host_ops.append(_device_event(
                                e.name, e.start_ns, e.duration_ns))
        if not ops and host_ops:
            # A CPU rehearsal: XLA's CPU client runs the operations on host
            # threads; they stand in for one device so that the path runs.
            ops[0] = host_ops
        return cls(ops, spans)

    @classmethod
    def from_json(cls, path: str) -> "Trace":
        with open(path) as f:
            raw = json.load(f)
        ev = lambda rows: [Event(*row) for row in rows]  # noqa: E731
        return cls({c: ev(rows) for c, rows in raw["ops"].items()},
                   ev(raw["spans"]))

    def to_json(self, path: str) -> None:
        rows = lambda evs: [[e.name, e.start, e.end, e.what]  # noqa: E731
                            for e in evs]
        with open(path, "w") as f:
            json.dump({"ops": {str(c): rows(v) for c, v in self.ops.items()},
                       "spans": rows(self.spans)}, f)

    # -- the benchmark's own structure --------------------------------------
    def spans_named(self, name: str, inside: Event | None = None) -> list:
        out = [s for s in self.spans if s.name == name]
        if inside is not None:
            out = [s for s in out
                   if inside.start <= s.start and s.end <= inside.end]
        return out

    def stretch(self, name: str) -> Event | None:
        """The one span ``bench.<name>`` that encloses a traced stretch."""
        found = self.spans_named(SPAN_PREFIX + name)
        return found[0] if found else None

    def chips(self) -> list:
        return sorted(self.ops)

    def device_ns_in(self, chip: int, spans) -> float:
        """Device-busy nanoseconds on ``chip`` inside the given spans."""
        merged = union(pairs(self.ops[chip]))
        return sum(length(clip(merged, s.start, s.end)) for s in spans)
