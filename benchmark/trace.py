"""From a profiler trace (``.xplane.pb``) to numbers.

``jax.profiler.ProfileData`` reads the file with nothing but jax. The
reduction below is plain interval arithmetic on (start, end) pairs in
nanoseconds, kept apart from the loading so that it can be checked on
hand-made events (tests/benchmark/test_benchmark_trace.py).

What a TPU trace looks like (one plane per chip, ``/device:TPU:<n>``): the
line ``XLA Ops`` holds one event per executed HLO instruction, named by the
instruction's whole text (``%fusion.12 = bf16[32,196,1024]{...}
fusion(...), kind=kOutput, ...``), which the loader cuts down to the
instruction's name (``fusion.12``) and a group (``fusion[kOutput]
bf16[32,196,1024]``: the name less its number, the kind of fusion and the
first result's shape, which says which part of the model it is). A
``while`` (a ``lax.scan``) is one long event with its body's events nested
inside it, so time per op is *self* time: an event's duration less its
children's. The line ``Async XLA Ops`` holds one event per asynchronous
operation, from its ``-start`` to its ``-done``. Host threads are lines of
the ``/host:CPU`` plane, and a ``jax.profiler.TraceAnnotation`` is an event
there, on the same clock.

Definitions, per device and then averaged over the devices traced:

- window: the host span named ``WINDOW_SPAN`` that the runner opens round
  the traced slice (falls back to the extent of the device events);
- busy: the length of the union of the op events inside the window; idle
  share is 1 - busy / window;
- collective time: the union of the collective events, an asynchronous
  pair counted from the start of ``<op>-start`` to the end of
  ``<op>-done``; exposed: the part of it during which no other leaf op
  runs on that device;
- idle gaps: the complement of busy in the window, each gap attributed to
  the innermost benchmark host span (name starting ``SPAN_PREFIX``) that
  overlaps it most.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

WINDOW_SPAN = "bench:window"
SPAN_PREFIX = "bench:"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")

Interval = Tuple[float, float]


class Event(NamedTuple):
    name: str
    start: float  # ns
    end: float  # ns
    group: str = ""  # device ops only: what a breakdown sums it under


class Line(NamedTuple):
    name: str
    events: List[Event]


class Plane(NamedTuple):
    name: str
    lines: List[Line]


# -- loading ---------------------------------------------------------------

def find_xplane(logdir: str) -> str:
    """The newest ``.xplane.pb`` the profiler wrote under ``logdir``."""
    found = glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(found, key=os.path.getmtime)


_HLO = re.compile(r"^%?([\w.\-]+) = ")
_SHAPE = re.compile(r"\b([a-z]+\d*\[[\d,]*\])")
_KIND = re.compile(r"\bkind=(\w+)")


def parse_hlo(text: str) -> Tuple[str, str]:
    """``(name, group)`` of a device event named by an HLO instruction's
    text; a plain name (no `` = ``) is its own name, grouped without its
    numeric suffix."""
    m = _HLO.match(text)
    name = m.group(1) if m else text
    group = re.sub(r"[.\d]+$", "", name) or name
    if m:
        kind = _KIND.search(text)
        shape = _SHAPE.search(text, m.end())
        if kind:
            group += f"[{kind.group(1)}]"
        if shape:
            group += f" {shape.group(1)}"
    return name, group


def _planes(profile_data) -> List[Plane]:
    planes = []
    parsed: Dict[str, Tuple[str, str]] = {}
    for plane in profile_data.planes:
        is_device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if is_device and line.name not in (OPS_LINE, ASYNC_LINE):
                continue  # steps, modules, overlays: not read, not kept
            events = []
            for ev in line.events:
                name, group = ev.name, ""
                if is_device:
                    if name not in parsed:
                        parsed[name] = parse_hlo(name)
                    name, group = parsed[name]
                start = float(ev.start_ns)
                events.append(Event(name, start,
                                    start + float(ev.duration_ns), group))
            lines.append(Line(line.name, events))
        planes.append(Plane(plane.name, lines))
    return planes


def load(path: str) -> List[Plane]:
    """Planes of an ``.xplane.pb`` file: every host line, and of each
    device plane the ``XLA Ops`` and ``Async XLA Ops`` lines."""
    from jax.profiler import ProfileData

    return _planes(ProfileData.from_file(path))


def load_text_proto(text: str) -> List[Plane]:
    """The same from an XSpace in protobuf text format (fixtures)."""
    from jax.profiler import ProfileData

    return _planes(ProfileData.from_text_proto(text))


# -- interval arithmetic ----------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[Interval] = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def length(intervals: Iterable[Interval]) -> float:
    return sum(hi - lo for lo, hi in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(a: Iterable[Interval], b: Iterable[Interval]
             ) -> List[Interval]:
    """The points of ``a`` that are in no interval of ``b``."""
    b = union(b)
    out: List[Interval] = []
    j = 0
    for lo, hi in union(a):
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k, cur = j, lo
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


# -- reduction --------------------------------------------------------------

def self_times(events: Iterable[Event]) -> List[Tuple[Event, float, bool]]:
    """``(event, self_ns, is_leaf)`` for the events of one line: an event
    that starts inside an earlier, still open one is its child, and a
    parent's self time is its duration less its children's."""
    out: List[list] = []
    stack: List[int] = []
    for ev in sorted(events, key=lambda e: (e.start, -(e.end - e.start))):
        while stack and out[stack[-1]][0].end <= ev.start:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[1] -= min(ev.end, parent[0].end) - ev.start
            parent[2] = False
        out.append([ev, ev.end - ev.start, True])
        stack.append(len(out) - 1)
    return [(ev, max(0.0, self_ns), leaf) for ev, self_ns, leaf in out]


def collective_kind(name: str) -> Optional[str]:
    """``all-reduce`` for ``all-reduce.3``, ``all-reduce-start.3`` and
    ``all-reduce-done.3``; ``None`` for an op that is no collective."""
    for kind in COLLECTIVES:
        if name == kind or name.startswith((kind + ".", kind + "-start",
                                            kind + "-done")):
            return kind
    return None


def collective_intervals(events: Iterable[Event]) -> List[Interval]:
    """One interval per collective: a synchronous one its own event, an
    asynchronous pair from the start of ``-start`` to the end of the
    ``-done`` that follows with the same suffix."""
    out: List[Interval] = []
    open_starts: Dict[str, List[float]] = defaultdict(list)
    for ev in sorted(events, key=lambda e: e.start):
        kind = collective_kind(ev.name)
        if kind is None:
            continue
        rest = ev.name[len(kind):]
        if rest.startswith("-start"):
            open_starts[kind + rest[len("-start"):]].append(ev.start)
            out.append((ev.start, ev.end))
        elif rest.startswith("-done"):
            begun = open_starts.get(kind + rest[len("-done"):])
            out.append((begun.pop(0) if begun else ev.start, ev.end))
        else:
            out.append((ev.start, ev.end))
    return out


def host_spans(planes: List[Plane]) -> List[Event]:
    """The benchmark's own spans, from every host line."""
    return [ev for plane in planes if not DEVICE_PLANE.match(plane.name)
            for line in plane.lines for ev in line.events
            if ev.name.startswith(SPAN_PREFIX)]


def device_ops(planes: List[Plane], line_name: str = OPS_LINE
               ) -> Dict[str, List[Event]]:
    """Plane name -> events of its ``line_name`` line, for device planes
    that have any."""
    out = {}
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            events = [ev for line in plane.lines if line.name == line_name
                      for ev in line.events]
            if events:
                out[plane.name] = events
    return out


def attribute(gap: Interval, spans: List[Event]) -> str:
    """The benchmark span a gap belongs to: the one that overlaps it most,
    the shorter (inner) one where two overlap it alike."""
    best, best_key = "(no span)", (0.0, 0.0)
    for sp in spans:
        if sp.name == WINDOW_SPAN:
            continue
        got = overlap(gap, (sp.start, sp.end))
        key = (got, -(sp.end - sp.start))
        if got > 0 and key > best_key:
            best, best_key = sp.name, key
    return best


def reduce(planes: List[Plane], top: int = 10) -> dict:
    """The numbers a traced run reports; see the module docstring. Times
    in seconds. Raises ``ValueError`` where no operation ran on a device:
    a traced run that never reached the chip has nothing to report."""
    ops = device_ops(planes)
    in_flight = device_ops(planes, ASYNC_LINE)
    if not ops:
        raise ValueError("the trace holds no device operation "
                         f"(planes: {[p.name for p in planes]})")
    spans = host_spans(planes)
    extent = (min(e.start for evs in ops.values() for e in evs),
              max(e.end for evs in ops.values() for e in evs))
    window, source = extent, "device_extent"
    for sp in spans:
        if sp.name == WINDOW_SPAN and overlap((sp.start, sp.end), extent) \
                > 0.5 * (extent[1] - extent[0]):
            window, source = (sp.start, sp.end), "host_span"
    lo, hi = window
    n = len(ops)
    busy = coll = exposed = 0.0
    op_seconds: Dict[str, float] = defaultdict(float)
    gap_seconds: Dict[str, float] = defaultdict(float)
    longest_gap = 0.0
    for plane_name, events in ops.items():
        events = [Event(e.name, max(e.start, lo), min(e.end, hi), e.group)
                  for e in events if min(e.end, hi) > max(e.start, lo)]
        covered = union((e.start, e.end) for e in events)
        busy += length(covered)
        timed = self_times(events)
        for ev, self_ns, _leaf in timed:
            op_seconds[ev.group or ev.name] += self_ns / n
        cints = union(clip(
            collective_intervals(events)
            + collective_intervals(in_flight.get(plane_name, [])), lo, hi))
        other = [(ev.start, ev.end) for ev, _s, leaf in timed
                 if leaf and collective_kind(ev.name) is None]
        coll += length(cints)
        exposed += length(subtract(cints, other))
        for gap in subtract([window], covered):
            gap_seconds[attribute(gap, spans)] += (gap[1] - gap[0]) / n
            longest_gap = max(longest_gap, gap[1] - gap[0])

    def ranked(table):
        return [[k, v / 1e9] for k, v in sorted(
            table.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "devices": n,
        "window_source": source,
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / n / 1e9,
        "collective_s": coll / n / 1e9,
        "collective_exposed_s": exposed / n / 1e9,
        "longest_gap_s": longest_gap / 1e9,
        "device_ops": ranked(op_seconds),
        "idle_gaps": ranked(gap_seconds),
    }


def describe(planes: List[Plane], names: int = 12) -> dict:
    """What the trace holds, for reading one by hand: per plane and line
    the number of events and the names that took most time."""
    out = {}
    for plane in planes:
        for line in plane.lines:
            total: Dict[str, float] = defaultdict(float)
            for ev in line.events:
                total[ev.group or ev.name] += ev.end - ev.start
            out[f"{plane.name} | {line.name}"] = {
                "events": len(line.events),
                "top": [[k, v / 1e9] for k, v in sorted(
                    total.items(), key=lambda kv: -kv[1])[:names]],
            }
    return out
