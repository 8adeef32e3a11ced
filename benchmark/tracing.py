"""From a profiler trace to numbers: the reduction every PR is measured
with. ``load_events`` turns an ``.xplane.pb`` into a neutral event list;
everything else works on that list alone, so it is tested on hand-written
fixtures and never needs a chip.

A neutral event is ``(plane, line, name, start_s, dur_s)``: the plane is
a device (``/device:TPU:0``) or the host (``/host:CPU``), the line is
what the profiler calls a row of the plane (``XLA Ops``, ``Async XLA
Ops``, ``XLA Modules``, a host thread), the name is the instruction's
own (``short_name``). Times are seconds on the profiler's one clock.

One rule says what an operation's time is (``self_intervals``): its
interval less what the operations nested in it cover. Every sum over
operations here and in ``readers/`` goes through it.
"""

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_s: float
    dur_s: float

    @property
    def end_s(self):
        return self.start_s + self.dur_s


class Events(tuple):
    """A run's whole slice: it cannot change, so the ``self_intervals``
    of a plane are worked out once and kept for the readers that ask for
    them. On the four-chip cell's slice (208,290 events, four planes) the
    cell's readers and the breakdown take 26.0 s as a plain list and
    11.2 s as this; on the one-chip cell's (17,856 events) 1.27 s and
    0.60 s (PR 32, on the sandbox's CPU from the recorded slices)."""

    def __new__(cls, events=()):
        self = super().__new__(cls, events)
        self.kept = {}
        return self


DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# where the TPU's profiler puts operations that run beside the main
# stream: async copies and the async halves of collectives
ASYNC_LINE = "Async XLA Ops"
# the collectives ZeRO and data parallelism issue, as HLO names them
# (``all-gather-start.3``, ``all-to-all.6``, the TPU backend's
# ``async_collective_fusion``)
COLLECTIVE = re.compile(
    r"all-gather|reduce-scatter|all-reduce|all-to-all|collective",
    re.IGNORECASE)


def newest_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_events(xplane_path, keep_host_line=None) -> List[Event]:
    """Device planes whole; of the host plane only events that carry one
    of ``keep_host_line``'s names (the program's span names — the host
    plane also holds every runtime TraceMe, which nothing here reads)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    keep = set(keep_host_line or ())
    out = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE,
                                            ASYNC_LINE):
                continue
            for ev in line.events:
                if device or ev.name in keep:
                    out.append(Event(plane.name, line.name,
                                     short_name(ev.name),
                                     ev.start_ns * 1e-9,
                                     ev.duration_ns * 1e-9))
    return Events(out)


def short_name(name):
    """The TPU's op events carry the whole HLO instruction as their name
    (``%fusion.4 = bf16[...] fusion(...)``); keep the instruction's own
    name, which is what a kernel or a named scope shows up in. A Mosaic
    (Pallas) kernel is marked ``:tpu_custom_call``: a kernel built
    without a name is called after the jaxpr around it (``checkpoint.20``,
    ``closed_call.8``), and the mark is all that tells it from a fusion."""
    if name.startswith("%") and " = " in name:
        short = name.split(" = ", 1)[0][1:]
        if 'custom_call_target="tpu_custom_call"' in name:
            short += ":tpu_custom_call"
        return short
    return name


# ---------------------------------------------------------------------------
# intervals
# ---------------------------------------------------------------------------
Interval = Tuple[float, float]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of ``intervals`` (start, end)."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def overlap(a, b) -> float:
    """Seconds covered by both of two disjoint sorted interval lists."""
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------
def device_planes(events: Sequence[Event]) -> List[str]:
    return sorted({e.plane for e in events if DEVICE_PLANE.match(e.plane)})


def _line(events, plane, line):
    return [e for e in events if e.plane == plane and e.line == line]


def window(events: Sequence[Event]) -> Interval:
    """First start to last end of any device event."""
    dev = [e for e in events if DEVICE_PLANE.match(e.plane)]
    if not dev:
        raise ValueError("the trace holds no device event: nothing ran on "
                         "the device, or the device planes were not found")
    return min(e.start_s for e in dev), max(e.end_s for e in dev)


def busy(events, plane) -> List[Interval]:
    """Union of the intervals in which an operation ran on ``plane``."""
    return union((e.start_s, e.end_s)
                 for e in _line(events, plane, OPS_LINE))


def busy_and_window(events) -> Tuple[float, float]:
    """(busy seconds averaged over the device planes, window seconds)."""
    lo, hi = window(events)
    planes = device_planes(events)
    b = sum(total(clip(busy(events, p), lo, hi)) for p in planes)
    return b / len(planes), hi - lo


def idle_percent(events) -> float:
    b, w = busy_and_window(events)
    return 100.0 * (1.0 - b / w)


def self_intervals(events, plane) -> List[Tuple[Event, List[Interval]]]:
    """``(event, intervals)`` for every operation of ``plane``'s XLA Ops
    line: the parts of its interval in which no operation nested in it
    runs. A ``while`` spans its body's operations on the same line and
    keeps only the time between them; a kernel or a fusion with a small
    operation inside its interval keeps all of its time but that (on the
    v5e, an ``AllocateBuffer`` whose start and length round to the
    kernel's own start and to 0 ns: a rule that dropped what contains
    another operation lost such a call whole). So nothing is counted
    twice, nothing is dropped, and the intervals of all operations
    together are the busy time. An operation that starts inside another and ends
    after it is not nested in it: the two ran side by side, and each
    keeps the time they share."""
    kept = getattr(events, "kept", None)
    if kept is not None and plane in kept:
        return kept[plane]
    ops = sorted(_line(events, plane, OPS_LINE),
                 key=lambda e: (e.start_s, -e.dur_s))
    out: List[Tuple[Event, List[Interval]]] = []
    open_ops: List[list] = []       # [event, own pieces, covered up to]
    for e in ops:
        # what has ended, and what e outlasts, does not contain e
        while open_ops and (open_ops[-1][0].end_s <= e.start_s + 1e-12
                            or open_ops[-1][0].end_s < e.end_s - 1e-12):
            _close(open_ops.pop())
        if open_ops:
            outer = open_ops[-1]
            if e.start_s > outer[2]:
                outer[1].append((outer[2], e.start_s))
            outer[2] = max(outer[2], e.end_s)
        pieces: List[Interval] = []
        open_ops.append([e, pieces, e.start_s])
        out.append((e, pieces))
    while open_ops:
        _close(open_ops.pop())
    if kept is not None:
        kept[plane] = out
    return out


def _close(item):
    e, pieces, upto = item
    if e.end_s > upto:
        pieces.append((upto, e.end_s))


def self_times(events, plane) -> List[Tuple[Event, float]]:
    """``(event, seconds)``: the self time of every operation of
    ``plane``'s XLA Ops line (``self_intervals``, summed)."""
    return [(e, total(pieces)) for e, pieces in self_intervals(events, plane)]


def op_seconds(events, pattern) -> float:
    """Device seconds (self time) of the operations whose name matches
    ``pattern``, averaged over the device planes."""
    rx = re.compile(pattern)
    planes = device_planes(events)
    if not planes:
        return 0.0
    return sum(s for p in planes for e, s in self_times(events, p)
               if rx.search(e.name)) / len(planes)


def op_share_percent(events, pattern) -> Optional[float]:
    b, _ = busy_and_window(events)
    return 100.0 * op_seconds(events, pattern) / b if b > 0 else None


def modules(events, plane) -> List[Event]:
    """Device program executions (one event per launch), in time order."""
    return sorted(_line(events, plane, MODULES_LINE),
                  key=lambda e: e.start_s)


def program_gaps(events, plane) -> List[Interval]:
    """Idle gaps between consecutive device programs on ``plane``."""
    cover = union((e.start_s, e.end_s) for e in modules(events, plane))
    return [(a[1], b[0]) for a, b in zip(cover, cover[1:])]


def attribute_gaps(gaps, host_spans: Sequence[Event], top=10
                   ) -> List[Tuple[str, float]]:
    """Charge each gap to the host span that covers most of it — or to
    ``(no span)`` where none covers half — and return the ``top``
    longest as (name, seconds). ``host_spans`` are host-plane events."""
    spans = sorted(host_spans, key=lambda e: e.start_s)
    out = []
    for lo, hi in gaps:
        cover: Dict[str, float] = defaultdict(float)
        for s in spans:
            if s.start_s >= hi:
                break
            ov = min(hi, s.end_s) - max(lo, s.start_s)
            if ov > 0:
                cover[s.name] += ov
        name = max(cover, key=cover.get) if cover else None
        if name is None or cover[name] < (hi - lo) / 2:
            name = "(no span)"
        out.append((name, hi - lo))
    return sorted(out, key=lambda kv: -kv[1])[:top]


def top_ops(events, top=10) -> List[Tuple[str, float]]:
    """The device operations that took most self time, by name, seconds
    summed on the first device plane."""
    planes = device_planes(events)
    if not planes:
        return []
    acc: Dict[str, float] = defaultdict(float)
    for e, s in self_times(events, planes[0]):
        acc[e.name] += s
    return sorted(acc.items(), key=lambda kv: -kv[1])[:top]


def collective_intervals(events, plane, keep=None) -> List[Interval]:
    """Union of the intervals in which a collective ran on ``plane``:
    its own intervals on the main stream (``self_intervals``) or, its
    async half, its event on the async line. ``keep(event)`` narrows it
    to some collectives."""
    own = [iv for e, pieces in self_intervals(events, plane)
           if COLLECTIVE.search(e.name) and (keep is None or keep(e))
           for iv in pieces]
    beside = [(e.start_s, e.end_s) for e in _line(events, plane, ASYNC_LINE)
              if COLLECTIVE.search(e.name) and (keep is None or keep(e))]
    return union(own + beside)


def collective_seconds(events, plane) -> Tuple[float, float]:
    """(seconds in which a collective ran on ``plane``, seconds of those
    in which nothing else ran there). Compute is the main stream's other
    operations, each over its own intervals. They overlap on a TPU, so
    both are unions of intervals, not sums."""
    coll = collective_intervals(events, plane)
    comp = union(iv for e, pieces in self_intervals(events, plane)
                 if not COLLECTIVE.search(e.name) for iv in pieces)
    t = total(coll)
    return t, t - overlap(coll, comp)


def from_jsonable(rows) -> List[Event]:
    return [Event(str(p), str(ln), str(n), float(s), float(d))
            for p, ln, n, s, d in rows]
