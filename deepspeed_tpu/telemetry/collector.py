"""What the process did to the host thread, on the span clock: the
garbage collector's pauses, and the stalled leaf span by name and cause.

**The collector.** A Python process that holds large pytrees stops for a
generation-2 collection now and then, and from outside that is a pause
like any other: ``install_gc_hook()`` (the engines' constructors call
it; it installs ONE ``gc.callbacks`` hook a process) counts every
collection into ``process_gc_collections_total{generation}``, observes
its seconds in ``process_gc_pause_seconds{generation}`` and records a
``gc_pause`` span (attrs ``generation``, ``collected``) for a collection
of ``SPAN_FROM_S`` or more. Generation-0 collections come by the
thousand and take microseconds: as spans they would push a window's
``ragged_step`` / ``decode_window`` spans out of the ring.

A collection falls wherever an allocation crosses the threshold, inside
the registry's or the ring's own locked sections too, so the hook waits
for no lock: the series of the three generations are made here, at
install time, for the registry that is then the default (a registry
never installed under counts nothing), and the span goes through
``trace.record_nowait``.

**The host thread.** A span is a wall-clock interval: it cannot tell a
thread that COMPUTED from one that sat runnable on a run queue from one
that slept in a runtime call. The kernel keeps those numbers a thread,
and :func:`thread_usage` samples them; a :class:`HostThread` (one an
engine) takes the sample just before every launch span opens and just
behind its close, and at the open and close of a call's root, which cuts
the calling thread's time into segments, in a launch and between two. A
launch span and a root carry their own deltas as attrs (``cpu_s``,
``runq_s``, ``nvcsw``, ``nivcsw``, ``majflt``) and
``host_thread_cpu_seconds_total{path, where}`` keeps the CPU's total.
When a call has closed, :meth:`HostThread.judge` holds every leaf span
of it against the rolling median of its own kind over the calls BEFORE
it (a kind with fewer than eight leaves yet, a chunk step's, against the
longest leaf of its name): one over it by ``max(STALL_MIN_S, STALL_OVER
x that)`` becomes a ``host_stall`` span over the excess, beside
``gc_pause`` and put on the ring the same way, ``host_stalls_total`` /
``host_stall_seconds_total{path, cause}``, and one
``anomaly.report("host_stall", ...)`` whose warning line names the leaf,
its program, the seconds against expected, ONE cause and the numbers it
was decided from. Linux only (``RUSAGE_THREAD``, ``/proc/thread-self``).

What a kernel gives decides what can be told. A plain one (a TPU VM's,
the tests' sandbox) gives all five numbers: ``runq_s`` at half a stall's
excess or more reads ``runqueue``, the machine's (a neighbour, a
throttled cgroup), and ``majflt`` above 0 beside ``blocked`` says
paging. A sandboxed one (gVisor, the benchmark's chip host: no
``schedstat``, no switch or fault counted, CPU time in ticks of 10 ms)
leaves ``runq_s`` None, never 0, and a cause of ``compile``, ``gc``,
``cpu`` or ``blocked``.

A ``host_stall`` says what the host did, not what it cost: a pause that
launch-ahead hides is still recorded (the device's idle share and the
benchmark's gap metrics say what a pause cost).
"""

import bisect
import gc
import os
import resource
import statistics
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple

from . import anomaly, trace, watchdog
from .registry import get_registry

# a collection shorter than this leaves no span (seconds)
SPAN_FROM_S = 1e-3
# a leaf span is a stall where it ran over the rolling median of its own
# kind by more than max(STALL_MIN_S seconds, STALL_OVER of the median):
# 50 ms is half of the shortest pause the records name (~110 ms) and ten
# times a sound host leaf; a quarter is over what a decode window's wait
# grows by between a call's first and last window
STALL_MIN_S = 0.05
STALL_OVER = 0.25
# of a kind of leaf, the durations kept, and how many there have to be
# before one is judged (a cold first call is judged by nothing)
_KEPT = 32
_JUDGED_FROM = 8
# the spans this module puts on the ring itself: evidence, never leaves
_PAUSES = ("gc_pause", "host_stall")
_USAGE = ("cpu_s", "runq_s", "nvcsw", "nivcsw", "majflt")
_SCHEDSTAT = "/proc/thread-self/schedstat"
_GENERATIONS = (0, 1, 2)
_started = 0.0
_tls = threading.local()


def _pause(name: str, start: float, seconds: float, **attrs) -> bool:
    """The one way a pause gets on the span clock: a retroactive span
    that waits for no lock (a collection falls inside the ring's own
    locked sections too)."""
    return trace.record_nowait(name, start, seconds, **attrs)


def _on_gc(phase, info):
    global _started
    if phase == "start":
        _started = time.perf_counter()
        return
    pause = time.perf_counter() - _started
    generation = info["generation"]
    reg = get_registry()
    count = reg.get("process_gc_collections_total")
    seconds = reg.get("process_gc_pause_seconds")
    if count is not None and seconds is not None:
        count.labels(generation=generation).inc()
        seconds.labels(generation=generation).observe(pause)
    if pause >= SPAN_FROM_S:
        _pause("gc_pause", _started, pause, generation=generation,
               collected=info["collected"])


def install_gc_hook() -> None:
    """Count the collector's runs into the default registry and record
    the long ones as ``gc_pause`` spans. Idempotent: the hook is in
    ``gc.callbacks`` once however often this is called; each call makes
    sure the registry that is the default NOW has the series."""
    reg = get_registry()
    count = reg.counter(
        "process_gc_collections_total",
        "garbage collections the interpreter ran, by generation",
        labelnames=("generation",))
    seconds = reg.histogram(
        "process_gc_pause_seconds",
        "seconds a garbage collection held the interpreter, by generation",
        unit="seconds", labelnames=("generation",),
        buckets=(1e-5, 1e-4, 1e-3, 1e-2, 0.05, 0.1, 0.5, 1.0, 5.0))
    for generation in _GENERATIONS:
        count.labels(generation=generation)
        seconds.labels(generation=generation)
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


class _SchedStat:
    """The calling thread's ``schedstat``, kept open while the thread
    lives (``/proc/thread-self`` is resolved when the file is opened, so
    a thread has its own). ``fd`` is None where the kernel has none."""

    def __init__(self):
        try:
            self.fd = os.open(_SCHEDSTAT, os.O_RDONLY)
        except OSError:
            self.fd = None

    def wait_s(self) -> Optional[float]:
        """Seconds the thread has sat runnable on a run queue (the
        file's second field, ns), or None."""
        if self.fd is None:
            return None
        return int(os.pread(self.fd, 64, 0).split()[1]) * 1e-9

    def __del__(self):
        if self.fd is not None:
            os.close(self.fd)


def thread_usage() -> Tuple:
    """A sample of the calling thread: ``(perf_counter, CPU seconds,
    run-queue wait seconds or None, voluntary context switches,
    involuntary ones, major faults)``, all since the thread began. Two
    system calls (``getrusage(RUSAGE_THREAD)``, whose user and system
    times are the CPU seconds, and the ``pread`` of ``schedstat``): 2 us
    on a plain kernel, 6 on the chip's host, whose sandboxed kernel
    takes microseconds a call and has no ``schedstat`` to read (my chip
    run, PR 70). What reads it takes the difference of two
    (:func:`usage_between`)."""
    stat = getattr(_tls, "schedstat", None)
    if stat is None:
        stat = _tls.schedstat = _SchedStat()
    ru = resource.getrusage(resource.RUSAGE_THREAD)
    return (time.perf_counter(), ru.ru_utime + ru.ru_stime, stat.wait_s(),
            ru.ru_nvcsw, ru.ru_nivcsw, ru.ru_majflt)


def usage_between(a: Tuple, b: Tuple) -> Dict:
    """What the thread used between two samples of :func:`thread_usage`,
    under the names a span carries. ``runq_s`` is None where either
    sample had none: never 0."""
    waited = None if a[2] is None or b[2] is None else b[2] - a[2]
    return {"cpu_s": b[1] - a[1], "runq_s": waited, "nvcsw": b[3] - a[3],
            "nivcsw": b[4] - a[4], "majflt": b[5] - a[5]}


class HostThread:
    """The calling thread's usage over one engine's launches and calls
    (``path``: "generate", the serving engine's; "train"), and the
    judgement of a closed call's leaf spans (module docstring).

    ``with host.launch("ragged_step", rows=...):`` is a launch span;
    ``with host.call(root):`` a call, opened INSIDE its root span where
    it has one (``root``: the span that takes the call's totals as
    attrs) and around the call's top-level spans where it has none;
    ``host.judge()`` behind it, outside every span. A launch outside a
    call (``put()`` alone, the serving loop) carries its attrs and
    counts under ``where="launch"``; the time between two such launches
    is whoever called's, and is not counted.

    ``kind_attrs`` are the engine's span attrs that tell one kind of
    leaf from another: a leaf's kind is its name, what it carries of
    them itself (the program a call ran) and what the spans above it do
    (a launch's place in the device's queue: a window behind another
    waits a whole window, a chunk step attends more than the one before
    it)."""

    def __init__(self, path: str, kind_attrs: Tuple[str, ...] = ()):
        self.path = path
        self.kind_attrs = tuple(kind_attrs)
        reg = get_registry()
        cpu = reg.counter(
            "host_thread_cpu_seconds_total",
            "CPU seconds of the thread that drives the engine, inside "
            "launch spans and between two of a call",
            unit="seconds", labelnames=("path", "where"))
        self._cpu = {where: cpu.labels(path=path, where=where)
                     for where in ("launch", "between")}
        self._stalls = reg.counter(
            "host_stalls_total",
            "leaf spans of a call that ran over the rolling median of "
            "their kind by max(50 ms, 25 %), by cause",
            labelnames=("path", "cause"))
        self._stall_s = reg.counter(
            "host_stall_seconds_total",
            "seconds those leaves ran over their median, by cause",
            unit="seconds", labelnames=("path", "cause"))
        self._last = None           # (thread, sample) a segment began at
        # of the open call: [(from, to, where, usage)], else None
        self._segments = None
        self._closed = None         # the last closed call, not judged yet
        self._kept: Dict[Tuple, deque] = {}

    def _cut(self, where: Optional[str]) -> Optional[Dict]:
        """A sample, which ends a segment of kind ``where`` (None: the
        time before it is nobody's) and begins the next: the segment's
        usage, counted and kept for the open call."""
        me, now = threading.get_ident(), thread_usage()
        last, self._last = self._last, (me, now)
        if where is None or last is None or last[0] != me:
            return None
        used = usage_between(last[1], now)
        if where == "launch" or self._segments is not None:
            self._cpu[where].inc(used["cpu_s"])
        if self._segments is not None:
            self._segments.append((last[1][0], now[0], where, used))
        return used

    @contextmanager
    def launch(self, name: str, **attrs):
        """A launch span (``trace.span(name, **attrs)``, whose record it
        hands) between two samples: they lie OUTSIDE the span, just
        before it opens and just behind its close, so the span's leaves
        tile it as they did, and its usage joins the attrs of the
        record the ring already holds."""
        self._cut("between")
        span = None
        try:
            with trace.span(name, **attrs) as span:
                # the record reaches the ring with the key, whatever it
                # opened with: nothing is ADDED to it behind its close
                span["attrs"] = {}
                yield span
        finally:
            used = self._cut("launch")
            if used is not None and span is not None:
                # a new dict, put in one step: another thread may be
                # reading the record (export() copies the ring, not
                # what it holds)
                span["attrs"] = {**span["attrs"], **used}

    @contextmanager
    def call(self, root: Optional[Dict] = None):
        """Around a call: its segments are kept, ``root`` takes its
        totals, and :meth:`judge` finds it closed."""
        self._cut(None)
        self._segments = []
        mark, parent = trace.mark(), trace.current_span_id()
        first = self._last[1]
        try:
            yield
        finally:
            self._cut("between")
            if root is not None:
                root["attrs"] = usage_between(first, self._last[1])
            self._closed = (mark, parent, self._segments)
            self._segments = None

    def judge(self) -> List[Dict]:
        """Hold the leaf spans of the call that closed last against what
        their kinds took in the calls BEFORE it, and record the stalls
        (the anomaly verdicts, returned). One pass over that call's
        spans: the ring's tail since the call opened."""
        if self._closed is None:
            return []
        (mark, parent, segments), self._closed = self._closed, None
        tail = trace.since(mark)
        track = trace.current_track()
        # a span closes after its children: from the end, a parent
        # comes first (and ``mine`` from ITS end is in the order of time)
        mine, inside = {}, {parent}
        for s in reversed(tail):
            if s["parent"] in inside and s["track"] == track \
                    and s["name"] not in _PAUSES:
                inside.add(s["id"])
                mine[s["id"]] = s
        parents = {s["parent"] for s in mine.values()}
        begins = [seg[0] for seg in segments]
        verdicts, seen, usual = [], [], {}
        named = self.kind_attrs
        # what the spans from ``id`` up say of ``named``, the nearest
        # first; the spans under one launch ask once
        above_of = {parent: (None,) * len(named)}

        def above(id):
            got = above_of.get(id)
            if got is None:
                got = above(mine[id]["parent"])
                attrs = mine[id].get("attrs")
                if attrs:
                    got = tuple(r if attrs.get(a) is None else attrs[a]
                                for a, r in zip(named, got))
                above_of[id] = got
            return got

        for s in reversed(mine.values()):
            if s["id"] in parents:
                continue
            attrs = s.get("attrs")
            own = tuple(attrs.get(a) for a in named) if attrs \
                else above_of[parent]
            over = above(s["parent"])
            kinds = [(s["name"], own, over)]
            if over != above_of[parent]:
                # every leaf of the name, whatever launch it lay under:
                # what a kind too young to have a median is held to (a
                # chunk step's leaves come once a call: eight calls is
                # more than a run has)
                kinds.append((s["name"], own))
            at = bisect.bisect_right(begins, s["start"]) - 1
            where, used = segments[at][2:] if at >= 0 else (None, None)
            seconds = s["duration_s"]
            if seconds > STALL_MIN_S:
                expected = self._usual(kinds, usual)
                if expected is not None and seconds - expected[0] > max(
                        STALL_MIN_S, STALL_OVER * expected[0]):
                    # and not into the history: the longest leaf of a
                    # name must not be the last stall
                    verdicts.append(self._stall(
                        s, kinds[0], expected, where, used, tail,
                        begins[0] if begins else None))
                    continue
            seen.append((kinds, (seconds, used["cpu_s"] if used else 0.0)))
        # behind the pass: a leaf is held to the calls before its own,
        # so the call that first fills a kind's history is judged by
        # nothing (a fresh engine's first call is not its usual one)
        for kinds, took in seen:
            for kind in kinds:
                kept = self._kept.get(kind)
                if kept is None:
                    kept = self._kept[kind] = deque(maxlen=_KEPT)
                kept.append(took)
        return verdicts

    def _usual(self, kinds, usual) -> Optional[Tuple[float, float]]:
        """(seconds, CPU seconds) a leaf of ``kinds[0]`` is held to: the
        medians of its kind, or, of one too young, the LONGEST leaf of
        its name (a later chunk step attends more than the median one,
        none more than the last) with that history's median CPU; None
        where neither has ``_JUDGED_FROM`` leaves. ``usual`` keeps a
        pass's answers."""
        if kinds[0] not in usual:
            found = None
            for kind, reduce in zip(kinds, (statistics.median, max)):
                kept = self._kept.get(kind, ())
                if len(kept) >= _JUDGED_FROM:
                    found = (reduce(k[0] for k in kept),
                             statistics.median(k[1] for k in kept))
                    break
            usual[kinds[0]] = found
        return usual[kinds[0]]

    def _stall(self, leaf, kind, expected, where, used, tail,
               began) -> Dict:
        """One leaf over its median: its cause, and the three records."""
        expected, expected_cpu = expected
        over = leaf["duration_s"] - expected
        lo, hi = leaf["start"], leaf["start"] + leaf["duration_s"]
        gc_s = sum(max(0.0, min(hi, s["start"] + s["duration_s"])
                       - max(lo, s["start"]))
                   for s in tail if s["name"] == "gc_pause")
        # the watchdog stamps a compile with the wall clock, at its end
        wall = time.time() - time.perf_counter()
        compiled = [e["seconds"] for e in watchdog.events()
                    if lo <= e["time"] - wall <= hi]
        used = used or dict.fromkeys(_USAGE)
        half = 0.5 * over
        if compiled:
            # a call that compiled traced and lowered too, which the
            # watchdog's seconds leave out: it accounts for its call
            cause = "compile"
        elif gc_s >= half:
            cause = "gc"
        elif used["runq_s"] is not None and used["runq_s"] >= half:
            cause = "runqueue"
        elif used["cpu_s"] is not None \
                and used["cpu_s"] - expected_cpu >= half:
            cause = "cpu"
        else:
            cause = "blocked"
        name, own, above = kind
        evidence = dict(used, gc_s=gc_s, compiles=len(compiled),
                        compile_s=sum(compiled))
        # seconds into its call: which window, which chunk step
        at_s = lo - began if began is not None else None
        of = {a: v if v is not None else w
              for a, v, w in zip(self.kind_attrs, own, above)
              if v is not None or w is not None}
        _pause("host_stall", lo + expected, over, leaf=name, **of,
               at_s=at_s, expected_s=expected, cause=cause, where=where,
               **evidence)
        self._stalls.labels(path=self.path, cause=cause).inc()
        self._stall_s.labels(path=self.path, cause=cause).inc(over)
        said = ", ".join(f"{k} {v}" for k, v in of.items())
        numbers = " ".join(
            f"{k}={v:.4f}" if isinstance(v, float) else f"{k}={v}"
            for k, v in evidence.items())
        return anomaly.report(
            "host_stall",
            f"{self.path}: {name}{f' ({said})' if said else ''} took "
            f"{leaf['duration_s']:.3f} s against {expected:.3f} expected"
            + (f", {at_s:.3f} s into its call" if at_s is not None else "")
            + f": {cause} [{where}: {numbers}]",
            path=self.path, leaf=name, **of, seconds=leaf["duration_s"],
            at_s=at_s, expected_s=expected, cause=cause, where=where,
            **evidence)
