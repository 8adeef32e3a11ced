"""Lightweight span tracing with a ring-buffer exporter.

``with trace.span("decode_step"):`` records (name, start, duration,
depth) into a bounded deque — overhead is two ``perf_counter`` calls and
one locked append, so the serving hot path can stay instrumented in
production. ``with trace.span(...) as sp:`` hands the record itself:
``sp["duration_s"]`` is there once the block has closed, so a histogram
beside a span observes the span's own duration and not a second clock;
``sp["attrs"] = {...}`` inside the block adds attrs that are known only
at its close (the host thread's usage in a launch:
:mod:`deepspeed_tpu.telemetry.collector`).
``export()`` drains a copy for offline analysis; ``durations(name)``
feeds assertions and benchmarks.

Every span also carries a process-unique ``id``, the ``parent`` id of
the enclosing span (None at top level), and a ``track`` — by default the
recording thread's name, overridable with :func:`set_track` — so the
ring buffer reconstructs into per-thread timelines
(:mod:`deepspeed_tpu.telemetry.timeline` exports them as Chrome trace
events). :func:`record` appends a RETROACTIVE span from saved
timestamps (e.g. a request's queue wait, measured between two scheduler
events rather than around a ``with`` block).

Fleet serving adds a second grouping axis: the ``lane`` — which serving
REPLICA (or the router) recorded the span. In-process replicas share
this one ring buffer, so each replica's loop thread names its lane once
(:func:`set_lane`; the router passes ``lane=`` explicitly) and the
fleet timeline export groups lanes into per-replica process rows —
exactly the shape N remote rings would stitch into. Spans without a
lane belong to no replica (single-engine serving, training).

``enable_xla_annotations(True)`` mirrors every span into a
``jax.profiler.TraceAnnotation`` so spans line up with device activity
in a TensorBoard/XProf trace captured via
``deepspeed_tpu.utils.xla_profile.capture_trace`` (the hook is optional:
absent/failed jax.profiler leaves spans host-only). A span recorded while
it was mirrored carries ``annotated: True``: the same span exists on the
profiler's clock under the same name, so pairing the two in order gives
the offset between ``perf_counter`` and that clock, and with it every
span of the ring a place on the device trace's time axis.
"""

import itertools
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Dict, List, Optional

# a generate() call records ~200 spans (its root, a ragged step and 32
# decode windows with their leaves), a rollout cell finishes ~20 calls in
# its window, and the benchmark's runner reads the whole window's
# ``ragged_step`` / ``decode_window`` spans from the ring: 4,096 would
# have dropped the window's first calls
_DEFAULT_CAPACITY = 16384

_lock = threading.Lock()
_buffer: deque = deque(maxlen=_DEFAULT_CAPACITY)
_xla_annotations = False
_local = threading.local()
_ids = itertools.count(1)
_appended = 0       # spans put on the ring since the process began


def enable_xla_annotations(on: bool = True) -> None:
    """Mirror spans into jax.profiler trace annotations (see module
    docstring)."""
    global _xla_annotations
    _xla_annotations = on


def set_capacity(capacity: int) -> None:
    """Resize the ring buffer (drops recorded spans)."""
    global _buffer
    with _lock:
        _buffer = deque(maxlen=int(capacity))


def set_track(name: Optional[str]) -> None:
    """Name this thread's timeline track (None restores the default —
    the thread's own name). Tracks map to rows in the Chrome trace
    export."""
    _local.track = name


def current_track() -> str:
    track = getattr(_local, "track", None)
    return track if track is not None else threading.current_thread().name


def set_lane(name: Optional[str]) -> None:
    """Name this thread's fleet lane (the replica whose spans it
    records; None clears it). Lanes map to process rows in the stitched
    fleet timeline (:func:`timeline.stitch_fleet`)."""
    _local.lane = name


def current_lane() -> Optional[str]:
    return getattr(_local, "lane", None)


@contextmanager
def span(name: str, lane: Optional[str] = None, **attrs):
    """Record a wall-clock span; nests (depth reflects enclosing spans).
    ``lane`` overrides the thread's fleet lane for this span."""
    depth = getattr(_local, "depth", 0)
    parent = getattr(_local, "span_id", None)
    span_id = next(_ids)
    _local.depth = depth + 1
    _local.span_id = span_id
    annotation = None
    if _xla_annotations:
        try:
            import jax
            annotation = jax.profiler.TraceAnnotation(name)
            annotation.__enter__()
        except Exception:
            annotation = None
    rec = {"name": name, "start": time.perf_counter(), "duration_s": None,
           "depth": depth, "id": span_id, "parent": parent}
    try:
        yield rec
    finally:
        rec["duration_s"] = time.perf_counter() - rec["start"]
        if annotation is not None:
            annotation.__exit__(None, None, None)
            rec["annotated"] = True
        _local.depth = depth
        _local.span_id = parent
        rec["track"] = current_track()
        ln = lane if lane is not None else current_lane()
        if ln is not None:
            rec["lane"] = ln
        if attrs or "attrs" in rec:
            # what the block set on its record, over what it opened with
            rec["attrs"] = {**attrs, **rec.get("attrs", {})}
        _append(rec)


def _append(rec: Dict) -> None:
    # under _lock: export() snapshots the deque while other threads
    # record, and set_capacity() swaps the buffer out entirely
    global _appended
    with _lock:
        _buffer.append(rec)
        _appended += 1


def _retroactive(name, start, duration_s, track, lane, attrs) -> Dict:
    rec = {"name": name, "start": float(start),
           "duration_s": float(duration_s), "depth": 0, "id": next(_ids),
           "parent": None,
           "track": track if track is not None else current_track()}
    ln = lane if lane is not None else current_lane()
    if ln is not None:
        rec["lane"] = ln
    if attrs:
        rec["attrs"] = attrs
    return rec


def record(name: str, start: float, duration_s: float,
           track: Optional[str] = None, lane: Optional[str] = None,
           **attrs) -> None:
    """Append a retroactive span from saved ``perf_counter`` timestamps.

    For phases whose boundaries are events rather than a ``with`` block
    (a request's queue wait between submit and first prefill chunk, its
    decode phase between first token and finish). Retroactive spans are
    top-level (no parent) on ``track`` (default: the calling thread's
    track) in fleet lane ``lane`` (default: the thread's lane)."""
    _append(_retroactive(name, start, duration_s, track, lane, attrs))


def record_nowait(name: str, start: float, duration_s: float,
                  **attrs) -> bool:
    """:func:`record` for a caller that may interrupt this module on its
    own thread (a ``gc.callbacks`` hook runs wherever a collection
    falls, inside ``export()``'s copy of the ring too): where the lock
    is taken the span is dropped and False returned, never waited for."""
    global _appended
    rec = _retroactive(name, start, duration_s, None, None, attrs)
    if not _lock.acquire(blocking=False):
        return False
    try:
        _buffer.append(rec)
        _appended += 1
    finally:
        _lock.release()
    return True


def current_span_id() -> Optional[int]:
    """The id of the span this thread is inside (None at top level):
    what a span opened now would carry as ``parent``."""
    return getattr(_local, "span_id", None)


def mark() -> int:
    """A place on the ring: :func:`since` gives what was recorded after
    it. The count of spans recorded so far, whatever the ring has
    dropped or :func:`clear` taken since."""
    return _appended


def since(mark: int) -> List[Dict]:
    """The spans recorded since :func:`mark` returned ``mark`` (oldest
    first; those the ring still holds): the tail alone is copied, so a
    caller that reads one call's few hundred spans does not pay for the
    ring's sixteen thousand."""
    with _lock:
        n = min(_appended - mark, len(_buffer))
        tail = list(itertools.islice(reversed(_buffer), n))
    tail.reverse()
    return tail


def export(name: Optional[str] = None) -> List[Dict]:
    """Copy of the recorded spans (oldest first), optionally filtered."""
    with _lock:
        spans = list(_buffer)
    if name is not None:
        spans = [s for s in spans if s["name"] == name]
    return spans


def durations(name: str) -> List[float]:
    return [s["duration_s"] for s in export(name)]


def clear() -> None:
    with _lock:
        _buffer.clear()
