"""The garbage collector's pauses, counted and put on the span clock.

A Python process that holds large pytrees stops for a generation-2
collection now and then, and from outside that is a pause like any
other: ``install_gc_hook()`` (the engines' constructors call it; it
installs ONE ``gc.callbacks`` hook a process) counts every collection
into ``process_gc_collections_total{generation}``, observes its seconds
in ``process_gc_pause_seconds{generation}`` and records a ``gc_pause``
span (attrs ``generation``, ``collected``) for a collection of
``SPAN_FROM_S`` or more. Generation-0 collections come by the thousand
and take microseconds: as spans they would push a window's
``ragged_step`` / ``decode_window`` spans out of the ring.

A collection falls wherever an allocation crosses the threshold, inside
the registry's or the ring's own locked sections too, so the hook waits
for no lock: the series of the three generations are made here, at
install time, for the registry that is then the default (a registry
never installed under counts nothing), and the span goes through
``trace.record_nowait``.
"""

import gc
import time

from . import trace
from .registry import get_registry

# a collection shorter than this leaves no span (seconds)
SPAN_FROM_S = 1e-3
_GENERATIONS = (0, 1, 2)
_started = 0.0


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
        trace.record_nowait("gc_pause", _started, pause,
                            generation=generation,
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
