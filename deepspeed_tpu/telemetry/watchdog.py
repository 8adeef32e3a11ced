"""Recompile watchdog: every jit compile point, counted and attributed.

On TPU a silent XLA recompile is a multi-second stall that looks like a
latency spike; the bucketing layers (engine_v2's power-of-two decode
buckets, the scheduler's chunk-aligned prefill sizes) exist precisely so
steady-state serving never retraces. This module makes that property
observable and enforceable:

  * :func:`watch` wraps a jitted callable in a proxy that detects cache
    growth (``fn._cache_size()`` delta around each call), recording the
    program name, the argument shape signature (the bucket key), and the
    compile wall time into registry counters.
  * :func:`mark_steady` flips the process into steady-state mode — from
    then on ANY compile increments
    ``xla_steady_state_recompiles_total`` and logs a warning naming the
    program and the shapes that triggered it. Benches call it after
    their warmup pass; serving can call it once traffic is warm.
  * :func:`record_compile` covers explicit compile points that don't go
    through a jit call (``engine.lower_train_step`` AOT compiles).
  * :func:`watch_jit` is how a serving program is built: the function is
    NAMED for the program before it is jitted, so the device trace's
    "XLA Modules" line reads ``jit_<program>`` (a lambda would read
    ``jit__lambda``, the same for every program), and on a compile event
    the proxy offers ``telemetry.memory`` the executable's scope map
    (``memory.scopes(program)``) as a thunk over the abstract signature:
    nothing is lowered, compiled or parsed until a reader asks.

Compile wall time comes from jax.monitoring's
``backend_compile_duration`` events accumulated on the calling thread
(compiles run synchronously on it); when the event doesn't fire (e.g. a
persistent-cache hit still traces and loads) the call's wall time is
recorded as an upper bound.

Registry series (docs/TELEMETRY.md): ``xla_compile_events_total``,
``xla_compile_seconds_total``, ``xla_steady_state_recompiles_total``
(all labeled by ``program``) and the ``xla_compiled_programs`` gauge
(live jit-cache size per program).
"""

import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..utils.logging import logger
from .registry import get_registry

_EVENT_CAPACITY = 256

_lock = threading.Lock()
_events: deque = deque(maxlen=_EVENT_CAPACITY)
_steady = False
_listener_installed = False
_tls = threading.local()


def _install_listener() -> None:
    """Accumulate jax backend-compile durations per thread (idempotent;
    jax.monitoring listeners cannot be unregistered individually, so one
    process-lifetime hook serves every watched function)."""
    global _listener_installed
    if _listener_installed:
        return
    with _lock:
        if _listener_installed:
            return
        try:
            import jax.monitoring

            def _on_duration(name: str, dur: float, **kw) -> None:
                if name.endswith("backend_compile_duration"):
                    _tls.compile_s = getattr(_tls, "compile_s", 0.0) + dur

            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
        except Exception:  # no jax / API drift: wall-time fallback only
            pass
        _listener_installed = True


def _metrics():
    reg = get_registry()
    return (
        reg.counter("xla_compile_events_total",
                    "XLA program compiles observed by the watchdog",
                    labelnames=("program",)),
        reg.counter("xla_compile_seconds_total",
                    "wall time spent compiling, per program", unit="s",
                    labelnames=("program",)),
        reg.counter("xla_steady_state_recompiles_total",
                    "compiles AFTER mark_steady() — a supposedly-bucketed "
                    "path retraced at steady state",
                    labelnames=("program",)),
        reg.gauge("xla_compiled_programs",
                  "live jit-cache entries per watched program",
                  labelnames=("program",)),
    )


def mark_steady(on: bool = True) -> None:
    """Enter (or leave) steady-state mode: further compiles are counted
    as recompile violations and logged."""
    global _steady
    _steady = on


def is_steady() -> bool:
    return _steady


def reset() -> None:
    """Drop the event log and leave steady-state mode (tests/benches)."""
    global _steady
    _steady = False
    with _lock:
        _events.clear()


def _signature(args: tuple, kwargs: dict) -> Tuple:
    """Shape/dtype signature of the array arguments — the bucket key a
    compile was keyed on."""
    try:
        import jax
        leaves = jax.tree.leaves((args, kwargs))
    except Exception:
        leaves = list(args) + list(kwargs.values())
    return tuple((tuple(x.shape), str(x.dtype)) for x in leaves
                 if hasattr(x, "shape") and hasattr(x, "dtype"))


def record_compile(program: str, seconds: float,
                   signature: Optional[Tuple] = None,
                   cached_programs: Optional[int] = None,
                   analysis: bool = False) -> None:
    """Record one observed compile of ``program`` (counters + event log;
    warns when it happened at steady state). ``analysis=True`` marks a
    deliberate AOT analysis compile (``lower_train_step``,
    ``memory_report``): counted in the compile totals but never a
    steady-state violation — it is not a hot path retracing."""
    ev_total, sec_total, steady_total, progs = _metrics()
    ev_total.labels(program=program).inc()
    sec_total.labels(program=program).inc(max(float(seconds), 0.0))
    if cached_programs is not None:
        progs.labels(program=program).set(cached_programs)
    rec = {"program": program, "seconds": float(seconds),
           "signature": signature, "steady_state": _steady and not analysis,
           "time": time.time()}
    with _lock:
        _events.append(rec)
    # mirror into the flight recorder: a compile near an incident is a
    # prime suspect, and the black box should hold it without anyone
    # having to correlate the watchdog's own deque after the fact
    from . import recorder as ds_recorder
    ds_recorder.record(
        "xla_compile", program=program, seconds=round(float(seconds), 4),
        signature=repr(signature) if signature else None,
        steady_state=_steady and not analysis, analysis=analysis)
    if _steady and not analysis:
        steady_total.labels(program=program).inc()
        logger.warning(
            f"steady-state recompile: program={program!r} took "
            f"{seconds * 1e3:.1f}ms for shapes {signature} — a bucketed "
            f"path retraced after warmup (check bucket keys / weak types)")


def events() -> List[Dict[str, Any]]:
    """The recent compile events (oldest first, bounded)."""
    with _lock:
        return list(_events)


def summary() -> Dict[str, Dict[str, float]]:
    """Per-program rollup: {program: {compiles, seconds,
    steady_state_recompiles}}. Built from the registry counters — the
    authoritative totals — not the bounded event log, so a long-lived
    server's /statusz matches /metrics even after the deque wraps."""
    reg = get_registry()
    out: Dict[str, Dict[str, float]] = {}
    for metric, key in (
            ("xla_compile_events_total", "compiles"),
            ("xla_compile_seconds_total", "seconds"),
            ("xla_steady_state_recompiles_total",
             "steady_state_recompiles")):
        fam = reg.get(metric)
        if fam is None:
            continue
        for values, s in fam.series():
            prog = values[0] if values else ""
            out.setdefault(prog, {"compiles": 0, "seconds": 0.0,
                                  "steady_state_recompiles": 0})[key] = \
                s.value
    return out


def _abstract(x):
    """An array argument as its shape and type (and its sharding where
    it spans devices: a single-device placement is the default and says
    nothing); anything else as it is."""
    if not (hasattr(x, "shape") and hasattr(x, "dtype")):
        return x
    import jax
    sharding = getattr(x, "sharding", None)
    if sharding is not None and len(sharding.device_set) < 2:
        sharding = None
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


class WatchedFunction:
    """Transparent proxy over a jitted callable: forwards calls and
    attribute access (``.lower``, ``._cache_size`` keep working),
    recording a compile event whenever the jit cache grows. On that
    event it also offers the program's scope map
    (``memory.offer_executable``): the abstract signature of the call
    that compiled and a thunk that lowers and compiles it again (a
    cache hit) when ``memory.scopes(program)`` is first asked for. The
    thunk holds the jitted function and shapes, no array."""

    def __init__(self, program: str, fn: Callable):
        self.program = program
        self._fn = fn
        _install_listener()

    def __call__(self, *args, **kwargs):
        fn = self._fn
        try:
            before = fn._cache_size()
        except Exception:
            before = None
        _tls.compile_s = 0.0
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        if before is not None:
            try:
                after = fn._cache_size()
            except Exception:
                after = before
            if after > before:
                compile_s = getattr(_tls, "compile_s", 0.0)
                record_compile(
                    self.program,
                    compile_s if compile_s > 0
                    else time.perf_counter() - t0,
                    signature=_signature(args, kwargs),
                    cached_programs=after)
                self._offer_scopes(args, kwargs)
        return out

    def _offer_scopes(self, args, kwargs) -> None:
        try:
            import jax
            a, k = jax.tree.map(_abstract, (args, kwargs))
            fn = self._fn
            from . import memory
            memory.offer_executable(
                self.program, lambda: fn.lower(*a, **k).compile())
        except Exception:   # a map nobody may ask for never blocks a call
            pass

    def __getattr__(self, name):
        return getattr(self._fn, name)

    def __repr__(self) -> str:
        return f"WatchedFunction({self.program!r}, {self._fn!r})"


def watch(program: str, fn: Callable) -> WatchedFunction:
    """Wrap ``fn`` (typically ``jax.jit(...)``) so its compiles are
    counted under ``program``. Idempotent on already-watched functions."""
    if isinstance(fn, WatchedFunction):
        return fn
    return WatchedFunction(program, fn)


def watch_jit(program: str, fn: Callable, **jit_kwargs) -> WatchedFunction:
    """``watch(program, jax.jit(fn, **jit_kwargs))`` with ``fn`` named
    for the program first: jax calls the module it compiles
    ``jit_<fn.__name__>``, which is what a device trace's "XLA Modules"
    line shows for every launch, and a lambda's name says nothing."""
    import jax
    fn.__name__ = fn.__qualname__ = program
    return watch(program, jax.jit(fn, **jit_kwargs))
