"""Device-memory accounting from XLA's own numbers.

XLA already knows every program's device footprint
(``compiled.memory_analysis()``: argument / output / temp / code bytes)
— this module publishes it, chip-free, as registry gauges plus a
one-call OOM-forensics report, instead of leaving it buried in
``benchmarks/aot_scale.py``.

Two record kinds:

  * **programs** — :func:`record_memory_analysis` extracts an
    AOT-compiled program's memory stats (and its cost analysis: flops /
    bytes accessed, the MFU inputs) into
    ``xla_program_{peak,argument,temp,output}_bytes{program=...}``
    gauges. ``runtime.engine.lower_train_step`` records the train step,
    and the record keeps the executable, so that :func:`scopes` can say
    later which phase of the step each of its instructions belongs to;
    ``InferenceEngineV2.memory_report()`` AOT-lowers the decode/prefill
    programs at representative bucket shapes (no chip needed — the
    compiler runs on the host). A serving program offers its executable
    without any of that (:func:`offer_executable`, from the watchdog's
    proxy when the program compiles on its dispatch path), so
    ``scopes("ragged_step")`` answers for the program that really ran.
  * **buffers** — :func:`record_buffer` publishes long-lived allocations
    the programs reference (KV pool, weights, optimizer state) as
    ``device_buffer_bytes{buffer=...}``.

:func:`oom_report` ranks both and names the largest — the first thing to
read after a RESOURCE_EXHAUSTED (docs/PROFILING.md, "Triaging OOMs").
"""

import threading
from typing import Any, Callable, Dict, List, Optional

from .registry import get_registry

_lock = threading.Lock()
_programs: Dict[str, Dict[str, Any]] = {}
_buffers: Dict[str, int] = {}
# per program: a zero-argument callable that returns the ``Compiled`` the
# program runs as, and the scope map built from it on first request
_executables: Dict[str, Callable[[], Any]] = {}
_scopes: Dict[str, Dict[str, str]] = {}
# per program: every executable it was offered as (offer_executable),
# oldest first, each [thunk, its scope map once somebody asked]
_offered: Dict[str, List[list]] = {}
# an offer holds the jitted function, and with it every executable it
# loaded, for as long as it is kept: a program's newest signatures (a
# serving process compiles a few; a test process builds hundreds of
# engines, and XLA:CPU dies loading one more executable once thousands
# are resident)
_OFFERS_KEPT = 8

_MEM_FIELDS = ("argument_size_in_bytes", "output_size_in_bytes",
               "temp_size_in_bytes", "alias_size_in_bytes",
               "generated_code_size_in_bytes")


def _gauges():
    reg = get_registry()
    return {
        "peak": reg.gauge("xla_program_peak_bytes",
                          "arguments + temps + code of a compiled "
                          "program (donated inputs alias outputs)",
                          unit="bytes", labelnames=("program",)),
        "argument": reg.gauge("xla_program_argument_bytes",
                              "argument bytes of a compiled program",
                              unit="bytes", labelnames=("program",)),
        "temp": reg.gauge("xla_program_temp_bytes",
                          "temp/scratch bytes of a compiled program",
                          unit="bytes", labelnames=("program",)),
        "output": reg.gauge("xla_program_output_bytes",
                            "output bytes of a compiled program",
                            unit="bytes", labelnames=("program",)),
    }


def cost_analysis_dict(compiled) -> Dict[str, float]:
    """``compiled.cost_analysis()`` normalized to a plain dict (older
    jax returns ``[dict]``) — the ONE copy of this shim; every reader
    of a compiled program's cost shares it."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return dict(ca or {})


def record_memory_analysis(program: str, compiled,
                           dispatched: Optional[Callable[[], Any]] = None
                           ) -> Dict[str, Any]:
    """Extract ``compiled.memory_analysis()`` (+ ``cost_analysis()``)
    into gauges and the program table; returns the record.

    The executable itself is kept for :func:`scopes` (a reference, not
    its text: nothing is printed or parsed here). Where ``compiled`` was
    built with other options than the program is dispatched with,
    ``dispatched`` returns the executable that really runs; it is called
    on the first :func:`scopes` request, not here."""
    ma = compiled.memory_analysis()
    rec: Dict[str, Any] = {k: int(getattr(ma, k)) for k in _MEM_FIELDS
                           if hasattr(ma, k)}
    # donated inputs alias outputs, so peak live state is args + temps
    # (+ the program text itself) — the aot_scale.py convention
    rec["peak_bytes"] = (rec.get("argument_size_in_bytes", 0)
                         + rec.get("temp_size_in_bytes", 0)
                         + rec.get("generated_code_size_in_bytes", 0))
    try:
        ca = cost_analysis_dict(compiled)
        rec["flops"] = float(ca.get("flops", 0.0))
        rec["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
    except Exception:  # cost analysis is a bonus, never a blocker
        pass
    g = _gauges()
    g["peak"].labels(program=program).set(rec["peak_bytes"])
    g["argument"].labels(program=program).set(
        rec.get("argument_size_in_bytes", 0))
    g["temp"].labels(program=program).set(rec.get("temp_size_in_bytes", 0))
    g["output"].labels(program=program).set(
        rec.get("output_size_in_bytes", 0))
    with _lock:
        _programs[program] = dict(rec)
        _executables[program] = dispatched or (lambda: compiled)
        _scopes.pop(program, None)
    return rec


def offer_executable(program: str, executable: Callable[[], Any]) -> None:
    """Offer ``program``'s scope map at no cost now: ``executable``, a
    zero-argument callable that returns the ``Compiled`` the program
    runs as, is kept and called on the first :func:`scopes` request
    (``telemetry.watchdog.WatchedFunction`` offers one over the abstract
    signature of every call that compiled: lowering again then is a
    compile-cache hit). A program compiled under several signatures
    (bucket shapes) is several executables with instruction names of
    their own: :func:`scopes` answers for the newest,
    :func:`signatures_offered` says how many there were and
    :func:`scopes_offered` gives every one's map, so that a reader of a
    trace in which more than one of them ran trusts a name only where
    the maps agree on it."""
    with _lock:
        _executables[program] = executable
        kept = _offered.setdefault(program, [])
        kept.append([executable, None])
        del kept[:-_OFFERS_KEPT]
        _scopes.pop(program, None)


def signatures_offered(program: str) -> int:
    """How many signatures ``program`` compiled under and offered."""
    with _lock:
        return len(_offered.get(program, ()))


def scopes_offered(program: str) -> List[Dict[str, str]]:
    """One scope map for each executable ``program`` was offered as,
    oldest first (the last is ``scopes(program)``); each is built on the
    first request and kept. A program recorded some other way has the
    one map :func:`scopes` gives; one never recorded has none."""
    from ..utils.xla_profile import scope_map
    with _lock:
        entries = list(_offered.get(program, ()))
        newest = _executables.get(program)
    for entry in entries:
        if entry[1] is None:
            entry[1] = (scopes(program) if entry[0] is newest
                        else scope_map(entry[0]()))
    if entries:
        return [entry[1] for entry in entries]
    only = scopes(program)
    return [] if only is None else [only]


def scopes(program: str) -> Optional[Dict[str, str]]:
    """``{instruction name: op_name}`` of a recorded program
    (``utils.xla_profile.scope_map`` of the executable it runs as), or
    None for a program never recorded. Built on the first request and
    kept; outlives the engine that recorded it."""
    with _lock:
        got, executable = _scopes.get(program), _executables.get(program)
    if got is None and executable is not None:
        from ..utils.xla_profile import scope_map
        got = scope_map(executable())
        with _lock:
            _scopes[program] = got
    return got


def tree_bytes(tree) -> int:
    """Total bytes of a pytree of arrays (KV cache, params, opt state)."""
    import jax
    total = 0
    for leaf in jax.tree.leaves(tree):
        nbytes = getattr(leaf, "nbytes", None)
        if nbytes is None and hasattr(leaf, "shape"):
            import numpy as np
            nbytes = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        total += int(nbytes or 0)
    return total


def record_buffer(name: str, nbytes: int) -> None:
    """Publish a long-lived device allocation (KV pool, weights, ...)."""
    get_registry().gauge(
        "device_buffer_bytes",
        "long-lived device allocations (KV pool, weights, optimizer "
        "state)", unit="bytes", labelnames=("buffer",)).labels(
        buffer=name).set(int(nbytes))
    with _lock:
        _buffers[name] = int(nbytes)


def programs() -> Dict[str, Dict[str, Any]]:
    with _lock:
        return {k: dict(v) for k, v in _programs.items()}


def buffers() -> Dict[str, int]:
    with _lock:
        return dict(_buffers)


def reset() -> None:
    with _lock:
        _programs.clear()
        _buffers.clear()
        _executables.clear()
        _scopes.clear()
        _offered.clear()


def oom_report(top: int = 5) -> Dict[str, Any]:
    """One-call OOM forensics: programs by peak bytes and buffers by
    size, largest first, plus the headline culprit."""
    all_buffers = buffers()
    progs = sorted(
        ({"program": name, **rec} for name, rec in programs().items()),
        key=lambda r: -r.get("peak_bytes", 0))[:top]
    bufs = sorted(({"buffer": name, "bytes": b}
                   for name, b in all_buffers.items()),
                  key=lambda r: -r["bytes"])[:top]
    rep: Dict[str, Any] = {
        "programs": progs,
        "buffers": bufs,
        # the total covers EVERY recorded buffer, not just the top-N
        # shown — a truncated "total" would mislead the OOM triage
        "total_buffer_bytes": sum(all_buffers.values()),
    }
    if progs:
        rep["largest_program"] = progs[0]["program"]
        rep["largest_program_peak_bytes"] = progs[0].get("peak_bytes", 0)
    if bufs:
        rep["largest_buffer"] = bufs[0]["buffer"]
        rep["largest_buffer_bytes"] = bufs[0]["bytes"]
    return rep


def format_oom_report(rep: Optional[Dict[str, Any]] = None) -> str:
    """Human-readable :func:`oom_report` (what to paste into an OOM
    issue)."""
    rep = rep or oom_report()
    lines = ["device-memory forensics (largest first):", "  programs:"]
    for p in rep["programs"]:
        lines.append(
            f"    {p['program']:<24} peak={p.get('peak_bytes', 0) / 2**20:8.1f} MiB "
            f"(args={p.get('argument_size_in_bytes', 0) / 2**20:.1f} "
            f"temps={p.get('temp_size_in_bytes', 0) / 2**20:.1f})")
    lines.append("  buffers:")
    for b in rep["buffers"]:
        lines.append(f"    {b['buffer']:<24} {b['bytes'] / 2**20:8.1f} MiB")
    if not rep["programs"] and not rep["buffers"]:
        lines.append("    (nothing recorded yet — run memory_report() "
                     "or lower_train_step first)")
    return "\n".join(lines)
