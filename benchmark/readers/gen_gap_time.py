"""Where the device's idle time inside one traced ``generate()`` call
goes, by what the host was doing: from the device trace and the
program's span ring on ONE clock.

The ring's ``start`` is ``perf_counter``; the device trace has the
profiler's clock, and of the host plane the runner keeps only the
``ragged_step`` / ``decode_window`` / ``decode_step`` annotations. A
ring span recorded while annotations were mirrored carries ``annotated``
(``deepspeed_tpu.telemetry.trace``): it is the same span as the host
event of its name, so the two lists pair in order, and the median
difference of their starts is the offset between the clocks
(``clock_offset``). With it every annotated ring span, the leaves too,
stands on the device trace's time axis.

The idle time is the gaps between consecutive launches of the call
(``tracing.program_gaps`` on the "XLA Modules" line) less what an
operation still covers of them. Each is laid over the leaf spans:

* ``what: "host"``: ms a call under ``host_spans`` (the host's own
  work: what trimming host code can win),
* ``what: "launch"``: ms a call under ``launch_spans`` (uploads, launch
  and readback latency: what only launching ahead can win),
* ``what: "unattributed"``: % of the idle time under neither.

None where the program has no annotated spans (the parent of the PR
that added the mark), where the counts of host events and annotated
spans differ, or where a pair lies more than 0.2 ms from the offset."""

import statistics

from .. import tracing

PAIRED = ("ragged_step", "decode_window", "decode_step")


def clock_offset(host_events, ring, names=PAIRED, tolerance_s=2e-4):
    """Seconds to add to a ring span's ``start`` to stand on the host
    events' clock, or None: ``host_events`` (``tracing.Event``) and the
    annotated spans of ``ring`` named in ``names`` pair in order of
    their starts."""
    events = sorted((e for e in host_events if e.name in names),
                    key=lambda e: e.start_s)
    spans = sorted((s for s in ring if s.get("annotated")
                    and s["name"] in names), key=lambda s: s["start"])
    if not events or len(events) != len(spans):
        return None
    if any(e.name != s["name"] for e, s in zip(events, spans)):
        return None
    diffs = [e.start_s - s["start"] for e, s in zip(events, spans)]
    offset = statistics.median(diffs)
    if max(abs(d - offset) for d in diffs) > tolerance_s:
        return None
    return offset


def idle_between_launches(events, plane):
    """The gaps between consecutive launches on ``plane``, less what an
    operation covers of them: sorted disjoint intervals."""
    busy = tracing.busy(events, plane)
    out = []
    for lo, hi in tracing.program_gaps(events, plane):
        at = lo
        for s, e in tracing.clip(busy, lo, hi):
            if s > at:
                out.append((at, s))
            at = max(at, e)
        if hi > at:
            out.append((at, hi))
    return out


def split(gaps, spans, classes):
    """Seconds of ``gaps`` under the spans of each class, and under
    none: ``({class: seconds}, unattributed seconds)``. ``spans`` are
    (name, start, end) on the gaps' clock and do not overlap each other
    (leaves of one thread); ``classes`` is {class: names}."""
    of = {n: c for c, names in classes.items() for n in names}
    out = {c: 0.0 for c in classes}
    covered = 0.0
    for name, s, e in spans:
        if name not in of:
            continue
        ov = tracing.total(tracing.clip(gaps, s, e))
        out[of[name]] += ov
        covered += ov
    return out, tracing.total(gaps) - covered


def read(ev, params):
    planes = tracing.device_planes(ev.events)
    if not planes or not ev.slice_steps:
        return None
    try:
        from deepspeed_tpu.telemetry import trace
    except ImportError:
        return None
    ring = trace.export()
    offset = clock_offset(ev.host_spans(), ring)
    if offset is None:
        return None
    gaps = idle_between_launches(ev.events, planes[0])
    idle = tracing.total(gaps)
    if idle <= 0:
        return None
    spans = [(s["name"], s["start"] + offset,
              s["start"] + offset + s["duration_s"])
             for s in ring if s.get("annotated")]
    by, rest = split(gaps, spans, {"host": params["host_spans"],
                                   "launch": params["launch_spans"]})
    if params["what"] == "unattributed":
        return 100.0 * rest / idle
    return 1e3 * by[params["what"]] / ev.slice_steps
