"""An attr of the program's spans of one name, reduced over the window:
the spans of the program's ring named ``span`` less the first ``skip``
(the warm-up steps a runner makes before its window), the value each
carries under ``attr``, their ``reduce`` (``median`` or ``mean``) times
``scale`` (1e3: an attr in seconds, a metric in ms).

With ``cpu_s`` of ``generate`` that is the calling thread's CPU time a
call, the host's "time busy" (``host_cpu_ms.gen``); with ``runq_s``,
where a kernel gives it, the time it sat runnable and was not run,
"time work waited for it" (``deepspeed_tpu.telemetry.collector
.thread_usage``, sampled at the span's open and close). Host clock and
the kernel's per-thread counts; it needs no device trace, and is read
from the window's whole ring.

None where the ring holds no such span or none carries the attr (the
parent of the PR that added it), and where every value is None (a
kernel without ``schedstat`` has no run-queue wait to give: never 0)."""

import statistics

REDUCE = {"median": statistics.median, "mean": statistics.fmean}


def read(ev, params):
    try:
        from deepspeed_tpu.telemetry import trace
    except ImportError:
        return None
    return reduced(trace.export(), params["span"], params["attr"],
                   params["reduce"], params.get("skip", 0),
                   params.get("scale", 1.0))


def reduced(ring, span, attr, reduce, skip=0, scale=1.0):
    spans = sorted((s for s in ring if s["name"] == span),
                   key=lambda s: s["start"])[skip:]
    values = [s["attrs"][attr] for s in spans
              if s.get("attrs", {}).get(attr) is not None]
    if not values:
        return None
    return scale * REDUCE[reduce](values)
