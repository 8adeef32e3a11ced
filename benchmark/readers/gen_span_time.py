"""The host's own work in a decode window of ``generate()``, ms: the
median over the window's decode windows of the program's leaf spans
named in ``spans``, summed per decode window, from the program's span
ring (``deepspeed_tpu.telemetry.trace``; host clock; it needs no device
trace). With ``gen_schedule``, ``window_assemble``, ``window_dispatch``
and ``window_bookkeeping`` that is everything the host does a window
outside ``window_fetch``, where it waits for the device:
``host_ms.train``'s twin.

A leaf belongs to the ``decode_window`` span that is its parent; a leaf
beside it under the same ``generate`` root (same ``parent``) belongs to
the decode window that follows it (``before``: ``gen_schedule``) or that
it follows (the rest: ``window_bookkeeping``). A decode window counts
where it has every span of ``spans``. A program without the leaves (the
parent of the PR that added them) gives None."""

import statistics
from collections import defaultdict


def read(ev, params):
    try:
        from deepspeed_tpu.telemetry import trace
    except ImportError:
        return None
    whole = per_window(trace.export(), params["spans"],
                       params.get("before", ()))
    if not whole:
        return None
    return 1e3 * statistics.median(whole)


def per_window(ring, spans, before=()):
    """Seconds of ``spans`` summed per ``decode_window`` span of
    ``ring``, in the ring's order of starts; only the windows that have
    them all."""
    sums = defaultdict(lambda: defaultdict(float))      # window id -> name
    waiting = defaultdict(lambda: defaultdict(float))   # root -> name
    last = {}                                           # root -> window id
    order, windows = [], set()
    for s in sorted(ring, key=lambda s: s["start"]):
        name, root = s["name"], s.get("parent")
        if name == "decode_window":
            order.append(s["id"])
            windows.add(s["id"])
            last[root] = s["id"]
            for k, v in waiting.pop(root, {}).items():
                sums[s["id"]][k] += v
        elif name not in spans:
            continue
        elif root in windows:
            sums[root][name] += s["duration_s"]         # the window's child
        elif name in before:
            waiting[root][name] += s["duration_s"]
        elif root in last:
            sums[last[root]][name] += s["duration_s"]
    return [sum(sums[w].values()) for w in order
            if len(sums[w]) == len(spans)]
