"""What the program's own retroactive spans of one name cost its work,
ms: the spans of the program's ring named ``span`` (``host_stall``: one
a leaf span of a call that ran over the rolling median of its kind by
max(50 ms, 25 %), over the excess; ``deepspeed_tpu.telemetry.collector``)
laid over the spans named in ``inside`` (``generate``: a call;
``train_step``, ``train_data`` and ``train_bookkeeping``: a step has one
of each), the overlap summed and divided by the number of spans of the
first name, each name less its first ``skip`` spans (the warm-up steps a
runner makes before its window). ``readers/gc_pause_time.per_span`` with
the span's name a parameter. Host clock; it needs no device trace, and
is read from the window's whole ring.

0.0 where none fell inside: a sound call. None where the ring holds no
span of ``inside``, and where none of ``inside[0]`` carries the attr
``marked_by``: the program that records the span marks those with what
it sampled (``cpu_s``), and on one that does not (the parent of the PR
that added it) no span would not mean no stall."""


def read(ev, params):
    try:
        from deepspeed_tpu.telemetry import trace
    except ImportError:
        return None
    return per_span(trace.export(), params["span"], params["inside"],
                    params.get("skip", 0), params.get("marked_by"))


def per_span(ring, span, inside, skip=0, marked_by=None):
    """ms of ``span`` spans inside the spans of ``ring`` named in
    ``inside`` (each name but its first ``skip``), over the number of
    spans of ``inside[0]``; None without any, or where ``marked_by`` is
    given and none of them carries it."""
    by_name = [sorted((s["start"], s["start"] + s["duration_s"])
                      for s in ring if s["name"] == name)[skip:]
               for name in inside]
    if not by_name[0]:
        return None
    if marked_by is not None and not any(
            marked_by in s.get("attrs", {}) for s in ring
            if s["name"] == inside[0]):
        return None
    outer = [pair for pairs in by_name for pair in pairs]
    laid = [(s["start"], s["start"] + s["duration_s"])
            for s in ring if s["name"] == span]
    seconds = sum(max(0.0, min(hi, e) - max(lo, s))
                  for lo, hi in outer for s, e in laid)
    return 1e3 * seconds / len(by_name[0])
