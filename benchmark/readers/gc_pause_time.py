"""What the garbage collector's pauses cost the program's own work, ms:
the ``gc_pause`` spans of the program's ring
(``deepspeed_tpu.telemetry.collector``: one a collection of 1 ms or
more, on the ring's clock) laid over the spans named in ``inside``
(``generate``: a call; ``train_data``, ``train_step`` and
``train_bookkeeping``: a step has one of each), the overlap summed and
divided by the number of spans of the first name, each name less its
first ``skip`` spans (the warm-up steps a runner makes before its
window). Host clock; it needs no device trace, and is read from the
window's whole ring.

0.0 where no pause fell inside. None where the program has no collector
hook (the parent of the PR that added it) or the ring holds no span of
``inside``."""


def read(ev, params):
    try:
        from deepspeed_tpu.telemetry import collector, trace
    except ImportError:
        return None
    del collector           # asked for only to know the hook is there
    return per_span(trace.export(), params["inside"], params.get("skip", 0))


def per_span(ring, inside, skip=0):
    """ms of ``gc_pause`` spans inside the spans of ``ring`` named in
    ``inside`` (each name but its first ``skip``), over the number of
    spans of ``inside[0]``; None without any."""
    by_name = [sorted((s["start"], s["start"] + s["duration_s"])
                      for s in ring if s["name"] == name)[skip:]
               for name in inside]
    if not by_name[0]:
        return None
    outer = [span for spans in by_name for span in spans]
    pauses = [(s["start"], s["start"] + s["duration_s"])
              for s in ring if s["name"] == "gc_pause"]
    seconds = sum(max(0.0, min(hi, e) - max(lo, s))
                  for lo, hi in outer for s, e in pauses)
    return 1e3 * seconds / len(by_name[0])
