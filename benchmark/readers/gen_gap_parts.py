"""``gap_launch_ms.gen`` by the part of a launch the host was in: the
idle time between the launches of one traced ``generate()`` call that
lies under the leaf spans named in ``spans``, ms a call.

``gen_gap_time.py``'s arithmetic (its clock offset, its gaps, its
``split``) over other span lists: a dispatch span holds two leaves of
its own since the PR that added this, ``*_upload`` (the host arrays
handed to the device) and ``*_call`` (the jit call alone), and the
``*_fetch`` spans stand beside it. So ``gap_upload_ms.gen`` +
``gap_call_ms.gen`` + ``gap_fetch_ms.gen`` is ``gap_launch_ms.gen`` but
for the slivers between a dispatch span's two children.

0.0 where the spans are there and no idle time lay under them. None
where ``gen_gap_time`` gives None (no trace, no annotated spans, clocks
that do not pair) and where the ring holds no annotated span of any of
``spans`` (the parent of the PR that added the leaves)."""

from .. import tracing
from .gen_gap_time import clock_offset, idle_between_launches, split


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
    names = set(params["spans"])
    spans = [(s["name"], s["start"] + offset,
              s["start"] + offset + s["duration_s"])
             for s in ring if s.get("annotated") and s["name"] in names]
    if not spans:
        return None
    gaps = idle_between_launches(ev.events, planes[0])
    by, _ = split(gaps, spans, {"part": names})
    return 1e3 * by["part"] / ev.slice_steps
