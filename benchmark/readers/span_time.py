"""The host's own work in a training step, ms: the median over the
window's steps of the program's spans named in ``spans``, summed per
step, from the program's span ring (``deepspeed_tpu.telemetry.trace``;
host clock). With ``train_data``, ``train_device_dispatch`` and
``train_bookkeeping`` that is everything ``train_batch`` does outside
``train_host_sync``, where the host waits for the device.

A span belongs to the step its ``step`` attribute names, or its parent
span's. params: ``spans`` (list), ``skip_steps`` (the warm-up steps the
runner makes before the window). A program that lacks one of the spans
gives None."""

import statistics
from collections import defaultdict


def read(ev, params):
    try:
        from deepspeed_tpu.telemetry import trace
    except ImportError:
        return None
    ring = trace.export()
    step_of_id = {s["id"]: s["attrs"]["step"] for s in ring
                  if "step" in s.get("attrs", {})}
    per_step = defaultdict(lambda: defaultdict(float))
    for s in ring:
        if s["name"] in params["spans"]:
            step = step_of_id.get(s["id"], step_of_id.get(s.get("parent")))
            if step is not None:
                per_step[step][s["name"]] += s["duration_s"]
    whole = [sum(spans.values()) for step, spans in sorted(per_step.items())
             if len(spans) == len(params["spans"])]
    whole = whole[params.get("skip_steps", 0):]
    if not whole:
        return None
    return 1e3 * statistics.median(whole)
