"""The GROUPED state-space (Mamba-2, B and C a group of heads) layers'
recurrence's share of its roofline over the traced slice, %, in either
of its forms (``params["form"]``), as ``ssm_roofline.py`` reads the
one-group block's, over ``arith_nemotron.py``'s floors:

* ``step`` (the default): the one-token state update of the decode
  steps (``arith_nemotron.state_least_seconds``: every row's state in
  every state-space layer read once and written once at every decode
  step);
* ``prompt``: the recurrence over the rows' prompt tokens
  (``arith_nemotron.scan_least_seconds``: each token's x, y, every
  group's B and C, and dt at the activations' width, a row's state
  written once a launch of the prompt and read once by every launch but
  the first);

over the device self time of the operations the program's scope maps
put under ``params["phases"]`` (scope ``ssm_state`` / ``ssm_scan``;
``serve_scope_time``). The slice is one whole ``generate()`` call. A run
without a slice, a program without such a scope or a configuration
without state-space layers reads nothing. params: ``phases``,
``form``."""

from .. import arith, arith_nemotron
from . import serve_scope_time

ITEMSIZE = {"float32": 4, "bfloat16": 2}


def read(ev, params):
    fields, tr = ev.ctx.fields, ev.ctx.traffic
    if not arith_nemotron.ssm_layers(fields):
        return None
    ms = serve_scope_time.read(ev, {"phases": params["phases"]})
    if ms is None:
        return None
    peaks = arith.peaks(ev.ctx.devices[0].device_kind)
    engine = ev.ctx.cell["engine"]
    kept = ITEMSIZE[engine.get("state_dtype", "float32")]
    if params.get("form", "step") == "prompt":
        budget = engine["state_manager"]["max_ragged_batch_size"]
        chunks = -(-tr["rows"] * tr["prompt_len"] // budget)
        least = arith_nemotron.scan_least_seconds(
            fields, tr["rows"], tr["prompt_len"], peaks, chunks, kept)
    else:
        least = arith_nemotron.state_least_seconds(
            fields, tr["rows"], tr["new_tokens"] - 1, peaks, kept)
    # ``ms`` is a call's, a chip's; so is ``least``
    return arith.roofline_percent(least, ms / 1e3)
