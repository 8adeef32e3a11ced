"""The state-space (Mamba-2) layers' recurrence's share of its roofline
over the traced slice, %, in either of its forms (``params["form"]``):

* ``step`` (the default): the one-token state update of the decode
  steps (``arith_ssm.least_seconds``: every row's state in every
  state-space layer read once and written once at every decode step,
  over the chip's HBM bandwidth, or its operations over the peak if
  larger);
* ``prompt``: the recurrence over the rows' prompt tokens
  (``arith_ssm.prompt_least_seconds``: each token's x, B, C and dt in
  and y out, a row's state written once a launch of the prompt and read
  once by every launch but the first, or the recurrence's own
  operations over the peak if larger);

over the device self time of the operations the program's scope maps
put under ``params["phases"]`` (scope ``ssm_state``: the state's way out
of its slot, the update and its way back; scope ``ssm_scan``: the
chunked form; whatever implements them; ``serve_scope_time``). The slice
is one whole ``generate()`` call: ``rows`` fresh rows of ``prompt_len``
tokens, fed in as many launches as the step's budget
(``max_ragged_batch_size``) makes of them, then ``new_tokens - 1``
decode steps of ``rows`` rows. A run without a slice, a program without
such a scope (the parent of the PR that added it) or a configuration
without state-space layers reads nothing. params: ``phases``,
``form``."""

from .. import arith, arith_ssm
from . import serve_scope_time

ITEMSIZE = {"float32": 4, "bfloat16": 2}


def read(ev, params):
    fields, tr = ev.ctx.fields, ev.ctx.traffic
    if not arith_ssm.ssm_layers(fields):
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
        least = arith_ssm.prompt_least_seconds(
            fields, tr["rows"], tr["prompt_len"], peaks, chunks, kept)
    else:
        least = arith_ssm.least_seconds(
            fields, tr["rows"], tr["new_tokens"] - 1, peaks, kept)
    # ``ms`` is a call's, a chip's; so is ``least``
    return arith.roofline_percent(least, ms / 1e3)
