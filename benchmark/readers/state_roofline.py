"""The linear-attention layers' recurrence's share of its roofline over
the traced slice, %, in either of its forms (``params["form"]``):

* ``step`` (the default): the one-token state update of the decode
  steps: the least seconds the slice's decode updates could take
  (``arith_state.least_seconds``: every row's state in every linear
  layer read once and written once at every decode step, over the
  chip's HBM bandwidth, or its operations over the peak if larger);
* ``prompt``: the recurrence over the rows' prompt tokens
  (``arith_state.prompt_least_seconds``: each token's inputs in and
  output out, a row's state written once, or the recurrence's own
  operations over the peak if larger);

over the device self time of the operations the program's scope maps
put under ``params["phases"]`` (scope ``kda_state``: the state's way out
of its slot, the update and its way back; scope ``kda_chunk``: the
chunked form; whatever implements them; ``serve_scope_time``). The slice
is one whole ``generate()`` call: ``rows`` fresh rows of ``prompt_len``
tokens, then ``new_tokens - 1`` decode steps of ``rows`` rows. A run
without a slice, a program without such a scope (the parent of the PR
that added it) or a configuration without linear layers reads nothing.
params: ``phases``, ``form``."""

from .. import arith, arith_state
from . import serve_scope_time

ITEMSIZE = {"float32": 4, "bfloat16": 2}


def read(ev, params):
    fields, tr = ev.ctx.fields, ev.ctx.traffic
    if not fields.get("linear_attn_period"):
        return None
    ms = serve_scope_time.read(ev, {"phases": params["phases"]})
    if ms is None:
        return None
    peaks = arith.peaks(ev.ctx.devices[0].device_kind)
    kept = ITEMSIZE[ev.ctx.cell["engine"].get("state_dtype", "float32")]
    if params.get("form", "step") == "prompt":
        least = arith_state.prompt_least_seconds(
            fields, tr["rows"], tr["prompt_len"], peaks, kept)
    else:
        least = arith_state.least_seconds(
            fields, tr["rows"], tr["new_tokens"] - 1, peaks, kept)
    # ``ms`` is a call's, a chip's; so is ``least``
    return arith.roofline_percent(least, ms / 1e3)
