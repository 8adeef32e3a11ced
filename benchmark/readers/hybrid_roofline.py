"""The two-mixer layers' state-space recurrence's share of its roofline
over the traced slice, %, in either of its forms (``params["form"]``),
as ``ssm_grouped_roofline.py`` reads another block's, over
``arith_falcon_h1.py``'s floors (a state of 256 a channel, B and C in
two groups, the layers ``mamba_attention`` entries):

* ``step`` (the default): the one-token state update of the decode
  steps (``arith_falcon_h1.state_least_seconds``: every row's state in
  every layer read once and written once at every decode step);
* ``prompt``: the recurrence over the rows' prompt tokens
  (``arith_falcon_h1.scan_least_seconds``: each token's x, y, both
  groups' B and C at the activations' width and dt, a row's state
  written once a launch of the prompt and read once by every launch but
  the first);

over the device self time of the operations the program's scope maps
put under ``params["phases"]`` (scope ``ssm_state`` / ``ssm_scan``,
which stand inside the two-mixer scope; ``serve_scope_time``). The slice
is one whole ``generate()`` call. A run without a slice, a program
without such a scope (the parent of the PR that added the layer) or a
configuration without two-mixer layers reads nothing. params:
``phases``, ``form``."""

from .. import arith, arith_falcon_h1
from . import serve_scope_time

ITEMSIZE = {"float32": 4, "bfloat16": 2}


def read(ev, params):
    fields, tr = ev.ctx.fields, ev.ctx.traffic
    if not arith_falcon_h1.hybrid_layers(fields):
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
        least = arith_falcon_h1.scan_least_seconds(
            fields, tr["rows"], tr["prompt_len"], peaks, chunks, kept)
    else:
        least = arith_falcon_h1.state_least_seconds(
            fields, tr["rows"], tr["new_tokens"] - 1, peaks, kept)
    # ``ms`` is a call's, a chip's; so is ``least``
    return arith.roofline_percent(least, ms / 1e3)
