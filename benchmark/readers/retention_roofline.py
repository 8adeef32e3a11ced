"""The power-retention layers' core's share of its roofline over the
traced slice, %, in either of its forms (``params["form"]``):

* ``step`` (the default): the one-token state update of the decode
  steps (``arith_retention.least_seconds``: every row's state in every
  layer read once and written once at every decode step, with the
  token's q, k, v, gate and output, over the chip's HBM bandwidth, or
  its operations over the peak if larger);
* ``prompt``: the recurrence over the rows' prompt tokens
  (``arith_retention.prompt_least_seconds``: the larger of ``phi(Q) S``
  and ``phi(K)^T V`` at the MXU's rate for the precision the products
  run in, ``params["mxu_passes"]`` bf16 passes a product, and of each
  token's q, k, v, gate and output with a row's state once a launch
  each way over the chip's HBM bandwidth);

over the device self time of the operations the program's scope maps
put under ``params["phases"]`` (scope ``retention_state``: the state's
way out of its slot, the update and its way back; scope
``retention_chunk``: the chunked form; whatever implements them: the
XLA twin's time counts against the same floor; ``serve_scope_time``).
The slice is one whole ``generate()`` call: ``rows`` fresh rows of
``prompt_len`` tokens, fed in as many launches as the step's budget
(``max_ragged_batch_size``) makes of them, then ``new_tokens - 1``
decode steps of ``rows`` rows. A run without a slice, a program without
such a scope (the parent of the PR that added it) or a configuration
without retention layers reads nothing. params: ``phases``, ``form``,
``mxu_passes``."""

from .. import arith, arith_retention
from . import serve_scope_time

ITEMSIZE = {"float32": 4, "bfloat16": 2}


def read(ev, params):
    fields, tr = ev.ctx.fields, ev.ctx.traffic
    if not arith_retention.retention_layers(fields):
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
        least = arith_retention.prompt_least_seconds(
            fields, tr["rows"], tr["prompt_len"], peaks, chunks, kept,
            params.get("mxu_passes", 1))
    else:
        least = arith_retention.least_seconds(
            fields, tr["rows"], tr["new_tokens"] - 1, peaks, kept)
    # ``ms`` is a call's, a chip's; so is ``least``
    return arith.roofline_percent(least, ms / 1e3)
