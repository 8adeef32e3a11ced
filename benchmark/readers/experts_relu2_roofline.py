"""The TWO-matrix (relu^2) routed experts' grouped matmuls' share of
their roofline over the traced slice, %: the least seconds they could
take (``arith_nemotron.experts_least_seconds``: by program kind, a
pass's touched experts' two matrices once over the chip's HBM bandwidth
or its rows' operations over the bf16 peak, whichever is larger, times
the passes of that kind in the slice) over the device self time of the
operations matching ``pattern``. The touched experts and the rows are
the program's own counters (``moe_counters.per_pass``, means a pass);
the passes in the slice are the cell's traffic: one whole ``generate()``
call is as many ragged steps as the step's budget
(``max_ragged_batch_size``) makes of its prompts and ``new_tokens - 1``
decode steps, each over every expert layer (the ``moe`` entries of
``fields.layer_types``). A run without a slice, without the kernels in
it or without the counters, or a configuration without such layers,
reads nothing. params: ``pattern``."""

from .. import arith, arith_nemotron, tracing
from . import moe_counters


def read(ev, params):
    if not ev.events or not ev.slice_steps:
        return None
    fields, tr = ev.ctx.fields, ev.ctx.traffic
    layers = arith_nemotron.expert_layers(fields)
    if not layers:
        return None
    seconds = tracing.op_seconds(ev.events, params["pattern"])
    if seconds <= 0:
        return None
    budget = ev.ctx.cell["engine"]["state_manager"]["max_ragged_batch_size"]
    chunks = -(-tr["rows"] * tr["prompt_len"] // budget)
    kinds = []
    for program, launches in (("ragged_step", chunks),
                              ("decode_window", tr["new_tokens"] - 1)):
        got = moe_counters.per_pass(program)
        if got is None:
            return None
        kinds.append((ev.slice_steps * launches * layers, *got))
    peaks = arith.peaks(ev.ctx.devices[0].device_kind)
    return arith.roofline_percent(
        arith_nemotron.experts_least_seconds(fields, kinds, peaks), seconds)
