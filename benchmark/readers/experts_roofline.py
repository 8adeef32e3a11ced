"""The routed experts' grouped matmuls' share of their roofline over the
traced slice, %: the least seconds they could take
(``arith_experts.least_seconds``: by program kind, a pass's touched
experts' weights once over the chip's HBM bandwidth or its rows'
operations over the bf16 peak, whichever is larger, times the passes of
that kind in the slice) over the device self time of the operations
matching ``pattern``. The touched experts and the rows are the program's
own counters (``moe_counters.per_pass``, means a pass); the passes in
the slice are the cell's traffic: one whole ``generate()`` call is one
ragged step and ``new_tokens - 1`` decode steps, each over every expert
layer. A run without a slice, without the kernels in it or without the
counters reads nothing. params: ``pattern``."""

from .. import arith, arith_experts, tracing
from . import moe_counters


def read(ev, params):
    if not ev.events or not ev.slice_steps:
        return None
    seconds = tracing.op_seconds(ev.events, params["pattern"])
    if seconds <= 0:
        return None
    fields, tr = ev.ctx.fields, ev.ctx.traffic
    layers = arith_experts.expert_layers(fields)
    kinds = []
    for program, launches in (("ragged_step", 1),
                              ("decode_window", tr["new_tokens"] - 1)):
        got = moe_counters.per_pass(program)
        if got is None:
            return None
        kinds.append((ev.slice_steps * launches * layers, *got))
    peaks = arith.peaks(ev.ctx.devices[0].device_kind)
    return arith.roofline_percent(
        arith_experts.least_seconds(fields, kinds, peaks), seconds)
