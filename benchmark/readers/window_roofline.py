"""The per-head attention kernels' share of their roofline over the
traced slice, %, for a configuration whose layer PATTERN mixes window
and full layers (``fields["layer_types"]``): the least seconds the
pattern's attention could take for the slice's launches
(``arith_window.least_seconds``: launch by launch and layer kind by
layer kind the larger of a byte floor, a row's VISIBLE positions once
and each new token's query and output, and a FLOP floor) over the device
self time of the operations matching ``pattern``, the window launches
and the full ones together. A run without a slice, without the kernels
in it, without the rows, or of a configuration without such a pattern
reads nothing (``ragged_roofline.gen`` is the homogeneous block's
metric). params: ``pattern``."""

from .. import arith, arith_window, tracing


def read(ev, params):
    fields = ev.ctx.fields
    rows = getattr(ev, "launch_rows", None)
    if not ev.events or not rows or not fields.get("layer_types"):
        return None
    seconds = tracing.op_seconds(ev.events, params["pattern"])
    if seconds <= 0:
        return None
    peaks = arith.peaks(ev.ctx.devices[0].device_kind)
    least = arith_window.least_seconds(
        fields, rows, ev.ctx.traffic["rows"], peaks) \
        * ev.slice_steps / len(ev.ctx.devices)
    return arith.roofline_percent(least, seconds)
