"""The latent attention kernel's share of its roofline over the traced
slice, %: the least seconds attention over a latent pool could take for
the slice's launches (``arith_latent.least_seconds``: launch by launch
the larger of a byte floor, a row's cached positions once and each new
token's query and output at their smallest form, and a FLOP floor in the
cheaper, expanded form) over the device self time of the operations
matching ``pattern``. A run without a slice, without the kernel in it,
without the rows, or of a configuration with no latent reads nothing.
params: ``pattern``."""

from .. import arith, arith_latent, tracing


def read(ev, params):
    rows = getattr(ev, "launch_rows", None)
    if not ev.events or not rows or "kv_lora_rank" not in ev.ctx.fields:
        return None
    seconds = tracing.op_seconds(ev.events, params["pattern"])
    if seconds <= 0:
        return None
    peaks = arith.peaks(ev.ctx.devices[0].device_kind)
    least = arith_latent.least_seconds(
        ev.ctx.fields, rows, ev.ctx.traffic["rows"], peaks) \
        * ev.slice_steps / len(ev.ctx.devices)
    return arith.roofline_percent(least, seconds)
