"""A memory-bound kernel's share of its HBM roofline over the traced
slice, %: the least seconds the chip could take, which is the bytes the
kernel must move (``arith_gen.ragged_attention_bytes`` over the slice's
``launch_rows``: a row's attended keys and values once a launch, each new
token's query and output once) over the chip's published HBM bandwidth,
divided by the device self time of the operations matching ``pattern``
(``tracing.self_intervals``). Bandwidth bounds it: attention over a
paged cache does 1 flop a byte at decode and ~67 in a 256-token prefill,
against the chip's 240. A run without a slice, without the kernel in it
or without the rows reads nothing. params: ``pattern``."""

from .. import arith, arith_gen, tracing


def read(ev, params):
    rows = getattr(ev, "launch_rows", None)
    if not ev.events or not rows:
        return None
    seconds = tracing.op_seconds(ev.events, params["pattern"])
    if seconds <= 0:
        return None
    moved = arith_gen.ragged_attention_bytes(ev.ctx.fields, rows) \
        * ev.slice_steps / len(ev.ctx.devices)
    peak = arith.peaks(ev.ctx.devices[0].device_kind)["hbm_bytes_per_s"]
    return arith.roofline_percent(moved / peak, seconds)
