"""Flash attention's share of its compute roofline over the traced
steps, %: the operations causal attention requires forward and backward
(``arith.flash_flops``) over the chip's bf16 peak, divided by the device
time of the flash kernels. Compute-bound at sequence 2048. The kernels'
time includes the forward that activation checkpointing re-runs; its
operations are not counted twice. params: ``pattern``."""

from .. import arith, tracing


def read(ev, params):
    if not ev.events or not ev.slice_steps:
        return None
    seconds = tracing.op_seconds(ev.events, params["pattern"])
    if seconds <= 0:
        return None
    seq = ev.ctx.traffic["seq_len"]
    sequences = ev.slice_steps * ev.tokens_per_step / seq / len(
        ev.ctx.devices)
    flops = arith.flash_flops(ev.ctx.fields, seq, sequences)
    peak = arith.peaks(ev.ctx.devices[0].device_kind)["bf16_flops_per_s"]
    return arith.roofline_percent(flops / peak, seconds)
