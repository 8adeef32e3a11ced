"""Model FLOP/s utilisation of training, %: tokens/s/chip of the window's
median step (the traced run's profiler stalls a few steps, so not the rate
over all of them) times the operations a token requires
(``arith.train_flops_per_token``: forward + backward, nothing recomputed,
causal half of attention) over the chip's published bf16 peak."""

from .. import arith


def read(ev, params):
    if ev.step_tok_s is None:
        return None
    flops = arith.train_flops_per_token(ev.ctx.fields,
                                        ev.ctx.traffic["seq_len"])
    peak = arith.peaks(ev.ctx.devices[0].device_kind)["bf16_flops_per_s"]
    return arith.mfu_percent(ev.step_tok_s, flops, peak)
