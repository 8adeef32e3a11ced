"""Tokens a second a chip of the window's MEDIAN pass over the runner's
distinct batches (``arith.steady_rate``), from the host clock's step
times: the steadier statistic beside ``train_tok_s``, which is all the
steps over all their time. One stall of the host, or the profiler's start
and stop in a traced run, spoils one pass and does not move it; what the
program pays every few steps is in every pass and does. A window of
fewer than two whole passes reads nothing."""

from .. import arith


def read(ev, params):
    got = arith.steady_rate(ev.step_seconds,
                            ev.ctx.traffic["distinct_batches"],
                            ev.tokens_per_step)
    return got and got / len(ev.ctx.devices)
