"""Time per training step in which an all-gather, reduce-scatter or
all-reduce ran on a device (ms, mean over chips), or — with
``exposed: true`` — the share of that time in which nothing else ran on
that device (%). From the device trace, not from the compiler's
schedule."""

from .. import tracing


def read(ev, params):
    planes = tracing.device_planes(ev.events)
    if not planes or not ev.slice_steps:
        return None
    pairs = [tracing.collective_seconds(ev.events, p) for p in planes]
    total = sum(t for t, _ in pairs) / len(planes)
    alone = sum(a for _, a in pairs) / len(planes)
    if total <= 0:
        return None
    if params.get("exposed"):
        return 100.0 * alone / total
    return 1e3 * total / ev.slice_steps
