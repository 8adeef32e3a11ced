"""Device time of the operations matching ``pattern`` over the device's
busy time, in %. params: ``pattern`` (regex on the instruction name)."""

from .. import tracing


def read(ev, params):
    if not tracing.device_planes(ev.events):
        return None
    if tracing.op_seconds(ev.events, params["pattern"]) <= 0:
        return None     # no such operation in the trace: nothing to read
    return tracing.op_share_percent(ev.events, params["pattern"])
