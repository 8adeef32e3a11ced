"""Share of the traced slice in which no operation ran on the device, %
(1 - union of device-op intervals over the slice, averaged over chips)."""

from .. import tracing


def read(ev, params):
    if not tracing.device_planes(ev.events):
        return None
    return tracing.idle_percent(ev.events)
