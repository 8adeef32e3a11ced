"""One labelled counter family of the program's registry
(``docs/TELEMETRY.md``) over another, each summed over its series:
``params["over"]`` a ``params["per"]`` (the positions read a query).
The counters run from the process's start (the probe and the warm call
too); every call of a cell feeds the same rows and lengths, so a mean
over the process stands for the traced slice. Where the program has no
such families (the parent of the PR that added them), or the
denominator is 0, there is nothing to read."""


def read(ev, params):
    try:
        from deepspeed_tpu.telemetry import get_registry
    except ImportError:
        return None
    fams = [get_registry().get(params[k]) for k in ("over", "per")]
    if any(f is None for f in fams):
        return None
    try:
        over, per = (sum(s.value for _, s in f.series()) for f in fams)
    except (ValueError, AttributeError, TypeError):
        return None
    return over / per if per > 0 else None
