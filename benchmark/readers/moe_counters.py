"""What the program's expert-layer counters say, by program kind:
``moe_launches_total``, ``moe_routed_rows_total`` and
``moe_experts_touched_total`` of the process's registry, labelled
``program`` (``docs/TELEMETRY.md``). A pass is one expert layer in one
launch of a program. The counters run from the process's start (the
probe and the warm call too); every call of a cell routes the same
number of rows, and its batches differ only in which experts they touch,
so a MEAN a pass over the process stands for a pass of the traced slice.

``read`` is the metric ``experts_touched``: the mean distinct experts a
layer a launch of ``params["program"]``. Where the program has no such
counters, or they are empty, there is nothing to read."""


def per_pass(program):
    """(mean experts touched, mean rows routed) a pass of ``program``,
    or None where the registry has nothing of it."""
    try:
        from deepspeed_tpu.telemetry import get_registry
    except ImportError:
        return None
    reg = get_registry()
    fams = [reg.get(name) for name in ("moe_launches_total",
                                       "moe_experts_touched_total",
                                       "moe_routed_rows_total")]
    if any(f is None for f in fams):
        return None
    try:
        passes, touched, rows = (f.labels(program=program).value
                                 for f in fams)
    except (ValueError, AttributeError):
        return None
    if passes <= 0:
        return None
    return touched / passes, rows / passes


def read(ev, params):
    got = per_pass(params["program"])
    return None if got is None else got[0]
