"""A gauge of the program's registry (``docs/TELEMETRY.md``), times
``params["scale"]`` (1 where left out): ``params["name"]``, unlabelled.
Where the program has no such gauge (the parent of the PR that added
it), or it was never set, there is nothing to read."""


def read(ev, params):
    try:
        from deepspeed_tpu.telemetry import get_registry
    except ImportError:
        return None
    fam = get_registry().get(params["name"])
    if fam is None:
        return None
    try:
        value = fam.value
    except (ValueError, AttributeError):
        return None
    return value * params.get("scale", 1.0) if value > 0 else None
