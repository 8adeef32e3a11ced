"""A LABELLED gauge family of the program's registry
(``docs/TELEMETRY.md``) summed over its series, times
``params["scale"]`` (1 where left out): ``params["name"]``
(``registry_gauge`` reads an unlabelled one). Where the program has no
such family (the parent of the PR that added it), or nothing in it was
set above 0, there is nothing to read."""


def read(ev, params):
    try:
        from deepspeed_tpu.telemetry import get_registry
    except ImportError:
        return None
    fam = get_registry().get(params["name"])
    if fam is None:
        return None
    try:
        value = sum(s.value for _, s in fam.series())
    except (ValueError, AttributeError, TypeError):
        return None
    return value * params.get("scale", 1.0) if value > 0 else None
