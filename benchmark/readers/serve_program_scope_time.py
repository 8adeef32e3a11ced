"""Device time of one traced ``generate()`` call by program AND
innermost scope: the inside of ``prefill_ms.gen``.

``serve_scope_time.py`` sums by program OR by phase, and a phase folds
scopes (``ssm_proj``, ``ssm_conv``, ``ssm_gate_norm`` and ``ssm_out``
are all ``ssm``) over every program. Here an operation is joined to the
launch that holds it (``serve_scope_time.by_launch``), the launch to its
program, the instruction to THAT program's maps
(``serve_scope_time.offered_maps``), and its ``op_name`` is read as its
innermost scope by the program's own ``xla_profile.serve_scope``; the
sum by (program family, scope) is the program's too
(``xla_profile.scope_seconds``), so that ``scripts/trace_by_scope.py``
prints the same table this reads.

* ``programs: [...]``: the launches of the programs whose name starts
  with one of these (``ragged_step``),
* ``scopes: [...]``: the scopes summed; ``"other"`` is what no scope
  claims: an instruction no map knows, one whose maps (two signatures
  of one program in one call) put it in different scopes, and one whose
  path holds no scope word.

ms a call of self times (``tracing.self_times``), mean over chips. The
scopes of a program partition its time: the metrics that name every
scope between them add up to ``serve_scope_time``'s ``programs`` sum.
**0.0 where the launches are there and the scopes took no time; None
only where there is no trace, the program offers no maps, or it has no
``serve_scope`` (the parent of the PR that added it).**"""

from .. import tracing
from ..evidence import instruction
from .serve_scope_time import by_launch, offered_maps


def joined(rows, maps, serve_scope):
    """``(program, instruction, op_name or None, seconds)`` of
    ``by_launch``'s rows: the ``op_name`` the program's maps give the
    instruction, None where none knows it or two of them put it in
    different scopes."""
    out = []
    for program, e, s in rows:
        name = instruction(e.name)
        names = [m[name] for m in maps.get(program, ()) if name in m]
        agreed = len({serve_scope(n) for n in names}) == 1
        out.append((program, name, names[0] if agreed else None, s))
    return out


def call_rows(ev):
    """``joined`` rows of every device plane of the traced slice, with
    the number of planes, or None where the trace or the program has
    not what it takes."""
    planes = tracing.device_planes(ev.events)
    if not planes or not ev.slice_steps:
        return None
    try:
        from deepspeed_tpu.utils.xla_profile import serve_scope
    except ImportError:
        return None
    rows = [r for p in planes for r in by_launch(ev.events, p)]
    maps, _ = offered_maps({program for program, _, _ in rows})
    if not maps:
        return None
    return joined(rows, maps, serve_scope), len(planes)


def read(ev, params):
    got = call_rows(ev)
    if got is None:
        return None
    rows, planes = got
    from deepspeed_tpu.utils.xla_profile import scope_seconds
    wanted = tuple(params["programs"])
    mine = [r for r in rows if r[0] is not None and r[0].startswith(wanted)]
    if not mine:
        return None         # no launch of the programs in this call
    scopes = set(params["scopes"])
    seconds = sum(s for (_, scope), s in scope_seconds(mine).items()
                  if scope in scopes)
    return 1e3 * seconds / planes / ev.slice_steps
