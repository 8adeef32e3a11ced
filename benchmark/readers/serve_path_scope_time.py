"""Device time of one traced ``generate()`` call under a scope ANYWHERE
on an operation's path, by program: what a scope that wraps other
scopes holds.

``serve_scope_time.py`` and ``serve_program_scope_time.py`` read an
operation's INNERMOST scope of the program's table
(``xla_profile.serve_scope``), so the scope a two-mixer layer opens
round both of its halves (``hybrid_mixer`` ⊃ {``attention``,
``ssm_mixer``}) is a word they never return: the halves' time stays
where the accepted metrics read it (``attn_proj``, ``ssm`` ...), and
this reader sums it again by the wrapping scope. An operation is joined
to its launch, program and ``op_name`` as
``serve_program_scope_time.call_rows`` does.

* ``programs: [...]``: the launches of the programs whose name starts
  with one of these (``ragged_step``; ``decode``),
* ``within: [...]``: the operations whose ``op_name`` holds one of
  these words as a whole step of its path.

ms a call of self times, mean over chips. None where there is no trace,
the program offers no maps, no launch of the programs is in the call,
or no operation stands under a ``within`` scope (a program that lacks
the layer: the parent of the PR that added it)."""

import re

from .serve_program_scope_time import call_rows


def read(ev, params):
    got = call_rows(ev)
    if got is None:
        return None
    rows, planes = got
    wanted = tuple(params["programs"])
    mine = [(op_name, s) for program, _, op_name, s in rows
            if program is not None and program.startswith(wanted)
            and op_name]

    step = re.compile(r"(?:^|/)(?:%s)(?:/|$)" % "|".join(
        re.escape(w) for w in params["within"]))
    under = [s for op_name, s in mine if step.search(op_name)]
    if not under:
        return None
    return 1e3 * sum(under) / planes / ev.slice_steps
