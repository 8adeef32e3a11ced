"""Device time of one traced ``generate()`` call by program and by
phase, from the device trace and the scope maps the program offers.

A trace names an operation by its HLO instruction (``fusion.147``), and
the ragged step's ``fusion.147`` is another instruction than the decode
window's. So an operation is joined to the launch that encloses it on
its plane (the "XLA Modules" line: ``jit_ragged_step(...)``), the
launch's name to its program (``jit_<program>``: every serving jit is
named for its ``telemetry.watchdog`` program), and the instruction to
THAT program's map (``deepspeed_tpu.telemetry.memory.scopes(program)``:
``{instruction name: op_name}``), which
``deepspeed_tpu.utils.xla_profile.serve_phase`` reads as a phase:
embed, attn_proj, kv_write, attn_kernel, mlp, router, experts, head,
pick, other.

A program that compiled under several signatures (two widths of the
block table in one call, say) ran as several executables, each with
instruction names of its own, and a launch does not say which it was.
So the program offers one map a signature
(``memory.scopes_offered(program)``) and an instruction is given a phase
only where every map that knows its name puts it in the same one; a name
the maps disagree on counts as not known, which ``coverage`` shows.

Every sum is of self times (``tracing.self_times``), ms a call (the
slice is one call), mean over chips:

* ``programs: [...]``: the operations inside the launches of the
  programs whose name starts with one of these (``ragged_step``;
  ``decode``), whatever their phase: together they are the busy time.
* ``phases: [...]``: the operations whose instruction its program's map
  puts in one of these phases, every program.
* ``coverage: true``: % of the operations' self time whose instruction
  a map knows and puts in a phase other than ``other``.

A program without names (launches called ``jit__lambda_``), maps or
``serve_phase`` (the parent of the PR that added them) gives None, and
so does a trace with no operation of what was asked."""

import bisect
import re

from .. import tracing
from ..evidence import instruction

LAUNCH = re.compile(r"^jit_(?P<program>.+?)(?:\(\d+\))?$")


def program_of(launch_name):
    m = LAUNCH.match(launch_name)
    return m.group("program") if m else None


def by_launch(events, plane):
    """``(program or None, event, self seconds)`` of every operation of
    ``plane``: the program is that of the launch whose interval holds
    the operation's start."""
    launches = tracing.modules(events, plane)
    starts = [m.start_s for m in launches]
    out = []
    for e, s in tracing.self_times(events, plane):
        i = bisect.bisect_right(starts, e.start_s + 1e-12) - 1
        inside = i >= 0 and e.start_s < launches[i].end_s + 1e-12
        out.append((program_of(launches[i].name) if inside else None, e, s))
    return out


def phased(rows, maps, serve_phase):
    """``(program, phase or None, seconds)``: None for an instruction
    that none of its program's maps knows, or that two of them put in
    different phases."""
    out = []
    for program, e, s in rows:
        name = instruction(e.name)
        phases = {serve_phase(m[name]) for m in maps.get(program, ())
                  if name in m}
        out.append((program, phases.pop() if len(phases) == 1 else None, s))
    return out


def offered_maps(programs):
    """``({program: [a map a signature]}, serve_phase)`` of the
    programs that offer any, or (None, None) where the program has
    neither."""
    try:
        from deepspeed_tpu.telemetry import memory
        from deepspeed_tpu.utils.xla_profile import serve_phase
    except ImportError:
        return None, None
    maps = {p: [m for m in memory.scopes_offered(p) if m]
            for p in programs if p is not None}
    return {p: ms for p, ms in maps.items() if ms}, serve_phase


def read(ev, params):
    planes = tracing.device_planes(ev.events)
    if not planes or not ev.slice_steps:
        return None
    rows = [r for p in planes for r in by_launch(ev.events, p)]
    maps, serve_phase = offered_maps({program for program, _, _ in rows})
    if not maps:
        return None
    rows = phased(rows, maps, serve_phase)
    if params.get("coverage"):
        all_s = sum(s for _, _, s in rows)
        known = sum(s for _, phase, s in rows
                    if phase is not None and phase != "other")
        return 100.0 * known / all_s if all_s > 0 else None
    if "programs" in params:
        wanted = tuple(params["programs"])
        seconds = sum(s for program, _, s in rows
                      if program is not None and program.startswith(wanted))
    else:
        wanted = set(params["phases"])
        seconds = sum(s for _, phase, s in rows if phase in wanted)
    if seconds <= 0:
        return None
    return 1e3 * seconds / len(planes) / ev.slice_steps
