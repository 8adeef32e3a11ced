"""Device time of the training step by phase, from the device trace and
the scope map the program offers.

A trace names an operation by its HLO instruction (``fusion.491``). The
program keeps, for the step it compiled, ``{instruction name: op_name}``
(``deepspeed_tpu.telemetry.memory.scopes("train_step")``: the
``jax.named_scope`` and autodiff path each instruction was traced under)
and classifies an ``op_name`` into a phase
(``deepspeed_tpu.utils.xla_profile.scope_phase``: forward, recompute,
backward, loss_head, optimizer, grad_reduce, param_gather, other).

params: ``phases``, a list of phases; ``"loss_head/transpose"`` is the
part of a phase that autodiff transposed (its backward) and
``"loss_head/jvp"`` the rest.

* plain: ms a step of the XLA Ops line's operations whose instruction the
  map puts in one of ``phases``, mean over chips. An operation counts
  with its SELF time, its duration less the operations nested in it: on
  the v5e a fusion or a kernel often contains a smaller operation (the
  issue of an async copy), and a loop contains its body. Summing leaf
  operations alone would drop every such container whole (32 ms a step of
  ZeRO-3's 1,042; my chip run, PR 25); self times add up to busy time.
* ``collectives: true``: ms a step in which a collective of those phases
  ran, mean over chips: union of intervals over leaf operations and the
  async line, the population ``collective_ms.train`` is made of.
* ``coverage: true``: % of the operations' self time whose instruction
  the map knows and puts in a phase other than ``other``.

A program without the map (the parent of the PR that added it) gives
None, and so does a trace with no operation of the asked phases.

No cell lists a metric of this reader yet: their specs wait in
``tests/benchmark/fixtures/proposed_layer_metrics.json`` (PERF.md, Open
questions).
"""

from .. import tracing

MARK = ":tpu_custom_call"


def scope_map():
    """``{instruction name: op_name}`` of the step that ran, and the
    program's classifier of an ``op_name``; (None, None) without them."""
    try:
        from deepspeed_tpu.telemetry import memory
        from deepspeed_tpu.utils.xla_profile import scope_phase
    except ImportError:
        return None, None
    scopes = getattr(memory, "scopes", None)
    return (scopes("train_step") if scopes is not None else None), scope_phase


def self_times(events, plane):
    """``(event, seconds)`` for every operation of ``plane``'s XLA Ops
    line: its duration less what the operations nested in it take."""
    ops = sorted((e for e in events
                  if e.plane == plane and e.line == tracing.OPS_LINE),
                 key=lambda e: (e.start_s, -e.dur_s))
    out, enclosing = [], []
    for e in ops:
        while enclosing and enclosing[-1][0].end_s <= e.start_s + 1e-12:
            enclosing.pop()
        if enclosing:
            outer = enclosing[-1]
            outer[1] -= min(e.end_s, outer[0].end_s) - e.start_s
        enclosing.append([e, e.dur_s])
        out.append(enclosing[-1])
    return [(e, max(s, 0.0)) for e, s in out]


def read(ev, params):
    planes = tracing.device_planes(ev.events)
    if not planes or not ev.slice_steps:
        return None
    mapped, scope_phase = scope_map()
    if not mapped:
        return None

    def phase_of(event):
        """(phase, the phase qualified by its side of autodiff) or None
        for an instruction the map does not know."""
        name = event.name[:-len(MARK)] if event.name.endswith(MARK) \
            else event.name
        op_name = mapped.get(name)
        if op_name is None:
            return None
        phase = scope_phase(op_name)
        side = "transpose" if "transpose(" in op_name else "jvp"
        return phase, f"{phase}/{side}"

    def among(event, wanted):
        got = phase_of(event)
        return got is not None and not wanted.isdisjoint(got)

    if params.get("collectives"):
        wanted = set(params["phases"])
        seconds = sum(tracing.total(tracing.union(
            (e.start_s, e.end_s)
            for e in tracing.leaf_ops(ev.events, p) + [
                a for a in ev.events
                if a.plane == p and a.line == tracing.ASYNC_LINE]
            if tracing.COLLECTIVE.search(e.name) and among(e, wanted)))
            for p in planes)
    else:
        selfs = [pair for p in planes for pair in self_times(ev.events, p)]
        if params.get("coverage"):
            all_s = sum(s for _, s in selfs)
            known = sum(s for e, s in selfs
                        if (phase_of(e) or ("other",))[0] != "other")
            return 100.0 * known / all_s if all_s > 0 else None
        wanted = set(params["phases"])
        seconds = sum(s for e, s in selfs if among(e, wanted))
    if seconds <= 0:
        return None
    return 1e3 * seconds / len(planes) / ev.slice_steps
