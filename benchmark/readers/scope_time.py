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
part of a phase that belongs to its backward and ``"loss_head/jvp"`` the
rest. An operation is on the backward side where autodiff transposed it
(``transpose(`` in its ``op_name``) or where its ``op_name`` holds one of
``transpose_marks``: the loss head forms its gradients on the forward
walk, so its two gradient matmuls run under ``jvp(loss_head)`` and only
their einsum names (``bcv,hv->bch``, ``bch,bcv->hv``) tell them, and
what they exchange between chips, from the logits matmul.

* plain: ms a step of the XLA Ops line's operations whose instruction the
  map puts in one of ``phases``, mean over chips, each with its self time
  (``tracing.self_intervals``), so the phases add up to the busy time.
* ``collectives: true``: ms a step in which a collective of those phases
  ran, mean over chips: union of their own intervals on the main stream
  and of the async line, the population ``collective_ms.train`` is made
  of.
* ``coverage: true``: % of the operations' self time whose instruction
  the map knows and puts in a phase other than ``other``.

A program without the map (the parent of the PR that added it) gives
None, and so does a trace with no operation of the asked phases.
"""

from .. import tracing
from ..evidence import instruction, scope_map


def read(ev, params):
    planes = tracing.device_planes(ev.events)
    if not planes or not ev.slice_steps:
        return None
    mapped, scope_phase = scope_map()
    if not mapped:
        return None

    marks = params.get("transpose_marks", ())

    def phase_of(event):
        """(phase, the phase qualified by its side of autodiff) or None
        for an instruction the map does not know."""
        op_name = mapped.get(instruction(event.name))
        if op_name is None:
            return None
        phase = scope_phase(op_name)
        back = "transpose(" in op_name or any(m in op_name for m in marks)
        return phase, f"{phase}/{'transpose' if back else 'jvp'}"

    def among(event, wanted):
        got = phase_of(event)
        return got is not None and not wanted.isdisjoint(got)

    if params.get("collectives"):
        wanted = set(params["phases"])
        seconds = sum(tracing.total(tracing.collective_intervals(
            ev.events, p, keep=lambda e: among(e, wanted))) for p in planes)
    else:
        selfs = [pair for p in planes
                 for pair in tracing.self_times(ev.events, p)]
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
