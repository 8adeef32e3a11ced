"""The share of its roofline of a piece of work of a model whose full
latent layers read what an indexer picks, over the traced slice, %: the
least seconds the work could take for the slice's launches
(``arith_sparse.least_seconds``: the MODEL's operations and bytes from
its equations, whatever implements them) over the device self time of
the operations under ALL of the scopes ``within_all`` (whole steps of
an operation's path: the two latent kinds' ``attn_kernel`` differ by the
scope round them), joined to their launches and ``op_name`` as
``serve_program_scope_time.call_rows`` does.

params: ``work`` ("selected_read", "ring_read" or "indexer") and
``within_all``. A run without a slice, without the rows, of a
configuration with no indexer, or of a program with no operation under
the scopes (the parent of the PR that added them) reads nothing."""

import re

from .. import arith, arith_sparse
from .serve_program_scope_time import call_rows


def seconds_within_all(ev, words):
    """Device seconds a call (mean over chips) of the operations whose
    ``op_name`` holds every one of ``words`` as a whole step; None
    where there is nothing to read."""
    got = call_rows(ev)
    if got is None:
        return None
    rows, planes = got
    steps = [re.compile(r"(?:^|/)%s(?:/|$)" % re.escape(w)) for w in words]
    under = [s for _, _, op_name, s in rows
             if op_name and all(rx.search(op_name) for rx in steps)]
    if not under:
        return None
    return sum(under) / planes


def read(ev, params):
    rows = getattr(ev, "launch_rows", None)
    if not ev.events or not rows or not ev.ctx.fields.get("index_topk"):
        return None
    seconds = seconds_within_all(ev, params["within_all"])
    if not seconds:
        return None
    peaks = arith.peaks(ev.ctx.devices[0].device_kind)
    least = arith_sparse.least_seconds(
        ev.ctx.fields, rows, ev.ctx.traffic["rows"], peaks, params["work"]) \
        * ev.slice_steps / len(ev.ctx.devices)
    return arith.roofline_percent(least, seconds)
