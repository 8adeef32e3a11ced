"""A traced ``generate()`` call, operation by operation: which program
launched it, which innermost scope it was traced under, how often it ran
and what it took.

    chiprun -- python scripts/trace_by_scope.py --workload <cell> \
        --seed <n> --seconds <s> [--program ragged_step] [--top 40]

Runs one generation cell of the benchmark as ``benchmark/run.py --trace
1`` does (the same runner, the same traced slice: the window's second
call) and prints, longest first,

    program  scope  instruction  launches  self seconds  op_name's end

of the slice's device operations, then the sums by (program family,
scope) that the ``prefill_*_ms.gen`` metrics read
(``deepspeed_tpu.utils.xla_profile.scope_seconds``), then the call's
idle gaps between launches, each by the leaf span the host was in
(``gap_*_ms.gen``'s arithmetic, gap by gap), what the garbage
collector did (``process_gc_*``, the ``gc_pause`` spans), the call's
host-thread totals and its ``host_stall`` records (leaf, cause, evidence)
against the gaps they overlap (``telemetry/collector.py``), which form
brought the routed rows back (``moe_rows_combined_total``) and the runs a
pass of a share's launches (``moe_share_runs_total``), then the cell's
per-layer metrics as ``benchmark/run.py`` would print them. A trace names
an operation by its HLO instruction, and only the process that compiled
the programs can say what scope an instruction was traced under
(``telemetry.memory.scopes_offered``): so the engine is built here, and
``table(events)`` is there for any other process that has built one and
holds a trace (``benchmark.tracing.load_events(path)`` of an
``.xplane.pb``). The whole table also goes to
``chiprun_out/trace_by_scope/<cell>.txt``.

``--program`` keeps the launches of the programs whose name starts so
(``ragged_step``: the prompt path; ``decode``). ``--rehearse`` runs toy
widths on the CPU, where no time means anything. Like the other chip
scripts it exits non-zero without a TPU.
"""

import argparse
import importlib
import json
import os
import sys
from collections import defaultdict
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


def table(events, planes=None):
    """``[(program, scope, instruction, launches, self seconds, the end
    of its op_name)]`` of the device operations of ``events`` (``benchmark.tracing`` events of a
    traced call), longest first, and the sums by (program family,
    scope), with under ``(program, "(signatures disagree)")`` the part
    of ``other`` that two signatures' maps put in different scopes;
    seconds are means over the device planes. None where the
    process has compiled no serving program."""
    from benchmark import tracing
    from benchmark.readers import serve_program_scope_time as by_scope
    from benchmark.readers.serve_scope_time import by_launch, offered_maps
    from deepspeed_tpu.utils.xla_profile import scope_seconds, serve_scope
    planes = planes or tracing.device_planes(events)
    launched = [r for p in planes for r in by_launch(events, p)]
    maps, _ = offered_maps({program for program, _, _ in launched})
    if not maps:
        return None
    rows = by_scope.joined(launched, maps, serve_scope)
    # what two signatures of one program disagree on: a launch does not
    # say which of them it ran, so these count as scope ``other``
    apart = defaultdict(float)
    for (program, e, s), row in zip(launched, rows):
        if row[2] is None and any(row[1] in m
                                  for m in maps.get(program, ())):
            apart[program] += s / len(planes)
    ops = defaultdict(lambda: [0, 0.0])
    for program, name, op_name, s in rows:
        # what the instruction is, in the path's last two words
        what = "/".join(op_name.split("/")[-2:]) if op_name else ""
        at = ops[program, serve_scope(op_name) if op_name else "other",
                 name, what]
        at[0] += 1
        at[1] += s
    n = len(planes)
    listed = sorted(((p, sc, name, k // n, s / n, what)
                     for (p, sc, name, what), (k, s) in ops.items()),
                    key=lambda r: -r[4])
    sums = {k: s / n for k, s in scope_seconds(rows).items()}
    sums.update({(p, "(signatures disagree)"): s for p, s in apart.items()})
    return listed, sums


def render(listed, sums, program=None, top=None):
    keep = [r for r in listed
            if program is None or (r[0] or "").startswith(program)]
    lines = [f"{'program':24} {'scope':18} {'instruction':44} "
             f"{'launches':>8} {'self s':>10}  op_name's end"]
    lines += [f"{p or '-':24} {sc:18} {name:44} {k:8d} {s:10.6f}  {what}"
              for p, sc, name, k, s, what in keep[:top]]
    lines.append("")
    lines.append(f"{'program family':24} {'scope':18} {'self s':>10}")
    lines += [f"{fam:24} {sc:18} {s:10.6f}" for (fam, sc), s in sorted(
        sums.items(), key=lambda kv: (kv[0][0], -kv[1]))
        if not sc.startswith("(")]
    lines.append("of `other`, instructions two signatures of a program "
                 "put in different scopes, s: " + (", ".join(
                     f"{p} {s:.6f}" for (p, sc), s in sorted(sums.items())
                     if sc.startswith("(")) or "none"))
    return "\n".join(lines)


def idle_gaps(ev, least_s=1e-4):
    """The idle gaps between the launches of the traced call, longest
    first, each laid over the program's LEAF spans (the annotated spans
    of the ring that hold no other, on the device trace's clock:
    ``readers/gen_gap_time.py``): ``[(seconds, {leaf: seconds}, seconds
    under no leaf, [(the leaf that follows a stretch under none, its
    seconds)])]`` of the gaps of
    ``least_s`` or more; None where the clocks do not pair."""
    from benchmark import tracing
    from benchmark.readers.gen_gap_time import (clock_offset,
                                                idle_between_launches)
    from deepspeed_tpu.telemetry import trace
    ring = trace.export()
    offset = clock_offset(ev.host_spans(), ring)
    planes = tracing.device_planes(ev.events)
    if offset is None or not planes:
        return None
    holds = {s["parent"] for s in ring}
    leaves = sorted((s["start"] + offset,
                     s["start"] + offset + s["duration_s"], s["name"])
                    for s in ring
                    if s.get("annotated") and s["id"] not in holds)
    out = []
    for lo, hi in idle_between_launches(ev.events, planes[0]):
        if hi - lo < least_s:
            continue
        under, bare, at, near = defaultdict(float), 0.0, lo, []
        for s, e, name in leaves:
            if e <= lo or s >= hi:
                continue
            if s > at + 1e-9:           # at .. s under no leaf
                bare += s - at
                near.append((name, s - at))
            under[name] += min(e, hi) - max(s, lo)
            at = max(at, e)
        if hi > at:
            bare += hi - at
        out.append((hi - lo, dict(under), bare, near))
    return sorted(out, key=lambda g: -g[0])


def render_gaps(gaps, top=12):
    if gaps is None:
        return "idle gaps: the ring's and the trace's clocks do not pair"
    lines = [f"idle gaps of 0.1 ms or more between launches: {len(gaps)}, "
             f"{1e3 * sum(g[0] for g in gaps):.3f} ms; the longest, ms, by "
             f"the leaf span the host was in:"]
    for seconds, under, bare, near in gaps[:top]:
        parts = ", ".join(f"{n} {1e3 * v:.3f}" for n, v in sorted(
            under.items(), key=lambda kv: -kv[1]))
        lines.append(f"  {1e3 * seconds:9.3f}  {parts}; under no leaf "
                     f"{1e3 * bare:.3f}"
                     + (" (" + ", ".join(
                         f"{1e3 * v:.3f} before {n}" for n, v in near) + ")"
                        if near else ""))
    return "\n".join(lines)


def collector_summary():
    """What the collector did in this process, in a line: collections
    and their seconds by generation (``process_gc_*``), and the
    ``gc_pause`` spans the ring still holds (the window's: a collection
    of 1 ms or more), with those that fell inside a ``generate`` call."""
    from deepspeed_tpu.telemetry import get_registry, trace
    reg = get_registry()
    count, pause = (reg.get("process_gc_collections_total"),
                    reg.get("process_gc_pause_seconds"))
    if count is None or pause is None:
        return "collector: this program has no gc hook"
    by_gen = ", ".join(
        f"gen {g}: {int(c.value)} in "
        f"{1e3 * pause.labels(generation=g).sum:.1f} ms"
        for (g,), c in sorted(count.series()))
    ring = trace.export()
    calls = [(s["start"], s["start"] + s["duration_s"]) for s in ring
             if s["name"] == "generate"]
    spans = [s for s in ring if s["name"] == "gc_pause"]
    inside = [s for s in spans if any(
        lo <= s["start"] < hi for lo, hi in calls)]
    longest = max(spans, key=lambda s: s["duration_s"], default=None)
    return (f"collector: {by_gen}; gc_pause spans in the ring "
            f"{len(spans)} ({1e3 * sum(s['duration_s'] for s in spans):.1f}"
            f" ms), inside {len(calls)} generate calls {len(inside)} "
            f"({1e3 * sum(s['duration_s'] for s in inside):.1f} ms)"
            + (f"; the longest {1e3 * longest['duration_s']:.1f} ms, "
               f"generation {longest['attrs']['generation']}"
               if longest else ""))


def host_thread_summary(ev):
    """The traced call's host thread (``telemetry/collector.py``): what
    its root says the calling thread used (CPU, run-queue wait,
    switches, faults), the launches' share of it, and every
    ``host_stall`` record that fell in the call: leaf, cause and
    evidence, against the device's idle gaps it overlaps (the ring and
    the device trace share a clock through the annotated spans). A
    stall says what the host did; the gap beside it what that cost."""
    from benchmark import tracing
    from benchmark.readers.gen_gap_time import (clock_offset,
                                                idle_between_launches)
    from deepspeed_tpu.telemetry import trace
    ring = trace.export()
    roots = [s for s in ring if s["name"] == "generate"
             and s.get("annotated")] or \
        [s for s in ring if s["name"] == "generate"][-1:]
    if not roots or "cpu_s" not in roots[0].get("attrs", {}):
        return "host thread: this program samples no launch"
    root = roots[0]
    lo, hi = root["start"], root["start"] + root["duration_s"]
    used = root["attrs"]
    launches = [s for s in ring if s["parent"] == root["id"]
                and "cpu_s" in s.get("attrs", {})]

    def ms(v):
        return "none" if v is None else f"{1e3 * v:.3f}"

    waits = [s["attrs"]["runq_s"] for s in launches]
    lines = [
        f"host thread, the traced call ({root['duration_s']:.3f} s): CPU "
        f"{ms(used['cpu_s'])} ms, run-queue wait {ms(used['runq_s'])} ms, "
        f"switches {used['nvcsw']} voluntary / {used['nivcsw']} "
        f"involuntary, major faults {used['majflt']}; in its "
        f"{len(launches)} launch spans CPU "
        f"{ms(sum(s['attrs']['cpu_s'] for s in launches))} ms, run-queue "
        f"wait {ms(None if None in waits else sum(waits))} ms"]
    stalls = [s for s in ring if s["name"] == "host_stall"]
    mine = [s for s in stalls if lo <= s["start"] < hi]
    lines.append(f"host_stall records: {len(stalls)} in the ring "
                 f"({1e3 * sum(s['duration_s'] for s in stalls):.1f} ms), "
                 f"{len(mine)} in the traced call")
    offset = clock_offset(ev.host_spans(), ring)
    planes = tracing.device_planes(ev.events)
    gaps = idle_between_launches(ev.events, planes[0]) \
        if offset is not None and planes else None
    for s in mine:
        a = s["attrs"]
        at, end = s["start"], s["start"] + s["duration_s"]
        idle = "the clocks do not pair"
        if gaps is not None:
            under = sum(max(0.0, min(end + offset, e) - max(at + offset, b))
                        for b, e in gaps)
            idle = f"{1e3 * under:.3f} ms of the device's idle gaps under it"
        numbers = " ".join(f"{k}={a[k]}" for k in (
            "cpu_s", "runq_s", "nvcsw", "nivcsw", "majflt", "gc_s",
            "compiles"))
        lines.append(
            f"  +{1e3 * (at - lo):9.1f} ms  {a['leaf']}"
            f"{' (' + a['program'] + ')' if a.get('program') else ''} "
            f"{1e3 * s['duration_s']:.1f} ms over {1e3 * a['expected_s']:.1f}"
            f": {a['cause']} [{a['where']}: {numbers}]; {idle}")
    return "\n".join(lines)


def rows_forms_summary():
    """Which form brought the expert layers' routed rows back from
    expert order, by program, over the whole process
    (``moe_rows_combined_total{program, form}``: ``kernel`` is
    ``kernels/expert_combine.py``'s two launches, ``gather`` XLA's)."""
    from deepspeed_tpu.telemetry import get_registry
    combined = get_registry().get("moe_rows_combined_total")
    if combined is None or not combined.series():
        return "routed rows by form: no expert layer ran"
    return "routed rows by form: " + ", ".join(
        f"{program} {form} {int(c.value)}"
        for (program, form), c in sorted(combined.series()))


def share_runs_summary():
    """The runs of tokens a share's launches went through the experts
    in, by program, over the expert-layer passes
    (``moe_share_runs_total`` over ``moe_launches_total``: the runs a
    pass; 0 where a pass is one dispatch)."""
    from deepspeed_tpu.telemetry import get_registry
    runs = get_registry().get("moe_share_runs_total")
    passes = get_registry().get("moe_launches_total")
    if runs is None or passes is None or not passes.series():
        return "runs a pass: no expert layer ran, or a tree before the count"
    return "runs a pass: " + ", ".join(
        f"{program} {int(runs.labels(program=program).value)} / "
        f"{int(c.value)} = {runs.labels(program=program).value / c.value:g}"
        for (program,), c in sorted(passes.series()) if c.value)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--program", default=None)
    ap.add_argument("--top", type=int, default=40)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmark import run as bench
    if args.rehearse:
        print(bench.REHEARSAL_BANNER, flush=True)
        os.environ["JAX_PLATFORMS"] = "cpu"
    cell, config, traffic = bench.load_cell(args.workload, args.rehearse)
    if args.seconds is None:
        args.seconds = cell.get("rehearse_seconds", 3.0) if args.rehearse \
            else 40.0
    import jax
    devices = jax.devices()
    if not args.rehearse:
        bench.require_tpu(devices, cell["chips"])
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmark.evidence import Context
    ctx = Context(
        cell=cell, config=config, traffic=traffic, seed=args.seed,
        seconds=args.seconds, trace=True, rehearse=args.rehearse,
        devices=devices[:cell["chips"]], clock=bench.CompileClock(),
        t_process_start=bench.T_PROCESS_START, log=bench.log,
        scratch=REPO / ".bench_scratch" / args.workload)
    result = importlib.import_module(
        f"benchmark.runners.{traffic['runner']}").run(ctx)
    ev = result.evidence
    got = table(ev.events)
    if got is None and args.rehearse:
        # the CPU's trace has no device plane: the control flow ran
        print("no device plane in a CPU trace: nothing to list",
              flush=True)
        print(bench.REHEARSAL_BANNER, flush=True)
        return 0
    if got is None:
        raise SystemExit("trace_by_scope: the traced call ran no program "
                         "that offers a scope map")
    listed, sums = got
    out = REPO / "chiprun_out" / "trace_by_scope"
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{args.workload}.txt").write_text(
        render(listed, sums, args.program) + "\n"
        + render_gaps(idle_gaps(ev), top=None) + "\n")
    print(render(listed, sums, args.program, args.top), flush=True)
    print(render_gaps(idle_gaps(ev)), flush=True)
    print(collector_summary(), flush=True)
    print(host_thread_summary(ev), flush=True)
    print(rows_forms_summary(), flush=True)
    print(share_runs_summary(), flush=True)
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    metrics = bench.layer_metrics(
        bench.reported_by(manifest, args.workload, "per_layer"), ev,
        rehearse=args.rehearse)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "correct": bool(result.correct),
                      "rehearsal": args.rehearse,
                      "metrics": {k: v["value"]
                                  for k, v in metrics.items()}}),
          flush=True)
    if args.rehearse:
        print(bench.REHEARSAL_BANNER, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
