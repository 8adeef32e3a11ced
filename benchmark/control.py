#!/usr/bin/env python3
"""The control of a cell's ``correct``: the same run with the cell's
``control`` overlay laid on (for a served bf16 cell the program's own
int8 path, ``kv_quant``), which has to come out NOT correct.

    python benchmark/control.py --workload <cell> --seeds 1,2,3 --seconds 15
    python benchmark/control.py --workload <cell> --seeds 1,2,3 --sound 1

One process, one engine after another (a chip belongs to one process),
one JSON line a run on standard output: the seed, whether the overlay
was on, ``correct`` and every number compared beside its limit. With
``--sound 1`` each seed is also run as the cell stands, so that the
sound runs' largest and the control's smallest are read side by side:
the limits in the cell's file are set from those two and from nothing
else. The benchmark's own runs never call this.
"""

import argparse
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from benchmark import run as harness          # noqa: E402


def run_once(workload, seed, seconds, control, devices, clock,
             rehearse=False, overlay=None):
    """One run of ``workload``'s runner; ``control`` lays the cell's
    ``control`` overlay on, ``overlay`` (a test's) any other:
    ``{"cell": ..., "config": ..., "traffic": ...}``."""
    import importlib
    from benchmark.evidence import Context
    cell, config, traffic = harness.load_cell(workload, rehearse)
    if control:
        cell = harness.merge(cell, cell["control"])
    overlay = overlay or {}
    cell = harness.merge(cell, overlay.get("cell", {}))
    config = harness.merge(config, overlay.get("config", {}))
    traffic = harness.merge(traffic, overlay.get("traffic", {}))
    ctx = Context(
        cell=cell, config=config, traffic=traffic, seed=seed,
        seconds=seconds, trace=False, rehearse=rehearse,
        devices=devices[:cell["chips"]], clock=clock,
        t_process_start=time.perf_counter(), log=harness.log,
        scratch=REPO / ".bench_scratch" / workload)
    runner = importlib.import_module(
        f"benchmark.runners.{traffic['runner']}")
    return runner.run(ctx)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--sound", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    import jax
    devices = jax.devices()
    cell = harness.load_json("workloads", args.workload)
    harness.require_tpu(devices, cell["chips"])
    enable_compile_cache()
    clock = harness.CompileClock()
    for seed in (int(s) for s in args.seeds.split(",")):
        for control in ((False, True) if args.sound else (True,)):
            result = run_once(args.workload, seed, args.seconds, control,
                              devices, clock)
            print(json.dumps({
                "seed": seed, "control": control,
                "correct": bool(result.correct),
                "compared": result.correct_detail.get("compared"),
                "calls": result.correct_detail.get("calls"),
                "rate": result.end_to_end}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
