#!/usr/bin/env python3
"""One cell of the benchmark, one process, one last line.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python benchmark/run.py --workload <cell> --rehearse      # toy widths, CPU

Everything that belongs to one cell, configuration, traffic mix or
per-layer metric is a file found by name (``workloads/<cell>.json``,
``configs/<config>.json``, ``traffic/<mix>.json``,
``layer_metrics/<metric>.json``, ``runners/<runner>.py``,
``readers/<reader>.py``); this file holds no list of them. Which metrics
a cell reports is said once, in ``BENCHMARK.json`` (``reported_by``): a
later PR adds a metric as an entry there and a spec file here, and gives
a new cell the metrics that are there by adding the cell's name to their
``workloads`` lists in ``BENCHMARK.json``; no file under this directory
is edited for either.

A configuration of another block than OPT's is files too. Its
``configs/<config>.json`` names, beside ``fields``, the modules under
this directory that hold the block: ``"reference"`` (its plain float32
reference) and ``"weights"`` (its seeded weights), which the runners
reach through the ``Context`` (``evidence.CONTRACT`` says what each
must offer; absent, ``reference.py`` and ``weights.py``, the OPT
block's); and ``"toy_fields"``, the widths ``--rehearse`` lays on its
``fields`` (absent, ``TOY_FIELDS``). How far it is cut from its source
it says under ``"published_as"``, ``"reduced"`` and ``"cuts"``, which
``manifest.py`` holds to the floors of a cut: depth, experts held,
vocabulary, a side module left out, and never a width.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero naming what JAX found, and prints no result: there is no CPU
fallback. ``--rehearse`` runs the same control flow at toy widths on
JAX_PLATFORMS=cpu with four virtual devices, says so first and last, and
prints NO metrics line — nothing measured there is a speed.
"""

import time

T_PROCESS_START = time.perf_counter()   # set-up is counted from here

import argparse        # noqa: E402
import importlib       # noqa: E402
import json            # noqa: E402
import os              # noqa: E402
import sys             # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
REHEARSAL_BANNER = "REHEARSAL (cpu) -- toy widths, not a chip result"
# the OPT block at toy widths for --rehearse: every width shrinks, the
# block and the control flow stay. The default of a configuration's own
# ``toy_fields`` (``load_cell``), and read nowhere else
TOY_FIELDS = dict(vocab_size=512, hidden_size=128, intermediate_size=512,
                  num_layers=2, num_heads=4, max_seq_len=256,
                  flash_min_seq=256)


def load_json(kind, name):
    path = HERE / kind / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"benchmark: no {kind}/{name}.json under {HERE}")
    return json.loads(path.read_text())


def merge(base, over):
    """``over`` laid on ``base``, dict by dict."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def load_cell(name, rehearse=False):
    """(cell, config, traffic) for a cell name, rehearsal overrides laid
    on where asked: the cell's ``rehearse``, its ``rehearse_traffic``,
    and on the configuration's ``fields`` its own ``toy_fields``."""
    cell = load_json("workloads", name)
    config = load_json("configs", cell["config"])
    traffic = load_json("traffic", cell["traffic"])
    if rehearse:
        cell = merge(cell, cell.get("rehearse", {}))
        traffic = merge(traffic, cell.get("rehearse_traffic", {}))
        config = merge(config, {"fields": config.get("toy_fields",
                                                     TOY_FIELDS)})
    return cell, config, traffic


def reported_by(manifest, cell_name, kind):
    """Names of the ``kind`` (``end_to_end`` or ``per_layer``) metrics
    the cell reports, in ``BENCHMARK.json``'s order: those that list it
    under ``workloads``. An end-to-end metric without the list is every
    cell's (``setup_s``). A per-layer metric always has the list, and a
    cell in it that does not report the metric's ``moves`` is an error,
    not a metric silently left out."""
    e2e = [m["name"] for m in manifest["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])]
    if kind == "end_to_end":
        return e2e
    mine = [m for m in manifest["per_layer"] if cell_name in m["workloads"]]
    for m in mine:
        if m["moves"] not in e2e:
            raise SystemExit(
                f"benchmark: BENCHMARK.json lists {cell_name} under "
                f"{m['name']}, which moves {m['moves']}, and the cell "
                f"reports only {e2e}")
    return [m["name"] for m in mine]


def require_tpu(devices, chips):
    """The chips the cell asks for, or an error naming what JAX found
    (deepspeed_tpu.accelerator.require_tpu's behaviour, kept here so that
    the benchmark's refusal does not depend on the program's)."""
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"benchmark: this cell needs a TPU and JAX found none: "
            f"jax.devices()[0].platform == {platform!r} (device_kind "
            f"{devices[0].device_kind!r}, JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '')!r}). No CPU fallback; "
            f"--rehearse is the labelled CPU route.")
    if len(devices) < chips:
        raise SystemExit(
            f"benchmark: this cell needs {chips} TPU chips and JAX found "
            f"{len(devices)} ({devices[0].device_kind})")


class CompileClock:
    """Backend compiles JAX made (or read from the persistent cache in
    their place), counted through jax.monitoring; ``mark()`` starts the
    window whose count has to stay 0."""

    def __init__(self):
        import jax.monitoring as mon
        self.compiles = 0
        self.cache_hits = 0
        self.seconds = 0.0
        self._mark = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        self._mark = self.compiles

    def since_mark(self):
        return self.compiles - self._mark


def log(msg):
    print(f"[bench +{time.perf_counter() - T_PROCESS_START:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def layer_metrics(names, evidence, rehearse=False):
    """The per-layer metrics ``names``, each by its own reader. A reader
    that finds nothing to read returns None and the metric is left out.
    A rehearsal runs the readers for their control flow; the CPU has no
    published peak, and a reader that asks for one is skipped there."""
    out = {}
    for name in names:
        spec = load_json("layer_metrics", name)
        reader = importlib.import_module(
            f"benchmark.readers.{spec['reader']}")
        try:
            value = reader.read(evidence, spec.get("params", {}))
        except KeyError as e:
            if not rehearse:
                raise
            log(f"  {name}: not computed in a rehearsal ({e})")
            continue
        if value is not None:
            out[name] = {"value": float(value), "unit": spec["unit"]}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(REPO))
    if args.rehearse:
        print(REHEARSAL_BANNER, flush=True)
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4")
    cell, config, traffic = load_cell(args.workload, args.rehearse)
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    if args.seconds is None:
        if not args.rehearse:
            raise SystemExit("benchmark: --seconds is required")
        args.seconds = cell.get("rehearse_seconds", 3.0)

    try:
        from deepspeed_tpu.accelerator.tpu_accelerator import \
            apply_collective_overlap_flags
        from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    except ImportError as e:
        raise SystemExit(f"benchmark: the program is not here ({e}); run "
                         f"from the root of a checkout of the repository")
    # libtpu reads these when the TPU client starts (the first
    # jax.devices() below); the trainer's entry points set them too
    apply_collective_overlap_flags()
    import jax
    t_imported = time.perf_counter()
    devices = jax.devices()
    t_client = time.perf_counter()
    if not args.rehearse:
        require_tpu(devices, cell["chips"])
    elif len(devices) < cell["chips"]:
        raise SystemExit(f"benchmark: rehearsal needs {cell['chips']} "
                         f"virtual devices, found {len(devices)}")
    devices = devices[:cell["chips"]]
    # JAX_COMPILATION_CACHE_DIR if the machine sets it, else .jax_cache/
    # in this checkout: a fixed path, because the path is part of the key
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    log(f"{args.workload}: {devices[0].platform} "
        f"{devices[0].device_kind} x{len(devices)}, cache {cache_dir}")

    from benchmark.evidence import Context
    ctx = Context(
        cell=cell, config=config, traffic=traffic,
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        rehearse=args.rehearse, devices=devices, clock=clock,
        t_process_start=T_PROCESS_START, log=log,
        scratch=REPO / ".bench_scratch" / args.workload)
    ctx.part("import_jax", at=t_imported)
    ctx.part("tpu_client", at=t_client)
    runner = importlib.import_module(
        f"benchmark.runners.{traffic['runner']}")
    result = runner.run(ctx)

    log(f"done: attempted {result.attempted} failed {result.failed} "
        f"correct {result.correct} ({result.correct_detail}); "
        f"{clock.compiles} compiles, {clock.cache_hits} cache hits, "
        f"{clock.seconds:.1f}s compiling")
    if args.rehearse:
        if not result.correct:
            raise SystemExit(f"rehearsal failed its checks: "
                             f"{result.correct_detail}")
        if args.trace:
            got = layer_metrics(
                reported_by(manifest, args.workload, "per_layer"),
                result.evidence, rehearse=True)
            log(f"readers ran (values are not results): {sorted(got)}")
        print(REHEARSAL_BANNER, flush=True)
        return 0

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": result.evidence.memory_peak_bytes}
    line = {"correct": bool(result.correct),
            "attempted": int(result.attempted),
            "failed": int(result.failed)}
    if args.trace:
        ev = result.evidence
        line["metrics"] = layer_metrics(
            reported_by(manifest, args.workload, "per_layer"), ev)
        device["busy_s"], device["window_s"] = ev.busy_and_window()
        line["breakdown"] = ev.breakdown()
    else:
        spec = {m["name"]: m for m in manifest["end_to_end"]}
        line["metrics"] = {
            k: {"value": float(result.end_to_end[k]),
                "unit": spec[k]["unit"]}
            for k in reported_by(manifest, args.workload, "end_to_end")
            if k in result.end_to_end}
    line["device"] = device
    line["detail"] = result.correct_detail
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
