"""The three per-layer metrics that read the host thread's samples and
the ``host_stall`` spans (``deepspeed_tpu/telemetry/collector.py``):
``host_stall_ms.gen`` / ``.train`` and ``host_cpu_ms.gen``. ISSUE 70's
other three are NOT listed, each for what the chip's host, a sandboxed
kernel, gives: ``runqueue_wait_ms.*`` because it has no ``schedstat``
(the launch spans' ``runq_s`` reads None there and a listed metric would
be in no result line; ``span_attr`` reads it wherever a kernel gives
it), ``host_cpu_ms.train`` because it keeps a thread's CPU time in ticks
of 10 ms and a step's launch span takes a few ms of it (fifteen to
twenty ticks a window: a yardstick that swings a quarter run to run).
What ``BENCHMARK.json`` and the files say of the three (entries found BY
NAME, "at least these", never by position: a later PR appends), the two
new readers over hand-made rings and where there is nothing to read (a
parent without the spans or the attrs), and the rehearsal of a
generation cell and of ``opt-125m.train-dense`` with the readers run.
Every reader reads the program's span ring alone: no device trace, so a
traced run pays milliseconds for the three.
"""

import json
from pathlib import Path

import pytest

from benchmark import manifest
from benchmark.readers import span_attr, span_overlap_time
from benchmark.run import reported_by
from deepspeed_tpu.telemetry import trace

from test_benchmark_run import assert_rehearsed, copy_the_benchmark, run_py

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmark"
GEN = ("generation step", "gen_tok_s")
TRAIN = ("training step", "train_tok_s")
GEN_CELLS = {
    "opt-1.3b.rollout-256", "joyai-llm-flash.rollout-64x256",
    "ling-3.0-flash.rollout-128x256",
    "granite-4.0-h-small.rollout-64x1024-256",
    "nemotron-3-nano-30b-a3b.rollout-128x256-384",
    "brumby-14b-base.rollout-16x2048-256",
    "smallthinker-21ba3b-instruct.rollout-16x8192-512",
    "falcon-h1-34b-instruct.rollout-64x1024-512",
    "lfm2-8b-a1b.rollout-256x512-512",
    "dots3-note-prev.rollout-8x32768-256",
    # gc_pause_ms.gen does not list it; the one test that pins its list
    # (test_benchmark_window.py, ``sorted(mine) == ...``) fails on the
    # accepted tree already, and no passing test does
    "trinity-mini.rollout-16x8192-512"}
TRAIN_CELLS = {"opt-125m.train-dense", "opt-1.3b.zero3-dp4"}
STEP = ["train_step", "train_data", "train_bookkeeping"]
# name -> (layer and moves, cells at least, reader, params)
NEW = {
    "host_stall_ms.gen": (GEN, GEN_CELLS, "span_overlap_time", {
        "span": "host_stall", "inside": ["generate"],
        "marked_by": "cpu_s"}),
    "host_stall_ms.train": (TRAIN, TRAIN_CELLS, "span_overlap_time", {
        "span": "host_stall", "inside": STEP, "skip": 2,
        "marked_by": "cpu_s"}),
    "host_cpu_ms.gen": (GEN, GEN_CELLS, "span_attr", {
        "span": "generate", "attr": "cpu_s", "reduce": "median",
        "scale": 1000.0}),
}
# ISSUE 70's other three (the module's docstring says why each)
NOT_LISTED = ("host_cpu_ms.train", "runqueue_wait_ms.gen",
              "runqueue_wait_ms.train")
SPECS = {m: json.loads((BENCH / "layer_metrics" / f"{m}.json").read_text())
         for m in NEW}


def _named(entries, name):
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, name
    return found[0]


def span(name, start_ms, dur_ms, id=0, parent=None, **attrs):
    s = {"name": name, "start": start_ms * 1e-3,
         "duration_s": dur_ms * 1e-3, "depth": 0, "id": id,
         "parent": parent, "track": "MainThread"}
    if attrs:
        s["attrs"] = attrs
    return s


# ---------------------------------------------------------------------------
# the tree
# ---------------------------------------------------------------------------
def test_the_tree_passes_the_manifest():
    manifest.check(REPO)


@pytest.mark.parametrize("name", sorted(NEW))
def test_an_entry_its_file_its_reader_and_its_cells(name):
    (layer, moves), cells, reader, params = NEW[name]
    bm = manifest.read(REPO)
    m = _named(bm["per_layer"], name)
    assert (m["unit"], m["better"], m["source"]) == (
        "ms", "lower", "program_span")
    assert (m["layer"], m["moves"]) == (layer, moves)
    assert cells <= set(m["workloads"])
    spec = SPECS[name]
    for key in ("unit", "better", "source", "layer", "moves"):
        assert spec[key] == m[key]
    assert spec["reader"] == reader and spec["params"] == params
    assert (BENCH / "readers" / f"{reader}.py").is_file()
    # where gc_pause_ms is read, so are they: the same lists at least
    twin = _named(bm["per_layer"], "gc_pause_ms." + name.rsplit(".")[-1])
    assert set(twin["workloads"]) <= set(m["workloads"])
    for cell in m["workloads"]:
        assert name in reported_by(bm, cell, "per_layer")
        assert moves in reported_by(bm, cell, "end_to_end")


@pytest.mark.parametrize("name", NOT_LISTED)
def test_what_the_chips_host_cannot_give_is_not_listed(name):
    """No entry and no file: a listed metric that no result line
    carries refuses the run, and a file nobody lists is read by
    nothing."""
    bm = manifest.read(REPO)
    assert name not in {m["name"] for m in bm["per_layer"]}
    assert not (BENCH / "layer_metrics" / f"{name}.json").exists()


def test_the_readers_take_no_device_trace():
    """The ring alone: nothing of ``ev`` is touched, so the three cost a
    traced run of the longest cell milliseconds."""
    import inspect
    for reader in (span_attr, span_overlap_time):
        source = inspect.getsource(reader)
        assert "ev." not in source and "trace.export()" in source


# ---------------------------------------------------------------------------
# span_overlap_time: host_stall spans laid over calls and steps
# ---------------------------------------------------------------------------
def test_stall_ms_is_the_overlap_a_call():
    ring = [span("generate", 0, 1000, 1, cpu_s=0.01),
            span("host_stall", 100, 300, 2, leaf="window_fetch"),
            span("generate", 2000, 1000, 3, cpu_s=0.01),
            span("host_stall", 2900, 400, 4),       # 100 ms inside
            span("host_stall", 5000, 50, 5),        # outside every call
            span("gc_pause", 150, 20, 6)]
    p = SPECS["host_stall_ms.gen"]["params"]
    got = span_overlap_time.per_span(ring, p["span"], p["inside"],
                                     marked_by=p["marked_by"])
    assert got == pytest.approx((300 + 100) / 2)
    # a sound window is 0.0, a number
    assert span_overlap_time.per_span(
        ring[:1] + ring[2:3], "host_stall", ["generate"],
        marked_by="cpu_s") == 0.0
    # no call in the ring: nothing to read
    assert span_overlap_time.per_span(
        ring[1:2], "host_stall", ["generate"]) is None


def test_stall_ms_is_none_on_a_program_that_marks_no_root():
    """The parent's calls carry no ``cpu_s``: it samples nothing and
    judges no leaf, and 0.0 there would read as a sound call."""
    ring = [span("generate", 0, 1000, 1, rows=4),
            span("generate", 2000, 1000, 2, rows=4)]
    assert span_overlap_time.per_span(
        ring, "host_stall", ["generate"], marked_by="cpu_s") is None
    assert span_overlap_time.per_span(
        ring, "host_stall", ["generate"]) == 0.0


def test_stall_ms_of_a_step_skips_the_warm_steps():
    p = SPECS["host_stall_ms.train"]["params"]
    ring = []
    for i in range(5):
        at = 1000 * i
        ring += [span("train_data", at, 10, 10 * i, step=i),
                 span("train_step", at + 10, 900, 10 * i + 1, step=i,
                      cpu_s=0.002),
                 span("train_bookkeeping", at + 910, 20, 10 * i + 2,
                      step=i)]
    ring += [span("host_stall", 500, 200, 90),      # a warm step's
             span("host_stall", 3100, 600, 91),     # step 3's train_step
             span("host_stall", 4915, 10, 92)]      # step 4's bookkeeping
    got = span_overlap_time.per_span(ring, p["span"], p["inside"],
                                     p["skip"], p["marked_by"])
    assert got == pytest.approx((600 + 10) / 3)


# ---------------------------------------------------------------------------
# span_attr: the launch spans' and roots' own numbers
# ---------------------------------------------------------------------------
def test_cpu_ms_is_the_median_and_the_wait_the_mean():
    ring = [span("generate", 1000 * i, 900, i, cpu_s=c, runq_s=w)
            for i, (c, w) in enumerate(
                [(0.030, 0.0), (0.020, 0.0), (0.900, 0.6), (0.025, 0.0)])]
    cpu = SPECS["host_cpu_ms.gen"]["params"]
    assert span_attr.reduced(
        ring, cpu["span"], cpu["attr"], cpu["reduce"],
        scale=cpu["scale"]) == pytest.approx(27.5)
    # a run-queue wait, where a kernel gives one: no metric lists it on
    # this machine, the reader reads it all the same
    assert span_attr.reduced(ring, "generate", "runq_s", "mean",
                             scale=1e3) == pytest.approx(150.0)
    # the first spans skipped are the earliest, whatever the ring's order
    assert span_attr.reduced(ring[::-1], "generate", "cpu_s", "median",
                             skip=2, scale=1e3) == pytest.approx(462.5)


def test_an_attr_nobody_set_reads_none_never_zero():
    ring = [span("generate", 0, 900, 1, rows=4),            # the parent's
            span("train_step", 0, 900, 2, step=0, cpu_s=0.003,
                 runq_s=None)]                              # no schedstat
    assert span_attr.reduced(ring, "generate", "cpu_s", "median") is None
    assert span_attr.reduced(ring, "train_step", "runq_s", "mean") is None
    assert span_attr.reduced(ring, "train_step", "cpu_s", "median") \
        == pytest.approx(0.003)
    assert span_attr.reduced(ring, "decode_window", "cpu_s", "mean") is None
    assert span_attr.reduced(ring, "train_step", "cpu_s", "median",
                             skip=1) is None


def test_both_readers_read_the_programs_ring():
    trace.clear()
    try:
        for name in NEW:
            assert __import__(
                f"benchmark.readers.{SPECS[name]['reader']}",
                fromlist=["read"]).read(None, SPECS[name]["params"]) is None
        for i in range(4):
            with trace.span("generate") as root:
                root["attrs"] = {"cpu_s": 0.01 * (i + 1), "runq_s": 0.001,
                                 "nvcsw": 1, "nivcsw": 0, "majflt": 0}
        s = trace.export("generate")[1]
        trace.record("host_stall", s["start"], s["duration_s"],
                     leaf="window_fetch", cause="blocked")
        got = {m: __import__(f"benchmark.readers.{SPECS[m]['reader']}",
                             fromlist=["read"]).read(
                                 None, SPECS[m]["params"]) for m in NEW}
        assert got["host_cpu_ms.gen"] == pytest.approx(25.0)
        assert got["host_stall_ms.gen"] == pytest.approx(
            1e3 * s["duration_s"] / 4)
        assert all(got[m] is None for m in NEW if m.endswith(".train"))
    finally:
        trace.clear()


# ---------------------------------------------------------------------------
# the rehearsals: the program's own spans, read by the readers
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cell,kind", [("opt-1.3b.rollout-256", "gen"),
                                       ("opt-125m.train-dense", "train")])
def test_a_traced_rehearsal_runs_the_readers(tmp_path, cell, kind):
    # in a copy: the trace goes to .bench_scratch/<cell> of the checkout,
    # and another worker may be rehearsing the same cell in this one
    copy_the_benchmark(tmp_path)
    p = run_py(["--workload", cell, "--rehearse", "--trace", "1"],
               cwd=tmp_path)
    assert_rehearsed(p)
    ran = [ln for ln in p.stderr.splitlines() if "readers ran" in ln]
    assert len(ran) == 1
    for name in NEW:
        if name.endswith("." + kind):
            assert f"'{name}'" in ran[0], (name, ran[0])
