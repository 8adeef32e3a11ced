"""The readers that join a device trace with what the program says about
itself (``scope_time``: the compiled step's scope map; ``span_time``: the
span ring) on hand-written events, map and ring with known answers; the
per-kernel patterns on the names the chip shows; and both cells rehearsed
with every reader their metrics name."""

import json
import os
import re
import shutil
import subprocess
import sys
import types
from pathlib import Path

import pytest

from benchmark import tracing
from benchmark.readers import op_time_share, scope_time, span_time
from benchmark.run import reported_by
from benchmark.tracing import Event

REPO = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
SPECS = {p.name[:-len(".json")]: json.loads(p.read_text())
         for p in (REPO / "benchmark" / "layer_metrics").glob("*.json")}


DEV, DEV1 = "/device:TPU:0", "/device:TPU:1"
OPS, ASYNC = tracing.OPS_LINE, tracing.ASYNC_LINE
LAYERS = "jit(train_step)/while/body/closed_call/"
FWD = LAYERS + "jvp(layers)/while/body/closed_call/"
BWD = LAYERS + "transpose(jvp(layers))/while/body/closed_call/checkpoint/"

# instruction name -> op_name, as utils/xla_profile.scope_map gives it
SCOPES = {
    "while.0": LAYERS + "jvp(layers)/while",
    "fusion.1": FWD + "mlp/dot_general",
    "flash_attention_fwd.2": BWD + "rematted_computation/attention/"
                                   "flash_attention_fwd/pallas_call",
    "fusion.3": BWD + "mlp/dot_general",
    "all-gather.4": FWD + "attention/dot_general",
    "fusion.6": LAYERS + "transpose(jvp(loss_head))/while/body/mul",
    "all-gather-start.11": LAYERS + "jvp(loss_head)/dot_general",
    "all-reduce.13": LAYERS + "jvp(loss_head)/while/body/bch,bcv->hv/"
                              "dot_general",
    "fusion.7": "jit(train_step)/optimizer/add",
    "fusion.9": LAYERS + "jit(_threefry_split)/add",
    "reduce-scatter-start.10": BWD + "attention/dot_general",
}
# one chip, two steps. The forward loop wraps three operations and runs
# 0.1 s longer than they do; the kernel contains the issue of an async
# slice; copy.8 and that slice are in no map, fusion.9 in no phase; the
# async line holds the async halves of two collectives
HAND = [
    Event(DEV, OPS, "while.0", 0.0, 1.1),
    Event(DEV, OPS, "fusion.1", 0.0, 0.4),
    Event(DEV, OPS, "flash_attention_fwd.2:tpu_custom_call", 0.4, 0.3),
    Event(DEV, OPS, "slice-start.12", 0.5, 0.01),
    Event(DEV, OPS, "fusion.3", 0.7, 0.3),
    Event(DEV, OPS, "all-gather.4", 1.5, 0.4),
    Event(DEV, OPS, "fusion.6", 2.0, 0.4),
    Event(DEV, OPS, "fusion.7", 2.4, 0.1),
    Event(DEV, OPS, "copy.8", 2.5, 0.1),
    Event(DEV, OPS, "fusion.9", 2.6, 0.1),
    Event(DEV, ASYNC, "all-gather-start.11", 0.1, 0.2),
    Event(DEV, ASYNC, "reduce-scatter-start.10", 2.3, 0.3),
]


@pytest.fixture
def program_map(monkeypatch):
    """The program's record of its compiled step, hand-written."""
    from deepspeed_tpu.telemetry import memory
    monkeypatch.setattr(
        memory, "scopes",
        lambda program: SCOPES if program == "train_step" else None)


def evidence(events=HAND, steps=2):
    return types.SimpleNamespace(events=events, slice_steps=steps)


@pytest.mark.parametrize("params,ms", [
    # fusion.1, the gather, and the loop's own 0.1 s
    ({"phases": ["forward"]}, 450.0),
    # found under its kernel mark; less the 0.01 s nested in it
    ({"phases": ["recompute"]}, 145.0),
    ({"phases": ["backward"]}, 150.0),
    ({"phases": ["loss_head"]}, 200.0),
    ({"phases": ["loss_head/transpose"]}, 200.0),
    ({"phases": ["loss_head/jvp"]}, None),
    ({"phases": ["optimizer"]}, 50.0),
    ({"phases": ["forward", "recompute", "backward"]}, 745.0),
    ({"phases": ["grad_reduce"]}, None),    # nothing of that phase ran
    # collectives: a union of intervals, main line and async line
    ({"phases": ["forward", "recompute", "param_gather"],
      "collectives": True}, 200.0),
    ({"phases": ["forward", "recompute", "param_gather", "loss_head/jvp"],
      "collectives": True}, 300.0),         # the head's gather, async
    ({"phases": ["backward", "grad_reduce", "optimizer",
                 "loss_head/transpose"], "collectives": True}, 150.0),
])
def test_scope_time_on_the_hand_written_events(program_map, params, ms):
    got = scope_time.read(evidence(), params)
    assert got == (None if ms is None else pytest.approx(ms))


# the loss head forms its gradients on the forward walk: what its
# gradient matmuls exchange runs under jvp(loss_head) and only the einsum
# name tells it from the logits matmul's gather
HEAD = [Event(DEV, OPS, "fusion.1", 0.0, 0.4),
        Event(DEV, ASYNC, "all-gather-start.11", 0.1, 0.2),
        Event(DEV, OPS, "all-reduce.13", 0.4, 0.1)]
MARKS = SPECS["collective_bwd_ms.train"]["params"]["transpose_marks"]


@pytest.mark.parametrize("metric,marks,ms", [
    ("collective_fwd_ms.train", MARKS, 100.0),
    ("collective_bwd_ms.train", MARKS, 50.0),
    # without the marks both are the forward's: what the ledger read
    ("collective_fwd_ms.train", [], 150.0),
    ("collective_bwd_ms.train", [], None),
])
def test_the_loss_heads_gradient_collectives_count_as_backward(
        program_map, metric, marks, ms):
    spec = SPECS[metric]
    assert spec["reader"] == "scope_time" and spec["params"]["collectives"]
    assert MARKS == ["bcv,hv->bch", "bch,bcv->hv"]
    got = scope_time.read(evidence(HEAD),
                          dict(spec["params"], transpose_marks=marks))
    assert got == (None if ms is None else pytest.approx(ms))
    # the plain phase sum does not split: the head is one phase
    assert scope_time.read(evidence(HEAD), {"phases": ["loss_head"]}) == \
        pytest.approx(50.0)


def test_scope_coverage_counts_unknown_and_unnamed_against(program_map):
    # self times add up to the 2.2 s the device was busy; copy.8 and the
    # nested slice are unknown to the map and fusion.9 is in no phase
    assert sum(s for _, s in tracing.self_times(HAND, DEV)) == \
        pytest.approx(tracing.busy_and_window(HAND)[0]) == \
        pytest.approx(2.2)
    assert scope_time.read(evidence(), {"coverage": True}) == \
        pytest.approx(100 * 1.99 / 2.2)


def test_the_breakdown_says_what_a_fusion_is(program_map):
    from benchmark.evidence import Evidence
    ops = dict((n.split(" ")[0], n) for n, _ in
               Evidence(ctx=None, events=HAND).breakdown()["device_ops"])
    assert ops["fusion.1"] == "fusion.1 forward:mlp/dot_general"
    assert ops["flash_attention_fwd.2:tpu_custom_call"].endswith(
        " recompute:flash_attention_fwd/pallas_call")
    assert ops["copy.8"] == "copy.8"            # in no map: the name alone


def test_scope_time_is_the_mean_over_chips(program_map):
    both = HAND + [Event(DEV1, OPS, "fusion.1", 0.0, 0.2)]
    assert scope_time.read(evidence(both), {"phases": ["forward"]}) == \
        pytest.approx(1e3 * (0.9 + 0.2) / 2 / 2)
    assert scope_time.read(evidence(both), {"coverage": True}) == \
        pytest.approx(100 * (1.99 + 0.2) / (2.2 + 0.2))


def test_scope_time_without_a_map_or_a_trace_reads_nothing(monkeypatch):
    from deepspeed_tpu.telemetry import memory
    params = {"phases": ["forward"]}
    assert scope_time.read(evidence([]), params) is None
    assert scope_time.read(evidence(steps=0), params) is None
    monkeypatch.setattr(memory, "scopes", lambda program: None)
    assert scope_time.read(evidence(), params) is None
    # the parent of the PR that added the map: no such function
    monkeypatch.delattr(memory, "scopes")
    assert scope_time.read(evidence(), params) is None
    assert scope_time.read(evidence(), {"coverage": True}) is None


def span(name, ident, dur, step=None, parent=None):
    rec = {"name": name, "id": ident, "parent": parent, "start": 0.0,
           "duration_s": dur, "depth": 0 if parent is None else 1}
    if step is not None:
        rec["attrs"] = {"step": step}
    return rec


def ring_of(steps):
    """``train_batch``'s five spans for each (data, dispatch, sync,
    bookkeeping) row, in the order they close."""
    ring = []
    for n, (data, dispatch, sync, book) in enumerate(steps):
        i = 10 * n
        ring += [span("train_data", i, data, step=n),
                 span("train_device_dispatch", i + 2, dispatch, parent=i + 1),
                 span("train_host_sync", i + 3, sync, parent=i + 1),
                 span("train_step", i + 1, dispatch + sync, step=n),
                 span("train_bookkeeping", i + 4, book, step=n)]
    return ring


HOST = {"spans": ["train_data", "train_device_dispatch",
                  "train_bookkeeping"], "skip_steps": 2}


def test_host_time_is_the_median_step_outside_the_sync(monkeypatch):
    from deepspeed_tpu.telemetry import trace
    steps = [(0.5, 9.0, 0.1, 0.5),          # the two warm-up steps: the
             (0.1, 0.1, 1.0, 0.1),          # first one compiles
             (0.001, 0.002, 1.0, 0.003), (0.001, 0.002, 1.0, 0.004),
             (0.002, 0.004, 1.0, 0.014)]
    monkeypatch.setattr(trace, "export", lambda: ring_of(steps))
    # 6, 7 and 20 ms: the sync, where the host waits, is in none of them
    assert span_time.read(None, HOST) == pytest.approx(7.0)
    assert span_time.read(None, dict(HOST, skip_steps=0)) == \
        pytest.approx(20.0)
    # a step still open (no bookkeeping yet) is not a step
    open_step = ring_of(steps)[:-1]
    monkeypatch.setattr(trace, "export", lambda: open_step)
    assert span_time.read(None, HOST) == pytest.approx(6.5)


def test_host_time_without_the_span_reads_nothing(monkeypatch):
    from deepspeed_tpu.telemetry import trace
    parent = [s for s in ring_of([(0.001, 0.002, 1.0, 0.003)] * 5)
              if s["name"] != "train_bookkeeping"]
    monkeypatch.setattr(trace, "export", lambda: parent)
    assert span_time.read(None, HOST) is None
    monkeypatch.setattr(trace, "export", lambda: [])
    assert span_time.read(None, HOST) is None


# the names a v5e trace shows for the two flash kernels of a step, in the
# dense step and under ZeRO-3's shard_map (my chip runs, PR 25 and 28),
# and what the patterns must leave alone. The dq + dk/dv pair, which no
# cell runs since the backward was fused, counts as backward
KERNEL_NAMES = {
    "flash_fwd_share.train": ["flash_attention_fwd.13",
                              "flash_attention_fwd.2"],
    "flash_bwd_share.train": ["flash_attention_bwd.10",
                              "flash_attention_bwd.2",
                              "flash_attention_bwd_dq.10",
                              "flash_attention_bwd_dkv.10"],
}
STRANGERS = ["sparse_flash_attention_fwd.1", "ragged_attention_pipelined.4",
             "checkpoint.20", "rms_norm.3", "fusion.491"]


@pytest.mark.parametrize("metric", sorted(KERNEL_NAMES))
def test_each_flash_kernel_is_found_by_its_own_name(metric):
    spec = SPECS[metric]
    assert spec["reader"] == "op_time_share"
    rx = re.compile(spec["params"]["pattern"])
    for own in KERNEL_NAMES[metric]:
        assert rx.search(own + ":tpu_custom_call"), own
        assert not rx.search(own), "a fusion of that name is no kernel"
    others = [n for m, ns in KERNEL_NAMES.items() if m != metric
              for n in ns] + STRANGERS
    for name in others:
        assert not rx.search(name + ":tpu_custom_call"), name
    events = [Event(DEV, OPS, KERNEL_NAMES[metric][0] + ":tpu_custom_call",
                    0.0, 0.25),
              Event(DEV, OPS, "fusion.1", 0.25, 0.75)]
    assert op_time_share.read(evidence(events), spec["params"]) == \
        pytest.approx(25.0)


def test_forward_and_backward_shares_make_up_the_flash_share():
    events = [Event(DEV, OPS, n + ":tpu_custom_call", t, 0.1)
              for t, n in enumerate(["flash_attention_fwd.13",
                                     "flash_attention_bwd.10",
                                     "flash_attention_fwd.14"])] + \
        [Event(DEV, OPS, "fusion.1", 3.0, 0.7)]
    read = {m: op_time_share.read(evidence(events), SPECS[m]["params"])
            for m in ("flash_share.train", "flash_fwd_share.train",
                      "flash_bwd_share.train")}
    assert read["flash_fwd_share.train"] == pytest.approx(20.0)
    assert read["flash_bwd_share.train"] == pytest.approx(10.0)
    assert read["flash_share.train"] == pytest.approx(30.0)


CELLS = [w["name"] for w in MANIFEST["workloads"]]
# the cells that train: the others (generation) have a file of their own,
# test_benchmark_generate.py
TRAIN_CELLS = [c for c in CELLS
               if "train_tok_s" in reported_by(MANIFEST, c, "end_to_end")]


def test_every_spec_is_in_a_cell_and_every_cells_metric_has_a_spec():
    reported = {c: reported_by(MANIFEST, c, "per_layer") for c in CELLS}
    everywhere = {n for names in reported.values() for n in names}
    assert everywhere == set(SPECS)
    for name, spec in SPECS.items():
        assert (REPO / "benchmark" / "readers"
                / f"{spec['reader']}.py").is_file(), name
    # the collectives' metrics are the four-chip cell's alone
    one_chip = [w["name"] for w in MANIFEST["workloads"] if w["chips"] == 1]
    for cell in one_chip:
        assert not [n for n in reported[cell] if n.startswith("collective")]
    # the phase metrics this file tests are in every training cell
    for name in ("forward_ms.train", "recompute_ms.train",
                 "backward_ms.train", "loss_head_ms.train",
                 "optimizer_ms.train", "steady_tok_s.train",
                 "flash_fwd_share.train",
                 "flash_bwd_share.train", "scope_coverage.train",
                 "host_ms.train"):
        assert all(name in reported[c] for c in TRAIN_CELLS), name


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_the_cell_rehearses_with_every_reader(tmp_path, cell):
    # in a copy: the trace goes to .bench_scratch/<cell> of the checkout,
    # and another worker may be rehearsing the same cell in this one
    shutil.copytree(REPO / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    os.symlink(REPO / "deepspeed_tpu", tmp_path / "deepspeed_tpu")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    p = subprocess.run(
        ["nice", "-n", "15", sys.executable, "benchmark/run.py",
         "--workload", cell, "--rehearse", "--trace", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    assert "correct True" in p.stderr and "readers ran" in p.stderr
    # the span ring is there on the CPU too; the device's planes are not
    assert "host_ms.train" in p.stderr.split("readers ran")[1]
    ran = p.stderr.split("readers ran")[1]
    assert "steady_tok_s.train" in ran and "'steady_tok_s': " in p.stderr
    assert "'setup_parts_s': {'import_jax':" in p.stderr
