"""The readers that see inside a generation step (``gen_gap_time``: the
span ring and the device trace on one clock; ``gen_span_time``: the ring
by decode window; ``serve_scope_time``: operations by the launch that
holds them and that program's scope map) on hand-written events, rings
and maps with known answers; and the new metric files against
``BENCHMARK.json``."""

import json
import types
from pathlib import Path

import pytest

from benchmark import tracing
from benchmark.readers import gen_gap_time, gen_span_time, serve_scope_time
from benchmark.run import reported_by
from benchmark.tracing import Event

REPO = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
SPECS = {p.name[:-len(".json")]: json.loads(p.read_text())
         for p in (REPO / "benchmark" / "layer_metrics").glob("*.json")}
GEN_CELLS = ["opt-1.3b.rollout-256", "joyai-llm-flash.rollout-64x256"]
NEW = {"host_ms.gen": "gen_span_time",
       "gap_host_ms.gen": "gen_gap_time", "gap_launch_ms.gen": "gen_gap_time",
       "gap_unattributed.gen": "gen_gap_time",
       "prefill_ms.gen": "serve_scope_time",
       "decode_ms.gen": "serve_scope_time",
       "attn_proj_ms.gen": "serve_scope_time",
       "kv_write_ms.gen": "serve_scope_time",
       "mlp_ms.gen": "serve_scope_time", "head_ms.gen": "serve_scope_time",
       "router_ms.gen": "serve_scope_time",
       "scope_coverage.gen": "serve_scope_time"}

DEV, HOST = "/device:TPU:0", "/host:CPU"
OPS, MODULES = tracing.OPS_LINE, tracing.MODULES_LINE
# the ring's clock (perf_counter) runs 1000 s behind the profiler's
OFFSET = 1000.0


def span(name, start, dur, id, parent=None, annotated=True):
    s = {"name": name, "start": start, "duration_s": dur, "id": id,
         "parent": parent, "depth": 0, "track": "MainThread"}
    if annotated:
        s["annotated"] = True
    return s


# one traced call on the ring: a ragged step and two decode windows with
# their leaves. Times in ms on the ring's clock, from 0
CALL = [
    ("generate", 0.0, 40.0, 1, None),
    ("gen_admit", 0.0, 1.0, 2, 1),
    ("ragged_pack", 1.0, 1.0, 3, 1),
    ("ragged_step", 2.0, 8.0, 4, 1),
    ("ragged_dispatch", 2.0, 2.0, 5, 4),
    ("ragged_fetch", 4.0, 6.0, 6, 4),
    ("ragged_bookkeeping", 10.0, 1.0, 7, 1),
    ("gen_first_token", 11.0, 1.0, 8, 1),
    ("gen_schedule", 12.0, 1.0, 9, 1),
    ("decode_window", 13.0, 10.0, 10, 1),
    ("window_assemble", 13.0, 1.0, 11, 10),
    ("window_dispatch", 14.0, 1.0, 12, 10),
    ("window_fetch", 15.0, 8.0, 13, 10),
    ("window_bookkeeping", 23.0, 2.0, 14, 1),
    ("gen_schedule", 25.0, 1.0, 15, 1),
    ("decode_window", 26.0, 10.0, 16, 1),
    ("window_assemble", 26.0, 2.0, 17, 16),
    ("window_dispatch", 28.0, 1.0, 18, 16),
    ("window_fetch", 29.0, 7.0, 19, 16),
    ("window_bookkeeping", 36.0, 3.0, 20, 1),
    ("gen_schedule", 39.0, 0.5, 21, 1),
    ("gen_flush", 39.5, 0.5, 22, 1),
]
RING = [span(n, s * 1e-3, d * 1e-3, i, p) for n, s, d, i, p in CALL]
# an untraced call before it: the same names, no mark, another clock reading
EARLIER = [span(n, s * 1e-3 - 1.0, d * 1e-3, 100 + i, p and 100 + p,
                annotated=False) for n, s, d, i, p in CALL]


def host_events(ring=RING, shift=OFFSET, nudge=None):
    """The host plane as the runner keeps it: the coarse spans only."""
    out = []
    for s in ring:
        if s["name"] in ("ragged_step", "decode_window"):
            at = s["start"] + shift + (nudge or {}).get(s["id"], 0.0)
            out.append(Event(HOST, "MainThread", s["name"], at,
                             s["duration_s"]))
    return out


def ms(x):
    return OFFSET + x * 1e-3


# the device under that call: three launches, the first at 3 ms. Between
# the ragged step's end (9.5) and the first window's start (14.5) the
# host ran ragged_fetch's tail, bookkeeping, the pick, the schedule,
# assemble and half the dispatch; an operation still runs 0.5 ms into the
# gap. Between the windows (22.5 .. 28.5): fetch's tail, bookkeeping,
# schedule, assemble, half the dispatch
LAUNCHES = [
    Event(DEV, MODULES, "jit_ragged_step(11)", ms(3.0), 6.5e-3),
    Event(DEV, MODULES, "jit_decode_window_greedy(22)", ms(14.5), 8.0e-3),
    Event(DEV, MODULES, "jit_decode_window_greedy(22)", ms(28.5), 7.0e-3),
]
OPS_RUN = [
    Event(DEV, OPS, "fusion.1", ms(3.0), 3.0e-3),
    Event(DEV, OPS, "fusion.2", ms(6.0), 4.0e-3),        # ends at 10.0
    Event(DEV, OPS, "fusion.1", ms(14.5), 8.0e-3),
    Event(DEV, OPS, "fusion.1", ms(28.5), 3.0e-3),
    Event(DEV, OPS, "fusion.3", ms(31.5), 3.0e-3),
    Event(DEV, OPS, "copy.9", ms(34.5), 1.0e-3),
]
TRACE = tracing.Events(LAUNCHES + OPS_RUN + host_events())
GAP = SPECS["gap_host_ms.gen"]["params"]


@pytest.fixture
def ring(monkeypatch):
    from deepspeed_tpu.telemetry import trace
    monkeypatch.setattr(trace, "export", lambda name=None: EARLIER + RING)


def evidence(events=TRACE, steps=1):
    ev = types.SimpleNamespace(events=events, slice_steps=steps)
    ev.host_spans = lambda: [e for e in events if e.plane == HOST]
    return ev


# ---------------------------------------------------------------------------
# one clock
# ---------------------------------------------------------------------------
def test_the_clocks_pair_with_a_known_offset():
    got = gen_gap_time.clock_offset(host_events(), EARLIER + RING)
    assert got == pytest.approx(OFFSET, abs=1e-9)
    # a few microseconds between the annotation and the clock reading
    jitter = {4: 3e-6, 10: -2e-6, 16: 5e-6}
    got = gen_gap_time.clock_offset(host_events(nudge=jitter), RING)
    assert got == pytest.approx(OFFSET, abs=5e-6)


def test_a_miscount_gives_none():
    assert gen_gap_time.clock_offset(host_events()[:-1], RING) is None
    assert gen_gap_time.clock_offset([], RING) is None
    # the ring without the mark: the parent's
    assert gen_gap_time.clock_offset(host_events(), EARLIER) is None
    # as many spans, another order of names
    swapped = [e._replace(name="decode_window") for e in host_events()]
    assert gen_gap_time.clock_offset(swapped, RING) is None


def test_an_outlier_gives_none():
    assert gen_gap_time.clock_offset(
        host_events(nudge={16: 0.3e-3}), RING) is None
    assert gen_gap_time.clock_offset(
        host_events(nudge={16: 0.3e-3}), RING, tolerance_s=1e-3) \
        == pytest.approx(OFFSET)


# ---------------------------------------------------------------------------
# the gaps, split
# ---------------------------------------------------------------------------
def test_a_gap_is_what_no_launch_and_no_operation_covers():
    gaps = gen_gap_time.idle_between_launches(TRACE, DEV)
    assert [(round(lo - OFFSET, 6), round(hi - OFFSET, 6))
            for lo, hi in gaps] == [(0.010, 0.0145), (0.0225, 0.0285)]


def test_a_gap_splits_across_the_spans_it_lies_under():
    by, rest = gen_gap_time.split(
        [(10.0, 20.0)],
        [("window_bookkeeping", 8.0, 13.0), ("gen_schedule", 13.0, 14.0),
         ("window_dispatch", 15.0, 30.0), ("decode_window", 14.0, 40.0)],
        {"host": ["window_bookkeeping", "gen_schedule"],
         "launch": ["window_dispatch"]})
    assert by == {"host": pytest.approx(4.0), "launch": pytest.approx(5.0)}
    assert rest == pytest.approx(1.0)       # 14 .. 15: under no leaf


@pytest.mark.parametrize("metric,want", [
    # gap 1 (10.0 .. 14.5): bookkeeping 1 + pick 1 + schedule 1 +
    # assemble 1; gap 2 (22.5 .. 28.5): bookkeeping 2 + schedule 1 +
    # assemble 2
    ("gap_host_ms.gen", 4.0 + 5.0),
    # gap 1: dispatch's half 0.5; gap 2: fetch's tail 0.5 + dispatch 0.5
    ("gap_launch_ms.gen", 0.5 + 1.0),
    ("gap_unattributed.gen", 0.0),
])
def test_gap_metrics_on_the_hand_written_call(ring, metric, want):
    spec = SPECS[metric]
    assert spec["reader"] == "gen_gap_time"
    assert gen_gap_time.read(evidence(), spec["params"]) \
        == pytest.approx(want, abs=1e-6)


def test_gap_metrics_add_up_to_the_idle_time(ring):
    host, launch, rest = (gen_gap_time.read(evidence(), SPECS[m]["params"])
                          for m in ("gap_host_ms.gen", "gap_launch_ms.gen",
                                    "gap_unattributed.gen"))
    idle = 1e3 * tracing.total(
        gen_gap_time.idle_between_launches(TRACE, DEV))
    assert host + launch + rest / 100.0 * idle == pytest.approx(idle)
    assert idle == pytest.approx(10.5)


def test_time_under_no_leaf_is_unattributed(monkeypatch):
    from deepspeed_tpu.telemetry import trace
    no_pick = [s for s in RING if s["name"] != "gen_first_token"]
    monkeypatch.setattr(trace, "export", lambda name=None: no_pick)
    got = gen_gap_time.read(evidence(), SPECS["gap_unattributed.gen"]
                            ["params"])
    assert got == pytest.approx(100.0 * 1.0 / 10.5)


def test_gap_metrics_give_none_without_the_marks(monkeypatch):
    from deepspeed_tpu.telemetry import trace
    monkeypatch.setattr(trace, "export", lambda name=None: EARLIER)
    assert gen_gap_time.read(evidence(), GAP) is None
    monkeypatch.setattr(trace, "export", lambda name=None: RING)
    assert gen_gap_time.read(evidence(tracing.Events(host_events())),
                             GAP) is None            # no device plane


# ---------------------------------------------------------------------------
# the host's work a decode window
# ---------------------------------------------------------------------------
def test_host_ms_sums_the_leaves_of_each_window(ring):
    spec = SPECS["host_ms.gen"]
    assert spec["reader"] == "gen_span_time"
    assert spec["source"] == "program_span"
    p = spec["params"]
    # schedule + assemble + dispatch + bookkeeping, of either call's two
    # windows: 1 + 1 + 1 + 2 and 1 + 2 + 1 + 3; fetch is the device's
    per = gen_span_time.per_window(RING, p["spans"], p["before"])
    assert per == [pytest.approx(5e-3), pytest.approx(7e-3)]
    assert gen_span_time.read(None, p) == pytest.approx(6.0)


def test_host_ms_gives_none_without_the_leaves(monkeypatch):
    from deepspeed_tpu.telemetry import trace
    coarse = [s for s in RING if s["name"] in ("ragged_step",
                                               "decode_window")]
    monkeypatch.setattr(trace, "export", lambda name=None: coarse)
    assert gen_span_time.read(None, SPECS["host_ms.gen"]["params"]) is None


# ---------------------------------------------------------------------------
# operations by launch, program and phase
# ---------------------------------------------------------------------------
# fusion.1 is the ragged step's MLP matmul and the decode window's
# query projection: one name, two instructions
MAPS = {
    "ragged_step": {
        "fusion.1": "jit(ragged_step)/layers/while/body/mlp/dot_general",
        "fusion.2": "jit(ragged_step)/head/dot_general"},
    "decode_window_greedy": {
        "fusion.1": "jit(decode_window_greedy)/while/body/layers/while/"
                    "body/attention/qkv_proj/dot_general",
        "fusion.3": "jit(decode_window_greedy)/while/body/pick/argmax"},
}


# the decode window compiled a second time, for a wider block table:
# its fusion.1 is the same projection under another path, its fusion.3
# is an MLP matmul and not the pick, and only it has a copy.9
WIDER = {
    "fusion.1": "jit(decode_window_greedy)/while/body/layers/while/body/"
                "attention/out_proj/dot_general",
    "fusion.3": "jit(decode_window_greedy)/while/body/layers/while/body/"
                "mlp/dot_general",
    "copy.9": "jit(decode_window_greedy)/while/body/layers/while/body/"
              "attention/kv_write/scatter"}


def offer(monkeypatch, maps):
    """The program offers ``maps``: ``{program: [a map a signature]}``."""
    from deepspeed_tpu.telemetry import memory
    monkeypatch.setattr(memory, "scopes_offered",
                        lambda program: maps.get(program, []))


@pytest.fixture
def program_maps(monkeypatch):
    offer(monkeypatch, {p: [m] for p, m in MAPS.items()})


def test_a_launchs_name_is_its_program():
    assert serve_scope_time.program_of("jit_ragged_step(1234)") \
        == "ragged_step"
    assert serve_scope_time.program_of("jit_decode_window_greedy") \
        == "decode_window_greedy"
    assert serve_scope_time.program_of("jit__lambda_(99)") == "_lambda_"
    assert serve_scope_time.program_of("something else") is None


@pytest.mark.parametrize("metric,want", [
    ("prefill_ms.gen", 7.0),            # fusion.1 3 + fusion.2 4
    ("decode_ms.gen", 15.0),            # 8 + 3 + 3 + the copy's 1
    ("mlp_ms.gen", 3.0),                # the ragged step's fusion.1 alone
    ("attn_proj_ms.gen", 11.0),         # the windows' fusion.1: 8 + 3
    ("head_ms.gen", 7.0),               # head 4 + pick 3
    ("kv_write_ms.gen", None),
    ("router_ms.gen", None),
    ("scope_coverage.gen", 100.0 * 21.0 / 22.0),    # copy.9 in no map
])
def test_scope_metrics_on_the_hand_written_call(program_maps, metric, want):
    spec = SPECS[metric]
    assert spec["reader"] == "serve_scope_time"
    got = serve_scope_time.read(evidence(), spec["params"])
    assert got == (None if want is None else pytest.approx(want))


def test_the_programs_add_up_to_the_busy_time(program_maps):
    both = sum(serve_scope_time.read(evidence(), SPECS[m]["params"])
               for m in ("prefill_ms.gen", "decode_ms.gen"))
    busy, _ = tracing.busy_and_window(TRACE)
    assert both == pytest.approx(1e3 * busy)


def test_scope_metrics_give_none_without_names_or_maps(monkeypatch,
                                                        program_maps):
    lambdas = tracing.Events(
        [e._replace(name="jit__lambda_(7)") if e.line == MODULES else e
         for e in TRACE])
    for m in ("prefill_ms.gen", "mlp_ms.gen", "scope_coverage.gen"):
        assert serve_scope_time.read(evidence(lambdas),
                                     SPECS[m]["params"]) is None
    offer(monkeypatch, {})
    assert serve_scope_time.read(evidence(),
                                 SPECS["mlp_ms.gen"]["params"]) is None


@pytest.mark.parametrize("metric,want", [
    ("decode_ms.gen", 15.0),            # by launch: no map is asked
    ("mlp_ms.gen", 3.0),                # the ragged step's alone, still
    ("attn_proj_ms.gen", 11.0),         # fusion.1: both say attn_proj
    ("head_ms.gen", 4.0),               # fusion.3: pick or mlp? neither
    ("kv_write_ms.gen", 1.0),           # copy.9: the one map that has it
    ("scope_coverage.gen", 100.0 * 19.0 / 22.0),    # fusion.3's 3 show
])
def test_one_program_under_two_signatures(monkeypatch, metric, want):
    """A launch does not say which executable of its program it was, so
    a name is trusted where every map that has it agrees on the phase,
    and counts as not known, in ``scope_coverage.gen``, where not."""
    offer(monkeypatch, {
        "ragged_step": [MAPS["ragged_step"]],
        "decode_window_greedy": [MAPS["decode_window_greedy"], WIDER]})
    got = serve_scope_time.read(evidence(), SPECS[metric]["params"])
    assert got == pytest.approx(want)


# ---------------------------------------------------------------------------
# the files
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("metric", sorted(NEW))
def test_new_metric_files_mirror_the_manifest(metric):
    spec = SPECS[metric]
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == metric)
    assert spec["reader"] == NEW[metric]
    assert (REPO / "benchmark" / "readers" / f"{spec['reader']}.py").is_file()
    for key in ("unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], key
    assert entry["moves"] == "gen_tok_s"
    assert entry["workloads"] == (GEN_CELLS[1:] if metric == "router_ms.gen"
                                  else GEN_CELLS)
    for cell in entry["workloads"]:
        assert metric in reported_by(MANIFEST, cell, "per_layer")


def test_the_new_metrics_follow_what_was_there():
    names = [m["name"] for m in MANIFEST["per_layer"]]
    first = min(names.index(m) for m in NEW)
    assert sorted(names[first:]) == sorted(NEW)
    assert len(names) == len(set(names)) == 30 + len(NEW)


# ---------------------------------------------------------------------------
# what two earlier pins said of the lists, restated after the addition:
# test_benchmark_latent_experts.py::
# test_the_cell_is_appended_entries_at_published_widths and
# test_benchmark_generate.py::
# test_what_the_generation_cell_reports_and_what_the_others_do_not hold
# that per_layer ENDS with PR 36's five and that the cells report what
# they reported then, which no addition leaves true. They fail, unmarked
# (a PR may edit no file the benchmark has), until a ``benchmark`` PR
# rewrites them; every other line of theirs is held here meanwhile
# ---------------------------------------------------------------------------
OLD_GEN = ["compiles.gen", "idle.gen", "peak_hbm.gen"]
PER_HEAD = ["ragged_share.gen", "ragged_roofline.gen"]
LATENT = ["latent_share.gen", "latent_roofline.gen", "experts_share.gen",
          "experts_roofline.gen", "experts_touched.gen"]
INSIDE = [m["name"] for m in MANIFEST["per_layer"] if m["name"] in NEW]


def test_what_the_generation_cells_report_now():
    dense, sparse = GEN_CELLS
    for cell in GEN_CELLS:
        assert reported_by(MANIFEST, cell, "end_to_end") \
            == ["setup_s", "gen_tok_s"]
    assert reported_by(MANIFEST, dense, "per_layer") == OLD_GEN + PER_HEAD \
        + [m for m in INSIDE if m != "router_ms.gen"]
    assert reported_by(MANIFEST, sparse, "per_layer") \
        == OLD_GEN + LATENT + INSIDE
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert (e2e["gen_tok_s"]["bound"], e2e["train_tok_s"]["bound"],
            e2e["setup_s"]["bound"]) == (0.02, 0.01, 0.1)
    assert e2e["gen_tok_s"]["workloads"] == GEN_CELLS
    # no training cell reports a generation metric or gen_tok_s
    gen_names = {"gen_tok_s"} | {m["name"] for m in MANIFEST["per_layer"]
                                 if m["moves"] == "gen_tok_s"}
    for cell in e2e["train_tok_s"]["workloads"]:
        assert not gen_names & set(
            reported_by(MANIFEST, cell, "end_to_end")
            + reported_by(MANIFEST, cell, "per_layer"))


def test_the_latent_cells_entries_stand_where_pr36_put_them():
    from benchmark import manifest
    manifest.check(REPO)
    sparse = GEN_CELLS[1]
    assert MANIFEST["configs"][-1]["name"] == "joyai-llm-flash"
    assert MANIFEST["workloads"][-1]["name"] == sparse
    assert MANIFEST["workloads"][-1]["chips"] == 1
    names = [m["name"] for m in MANIFEST["per_layer"]]
    # PR 36's five close the accepted list; PR 38's follow them
    assert names[30 - len(LATENT):30] == LATENT
    assert names[30:] == INSIDE
    for m in MANIFEST["per_layer"]:
        if m["name"] in LATENT:
            assert m["workloads"] == [sparse] and m["moves"] == "gen_tok_s"
        if m["name"] in PER_HEAD:
            assert sparse not in m["workloads"]     # per-head arithmetic
        if m["name"] in OLD_GEN:
            assert m["workloads"][-1] == sparse
    assert reported_by(MANIFEST, sparse, "end_to_end") \
        == ["setup_s", "gen_tok_s"]
    joined = [m["name"] for m in MANIFEST["end_to_end"]
              + MANIFEST["per_layer"][:30]
              if m["name"] not in LATENT and sparse in m.get("workloads", [])]
    assert joined == ["gen_tok_s"] + OLD_GEN
    # the published widths and the cuts, as the old pin holds them
    config = json.loads(
        (REPO / "benchmark" / "configs" / "joyai-llm-flash.json").read_text())
    assert config["reduced"] == MANIFEST["configs"][-1]["reduced"] == [
        "num_hidden_layers", "num_nextn_predict_layers"]
    for key, want in dict(
            hidden_size=2048, num_heads=32, q_lora_rank=1536,
            kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
            v_head_dim=128, intermediate_size=7168,
            moe_intermediate_size=768, moe_num_experts=256, moe_top_k=8,
            moe_shared_experts=1, vocab_size=129280).items():
        assert config["fields"][key] == want
    # every number of the source's config stands at the top level too
    for key, value in config["published"].items():
        if isinstance(value, (int, float)) and not isinstance(value, bool) \
                and key not in config["reduced"]:
            assert config[key] == value, key
