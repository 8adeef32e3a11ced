"""The prompt path from inside (PR 54): the seventeen per-layer metrics
``prefill_*_ms.gen``, ``gap_upload_ms.gen`` / ``gap_call_ms.gen`` /
``gap_fetch_ms.gen`` and ``gc_pause_ms.gen`` / ``gc_pause_ms.train``:
what ``BENCHMARK.json`` and the files say of them (entries found BY
NAME, "at least these", never by position: a later PR appends), the
three new readers on ``test_benchmark_gen_inside.py``'s hand-written
call, the sums the metrics promise (a program's scopes add up to its
``prefill_ms.gen``, a launch's three parts to ``gap_launch_ms.gen``),
0.0 and not None where the launches are there and a scope took no time,
and None where the program has not what it takes (the parent of the PR
that added it)."""

import json
import sys
import types
from pathlib import Path

import pytest

from benchmark import manifest, tracing
from benchmark.readers import (gc_pause_time, gen_gap_parts, gen_gap_time,
                               serve_program_scope_time, serve_scope_time)
from benchmark.run import reported_by
from benchmark.tracing import Event

import test_benchmark_gen_inside as inside
from test_benchmark_gen_inside import (DEV, MAPS, MODULES, OPS, RING, TRACE,
                                       WIDER, evidence, ms, offer, span)

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmark"
# the generation cells that take the new metrics: all but
# ``trinity-mini.rollout-16x8192-512``, whose accepted test pins the
# cell's per-layer list EXACTLY (``test_benchmark_window.py``: ``sorted(
# mine) == sorted(JOINED + NEW)``): it joins these lists once a
# ``benchmark`` PR has pinned that "at least these" (PERF.md §7)
TRINITY = "trinity-mini.rollout-16x8192-512"
G6 = ["opt-1.3b.rollout-256", "joyai-llm-flash.rollout-64x256",
      "ling-3.0-flash.rollout-128x256",
      "granite-4.0-h-small.rollout-64x1024-256",
      "nemotron-3-nano-30b-a3b.rollout-128x256-384"]
S5 = G6[1:]
LING = [G6[2]]
SSM = G6[3:]
TRAIN = ["opt-125m.train-dense", "opt-1.3b.zero3-dp4"]
GEN, EXPERTS, LINEAR, STATE = ("generation step", "expert layer",
                               "linear attention layers",
                               "state-space layers")
# metric -> (layer, the cells that report it AT LEAST, its scopes)
PREFILL = {
    "prefill_attn_proj_ms.gen": (GEN, G6, [
        "attention", "mla_attention", "qkv_proj", "out_proj", "attn_gate",
        "kv_write"]),
    "prefill_attn_kernel_ms.gen": (GEN, G6, ["attn_kernel"]),
    "prefill_mlp_ms.gen": (GEN, G6, ["mlp", "dense_mlp",
                                     "moe_shared_expert"]),
    "prefill_experts_ms.gen": (EXPERTS, S5, ["moe_experts"]),
    "prefill_router_ms.gen": (EXPERTS, S5, ["moe_router"]),
    "prefill_head_ms.gen": (GEN, G6, ["embed", "head", "pick"]),
    "prefill_linear_ms.gen": (LINEAR, LING, [
        "linear_attention", "kda_proj", "kda_conv", "kda_chunk",
        "kda_out"]),
    "prefill_ssm_proj_ms.gen": (STATE, SSM, ["ssm_proj", "ssm_out"]),
    "prefill_ssm_conv_ms.gen": (STATE, SSM, ["ssm_conv"]),
    "prefill_ssm_scan_ms.gen": (STATE, SSM, ["ssm_scan"]),
    "prefill_ssm_norm_ms.gen": (STATE, SSM, ["ssm_gate_norm",
                                             "ssm_mixer"]),
    "prefill_other_ms.gen": (GEN, G6, ["other"]),
}
GAPS = {"gap_upload_ms.gen": ["ragged_upload", "window_upload"],
        "gap_call_ms.gen": ["ragged_call", "window_call"],
        "gap_fetch_ms.gen": ["ragged_fetch", "window_fetch", "step_fetch"]}
PAUSES = {"gc_pause_ms.gen": (GEN, G6, "gen_tok_s"),
          "gc_pause_ms.train": ("training step", TRAIN, "train_tok_s")}
NEW = list(PREFILL) + list(GAPS) + list(PAUSES)
SPECS = {m: json.loads((BENCH / "layer_metrics" / f"{m}.json").read_text())
         for m in NEW + ["prefill_ms.gen", "gap_launch_ms.gen"]}


def _named(entries, name):
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, name
    return found[0]


# ---------------------------------------------------------------------------
# the tree
# ---------------------------------------------------------------------------
def test_the_tree_passes_the_manifest():
    manifest.check(REPO)


@pytest.mark.parametrize("name", NEW)
def test_the_entry_and_its_file_by_name(name):
    bm = manifest.read(REPO)
    m = _named(bm["per_layer"], name)
    if name in PREFILL:
        layer, cells, _ = PREFILL[name]
        want = (layer, "ms", "lower", "device_trace", "gen_tok_s")
        assert SPECS[name]["reader"] == "serve_program_scope_time"
        assert SPECS[name]["params"] == {"programs": ["ragged_step"],
                                         "scopes": PREFILL[name][2]}
    elif name in GAPS:
        cells = G6
        want = (GEN, "ms", "lower", "device_trace", "gen_tok_s")
        assert SPECS[name]["reader"] == "gen_gap_parts"
        assert SPECS[name]["params"] == {"spans": GAPS[name]}
    else:
        layer, cells, moves = PAUSES[name]
        want = (layer, "ms", "lower", "program_span", moves)
        assert SPECS[name]["reader"] == "gc_pause_time"
    assert (m["layer"], m["unit"], m["better"], m["source"],
            m["moves"]) == want
    assert set(m["workloads"]) >= set(cells)            # at least these
    for key in ("unit", "better", "source", "layer", "moves"):
        assert SPECS[name][key] == m[key]
    for cell in cells:
        assert name in reported_by(bm, cell, "per_layer")


def test_the_prompt_scopes_are_the_programs_table_once_each():
    """Between them the ``prefill_*`` metrics name every scope a prompt
    launch can hold, each once, so that a cell's add up to its
    ``prefill_ms.gen``: every word of the program's table but the two
    one-token forms, which the ragged step never runs, and ``other``."""
    from deepspeed_tpu.utils.xla_profile import _SERVE_PHASE_OF_SCOPE
    named = [s for _, _, scopes in PREFILL.values() for s in scopes]
    assert len(named) == len(set(named))
    assert set(named) == (set(_SERVE_PHASE_OF_SCOPE) | {"other"}) \
        - {"kda_state", "ssm_state"}


@pytest.mark.parametrize("cell", G6)
def test_a_cell_reports_every_scope_its_block_has(cell):
    """What a cell's prompt launch can spend time in is in its list: the
    shared scopes everywhere, the experts' on the five sparse cells, the
    linear layers' on ling's, the state-space layers' on the two hybrids."""
    mine = set(reported_by(manifest.read(REPO), cell, "per_layer"))
    want = {m for m, (_, cells, _) in PREFILL.items() if cell in cells}
    assert want <= mine
    assert {"prefill_ms.gen", "gap_launch_ms.gen", "gc_pause_ms.gen"} \
        | set(GAPS) <= mine


def test_the_cell_with_an_exact_pin_is_left_as_it_was():
    """``trinity-mini``'s cell reports what it reported: its accepted
    test allows it nothing more."""
    bm = manifest.read(REPO)
    assert not set(reported_by(bm, TRINITY, "per_layer")) & set(NEW)


def test_nothing_accepted_was_edited_or_lost():
    """The entries that were there stand first, in their order, and the
    seventeen follow: entries only are added."""
    bm = manifest.read(REPO)
    names = [m["name"] for m in bm["per_layer"]]
    assert len(names) == len(set(names))
    first_new = min(names.index(n) for n in NEW)
    assert set(names[first_new:]) >= set(NEW)
    assert not set(names[:first_new]) & set(NEW)
    for accepted in ("prefill_ms.gen", "gap_launch_ms.gen",
                     "gap_host_ms.gen", "gap_unattributed.gen",
                     "host_ms.gen", "ssm_ms.gen", "scope_coverage.gen"):
        assert names.index(accepted) < first_new


# ---------------------------------------------------------------------------
# device time by program and scope
# ---------------------------------------------------------------------------
def _scopes(*scopes):
    return {"programs": ["ragged_step"], "scopes": list(scopes)}


@pytest.fixture
def program_maps(monkeypatch):
    offer(monkeypatch, {p: [m] for p, m in MAPS.items()})


@pytest.mark.parametrize("scopes,want", [
    (["mlp"], 3.0),                 # the ragged step's fusion.1 alone:
    (["qkv_proj"], 0.0),            # ... the windows' fusion.1 is not it
    (["head"], 4.0),                # fusion.2
    (["pick"], 0.0),                # the windows' fusion.3
    (["other"], 0.0),
    (["mlp", "head", "other"], 7.0),
])
def test_scopes_of_the_prompt_program_on_the_hand_written_call(
        program_maps, scopes, want):
    got = serve_program_scope_time.read(evidence(), _scopes(*scopes))
    assert got == pytest.approx(want) and got is not None


def test_the_decode_programs_scopes_stand_apart(program_maps):
    p = {"programs": ["decode"], "scopes": ["qkv_proj"]}
    assert serve_program_scope_time.read(evidence(), p) \
        == pytest.approx(11.0)
    p = {"programs": ["decode"], "scopes": ["other"]}       # copy.9
    assert serve_program_scope_time.read(evidence(), p) \
        == pytest.approx(1.0)


def test_a_programs_metrics_add_up_to_its_prefill_ms(program_maps):
    """Every reported ``prefill_*`` of a cell, summed, is that cell's
    ``prefill_ms.gen``: here all twelve, on a call whose prompt launch
    holds an instruction no map knows."""
    unknown = tracing.Events(list(TRACE) + [
        Event(DEV, OPS, "fusion.77", ms(9.0), 0.25e-3)])
    whole = serve_scope_time.read(evidence(unknown),
                                  SPECS["prefill_ms.gen"]["params"])
    parts = {m: serve_program_scope_time.read(evidence(unknown),
                                              SPECS[m]["params"])
             for m in PREFILL}
    assert all(v is not None for v in parts.values())
    assert sum(parts.values()) == pytest.approx(whole)
    assert parts["prefill_other_ms.gen"] == pytest.approx(0.25)
    assert parts["prefill_mlp_ms.gen"] == pytest.approx(3.0)
    assert parts["prefill_head_ms.gen"] == pytest.approx(4.0 - 0.25)


def test_two_signatures_that_disagree_are_other(monkeypatch):
    """The ragged step compiled twice and its ``fusion.2`` is the head in
    one executable and an MLP matmul in the other: neither; ``fusion.1``
    is ``mlp`` in both."""
    second = {"fusion.1": MAPS["ragged_step"]["fusion.1"],
              "fusion.2": "jit(ragged_step)/layers/while/body/mlp/"
                          "dense_mlp/dot_general"}
    offer(monkeypatch, {"ragged_step": [MAPS["ragged_step"], second],
                        "decode_window_greedy": [
                            MAPS["decode_window_greedy"], WIDER]})
    read = serve_program_scope_time.read
    assert read(evidence(), _scopes("mlp")) == pytest.approx(3.0)
    assert read(evidence(), _scopes("head")) == 0.0
    assert read(evidence(), _scopes("dense_mlp")) == 0.0
    assert read(evidence(), _scopes("other")) == pytest.approx(4.0)


def test_zero_where_the_launches_are_there_none_where_they_are_not(
        monkeypatch, program_maps):
    """A ``null`` on a ledger line reads as a metric done away with: a
    scope that took no time in a launch that ran is 0.0."""
    read = serve_program_scope_time.read
    got = read(evidence(), SPECS["prefill_ssm_scan_ms.gen"]["params"])
    assert got == 0.0 and isinstance(got, float)
    # no launch of the program in the call: nothing to read
    assert read(evidence(), {"programs": ["spec_window"],
                             "scopes": ["mlp"]}) is None
    # no device plane, no slice
    assert read(evidence(tracing.Events(inside.host_events())),
                _scopes("mlp")) is None
    assert read(evidence(steps=0), _scopes("mlp")) is None
    # the program offers no maps
    offer(monkeypatch, {})
    assert read(evidence(), _scopes("mlp")) is None


def test_none_on_a_program_without_serve_scope(monkeypatch, program_maps):
    """The parent of this PR has ``serve_phase`` and no ``serve_scope``:
    the reader returns nothing there and does not raise."""
    from deepspeed_tpu.utils import xla_profile
    stub = types.ModuleType("deepspeed_tpu.utils.xla_profile")
    stub.serve_phase = xla_profile.serve_phase
    monkeypatch.setitem(sys.modules, "deepspeed_tpu.utils.xla_profile",
                        stub)
    assert serve_program_scope_time.read(evidence(), _scopes("mlp")) \
        is None


def test_two_planes_are_a_mean_over_chips(program_maps):
    second = [e._replace(plane="/device:TPU:1") for e in TRACE
              if e.plane == DEV]
    both = tracing.Events(list(TRACE) + second)
    assert serve_program_scope_time.read(evidence(both), _scopes("mlp")) \
        == pytest.approx(3.0)


# ---------------------------------------------------------------------------
# a launch's three parts
# ---------------------------------------------------------------------------
# the hand-written call's dispatch spans with their two leaves: the
# ragged dispatch (2.0 .. 4.0) uploads for 0.5 ms, each window's
# dispatch (14.0 .. 15.0, 28.0 .. 29.0) for 0.25 ms, a sliver of 0.05
# ms between the two
PARTS = [
    ("ragged_upload", 2.0, 0.5, 31, 5), ("ragged_call", 2.5, 1.5, 32, 5),
    ("window_upload", 14.0, 0.25, 33, 12),
    ("window_call", 14.3, 0.7, 34, 12),
    ("window_upload", 28.0, 0.25, 35, 18),
    ("window_call", 28.3, 0.7, 36, 18),
]
SPLIT_RING = RING + [span(n, s * 1e-3, d * 1e-3, i, p)
                     for n, s, d, i, p in PARTS]


@pytest.fixture
def ring(monkeypatch):
    from deepspeed_tpu.telemetry import trace
    monkeypatch.setattr(trace, "export", lambda name=None: SPLIT_RING)


@pytest.mark.parametrize("metric,want", [
    # gap 1 (10.0 .. 14.5) holds the first window's upload whole and
    # 0.2 ms of its call; gap 2 (22.5 .. 28.5) 0.5 of the first
    # window's fetch, the second's upload and 0.2 of its call
    ("gap_upload_ms.gen", 0.25 + 0.25),
    ("gap_call_ms.gen", 0.2 + 0.2),
    ("gap_fetch_ms.gen", 0.5),
])
def test_gap_parts_on_the_hand_written_call(ring, metric, want):
    got = gen_gap_parts.read(evidence(), SPECS[metric]["params"])
    assert got == pytest.approx(want, abs=1e-9)


def test_the_three_parts_add_up_to_gap_launch_but_for_the_slivers(ring):
    parts = sum(gen_gap_parts.read(evidence(), SPECS[m]["params"])
                for m in GAPS)
    launch = gen_gap_time.read(evidence(),
                               SPECS["gap_launch_ms.gen"]["params"])
    assert launch == pytest.approx(1.5)         # the parents', as it was
    assert launch - parts == pytest.approx(2 * 0.05)    # 14.25 .. 14.3


def test_a_part_no_idle_time_lay_under_is_zero_not_none(monkeypatch):
    from deepspeed_tpu.telemetry import trace
    early = RING + [span("window_upload", 14.6e-3, 0.1e-3, 41, 12)]
    monkeypatch.setattr(trace, "export", lambda name=None: early)
    got = gen_gap_parts.read(evidence(), {"spans": ["window_upload"]})
    assert got == 0.0 and isinstance(got, float)


def test_gap_parts_give_none_where_the_program_has_not_the_leaves(
        monkeypatch):
    """The parent's ring has the dispatch spans and no part of them;
    ``gap_fetch_ms.gen`` reads there what it reads here."""
    from deepspeed_tpu.telemetry import trace
    monkeypatch.setattr(trace, "export", lambda name=None: RING)
    for m in ("gap_upload_ms.gen", "gap_call_ms.gen"):
        assert gen_gap_parts.read(evidence(), SPECS[m]["params"]) is None
    assert gen_gap_parts.read(
        evidence(), SPECS["gap_fetch_ms.gen"]["params"]) \
        == pytest.approx(0.5)
    # what gen_gap_time cannot read, this cannot either
    monkeypatch.setattr(trace, "export", lambda name=None: inside.EARLIER)
    assert gen_gap_parts.read(
        evidence(), SPECS["gap_fetch_ms.gen"]["params"]) is None
    monkeypatch.setattr(trace, "export", lambda name=None: SPLIT_RING)
    assert gen_gap_parts.read(
        evidence(tracing.Events(inside.host_events())),
        SPECS["gap_fetch_ms.gen"]["params"]) is None


# ---------------------------------------------------------------------------
# the collector's pauses
# ---------------------------------------------------------------------------
def pause(start_ms, dur_ms, id):
    s = span("gc_pause", start_ms * 1e-3, dur_ms * 1e-3, id,
             annotated=False)
    s["attrs"] = {"generation": 2, "collected": 0}
    return s


def test_pauses_inside_the_calls_a_call():
    """Two calls; a pause of 3 ms inside the first, one of 4 ms astride
    the second's end (1 ms of it inside), one of 60 ms between them (the
    runner's, not the program's)."""
    ring = [span("generate", 0.0, 40e-3, 1), span("generate", 0.2, 40e-3, 2),
            pause(10.0, 3.0, 3), pause(237.0, 4.0, 4), pause(100.0, 60.0, 5)]
    assert gc_pause_time.per_span(ring, ["generate"]) \
        == pytest.approx((3.0 + 3.0) / 2)
    assert gc_pause_time.per_span(ring[:2], ["generate"]) == 0.0
    assert gc_pause_time.per_span(ring[2:], ["generate"]) is None


def test_pauses_inside_the_steps_a_step():
    """A step is one ``train_data``, one ``train_step`` and one
    ``train_bookkeeping``; the first ``skip`` of each are the warm-up's."""
    ring, at = [], 0.0
    for step in range(4):
        for name, dur in (("train_data", 1e-3), ("train_step", 50e-3),
                          ("train_bookkeeping", 2e-3)):
            ring.append(span(name, at, dur, len(ring) + 1))
            at += dur
        at += 1e-3                      # the runner between two steps
    p = SPECS["gc_pause_ms.train"]["params"]
    assert p == {"inside": ["train_step", "train_data",
                            "train_bookkeeping"], "skip": 2}
    ring += [pause(5.0, 20.0, 90),              # in the warm-up: skipped
             pause(2 * 54.0 + 10.0, 2.0, 91),   # in step 2's train_step
             pause(3 * 54.0 + 52.0, 3.0, 92)]   # 1 ms of step 3's
    #                                             bookkeeping, 1 between
    #                                             the steps, 1 after
    got = gc_pause_time.per_span(ring, p["inside"], p["skip"])
    assert got == pytest.approx((2.0 + 1.0) / 2)


def test_gc_pause_reads_the_ring_and_zero_is_a_number(monkeypatch):
    from deepspeed_tpu.telemetry import trace
    monkeypatch.setattr(trace, "export", lambda name=None: RING)
    got = gc_pause_time.read(None, SPECS["gc_pause_ms.gen"]["params"])
    assert got == 0.0 and isinstance(got, float)
    monkeypatch.setattr(trace, "export",
                        lambda name=None: RING + [pause(5.0, 2.0, 99)])
    assert gc_pause_time.read(None, SPECS["gc_pause_ms.gen"]["params"]) \
        == pytest.approx(2.0)
    # a training ring has no ``generate`` root
    assert gc_pause_time.read(
        None, SPECS["gc_pause_ms.train"]["params"]) is None


def test_gc_pause_gives_none_on_a_program_without_the_hook(monkeypatch):
    """The parent of this PR has no ``telemetry.collector``: 0.0 there
    would say "no pause" of a program that cannot see one."""
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.telemetry import trace
    monkeypatch.setattr(trace, "export", lambda name=None: RING)
    monkeypatch.delattr(telemetry, "collector")
    monkeypatch.setitem(sys.modules, "deepspeed_tpu.telemetry.collector",
                        None)
    assert gc_pause_time.read(
        None, SPECS["gc_pause_ms.gen"]["params"]) is None


# ---------------------------------------------------------------------------
# the operator's tool: scripts/trace_by_scope.py on the hand-written call
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tool():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "trace_by_scope", REPO / "scripts" / "trace_by_scope.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_the_table_lists_operations_by_program_scope_and_instruction(
        tool, program_maps):
    listed, sums = tool.table(TRACE)
    # the decode window's fusion.1 ran twice (8 + 3 ms), the ragged
    # step's once: one name, two rows
    assert listed[0] == ("decode_window_greedy", "qkv_proj", "fusion.1", 2,
                         pytest.approx(11e-3), "qkv_proj/dot_general")
    assert ("ragged_step", "mlp", "fusion.1", 1, pytest.approx(3e-3),
            "mlp/dot_general") in listed
    assert ("decode_window_greedy", "other", "copy.9", 1,
            pytest.approx(1e-3), "") in listed
    assert [r[4] for r in listed] == sorted((r[4] for r in listed),
                                            reverse=True)
    assert sums == {("ragged_step", "mlp"): pytest.approx(3e-3),
                    ("ragged_step", "head"): pytest.approx(4e-3),
                    ("decode", "qkv_proj"): pytest.approx(11e-3),
                    ("decode", "pick"): pytest.approx(3e-3),
                    ("decode", "other"): pytest.approx(1e-3)}
    text = tool.render(listed, sums, program="ragged_step")
    assert "decode_window_greedy" not in text.split("program family")[0]
    assert "fusion.2" in text and "head" in text
    assert text.splitlines()[-1].endswith("different scopes, s: none")


def test_the_table_says_what_two_signatures_disagree_on(tool, monkeypatch):
    """The decode window compiled for a wider table: its ``fusion.3`` is
    the pick in one executable and an MLP matmul in the other, and its
    ``fusion.1`` ``qkv_proj`` in one and ``out_proj`` in the other (one
    PHASE, two scopes: a scope is the finer word): 3 + 11 ms that count
    as ``other`` and are named as such."""
    offer(monkeypatch, {"ragged_step": [MAPS["ragged_step"]],
                        "decode_window_greedy": [
                            MAPS["decode_window_greedy"], WIDER]})
    _, sums = tool.table(TRACE)
    assert sums["decode_window_greedy", "(signatures disagree)"] \
        == pytest.approx(14e-3)
    assert sums["decode", "other"] == pytest.approx(14e-3)
    assert sums["decode", "kv_write"] == pytest.approx(1e-3)   # copy.9
    assert ("ragged_step", "(signatures disagree)") not in sums
    text = tool.render([], sums)
    assert "decode_window_greedy 0.014000" in text.splitlines()[-1]


def test_the_table_is_none_where_no_program_offers_a_map(tool, monkeypatch):
    offer(monkeypatch, {})
    assert tool.table(TRACE) is None


def test_the_gaps_are_listed_by_the_leaf_the_host_was_in(tool, ring):
    """The hand-written call's two gaps (10.0 .. 14.5 and 22.5 .. 28.5
    ms), the longer first, each by its leaves; the sliver between a
    window's upload and its call (14.25 .. 14.3) is under no leaf and
    the listing says which leaf follows it."""
    gaps = tool.idle_gaps(evidence())
    assert [round(1e3 * g[0], 6) for g in gaps] == [6.0, 4.5]
    second = gaps[0]
    assert second[1]["window_bookkeeping"] == pytest.approx(2e-3)
    assert second[1]["window_upload"] == pytest.approx(0.25e-3)
    assert second[1]["window_call"] == pytest.approx(0.2e-3)
    assert second[1]["window_fetch"] == pytest.approx(0.5e-3)
    assert "window_dispatch" not in second[1]       # it holds two leaves
    assert second[2] == pytest.approx(0.05e-3)
    assert second[3] == [("window_call", pytest.approx(0.05e-3))]
    assert "under no leaf 0.050 (0.050 before window_call)" \
        in tool.render_gaps(gaps)


def test_the_gaps_say_so_where_the_clocks_do_not_pair(tool, monkeypatch):
    from deepspeed_tpu.telemetry import trace
    monkeypatch.setattr(trace, "export", lambda name=None: inside.EARLIER)
    assert tool.idle_gaps(evidence()) is None
    assert "do not pair" in tool.render_gaps(None)
