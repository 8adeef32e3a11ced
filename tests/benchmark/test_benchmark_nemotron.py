"""The configuration ``nemotron-3-nano-30b-a3b`` and its cell
``nemotron-3-nano-30b-a3b.rollout-128x256-384``: what ``BENCHMARK.json``
and the files say of them (entries found BY NAME, "at least these",
never by position: a later PR appends), the manifest's rules on a copy,
the 10.57 GB of the cut and the 12.70 GB of the cell's arguments
reckoned from ``fields`` and the cell's ``engine``, ``arith_nemotron.py``'s
floors against numbers worked by hand, the two new readers on a
hand-made trace and where there is nothing to read, the cell's
rehearsal with its three limits, the control that fails the state's
number, and that the cell is in none of the three older roofline lists
(their arithmetic is another block's: three matrices an expert on every
layer; one group of B and C).

Toy readings (the sandbox's CPU, no chip result; float32 engine, seeds
2**31 + 5200000001 and ..002): ``logit_err`` 6e-7, ``token_gap`` 0,
``state_err`` 3e-7 in layer 0 (3e-7 to 7e-7 in the next four state-space
layers, where a float32 engine swaps no expert); the control
(``state_dtype`` bfloat16) reads ``state_err`` over fifty times the toy
limit.
"""

import json
import shutil
import types
from pathlib import Path

import pytest

from benchmark import arith, arith_nemotron, manifest, tracing
from benchmark.readers import (experts_relu2_roofline, serve_scope_time,
                               ssm_grouped_roofline)
from benchmark.run import reported_by
from benchmark.tracing import Event
from deepspeed_tpu.telemetry import (MetricsRegistry, get_registry,
                                     set_registry)

from test_benchmark_run import assert_rehearsed, run_py

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmark"
CONFIG = "nemotron-3-nano-30b-a3b"
TRAFFIC = "rollout-128x256-384"
CELL = f"{CONFIG}.{TRAFFIC}"
FILE = json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())
FIELDS = FILE["fields"]
OPT = json.loads((BENCH / "configs/opt-1.3b.json").read_text())["fields"]
PEAKS = arith.peaks("TPU v5 lite")
NEW = {"experts_relu2_roofline.gen": "expert layer",
       "ssm_grouped_state_roofline.gen": "state-space layers",
       "ssm_grouped_scan_roofline.gen": "state-space layers"}
JOINED = ["compiles.gen", "idle.gen", "peak_hbm.gen", "host_ms.gen",
          "gap_host_ms.gen", "gap_launch_ms.gen", "gap_unattributed.gen",
          "prefill_ms.gen", "decode_ms.gen", "attn_proj_ms.gen",
          "kv_write_ms.gen", "mlp_ms.gen", "head_ms.gen",
          "scope_coverage.gen", "router_ms.gen", "experts_share.gen",
          "experts_touched.gen", "ragged_share.gen", "ssm_ms.gen",
          "state_gb.gen"]
ANOTHER_BLOCKS = ["experts_roofline.gen", "ssm_state_roofline.gen",
                  "ssm_scan_roofline.gen"]
SPECS = {m: json.loads((BENCH / "layer_metrics" / f"{m}.json").read_text())
         for m in NEW}
DEV = "/device:TPU:0"


def _named(entries, name):
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, name
    return found[0]


# ---------------------------------------------------------------------------
# the tree
# ---------------------------------------------------------------------------
def test_the_tree_passes_the_manifest():
    manifest.check(REPO)


def test_the_configuration_the_cell_and_the_metrics_by_name():
    bm = manifest.read(REPO)
    c = _named(bm["configs"], CONFIG)
    assert c["reduced"] == FILE["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert c["source"] == FILE["source"] \
        == "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-" \
           "BF16/blob/main/config.json"
    w = _named(bm["workloads"], CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, TRAFFIC, 1)
    assert "6 rows a step" in w["why"] and "12" in w["why"]
    assert reported_by(bm, CELL, "end_to_end") == ["setup_s", "gen_tok_s"]
    mine = reported_by(bm, CELL, "per_layer")
    assert set(mine) >= set(JOINED) | set(NEW)      # at least these
    for name, layer in NEW.items():
        m = _named(bm["per_layer"], name)
        assert m["workloads"] == [CELL] and m["moves"] == "gen_tok_s"
        assert (m["layer"], m["unit"], m["better"], m["source"]) == (
            layer, "%", "higher", "device_trace")
        for key in ("unit", "better", "source", "layer", "moves"):
            assert SPECS[name][key] == m[key]


@pytest.mark.parametrize("name", ANOTHER_BLOCKS + [
    "latent_share.gen", "latent_roofline.gen", "linear_ms.gen",
    "state_roofline.gen", "chunk_roofline.gen", "window_roofline.gen",
    "kv_gb.gen", "ragged_roofline.gen"])
def test_the_cell_is_in_no_list_whose_arithmetic_is_another_blocks(name):
    """``experts_roofline.gen`` counts three matrices an expert on every
    layer behind the leading dense ones (here: two, on 7 layers of 16:
    it would read 3.4 times the true share); ``ssm_*_roofline.gen``
    count one group's B and C; the rest read mechanisms the block has
    not."""
    bm = manifest.read(REPO)
    assert CELL not in _named(bm["per_layer"], name)["workloads"]


@pytest.mark.parametrize("name", JOINED + ["gen_tok_s"])
def test_nothing_that_was_there_lost_a_cell(name):
    """The cell's name is appended and every accepted cell stays."""
    bm = manifest.read(REPO)
    m = _named(bm["per_layer"] + bm["end_to_end"], name)
    assert CELL in m["workloads"]
    assert "granite-4.0-h-small.rollout-64x1024-256" in m["workloads"] \
        or name == "state_gb.gen" and "ling-3.0-flash.rollout-128x256" \
        in m["workloads"]
    assert m["workloads"].index(CELL) > m["workloads"].index(
        "granite-4.0-h-small.rollout-64x1024-256")


def test_the_manifests_rules_on_a_copy(tmp_path):
    """The rules hold on a copy of the benchmark's files, and refuse the
    copy once a width stands in ``reduced``, once the held experts stop
    adding up to the router's, and once the depth falls under the
    pattern's longest segment."""
    for part in ("BENCHMARK.json", "PERF.md"):
        shutil.copy(REPO / part, tmp_path / part)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    manifest.check(tmp_path)
    path = tmp_path / "benchmark/configs" / f"{CONFIG}.json"
    entry = _named(manifest.read(tmp_path)["configs"], CONFIG)

    def refused(change, word):
        f = json.loads(json.dumps(FILE))
        change(f)
        path.write_text(json.dumps(f))
        with pytest.raises(manifest.Refused, match=word):
            manifest.config(tmp_path, {**entry, "reduced": f["reduced"]})

    def a_width(f):
        f["reduced"].append("moe_intermediate_size")
        f["cuts"]["moe_intermediate_size"] = {}
    refused(a_width, "is a width")

    def experts(f):
        f["cuts"]["n_routed_experts"]["shared_over_chips"] = 4
    refused(experts, "are not the source's 128")

    def depth(f):
        f["fields"]["num_layers"] = 8
        f["fields"]["layer_types"] = f["fields"]["layer_types"][:8]
        f["cuts"]["num_hidden_layers"]["here"] = 8
    refused(depth, "a whole period of 9")
    path.write_text(json.dumps(FILE))
    manifest.config(tmp_path, entry)


def test_published_widths_and_the_cut():
    pub = FILE["published"]
    for key, want in dict(
            hidden_size=2688, num_heads=32, num_kv_heads=2,
            head_dim_override=128, intermediate_size=1856,
            moe_intermediate_size=1856, moe_num_experts=128, moe_top_k=6,
            moe_shared_experts=2, moe_routed_scale=2.5,
            moe_scoring="sigmoid", moe_selection_bias=True,
            moe_norm_topk=True, moe_expert_form="relu2", mamba_n_heads=64,
            mamba_d_head=64, mamba_d_state=128, mamba_d_conv=4,
            mamba_n_groups=8, mamba_expand=2, mamba_conv_bias=True,
            mamba_chunk_size=128, norm_eps=1e-5, positional="none",
            tie_embeddings=False).items():
        assert FIELDS[key] == want, key
    # the router keeps its published width; what is cut is what is HELD
    assert FIELDS["moe_num_experts"] == pub["n_routed_experts"] == 128
    assert FILE["published_as"]["moe_experts_held"] == "n_routed_experts"
    # the shared expert is served uncut, spelled in experts' widths
    assert FIELDS["moe_shared_experts"] * FIELDS["moe_intermediate_size"] \
        == pub["moe_shared_expert_intermediate_size"] == 3712
    cuts = FILE["cuts"]
    assert (cuts["num_hidden_layers"]["here"],
            cuts["n_routed_experts"]["here"],
            cuts["vocab_size"]["here"]) == (
        FIELDS["num_layers"], FIELDS["moe_experts_held"],
        FIELDS["vocab_size"]) == (16, 64, 65536)
    assert cuts["n_routed_experts"]["shared_over_chips"] == 2
    assert (cuts["num_hidden_layers"]["period"],
            cuts["num_hidden_layers"]["leading_dense"]) == (9, 0)
    assert 2 * 65536 == pub["vocab_size"]
    # the pattern stands whole; its first sixteen characters are served,
    # and 9 is its longest segment between attention layers
    pattern = pub["hybrid_override_pattern"]
    assert FILE["hybrid_override_pattern"] == pattern and len(pattern) == 52
    assert (pattern.count("M"), pattern.count("E"), pattern.count("*")) \
        == (23, 23, 6)
    assert pattern[:16] == "MEMEM*EMEMEM*EME"
    word = {"M": "mamba", "E": "moe", "*": "attention"}
    assert FIELDS["layer_types"] == [word[c] for c in pattern[:16]]
    segments = [len(s) + 1 for s in pattern.split("*")[:-1]] \
        + [len(pattern.split("*")[-1])]
    assert sorted(segments) == [6, 7, 7, 7, 7, 9, 9] and sum(segments) == 52
    # every number of the source's config stands at the top level too
    for key, value in pub.items():
        if key not in FILE["reduced"]:
            assert FILE[key] == value, key
    for key in ("source_of_equations", "layer_types", "one_sublayer",
                "position", "head_dim", "expand", "groups", "dt",
                "activations", "router", "mamba_chunk_size", "stream",
                "state", "seeded", "e_up_layout", "sizes"):
        assert FILE["assumed"][key]


def _parameters(f):
    """(a mamba layer's, an attention layer's, an expert layer's, the
    untied table and head's) parameters, from ``fields``, each layer
    with its one norm."""
    h, nh, nkv = f["hidden_size"], f["num_heads"], f["num_kv_heads"]
    hd = f["head_dim_override"]
    di = f["mamba_n_heads"] * f["mamba_d_head"]
    dc = di + 2 * f["mamba_n_groups"] * f["mamba_d_state"]
    mamba = h * (di + dc + f["mamba_n_heads"]) + f["mamba_d_conv"] * dc \
        + dc + 3 * f["mamba_n_heads"] + di + di * h + h
    attention = h * (nh + 2 * nkv) * hd + nh * hd * h + h
    expert = 2 * h * f["moe_intermediate_size"]
    experts = f["moe_experts_held"] * expert \
        + f["moe_shared_experts"] * expert \
        + h * f["moe_num_experts"] + f["moe_num_experts"] + h
    return mamba, attention, experts, 2 * f["vocab_size"] * h


def _weights(f):
    per = dict(zip(("mamba", "attention", "moe"), _parameters(f)))
    return sum(per[t] for t in f["layer_types"]) + _parameters(f)[3] \
        + f["hidden_size"]


def test_the_bytes_of_the_cut_from_fields():
    """ISSUE 52's arithmetic, 2 B a parameter, from ``fields`` alone."""
    f = FIELDS
    mamba, attention, experts, tables = _parameters(f)
    assert mamba == pytest.approx(38.74e6, rel=1e-3)
    assert attention == pytest.approx(23.40e6, rel=1e-3)
    assert 2 * f["hidden_size"] * f["moe_intermediate_size"] \
        == arith_nemotron.expert_bytes(f) // 2 == 9_977_856
    assert experts == pytest.approx(658.9e6, rel=1e-4)
    assert tables == pytest.approx(352.3e6, rel=1e-3)
    assert _weights(f) == pytest.approx(5282.5e6, rel=1e-4)
    assert 2 * _weights(f) == pytest.approx(10.57e9, rel=1e-3)
    # the whole model by the same count: the source's 31.6B
    whole = {**f, "moe_experts_held": 128, "vocab_size": 131072,
             "layer_types": [{"M": "mamba", "E": "moe", "*": "attention"}[c]
                             for c in FILE["hybrid_override_pattern"]]}
    assert _weights(whole) == pytest.approx(31.58e9, rel=1e-3)


def test_the_cells_arguments_from_fields():
    """Weights, the state's slots and the two attention layers' pool:
    12.70 GB, 79 % of the chip."""
    f = FIELDS
    cell = json.loads((BENCH / "workloads" / f"{CELL}.json").read_text())
    sm = cell["engine"]["state_manager"]
    row = arith_nemotron.state_values(f) * 4
    assert row == 2_170_880
    slots = sm["max_tracked_sequences"] + 1
    state = 7 * slots * row
    assert state == pytest.approx(1.960e9, rel=1e-3)
    assert 7 * row == pytest.approx(15.2e6, rel=2e-3)   # a row's, all layers
    position = f["num_kv_heads"] * f["head_dim_override"] * 2     # bytes
    assert 2 * position == 1024                         # k and v, a layer
    pool = 2 * 2 * sm["num_blocks"] * sm["block_size"] * position
    assert pool == pytest.approx(0.172e9, rel=1e-3)
    assert sm["num_blocks"] == 128 * 41 + 1 and sm["max_seq_len"] == 640
    total = 2 * _weights(f) + state + pool
    assert total == pytest.approx(12.70e9, rel=1e-3)
    assert total / 16e9 == pytest.approx(0.79, abs=0.01)


# ---------------------------------------------------------------------------
# arith_nemotron.py's floors, by hand
# ---------------------------------------------------------------------------
def test_expert_arithmetic_by_hand():
    a = arith_nemotron
    assert a.expert_layers(FIELDS) == 7 and a.ssm_layers(FIELDS) == 7
    assert a.expert_layers(OPT) == 0 == a.ssm_layers(OPT)
    # two matrices of 2,688 x 1,856 in bf16
    assert a.expert_bytes(FIELDS) == 2 * 2688 * 1856 * 2 == 19_955_712
    assert a.expert_row_flops(FIELDS) == 2 * 2 * 2688 * 1856 == 19_955_712
    # a decode pass: 64 touched, 384 rows: the weights' bytes bound it
    bytes_s = 64 * 19_955_712 / 819e9
    assert a.expert_pass_least_seconds(FIELDS, 64, 384, PEAKS) \
        == pytest.approx(bytes_s) == pytest.approx(1.5594e-3, rel=1e-3)
    # a prompt pass: 64 touched, 49,152 rows: the operations bound it
    flops_s = 49152 * 19_955_712 / 197e12
    assert a.expert_pass_least_seconds(FIELDS, 64, 49152, PEAKS) \
        == pytest.approx(flops_s) == pytest.approx(4.979e-3, rel=1e-3)
    # one call: 2 chunk steps and 383 decode steps over 7 expert layers;
    # a decode step's seven layers 10.9 ms (ISSUE 52's floor)
    assert 7 * bytes_s == pytest.approx(10.9e-3, rel=2e-3)
    kinds = [(2 * 7, 64, 49152), (383 * 7, 64, 384)]
    assert a.experts_least_seconds(FIELDS, kinds, PEAKS) \
        == pytest.approx(14 * flops_s + 2681 * bytes_s)


def test_state_arithmetic_by_hand():
    a = arith_nemotron
    # 64 heads x 64 x 128, and three inputs of x and of 8 groups' B and C
    assert a.inner(FIELDS) == 4096 and a.bc_values(FIELDS) == 2048
    assert a.state_values(FIELDS) == 64 * 64 * 128 + 3 * 6144 == 542_720
    assert a.state_row_bytes(FIELDS) == 2 * 542_720 * 4 == 4_341_760
    assert a.state_row_bytes(FIELDS, 2) == 2_170_880        # the control
    assert a.state_row_flops(FIELDS) == 5 * 4096 * 128
    assert a.state_row_flops(FIELDS) / 197e12 \
        < a.state_row_bytes(FIELDS) / 819e9
    # a decode step: 128 rows x 7 layers: 4.75 ms (ISSUE 52's floor)
    step = 7 * 128 * 4_341_760 / 819e9
    assert step == pytest.approx(4.75e-3, rel=1e-3)
    assert a.state_least_seconds(FIELDS, 128, 383, PEAKS) \
        == pytest.approx(383 * step)
    # accepted arith_ssm.py counts ONE group's B and C: 1 % low here
    from benchmark import arith_ssm
    assert arith_ssm.state_values(FIELDS) == 524_288 + 3 * (4096 + 256)
    assert arith_ssm.row_bytes(FIELDS) / a.state_row_bytes(FIELDS) \
        == pytest.approx(0.990, abs=1e-3)


def test_prompt_arithmetic_by_hand():
    """The recurrence over a fresh row's 256 prompt tokens fed in two
    launches: a token's x and y (4,096 values each), B and C of 8 groups
    (2,048) and dt (64) in bf16, and the row's state written twice and
    read once."""
    a = arith_nemotron
    per_token = (2 * 4096 + 2048 + 64) * 2
    assert a.scan_row_bytes(FIELDS, 256, 2) \
        == 256 * per_token + 3 * 542_720 * 4 == 11_788_288
    assert a.scan_row_bytes(FIELDS, 256) == 256 * per_token + 542_720 * 4
    assert 256 * a.state_row_flops(FIELDS) / 197e12 < 11_788_288 / 819e9
    want = 7 * 128 * 11_788_288 / 819e9
    assert a.scan_least_seconds(FIELDS, 128, 256, PEAKS, 2) \
        == pytest.approx(want) == pytest.approx(12.90e-3, rel=1e-3)
    # accepted arith_ssm.py counts one group's B and C: 17 % low a token
    # (8,512 of 10,304 values), 8 % low over the cell's two launches
    from benchmark import arith_ssm
    low = arith_ssm.prompt_row_bytes(FIELDS, 256, 2)
    assert low / 11_788_288 == pytest.approx(0.917, abs=0.005)
    assert (2 * 4096 + 256 + 64) / (2 * 4096 + 2048 + 64) \
        == pytest.approx(0.826, abs=1e-3)


def _evidence(events, fields=FIELDS, engine=None, rows=2, new_tokens=3,
              prompt_len=5):
    ctx = types.SimpleNamespace(
        fields=fields, traffic={"rows": rows, "new_tokens": new_tokens,
                                "prompt_len": prompt_len},
        cell={"engine": {"state_manager": {"max_ragged_batch_size": 4},
                         **(engine or {})}},
        devices=[types.SimpleNamespace(device_kind="TPU v5 lite")])
    return types.SimpleNamespace(events=tracing.Events(events), ctx=ctx,
                                 slice_steps=1)


WINDOW = "jit(decode_window_greedy)/while/body/layers/while/body/"
MAPS = {"decode_window_greedy": [{
    "fusion.1": WINDOW + "ssm_mixer/ssm_state/mul",
    "fusion.2": WINDOW + "ssm_mixer/ssm_state/broadcast",
    "fusion.3": WINDOW + "ssm_mixer/ssm_proj/dot_general",
    "fusion.5": WINDOW + "mlp/moe_experts/gather"}],
    "ragged_step": [{
        "fusion.1": "jit(ragged_step)/layers/while/body/ssm_mixer/"
                    "ssm_scan/pallas_call"}]}
OPS, MODULES = tracing.OPS_LINE, tracing.MODULES_LINE
TRACE = [Event(DEV, MODULES, "jit_ragged_step(1)", 0.0, 5e-3),
         Event(DEV, OPS, "fusion.1", 1e-3, 3e-3),
         Event(DEV, OPS, "gmm.7:tpu_custom_call", 4e-3, 1e-3),
         Event(DEV, MODULES, "jit_decode_window_greedy(2)", 1e-2, 2e-2),
         Event(DEV, OPS, "fusion.1", 1.0e-2, 4e-3),
         Event(DEV, OPS, "fusion.2", 1.4e-2, 2e-3),
         Event(DEV, OPS, "fusion.3", 1.6e-2, 1e-3),
         Event(DEV, OPS, "gmm.2:tpu_custom_call", 1.7e-2, 5e-4),
         Event(DEV, OPS, "fusion.5", 1.8e-2, 7e-3)]


@pytest.fixture
def offered(monkeypatch):
    from deepspeed_tpu.telemetry import memory
    monkeypatch.setattr(memory, "scopes_offered",
                        lambda program: MAPS.get(program, []))


@pytest.fixture
def fresh_registry():
    old = get_registry()
    set_registry(MetricsRegistry())
    yield get_registry()
    set_registry(old)


def test_the_state_readers_on_a_hand_made_trace(offered):
    ev = _evidence(TRACE)
    # the one-token update: 4 + 2 ms, against 7 layers x 2 steps x 2 rows
    least = arith_nemotron.state_least_seconds(FIELDS, 2, 2, PEAKS)
    assert least == pytest.approx(7 * 2 * 2 * 4_341_760 / 819e9)
    step = SPECS["ssm_grouped_state_roofline.gen"]["params"]
    assert step == {"phases": ["ssm_state"]}
    assert ssm_grouped_roofline.read(ev, step) \
        == pytest.approx(100 * least / 6e-3)
    # the control keeps half the bytes: its floor is half
    half = _evidence(TRACE, engine={"state_dtype": "bfloat16"})
    assert ssm_grouped_roofline.read(half, step) \
        == pytest.approx(50 * least / 6e-3)
    # the chunked form: 3 ms in the ragged step, against 7 layers x 2
    # fresh rows of 5 prompt tokens fed in ceil(10 / 4) = 3 launches
    scan = SPECS["ssm_grouped_scan_roofline.gen"]["params"]
    assert scan == {"phases": ["ssm_scan"], "form": "prompt"}
    least = arith_nemotron.scan_least_seconds(FIELDS, 2, 5, PEAKS, 3)
    assert least == pytest.approx(
        7 * 2 * (5 * 10_304 * 2 + 5 * 542_720 * 4) / 819e9)
    assert ssm_grouped_roofline.read(ev, scan) \
        == pytest.approx(100 * least / 3e-3)


def test_the_experts_reader_on_a_hand_made_trace(fresh_registry):
    """The grouped matmuls' 1.5 ms against the passes of one call: 3
    ragged steps (10 prompt tokens under a budget of 4) and 2 decode
    steps over 7 expert layers, touched and rows the counters' means."""
    spec = SPECS["experts_relu2_roofline.gen"]["params"]
    assert spec == {"pattern": "^gmm[_.0-9]*:tpu_custom_call$"}
    ev = _evidence(TRACE)
    assert experts_relu2_roofline.read(ev, spec) is None    # no counters
    reg = fresh_registry
    for name in ("moe_launches_total", "moe_experts_touched_total",
                 "moe_routed_rows_total"):
        reg.counter(name, "", labelnames=("program",))
    for program, passes, touched, rows in (("ragged_step", 14, 14 * 5, 14 * 9),
                                           ("decode_window", 28, 28 * 3,
                                            28 * 4)):
        reg.get("moe_launches_total").labels(program=program).inc(passes)
        reg.get("moe_experts_touched_total").labels(
            program=program).inc(touched)
        reg.get("moe_routed_rows_total").labels(program=program).inc(rows)
    least = arith_nemotron.experts_least_seconds(
        FIELDS, [(3 * 7, 5, 9), (2 * 7, 3, 4)], PEAKS)
    assert least == pytest.approx(
        (21 * 5 + 14 * 3) * 19_955_712 / 819e9)
    assert experts_relu2_roofline.read(ev, spec) \
        == pytest.approx(100 * least / 1.5e-3)


def test_the_new_readers_read_nothing_where_there_is_nothing(
        monkeypatch, offered, fresh_registry):
    """No slice; a configuration without such layers; a program whose
    maps have no such scope, or that offers none (a parent commit's); a
    trace without the kernels: None, and no error, so that the line
    leaves the metric out."""
    roof = SPECS["ssm_grouped_state_roofline.gen"]["params"]
    gmm = SPECS["experts_relu2_roofline.gen"]["params"]
    no_slice = _evidence([])
    no_slice.slice_steps = 0
    assert ssm_grouped_roofline.read(no_slice, roof) is None
    assert experts_relu2_roofline.read(no_slice, gmm) is None
    assert ssm_grouped_roofline.read(_evidence(TRACE, fields=OPT), roof) \
        is None
    assert experts_relu2_roofline.read(_evidence(TRACE, fields=OPT), gmm) \
        is None
    only_experts = _evidence([TRACE[3], TRACE[8]])
    assert ssm_grouped_roofline.read(only_experts, roof) is None
    assert experts_relu2_roofline.read(only_experts, gmm) is None
    from deepspeed_tpu.telemetry import memory
    monkeypatch.setattr(memory, "scopes_offered", lambda program: [])
    assert ssm_grouped_roofline.read(_evidence(TRACE), roof) is None
    assert serve_scope_time.read(_evidence(TRACE), roof) is None


# ---------------------------------------------------------------------------
# the cell's files
# ---------------------------------------------------------------------------
def test_the_cell_and_its_traffic_say_what_the_issue_asked():
    cell = json.loads((BENCH / "workloads" / f"{CELL}.json").read_text())
    sm = cell["engine"]["state_manager"]
    traffic = json.loads((BENCH / "traffic" / f"{TRAFFIC}.json")
                         .read_text())
    rows = traffic["rows"]
    assert rows in (128, 96) and "taken" in traffic["rule"]["branch"]
    assert sm["max_tracked_sequences"] == 128
    assert sm["max_ragged_batch_size"] in (16384, 8192)
    assert (sm["max_seq_len"], sm["block_size"]) == (640, 16)
    assert sm["num_blocks"] == rows * 41 + 1
    assert (traffic["runner"], traffic["prompt_len"], traffic["temperature"],
            traffic["distinct_batches"], traffic["check_rows"]) == (
        "generate_ssm", 256, 0.0, 4, 4)
    assert traffic["new_tokens"] in (384, 256)
    assert cell["engine"]["dtype"] == "bfloat16" \
        and cell["engine"]["use_paged_kernel"] is True
    assert set(cell["engine"]) == {"dtype", "use_paged_kernel",
                                   "state_manager"}    # no new option
    assert cell["control"] == {"engine": {"state_dtype": "bfloat16"}}
    for name in ("logit_err", "token_gap", "state_err"):
        assert 0 < cell["limits"][name]["limit"] < 1
        assert "control" in cell["limits"][name]["from"].lower()
        assert "PR 52" in cell["limits"][name]["from"]
        assert 0 < cell["rehearse"]["limits"][name]["limit"] < 1
    assert "12.70" in cell["sizing"] or "12.7 " in cell["sizing"]
    toy = FILE["toy_fields"]
    assert toy["mamba_n_groups"] >= 2 \
        and toy["mamba_n_heads"] % toy["mamba_n_groups"] == 0
    assert toy["moe_intermediate_size"] % 128 and toy["moe_num_experts"] \
        == 2 * toy["moe_experts_held"] == 16


def test_the_cell_rehearses_with_its_three_limits():
    p = run_py(["--workload", CELL, "--rehearse", "--trace", "1",
                "--seed", str(2 ** 31 + 5200000001)])
    assert_rehearsed(p)
    assert "compared: logit_err" in p.stderr
    assert "compared: token_gap" in p.stderr
    assert "compared: state_err" in p.stderr
    assert "calls finished 0" not in p.stderr
    ran = p.stderr.split("readers ran")[1]
    assert "experts_touched.gen" in ran and "state_gb.gen" in ran


def test_the_control_fails_the_state_number():
    """``state_dtype`` bfloat16 on the toy: the cell as it stands passes
    its three numbers, and under the control ``state_err`` reads over
    fifty times its limit: the comparison can come out not correct on
    what this cell adds."""
    import jax
    from benchmark import control
    from benchmark import run as harness
    seed = 2 ** 31 + 5200000002
    read = {}
    for on in (False, True):
        result = control.run_once(CELL, seed, 2.0, on, jax.devices(),
                                  harness.CompileClock(), rehearse=True)
        read[on] = result.correct, result.correct_detail["compared"]
    assert read[False][0] is True and read[True][0] is False
    for name in ("logit_err", "token_gap"):
        assert read[False][1][name]["value"] \
            <= read[False][1][name]["limit"]
    sound, control_ = (read[on][1]["state_err"] for on in (False, True))
    assert sound["value"] <= sound["limit"] / 20
    assert control_["value"] >= 50 * control_["limit"]
