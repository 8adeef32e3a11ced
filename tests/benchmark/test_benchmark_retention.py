"""The configuration ``brumby-14b-base`` and its cell
``brumby-14b-base.rollout-16x2048-256``: what ``BENCHMARK.json`` and the
files say of them (entries found BY NAME, "at least these", never by
position: a later PR appends), the manifest's rules on a copy, the
8.40 GB of the cut and the ~13.1 GB of the cell's arguments reckoned
from ``fields`` and the cell's ``engine``, ``arith_retention.py``'s
floors against numbers worked by hand, the new reader on a hand-made
trace and where there is nothing to read, the cell's rehearsal with its
three limits, the control that fails the state's number, and that the
cell is in no list whose arithmetic is another block's.

Toy readings (the sandbox's CPU, no chip result; float32 engine, seeds
2**31 + 5600000001 and ..002): ``logit_err`` 4e-7, ``token_gap`` 0,
``state_err`` 4e-7 over both layers; the control (``state_dtype``
bfloat16) reads ``state_err`` over fifty times the toy limit.
"""

import json
import shutil
import types
from pathlib import Path

import pytest

from benchmark import arith, arith_retention, manifest, tracing
from benchmark.readers import (retention_roofline, serve_program_scope_time,
                               serve_scope_time)
from benchmark.run import reported_by
from benchmark.tracing import Event

from test_benchmark_run import assert_rehearsed, run_py

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmark"
CONFIG = "brumby-14b-base"
TRAFFIC = "rollout-16x2048-256"
CELL = f"{CONFIG}.{TRAFFIC}"
FILE = json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())
FIELDS = FILE["fields"]
OPT = json.loads((BENCH / "configs/opt-1.3b.json").read_text())["fields"]
PEAKS = arith.peaks("TPU v5 lite")
LAYER = "power-retention layers"
NEW = {"retention_ms.gen": ("ms", "lower"),
       "prefill_retention_ms.gen": ("ms", "lower"),
       "retention_state_roofline.gen": ("%", "higher"),
       "retention_chunk_roofline.gen": ("%", "higher")}
JOINED = ["compiles.gen", "idle.gen", "peak_hbm.gen", "host_ms.gen",
          "gap_host_ms.gen", "gap_launch_ms.gen", "gap_unattributed.gen",
          "gap_upload_ms.gen", "gap_call_ms.gen", "gap_fetch_ms.gen",
          "prefill_ms.gen", "decode_ms.gen", "attn_proj_ms.gen",
          "mlp_ms.gen", "head_ms.gen", "scope_coverage.gen", "state_gb.gen",
          "prefill_attn_proj_ms.gen", "prefill_mlp_ms.gen",
          "prefill_head_ms.gen", "prefill_other_ms.gen", "gc_pause_ms.gen"]
ANOTHER_BLOCKS = [
    "ragged_share.gen", "ragged_roofline.gen", "latent_share.gen",
    "latent_roofline.gen", "latent_pattern_roofline.gen",
    "experts_share.gen", "experts_roofline.gen", "experts_touched.gen",
    "experts_relu2_roofline.gen", "router_ms.gen", "linear_ms.gen",
    "state_roofline.gen", "chunk_roofline.gen", "window_roofline.gen",
    "kv_gb.gen", "kv_write_ms.gen", "ssm_ms.gen", "ssm_state_roofline.gen",
    "ssm_scan_roofline.gen", "ssm_grouped_state_roofline.gen",
    "ssm_grouped_scan_roofline.gen", "prefill_attn_kernel_ms.gen",
    "prefill_experts_ms.gen", "prefill_router_ms.gen",
    "prefill_linear_ms.gen", "prefill_ssm_scan_ms.gen"]
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
    assert c["reduced"] == FILE["reduced"] == ["num_hidden_layers"]
    assert c["source"] == FILE["source"] == "https://huggingface.co/" \
        "manifestai/Brumby-14B-Base/blob/main/config.json"
    w = _named(bm["workloads"], CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, TRAFFIC, 1)
    assert "no position cached" in w["why"] and "273 MB a row" in w["why"]
    assert reported_by(bm, CELL, "end_to_end") == ["setup_s", "gen_tok_s"]
    mine = reported_by(bm, CELL, "per_layer")
    assert set(mine) >= set(JOINED) | set(NEW)      # at least these
    for name, (unit, better) in NEW.items():
        m = _named(bm["per_layer"], name)
        assert m["workloads"] == [CELL] and m["moves"] == "gen_tok_s"
        assert (m["layer"], m["unit"], m["better"], m["source"]) == (
            LAYER, unit, better, "device_trace")
        for key in ("unit", "better", "source", "layer", "moves"):
            assert SPECS[name][key] == m[key]
    assert LAYER in (REPO / "PERF.md").read_text()


@pytest.mark.parametrize("name", ANOTHER_BLOCKS)
def test_the_cell_is_in_no_list_whose_arithmetic_is_another_blocks(name):
    """The block has no pool, no page walk, no latent, no expert, no
    convolution and no state-space scan: a metric that reads one of them
    would read nothing, or another block's floor against this one's
    time."""
    bm = manifest.read(REPO)
    assert CELL not in _named(bm["per_layer"], name)["workloads"]


@pytest.mark.parametrize("name", JOINED + ["gen_tok_s"])
def test_nothing_that_was_there_lost_a_cell(name):
    """The cell's name is appended and every accepted cell stays."""
    bm = manifest.read(REPO)
    m = _named(bm["per_layer"] + bm["end_to_end"], name)
    older = [w for w in m["workloads"] if w != CELL]
    assert CELL in m["workloads"] and older
    assert m["workloads"].index(CELL) > max(
        m["workloads"].index(w) for w in older
        if w.split(".rollout")[0] in (
            "opt-1.3b", "joyai-llm-flash", "ling-3.0-flash", "trinity-mini",
            "granite-4.0-h-small", "nemotron-3-nano-30b-a3b"))
    assert "nemotron-3-nano-30b-a3b.rollout-128x256-384" in m["workloads"] \
        or name == "state_gb.gen"


def test_the_manifests_rules_on_a_copy(tmp_path):
    """The rules hold on a copy of the benchmark's files, and refuse the
    copy once a width stands in ``reduced``, and once the depth falls
    under the floor of four layers."""
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
        f["reduced"].append("head_dim")
        f["cuts"]["head_dim"] = {}
    refused(a_width, "is a width")

    def heads(f):
        f["fields"]["num_kv_heads"] = 4
    refused(heads, "num_kv_heads is 4, the source's")

    def depth(f):
        f["fields"]["num_layers"] = 3
        f["fields"]["layer_types"] = f["fields"]["layer_types"][:3]
        f["cuts"]["num_hidden_layers"]["here"] = 3
    refused(depth, "four at least")
    path.write_text(json.dumps(FILE))
    manifest.config(tmp_path, entry)


def test_published_widths_and_the_cut():
    pub = FILE["published"]
    for key, want in dict(
            hidden_size=5120, num_heads=40, num_kv_heads=8,
            head_dim_override=128, intermediate_size=17408,
            vocab_size=151936, max_seq_len=32768, norm_eps=1e-6,
            rope_theta=1e6, positional="rope", qk_norm=True,
            tie_embeddings=False, attn_bias=False, activation="swiglu",
            retention_eps=1e-6).items():
        assert FIELDS[key] == want, key
    assert FIELDS["layer_types"] == ["power_retention"] * 8
    cut = FILE["cuts"]["num_hidden_layers"]
    assert (cut["kind"], cut["published"], cut["here"], cut["period"],
            cut["leading_dense"]) == ("depth", 40, 8, 1, 0)
    assert FIELDS["num_layers"] == FILE["num_hidden_layers"] == 8
    assert pub["num_hidden_layers"] == 40 and pub["model_type"] == "brumby"
    # every number of the source's config stands at the top level too,
    # the three Qwen3 leftovers among them
    for key, value in pub.items():
        if key not in FILE["reduced"]:
            assert FILE[key] == value, key
    assert (FILE["max_window_layers"], FILE["sliding_window"],
            FILE["use_sliding_window"]) == (40, None, False)
    for key in ("source_of_equations", "degree", "gate", "normaliser",
                "head_norms_and_rotation", "unused_keys", "layer_types",
                "seeded_ranges"):
        assert FILE["assumed"][key]
    assert (FILE["reference"], FILE["weights"]) == ("reference_brumby",
                                                    "weights_brumby")


def _layer_parameters(f):
    h, nh, nkv = f["hidden_size"], f["num_heads"], f["num_kv_heads"]
    hd = f["head_dim_override"]
    mixer = 2 * h * nh * hd + 2 * h * nkv * hd      # wq, wo, wk, wv
    gate = h * nkv + nkv
    norms = 2 * h + 2 * hd
    return mixer + 3 * h * f["intermediate_size"] + gate + norms


def test_the_bytes_of_the_cut_from_fields():
    """ISSUE 56's arithmetic, 2 B a parameter, from ``fields`` alone,
    and the seeded tree's own count."""
    f = FIELDS
    layer = _layer_parameters(f)
    assert layer == pytest.approx(330.4e6, rel=1e-3)
    assert 2 * layer == pytest.approx(660.7e6, rel=1e-3)
    tables = 2 * f["vocab_size"] * f["hidden_size"]
    assert tables == pytest.approx(1.556e9, rel=1e-3)
    total = f["num_layers"] * layer + tables + f["hidden_size"]
    assert 2 * total == pytest.approx(8.40e9, rel=1e-3)
    from benchmark import weights_brumby
    assert weights_brumby.parameters(f) == total == 4_198_652_992
    # the whole model by the same count: the source's 14B
    assert 40 * layer + tables == pytest.approx(14.77e9, rel=1e-3)
    # training it at 16 B a parameter does not fit a chip at the floor of
    # four layers: the served path is the one that does
    assert 16 * 4 * layer > 16e9


def test_the_cells_arguments_from_fields():
    """Weights and the state's slots: a row's state is 272.6 MB as the
    mechanism has it and 274.8 MB as the leaves keep it; 17 slots are
    4.67 GB, and the arguments 13.07 GB, 82 % of the chip."""
    f = FIELDS
    cell = json.loads((BENCH / "workloads" / f"{CELL}.json").read_text())
    sm = cell["engine"]["state_manager"]
    assert arith_retention.state_values(f) == 8 * 8256 * 129 == 8_520_192
    row = f["num_layers"] * arith_retention.state_values(f) * 4
    assert row == pytest.approx(272.6e6, rel=1e-3)
    from deepspeed_tpu.inference.v2.kernels import power_retention as pr
    state, norm = pr.leaf_shapes(f["num_layers"],
                                 sm["max_tracked_sequences"] + 1,
                                 f["num_kv_heads"], f["head_dim_override"])
    assert state == (8, 17, 8, 65, 128, 128) and norm == (8, 17, 8, 72, 128)
    kept = 4 * (_prod(state) + _prod(norm))
    assert kept / 17 == pytest.approx(274.8e6, rel=1e-3)
    assert kept == 4_674_813_952 and round(kept / 1e9, 2) == 4.67
    total = 2 * 4_198_652_992 + kept
    assert total == pytest.approx(13.07e9, rel=1e-3)
    assert total / 16e9 == pytest.approx(0.82, abs=0.01)
    # a decode step's bytes: the weights once, the rows' states twice
    weights = 8 * 2 * _layer_parameters(f) + f["vocab_size"] \
        * f["hidden_size"] * 2
    states = 16 * f["num_layers"] * arith_retention.row_bytes(f)
    assert weights == pytest.approx(6.84e9, rel=1e-3)
    assert states == pytest.approx(8.73e9, rel=1e-3)
    assert states / (states + weights) == pytest.approx(0.56, abs=0.01)


def _prod(shape):
    out = 1
    for s in shape:
        out *= s
    return out


# ---------------------------------------------------------------------------
# arith_retention.py's floors, by hand
# ---------------------------------------------------------------------------
def test_state_arithmetic_by_hand():
    a = arith_retention
    assert a.retention_layers(FIELDS) == 8 and a.retention_layers(OPT) == 0
    assert a.phi_values(FIELDS) == 128 * 129 // 2 == 8256
    assert a.token_values(FIELDS) == 2 * 40 * 128 + 2 * 8 * 128 + 8 \
        == 12_296
    assert a.row_bytes(FIELDS) == 2 * 8_520_192 * 4 + 4 * 12_296 \
        == 68_210_720
    assert a.row_bytes(FIELDS, 2) == 2 * 8_520_192 * 2 + 4 * 12_296
    assert a.row_flops(FIELDS) == 8_520_192 * 13
    # the bytes are the floor: 83.3 us a row and layer against 0.56
    assert a.row_flops(FIELDS) / 197e12 < 68_210_720 / 819e9
    want = 8 * 255 * 16 * 68_210_720 / 819e9
    assert a.least_seconds(FIELDS, 16, 255, PEAKS) == pytest.approx(want) \
        == pytest.approx(2.718, rel=1e-3)


def test_prompt_arithmetic_by_hand():
    """The recurrence over a fresh row's 2,048 prompt tokens fed in four
    launches: 101.4 MFLOP a token and layer between chunks, which in
    float32 (six bf16 passes a product) is the floor, 6.3 ms a row and
    layer, where the bytes are 0.35 ms."""
    a = arith_retention
    assert a.prompt_token_flops(FIELDS) == 48 * 2 * 8256 * 128 \
        == 101_449_728
    assert a.prompt_row_bytes(FIELDS, 2048, 4) \
        == 2048 * 12_296 * 2 + 7 * 8_520_192 * 4 == 288_929_792
    by_bytes = 288_929_792 / 819e9
    for passes, floor in ((1, 2048 * 101_449_728 / 197e12),
                          (6, 6 * 2048 * 101_449_728 / 197e12)):
        assert floor > by_bytes
        assert a.prompt_least_seconds(FIELDS, 16, 2048, PEAKS, 4, 4,
                                      passes) \
            == pytest.approx(8 * 16 * floor)
    assert a.prompt_least_seconds(FIELDS, 16, 2048, PEAKS, 4, 4, 6) \
        == pytest.approx(0.810, rel=1e-3)
    # a launch of the cell: 8,192 tokens of one layer, float32
    assert 6 * 8192 * 101_449_728 / 197e12 == pytest.approx(25.3e-3,
                                                            rel=1e-2)


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
STEP = "jit(ragged_step)/layers/while/body/"
MAPS = {"decode_window_greedy": [{
    "fusion.1": WINDOW + "attention/retention_state/pallas_call",
    "fusion.2": WINDOW + "attention/retention_state/exp",
    "fusion.3": WINDOW + "attention/qkv_proj/dot_general",
    "fusion.5": WINDOW + "mlp/dense_mlp/dot_general"}],
    "ragged_step": [{
        "fusion.1": STEP + "attention/retention_chunk/pallas_call",
        "fusion.2": STEP + "attention/out_proj/dot_general"}]}
OPS, MODULES = tracing.OPS_LINE, tracing.MODULES_LINE
TRACE = [Event(DEV, MODULES, "jit_ragged_step(1)", 0.0, 5e-3),
         Event(DEV, OPS, "fusion.1", 1e-3, 3e-3),
         Event(DEV, OPS, "fusion.2", 4e-3, 1e-3),
         Event(DEV, MODULES, "jit_decode_window_greedy(2)", 1e-2, 2e-2),
         Event(DEV, OPS, "fusion.1", 1.0e-2, 4e-3),
         Event(DEV, OPS, "fusion.2", 1.4e-2, 2e-3),
         Event(DEV, OPS, "fusion.3", 1.6e-2, 1e-3),
         Event(DEV, OPS, "fusion.5", 1.8e-2, 7e-3)]


@pytest.fixture
def offered(monkeypatch):
    from deepspeed_tpu.telemetry import memory
    monkeypatch.setattr(memory, "scopes_offered",
                        lambda program: MAPS.get(program, []))


def test_the_scopes_are_phases_of_their_own():
    """The mixer's core is apart from its projections, which are
    ``attn_proj`` as every per-head mixer's."""
    from deepspeed_tpu.utils.xla_profile import (SERVE_PHASES, serve_phase,
                                                 serve_scope)
    assert {"retention_state", "retention_chunk"} <= set(SERVE_PHASES)
    for path, scope, phase in (
            (WINDOW + "attention/retention_state/pallas_call",
             "retention_state", "retention_state"),
            (STEP + "attention/retention_chunk/while", "retention_chunk",
             "retention_chunk"),
            (STEP + "attention/qkv_proj/dot_general", "qkv_proj",
             "attn_proj"),
            (STEP + "attention/out_proj/dot_general", "out_proj",
             "attn_proj"),
            (STEP + "attention/add", "attention", "attn_proj")):
        assert (serve_scope(path), serve_phase(path)) == (scope, phase)


def test_the_readers_on_a_hand_made_trace(offered):
    ev = _evidence(TRACE)
    # the one-token update: 4 + 2 ms in the decode window
    assert SPECS["retention_ms.gen"]["params"] \
        == {"phases": ["retention_state"]}
    assert serve_scope_time.read(
        ev, SPECS["retention_ms.gen"]["params"]) == pytest.approx(6.0)
    # the chunked form: 3 ms in the ragged step
    spec = SPECS["prefill_retention_ms.gen"]
    assert (spec["reader"], spec["params"]) == (
        "serve_program_scope_time",
        {"programs": ["ragged_step"], "scopes": ["retention_chunk"]})
    assert serve_program_scope_time.read(ev, spec["params"]) \
        == pytest.approx(3.0)
    # against 8 layers x 2 decode steps x 2 rows
    step = SPECS["retention_state_roofline.gen"]["params"]
    assert step == {"phases": ["retention_state"]}
    least = arith_retention.least_seconds(FIELDS, 2, 2, PEAKS)
    assert least == pytest.approx(8 * 2 * 2 * 68_210_720 / 819e9)
    assert retention_roofline.read(ev, step) \
        == pytest.approx(100 * least / 6e-3)
    # the control keeps half the state's bytes
    half = _evidence(TRACE, engine={"state_dtype": "bfloat16"})
    assert retention_roofline.read(half, step) == pytest.approx(
        100 * 8 * 2 * 2 * (2 * 8_520_192 * 2 + 4 * 12_296) / 819e9 / 6e-3)
    # the chunked form: 8 layers x 2 fresh rows of 5 prompt tokens fed in
    # ceil(10 / 4) = 3 launches, float32 products
    chunk = SPECS["retention_chunk_roofline.gen"]["params"]
    assert chunk == {"phases": ["retention_chunk"], "form": "prompt",
                     "mxu_passes": 6}
    least = arith_retention.prompt_least_seconds(FIELDS, 2, 5, PEAKS, 3, 4,
                                                 6)
    assert least == pytest.approx(
        8 * 2 * (5 * 12_296 * 2 + 5 * 8_520_192 * 4) / 819e9)
    assert retention_roofline.read(ev, chunk) \
        == pytest.approx(100 * least / 3e-3)


def test_the_new_reader_reads_nothing_where_there_is_nothing(monkeypatch,
                                                             offered):
    """No slice; a configuration without such layers; a program whose
    maps have no such scope, or that offers none (a parent commit's):
    None, and no error, so that the line leaves the metric out."""
    roof = SPECS["retention_state_roofline.gen"]["params"]
    chunk = SPECS["retention_chunk_roofline.gen"]["params"]
    no_slice = _evidence([])
    no_slice.slice_steps = 0
    assert retention_roofline.read(no_slice, roof) is None
    assert retention_roofline.read(_evidence(TRACE, fields=OPT), roof) \
        is None
    only_mlp = _evidence([TRACE[3], TRACE[7]])
    assert retention_roofline.read(only_mlp, roof) is None
    assert retention_roofline.read(only_mlp, chunk) is None
    from deepspeed_tpu.telemetry import memory
    monkeypatch.setattr(memory, "scopes_offered", lambda program: [])
    assert retention_roofline.read(_evidence(TRACE), roof) is None
    assert retention_roofline.read(_evidence(TRACE), chunk) is None
    assert serve_scope_time.read(_evidence(TRACE), roof) is None


# ---------------------------------------------------------------------------
# the cell's files
# ---------------------------------------------------------------------------
def test_the_cell_and_its_traffic_say_what_the_issue_asked():
    cell = json.loads((BENCH / "workloads" / f"{CELL}.json").read_text())
    sm = cell["engine"]["state_manager"]
    traffic = json.loads((BENCH / "traffic" / f"{TRAFFIC}.json")
                         .read_text())
    assert traffic["rows"] in (16, 12) and "taken" in \
        traffic["rule"]["branch"]
    assert sm["max_tracked_sequences"] == 16
    assert sm["max_ragged_batch_size"] == 8192
    assert sm["max_seq_len"] == 2048 + 256
    assert (traffic["runner"], traffic["prompt_len"], traffic["new_tokens"],
            traffic["temperature"], traffic["distinct_batches"],
            traffic["check_rows"]) == ("generate_retention", 2048, 256, 0.0,
                                       4, 4)
    assert cell["engine"]["dtype"] == "bfloat16" \
        and cell["engine"]["use_paged_kernel"] is True
    assert set(cell["engine"]) == {"dtype", "use_paged_kernel",
                                   "state_manager"}    # no new option
    # nothing is paged, and the file says what the two keys then mean
    assert sm["num_blocks"] == 2 and "NOTHING is paged" \
        in cell["overrides"]["block_size"]
    assert cell["control"] == {"engine": {"state_dtype": "bfloat16"}}
    for name in ("logit_err", "token_gap", "state_err"):
        assert 0 < cell["limits"][name]["limit"] < 1
        assert "PR 56" in cell["limits"][name]["from"]
        assert 0 < cell["rehearse"]["limits"][name]["limit"] < 1
    assert "control" in cell["limits"]["state_err"]["from"].lower()
    assert "13.8" in cell["sizing"]
    toy = FILE["toy_fields"]
    assert toy["num_heads"] // toy["num_kv_heads"] > 1  # a group reads


def test_the_cell_rehearses_with_its_three_limits():
    p = run_py(["--workload", CELL, "--rehearse", "--trace", "1",
                "--seed", str(2 ** 31 + 5600000001)])
    assert_rehearsed(p)
    assert "compared: logit_err" in p.stderr
    assert "compared: token_gap" in p.stderr
    assert "compared: state_err" in p.stderr
    assert "calls finished 0" not in p.stderr
    ran = p.stderr.split("readers ran")[1]
    # the CPU has no device trace: the counters' and the host's readers
    assert "state_gb.gen" in ran and "host_ms.gen" in ran


def test_the_control_fails_the_state_number():
    """``state_dtype`` bfloat16 on the toy: the cell as it stands passes
    its three numbers, and under the control ``state_err`` reads over
    fifty times its limit: the comparison can come out not correct on
    what this cell adds."""
    import jax
    from benchmark import control
    from benchmark import run as harness
    seed = 2 ** 31 + 5600000002
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
