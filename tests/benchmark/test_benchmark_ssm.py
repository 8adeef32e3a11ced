"""The configuration ``granite-4.0-h-small`` and its cell
``granite-4.0-h-small.rollout-64x1024-256``: what ``BENCHMARK.json`` and
the files say of them (entries found BY NAME, "at least these", never
by position: a later PR appends), the 9.51 GB of the cut and the 12.36
GB of the cell's arguments reckoned from ``fields``, the arithmetic the
state-space rooflines rest on by hand at the published sizes, the new
reader on a hand-made trace and where there is nothing to read, the
cell's rehearsal with its readers, and the number of ``correct`` that
reads the state (``runners/generate_ssm.py``), which the cell's control
fails.

Toy readings (the sandbox's CPU, no chip result; float32 engine, seeds
2**31 + 4900000001 and ..002): ``logit_err`` 3.7e-6, ``token_gap`` 0,
``state_err`` 3.6e-7 in layer 0 (1e-6 to 3.6e-6 in layers 1-4, where a
float32 engine swaps no expert); the control (``state_dtype`` bfloat16)
reads ``state_err`` 3e-3 to 7e-3.
"""

import json
import types
from pathlib import Path

import pytest

from benchmark import arith, arith_experts, arith_ssm, manifest, tracing
from benchmark.readers import registry_gauge, serve_scope_time, ssm_roofline
from benchmark.run import reported_by
from benchmark.tracing import Event
from deepspeed_tpu.telemetry import (MetricsRegistry, get_registry,
                                     set_registry)

from test_benchmark_run import assert_rehearsed, run_py

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmark"
CONFIG = "granite-4.0-h-small"
TRAFFIC = "rollout-64x1024-256"
CELL = f"{CONFIG}.{TRAFFIC}"
FILE = json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())
FIELDS = FILE["fields"]
OPT = json.loads((BENCH / "configs/opt-1.3b.json").read_text())["fields"]
PEAKS = arith.peaks("TPU v5 lite")
NEW = ["ssm_ms.gen", "ssm_state_roofline.gen", "ssm_scan_roofline.gen"]
JOINED = ["compiles.gen", "idle.gen", "peak_hbm.gen", "host_ms.gen",
          "gap_host_ms.gen", "gap_launch_ms.gen", "gap_unattributed.gen",
          "prefill_ms.gen", "decode_ms.gen", "attn_proj_ms.gen",
          "kv_write_ms.gen", "mlp_ms.gen", "head_ms.gen", "router_ms.gen",
          "scope_coverage.gen", "experts_share.gen", "experts_roofline.gen",
          "experts_touched.gen", "ragged_share.gen", "state_gb.gen"]
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


def test_the_configuration_and_the_cell_by_name():
    bm = manifest.read(REPO)
    c = _named(bm["configs"], CONFIG)
    assert c["reduced"] == FILE["reduced"] == [
        "num_hidden_layers", "num_local_experts", "vocab_size"]
    assert c["source"] == FILE["source"] \
        == "https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/" \
           "main/config.json"
    w = _named(bm["workloads"], CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, TRAFFIC, 1)
    assert "8.9 rows a step" in w["why"]
    assert reported_by(bm, CELL, "end_to_end") == ["setup_s", "gen_tok_s"]
    mine = reported_by(bm, CELL, "per_layer")
    assert set(mine) >= set(JOINED + NEW)           # at least these
    # the latent kernel, the linear layers' recurrence and the window
    # layers are not here; nor the per-head roofline that counts every
    # layer whole
    for name in ("latent_share.gen", "latent_roofline.gen", "linear_ms.gen",
                 "state_roofline.gen", "chunk_roofline.gen",
                 "window_roofline.gen", "kv_gb.gen", "ragged_roofline.gen"):
        assert CELL not in _named(bm["per_layer"], name)["workloads"]
    for name in NEW:
        m = _named(bm["per_layer"], name)
        assert CELL in m["workloads"] and m["moves"] == "gen_tok_s"
        assert m["layer"] == "state-space layers"
        for key in ("unit", "better", "source", "layer", "moves"):
            assert SPECS[name][key] == m[key]
    # nothing that was there lost a cell
    for name in JOINED:
        assert "opt-1.3b.rollout-256" in _named(
            bm["per_layer"], name)["workloads"] or name in (
            "router_ms.gen", "experts_share.gen", "experts_roofline.gen",
            "experts_touched.gen", "state_gb.gen")
    assert "ling-3.0-flash.rollout-128x256" in _named(
        bm["per_layer"], "state_gb.gen")["workloads"]


def test_published_widths_and_the_cut():
    pub = FILE["published"]
    for key, want in dict(
            hidden_size=4096, num_heads=32, num_kv_heads=8,
            intermediate_size=768, moe_intermediate_size=768,
            moe_num_experts=72, moe_top_k=10, moe_shared_experts=2,
            mamba_n_heads=128, mamba_d_head=64, mamba_d_state=128,
            mamba_d_conv=4, mamba_n_groups=1, mamba_expand=2,
            mamba_conv_bias=True, mamba_chunk_size=256, embed_scale=12.0,
            attn_scale=0.0078125, residual_scale=0.22, logit_scale=16.0,
            positional="none", tie_embeddings=True).items():
        assert FIELDS[key] == want, key
    # the router keeps its published width; what is cut is what is HELD
    assert FIELDS["moe_num_experts"] == pub["num_local_experts"] == 72
    assert FILE["published_as"]["moe_experts_held"] == "num_local_experts"
    # the shared SwiGLU is served uncut, spelled in experts' widths
    assert FIELDS["moe_shared_experts"] * FIELDS["moe_intermediate_size"] \
        == pub["shared_intermediate_size"] == 1536
    cuts = FILE["cuts"]
    assert (cuts["num_hidden_layers"]["here"],
            cuts["num_local_experts"]["here"],
            cuts["vocab_size"]["here"]) == (
        FIELDS["num_layers"], FIELDS["moe_experts_held"],
        FIELDS["vocab_size"]) == (10, 36, 50176)
    assert cuts["num_local_experts"]["shared_over_chips"] == 2
    assert (cuts["num_hidden_layers"]["period"],
            cuts["num_hidden_layers"]["leading_dense"]) == (10, 0)
    assert 2 * 50176 == pub["vocab_size"]
    # the list stands whole at the top level, its first ten in fields
    assert FILE["layer_types"] == pub["layer_types"] \
        and len(pub["layer_types"]) == 40
    assert FIELDS["layer_types"] == pub["layer_types"][:10] \
        == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    # every number of the source's config stands at the top level too
    for key, value in pub.items():
        if key not in FILE["reduced"]:
            assert FILE[key] == value, key
    for key in ("intermediate_size", "shared_expert", "head_dim", "router",
                "gated_norm", "dt", "mamba_chunk_size", "state", "seeded"):
        assert FILE["assumed"][key]


def _parameters(f):
    """(a mamba mixer's, the attention mixer's, one layer's expert
    part's, the tied table's) parameters, from ``fields``."""
    h, nh, nkv = f["hidden_size"], f["num_heads"], f["num_kv_heads"]
    hd = h // nh
    di = f["mamba_n_heads"] * f["mamba_d_head"]
    dc = di + 2 * f["mamba_d_state"]
    mamba = h * (di + dc + f["mamba_n_heads"]) + f["mamba_d_conv"] * dc \
        + dc + 3 * f["mamba_n_heads"] + di + di * h
    attention = h * (nh + 2 * nkv) * hd + nh * hd * h
    expert = 3 * h * f["moe_intermediate_size"]
    experts = f["moe_experts_held"] * expert \
        + f["moe_shared_experts"] * expert + h * f["moe_num_experts"]
    return mamba, attention, experts, f["vocab_size"] * h


def test_the_bytes_of_the_cut_from_fields():
    """ISSUE 49's arithmetic, 2 B a parameter, from ``fields`` alone."""
    f = FIELDS
    mamba, attention, experts, table = _parameters(f)
    assert mamba == pytest.approx(102.29e6, rel=1e-4)
    assert attention == pytest.approx(41.94e6, rel=1e-3)
    assert 3 * f["hidden_size"] * f["moe_intermediate_size"] \
        == arith_experts.expert_bytes(f) // 2 == 9_437_184
    norms = 2 * f["hidden_size"]
    assert mamba + experts + norms == pytest.approx(461.2e6, rel=1e-3)
    assert attention + experts + norms == pytest.approx(400.9e6, rel=1e-3)
    total = table + f["hidden_size"] + sum(
        (mamba if t == "mamba" else attention) + experts + norms
        for t in f["layer_types"])
    assert total == pytest.approx(4757e6, rel=1e-3)
    assert 2 * total == pytest.approx(9.51e9, rel=1e-3)


def test_the_cells_arguments_from_fields():
    """Weights, the state's slots and the one attention layer's pool:
    12.36 GB, 77 % of the chip."""
    f = FIELDS
    cell = json.loads((BENCH / "workloads" / f"{CELL}.json").read_text())
    sm = cell["engine"]["state_manager"]
    weights = 2 * (sum(_parameters(f)[i] * n for i, n in (
        (0, 9), (1, 1), (2, 10), (3, 1))) + 21 * f["hidden_size"])
    row = arith_ssm.state_values(f) * 4
    assert row == 4_295_680
    slots = sm["max_tracked_sequences"] + 1
    state = 9 * slots * row
    assert state == pytest.approx(2.51e9, rel=2e-3)
    assert 9 * row == pytest.approx(38.7e6, rel=2e-3)   # a row's, all layers
    position = 2 * f["num_kv_heads"] * 128 * 2
    assert position == 4096
    pool = sm["num_blocks"] * sm["block_size"] * position
    assert pool == pytest.approx(0.34e9, rel=2e-2)
    assert sm["num_blocks"] == 64 * 81 + 1 and sm["max_seq_len"] == 1280
    assert weights + state + pool == pytest.approx(12.36e9, rel=2e-3)
    assert (weights + state + pool) / 16e9 == pytest.approx(0.77, abs=0.01)


# ---------------------------------------------------------------------------
# the state's arithmetic, by hand
# ---------------------------------------------------------------------------
def test_state_arithmetic_by_hand():
    assert arith_ssm.ssm_layers(FIELDS) == 9
    assert arith_ssm.ssm_layers(
        {**FIELDS, "layer_types": FILE["layer_types"]}) == 36
    assert arith_ssm.ssm_layers(OPT) == 0
    # 128 heads x 64 x 128, and three inputs of x, B and C
    assert arith_ssm.state_values(FIELDS) == 128 * 64 * 128 + 3 * 8448 \
        == 1_073_920
    assert arith_ssm.row_bytes(FIELDS) == 2 * 1_073_920 * 4 == 8_591_360
    assert arith_ssm.row_bytes(FIELDS, 2) == 4_295_680       # the control
    assert arith_ssm.row_flops(FIELDS) == 5 * 128 * 64 * 128
    # bound by bytes on a v5e: 10.5 us a row and layer, against 27 ns
    assert arith_ssm.row_flops(FIELDS) / 197e12 \
        < arith_ssm.row_bytes(FIELDS) / 819e9
    # one call of the cell: 255 decode steps of 64 rows, 9 layers
    want = 9 * 255 * 64 * 8_591_360 / 819e9
    assert arith_ssm.least_seconds(FIELDS, 64, 255, PEAKS) \
        == pytest.approx(want) == pytest.approx(1.5408, rel=1e-3)


def test_prompt_arithmetic_by_hand():
    """The recurrence over a fresh row's prompt fed in four launches: a
    token's x and y (8,192 values each), B and C (128 each) and dt (128)
    in bf16, and the row's state written four times and read three."""
    per_token = (2 * 8192 + 2 * 128 + 128) * 2
    assert arith_ssm.prompt_row_bytes(FIELDS, 1024, 4) \
        == 1024 * per_token + 7 * 1_073_920 * 4 == 64_410_624
    assert arith_ssm.prompt_row_bytes(FIELDS, 1024) \
        == 1024 * per_token + 1_073_920 * 4
    # bound by bytes on a v5e: 78.6 us a row and layer against 27.2 us
    assert 1024 * arith_ssm.row_flops(FIELDS) / 197e12 \
        < 64_410_624 / 819e9
    # the cell's prompt: 64 rows of 1,024 tokens, 9 layers: 45.3 ms
    want = 9 * 64 * 64_410_624 / 819e9
    assert arith_ssm.prompt_least_seconds(FIELDS, 64, 1024, PEAKS, 4) \
        == pytest.approx(want) == pytest.approx(45.30e-3, rel=1e-3)


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
    "fusion.4": WINDOW + "ssm_mixer/ssm_conv/reduce",
    "fusion.5": WINDOW + "mlp/moe_experts/gather"}],
    "ragged_step": [{
        "fusion.1": "jit(ragged_step)/layers/while/body/ssm_mixer/"
                    "ssm_scan/pallas_call"}]}
OPS, MODULES = tracing.OPS_LINE, tracing.MODULES_LINE
TRACE = [Event(DEV, MODULES, "jit_ragged_step(1)", 0.0, 5e-3),
         Event(DEV, OPS, "fusion.1", 1e-3, 3e-3),
         Event(DEV, MODULES, "jit_decode_window_greedy(2)", 1e-2, 2e-2),
         Event(DEV, OPS, "fusion.1", 1.0e-2, 4e-3),
         Event(DEV, OPS, "fusion.2", 1.4e-2, 2e-3),
         Event(DEV, OPS, "fusion.3", 1.6e-2, 1e-3),
         Event(DEV, OPS, "fusion.4", 1.7e-2, 5e-4),
         Event(DEV, OPS, "fusion.5", 1.8e-2, 7e-3)]


@pytest.fixture
def offered(monkeypatch):
    from deepspeed_tpu.telemetry import memory
    monkeypatch.setattr(memory, "scopes_offered",
                        lambda program: MAPS.get(program, []))


def test_the_state_space_scopes_are_phases():
    from deepspeed_tpu.utils.xla_profile import SERVE_PHASES, serve_phase
    for tail, phase in (("ssm_state/mul", "ssm_state"),
                        ("ssm_scan/pallas_call", "ssm_scan"),
                        ("ssm_proj/dot_general", "ssm"),
                        ("ssm_conv/reduce", "ssm"),
                        ("ssm_gate_norm/mul", "ssm"),
                        ("ssm_out/dot_general", "ssm"),
                        ("add", "ssm")):
        got = serve_phase(WINDOW + "ssm_mixer/" + tail)
        assert got == phase and got in SERVE_PHASES
    assert serve_phase(WINDOW + "attention/attn_kernel/x") == "attn_kernel"


def test_the_new_reader_on_a_hand_made_trace(offered):
    ev = _evidence(TRACE)
    # every operation under ssm_mixer, both programs: 3 + 4 + 2 + 1 +
    # 0.5 ms
    assert serve_scope_time.read(ev, SPECS["ssm_ms.gen"]["params"]) \
        == pytest.approx(10.5)
    # the one-token update: 4 + 2 ms, against 9 layers x 2 steps x 2 rows
    least = arith_ssm.least_seconds(FIELDS, 2, 2, PEAKS)
    assert least == pytest.approx(9 * 2 * 2 * 8_591_360 / 819e9)
    step = SPECS["ssm_state_roofline.gen"]["params"]
    assert ssm_roofline.read(ev, step) == pytest.approx(100 * least / 6e-3)
    # the control keeps half the bytes: its floor is half
    half = _evidence(TRACE, engine={"state_dtype": "bfloat16"})
    assert ssm_roofline.read(half, step) \
        == pytest.approx(50 * least / 6e-3)
    # the chunked form: 3 ms in the ragged step, against 9 layers x 2
    # fresh rows of 5 prompt tokens fed in ceil(10 / 4) = 3 launches
    scan = SPECS["ssm_scan_roofline.gen"]["params"]
    assert scan == {"phases": ["ssm_scan"], "form": "prompt"}
    least = arith_ssm.prompt_least_seconds(FIELDS, 2, 5, PEAKS, 3)
    assert least == pytest.approx(
        9 * 2 * (5 * 16_768 * 2 + 5 * 1_073_920 * 4) / 819e9)
    assert ssm_roofline.read(ev, scan) == pytest.approx(100 * least / 3e-3)


@pytest.fixture
def fresh_registry():
    old = get_registry()
    set_registry(MetricsRegistry())
    yield get_registry()
    set_registry(old)


def test_the_new_reader_reads_nothing_where_there_is_nothing(
        monkeypatch, offered, fresh_registry):
    """No slice; a configuration without state-space layers; a program
    whose maps have no such scope, or that offers none (the parent
    commit's); a registry without the gauge: None, and no error, so
    that the line leaves the metric out."""
    roof = SPECS["ssm_state_roofline.gen"]["params"]
    no_slice = _evidence([])
    no_slice.slice_steps = 0
    assert ssm_roofline.read(no_slice, roof) is None
    assert ssm_roofline.read(_evidence(TRACE, fields=OPT), roof) is None
    only_experts = _evidence([TRACE[2], TRACE[7]])
    assert ssm_roofline.read(only_experts, roof) is None
    assert serve_scope_time.read(
        only_experts, SPECS["ssm_ms.gen"]["params"]) is None
    from deepspeed_tpu.telemetry import memory
    monkeypatch.setattr(memory, "scopes_offered", lambda program: [])
    assert ssm_roofline.read(_evidence(TRACE), roof) is None
    gauge = json.loads((BENCH / "layer_metrics/state_gb.gen.json")
                       .read_text())["params"]
    assert registry_gauge.read(None, gauge) is None
    fresh_registry.gauge("inference_state_bytes").set(2.51e9)
    assert registry_gauge.read(None, gauge) == pytest.approx(2.51)


# ---------------------------------------------------------------------------
# the cell's files
# ---------------------------------------------------------------------------
def test_the_cell_and_its_traffic_say_what_the_issue_asked():
    cell = json.loads((BENCH / "workloads" / f"{CELL}.json").read_text())
    sm = cell["engine"]["state_manager"]
    traffic = json.loads((BENCH / "traffic" / f"{TRAFFIC}.json")
                         .read_text())
    rows = traffic["rows"]
    assert rows in (64, 48) and traffic["rule"]["branch"]
    assert sm["max_tracked_sequences"] == 64
    assert sm["max_ragged_batch_size"] in (16384, 8192)
    assert (sm["max_seq_len"], sm["block_size"]) == (1280, 16)
    assert sm["num_blocks"] == rows * 81 + 1
    assert (traffic["runner"], traffic["prompt_len"], traffic["new_tokens"],
            traffic["temperature"], traffic["distinct_batches"],
            traffic["check_rows"]) == ("generate_ssm", 1024, 256, 0.0, 4, 4)
    assert cell["engine"]["dtype"] == "bfloat16" \
        and cell["engine"]["use_paged_kernel"] is True
    assert cell["control"] == {"engine": {"state_dtype": "bfloat16"}}
    for name in ("logit_err", "token_gap", "state_err"):
        assert 0 < cell["limits"][name]["limit"] < 1
        assert "control" in cell["limits"][name]["from"]
        assert 0 < cell["rehearse"]["limits"][name]["limit"] < 1


def test_the_cell_rehearses_with_its_readers():
    p = run_py(["--workload", CELL, "--rehearse", "--trace", "1",
                "--seed", str(2 ** 31 + 4900000001)])
    assert_rehearsed(p)
    assert "compared: logit_err" in p.stderr
    assert "compared: token_gap" in p.stderr
    assert "compared: state_err" in p.stderr
    assert "calls finished 0" not in p.stderr
    ran = p.stderr.split("readers ran")[1]
    assert "experts_touched.gen" in ran and "state_gb.gen" in ran
    assert "state_roofline.gen" not in ran.replace("ssm_state_roofline", "")


def test_the_control_fails_the_state_number_and_no_other():
    """``state_dtype`` bfloat16 on the toy: the cell as it stands passes
    its three numbers, and under the control ``state_err`` reads over
    fifty times its limit: the comparison can come out not correct on
    what this cell adds. (At a hidden width of 64 a state of eight bits
    carried over two ``put()`` chunks moves the toy's logits and tokens
    too, 1e-2 and 1e-1; at published widths it does not: PERF.md
    section 4.)"""
    import jax
    from benchmark import control
    from benchmark import run as harness
    seed = 2 ** 31 + 4900000002
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
