"""The configuration ``ling-3.0-flash`` and its cell
``ling-3.0-flash.rollout-128x256``: what ``BENCHMARK.json`` and the
files say of them (entries found BY NAME, never by position: a later PR
appends), the arithmetic the state's roofline rests on by hand at the
published sizes, the new readers on a hand-made trace and where there is
nothing to read, the 10.54 GB of the cut reckoned from ``fields``, the
cell's rehearsal with its readers, and the number of ``correct`` that
reads the state (``runners/generate_state.py``), which the cell's
control fails."""

import json
import types
from pathlib import Path

import pytest

from benchmark import (arith, arith_experts, arith_latent, arith_state,
                       manifest, tracing)
from benchmark.readers import (latent_pattern_roofline, latent_roofline,
                               registry_gauge, serve_scope_time,
                               state_roofline)
from benchmark.run import reported_by
from benchmark.tracing import Event
from deepspeed_tpu.telemetry import (MetricsRegistry, get_registry,
                                     set_registry)

from test_benchmark_run import assert_rehearsed, run_py

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmark"
CONFIG, CELL = "ling-3.0-flash", "ling-3.0-flash.rollout-128x256"
FILE = json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())
FIELDS = FILE["fields"]
OPT = json.loads((BENCH / "configs/opt-1.3b.json").read_text())["fields"]
PEAKS = arith.peaks("TPU v5 lite")
NEW = ["linear_ms.gen", "state_roofline.gen", "state_gb.gen",
       "chunk_roofline.gen", "latent_pattern_roofline.gen"]
LAYER = {name: "linear attention layers" for name in NEW}
LAYER["latent_pattern_roofline.gen"] = "latent attention kernel"
JOINED = ["compiles.gen", "idle.gen", "peak_hbm.gen", "host_ms.gen",
          "gap_host_ms.gen", "gap_launch_ms.gen", "gap_unattributed.gen",
          "prefill_ms.gen", "decode_ms.gen", "attn_proj_ms.gen",
          "kv_write_ms.gen", "mlp_ms.gen", "head_ms.gen", "router_ms.gen",
          "scope_coverage.gen", "experts_share.gen", "experts_roofline.gen",
          "experts_touched.gen", "latent_share.gen"]
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
        "num_hidden_layers", "num_experts", "vocab_size",
        "num_nextn_predict_layers"]
    assert c["source"] == FILE["source"] \
        == "https://huggingface.co/inclusionAI/Ling-3.0-flash/blob/main/" \
           "config.json"
    w = _named(bm["workloads"], CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (
        CONFIG, "rollout-128x256", 1)
    assert "attention sees 4x its share" in w["why"]
    assert reported_by(bm, CELL, "end_to_end") == ["setup_s", "gen_tok_s"]
    mine = reported_by(bm, CELL, "per_layer")
    assert sorted(mine) == sorted(JOINED + NEW)
    # a pattern's latent layers are not num_layers (the cell reports
    # latent_pattern_roofline.gen in its place), and the per-head
    # kernels are not here
    for name in ("latent_roofline.gen", "ragged_share.gen",
                 "ragged_roofline.gen"):
        assert CELL not in _named(bm["per_layer"], name)["workloads"]
    for name in NEW:
        m = _named(bm["per_layer"], name)
        assert m["workloads"] == [CELL] and m["moves"] == "gen_tok_s"
        assert m["layer"] == LAYER[name]
        for key in ("unit", "better", "source", "layer", "moves"):
            assert SPECS[name][key] == m[key]
    # nothing that was there lost a cell
    for name in JOINED:
        assert "joyai-llm-flash.rollout-64x256" in _named(
            bm["per_layer"], name)["workloads"]


def test_published_widths_and_the_cut():
    pub = FILE["published"]
    for key, want in dict(
            hidden_size=2560, num_heads=32, intermediate_size=6144,
            kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
            v_head_dim=128, q_lora_rank=0, linear_head_dim=128,
            linear_conv_size=4, linear_decay_floor=-5.0,
            linear_attn_period=6, moe_intermediate_size=768,
            moe_num_experts=512, moe_top_k=8, moe_n_group=8,
            moe_topk_group=4, moe_shared_experts=1,
            moe_first_dense_layers=2, moe_routed_scale=2.5,
            rope_theta=6e6, attn_gate="head").items():
        assert FIELDS[key] == want, key
    # the router keeps its published width; what is cut is what is HELD
    assert FIELDS["moe_num_experts"] == pub["num_experts"] == 512
    assert FILE["published_as"]["moe_experts_held"] == "num_experts"
    assert "q_lora_rank" not in FILE["published_as"] \
        and pub["q_lora_rank"] is None
    cuts = FILE["cuts"]
    assert (cuts["num_hidden_layers"]["here"], cuts["num_experts"]["here"],
            cuts["vocab_size"]["here"]) == (
        FIELDS["num_layers"], FIELDS["moe_experts_held"],
        FIELDS["vocab_size"]) == (8, 128, 39296)
    assert cuts["num_experts"]["shared_over_chips"] == 4
    assert cuts["num_hidden_layers"]["period"] == 6
    assert 4 * 39296 == pub["vocab_size"]
    # every number of the source's config stands at the top level too
    for key, value in pub.items():
        if key not in FILE["reduced"]:
            assert FILE[key] == value, key
    for key in ("pattern", "qk_norm", "gate", "kda_lower_bound", "seeded",
                "swiglu_limits", "read_and_unused"):
        assert FILE["assumed"][key]


def test_the_bytes_of_the_cut_from_fields():
    """ISSUE 41's arithmetic, 2 B a parameter, from ``fields`` alone."""
    f = FIELDS
    h, nh, d = f["hidden_size"], f["num_heads"], f["linear_head_dim"]
    D = nh * d
    linear = 5 * h * D + 2 * h * nh + 4 * 3 * D + nh + D + d + h
    assert linear == pytest.approx(52.6e6, rel=2e-3)
    qk = f["qk_nope_head_dim"] + f["qk_rope_head_dim"]
    latent = h * nh * qk + h * (f["kv_lora_rank"] + f["qk_rope_head_dim"]) \
        + f["kv_lora_rank"] * nh * (f["qk_nope_head_dim"] + f["v_head_dim"]) \
        + nh * f["v_head_dim"] * h + h * nh + f["kv_lora_rank"] + h
    assert latent == pytest.approx(31.9e6, rel=3e-3)
    dense = 3 * h * f["intermediate_size"] + h
    expert = 3 * h * f["moe_intermediate_size"]
    assert expert == arith_experts.expert_bytes(f) // 2 == 5_898_240
    routed = f["moe_experts_held"] * expert + expert \
        + h * f["moe_num_experts"] + f["moe_num_experts"] + h
    lead = f["moe_first_dense_layers"]
    kinds = ["mla" if (i + 1) % f["linear_attn_period"] == 0 else "kda"
             for i in range(f["num_layers"])]
    total = 2 * f["vocab_size"] * h + h + sum(
        (linear if kind == "kda" else latent)
        + (dense if i < lead else routed) for i, kind in enumerate(kinds))
    assert 2 * total == pytest.approx(10.54e9, rel=1e-3)
    assert 2 * total / 16e9 == pytest.approx(0.66, abs=0.005)


# ---------------------------------------------------------------------------
# the state's arithmetic, by hand
# ---------------------------------------------------------------------------
def test_state_arithmetic_by_hand():
    assert arith_state.linear_layers(FIELDS) == 7
    assert arith_state.linear_layers({**FIELDS, "num_layers": 42}) == 35
    # 32 heads x 128 x 128, and three inputs of q, k and v
    assert arith_state.state_values(FIELDS) == 32 * 128 * 128 \
        + 3 * 3 * 4096 == 561_152
    assert arith_state.row_bytes(FIELDS) == 2 * 561_152 * 4 == 4_489_216
    assert arith_state.row_bytes(FIELDS, 2) == 2_244_608     # the control
    assert arith_state.row_flops(FIELDS) == 7 * 32 * 128 * 128
    # a row's 15.0 MB of ISSUE 41: the seven layers' states, once
    assert 7 * 561_152 * 4 == 15_712_256
    # bound by bytes on a v5e: 5.48 us a row and layer, against 18.6 ns
    assert arith_state.row_flops(FIELDS) / 197e12 \
        < arith_state.row_bytes(FIELDS) / 819e9
    # one call of the cell: 255 decode steps of 128 rows, 7 layers
    want = 7 * 255 * 128 * 4_489_216 / 819e9
    assert arith_state.least_seconds(FIELDS, 128, 255, PEAKS) \
        == pytest.approx(want) == pytest.approx(1.2524, rel=1e-3)


def test_prompt_arithmetic_by_hand():
    """The recurrence over a fresh row's prompt: a token's q, k, v and
    decay in and its output out (5 x 4096 values) and its 32 betas, in
    bf16, and the row's state written once."""
    assert arith_state.prompt_row_bytes(FIELDS, 128) \
        == 128 * (5 * 4096 + 32) * 2 + 561_152 * 4 == 7_495_680
    assert arith_state.prompt_row_bytes(FIELDS, 128, itemsize=2) \
        == 7_495_680 - 561_152 * 2
    # bound by bytes on a v5e: 9.15 us a row and layer against 2.38 us
    assert 128 * arith_state.row_flops(FIELDS) / 197e12 \
        < 7_495_680 / 819e9
    # the cell's launch: 128 rows of 128 tokens, 7 layers: 8.2 ms
    want = 7 * 128 * 7_495_680 / 819e9
    assert arith_state.prompt_least_seconds(FIELDS, 128, 128, PEAKS) \
        == pytest.approx(want) == pytest.approx(8.2004e-3, rel=1e-3)


def _evidence(events, fields=FIELDS, engine=None, rows=2, new_tokens=3,
              prompt_len=5):
    ctx = types.SimpleNamespace(
        fields=fields, traffic={"rows": rows, "new_tokens": new_tokens,
                                "prompt_len": prompt_len},
        cell={"engine": engine or {}},
        devices=[types.SimpleNamespace(device_kind="TPU v5 lite")])
    return types.SimpleNamespace(events=tracing.Events(events), ctx=ctx,
                                 slice_steps=1)


WINDOW = "jit(decode_window_greedy)/while/body/layers/while/body/"
MAPS = {"decode_window_greedy": [{
    "fusion.1": WINDOW + "linear_attention/kda_state/mul",
    "fusion.2": WINDOW + "linear_attention/kda_state/scatter",
    "fusion.3": WINDOW + "linear_attention/kda_proj/dot_general",
    "fusion.4": WINDOW + "linear_attention/kda_conv/reduce",
    "fusion.5": WINDOW + "mlp/moe_experts/gather"}],
    "ragged_step": [{
        "fusion.1": "jit(ragged_step)/layers/while/body/linear_attention/"
                    "kda_chunk/while/body/dot_general"}]}
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


def test_the_linear_scopes_are_phases():
    from deepspeed_tpu.utils.xla_profile import SERVE_PHASES, serve_phase
    for tail, phase in (("kda_state/mul", "linear_state"),
                        ("kda_chunk/while/body/dot", "linear_chunk"),
                        ("kda_proj/dot_general", "linear"),
                        ("kda_conv/reduce", "linear"),
                        ("kda_out/dot_general", "linear"),
                        ("add", "linear")):
        got = serve_phase(WINDOW + "linear_attention/" + tail)
        assert got == phase and got in SERVE_PHASES
    assert serve_phase(WINDOW + "mla_attention/attn_kernel/x") \
        == "attn_kernel"


def test_the_new_readers_on_a_hand_made_trace(offered):
    ev = _evidence(TRACE)
    # every operation under linear_attention, both programs: 3 + 4 + 2 +
    # 1 + 0.5 ms
    assert serve_scope_time.read(ev, SPECS["linear_ms.gen"]["params"]) \
        == pytest.approx(10.5)
    # the one-token update: 4 + 2 ms, against 7 layers x 2 steps x 2 rows
    least = arith_state.least_seconds(FIELDS, 2, 2, PEAKS)
    assert least == pytest.approx(7 * 2 * 2 * 4_489_216 / 819e9)
    assert state_roofline.read(ev, SPECS["state_roofline.gen"]["params"]) \
        == pytest.approx(100 * least / 6e-3)
    # the control keeps half the bytes: its floor is half
    half = _evidence(TRACE, engine={"state_dtype": "bfloat16"})
    assert state_roofline.read(
        half, SPECS["state_roofline.gen"]["params"]) \
        == pytest.approx(50 * least / 6e-3)
    # the chunked form: 3 ms in the ragged step, against 7 layers x 2
    # fresh rows of 5 prompt tokens
    chunk = SPECS["chunk_roofline.gen"]["params"]
    assert chunk == {"phases": ["linear_chunk"], "form": "prompt"}
    least = arith_state.prompt_least_seconds(FIELDS, 2, 5, PEAKS)
    assert least == pytest.approx(
        7 * 2 * (5 * (5 * 4096 + 32) * 2 + 561_152 * 4) / 819e9)
    assert state_roofline.read(ev, chunk) \
        == pytest.approx(100 * least / 3e-3)


def test_the_latent_roofline_of_a_pattern_counts_its_latent_layers():
    """One layer of the cell's eight is latent: the pattern's reader
    reads an eighth of what ``latent_roofline`` would (which multiplies
    by ``num_layers``), and nothing for a block without a pattern."""
    assert latent_pattern_roofline.latent_layers(FIELDS) == 1
    assert latent_pattern_roofline.latent_layers(
        {**FIELDS, "num_layers": 42}) == 7
    kernel = Event(DEV, OPS, "ragged_attention_latent.3:tpu_custom_call",
                   2e-3, 1e-3)
    ev = _evidence([TRACE[0], kernel])
    ev.launch_rows = [(5, 5), (5, 5), (1, 6), (1, 6)]
    params = SPECS["latent_pattern_roofline.gen"]["params"]
    got = latent_pattern_roofline.read(ev, params)
    launches = arith_latent.launches(ev.launch_rows, 2)
    least = sum(max(arith_latent.launch_bytes(FIELDS, ln) / 819e9,
                    arith_latent.launch_flops(FIELDS, ln) / 197e12)
                for ln in launches)
    assert got == pytest.approx(100 * least / 1e-3)
    assert latent_roofline.read(ev, params) == pytest.approx(8 * got)
    joyai = json.loads((BENCH / "configs/joyai-llm-flash.json")
                       .read_text())["fields"]
    plain = _evidence([TRACE[0], kernel], fields=joyai)
    plain.launch_rows = ev.launch_rows
    assert latent_pattern_roofline.read(plain, params) is None
    assert latent_roofline.read(plain, params) is not None
    no_kernel = _evidence([TRACE[0]])
    no_kernel.launch_rows = ev.launch_rows
    assert latent_pattern_roofline.read(no_kernel, params) is None
    assert latent_pattern_roofline.read(_evidence([]), params) is None


@pytest.fixture
def fresh_registry():
    old = get_registry()
    set_registry(MetricsRegistry())
    yield get_registry()
    set_registry(old)


def test_each_new_reader_reads_nothing_where_there_is_nothing(
        monkeypatch, offered, fresh_registry):
    """No slice; a configuration without linear layers; a program whose
    maps have no such scope, or that offers none (the parent commit's);
    a registry without the gauge: None, and no error, so that the line
    leaves the metric out."""
    roof = SPECS["state_roofline.gen"]["params"]
    no_slice = _evidence([])
    no_slice.slice_steps = 0
    assert state_roofline.read(no_slice, roof) is None
    assert state_roofline.read(_evidence(TRACE, fields=OPT), roof) is None
    only_experts = _evidence([TRACE[2], TRACE[7]])
    assert state_roofline.read(only_experts, roof) is None
    assert serve_scope_time.read(
        only_experts, SPECS["linear_ms.gen"]["params"]) is None
    from deepspeed_tpu.telemetry import memory
    monkeypatch.setattr(memory, "scopes_offered", lambda program: [])
    assert state_roofline.read(_evidence(TRACE), roof) is None
    gauge = SPECS["state_gb.gen"]["params"]
    assert registry_gauge.read(None, gauge) is None
    fresh_registry.gauge("inference_state_bytes").set(0)
    assert registry_gauge.read(None, gauge) is None
    fresh_registry.gauge("inference_state_bytes").set(2.0e9)
    assert registry_gauge.read(None, gauge) == pytest.approx(2.0)


# ---------------------------------------------------------------------------
# the cell's files
# ---------------------------------------------------------------------------
def test_the_cell_and_its_traffic_say_what_the_issue_asked():
    cell = json.loads((BENCH / "workloads" / f"{CELL}.json").read_text())
    sm = cell["engine"]["state_manager"]
    traffic = json.loads((BENCH / "traffic/rollout-128x256.json")
                         .read_text())
    rows = traffic["rows"]
    assert rows in (128, 96) and traffic["rule"]["branch"]
    assert (sm["max_tracked_sequences"], sm["max_ragged_batch_size"]) \
        == (rows, rows * 128)
    assert (sm["max_seq_len"], sm["block_size"], sm["num_blocks"]) \
        == (512, 16, 3201)
    assert (traffic["runner"], traffic["prompt_len"], traffic["new_tokens"],
            traffic["temperature"], traffic["distinct_batches"],
            traffic["check_rows"]) == ("generate_state", 128, 256, 0.0, 4,
                                       4)
    assert cell["engine"]["dtype"] == "bfloat16" \
        and cell["engine"]["use_paged_kernel"] is True
    assert cell["control"] == {"engine": {"state_dtype": "bfloat16"}}
    for name in ("logit_err", "token_gap", "state_err"):
        assert 0 < cell["limits"][name]["limit"] < 1
        assert "control" in cell["limits"][name]["from"]
        assert 0 < cell["rehearse"]["limits"][name]["limit"] < 1


def test_the_cell_rehearses_with_its_readers():
    p = run_py(["--workload", CELL, "--rehearse", "--trace", "1",
                "--seed", str(2 ** 31 + 4100000001)])
    assert_rehearsed(p)
    assert "compared: logit_err" in p.stderr
    assert "compared: token_gap" in p.stderr
    assert "compared: state_err" in p.stderr
    assert "calls finished 0" not in p.stderr
    ran = p.stderr.split("readers ran")[1]
    assert "experts_touched.gen" in ran and "state_gb.gen" in ran
    assert "latent_roofline.gen" not in ran


def test_the_control_fails_the_state_number_and_no_other():
    """``state_dtype`` bfloat16 on the toy: the two numbers of the
    accepted runner read what they read with a float32 state (a toy
    prompt is one chunk, and the served token stays the reference's
    best), and ``state_err`` reads over a hundred times its limit: the
    comparison can come out not correct on what this cell adds."""
    import jax
    from benchmark import control
    from benchmark import run as harness
    seed = 2 ** 31 + 4100000002
    read = {}
    for on in (False, True):
        result = control.run_once(CELL, seed, 2.0, on, jax.devices(),
                                  harness.CompileClock(), rehearse=True)
        read[on] = result.correct, result.correct_detail["compared"]
    assert read[False][0] is True and read[True][0] is False
    for name in ("logit_err", "token_gap"):
        assert read[True][1][name]["value"] \
            <= read[True][1][name]["limit"]
        assert read[True][1][name]["value"] == pytest.approx(
            read[False][1][name]["value"], abs=1e-6)
    sound, control_ = (read[on][1]["state_err"] for on in (False, True))
    assert sound["value"] <= sound["limit"] / 20
    assert control_["value"] >= 50 * control_["limit"]


def test_a_reference_without_states_is_named():
    from benchmark.runners import generate_state
    ctx = types.SimpleNamespace(
        reference=types.SimpleNamespace(), cell={"config": "some-config"})
    with pytest.raises(SystemExit, match="some-config's reference to "
                       "offer leading_states"):
        generate_state.state_error(ctx)
