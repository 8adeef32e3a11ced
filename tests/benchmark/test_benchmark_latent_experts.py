"""The arithmetic and the readers the latent-attention and expert-layer
metrics rest on: by hand at the published widths, on a hand-made trace,
and reading nothing where there is nothing to read. And the cell that
reads them, ``joyai-llm-flash.rollout-64x256``: what ``BENCHMARK.json``
and the configuration's file say of it, and its rehearsal with its
readers."""

import json
import types
from pathlib import Path

import pytest

from benchmark import (arith, arith_experts, arith_gen, arith_latent,
                       manifest, tracing)
from benchmark.readers import experts_roofline, latent_roofline, moe_counters
from benchmark.run import reported_by
from deepspeed_tpu.telemetry import (MetricsRegistry, get_registry,
                                     set_registry)

from test_benchmark_run import assert_rehearsed, run_py

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmark"
CONFIG, CELL = "joyai-llm-flash", "joyai-llm-flash.rollout-64x256"
FIELDS = json.loads((BENCH / "configs" / f"{CONFIG}.json")
                    .read_text())["fields"]
OPT = json.loads((REPO / "benchmark/configs/opt-1.3b.json")
                 .read_text())["fields"]
PEAKS = arith.peaks("TPU v5 lite")
LATENT = "ragged_attention_latent.7:tpu_custom_call"
GMM = "gmm.12:tpu_custom_call"
NEW = ["latent_share.gen", "latent_roofline.gen", "experts_share.gen",
       "experts_roofline.gen", "experts_touched.gen"]


def test_latent_arithmetic_by_hand():
    # 512 + 64 values in bf16 a position a layer
    assert arith_latent.latent_row_bytes(FIELDS) == 1152
    # a token's query in and output out at their smallest form:
    # 32 x (192 + 128) x 2 B
    decode = [(1, 200)] * 64
    assert arith_latent.launch_bytes(FIELDS, decode) == 64 * (
        200 * 1152 + 20480) == 16_056_320
    assert arith_latent.launch_flops(FIELDS, decode) == 64 * 200 * 20480
    # a 128-token prompt into an empty row: token i sees i + 1 positions
    assert arith_latent.visible_positions(128, 128) == 128 * 129 // 2
    assert arith_latent.visible_positions(1, 200) == 200
    assert arith_latent.visible_positions(5, 14) == 10 + 11 + 12 + 13 + 14
    prefill = [(128, 128)] * 64
    assert arith_latent.launch_bytes(FIELDS, prefill) == 64 * (
        128 * 1152 + 128 * 20480) == 177_209_344
    assert arith_latent.launch_flops(FIELDS, prefill) == 64 * 8256 * 20480
    # both launches are bound by bytes on a v5e: 216 us and 19.6 us a layer
    rows = prefill + decode
    want = 5 * (177_209_344 + 16_056_320) / 819e9
    assert arith_latent.least_seconds(FIELDS, rows, 64, PEAKS) \
        == pytest.approx(want)
    assert 64 * 8256 * 20480 / 197e12 < 177_209_344 / 819e9
    with pytest.raises(ValueError):
        arith_latent.launches(rows[:-1], 64)


def test_a_launch_bound_by_operations_takes_the_flop_floor():
    # one row of 4,096 new tokens: 8.4M visible positions x 20,480
    launch = [(4096, 4096)]
    flops = 4096 * 4097 // 2 * 20480
    assert arith_latent.launch_flops(FIELDS, launch) == flops
    by_bytes = arith_latent.launch_bytes(FIELDS, launch) / 819e9
    assert flops / 197e12 > by_bytes
    assert arith_latent.least_seconds(FIELDS, launch, 1, PEAKS) \
        == pytest.approx(5 * flops / 197e12)


def test_expert_arithmetic_by_hand():
    assert arith_experts.expert_bytes(FIELDS) == 3 * 2048 * 768 * 2 \
        == 9_437_184
    assert arith_experts.row_flops(FIELDS) == 3 * 2 * 2048 * 768
    assert arith_experts.expert_layers(FIELDS) == 4
    # a decode launch: 64 rows x 8 picks touch ~221 experts: their
    # weights, 2.09 GB, bound it (2.55 ms); the rows' operations take 25 us
    assert arith_experts.pass_least_seconds(FIELDS, 221, 512, PEAKS) \
        == pytest.approx(221 * 9_437_184 / 819e9)
    # the prefill launch: 65,536 rows are 3.14 ms of operations, over
    # the 2.95 ms all 256 experts take to stream
    assert arith_experts.pass_least_seconds(FIELDS, 256, 65536, PEAKS) \
        == pytest.approx(65536 * 9_437_184 / 197e12)
    kinds = [(4, 256.0, 65536.0), (4 * 255, 221.0, 512.0)]
    assert arith_experts.least_seconds(FIELDS, kinds, PEAKS) \
        == pytest.approx(4 * 65536 * 9_437_184 / 197e12
                         + 1020 * 221 * 9_437_184 / 819e9)


def _evidence(events, fields=FIELDS, rows=2, new_tokens=3, prompt=4):
    ctx = types.SimpleNamespace(
        fields=fields, traffic={"rows": rows, "new_tokens": new_tokens},
        devices=[types.SimpleNamespace(device_kind="TPU v5 lite")])
    return types.SimpleNamespace(
        events=tracing.Events(events), ctx=ctx, slice_steps=1,
        launch_rows=arith_gen.generate_call_rows(rows, prompt, new_tokens))


def _op(name, start, dur):
    return tracing.Event("/device:TPU:0", tracing.OPS_LINE, name, start, dur)


def test_latent_roofline_on_a_hand_made_trace():
    ev = _evidence([_op(LATENT, 0.0, 2e-6), _op(LATENT, 1e-5, 2e-6),
                    _op("fusion.3", 2e-5, 1e-5)])
    least = arith_latent.least_seconds(FIELDS, ev.launch_rows, 2, PEAKS)
    spec = json.loads((BENCH / "layer_metrics"
                       / "latent_roofline.gen.json").read_text())
    assert latent_roofline.read(ev, spec["params"]) \
        == pytest.approx(100 * least / 4e-6)


@pytest.fixture
def fresh_registry():
    old = get_registry()
    set_registry(MetricsRegistry())
    yield get_registry()
    set_registry(old)


def _count(reg, program, passes, touched, rows):
    for name, v in (("moe_launches_total", passes),
                    ("moe_experts_touched_total", touched),
                    ("moe_routed_rows_total", rows)):
        reg.counter(name, labelnames=("program",)).labels(
            program=program).inc(v)


def test_expert_readers_on_hand_made_counters(fresh_registry):
    # a process that ran 3 ragged steps and 10 decode steps over 4 expert
    # layers: 12 and 40 passes
    _count(fresh_registry, "ragged_step", 12, 12 * 250, 12 * 4096)
    _count(fresh_registry, "decode_window", 40, 40 * 200, 40 * 512)
    assert moe_counters.per_pass("decode_window") == (200.0, 512.0)
    assert moe_counters.read(None, {"program": "decode_window"}) == 200.0
    ev = _evidence([_op(GMM, 0.0, 0.05), _op("gmm.13:tpu_custom_call", 0.1,
                                             0.05),
                    _op("fusion.3", 0.2, 1e-5)])
    # the slice is one call: 1 ragged step and new_tokens - 1 = 2 decode
    # steps, each over 4 expert layers
    kinds = [(4, 250.0, 4096.0), (8, 200.0, 512.0)]
    spec = json.loads((BENCH / "layer_metrics"
                       / "experts_roofline.gen.json").read_text())
    assert experts_roofline.read(ev, spec["params"]) == pytest.approx(
        100 * arith_experts.least_seconds(FIELDS, kinds, PEAKS) / 0.1)


def test_each_new_reader_reads_nothing_where_there_is_nothing(
        fresh_registry):
    """No slice; a slice without the kernel; a configuration without a
    latent; a program without the counters (the parent commit's): None,
    and no error, so that the line leaves the metric out."""
    pat = {"pattern": "ragged_attention_latent[_.0-9]*:tpu_custom_call$"}
    gmm = {"pattern": "^gmm[_.0-9]*:tpu_custom_call$"}
    no_slice = _evidence([])
    no_slice.slice_steps = 0
    other = _evidence([_op("fusion.3", 0.0, 1e-3)])
    assert latent_roofline.read(no_slice, pat) is None
    assert latent_roofline.read(other, pat) is None
    assert latent_roofline.read(
        _evidence([_op(LATENT, 0.0, 1e-3)], fields=OPT), pat) is None
    assert experts_roofline.read(no_slice, gmm) is None
    assert experts_roofline.read(other, gmm) is None
    # the kernels ran and the registry has no such counter
    assert experts_roofline.read(_evidence([_op(GMM, 0.0, 1e-3)]),
                                 gmm) is None
    assert moe_counters.read(None, {"program": "decode_window"}) is None
    # the counters exist and nothing was counted
    _count(fresh_registry, "ragged_step", 0, 0, 0)
    assert moe_counters.per_pass("ragged_step") is None


def test_the_cell_is_appended_entries_at_published_widths():
    manifest.check(REPO)
    bm = manifest.read(REPO)
    # the configuration, the cell and the five metrics stand last in
    # their lists, and the cell last in the four lists it joins
    assert bm["configs"][-1]["name"] == CONFIG
    assert bm["workloads"][-1]["name"] == CELL
    assert bm["workloads"][-1]["chips"] == 1
    assert [m["name"] for m in bm["per_layer"][-len(NEW):]] == NEW
    joined = [m["name"] for m in bm["end_to_end"] + bm["per_layer"]
              if m["name"] not in NEW and CELL in m.get("workloads", [])]
    assert joined == ["gen_tok_s", "compiles.gen", "idle.gen",
                      "peak_hbm.gen"]
    for m in bm["end_to_end"] + bm["per_layer"]:
        if m["name"] in joined:
            assert m["workloads"][-1] == CELL
    assert reported_by(bm, CELL, "per_layer") == [
        "compiles.gen", "idle.gen", "peak_hbm.gen"] + NEW
    assert reported_by(bm, CELL, "end_to_end") == ["setup_s", "gen_tok_s"]
    for m in bm["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "gen_tok_s"
        if m["name"].startswith("ragged_"):
            assert CELL not in m["workloads"]    # per-head arithmetic
    config = json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())
    assert config["reduced"] == bm["configs"][-1]["reduced"] == [
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


def test_the_cell_rehearses_with_its_readers():
    p = run_py(["--workload", CELL, "--rehearse", "--trace", "1",
                "--seed", str(2 ** 31 + 3600000001)])
    assert_rehearsed(p)
    assert "compared: logit_err" in p.stderr
    assert "compared: token_gap" in p.stderr
    assert "calls finished 0" not in p.stderr
    ran = p.stderr.split("readers ran")[1]
    assert "experts_touched.gen" in ran and "ragged_share.gen" not in ran
