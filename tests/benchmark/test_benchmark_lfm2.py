"""The configuration ``lfm2-8b-a1b`` and its cell
``lfm2-8b-a1b.rollout-256x512-512``: what ``BENCHMARK.json`` and the files
say of them (entries found BY NAME, never by position and never as a
whole list: a later PR appends; "at least these"), the configuration's
file against what the source publishes, the cut held to the manifest's
floors, the 9.33 GB of the cut and the cell's pool and conv leaf reckoned
from ``fields``, the two new metrics' files mirrored,
``arith_lfm2.py`` by hand at a toy and at the published size, the runner's
``program_fields``, the control on the toy, and the cell's rehearsal with
its readers.

Toy readings on the sandbox's CPU (no chip result), float32 engine, seed
2**31 + 6500000001: ``logit_err`` 6.8e-7, ``token_gap`` 0, ``state_err``
7.2e-7 (layer 0 2.6e-7, layer 1 7.2e-7); under the cell's control (GELU
for SiLU in the leading dense MLPs) layer 1 reads 8.7e-2 to 9.1e-2. The
other toy controls (SiLU on the taps, a gate dropped, the projection read
x first, the bias weighing, the head norms behind the rotation) are the
serving contract's, on the same widths
(``tests/unit/inference/served_blocks.py``). The chip's limits and the
readings they lie between: the cell's file and PERF.md section 4."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmark import arith_lfm2, manifest
from benchmark.run import merge, reported_by

from test_benchmark_run import assert_rehearsed, run_py

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmark"
CONFIG = "lfm2-8b-a1b"
TRAFFIC = "rollout-256x512-512"
CELL = f"{CONFIG}.{TRAFFIC}"
FILE = json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())
FIELDS, PUB = FILE["fields"], FILE["published"]
TOY = merge(FIELDS, FILE["toy_fields"])
WORKLOAD = json.loads((BENCH / "workloads" / f"{CELL}.json").read_text())
MIX = json.loads((BENCH / "traffic" / f"{TRAFFIC}.json").read_text())
LAYER = "short-convolution layers"
# the accepted lists the cell joins AT LEAST: the generation step's, the
# device's, memory's, the expert layer's and the prompt's. Every accepted
# scope reader joins the slice's 1.23 million events to their launches
# again (12.5-14.4 s a metric on this cell's call of 8 chunk steps and 511
# decode steps over 14 layers, a share reader 7-11 s, a gap reader 1.5 s;
# my chip runs, PR 65) and the driver stops a run at 360 s: with
# ``ragged_share.gen`` and the six ``gap_*`` listed too the traced run
# took 340 s on a warm compile cache. What the cell lists beyond these is
# decided by that clock and pinned by nothing here (PERF.md section 7
# names the readers' repair, after which the rest are data to append)
JOINED = [
    "compiles.gen", "idle.gen", "peak_hbm.gen", "host_ms.gen",
    "gc_pause_ms.gen", "decode_ms.gen", "scope_coverage.gen",
    "experts_touched.gen", "experts_roofline.gen", "state_gb.gen",
    "kv_gb.gen", "prefill_ms.gen", "experts_share.gen"]
NEW = ["conv_mixer_ms.gen", "prefill_conv_mixer_ms.gen"]
# another block's: a latent pool, linear, state-space, retention or
# two-mixer layers, a ring, two-matrix experts
OTHERS = ["latent_share.gen", "linear_ms.gen", "ssm_ms.gen",
          "retention_ms.gen", "hybrid_mixer_ms.gen", "window_roofline.gen",
          "ssm_state_roofline.gen", "experts_relu2_roofline.gen",
          "prefill_ssm_conv_ms.gen", "prefill_linear_ms.gen"]


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
    assert c["reduced"] == FILE["reduced"] == ["num_hidden_layers"]
    assert c["source"] == FILE["source"] == "https://huggingface.co/" \
        "LiquidAI/LFM2-8B-A1B/blob/main/config.json"
    assert c["file"] == f"benchmark/configs/{CONFIG}.json"
    w = _named(bm["workloads"], CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, TRAFFIC, 1)
    assert w["why"] == WORKLOAD["why"] and "256" in w["why"]
    assert set(reported_by(bm, CELL, "end_to_end")) >= {"setup_s",
                                                        "gen_tok_s"}
    assert CELL in _named(bm["end_to_end"], "gen_tok_s")["workloads"]


@pytest.mark.parametrize("name", JOINED)
def test_the_cell_reports_at_least_this(name):
    bm = manifest.read(REPO)
    m = _named(bm["per_layer"], name)
    assert CELL in m["workloads"] and m["moves"] == "gen_tok_s"
    assert name in reported_by(bm, CELL, "per_layer")
    # appended: a cell that was there stands ahead of it
    assert m["workloads"].index(CELL) >= 1


@pytest.mark.parametrize("name", OTHERS)
def test_another_blocks_metric_is_not_the_cells(name):
    assert CELL not in _named(manifest.read(REPO)["per_layer"],
                              name)["workloads"]


@pytest.mark.parametrize("name", NEW)
def test_a_new_metrics_file_mirrors_the_manifest(name):
    m = _named(manifest.read(REPO)["per_layer"], name)
    spec = json.loads((BENCH / "layer_metrics" / f"{name}.json").read_text())
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) \
        == ("ms", "lower", "device_trace", LAYER, "gen_tok_s")
    for key in ("unit", "better", "source", "layer", "moves"):
        assert spec[key] == m[key]
    assert spec["reader"] == "serve_path_scope_time"
    assert (BENCH / "readers" / "serve_path_scope_time.py").is_file()
    assert spec["params"]["programs"] == [
        "ragged_step" if name.startswith("prefill") else "decode"]
    assert spec["params"]["within"] == ["short_conv"]
    assert CELL in m["workloads"]
    assert LAYER in (REPO / "PERF.md").read_text()


def test_the_new_metrics_read_the_programs_scopes(monkeypatch):
    """The accepted path reader on made-up rows under this block's
    scopes: the whole mixer by ``short_conv``, by program (and the gates
    and taps alone by ``conv_gate``, which no metric of the cell reads:
    PERF.md section 5 has it from the same reader); None for a program
    without them."""
    from benchmark.readers import serve_path_scope_time as reader
    at = "jit(x)/layers/while/body/short_conv/"
    rows = [("ragged_step", "a", at + "conv_proj/dot_general", 0.5),
            ("ragged_step", "b", at + "conv_gate/mul", 0.25),
            ("decode_window_greedy", "c", at + "conv_gate/custom", 0.125),
            ("decode_window_greedy", "d", at + "conv_out/dot_general", 1.0),
            ("decode_window_greedy", "e", at + "reduce", 2.0),
            ("decode_window_greedy", "f", "jit(x)/layers/mlp/dot", 16.0)]
    monkeypatch.setattr(reader, "call_rows", lambda ev: (rows, 1))
    ev = SimpleNamespace(slice_steps=1)

    def read(name, **over):
        spec = json.loads(
            (BENCH / "layer_metrics" / f"{name}.json").read_text())
        return reader.read(ev, {**spec["params"], **over})
    assert read("prefill_conv_mixer_ms.gen") == 750.0
    assert read("conv_mixer_ms.gen") == 3125.0
    assert read("conv_mixer_ms.gen", within=["conv_gate"]) == 125.0
    rows[:] = [r for r in rows if "short_conv" not in r[2]]
    assert read("conv_mixer_ms.gen") is None


# ---------------------------------------------------------------------------
# the configuration against its source, and the cut
# ---------------------------------------------------------------------------
def test_published_widths_and_the_cut():
    assert (PUB["hidden_size"], PUB["num_attention_heads"],
            PUB["num_key_value_heads"], PUB["intermediate_size"],
            PUB["moe_intermediate_size"]) == (2048, 32, 8, 7168, 1792)
    assert (PUB["num_experts"], PUB["num_experts_per_tok"],
            PUB["num_dense_layers"], PUB["conv_L_cache"],
            PUB["vocab_size"], PUB["num_hidden_layers"],
            PUB["max_position_embeddings"], PUB["rope_theta"]) \
        == (32, 4, 2, 3, 65536, 24, 128000, 1000000)
    assert PUB["model_type"] == "lfm2_moe" and not PUB["conv_bias"] \
        and PUB["norm_topk_prob"] and PUB["use_expert_bias"] \
        and PUB["routed_scaling_factor"] == 1
    types = PUB["layer_types"]
    assert len(types) == 24 and types.count("conv") == 18
    assert [i for i, t in enumerate(types) if t == "full_attention"] \
        == [2, 6, 10, 14, 18, 21]
    # every key of the source stands unchanged at the top level, but the cut
    for key, value in PUB.items():
        assert FILE[key] == (14 if key == "num_hidden_layers" else value), key
    assert FIELDS["layer_types"] == types[:14] and FIELDS["num_layers"] == 14
    assert FIELDS["layer_types"].count("conv") == 11
    # among the expert layers 9 conv to 3 attention, the source's 18 : 6
    assert FIELDS["layer_types"][2:].count("conv") == 9
    for field, key in FILE["published_as"].items():
        if key != "num_hidden_layers":
            assert FIELDS[field] == PUB[key], field
    assert FIELDS["hidden_size"] // FIELDS["num_heads"] == 64
    assert (FIELDS["moe_scoring"], FIELDS["moe_norm_topk_eps"],
            FIELDS["tie_embeddings"], FIELDS["qk_norm"]) \
        == ("sigmoid", 1e-6, True, True)
    assert "conv_act" not in FIELDS     # the mixer has no activation
    for key in ("layer_types", "moe_scoring", "moe_norm_topk_eps",
                "moe_selection_bias", "tie_embeddings", "intermediate_size",
                "qk_norm", "conv_act", "source_of_equations"):
        assert FILE["assumed"][key], key
    # the toy keeps what is new
    assert (TOY["conv_taps"], TOY["moe_first_dense_layers"],
            TOY["num_heads"] // TOY["num_kv_heads"]) == (3, 2, 4)
    assert TOY["layer_types"][:3] == ["conv", "conv", "full_attention"]


def test_the_cut_is_held_to_the_manifests_floors():
    c = _named(manifest.read(REPO)["configs"], CONFIG)
    manifest.config(REPO, c)
    said = FILE["cuts"]["num_hidden_layers"]
    assert (said["kind"], said["published"], said["here"],
            said["leading_dense"], said["period"]) == ("depth", 24, 14, 2, 4)
    assert "first" in said["deployment"]
    manifest.cut("depth", said, 24, 14)
    with pytest.raises(manifest.Refused, match="four at least"):
        manifest.cut("depth", {**said, "here": 5}, 24, 5)
    assert not manifest.WIDTH.search("num_hidden_layers")
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_experts_per_tok"):
        assert manifest.WIDTH.search(key), key


def test_the_bytes_the_cell_was_sized_by():
    """9.33 GB of bf16 weights, 1.64 GB of pool, 0.046 GB of conv leaf:
    the arithmetic of the cell's ``sizing``, from ``fields`` and the
    engine's options."""
    from benchmark import weights_lfm2
    shapes = weights_lfm2.shapes(FIELDS)
    count = {stack: sum(_prod(s) for s, _ in leaves.values())
             for stack, leaves in shapes.items()}
    assert count["conv_layers"] == 11 * (16_783_360 + 2048)
    assert count["full_layers"] == 3 * (10_485_888 + 2048)
    assert count["lead_layers"] == 2 * (44_040_192 + 2048)
    assert count["layers"] == 12 * (32 * 11_010_048 + 65_568 + 2048)
    assert count["top"] == 134_217_728 + 2048
    assert sum(count.values()) == arith_lfm2.parameters(FIELDS) \
        == 4_667_077_376
    assert round(2 * sum(count.values()) / 1e9, 2) == 9.33
    sm = WORKLOAD["engine"]["state_manager"]
    rows, reach = MIX["rows"], MIX["prompt_len"] + MIX["new_tokens"]
    assert sm["max_tracked_sequences"] == rows == 256
    assert sm["max_seq_len"] == reach == 1024
    assert sm["num_blocks"] == rows * reach // sm["block_size"] + rows + 1 \
        == 16641
    assert arith_lfm2.pool_bytes(FIELDS, sm["num_blocks"], 16) \
        == 16641 * 3 * 32768
    assert arith_lfm2.state_bytes(FIELDS, rows + 1) == 257 * 11 * 16384
    assert sm["max_ragged_batch_size"] * 8 == rows * MIX["prompt_len"]
    for name in ("logit_err", "token_gap", "state_err"):
        limit = WORKLOAD["limits"][name]
        assert 0 < limit["limit"] < 1 and "PR 65" in limit["from"]
        assert 0 < WORKLOAD["rehearse"]["limits"][name]["limit"] < 1
    # the control is an overlay of a field the program had: no knob of
    # the block's own exists for it
    assert WORKLOAD["control"] == {"program_fields": {"activation": "geglu"}}
    assert "program_fields" not in WORKLOAD
    assert MIX["runner"] == "generate_conv"


def _prod(shape):
    out = 1
    for n in shape:
        out *= n
    return out


# ---------------------------------------------------------------------------
# the arithmetic, by hand
# ---------------------------------------------------------------------------
def test_the_counts_at_a_toy_and_at_the_published_size():
    a = arith_lfm2
    toy = dict(hidden_size=8, num_heads=4, num_kv_heads=2, vocab_size=10,
               intermediate_size=16, moe_intermediate_size=4,
               moe_num_experts=3, moe_top_k=2, moe_first_dense_layers=1,
               conv_taps=3, num_layers=3,
               layer_types=["conv", "full_attention", "conv"])
    p = a.parts(toy)
    assert p["expert"] == 3 * 8 * 4 and p["expert_layer"] == 3 * (96 + 9)
    assert p["conv_mixer"] == 8 * 24 + 3 * 8 + 64
    assert p["attention"] == 2 * 64 + 2 * 8 * 4 + 4
    assert p["dense_mlp"] == 3 * 8 * 16 and p["table"] == 80
    assert a.parameters(toy) == 384 + 2 * 315 + 2 * 280 + 196 + 3 * 16 \
        + 80 + 8
    assert a.state_bytes(toy, 5) == 5 * 2 * 2 * 8 * 4
    assert a.pool_bytes(toy, 7, 16) == 2 * 1 * 7 * 16 * 4 * 2
    assert a.conv_gate_bytes(toy, 5) == 5 * (4 * 8 * 4 + 2 * 2 * 8 * 4)
    # published: the ISSUE's arithmetic
    p = a.parts(FIELDS)
    assert (p["expert"], p["conv_mixer"], p["attention"], p["dense_mlp"]) \
        == (11_010_048, 16_783_360, 10_485_888, 44_040_192)
    assert p["expert_layer"] == 352_321_536 + 65_568
    # a decode step of 256 rows at the mean context streams ~10.6 GB:
    # 12.9 to 13.0 ms at 819 GB/s
    step = a.decode_step_bytes(FIELDS, 256, 768)
    assert step == 2 * 4_667_077_376 + 2 * 3 * 256 * 768 * 512 * 2 \
        + 2 * 256 * 11 * 16384
    assert 12.9e-3 < step / 819e9 < 13.0e-3
    # the prompt: 131,072 tokens, ~219 TFLOP: 1.1 s at the peak
    flops = a.prompt_flops(FIELDS, 131072, 512)
    assert 2.1e14 < flops < 2.3e14
    # a conv layer's gated taps for 256 one-token rows: 16.8 MB, 20 us
    assert a.conv_gate_bytes(FIELDS, 256) == 256 * (32768 + 32768)


def test_program_fields_are_laid_on_the_program_alone():
    from benchmark.runners import generate_conv
    said = []
    ctx = SimpleNamespace(cell={"program_fields": WORKLOAD["control"][
        "program_fields"]}, fields=FIELDS, log=said.append)
    generate_conv.lay_program_fields(ctx)
    assert ctx.model_config().activation == "geglu"
    assert ctx.fields["activation"] == "swiglu" and "geglu" in said[0]
    plain = SimpleNamespace(cell={}, fields=FIELDS, log=said.append)
    generate_conv.lay_program_fields(plain)
    assert not hasattr(plain, "model_config") and len(said) == 1


# ---------------------------------------------------------------------------
# the rehearsal
# ---------------------------------------------------------------------------
def test_the_state_probe_is_the_windows_call():
    """``state_err`` reads the slots after a call of the window's own
    ``new_tokens`` (no shorter probe), in both leading dense layers."""
    from benchmark.runners import generate_conv
    assert "state_probe_new_tokens" not in MIX
    assert "state_probe_new_tokens" not in WORKLOAD["rehearse_traffic"]
    assert generate_conv.JUDGED_LAYERS == FIELDS["moe_first_dense_layers"] \
        == 2 and FIELDS["layer_types"][:2] == ["conv", "conv"]


def test_the_control_fails_the_state_number():
    """The cell's control on the toy (the leading dense MLPs' gate GELU
    where the block says SiLU, laid on the program alone): the run comes
    out NOT correct through the runner's own comparison, by ``state_err``
    in LAYER 1's slot, whose input the first dense MLP has moved; layer
    0's slot, ahead of it, reads what a sound run reads (the rehearsal
    below is the sound run)."""
    import jax
    from benchmark import control
    from benchmark import run as harness
    result = control.run_once(CELL, 2 ** 31 + 6500000002, 1.0, True,
                              jax.devices(), harness.CompileClock(),
                              rehearse=True)
    state = result.correct_detail["compared"]["state_err"]
    assert result.correct is False and state["value"] >= 50 * state["limit"]
    parts = result.correct_detail["state_err_by_row_and_layer"]
    assert {key.split(".")[1] for key in parts} == {"0", "1"}
    for key, value in parts.items():
        assert (value > state["limit"]) == key.endswith(".1"), (key, value)


def test_the_cell_rehearses_with_its_readers():
    p = run_py(["--workload", CELL, "--rehearse", "--trace", "1",
                "--seed", str(2 ** 31 + 6500000001)])
    assert_rehearsed(p)
    assert "pallas:pipelined" in p.stderr
    for name in ("logit_err", "token_gap", "state_err"):
        assert f"compared: {name}" in p.stderr
    assert "calls finished 0" not in p.stderr
    ran = p.stderr.split("readers ran")[1]
    for name in ("state_gb.gen", "kv_gb.gen", "peak_hbm.gen",
                 "compiles.gen", "experts_touched.gen"):
        assert name in ran, name
    # the reference is found through the configuration, a file
    assert FILE["reference"] == "reference_lfm2" \
        and FILE["weights"] == "weights_lfm2"
