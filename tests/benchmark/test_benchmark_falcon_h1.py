"""The configuration ``falcon-h1-34b-instruct`` and its cell
``falcon-h1-34b-instruct.rollout-64x1024-512``: what ``BENCHMARK.json``
and the files say of them (entries found BY NAME, never by position and
never as a whole list: a later PR appends; "at least these"), the
configuration's file against what the source publishes (the two lists of
multipliers entry by entry), the cut held to the manifest's floors, the
8.79 GB of the cut and the cell's state and pool reckoned from
``fields``, the four new metrics' files mirrored, ``arith_falcon_h1.py``
by hand at a toy and at the published size, the readers this PR brings
on made-up rows, and the cell's rehearsal with its readers.

Toy readings on the sandbox's CPU (no chip result), float32 engine, seed
2**31 + 6200000001: ``logit_err`` 4.4e-7, ``token_gap`` 0, ``state_err``
3.3e-7 in layer 0 (layers 1-3 4.5e-7 to 8.1e-7: the block has no router,
so every layer could be judged). The toy control (a state kept in
bfloat16) is the serving contract's, on the same widths
(``tests/unit/inference/served_blocks.py``). The chip's limits and the
readings they lie between: the cell's file and PERF.md section 4."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmark import arith, arith_falcon_h1, manifest
from benchmark.readers import serve_path_scope_time
from benchmark.run import merge, reported_by

from test_benchmark_run import assert_rehearsed, run_py

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmark"
CONFIG = "falcon-h1-34b-instruct"
TRAFFIC = "rollout-64x1024-512"
CELL = f"{CONFIG}.{TRAFFIC}"
FILE = json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())
FIELDS, PUB = FILE["fields"], FILE["published"]
TOY = merge(FIELDS, FILE["toy_fields"])
WORKLOAD = json.loads((BENCH / "workloads" / f"{CELL}.json").read_text())
MIX = json.loads((BENCH / "traffic" / f"{TRAFFIC}.json").read_text())
PEAKS = arith.peaks("TPU v5 lite")
# the accepted lists the cell joins AT LEAST: the generation step's, the
# device's, memory's, the state-space layers' and the prompt's inside
JOINED = [
    "compiles.gen", "idle.gen", "peak_hbm.gen", "host_ms.gen",
    "gap_host_ms.gen", "gap_launch_ms.gen", "gap_unattributed.gen",
    "gap_upload_ms.gen", "gap_call_ms.gen", "gap_fetch_ms.gen",
    "gc_pause_ms.gen", "prefill_ms.gen", "decode_ms.gen", "attn_proj_ms.gen",
    "kv_write_ms.gen", "mlp_ms.gen", "head_ms.gen", "scope_coverage.gen",
    "ragged_share.gen", "state_gb.gen", "kv_gb.gen", "ssm_ms.gen",
    "prefill_attn_proj_ms.gen", "prefill_attn_kernel_ms.gen",
    "prefill_mlp_ms.gen", "prefill_head_ms.gen", "prefill_ssm_proj_ms.gen",
    "prefill_ssm_conv_ms.gen", "prefill_ssm_scan_ms.gen",
    "prefill_ssm_norm_ms.gen", "prefill_other_ms.gen"]
# (unit, better, reader) of the metrics this PR brings
NEW = {"hybrid_mixer_ms.gen": ("ms", "lower", "serve_path_scope_time"),
       "prefill_hybrid_mixer_ms.gen": ("ms", "lower",
                                       "serve_path_scope_time"),
       "hybrid_state_roofline.gen": ("%", "higher", "hybrid_roofline"),
       "hybrid_scan_roofline.gen": ("%", "higher", "hybrid_roofline")}
# another block's: experts, a latent pool, linear or retention layers,
# a ring, and the rooflines that count ``mamba`` layers
OTHERS = ["experts_share.gen", "experts_roofline.gen", "router_ms.gen",
          "latent_share.gen", "linear_ms.gen", "retention_ms.gen",
          "window_roofline.gen", "ssm_state_roofline.gen",
          "ssm_grouped_state_roofline.gen", "ssm_grouped_scan_roofline.gen",
          "experts_relu2_roofline.gen", "prefill_experts_ms.gen"]


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
    assert c["source"] == FILE["source"] == "https://huggingface.co/tiiuae/" \
        "Falcon-H1-34B-Instruct/blob/main/config.json"
    assert c["file"] == f"benchmark/configs/{CONFIG}.json"
    w = _named(bm["workloads"], CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, TRAFFIC, 1)
    assert w["why"] == WORKLOAD["why"] and "72" in w["why"]
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


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_metrics_file_mirrors_the_manifest(name):
    m = _named(manifest.read(REPO)["per_layer"], name)
    spec = json.loads((BENCH / "layer_metrics" / f"{name}.json").read_text())
    unit, better, reader = NEW[name]
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) \
        == (unit, better, "device_trace", "two-mixer layers", "gen_tok_s")
    for key in ("unit", "better", "source", "layer", "moves"):
        assert spec[key] == m[key]
    assert spec["reader"] == reader
    assert (BENCH / "readers" / f"{reader}.py").is_file()
    assert CELL in m["workloads"]
    assert "two-mixer layers" in (REPO / "PERF.md").read_text()


# ---------------------------------------------------------------------------
# the configuration against its source, and the cut
# ---------------------------------------------------------------------------
def test_published_widths_multipliers_and_the_cut():
    assert (PUB["hidden_size"], PUB["num_attention_heads"],
            PUB["num_key_value_heads"], PUB["head_dim"]) == (5120, 20, 4, 128)
    assert (PUB["mamba_d_ssm"], PUB["mamba_n_heads"], PUB["mamba_d_head"],
            PUB["mamba_d_state"], PUB["mamba_n_groups"],
            PUB["mamba_d_conv"]) == (4096, 32, 128, 256, 2, 4)
    assert (PUB["intermediate_size"], PUB["vocab_size"],
            PUB["num_hidden_layers"], PUB["max_position_embeddings"],
            PUB["rope_theta"]) == (21504, 261120, 72, 262144, 1e11)
    assert PUB["model_type"] == "falcon_h1" \
        and not PUB["tie_word_embeddings"] \
        and not PUB["mamba_norm_before_gate"]
    # every key of the source stands unchanged at the top level, but the cut
    for key, value in PUB.items():
        assert FILE[key] == (4 if key == "num_hidden_layers" else value), key
    # the inner width is heads x head width, not expand x hidden
    assert FIELDS["mamba_n_heads"] * FIELDS["mamba_d_head"] \
        == PUB["mamba_d_ssm"] != PUB["mamba_expand"] * PUB["hidden_size"]
    # the lists, entry by entry; what divides against what multiplies
    assert [FIELDS[f"ssm_{k}_scale"] for k in ("z", "x", "b", "c", "dt")] \
        == PUB["ssm_multipliers"]
    assert [FIELDS["mlp_gate_scale"], FIELDS["mlp_down_scale"]] \
        == PUB["mlp_multipliers"]
    assert FIELDS["logit_scale"] * PUB["lm_head_multiplier"] == 1.0
    assert FIELDS["key_scale"] / 128 ** 0.5 == pytest.approx(1 / 1024)
    assert FIELDS["layer_types"] == ["mamba_attention"] * 4 \
        == ["mamba_attention"] * FIELDS["num_layers"]
    for field, key in FILE["published_as"].items():
        if key != "num_hidden_layers":
            assert FIELDS[field] == PUB[key], field
    for key in ("layer_types", "inner_width", "head_dim",
                "lists_of_multipliers", "logit_scale", "folded_multipliers",
                "toy_fields"):
        assert FILE["assumed"][key], key


def test_the_cut_is_held_to_the_manifests_floors():
    c = _named(manifest.read(REPO)["configs"], CONFIG)
    manifest.config(REPO, c)
    said = FILE["cuts"]["num_hidden_layers"]
    assert (said["kind"], said["published"], said["here"],
            said["leading_dense"], said["period"]) == ("depth", 72, 4, 0, 1)
    assert 72 % 4 == 0 and "18 stages" in said["deployment"]
    manifest.cut("depth", said, 72, 4)
    with pytest.raises(manifest.Refused, match="four at least"):
        manifest.cut("depth", {**said, "here": 3}, 72, 3)
    # depth is the one key that may fall; every width is refused by name
    assert not manifest.WIDTH.search("num_hidden_layers")
    for key in ("hidden_size", "intermediate_size", "head_dim",
                "mamba_d_state", "mamba_d_head", "mamba_d_ssm"):
        assert manifest.WIDTH.search(key), key


def test_the_bytes_the_cell_was_sized_by():
    """8.79 GB of bf16 weights, 1.11 GB of state, 0.81 GB of pool: the
    arithmetic of the cell's ``sizing``, from ``fields`` and the
    engine's options."""
    from benchmark import weights_falcon_h1
    shapes = weights_falcon_h1.shapes(FIELDS)
    count = {stack: sum(_prod(s) for s, _ in leaves.values())
             for stack, leaves in shapes.items()}
    assert count["hybrid_layers"] == 4 * (31_457_280 + 68_351_072 + 5_120)
    assert count["layers"] == 4 * (330_301_440 + 5_120)
    assert count["top"] == 2 * 1_336_934_400 + 5_120
    assert (count["hybrid_layers"] + count["layers"]) // 4 == 430_120_032
    assert round(2 * sum(count.values()) / 1e9, 2) == 8.79
    sm = WORKLOAD["engine"]["state_manager"]
    rows, reach = MIX["rows"], MIX["prompt_len"] + MIX["new_tokens"]
    assert sm["max_tracked_sequences"] == rows == 64
    assert sm["max_seq_len"] == reach == 1536
    assert sm["num_blocks"] == rows * reach // sm["block_size"] + rows + 1 \
        == 6209
    slot = 4 * (32 * 128 * 256 + 3 * 5120)
    assert slot == 4_255_744
    assert round((rows + 1) * 4 * slot / 1e9, 2) == 1.11
    assert round(sm["num_blocks"] * 4 * 2 * 16 * 4 * 128 * 2 / 1e9, 2) == 0.81
    assert sm["max_ragged_batch_size"] * 4 == rows * MIX["prompt_len"]
    for name in ("logit_err", "token_gap", "state_err"):
        limit = WORKLOAD["limits"][name]
        assert 0 < limit["limit"] < 1 and "PR 62" in limit["from"]
        assert 0 < WORKLOAD["rehearse"]["limits"][name]["limit"] < 1
    assert WORKLOAD["control"] == {"engine": {"state_dtype": "bfloat16"}}
    assert MIX["runner"] == "generate_ssm"


def _prod(shape):
    out = 1
    for n in shape:
        out *= n
    return out


# ---------------------------------------------------------------------------
# the arithmetic, by hand
# ---------------------------------------------------------------------------
def test_the_recurrences_floors_at_a_toy_and_at_the_published_size():
    a = arith_falcon_h1
    toy = dict(layer_types=["mamba_attention", "mamba", "mamba_attention"],
               mamba_n_heads=4, mamba_d_head=8, mamba_d_state=16,
               mamba_n_groups=2)
    assert a.hybrid_layers(toy) == 2 and a.inner(toy) == 32
    assert a.bc_values(toy) == 2 * 2 * 16 and a.state_values(toy) == 32 * 16
    assert a.state_row_bytes(toy) == 2 * 512 * 4
    assert a.state_row_flops(toy) == 5 * 512
    peaks = {"hbm_bytes_per_s": 1e3, "bf16_flops_per_s": 1e9}
    # 2 layers x 3 steps x 5 rows x 4,096 B over 1,000 B/s
    assert a.state_least_seconds(toy, 5, 3, peaks) == pytest.approx(
        2 * 3 * 5 * 4096 / 1e3)
    # a token: x and y 32 each and 64 of B and C at 2 B, 4 dt at 4 B;
    # the state once out (one launch), three times in two
    assert a.scan_row_bytes(toy, 10) == 10 * (128 * 2 + 16) + 512 * 4
    assert a.scan_row_bytes(toy, 10, chunks=2) \
        == 10 * (128 * 2 + 16) + 3 * 512 * 4
    # published: a slot's [32, 256, 128] float32, in and out
    assert a.hybrid_layers(FIELDS) == 4 and a.inner(FIELDS) == 4096
    assert a.state_values(FIELDS) == 32 * 256 * 128 == 1_048_576
    assert a.state_row_bytes(FIELDS) == 8_388_608
    # a call's 511 decode steps of 64 rows: 1.097 TB, 1.34 s at 819 GB/s
    # (bytes bound: 5.2e6 operations a row are 27 ns at the peak)
    least = a.state_least_seconds(FIELDS, 64, 511, PEAKS)
    assert least == pytest.approx(4 * 511 * 64 * 8_388_608 / 819e9)
    assert round(least, 2) == 1.34
    # the prompt: 1,024 tokens a row in 4 launches: 18.9 MB of tokens,
    # 29.4 MB of state; the recurrence's own operations are LESS than
    # that at the peak (27 us against 59), so the floor is the bytes'
    row = a.scan_row_bytes(FIELDS, 1024, chunks=4)
    assert row == 1024 * ((2 * 4096 + 1024) * 2 + 32 * 4) + 7 * 4_194_304
    assert a.scan_least_seconds(FIELDS, 64, 1024, PEAKS, chunks=4) \
        == pytest.approx(4 * 64 * row / 819e9)


def test_the_path_reader_sums_under_a_wrapping_scope(monkeypatch):
    """``serve_path_scope_time`` on made-up rows: the time under the
    wrapping scope whatever the innermost word, by program; None where
    the program has no such scope."""
    at = "jit(x)/layers/while/body/hybrid_mixer/"
    rows = [("ragged_step", "a", at + "ssm_mixer/ssm_scan/custom", 0.5),
            ("ragged_step", "b", at + "attention/out_proj/dot", 0.25),
            ("ragged_step", "c", "jit(x)/layers/mlp/dense_mlp/dot", 4.0),
            ("ragged_step", "d", "jit(x)/my_hybrid_mixer_kernel/mul", 8.0),
            ("decode_window_greedy", "e", at + "attention/hybrid_join/add",
             0.125),
            ("decode_window_greedy", "f", None, 16.0)]
    monkeypatch.setattr(serve_path_scope_time, "call_rows",
                        lambda ev: (rows, 1))
    ev = SimpleNamespace(slice_steps=1)
    read = serve_path_scope_time.read
    assert read(ev, {"programs": ["ragged_step"],
                     "within": ["hybrid_mixer"]}) == 750.0
    assert read(ev, {"programs": ["decode"],
                     "within": ["hybrid_mixer"]}) == 125.0
    assert read(ev, {"programs": ["ragged_step"],
                     "within": ["hybrid_join"]}) is None
    assert read(ev, {"programs": ["prefill"],
                     "within": ["hybrid_mixer"]}) is None
    monkeypatch.setattr(serve_path_scope_time, "call_rows", lambda ev: None)
    assert read(ev, {"programs": ["decode"],
                     "within": ["hybrid_mixer"]}) is None


# ---------------------------------------------------------------------------
# the rehearsal
# ---------------------------------------------------------------------------
def test_the_cell_rehearses_with_its_readers():
    p = run_py(["--workload", CELL, "--rehearse", "--trace", "1",
                "--seed", str(2 ** 31 + 6200000001)])
    assert_rehearsed(p)
    assert "pallas:pipelined" in p.stderr
    for name in ("logit_err", "token_gap", "state_err"):
        assert f"compared: {name}" in p.stderr
    assert "calls finished 0" not in p.stderr
    ran = p.stderr.split("readers ran")[1]
    for name in ("state_gb.gen", "kv_gb.gen", "peak_hbm.gen",
                 "compiles.gen"):
        assert name in ran, name
    # the reference is found through the configuration, a file
    assert FILE["reference"] == "reference_falcon_h1" \
        and FILE["weights"] == "weights_falcon_h1"
    assert TOY["mamba_d_state"] == 32
