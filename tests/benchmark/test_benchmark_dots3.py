"""The configuration ``dots3-note-prev`` and its cell
``dots3-note-prev.rollout-8x32768-256``: what ``BENCHMARK.json`` and the
files say of them (entries found BY NAME, never by position and never as
a whole list: a later PR appends; "at least these"), the configuration's
file against what the source publishes, the cut held to the manifest's
floors, the bytes the cell was sized by reckoned from ``fields``, the new
metrics' files mirrored and their readers on made-up rows,
``arith_sparse.py`` by hand, the control on the toy, and the cell's
rehearsal with its readers.

Toy readings on the sandbox's CPU (no chip result), float32 engine, seed
2**31 + 6800000001: ``logit_err`` 2.9e-7, ``token_gap`` 0, ``pool_err``
2.5e-7, ``select_miss`` 0; under the cell's control (``index_topk`` halved
on the program alone) ``select_miss`` reads 0.5. The chip's limits and
the readings they lie between: the cell's file and PERF.md section 4."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmark import arith_sparse, manifest
from benchmark.run import merge, reported_by

from test_benchmark_run import assert_rehearsed, run_py

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmark"
CONFIG = "dots3-note-prev"
TRAFFIC = "rollout-8x32768-256"
CELL = f"{CONFIG}.{TRAFFIC}"
FILE = json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())
FIELDS, PUB = FILE["fields"], FILE["published"]
TOY = merge(FIELDS, FILE["toy_fields"])
WORKLOAD = json.loads((BENCH / "workloads" / f"{CELL}.json").read_text())
MIX = json.loads((BENCH / "traffic" / f"{TRAFFIC}.json").read_text())
LAYER = "indexed latent attention"
# the accepted lists the cell joins AT LEAST; every accepted scope reader
# joins the slice's events to their launches again, so what the cell lists
# beyond these is decided by the driver's 360 s and pinned by nothing here
JOINED = ["compiles.gen", "idle.gen", "peak_hbm.gen", "host_ms.gen",
          "gc_pause_ms.gen", "prefill_ms.gen", "decode_ms.gen",
          "scope_coverage.gen", "experts_share.gen", "experts_touched.gen",
          "kv_gb.gen"]
# new metric -> (unit, better, source, reader)
NEW = {
    "indexer_ms.gen": ("ms", "lower", "device_trace",
                       "serve_path_scope_time"),
    "prefill_indexer_ms.gen": ("ms", "lower", "device_trace",
                               "serve_path_scope_time"),
    "index_select_ms.gen": ("ms", "lower", "device_trace",
                            "serve_path_scope_time"),
    "prefill_index_select_ms.gen": ("ms", "lower", "device_trace",
                                    "serve_path_scope_time"),
    "selected_read_roofline.gen": ("%", "higher", "device_trace",
                                   "sparse_roofline"),
    "indexer_roofline.gen": ("%", "higher", "device_trace",
                             "sparse_roofline"),
    "ring_read_roofline.gen": ("%", "higher", "device_trace",
                               "sparse_roofline"),
    "index_positions_read.gen": ("count", "lower", "program_counter",
                                 "registry_ratio"),
}
# another block's: the dense latent kernel's arithmetic (every layer one
# kind, every position read), per-head pools, state-keeping layers
OTHERS = ["latent_roofline.gen", "latent_pattern_roofline.gen",
          "ragged_roofline.gen", "window_roofline.gen", "linear_ms.gen",
          "ssm_ms.gen", "retention_ms.gen", "conv_mixer_ms.gen",
          "state_gb.gen"]


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
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert c["source"] == FILE["source"] == "https://huggingface.co/" \
        "dots-studio/dots3-note-prev/blob/main/config.json"
    assert c["file"] == f"benchmark/configs/{CONFIG}.json"
    w = _named(bm["workloads"], CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, TRAFFIC, 1)
    assert w["why"] == WORKLOAD["why"] and "0.125 rows a decode step" in w["why"]
    assert set(reported_by(bm, CELL, "end_to_end")) >= {"setup_s",
                                                        "gen_tok_s"}
    assert CELL in _named(bm["end_to_end"], "gen_tok_s")["workloads"]
    assert MIX["runner"] == "generate_sparse"
    assert (BENCH / "runners" / "generate_sparse.py").is_file()


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
    unit, better, source, reader = NEW[name]
    m = _named(manifest.read(REPO)["per_layer"], name)
    spec = json.loads((BENCH / "layer_metrics" / f"{name}.json").read_text())
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) \
        == (unit, better, source, LAYER, "gen_tok_s")
    for key in ("unit", "better", "source", "layer", "moves"):
        assert spec[key] == m[key]
    assert spec["reader"] == reader
    assert (BENCH / "readers" / f"{reader}.py").is_file()
    assert CELL in m["workloads"]
    assert LAYER in (REPO / "PERF.md").read_text()
    if reader == "serve_path_scope_time":
        assert spec["params"]["programs"] == [
            "ragged_step" if name.startswith("prefill") else "decode"]
        assert spec["params"]["within"] == [
            "index_select" if "select" in name else "indexer"]


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------
ROWS = [
    ("ragged_step", "a", "jit(x)/layers/mla_attention/indexer/dot", 0.5),
    ("ragged_step", "b", "jit(x)/layers/mla_attention/while/body/indexer/"
     "while/body/dot_general", 0.25),
    ("ragged_step", "c", "jit(x)/layers/mla_attention/while/body/"
     "index_select/top_k", 2.0),
    ("ragged_step", "d", "jit(x)/layers/mla_attention/while/body/"
     "attn_kernel/gather", 4.0),
    ("ragged_step", "e", "jit(x)/layers/mla_window_attention/attn_kernel/"
     "ragged_attention_latent_window", 8.0),
    ("decode_window_greedy", "f", "jit(x)/layers/mla_attention/while/body/"
     "indexer/dot_general", 0.125),
    ("decode_window_greedy", "g", "jit(x)/layers/mla_attention/while/body/"
     "index_select/top_k", 0.0625),
    ("decode_window_greedy", "h", "jit(x)/layers/mlp/dot", 16.0)]


def _spec(name):
    return json.loads((BENCH / "layer_metrics" / f"{name}.json").read_text())


def test_the_time_metrics_read_the_programs_scopes(monkeypatch):
    """The accepted path reader on made-up rows under this block's
    scopes, by program; None for a program without them (the parent)."""
    from benchmark.readers import serve_path_scope_time as reader
    rows = list(ROWS)
    monkeypatch.setattr(reader, "call_rows", lambda ev: (rows, 1))
    ev = SimpleNamespace(slice_steps=1)

    def read(name):
        return reader.read(ev, _spec(name)["params"])
    assert read("prefill_indexer_ms.gen") == 750.0
    assert read("indexer_ms.gen") == 125.0
    assert read("prefill_index_select_ms.gen") == 2000.0
    assert read("index_select_ms.gen") == 62.5
    rows[:] = [r for r in rows if "index" not in r[2]]
    assert read("indexer_ms.gen") is None
    assert read("prefill_index_select_ms.gen") is None


def test_the_rooflines_read_the_models_work_over_the_scopes_time(
        monkeypatch):
    """``sparse_roofline`` on made-up rows: the two latent kinds'
    ``attn_kernel`` are told apart by the scope round them, the floor is
    ``arith_sparse``'s, and a program without the scopes (the parent)
    or a configuration without an indexer reads nothing."""
    from benchmark import arith
    from benchmark.readers import sparse_roofline as reader
    rows = list(ROWS)
    monkeypatch.setattr(reader, "call_rows", lambda ev: (rows, 1))
    monkeypatch.setattr(arith, "peaks", lambda kind: {
        "bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e9})
    launch = [(16, 48), (16, 48)]
    ev = SimpleNamespace(
        events=[object()], slice_steps=1, launch_rows=launch,
        ctx=SimpleNamespace(fields=TOY, traffic={"rows": 2},
                            devices=[SimpleNamespace(device_kind="toy")]))
    peaks = arith.peaks("toy")
    for name, seconds in (("selected_read_roofline.gen", 4.0),
                          ("ring_read_roofline.gen", 8.0),
                          ("indexer_roofline.gen", 0.875)):
        params = _spec(name)["params"]
        want = 100.0 * arith_sparse.least_seconds(
            TOY, launch, 2, peaks, params["work"]) / seconds
        assert reader.read(ev, params) == pytest.approx(want)
        assert 0 < want < 100
    assert reader.seconds_within_all(
        ev, ["mla_window_attention", "attn_kernel"]) == 8.0
    rows[:] = [r for r in rows if "attn_kernel" not in r[2]]
    assert reader.read(ev, _spec("ring_read_roofline.gen")["params"]) is None
    ev.ctx.fields = {**TOY, "index_topk": 0}
    assert reader.read(ev, _spec("indexer_roofline.gen")["params"]) is None


def test_the_positions_read_a_query_is_a_ratio_of_the_programs_counters():
    from benchmark.readers import registry_ratio
    from deepspeed_tpu.telemetry import get_registry
    params = _spec("index_positions_read.gen")["params"]
    assert params == {"over": "inference_index_positions_read_total",
                      "per": "inference_index_queries_total"}
    reg = get_registry()
    over = reg.counter("test_dots3_over_total", "x", labelnames=("program",))
    per = reg.counter("test_dots3_per_total", "x", labelnames=("program",))
    mine = {"over": "test_dots3_over_total", "per": "test_dots3_per_total"}
    assert registry_ratio.read(None, mine) is None      # nothing counted
    over.labels(program="a").inc(30)
    over.labels(program="b").inc(10)
    per.labels(program="a").inc(16)
    per.labels(program="b").inc(4)
    assert registry_ratio.read(None, mine) == 2.0
    assert registry_ratio.read(None, {"over": "no_such", "per": "x"}) is None


# ---------------------------------------------------------------------------
# the arithmetic, by hand
# ---------------------------------------------------------------------------
def test_the_counts_by_hand():
    a = arith_sparse
    # 4 queries with bounds 5, 6, 7, 8 under a cap of 6: 5 + 6 + 6 + 6
    assert a.capped_positions(4, 8, 6) == 23
    assert a.capped_positions(4, 8, 100) == 5 + 6 + 7 + 8
    assert a.capped_positions(4, 8, 2) == 8
    assert a.capped_positions(1, 33000, 2048) == 2048
    # the indexer scores the bounds over topk alone: 7 + 8 of 5..8 at 6
    assert a.scored_positions(4, 8, 6) == 15
    assert a.scored_positions(4, 8, 8) == 0
    assert a.scored_positions(4, 8, 2) == 5 + 6 + 7 + 8
    by_loop = sum(min(b, 2048) for b in range(32768 - 1024 + 1, 32768 + 1))
    assert a.capped_positions(1024, 32768, 2048) == by_loop == 1024 * 2048
    assert a.scored_positions(1024, 3072, 2048) == sum(range(2049, 3073))
    f = FIELDS
    assert a.sizes(f, "full") == (2, 128, 512, 128, 64, 128)
    assert a.sizes(f, "window") == (3, 64, 1024, 192, 64, 128)
    one = [(1, 33000)] * 8                  # a decode step of the cell
    # a full layer: 2,048 positions x 128 heads x (192 + 128) x 2
    assert a.read_flops(f, one, "full") == 8 * 2048 * 128 * 320 * 2
    assert a.read_bytes(f, one, "full") == 8 * (2048 * 576 * 2
                                                + 128 * 320 * 2)
    # a window layer: 513 positions x 64 heads x (256 + 128) x 2
    assert a.read_flops(f, one, "window") == 8 * 513 * 64 * 384 * 2
    assert a.read_bytes(f, one, "window") == 8 * (513 * 1088 * 2
                                                  + 64 * 384 * 2)
    assert a.indexer_flops(f, one) == 8 * 33000 * 64 * 128 * 2
    assert a.indexer_bytes(f, one) == 8 * (33000 * 128 * 2 + 64 * 129 * 2)
    assert a.indexer_bytes(f, [(1, 2048)]) == 0 == a.indexer_flops(
        f, [(1, 2048)])
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # a decode step is bound by bytes, the prompt by operations
    step = a.least_seconds(f, one, 8, peaks, "selected_read")
    assert step == pytest.approx(
        2 * a.read_bytes(f, one, "full") / 819e9)
    prompt = [(32768, 32768)] * 8
    assert a.least_seconds(f, prompt, 8, peaks, "selected_read") \
        == pytest.approx(2 * a.read_flops(f, prompt, "full") / 197e12)
    # what the issue reckoned at the chip's peaks: indexer ~0.7 s,
    # selected read ~0.7 s in the cheaper form, the ring ~0.1 s
    assert 0.6 < a.least_seconds(f, prompt, 8, peaks, "indexer") < 0.8
    assert 0.4 < a.least_seconds(f, prompt, 8, peaks, "selected_read") < 0.8
    assert 0.05 < a.least_seconds(f, prompt, 8, peaks, "ring_read") < 0.2


# ---------------------------------------------------------------------------
# the configuration against its source, and the cut
# ---------------------------------------------------------------------------
def test_published_widths_and_the_cut():
    assert (PUB["hidden_size"], PUB["num_attention_heads"],
            PUB["q_lora_rank"], PUB["kv_lora_rank"],
            PUB["qk_nope_head_dim"], PUB["qk_rope_head_dim"],
            PUB["v_head_dim"], PUB["intermediate_size"],
            PUB["moe_intermediate_size"]) \
        == (5120, 128, 1024, 512, 128, 64, 128, 13824, 1536)
    assert (PUB["swa_num_attention_heads"], PUB["swa_q_lora_rank"],
            PUB["swa_kv_lora_rank"], PUB["swa_qk_nope_head_dim"],
            PUB["swa_qk_rope_head_dim"], PUB["swa_v_head_dim"],
            PUB["swa_rope_theta"], PUB["sliding_window_size"]) \
        == (64, 1024, 1024, 192, 64, 128, 50000, 513)
    assert (PUB["index_n_heads"], PUB["index_head_dim"],
            PUB["index_topk"]) == (64, 128, 2048)
    assert (PUB["n_routed_experts"], PUB["num_experts_per_tok"],
            PUB["first_k_dense_replace"], PUB["vocab_size"],
            PUB["num_hidden_layers"], PUB["rope_theta"]) \
        == (256, 8, 1, 152064, 46, 80000000)
    types = PUB["layer_types"]
    assert len(types) == 46 and types.count("full_attention") == 13
    assert [i for i, t in enumerate(types) if t == "full_attention"] \
        == [0] + list(range(1, 46, 4))
    # every key of the source stands unchanged at the top level, but the cuts
    here = {"num_hidden_layers": 5, "n_routed_experts": 32,
            "vocab_size": 19008}
    for key, value in PUB.items():
        assert FILE[key] == here.get(key, value), key
    assert FIELDS["layer_types"] == types[:5] and FIELDS["num_layers"] == 5
    for field, key in FILE["published_as"].items():
        if key not in here:
            assert FIELDS[field] == PUB[key], field
    # no new field where one existed
    assert (FIELDS["attn_gate"], FIELDS["attn_window"],
            FIELDS["moe_experts_held"], FIELDS["moe_experts_first"],
            FIELDS["moe_num_experts"], FIELDS["moe_selection_bias"],
            FIELDS["rope_interleave"], FIELDS["mla_lora_rescale"]) \
        == ("head", 513, 32, 0, 256, True, True, True)
    for key in ("mla_lora_rescale", "attn_gate", "window", "rope_pairing",
                "indexer_norm", "selection", "sizes"):
        assert FILE["assumed"][key], key
    assert set(FILE["left_out"]) == {"vision_and_audio_towers", "mtp"}
    for said in FILE["left_out"].values():
        assert said["left_out"] and said["why"]
    # the toy keeps what is new: two kinds, other heads and ranks, a
    # share that does not start at 0, contexts past topk and the window
    assert (TOY["num_heads"], TOY["swa_num_heads"], TOY["kv_lora_rank"],
            TOY["swa_kv_lora_rank"], TOY["index_topk"], TOY["attn_window"],
            TOY["moe_experts_held"], TOY["moe_experts_first"]) \
        == (4, 2, 32, 48, 32, 17, 4, 4)


def test_the_cut_is_held_to_the_manifests_floors():
    c = _named(manifest.read(REPO)["configs"], CONFIG)
    manifest.config(REPO, c)
    depth = FILE["cuts"]["num_hidden_layers"]
    assert (depth["kind"], depth["published"], depth["here"],
            depth["leading_dense"], depth["period"]) \
        == ("depth", 46, 5, 1, 4)
    assert "v5e-64" in depth["deployment"]
    with pytest.raises(manifest.Refused, match="four at least"):
        manifest.cut("depth", {**depth, "here": 4}, 46, 4)
    experts = FILE["cuts"]["n_routed_experts"]
    assert (experts["here"], experts["shared_over_chips"]) == (32, 8)
    with pytest.raises(manifest.Refused, match="are not the source's"):
        manifest.cut("experts", {**experts, "shared_over_chips": 4}, 256, 32)
    vocab = FILE["cuts"]["vocab_size"]
    assert vocab["here"] * 8 == vocab["published"] == 152064
    for key in ("hidden_size", "kv_lora_rank", "swa_kv_lora_rank",
                "index_head_dim", "qk_nope_head_dim", "swa_v_head_dim",
                "sliding_window_size", "num_experts_per_tok"):
        assert manifest.WIDTH.search(key), key
    for key in FILE["reduced"]:
        assert not manifest.WIDTH.search(key), key


def test_the_bytes_the_cell_was_sized_by():
    """8.17 GB of bf16 weights, 0.81 GB of latent rows and index keys,
    the ring: the arithmetic of the cell's ``sizing``, from ``fields``
    and the engine's options."""
    from benchmark import weights_dots3
    shapes = weights_dots3.shapes(FIELDS)
    total = sum(_prod(s) for leaves in shapes.values()
                for s, _, _ in leaves.values())
    assert total == 4_087_154_176 and round(total * 2 / 1e9, 2) == 8.17
    sm = WORKLOAD["engine"]["state_manager"]
    assert sm["num_blocks"] == 8 * (33024 // 16) + 8 + 1
    assert sm["max_seq_len"] == 32768 + MIX["new_tokens"]     # as asked
    assert sm["max_ragged_batch_size"] // sm["max_tracked_sequences"] == 1024
    pool = 2 * sm["num_blocks"] * 16 * (640 + 128) * 2
    assert round(pool / 1e9, 2) == 0.81
    ring_blocks = (528 + 1024 + 16) // 16
    ring = 3 * (8 * ring_blocks + 1) * 16 * 1152 * 2
    assert ring_blocks == 98 and round(ring / 1e9, 3) == 0.087
    # the rule's last branch: 4 rows of 16,384 in 16 half-full steps
    assert (MIX["rows"], MIX["prompt_len"], MIX["new_tokens"]) \
        == (4, 16384, 256) and "TAKEN: (2) and (3)" in MIX["rule"]["branch"]
    assert MIX["rows"] * MIX["prompt_len"] \
        == 16 * sm["max_ragged_batch_size"] // 2


def _prod(shape):
    out = 1
    for n in shape:
        out *= n
    return out


# ---------------------------------------------------------------------------
# the rehearsal and the control
# ---------------------------------------------------------------------------
def test_the_control_departs_through_a_field_the_program_has():
    from benchmark.runners import generate_sparse
    over = WORKLOAD["control"]["program_fields"]
    assert over == {"index_topk": FIELDS["index_topk"] // 2}
    assert generate_sparse.judged_layers(FIELDS) == 2
    said = []
    ctx = SimpleNamespace(cell={"program_fields": over}, fields=FIELDS,
                          log=said.append)
    generate_sparse.lay_program_fields(ctx)
    assert ctx.model_config().index_topk == 1024
    assert ctx.fields["index_topk"] == 2048 and "1024" in said[0]
    with pytest.raises(SystemExit, match="full latent layers ahead"):
        generate_sparse.judged_layers({**FIELDS, "layer_types": [
            "sliding_attention"] * 5})
    for name in ("logit_err", "token_gap", "pool_err", "select_miss"):
        assert WORKLOAD["limits"][name]["limit"] > 0
        assert WORKLOAD["rehearse"]["limits"][name]["limit"] > 0


def test_the_control_fails_the_selections_number():
    """The cell's control on the toy (``index_topk`` halved, laid on the
    program alone): NOT correct through the runner's own comparison. In
    layer 0, whose input no attention has touched, the program read
    exactly the better half of the reference's set (``select_miss`` 0.5)
    and its rows and keys are a sound run's; layer 1 stands behind layer
    0's attention and its pools moved with it (the rehearsal below is
    the sound run)."""
    import jax
    from benchmark import control
    from benchmark import run as harness
    result = control.run_once(CELL, 2 ** 31 + 6800000002, 1.0, True,
                              jax.devices(), harness.CompileClock(),
                              rehearse=True)
    compared = result.correct_detail["compared"]
    assert result.correct is False
    for name in ("select_miss", "pool_err", "logit_err"):
        assert compared[name]["value"] > compared[name]["limit"], name
    parts = result.correct_detail["sparse_by_what_row_layer"]
    pool_limit = compared["pool_err"]["limit"]
    for key, value in parts.items():
        what, _, layer = key.split(".")
        if what == "miss":
            assert value == pytest.approx(0.5) if layer == "0" \
                else value >= 0.5, key
        else:
            assert (value > pool_limit) == (layer == "1"), (key, value)


def test_the_cell_rehearses_with_its_readers():
    p = run_py(["--workload", CELL, "--rehearse", "--trace", "1",
                "--seed", str(2 ** 31 + 6800000001)])
    assert_rehearsed(p)
    assert "pallas:latent+window+indexed" in p.stderr
    for name in ("logit_err", "token_gap", "pool_err", "select_miss"):
        assert f"compared: {name}" in p.stderr
    assert "calls finished 0" not in p.stderr
    ran = p.stderr.split("readers ran")[1]
    for name in ("kv_gb.gen", "peak_hbm.gen", "compiles.gen",
                 "experts_touched.gen", "index_positions_read.gen"):
        assert name in ran, name
    # the reference is found through the configuration, a file
    assert FILE["reference"] == "reference_dots3" \
        and FILE["weights"] == "weights_dots3"
