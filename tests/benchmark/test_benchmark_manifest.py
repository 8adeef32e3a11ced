"""BENCHMARK.json and the files under benchmark/ say the same thing, in
the characters and lengths the contract allows, every per-layer metric
moves an end-to-end metric that its cells report, and which metrics a
cell reports is said in one place."""

import importlib
import json
import re
from pathlib import Path

import pytest

from benchmark.run import reported_by

ROOT = Path("benchmark")
BENCH = json.loads(Path("BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(kind, name):
    return json.loads((ROOT / kind / f"{name}.json").read_text())


def names(kind):
    return sorted(p.name[:-len(".json")]
                  for p in (ROOT / kind).glob("*.json"))


CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmark", "tests/benchmark"]
    assert BENCH["command"] == ["python3", "benchmark/run.py"]
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(Path("BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert 1 <= len(BENCH["workloads"]) <= 24
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    setup = [m for m in BENCH["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and setup[0]["bound"] <= 0.1
    assert all(0.01 <= m["bound"] <= 0.1 for m in BENCH["end_to_end"])


@pytest.mark.parametrize("entry", BENCH["configs"] + BENCH["workloads"]
                         + METRICS, ids=lambda e: e["name"])
def test_names_units_and_lines(entry):
    assert NAME.match(entry["name"]), entry["name"]
    for key in ("config", "traffic", "moves"):
        if key in entry:
            assert NAME.match(entry[key]), entry[key]
    if "unit" in entry:
        assert UNIT.match(entry["unit"]), entry["unit"]
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in SOURCES
    for key in ("why", "layer", "source"):
        if key in entry:
            v = entry[key]
            assert 1 <= len(v) <= 200 and "\n" not in v and "\t" not in v
    allowed = {"name", "source", "file", "reduced", "why"} \
        if "file" in entry else \
        {"name", "config", "traffic", "chips", "why"} \
        if "traffic" in entry else \
        {"name", "unit", "better", "bound", "source", "workloads"} \
        if "bound" in entry else \
        {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert set(entry) <= allowed, set(entry) - allowed


def test_no_two_entries_share_a_name():
    for group in (BENCH["configs"], BENCH["workloads"], METRICS):
        ns = [e["name"] for e in group]
        assert len(ns) == len(set(ns))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_file_under_benchmark_is_named_and_loads():
    for p in ROOT.rglob("*"):
        if "__pycache__" in p.parts or p.suffix == ".pyc":
            continue
        assert re.match(r"^[A-Za-z0-9_.\-/]+$", str(p)), p
        if p.suffix == ".json":
            json.loads(p.read_text())
    for mod in sorted((ROOT / "readers").glob("*.py")) + \
            sorted((ROOT / "runners").glob("*.py")):
        if mod.stem != "__init__":
            importlib.import_module(
                f"benchmark.{mod.parent.name}.{mod.stem}")
    for mod in ("arith", "tracing", "reference", "evidence", "run"):
        importlib.import_module(f"benchmark.{mod}")


def test_configs_mirror_their_files():
    assert {c["name"] for c in BENCH["configs"]} == set(names("configs"))
    assert {c["name"] for c in BENCH["configs"]} == \
        {w["config"] for w in BENCH["workloads"]}
    from deepspeed_tpu.models.transformer import TransformerConfig
    for c in BENCH["configs"]:
        f = json.loads(Path(c["file"]).read_text())
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        assert f["source"] == c["source"] and f["reduced"] == c["reduced"]
        cfg = TransformerConfig(**f["fields"])      # a file, no code
        pub = f["published"]
        # no width differs from the published config
        assert cfg.hidden_size == pub["hidden_size"]
        assert cfg.intermediate_size == pub["ffn_dim"]
        assert cfg.num_heads == pub["num_attention_heads"]
        assert cfg.num_layers == pub["num_hidden_layers"]
        assert cfg.vocab_size == pub["vocab_size"]
        assert cfg.max_seq_len == pub["max_position_embeddings"]


def test_cells_mirror_their_files_and_the_reverse():
    assert CELLS == sorted(CELLS, key=CELLS.index)
    assert set(CELLS) == set(names("workloads"))
    for w in BENCH["workloads"]:
        f = load("workloads", w["name"])
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        for key in ("config", "traffic", "chips", "why"):
            assert f[key] == w[key], (w["name"], key)
        traffic = load("traffic", f["traffic"])
        runner = ROOT / "runners" / f"{traffic['runner']}.py"
        assert runner.is_file(), runner
        e2e = reported_by(BENCH, w["name"], "end_to_end")
        assert "setup_s" in e2e and len(e2e) >= 2
        assert reported_by(BENCH, w["name"], "per_layer"), \
            "a cell reports at least one layer metric"
    assert {w["traffic"] for w in BENCH["workloads"]} == \
        set(names("traffic"))


def reported_in(metric):
    return metric.get("workloads", CELLS)


def test_which_metrics_a_cell_reports_is_said_once():
    """``BENCHMARK.json`` says it (``run.reported_by``), the driver reads
    it there, and a cell file that said it again could disagree."""
    for cell in CELLS:
        assert not {"end_to_end", "per_layer"} & set(load("workloads", cell))
        for kind in ("end_to_end", "per_layer"):
            assert reported_by(BENCH, cell, kind) == [
                m["name"] for m in BENCH[kind] if cell in reported_in(m)]
    assert all("workloads" in m for m in BENCH["per_layer"])


def test_a_per_layer_metric_names_its_cells_and_they_report_its_moves():
    bench = {
        "end_to_end": [
            {"name": "train_tok_s", "workloads": ["a.train"]},
            {"name": "ttft_p95_ms", "workloads": ["a.serve"]},
            {"name": "setup_s"}],
        "per_layer": [
            {"name": "mfu.train", "moves": "train_tok_s",
             "workloads": ["a.train"]},
            {"name": "prefix_hits", "moves": "ttft_p95_ms",
             "workloads": ["a.serve"]}]}
    assert reported_by(bench, "a.train", "end_to_end") == \
        ["train_tok_s", "setup_s"]
    assert reported_by(bench, "a.train", "per_layer") == ["mfu.train"]
    assert reported_by(bench, "a.serve", "per_layer") == ["prefix_hits"]
    assert reported_by(bench, "b.new", "end_to_end") == ["setup_s"]
    assert reported_by(bench, "b.new", "per_layer") == []
    # a cell listed under a metric whose ``moves`` it does not report is
    # an error, and so is a per-layer metric that names no cells
    bench["per_layer"].append({"name": "wrong", "moves": "ttft_p95_ms",
                               "workloads": ["a.train"]})
    with pytest.raises(SystemExit, match="wrong, which moves ttft_p95_ms"):
        reported_by(bench, "a.train", "per_layer")
    bench["per_layer"][-1] = {"name": "everywhere", "moves": "train_tok_s"}
    with pytest.raises(KeyError):
        reported_by(bench, "a.train", "per_layer")


def test_metrics_mirror_their_spec_files():
    assert {m["name"] for m in BENCH["per_layer"]} == \
        set(names("layer_metrics"))
    for m in BENCH["per_layer"]:
        f = load("layer_metrics", m["name"])
        for key in ("unit", "better", "source", "layer", "moves"):
            assert f[key] == m[key], (m["name"], key)
        assert (ROOT / "readers" / f"{f['reader']}.py").is_file()
    assert all(m["source"] == "host_clock" for m in BENCH["end_to_end"])


def test_every_moves_is_reported_wherever_the_metric_is():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s", m["name"]
        for cell in reported_in(m):
            assert cell in reported_in(e2e[m["moves"]]), (m["name"], cell)


def test_one_layer_one_spelling_and_perf_md_lists_it():
    layers = {m["layer"] for m in BENCH["per_layer"]}
    perf = Path("PERF.md").read_text()
    for layer in layers:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"
    for m in BENCH["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
