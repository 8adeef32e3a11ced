"""BENCHMARK.json and the files under benchmark/ say the same thing, in
the characters and lengths the contract allows, every per-layer metric
moves an end-to-end metric that its cells report, which metrics a cell
reports is said in one place, and a configuration is held to its source
by what it may cut. The rules are ``benchmark/manifest.py``'s, functions
of a root: here they are called on this repository, and in
``test_benchmark_run.py`` on a copy that a configuration was added to."""

import importlib
import json
import shutil
from pathlib import Path

import pytest

from benchmark import manifest
from benchmark.run import merge, reported_by

ROOT = Path(__file__).resolve().parents[2]
BENCH = manifest.read(ROOT)
FIXTURE = Path(__file__).resolve().parent / "fixtures" / "new_configuration"


def test_top_level_keys_and_limits():
    manifest.top_level(ROOT)


@pytest.mark.parametrize("kind,entry", manifest.entries(BENCH),
                         ids=lambda v: v["name"] if isinstance(v, dict)
                         else v)
def test_names_units_and_lines(kind, entry):
    manifest.entry(kind, entry)
    for bad in ({"note": "one more key"}, {"name": "two words"},
                {"name": "a/b"}, {"why": "two\nlines"}, {"why": ""}):
        with pytest.raises(manifest.Refused):
            manifest.entry(kind, dict(entry, **bad))


def test_no_two_entries_share_a_name():
    manifest.unique_names(ROOT)


def test_every_file_under_benchmark_is_named_and_loads():
    modules = manifest.files(ROOT)
    assert {"benchmark.run", "benchmark.manifest", "benchmark.control",
            "benchmark.runners.generate"} <= set(modules)
    for mod in modules:         # every one, whatever a later PR adds
        importlib.import_module(mod)


def test_configs_mirror_their_files():
    manifest.configs(ROOT)
    # the configurations that are there are not cut, name no module and
    # bring no toy widths: they take every default
    for c in BENCH["configs"]:
        f = manifest.load(ROOT, "configs", c["name"])
        assert c["reduced"] == [] and not {
            "cuts", "published_as", "reference", "weights",
            "toy_fields"} & set(f), c["name"]


def test_cells_mirror_their_files_and_the_reverse():
    manifest.cell_files(ROOT)


def test_which_metrics_a_cell_reports_is_said_once():
    manifest.reported_once(ROOT)


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
    manifest.metric_files(ROOT)


def test_every_moves_is_reported_wherever_the_metric_is():
    manifest.moves(ROOT)


def test_one_layer_one_spelling_and_perf_md_lists_it():
    manifest.layers(ROOT)


# ---------------------------------------------------------------------------
# what a configuration may cut from its source, and how far
# ---------------------------------------------------------------------------
DEPLOYED = "the layers left out lie on the pipeline's further stages"


def depth(here, lead=0, period=1, **more):
    return {"reduced": ["num_hidden_layers"], "fields": {"num_layers": here},
            "cuts": {"num_hidden_layers": {**dict(
                kind="depth", published=16, here=here, leading_dense=lead,
                period=period, deployment=DEPLOYED), **more}}}


def experts(here, chips, published=64):
    """The fixture with an expert layer: ``published`` routed experts,
    ``here`` of them held where ``chips`` chips share a layer."""
    return {"reduced": ["num_hidden_layers", "n_routed_experts"],
            "published": {"n_routed_experts": published},
            "published_as": {"moe_num_experts": "n_routed_experts"},
            "fields": {"moe_num_experts": here},
            "cuts": {"n_routed_experts": dict(
                kind="experts", published=published, here=here,
                shared_over_chips=chips, deployment=f"a layer's experts "
                f"over {chips} chips")}}


def vocabulary(here):
    return {"reduced": ["num_hidden_layers", "vocab_size"],
            "fields": {"vocab_size": here},
            "cuts": {"vocab_size": dict(
                kind="vocabulary", published=32000, here=here,
                deployment="the head and the embedding over eight chips")}}


def module(**said):
    """A published side module that is not loaded: no field of the
    program stands for it."""
    return {"reduced": ["num_hidden_layers", "num_nextn_predict_layers"],
            "published": {"num_nextn_predict_layers": 1},
            "cuts": {"num_nextn_predict_layers": dict(
                kind="module", published=1, here=0, deployment="a server "
                "that does not draft from the prediction head", **said)}}


LEFT_OUT = dict(left_out="the multi-token-prediction module",
                why="a fifth expert layer that no served token passes")
CUTS = {
    # what stands, as the fixture has it and as the guide's examples do
    "as_the_fixture_stands": ({}, None),
    "one_leading_dense_and_four": (depth(5, lead=1), None),
    "a_whole_period_of_six": (depth(7, lead=1, period=6), None),
    "eight_experts_of_64_over_8_chips": (experts(8, 8), None),
    "an_eighth_of_the_vocabulary": (vocabulary(4000), None),
    "a_side_module_left_out": (module(**LEFT_OUT), None),
    # what is refused
    "reduced_with_no_cuts_entry": ({"cuts": None}, "cuts explains"),
    "a_cuts_entry_not_in_reduced": (
        {"cuts": {"vocab_size": depth(4)["cuts"]["num_hidden_layers"]}},
        "cuts explains"),
    "a_kind_outside_the_four": (depth(4, kind="heads"), "a cut is one of"),
    "three_layers": (depth(3), "3 layers are under the 0 leading dense "
                     "and 4 that follow"),
    "four_layers_one_of_them_dense": (depth(4, lead=1), "are under"),
    "less_than_a_period": (depth(6, lead=1, period=6), "a whole period"),
    "depth_without_its_pattern": (depth(4, period=None),
                                  "states leading_dense and period"),
    "four_experts_held": (experts(4, 16), "4 experts held are under 8"),
    "experts_that_do_not_add_up": (experts(8, 4), "are not the source's"),
    "experts_over_unstated_chips": (experts(8, None),
                                    "states shared_over_chips"),
    "under_an_eighth_of_the_vocabulary": (vocabulary(3999),
                                          "under an eighth"),
    "a_module_without_a_reason": (module(left_out="the head"),
                                  "says what .* and why"),
    "no_deployment": (depth(4, deployment=""), "what deployment"),
    "a_cut_that_says_other_numbers": (depth(4, published=12),
                                      "the cut says 12 -> 4"),
    "a_cut_that_cuts_nothing": (
        {"published": {"num_hidden_layers": 4},
         "cuts": {"num_hidden_layers": {"published": 4}}}, "cuts nothing"),
    "a_width_that_differs": ({"fields": {"intermediate_size": 2816}},
                             "reduced does not list it"),
    "a_width_listed_as_a_cut": (
        {"reduced": ["num_hidden_layers", "intermediate_size"],
         "fields": {"intermediate_size": 2816},
         "cuts": {"intermediate_size": dict(
             kind="depth", published=5632, here=2816, leading_dense=0,
             period=1, deployment=DEPLOYED)}}, "is a width, and never cut"),
    "fewer_heads": ({"fields": {"num_heads": 16}},
                    "reduced does not list it"),
    "a_map_without_the_vocabulary": (
        {"published_as": {"vocab_size": None}}, "published_as maps"),
    "a_reference_that_is_not_there": ({"reference": "reference_lost"},
                                      "reference names"),
    "toy_fields_the_program_does_not_have": (
        {"toy_fields": {"hidden": 64}}, "unexpected keyword"),
}


def drop_nones(d):
    return {k: drop_nones(v) if isinstance(v, dict) else v
            for k, v in d.items() if v is not None}


@pytest.mark.parametrize("case", CUTS)
def test_a_cut_is_held_to_its_floor(tmp_path, case):
    """``manifest.config`` on the fixture configuration with ``change``
    laid on (a None takes the key out): the floors of the
    ``model-configs`` guide's section 4, each from both sides."""
    change, refused = CUTS[case]
    shutil.copytree(FIXTURE, tmp_path / "benchmark")
    path = tmp_path / "benchmark" / "configs" / "rope-gqa.json"
    f = drop_nones(merge(manifest.load(tmp_path, "configs", "rope-gqa"),
                         change))
    f.setdefault("cuts", {})
    path.write_text(json.dumps(f))
    entry = {"name": "rope-gqa", "file": "benchmark/configs/rope-gqa.json",
             "source": f["source"], "reduced": f["reduced"]}
    if refused is None:
        manifest.config(tmp_path, entry)
    elif refused == "unexpected keyword":
        with pytest.raises(TypeError, match=refused):
            manifest.config(tmp_path, entry)
    else:
        with pytest.raises(manifest.Refused, match=refused):
            manifest.config(tmp_path, entry)
