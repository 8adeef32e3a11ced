"""run.py end to end on the CPU: it refuses to measure without a TPU,
rehearses a training cell at toy widths, takes a new cell that is
nothing but data files, and takes a new configuration of another block,
cut in depth, that is nothing but files either."""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark import manifest

REPO = Path(__file__).resolve().parents[2]
BANNER = "REHEARSAL (cpu)"
# a configuration that is NOT the OPT block (pre-norm RMSNorm, rope,
# SwiGLU, GQA, an untied head), cut in depth, as the files a later PR
# would add: its file of sizes with ``cuts`` and ``toy_fields``, its own
# float32 reference and weights modules, a traffic mix and a cell
FIXTURE = Path(__file__).resolve().parent / "fixtures" / "new_configuration"
NEW_CONFIG, NEW_CELL = "rope-gqa", "rope-gqa.rollout-64"
# control.py's route into a runner, in a process of the copy's own
RUN_ONCE = """
import json, sys
sys.path.insert(0, ".")
import jax
from benchmark import control, run as harness
r = control.run_once(sys.argv[1], int(sys.argv[2]), 0.5, False,
                     jax.devices(), harness.CompileClock(), rehearse=True)
print(json.dumps({"correct": bool(r.correct), "attempted": r.attempted,
                  "compared": r.correct_detail["compared"]}))
"""


def run_py(args, cwd=REPO, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    # niced: a rehearsal compiles on every core, and the suite's other
    # workers hold tests with sub-second deadlines
    return subprocess.run(
        ["nice", "-n", "15", sys.executable, "benchmark/run.py", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=timeout)


def assert_rehearsed(p):
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    assert lines[0].startswith(BANNER) and lines[-1].startswith(BANNER)
    # a rehearsal prints no metrics line: nothing on stdout parses as one
    for ln in lines:
        assert not ln.lstrip().startswith("{"), ln
    assert "correct True" in p.stderr
    # nothing may compile inside a training window, anywhere
    assert "'compiles_in_window': 0" in p.stderr


def test_without_a_tpu_it_exits_nonzero_and_names_the_platform():
    p = run_py(["--workload", "opt-125m.train-dense", "--seed",
                "2400000001", "--seconds", "1", "--trace", "0"])
    assert p.returncode != 0
    assert "platform == 'cpu'" in p.stderr
    assert "No CPU fallback" in p.stderr
    assert p.stdout.strip() == ""           # and prints no result


def test_an_unknown_cell_is_an_error_not_a_default():
    p = run_py(["--workload", "no-such.cell", "--rehearse"])
    assert p.returncode != 0 and "no workloads/no-such.cell.json" in p.stderr


def test_rehearse_a_training_cell_with_its_readers():
    p = run_py(["--workload", "opt-125m.train-dense", "--rehearse",
                "--trace", "1"])
    assert_rehearsed(p)
    assert "readers ran" in p.stderr and "compiles.train" in p.stderr


@pytest.mark.slow
@pytest.mark.parametrize("cell", [
    w["name"] for w in json.loads(
        (REPO / "BENCHMARK.json").read_text())["workloads"]])
def test_rehearse_every_cell(cell):
    assert_rehearsed(run_py(["--workload", cell, "--rehearse",
                             "--trace", "0"]))


def test_a_new_cell_is_data_files_only(tmp_path):
    """Copy the benchmark, add a traffic mix and a cell as new files (a
    shorter sequence under ZeRO-1 on two chips), touch nothing that was
    there, and rehearse the new cell."""
    before = copy_the_benchmark(tmp_path)
    bench = tmp_path / "benchmark"
    cell = json.loads((bench / "workloads"
                       / "opt-125m.train-dense.json").read_text())
    cell.update(traffic="train-seq1k", chips=2,
                why="a cell added by a test: ZeRO-1 over two chips")
    cell["deepspeed"]["zero_optimization"] = {"stage": 1}
    cell["rehearse_traffic"]["seq_len"] = 128
    (bench / "workloads" / "opt-125m.train-seq1k.json").write_text(
        json.dumps(cell))
    # entries in BENCHMARK.json say what the new cell reports: the cell,
    # and its name in the lists of the metrics it takes
    manifest = json.loads((tmp_path / "BENCHMARK.json").read_text())
    manifest["workloads"].append({
        "name": "opt-125m.train-seq1k", "config": "opt-125m",
        "traffic": "train-seq1k", "chips": 2, "why": cell["why"]})
    takes = {"train_tok_s", "compiles.train", "mfu.train", "host_ms.train",
             "collective_ms.train"}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m["name"] in takes:
            m["workloads"].append("opt-125m.train-seq1k")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    (bench / "traffic" / "train-seq1k.json").write_text(json.dumps({
        "runner": "train", "seq_len": 1024, "distinct_batches": 4,
        "trace_steps": 2}))
    p = run_py(["--workload", "opt-125m.train-seq1k", "--rehearse",
                "--trace", "1"], cwd=tmp_path)
    assert_rehearsed(p)
    assert "micro 4 x dp 2" in p.stderr
    ran = p.stderr.split("readers ran")[1]
    assert "host_ms.train" in ran and "flash_share.train" not in ran
    for path, data in before.items():
        assert path.read_bytes() == data, f"{path} was edited"


def copy_the_benchmark(tmp_path):
    """A copy of what the rules and a rehearsal read, and its files'
    bytes."""
    shutil.copytree(REPO / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name in ("BENCHMARK.json", "PERF.md"):
        shutil.copy(REPO / name, tmp_path / name)
    os.symlink(REPO / "deepspeed_tpu", tmp_path / "deepspeed_tpu")
    return {p: p.read_bytes()
            for p in (tmp_path / "benchmark").rglob("*") if p.is_file()}


def add_the_new_configuration(tmp_path, edit=None):
    """The fixture's files laid into the copy as NEW files, and the
    entries in ``BENCHMARK.json`` that a generation cell of a new
    configuration joins: the configuration, the cell, the cell's name
    under ``gen_tok_s`` and the ``.gen`` metrics it takes. ``edit``
    changes the configuration's file first."""
    bench = tmp_path / "benchmark"
    for src in (p for p in FIXTURE.rglob("*")
                if p.is_file() and "__pycache__" not in p.parts):
        dst = bench / src.relative_to(FIXTURE)
        assert not dst.exists(), f"{dst} was there"
        shutil.copy(src, dst)
    path = bench / "configs" / f"{NEW_CONFIG}.json"
    config = json.loads(path.read_text())
    if edit is not None:
        edit(config)
        path.write_text(json.dumps(config))
    cell = json.loads((bench / "workloads" / f"{NEW_CELL}.json").read_text())
    bm = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bm["configs"].append({
        "name": NEW_CONFIG, "source": config["source"],
        "file": f"benchmark/configs/{NEW_CONFIG}.json",
        "reduced": config["reduced"], "why": "a configuration added by a "
        "test: another block than OPT's, cut in depth"})
    bm["workloads"].append({
        "name": NEW_CELL, "config": NEW_CONFIG, "traffic": cell["traffic"],
        "chips": 1, "why": cell["why"]})
    for m in bm["end_to_end"] + bm["per_layer"]:
        if m["name"] in {"gen_tok_s", "compiles.gen", "idle.gen",
                         "peak_hbm.gen"}:
            m["workloads"].append(NEW_CELL)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))


def test_a_new_configuration_is_files_only(tmp_path):
    """The twin of ``test_a_new_cell_is_data_files_only`` for a
    configuration: the fixture goes into a copy as new files, its cell
    rehearses to ``correct`` through ``run.py`` and through
    ``control.run_once`` with its own reference and weights (the OPT
    block's refuse these fields), the manifest's rules hold on the copy,
    and nothing that was there is edited."""
    before = copy_the_benchmark(tmp_path)
    manifest.check(tmp_path)
    add_the_new_configuration(tmp_path)
    p = run_py(["--workload", NEW_CELL, "--rehearse", "--trace", "1",
                "--seed", str(2 ** 31 + 3500000001)], cwd=tmp_path)
    assert_rehearsed(p)
    assert "compared: logit_err" in p.stderr
    assert "compared: token_gap" in p.stderr
    assert "calls finished 0" not in p.stderr
    ran = p.stderr.split("readers ran")[1]
    assert "compiles.gen" in ran and "ragged_share.gen" not in ran
    # benchmark/control.py builds its own Context and needs no edit
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    q = subprocess.run(
        ["nice", "-n", "15", sys.executable, "-c", RUN_ONCE, NEW_CELL,
         "3500000002"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=900)
    assert q.returncode == 0, q.stderr[-3000:]
    got = json.loads(q.stdout.splitlines()[-1])
    assert got["correct"] and got["attempted"] > 0
    assert set(got["compared"]) == {"logit_err", "token_gap"}
    manifest.check(tmp_path)
    for path, data in before.items():
        assert path.read_bytes() == data, f"{path} was edited"


def test_a_configurations_modules_are_found_by_name_or_refused():
    """``Context`` resolves what the configuration's file names; a module
    that is not there or lacks what a runner calls is an error naming the
    configuration, and a block the named reference does not describe is
    refused by it: nothing falls back to the OPT block's."""
    from benchmark import evidence, reference, weights

    def ctx(config):
        return evidence.Context(
            cell={"config": "some-config"}, config=config, traffic={},
            seed=0, seconds=1.0, trace=False, rehearse=True, devices=[],
            clock=None, t_process_start=0.0, log=print, scratch=Path("."))

    opt = json.loads((REPO / "benchmark/configs/opt-125m.json").read_text())
    c = ctx(opt)
    assert c.reference is reference and c.weights is weights
    assert c.reference is c.reference             # resolved once
    new = json.loads((FIXTURE / "configs" / f"{NEW_CONFIG}.json").read_text())
    for key in ("reference", "weights"):
        with pytest.raises(SystemExit, match=f"some-config names {key} "
                           f"'{new[key]}' and .* does not import"):
            getattr(ctx(new), key)     # the fixture's are not in this tree
    unnamed = {k: v for k, v in new.items()
               if k not in ("reference", "weights")}
    with pytest.raises(ValueError, match="implements the OPT block"):
        ctx(unnamed).reference
    with pytest.raises(SystemExit, match="some-config's reference module "
                       "benchmark.arith lacks logits, next_token_loss, "
                       "check_supported"):
        ctx(dict(opt, reference="arith")).reference
    with pytest.raises(SystemExit, match="weights module benchmark.arith "
                       "lacks make"):
        ctx(dict(opt, weights="arith")).weights
    # no runner imports either module by name, and the OPT block's toy
    # widths are read where they are the default and nowhere else
    for src in (REPO / "benchmark").rglob("*.py"):
        text = src.read_text()
        if src.parent.name == "runners":
            assert not re.search(r"import[^\n]*\b(reference|weights)\b",
                                 text), src
        if src.name != "run.py":
            assert "TOY_FIELDS" not in text, src


def cut_a_width(listed):
    def edit(config):
        config["fields"]["hidden_size"] = 1024      # the source's is 2048
        if listed:
            config["reduced"].append("hidden_size")
            config["cuts"]["hidden_size"] = dict(
                config["cuts"]["num_hidden_layers"], published=2048,
                here=1024)
    return edit


@pytest.mark.parametrize("listed", [False, True],
                         ids=["unlisted", "listed_as_a_cut"])
def test_a_new_configuration_with_a_cut_width_fails_the_rules(
        tmp_path, listed):
    copy_the_benchmark(tmp_path)
    add_the_new_configuration(tmp_path, edit=cut_a_width(listed))
    with pytest.raises(manifest.Refused, match="is a width, and never cut"
                       if listed else "reduced does not list it"):
        manifest.check(tmp_path)


def test_a_directory_with_the_benchmark_alone_gives_no_result(tmp_path):
    shutil.copytree(REPO / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = run_py(["--workload", "opt-125m.train-dense", "--seed", "1",
                "--seconds", "1", "--trace", "0"], cwd=tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "the program is not here" in p.stderr
