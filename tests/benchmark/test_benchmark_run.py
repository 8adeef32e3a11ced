"""run.py end to end on the CPU: it refuses to measure without a TPU,
rehearses a training cell at toy widths, and takes a new cell that is
nothing but data files."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
BANNER = "REHEARSAL (cpu)"


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
    shutil.copytree(REPO / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    os.symlink(REPO / "deepspeed_tpu", tmp_path / "deepspeed_tpu")
    before = {p: p.read_bytes()
              for p in (tmp_path / "benchmark").rglob("*") if p.is_file()}
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


def test_a_directory_with_the_benchmark_alone_gives_no_result(tmp_path):
    shutil.copytree(REPO / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = run_py(["--workload", "opt-125m.train-dense", "--seed", "1",
                "--seconds", "1", "--trace", "0"], cwd=tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "the program is not here" in p.stderr
