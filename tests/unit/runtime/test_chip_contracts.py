"""Contracts of the programs that speak for the chip (ISSUE 22): no TPU is
a failure and never a CPU fallback, the compile cache has one owner and
one place, and an unknown device has no peak."""

import os
import pathlib
import re
import subprocess
import sys
import types

import pytest

REPO = pathlib.Path(__file__).resolve().parents[3]
BANNER = "REHEARSAL (cpu) — not a chip result"


def _run(*args, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chip_smoke.py", *args],
                          cwd=REPO, env=env, capture_output=True, text=True,
                          timeout=timeout)


def test_chip_smoke_without_tpu_fails_naming_the_platform():
    out = _run(timeout=120)
    assert out.returncode != 0
    assert "platform: cpu" in out.stdout          # the header came first
    assert "platform == 'cpu'" in out.stderr      # require_tpu said why
    assert '"ok"' not in out.stdout and "PASS" not in out.stdout


def test_flash_kernel_table_without_tpu_prints_no_table():
    """Its rows are read into PERF.md and ``_auto_blocks`` as chip times;
    on the CPU they would be the Pallas interpreter's."""
    out = subprocess.run(
        [sys.executable, "scripts/flash_kernel_table.py", "--only",
         "head128"], cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "platform == 'cpu'" in out.stderr
    assert "ours_ms" not in out.stdout and "backend" not in out.stdout


def test_chip_smoke_rehearsal_passes_and_labels_itself():
    """One phase keeps this inside the tier-1 budget; the slow sibling
    below rehearses all five."""
    out = _run("--rehearse", "--phases", "T1", timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    assert lines[0] == BANNER and lines[-1] == BANNER
    assert any(ln.startswith("[T1] PASS") for ln in lines)
    assert '"ok"' not in out.stdout      # a rehearsal prints no chip result


@pytest.mark.slow
def test_chip_smoke_full_rehearsal():
    out = _run("--rehearse", timeout=1800)
    assert out.returncode == 0, out.stderr[-2000:]
    for name in ("K", "T1", "S1", "T4", "S4"):
        assert f"[{name}] PASS" in out.stdout


# -- compile cache: one helper, one place ----------------------------------
@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of applying them (the suite's
    own cache must stay where conftest put it)."""
    import jax
    seen = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: seen.__setitem__(name, value))
    return seen


def test_cache_helper_sets_no_directory_when_env_places_it(
        monkeypatch, config_updates, tmp_path):
    from deepspeed_tpu.utils import compile_cache
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in config_updates
    assert "jax_persistent_cache_min_compile_time_secs" in config_updates
    assert "jax_persistent_cache_min_entry_size_bytes" in config_updates


def test_cache_helper_defaults_to_the_checkout(monkeypatch, config_updates):
    from deepspeed_tpu.utils import compile_cache
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(REPO / ".jax_cache")
    assert config_updates["jax_compilation_cache_dir"] == path
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def _repo_py_files():
    """Every ``*.py`` of the checkout outside its dot-directories."""
    return [p for p in REPO.rglob("*.py")
            if not any(part.startswith(".")
                       for part in p.relative_to(REPO).parts)]


def test_only_the_helper_touches_the_cache_dir():
    owner = REPO / "deepspeed_tpu" / "utils" / "compile_cache.py"
    update = re.compile(r"""update\(\s*["']jax_compilation_cache_dir""")
    offenders = [
        str(p.relative_to(REPO)) for p in _repo_py_files()
        if p != owner and update.search(p.read_text())]
    assert offenders == []
    assert update.search(owner.read_text())


# -- one yardstick -----------------------------------------------------------
def test_one_yardstick_for_speed():
    """Speed is what ``benchmark/run.py`` reads in a cell, on the chip.
    Beside it only ``chip_smoke.py`` (start-up proof, log lines) and
    ``scripts/flash_kernel_table.py`` (one kernel alone) may be programs
    that print an MFU or a tokens-a-second figure. The three named below
    time serving, which has no cell yet, and are ROADMAP D2's to retire:
    this list may shrink, not grow."""
    speed = re.compile(r"\bmfu\b|tok(?:en)?s?(?:_per_|/|\s+per\s+)s(?:ec)?\b"
                       r"|tok_s\b", re.I)
    entry_point = re.compile(r"""^if __name__ == ["']__main__["']""", re.M)
    allowed = {"chip_smoke.py", "scripts/flash_kernel_table.py"}
    still_here = {"deepspeed_tpu/benchmarks/serving_bench.py",
                  "deepspeed_tpu/benchmarks/load_bench.py",
                  "examples/serve_hf.py"}
    found = set()
    for p in _repo_py_files():
        rel = p.relative_to(REPO)
        text = p.read_text()
        if rel.parts[0] not in ("benchmark", "tests") \
                and entry_point.search(text) and speed.search(text):
            found.add(rel.as_posix())
    assert found - allowed == still_here


# -- no fallback that hides the device --------------------------------------
def test_peak_flops_raises_on_unknown_kind():
    from deepspeed_tpu.accelerator.tpu_accelerator import peak_flops
    assert peak_flops(types.SimpleNamespace(device_kind="TPU v5 lite")) \
        == 197e12
    for kind in ("cpu", "TPU v9 imaginary"):
        with pytest.raises(ValueError, match="no peak-FLOPS entry"):
            peak_flops(types.SimpleNamespace(device_kind=kind))


def test_require_tpu_raises_on_cpu():
    from deepspeed_tpu.accelerator.tpu_accelerator import require_tpu
    with pytest.raises(RuntimeError, match="platform == 'cpu'"):
        require_tpu()


