"""Packaging smoke tests (reference setup.py + bin/ entry points)."""

import os
import subprocess
import sys
import tomllib

import pytest

REPO = os.path.join(os.path.dirname(__file__), "..", "..", "..")


def _pyproject():
    with open(os.path.join(REPO, "pyproject.toml"), "rb") as fh:
        return tomllib.load(fh)


def test_console_scripts_resolve():
    """Every declared console script points at an importable callable."""
    import importlib

    scripts = _pyproject()["project"]["scripts"]
    assert set(scripts) == {"ds_tpu", "ds_tpu_launch", "ds_tpu_report",
                            "ds_tpu_bench", "ds_tpu_elastic",
                            "ds_tpu_flash_check", "ds_tpu_to_universal",
                            "ds_tpu_zero_to_fp32"}
    for name, target in scripts.items():
        mod_name, func_name = target.split(":")
        mod = importlib.import_module(mod_name)
        fn = getattr(mod, func_name)
        assert callable(fn), f"{name} -> {target} is not callable"


def test_package_data_covers_csrc():
    """The JIT-compiled C++ host libraries must ship in the package."""
    data = _pyproject()["tool"]["setuptools"]["package-data"]["deepspeed_tpu"]
    assert any("csrc" in pat and pat.endswith(".cpp") for pat in data)
    # and the sources actually exist where the pattern points
    csrc = os.path.join(REPO, "deepspeed_tpu", "csrc")
    assert any(f.endswith(".cpp") for _, _, fs in os.walk(csrc) for f in fs)


def test_ds_tpu_report_runs():
    """ds_tpu_report's target prints the env report and returns 0
    (reference bin/ds_report). Pins the CPU backend: the report lists the
    devices of whatever backend its process runs on."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax; jax.config.update('jax_platforms', 'cpu');"
         "from deepspeed_tpu.env_report import main; raise SystemExit(main())"],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "deepspeed_tpu environment report" in out.stdout
    assert "op compatibility" in out.stdout
