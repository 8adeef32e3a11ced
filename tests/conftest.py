"""Test harness configuration.

The reference spawns `world_size` torch processes per test
(tests/unit/common.py:102 DistributedExec); on TPU/JAX we instead run every
test single-process over a virtual 8-device CPU mesh
(xla_force_host_platform_device_count), which exercises the same SPMD
partitioning + collectives XLA emits on a real pod slice (SURVEY.md §4
implication (a)).
"""

import os

# Must be set before jax initializes its backends: tests always run on the
# virtual 8-device CPU mesh.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

# The AOT lowering/scale tests use libtpu as a host COMPILER library
# (topology described explicitly, no devices). Off-GCP its init queries a
# metadata server that is not there, retrying 30x per variable before it
# gives up; tests never need the metadata.
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_threefry_partitionable", True)

# Persistent compilation cache: the suite is dominated by XLA compiles;
# warm runs reuse compiled executables.
from deepspeed_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "examples: heavyweight in-tree example subprocess smokes "
        "(separate tier; run with -m examples or DS_TPU_RUN_EXAMPLES=1)")
    config.addinivalue_line(
        "markers",
        "slow: long-running benchmarks/sweeps excluded from the tier-1 "
        "set (tier-1 runs with -m 'not slow')")


def pytest_collection_modifyitems(config, items):
    # The example smokes are the suite's long pole (subprocess + cold XLA
    # compile each). Keep the default tier fast; run the examples tier with
    # `pytest -m examples` or DS_TPU_RUN_EXAMPLES=1.
    if os.environ.get("DS_TPU_RUN_EXAMPLES") == "1":
        return
    if "examples" in (config.getoption("-m") or ""):
        return
    skip = pytest.mark.skip(
        reason="examples tier: run with -m examples or DS_TPU_RUN_EXAMPLES=1")
    for item in items:
        if "examples" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(scope="session")
def devices():
    return jax.devices()


@pytest.fixture(autouse=True)
def _assert_8_devices():
    assert jax.device_count() >= 8, "tests expect >=8 virtual devices"
    yield


@pytest.fixture(autouse=True)
def _no_gc_pause_spans(monkeypatch):
    """A garbage collection falls where it falls: once an engine has
    installed the collector's hook (telemetry/collector.py), one of 1 ms
    or more would put a ``gc_pause`` span into whatever ring a test is
    reading span by span. Tests see none unless they drive the threshold
    themselves (tests/unit/telemetry/test_collector.py)."""
    from deepspeed_tpu.telemetry import collector
    monkeypatch.setattr(collector, "SPAN_FROM_S", float("inf"))
    # and the sandbox's neighbours are not what a test is about: a leaf
    # span that ran 50 ms over its median leaves no ``host_stall`` span
    # and no anomaly unless a test sets the floor itself
    monkeypatch.setattr(collector, "STALL_MIN_S", float("inf"))
