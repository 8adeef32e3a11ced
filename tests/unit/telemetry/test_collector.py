"""The collector's pauses (telemetry/collector.py): every collection
counted by generation, the long ones as ``gc_pause`` spans, one hook a
process, and no lock waited for inside a collection."""

import gc

import pytest

from deepspeed_tpu.telemetry import (MetricsRegistry, collector,
                                     get_registry, set_registry, trace)

COUNT = "process_gc_collections_total"
PAUSE = "process_gc_pause_seconds"


@pytest.fixture
def registry():
    prev = set_registry(MetricsRegistry())
    trace.clear()
    try:
        yield get_registry()
    finally:
        set_registry(prev)
        trace.clear()


def _count(reg, generation):
    return reg.get(COUNT).labels(generation=str(generation)).value


def _pauses():
    return [s for s in trace.export() if s["name"] == "gc_pause"]


def test_a_forced_collection_is_counted_once(registry, monkeypatch):
    monkeypatch.setattr(collector, "SPAN_FROM_S", float("inf"))
    collector.install_gc_hook()
    before = _count(registry, 2)
    seen = registry.get(PAUSE).labels(generation="2").count
    gc.collect()
    assert _count(registry, 2) == before + 1
    assert registry.get(PAUSE).labels(generation="2").count == seen + 1
    assert registry.get(PAUSE).labels(generation="2").sum > 0
    gc.collect(0)
    assert _count(registry, 0) >= 1 and _count(registry, 2) == before + 1


@pytest.mark.parametrize("threshold,spans", [(0.0, 1), (float("inf"), 0)])
def test_a_span_only_for_a_collection_of_the_threshold_or_more(
        registry, monkeypatch, threshold, spans):
    """The threshold is driven, not slept for: at 0 every collection is
    long enough, at infinity none is."""
    collector.install_gc_hook()
    gc.collect()                    # what is there to collect goes first
    trace.clear()
    monkeypatch.setattr(collector, "SPAN_FROM_S", threshold)
    gc.collect(2)
    monkeypatch.setattr(collector, "SPAN_FROM_S", float("inf"))
    got = _pauses()
    assert len(got) == spans
    for s in got:
        assert s["attrs"]["generation"] == 2
        assert s["attrs"]["collected"] >= 0
        assert s["parent"] is None and s["duration_s"] > 0
        assert "annotated" not in s


def test_the_shipped_threshold_is_a_millisecond():
    """(tests/conftest.py raises it for every test: read the source)"""
    import inspect
    assert "\nSPAN_FROM_S = 1e-3\n" in inspect.getsource(collector)


def test_installed_twice_it_records_once(registry, monkeypatch):
    monkeypatch.setattr(collector, "SPAN_FROM_S", 0.0)
    collector.install_gc_hook()
    collector.install_gc_hook()
    assert gc.callbacks.count(collector._on_gc) == 1
    gc.collect()
    trace.clear()
    before = _count(registry, 2)
    gc.collect()
    monkeypatch.setattr(collector, "SPAN_FROM_S", float("inf"))
    assert _count(registry, 2) == before + 1
    assert len(_pauses()) == 1


def test_a_registry_it_was_never_installed_under_counts_nothing(
        registry, monkeypatch):
    """The hook makes no series inside a collection (that takes the
    family's lock): a default registry swapped in later has none until
    an engine is built under it."""
    monkeypatch.setattr(collector, "SPAN_FROM_S", float("inf"))
    collector.install_gc_hook()
    fresh = MetricsRegistry()
    prev = set_registry(fresh)
    try:
        gc.collect()
        assert fresh.get(COUNT) is None
        collector.install_gc_hook()
        gc.collect()
        assert _count(fresh, 2) == 1
    finally:
        set_registry(prev)


def test_the_span_waits_for_no_lock(registry):
    """A collection can fall inside the ring's own locked copy: the span
    is dropped there, never waited for."""
    assert trace.record_nowait("gc_pause", 1.0, 0.5, generation=2,
                               collected=0) is True
    with trace._lock:
        assert trace.record_nowait("gc_pause", 2.0, 0.5, generation=2,
                                   collected=0) is False
    got = _pauses()
    assert [s["start"] for s in got] == [1.0]
    assert got[0]["attrs"] == {"generation": 2, "collected": 0}


def test_both_engines_install_the_hook():
    """By name in the two constructors' telemetry set-up: a process that
    builds either has its pauses on the ring."""
    import inspect
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.runtime.engine import DeepSpeedTpuEngine
    for cls in (InferenceEngineV2, DeepSpeedTpuEngine):
        assert "collector.install_gc_hook()" in inspect.getsource(
            cls._init_telemetry)
