"""SLO-driven online adapter (autotuning/online.py). The cheap tests
drive the decision loop chip-free against a stub engine (ISSUE 16
acceptance: synthetic SLO burn moves decode_window down WITHIN registry
bounds, recovery restores it and re-arms). The last test runs the real
engine actuation end to end and holds zero steady-state recompiles
across adaptations."""

import pytest

from deepspeed_tpu.autotuning import OnlineAdapter, OnlineAdapterConfig
from deepspeed_tpu.inference.v2.serve.admission import (
    AdmissionConfig, AdmissionController)
from deepspeed_tpu.runtime import tunables
from deepspeed_tpu.telemetry import (FlightRecorder, MetricsRegistry,
                                     get_recorder, get_registry,
                                     set_recorder, set_registry, watchdog)


@pytest.fixture(autouse=True)
def _fresh():
    prev_reg = set_registry(MetricsRegistry())
    prev_rec = set_recorder(FlightRecorder())
    watchdog.reset()
    tunables.REGISTRY.reset_observations()
    yield
    watchdog.reset()
    tunables.REGISTRY.reset_observations()
    set_recorder(prev_rec)
    set_registry(prev_reg)


class StubEngine:
    """The adapter's engine surface, chip-free. ``set_decode_window``
    mirrors the real engine's registry check + warmth marking."""

    def __init__(self, window=8, warmed=(1, 2, 4, 8)):
        self.decode_window = window
        self.warmed = set(warmed)
        self.moves = []

    def warmed_decode_windows(self):
        return sorted(self.warmed)

    def set_decode_window(self, window, *, source="online"):
        window = tunables.check("serving.decode_window", window,
                                label="decode_window")
        self.moves.append((self.decode_window, window))
        self.decode_window = window
        self.warmed.add(window)
        tunables.observe("serving.decode_window", window, source)
        return window


class ScriptedSLO:
    def __init__(self):
        self.burn = False

    def burning(self):
        return self.burn


def make_adapter(engine=None, admission=None, **cfg):
    slo = ScriptedSLO()
    clock = {"t": 0.0}
    cfg.setdefault("interval_s", 0.0)
    cfg.setdefault("hold_ticks", 1)
    cfg.setdefault("restore_ticks", 2)
    adapter = OnlineAdapter(engine or StubEngine(), admission=admission,
                           slo=slo, config=OnlineAdapterConfig(**cfg),
                           clock=lambda: clock["t"])
    return adapter, slo, clock


def tick_n(adapter, clock, n):
    for _ in range(n):
        clock["t"] += 1.0
        adapter.tick()


class TestBurnResponse:
    def test_burn_steps_window_down_within_bounds(self):
        eng = StubEngine(window=8)
        adapter, slo, clock = make_adapter(eng, min_decode_window=2)
        slo.burn = True
        tick_n(adapter, clock, 20)
        # stepped down rung by rung, never below the adapter floor and
        # never outside the registry range
        assert eng.decode_window == 2
        lo = tunables.REGISTRY.get("serving.decode_window").lo
        for old, new in eng.moves:
            assert new >= 2 >= lo
            assert new < old
        assert not adapter.armed

    def test_first_burn_tick_acts_immediately(self):
        eng = StubEngine(window=8)
        adapter, slo, clock = make_adapter(eng, hold_ticks=5)
        slo.burn = True
        tick_n(adapter, clock, 1)
        assert eng.decode_window == 4   # no hold before the first move

    def test_hold_ticks_pace_successive_moves(self):
        eng = StubEngine(window=8)
        adapter, slo, clock = make_adapter(eng, hold_ticks=3)
        slo.burn = True
        tick_n(adapter, clock, 2)
        assert eng.decode_window == 4   # second move still holding
        tick_n(adapter, clock, 3)
        assert eng.decode_window == 2

    def test_interval_rate_limits_ticks(self):
        eng = StubEngine(window=8)
        adapter, slo, clock = make_adapter(eng, interval_s=10.0,
                                           hold_ticks=0)
        slo.burn = True
        for _ in range(5):
            clock["t"] += 1.0           # 5s total: below the interval
            adapter.tick()
        assert len(eng.moves) == 1      # only the first tick ran

    def test_steady_state_only_warmed_windows(self):
        """At steady state the adapter must not route through a cold
        rung — only already-compiled window programs are reachable."""
        eng = StubEngine(window=8, warmed=(8,))
        adapter, slo, clock = make_adapter(eng, min_decode_window=1)
        watchdog.mark_steady(True)
        slo.burn = True
        tick_n(adapter, clock, 10)
        assert eng.decode_window == 8   # nowhere warmed to go
        assert eng.moves == []

    def test_warmup_may_seed_cold_rungs(self):
        eng = StubEngine(window=8, warmed=(8,))
        adapter, slo, clock = make_adapter(eng, min_decode_window=2)
        assert not watchdog.is_steady()
        slo.burn = True
        tick_n(adapter, clock, 10)
        assert eng.decode_window == 2   # ladder rungs were allowed

    def test_burn_shrinks_admission_budget(self):
        adm = AdmissionController(AdmissionConfig(max_queued_tokens=4096))
        eng = StubEngine(window=8)
        adapter, slo, clock = make_adapter(eng, admission=adm,
                                           min_queued_tokens=64)
        slo.burn = True
        tick_n(adapter, clock, 20)
        assert adm.config.max_queued_tokens == 64   # halved to the floor
        fam = get_registry().get("autotune_admission_token_budget")
        assert fam.value == 64

    def test_uncapped_budget_gets_bounded_under_burn(self):
        adm = AdmissionController(AdmissionConfig(max_queued_tokens=None))
        adapter, slo, clock = make_adapter(StubEngine(), admission=adm)
        slo.burn = True
        tick_n(adapter, clock, 1)
        assert adm.config.max_queued_tokens is not None


class TestRecovery:
    def test_recovery_restores_and_rearms(self):
        """The acceptance pin: burn down, then clean ticks restore the
        configured window and re-arm the hysteresis."""
        eng = StubEngine(window=8)
        adm = AdmissionController(AdmissionConfig(max_queued_tokens=4096))
        adapter, slo, clock = make_adapter(eng, admission=adm,
                                           restore_ticks=2)
        slo.burn = True
        tick_n(adapter, clock, 6)
        assert eng.decode_window == 2
        assert not adapter.armed
        slo.burn = False
        tick_n(adapter, clock, 30)
        assert eng.decode_window == 8
        assert adm.config.max_queued_tokens == 4096
        assert adapter.armed
        fam = get_registry().get("autotune_online_armed")
        assert fam.value == 1

    def test_restore_paced_by_restore_ticks(self):
        eng = StubEngine(window=8)
        adapter, slo, clock = make_adapter(eng, restore_ticks=3)
        slo.burn = True
        tick_n(adapter, clock, 1)
        assert eng.decode_window == 4
        slo.burn = False
        tick_n(adapter, clock, 2)
        assert eng.decode_window == 4   # not yet: needs 3 clean ticks
        tick_n(adapter, clock, 1)
        assert eng.decode_window == 8

    def test_rearm_only_after_full_restore(self):
        adm = AdmissionController(AdmissionConfig(max_queued_tokens=4096))
        eng = StubEngine(window=8)
        adapter, slo, clock = make_adapter(eng, admission=adm,
                                           restore_ticks=1)
        slo.burn = True
        tick_n(adapter, clock, 4)
        slo.burn = False
        # window and budget each restore one rung per clean interval;
        # the adapter must not re-arm while either is still below base
        while not adapter._restored():
            assert not adapter.armed
            tick_n(adapter, clock, 1)
        tick_n(adapter, clock, 1)
        assert adapter.armed

    def test_armed_and_restored_is_a_noop(self):
        eng = StubEngine(window=8)
        adapter, slo, clock = make_adapter(eng)
        tick_n(adapter, clock, 10)
        assert eng.moves == []
        assert adapter.adaptations == 0


class TestObservability:
    def test_adaptations_counted_and_flight_recorded(self):
        eng = StubEngine(window=8)
        adapter, slo, clock = make_adapter(eng)
        slo.burn = True
        tick_n(adapter, clock, 2)
        slo.burn = False
        tick_n(adapter, clock, 10)
        fam = get_registry().get("autotune_online_adaptations_total")
        down = fam.labels(knob="decode_window", direction="down").value
        up = fam.labels(knob="decode_window", direction="up").value
        assert down >= 1 and up >= 1
        kinds = [e["kind"] for e in get_recorder().events()]
        assert "autotune_adapt" in kinds
        reasons = {e.get("reason") for e in get_recorder().events(
            kind="autotune_adapt")}
        assert {"slo_burn", "recovered", "rearmed"} <= reasons

    def test_provenance_online_after_nudge(self):
        eng = StubEngine(window=8)
        adapter, slo, clock = make_adapter(eng)
        slo.burn = True
        tick_n(adapter, clock, 1)
        value, source = tunables.REGISTRY.effective(
            "serving.decode_window")
        assert (value, source) == (4, "online")

    def test_disabled_adapter_never_moves(self):
        eng = StubEngine(window=8)
        adapter, slo, clock = make_adapter(eng, enabled=False)
        slo.burn = True
        tick_n(adapter, clock, 10)
        assert eng.moves == []


def test_real_engine_adaptation_zero_steady_recompiles(tiny_model_128):
    """End-to-end actuation on the real engine: warm two window rungs,
    mark steady, burn -> the adapter swaps the fused decode program
    down a warmed rung and back, with ZERO steady-state recompiles and
    the engine still generating."""
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.config_v2 import DSStateManagerConfig

    model, params = tiny_model_128
    eng = InferenceEngineV2(
        model, RaggedInferenceEngineConfig(
            state_manager=DSStateManagerConfig(
                max_tracked_sequences=8, max_seq_len=128, num_blocks=65,
                block_size=16),
            dtype="float32", prefill_bucket=16, decode_window=8),
        params=params)
    # warm both rungs the adapter will move across (and absorb the
    # fresh-pool respecialization), then freeze the program set
    eng.generate([[2, 4, 6, 8]], max_new_tokens=8)
    eng.set_decode_window(4)
    eng.generate([[3, 5, 7]], max_new_tokens=8, uids=[10])
    eng.set_decode_window(8)
    eng.generate([[2, 4, 6]], max_new_tokens=8, uids=[20])
    eng.generate([[9, 11]], max_new_tokens=8, uids=[21])
    assert set(eng.warmed_decode_windows()) >= {4, 8}
    watchdog.mark_steady(True)

    adapter, slo, clock = make_adapter(eng, min_decode_window=2)
    slo.burn = True
    tick_n(adapter, clock, 4)
    assert eng.decode_window == 4       # warmed rung reached...
    out_down = eng.generate([[2, 4, 6, 8]], max_new_tokens=8, uids=[30])
    slo.burn = False
    tick_n(adapter, clock, 10)
    assert eng.decode_window == 8       # ...and restored
    assert adapter.armed
    out_up = eng.generate([[2, 4, 6, 8]], max_new_tokens=8, uids=[40])
    # full sequences: 4 prompt tokens + 8 generated, at both rungs
    assert len(out_up[0]) == len(out_down[0]) == 12

    violations = get_registry().family_total(
        "xla_steady_state_recompiles_total")
    assert violations == 0.0, (
        f"online adaptation recompiled at steady state: {violations}")