"""Telemetry integration: inference v2 and the training engine populate
the unified registry, and the TelemetryBridge flushes through the CSV
monitor backend to disk."""

import csv

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.telemetry import MetricsRegistry, get_registry, set_registry
from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.config_v2 import DSStateManagerConfig
from deepspeed_tpu.inference.v2.scheduler import DynamicSplitFuseScheduler
from deepspeed_tpu.models import TransformerConfig, TransformerLM


@pytest.fixture(autouse=True)
def fresh_registry():
    """Each test gets an isolated process registry (engines bind their
    series at construction, so construct engines inside the test)."""
    prev = set_registry(MetricsRegistry())
    yield get_registry()
    set_registry(prev)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = TransformerConfig(vocab_size=128, hidden_size=64,
                            intermediate_size=128, num_layers=2,
                            num_heads=4, num_kv_heads=2, max_seq_len=128,
                            remat=False, use_flash=False)
    model = TransformerLM(cfg)
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          model.init_params(jax.random.PRNGKey(0)))
    return model, params


def _engine(model, params, **sm_kw):
    sm = dict(max_tracked_sequences=4, max_seq_len=128, num_blocks=17,
              block_size=16)
    sm.update(sm_kw)
    return InferenceEngineV2(
        model, RaggedInferenceEngineConfig(
            state_manager=DSStateManagerConfig(**sm), dtype="float32",
            prefill_bucket=16), params=params)


# -- inference v2 -----------------------------------------------------------
def test_generate_populates_inference_metrics(tiny_model, fresh_registry):
    model, params = tiny_model
    eng = _engine(model, params)
    rng = np.random.default_rng(0)
    prompts = [list(map(int, rng.integers(1, 127, n))) for n in (20, 7)]
    eng.generate(prompts, max_new_tokens=8)

    reg = fresh_registry
    ttft = reg.get("inference_ttft_seconds")
    assert ttft.count == 1 and ttft.sum > 0
    assert reg.get("inference_prefill_tokens_total").value == 27
    # first token comes from prefill; the remaining 7 tokens per row run
    # in ONE fused decode window (decode_window default 8 covers them),
    # i.e. one decode dispatch and one device->host sync
    assert reg.get("inference_decode_tokens_total").value == 14
    assert reg.get("inference_decode_steps_total").value == 1
    assert reg.get("inference_decode_host_syncs_total").value == 1
    assert reg.get("inference_decode_window_size").value == 8
    dt = reg.get("inference_decode_step_seconds")
    assert dt.count == 1 and dt.sum > 0
    fw = reg.get("inference_fused_window_seconds")
    assert fw.count == 1 and fw.sum > 0
    assert reg.get("inference_decode_tokens_per_s").value > 0
    # generate() flushed its uids: pool back to empty, gauge updated last
    assert reg.get("inference_kv_pool_utilization").value == 0.0
    assert reg.get("inference_tracked_sequences").value == 0


def test_kv_pool_utilization_nonzero_while_sequences_live(tiny_model,
                                                          fresh_registry):
    model, params = tiny_model
    eng = _engine(model, params)
    eng.put([7], [list(range(1, 33))])   # 32 tokens = 2 blocks of 16
    util = fresh_registry.get("inference_kv_pool_utilization")
    assert util.value == pytest.approx(2 / 16)
    assert fresh_registry.get("inference_tracked_sequences").value == 1
    eng.flush(7)
    assert util.value == 0.0
    # the high-water mark survives the flush (what bench/tuning reads)
    peak = fresh_registry.get("inference_kv_pool_utilization_peak")
    assert peak.value == pytest.approx(2 / 16)


def test_generate_metrics_render_in_prometheus(tiny_model, fresh_registry):
    model, params = tiny_model
    eng = _engine(model, params)
    eng.generate([[1, 2, 3, 4]], max_new_tokens=4)
    text = fresh_registry.render_prometheus()
    assert "# TYPE inference_ttft_seconds histogram" in text
    assert "inference_ttft_seconds_count 1" in text
    assert "inference_decode_tokens_total" in text


# -- scheduler --------------------------------------------------------------
def test_scheduler_populates_serving_metrics(tiny_model, fresh_registry):
    model, params = tiny_model
    eng = _engine(model, params, max_tracked_sequences=8, num_blocks=33,
                  max_ragged_batch_size=512)
    sched = DynamicSplitFuseScheduler(eng, token_budget=64)
    rng = np.random.default_rng(1)
    for uid, n in enumerate((30, 9)):
        sched.submit(uid, list(map(int, rng.integers(1, 127, n))),
                     max_new_tokens=5)
    reg = fresh_registry
    assert reg.get("serving_requests_submitted_total").value == 2
    assert reg.get("serving_queue_depth").value == 2
    sched.run(max_steps=100)
    assert reg.get("serving_requests_finished_total").value == 2
    assert reg.get("serving_queue_depth").value == 0
    assert reg.get("serving_running_sequences").value == 0
    assert reg.get("serving_generated_tokens_total").value == 10
    assert reg.get("serving_steps_total").value == sched.steps > 0
    ttft = reg.get("serving_ttft_seconds")
    assert ttft.count == 2 and ttft.sum > 0
    rt = reg.get("serving_request_seconds")
    assert rt.count == 2 and rt.sum >= ttft.sum


def test_scheduler_preemption_counter(tiny_model, fresh_registry):
    """Mutual exhaustion (two long prompts in a tiny pool) must show up
    as nonzero preemptions."""
    model, params = tiny_model
    eng = _engine(model, params, max_tracked_sequences=8, num_blocks=9,
                  max_seq_len=128, max_ragged_batch_size=512)
    rng = np.random.default_rng(2)
    sched = DynamicSplitFuseScheduler(eng, token_budget=64, chunk=16)
    sched.submit(0, list(map(int, rng.integers(1, 127, 100))),
                 max_new_tokens=4)
    sched.submit(1, list(map(int, rng.integers(1, 127, 100))),
                 max_new_tokens=4)
    sched.run(max_steps=200)
    assert fresh_registry.get("serving_preemptions_total").value >= 1
    assert fresh_registry.get("serving_requests_finished_total").value == 2


def test_scheduler_oversized_request_names_max_seq_len(tiny_model,
                                                       fresh_registry):
    """Satellite fix: a request that can never fit max_seq_len must say
    so, not claim the KV pool is exhausted."""
    model, params = tiny_model
    eng = _engine(model, params, max_seq_len=64, num_blocks=17)
    sched = DynamicSplitFuseScheduler(eng, token_budget=256)
    with pytest.raises(RuntimeError, match="max_seq_len=64"):
        sched.submit(0, list(range(1, 61)), max_new_tokens=32)  # 60+32 > 64
    # boundary request still admitted: the final emitted token is never
    # fed back, so prompt + new - 1 == max_seq_len fits exactly
    sched.submit(1, list(range(1, 50)), max_new_tokens=16)  # 49+15 == 64
    sched.run(max_steps=100)
    assert len(sched.results()[1]) == 49 + 16


# -- training ---------------------------------------------------------------
def test_train_step_flushes_through_bridge_to_csv(tmp_path, fresh_registry):
    """A training step's registry scalars land in the CSV monitor backend
    on disk via the TelemetryBridge (flush_interval=1)."""
    from tests.unit.simple_model import SimpleModel, base_config

    cfg = base_config(micro=2, lr=1e-2)
    cfg["csv_monitor"] = {"enabled": True, "output_path": str(tmp_path),
                          "job_name": "run"}
    cfg["telemetry"] = {"enabled": True, "flush_interval": 1}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=16), config=cfg)
    assert engine.telemetry_bridge is not None
    gm = engine.micro_batch_size * engine.ds_config.dp_world_size
    rng = np.random.default_rng(0)
    batch = {"x": rng.standard_normal((1, gm, 16)).astype("f4"),
             "y": rng.standard_normal((1, gm, 16)).astype("f4")}
    for _ in range(3):
        engine.train_batch(batch=batch)

    reg = fresh_registry
    assert reg.get("training_steps_total").value == 3
    assert reg.get("training_loss").value == pytest.approx(
        engine._last_metrics["loss"])
    assert reg.get("training_step_seconds").count == 3

    out = tmp_path / "run"
    step_csv = out / "training_steps_total.csv"
    assert step_csv.exists(), sorted(p.name for p in out.glob("*.csv"))
    rows = list(csv.reader(open(step_csv)))
    assert rows[0] == ["step", "training_steps_total"]
    assert [float(r[1]) for r in rows[1:]] == [1, 2, 3]
    assert (out / "training_loss.csv").exists()
    assert (out / "training_step_seconds_mean.csv").exists()


def test_train_telemetry_respects_flush_interval(tmp_path, fresh_registry):
    from tests.unit.simple_model import SimpleModel, base_config

    cfg = base_config(micro=2, lr=1e-2)
    cfg["csv_monitor"] = {"enabled": True, "output_path": str(tmp_path),
                          "job_name": "run"}
    cfg["telemetry"] = {"enabled": True, "flush_interval": 2}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=16), config=cfg)
    gm = engine.micro_batch_size * engine.ds_config.dp_world_size
    rng = np.random.default_rng(0)
    batch = {"x": rng.standard_normal((1, gm, 16)).astype("f4"),
             "y": rng.standard_normal((1, gm, 16)).astype("f4")}
    for _ in range(4):
        engine.train_batch(batch=batch)
    rows = list(csv.reader(open(tmp_path / "run"
                                / "training_steps_total.csv")))
    # flushed on steps 2 and 4 only
    assert [float(r[1]) for r in rows[1:]] == [2, 4]


def test_engine_destroy_final_flushes_bridge(tmp_path, fresh_registry):
    """destroy() closes the TelemetryBridge: metrics from the last
    partial flush interval reach the CSV backend instead of being
    dropped with the engine."""
    from tests.unit.simple_model import SimpleModel, base_config

    cfg = base_config(micro=2, lr=1e-2)
    cfg["csv_monitor"] = {"enabled": True, "output_path": str(tmp_path),
                          "job_name": "run"}
    cfg["telemetry"] = {"enabled": True, "flush_interval": 100}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=16), config=cfg)
    gm = engine.micro_batch_size * engine.ds_config.dp_world_size
    rng = np.random.default_rng(0)
    batch = {"x": rng.standard_normal((1, gm, 16)).astype("f4"),
             "y": rng.standard_normal((1, gm, 16)).astype("f4")}
    for _ in range(3):
        engine.train_batch(batch=batch)
    step_csv = tmp_path / "run" / "training_steps_total.csv"
    assert not step_csv.exists()     # interval (100) never reached
    engine.destroy()
    rows = list(csv.reader(open(step_csv)))
    assert [float(r[1]) for r in rows[1:]] == [3]


def test_train_telemetry_disabled_records_nothing(fresh_registry):
    from tests.unit.simple_model import SimpleModel, base_config

    cfg = base_config(micro=2, lr=1e-2)
    cfg["telemetry"] = {"enabled": False}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=16), config=cfg)
    gm = engine.micro_batch_size * engine.ds_config.dp_world_size
    rng = np.random.default_rng(0)
    batch = {"x": rng.standard_normal((1, gm, 16)).astype("f4"),
             "y": rng.standard_normal((1, gm, 16)).astype("f4")}
    engine.train_batch(batch=batch)
    assert fresh_registry.get("training_steps_total") is None


# -- the host thread over launches and calls (telemetry/collector.py) -------
_USAGE = {"cpu_s", "runq_s", "nvcsw", "nivcsw", "majflt"}


def _sound_floor(monkeypatch):
    """The judgement on, and deaf to the sandbox's neighbours: a leaf
    has to run half a second over its median (a cold first call's
    compiles do, and must be judged by nothing)."""
    from deepspeed_tpu.telemetry import anomaly, collector, trace
    monkeypatch.setattr(collector, "STALL_MIN_S", 0.5)
    anomaly.reset()
    trace.clear()


def test_generate_samples_its_launches_and_ten_sound_calls_hold_no_stall(
        tiny_model, fresh_registry, monkeypatch):
    from deepspeed_tpu.telemetry import anomaly, trace
    _sound_floor(monkeypatch)
    model, params = tiny_model
    eng = _engine(model, params)
    rng = np.random.default_rng(0)
    for _ in range(10):
        prompts = [list(map(int, rng.integers(1, 127, n))) for n in (20, 7)]
        eng.generate(prompts, max_new_tokens=24)
    ring = trace.export()
    by_name = {name: [s for s in ring if s["name"] == name]
               for name in ("generate", "ragged_step", "decode_window")}
    assert [len(v) for v in by_name.values()] == [10, 10, 30]
    for spans in by_name.values():
        for s in spans:
            assert _USAGE <= set(s["attrs"]), s
            # (the kernel splits a thread's time into user and system
            # by the tick: their sum over an interval is out by one)
            assert 0 <= s["attrs"]["cpu_s"] <= s["duration_s"] + 1e-2
    # a root's CPU is its segments': the launches' and what lay between
    for root in by_name["generate"]:
        inside = sum(s["attrs"]["cpu_s"]
                     for name in ("ragged_step", "decode_window")
                     for s in by_name[name] if s["parent"] == root["id"])
        assert inside <= root["attrs"]["cpu_s"] + 1e-6
        assert root["attrs"]["rows"] == 2       # what it opened with stays
    cpu = fresh_registry.get("host_thread_cpu_seconds_total")
    total = sum(s.value for _, s in cpu.series())
    assert total == pytest.approx(
        sum(r["attrs"]["cpu_s"] for r in by_name["generate"]), rel=1e-6)
    # a leaf carries none: two samples a launch, none a leaf
    assert all("cpu_s" not in s.get("attrs", {}) for s in ring
               if s["name"] in ("window_fetch", "window_call",
                                "gen_schedule", "ragged_call"))
    assert [s for s in ring if s["name"] == "host_stall"] == []
    assert [v for v in anomaly.recent() if v["kind"] == "host_stall"] == []
    assert fresh_registry.family_total("host_stalls_total") == 0
    # put() alone: its launch carries the attrs and counts as a launch
    eng.put([7], [prompts[0]])
    eng.flush(7)
    assert _USAGE <= set(trace.export("ragged_step")[-1]["attrs"])


def test_train_batch_samples_its_step_and_ten_sound_steps_hold_no_stall(
        fresh_registry, monkeypatch):
    from deepspeed_tpu.telemetry import anomaly, trace
    from tests.unit.simple_model import SimpleModel, base_config
    _sound_floor(monkeypatch)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=16), config=base_config(micro=2,
                                                             lr=1e-2))
    gm = engine.micro_batch_size * engine.ds_config.dp_world_size
    rng = np.random.default_rng(0)
    batch = {"x": rng.standard_normal((1, gm, 16)).astype("f4"),
             "y": rng.standard_normal((1, gm, 16)).astype("f4")}
    for _ in range(10):
        engine.train_batch(batch=batch)
    steps = trace.export("train_step")
    assert len(steps) == 10
    for s in steps:
        assert _USAGE <= set(s["attrs"]) and "step" in s["attrs"]
        assert s["parent"] is None          # no root was put over it
    cpu = fresh_registry.get("host_thread_cpu_seconds_total")
    assert cpu.labels(path="train", where="launch").value == pytest.approx(
        sum(s["attrs"]["cpu_s"] for s in steps))
    assert cpu.labels(path="train", where="between").value > 0
    assert trace.export("host_stall") == []
    assert [v for v in anomaly.recent() if v["kind"] == "host_stall"] == []
    # every leaf of a batch has a history of its own kind
    assert {k[0] for k in engine._host._kept} == {
        "train_data", "train_device_dispatch", "train_host_sync",
        "train_bookkeeping"}
    assert all(len(v) == 10 for v in engine._host._kept.values())


def test_train_telemetry_disabled_samples_and_judges_nothing(
        fresh_registry, monkeypatch):
    """``telemetry: {enabled: false}`` means what it meant: the spans
    are there, and no sample, series or judgement of the host thread."""
    from deepspeed_tpu.telemetry import anomaly, collector, trace
    from tests.unit.simple_model import SimpleModel, base_config
    monkeypatch.setattr(collector, "STALL_MIN_S", 0.0)
    monkeypatch.setattr(collector, "STALL_OVER", 0.0)
    anomaly.reset()
    trace.clear()
    cfg = base_config(micro=2, lr=1e-2)
    cfg["telemetry"] = {"enabled": False}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=16), config=cfg)
    assert engine._host is None
    gm = engine.micro_batch_size * engine.ds_config.dp_world_size
    rng = np.random.default_rng(0)
    batch = {"x": rng.standard_normal((1, gm, 16)).astype("f4"),
             "y": rng.standard_normal((1, gm, 16)).astype("f4")}
    for _ in range(10):
        engine.train_batch(batch=batch)
    steps = trace.export("train_step")
    assert len(steps) == 10
    assert all(set(s["attrs"]) == {"step"} for s in steps)
    assert fresh_registry.get("host_thread_cpu_seconds_total") is None
    assert fresh_registry.get("host_stalls_total") is None
    assert trace.export("host_stall") == []
    assert [v for v in anomaly.recent() if v["kind"] == "host_stall"] == []
