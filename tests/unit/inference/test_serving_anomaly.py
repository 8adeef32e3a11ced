"""Injected-fault e2e for the serving anomaly path (ISSUE 6
acceptance): a wedged decode loop trips the stall watchdog within the
configured deadline (with thread stacks); a skipped KV free path is
reported at drain; /statusz serves anomaly + SLO-quantile state; POST
/debug/postmortem writes a bundle."""

import asyncio
import json
import os
import time

import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.config_v2 import DSStateManagerConfig
from deepspeed_tpu.inference.v2.serve import (ServingAPI, ServingConfig,
                                              ServingEngine)
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.telemetry import (DiagnosticsConfig, FlightRecorder,
                                     MetricsRegistry, get_recorder,
                                     get_registry, set_recorder,
                                     set_registry, trace, watchdog)
from deepspeed_tpu.telemetry import anomaly, postmortem


@pytest.fixture(autouse=True)
def _fresh():
    prev_reg = set_registry(MetricsRegistry())
    prev_rec = set_recorder(FlightRecorder())
    anomaly.reset()
    postmortem._reset_for_tests()
    watchdog.reset()
    trace.clear()
    yield get_registry()
    anomaly.reset()
    postmortem._reset_for_tests()
    watchdog.reset()
    trace.clear()
    set_recorder(prev_rec)
    set_registry(prev_reg)


@pytest.fixture(scope="module")
def tiny(tiny_model_128):
    # session-shared tiny model (tests/unit/conftest.py): one
    # init_params for the whole tier instead of one per module
    return tiny_model_128


def _engine(model, params):
    return InferenceEngineV2(
        model, RaggedInferenceEngineConfig(
            state_manager=DSStateManagerConfig(
                max_tracked_sequences=8, max_seq_len=128, num_blocks=65,
                block_size=16),
            dtype="float32", prefill_bucket=16, decode_window=4),
        params=params)


def _anomaly_count(kind):
    fam = get_registry().get("anomaly_events_total")
    return fam.labels(kind=kind).value if fam else 0.0


def test_serving_records_request_and_kv_events(tiny, _fresh):
    """The black box covers a request's whole life: admit ->
    submit -> ragged prompt step -> decode windows -> kv alloc/free ->
    finish."""
    model, params = tiny
    eng = _engine(model, params)

    async def main():
        serving = ServingEngine(eng, ServingConfig(token_budget=64,
                                                   chunk=16))
        await serving.start()
        stream = await serving.submit([2, 4, 6, 8], 6)
        await stream.drain()
        await serving.stop()

    asyncio.run(main())
    kinds = {e["kind"] for e in get_recorder().events()}
    for expected in ("admit", "request_submit", "ragged_step",
                     "decode_window", "kv_alloc", "kv_free",
                     "request_finish", "xla_compile",
                     "kv_drain_clean"):
        assert expected in kinds, (expected, sorted(kinds))
    # clean run: nothing anomalous
    assert anomaly.recent() == []


def test_stalled_decode_loop_trips_watchdog(tiny, _fresh):
    """Wedge scheduler.step() mid-request: the stall watchdog thread
    must raise a `stall` verdict (with thread stacks) within the
    configured deadline, while the loop is still blocked."""
    import threading

    model, params = tiny
    eng = _engine(model, params)
    # pre-compile the workload's buckets: a first-step compile inside
    # the serving loop would itself outrun the tight 0.2s stall
    # deadline and burn the verdict before the wedge
    eng.generate([[2, 4, 6, 8]], max_new_tokens=8)
    release = threading.Event()

    async def warm():
        # generate() does not reach every program the serving loop
        # launches: tracing one and reading it from the compile cache took
        # 0.25 s in the loop's first step (the verdict's stack was inside
        # compile_or_get_cached, half the runs on an idle machine). Serve
        # the same request once with the default, lenient deadlines
        serving = ServingEngine(eng, ServingConfig(token_budget=64,
                                                   chunk=16))
        await serving.start()
        await (await serving.submit([2, 4, 6, 8], 8)).drain()
        await serving.stop()

    asyncio.run(warm())
    assert _anomaly_count("stall") == 0

    async def main():
        cfg = ServingConfig(
            token_budget=64, chunk=16,
            diagnostics=DiagnosticsConfig(stall_min_deadline_s=0.2,
                                          stall_check_interval_s=0.05))
        serving = ServingEngine(eng, cfg)
        real_step = serving.scheduler.step
        state = {"n": 0}

        def wedged_step():
            state["n"] += 1
            if state["n"] == 2:      # wedge mid-request, after warmup
                release.wait(timeout=10.0)
            return real_step()

        serving.scheduler.step = wedged_step
        await serving.start()
        stream = await serving.submit([2, 4, 6, 8], 8)
        # wait for the watchdog to catch the wedged loop
        deadline = time.time() + 5.0
        while _anomaly_count("stall") == 0 and time.time() < deadline:
            await asyncio.sleep(0.05)
        count = _anomaly_count("stall")
        release.set()
        toks = await stream.drain()
        await serving.stop()
        return count, toks

    count, toks = asyncio.run(main())
    assert count == 1, "stall verdict while the loop was wedged"
    assert len(toks) == 8, "request still completes after the wedge"
    v = [a for a in anomaly.recent() if a["kind"] == "stall"][-1]
    assert v["channel"] == "serving_loop"
    assert v["stacks"], "stall verdict must carry thread stacks"
    # the wedged frame is visible in the dump
    assert any("wedged_step" in "".join(frames)
               for frames in v["stacks"].values())
    # recovery recorded once the loop beat again
    assert get_recorder().events(kind="stall_recovered")


def test_skipped_kv_free_is_reported_at_drain(tiny, _fresh):
    """The acceptance scenario: suppress the engine's free path for one
    uid; the drain-time reconciliation names it as a leak."""
    model, params = tiny
    eng = _engine(model, params)
    real_flush = eng.flush
    leak_uids = set()

    def leaky_flush(uid):
        if uid in leak_uids:
            return           # free path 'forgotten'
        real_flush(uid)

    eng.flush = leaky_flush

    async def main():
        serving = ServingEngine(eng, ServingConfig(token_budget=64,
                                                   chunk=16))
        await serving.start()
        s1 = await serving.submit([2, 4, 6, 8], 4)
        leak_uids.add(s1.uid)
        s2 = await serving.submit([3, 5, 7], 4)
        await s1.drain()
        await s2.drain()
        await serving.stop()
        return s1.uid

    leaked_uid = asyncio.run(main())
    assert _anomaly_count("kv_leak") == 1
    v = [a for a in anomaly.recent() if a["kind"] == "kv_leak"][-1]
    assert v["orphan_uids"] == [leaked_uid]
    assert v["orphan_blocks"] >= 1


def test_clean_drain_raises_no_leak(tiny, _fresh):
    model, params = tiny
    eng = _engine(model, params)

    async def main():
        serving = ServingEngine(eng, ServingConfig(token_budget=64,
                                                   chunk=16))
        await serving.start()
        stream = await serving.submit([2, 4, 6], 4)
        await stream.drain()
        await serving.stop()

    asyncio.run(main())
    assert _anomaly_count("kv_leak") == 0
    assert get_recorder().events(kind="kv_drain_clean")


def test_statusz_and_postmortem_endpoints(tiny, tmp_path, _fresh):
    """/statusz bundles anomalies + SLO quantiles/burn; POST
    /debug/postmortem writes a bundle and returns its manifest."""
    model, params = tiny
    eng = _engine(model, params)

    async def main():
        cfg = ServingConfig(
            token_budget=64, chunk=16,
            diagnostics=DiagnosticsConfig(
                postmortem_dir=str(tmp_path), stall_enabled=False))
        serving = ServingEngine(eng, cfg)
        await serving.start()
        api = ServingAPI(serving)
        host, port = await api.start()

        async def http(method, target):
            reader, writer = await asyncio.open_connection(host, port)
            writer.write((f"{method} {target} HTTP/1.1\r\nHost: t\r\n"
                          f"Content-Length: 0\r\n\r\n").encode())
            await writer.drain()
            raw = await reader.read()
            writer.close()
            head, _, rest = raw.partition(b"\r\n\r\n")
            return int(head.split()[1]), rest

        stream = await serving.submit([2, 4, 6, 8], 6)
        await stream.drain()
        anomaly.report("stall", "synthetic verdict for statusz")

        status, rest = await http("GET", "/statusz")
        assert status == 200
        sz = json.loads(rest)
        assert sz["anomalies"]["recent"][-1]["kind"] == "stall"
        assert sz["recorder"]["recorded"] > 0
        assert "ttft" in sz["slo"]["quantiles"]
        q = sz["slo"]["quantiles"]["ttft"]
        assert q["count"] >= 1 and q["p50"] is not None
        assert "fast" in sz["slo"]["burn"]["ttft"]

        status, rest = await http("POST", "/debug/postmortem")
        assert status == 200
        pm = json.loads(rest)
        assert os.path.isdir(pm["path"])
        assert str(tmp_path) in pm["path"]
        for section in ("metrics", "recorder", "anomalies"):
            assert section in pm["manifest"]["files"]
        with open(os.path.join(pm["path"], "anomalies.json")) as fh:
            assert any(a["kind"] == "stall" for a in json.load(fh))
        # GET on the postmortem route is not a thing
        status, _ = await http("GET", "/debug/postmortem")
        assert status == 404

        await api.stop()
        await serving.stop()

    asyncio.run(main())


def test_step_error_raises_serving_anomaly(tiny, _fresh):
    """A step-time engine failure fails the in-flight requests AND
    leaves a serving_step_error verdict behind."""
    model, params = tiny
    eng = _engine(model, params)

    async def main():
        serving = ServingEngine(eng, ServingConfig(token_budget=64,
                                                   chunk=16))
        real_step = serving.scheduler.step
        state = {"n": 0}

        def exploding_step():
            state["n"] += 1
            if state["n"] == 2:
                raise RuntimeError("injected step failure")
            return real_step()

        serving.scheduler.step = exploding_step
        await serving.start()
        stream = await serving.submit([2, 4, 6, 8], 8)
        from deepspeed_tpu.inference.v2.serve.frontend import \
            RequestFailed
        with pytest.raises(RequestFailed, match="injected"):
            await stream.drain()
        await serving.stop()

    asyncio.run(main())
    assert _anomaly_count("serving_step_error") == 1
    v = [a for a in anomaly.recent()
         if a["kind"] == "serving_step_error"][-1]
    assert v["failed_uids"]
