"""Performance forensics over the serving stack: the recompile watchdog
mirrors the decode-bucket cache behavior (zero steady-state recompiles
on the fused path), and the /debug/timeline + /statusz HTTP surfaces
serve one request's full lifeline and the forensics snapshot."""

import asyncio
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.config_v2 import DSStateManagerConfig
from deepspeed_tpu.inference.v2.serve import (ServingAPI, ServingConfig,
                                              ServingEngine)
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.telemetry import (MetricsRegistry, get_registry,
                                     set_registry, trace, watchdog)


@pytest.fixture(autouse=True)
def _fresh():
    prev = set_registry(MetricsRegistry())
    watchdog.reset()
    trace.clear()
    yield get_registry()
    watchdog.reset()
    trace.clear()
    set_registry(prev)


@pytest.fixture(scope="module")
def tiny(tiny_model_128):
    # session-shared tiny model (tests/unit/conftest.py): one
    # init_params for the whole tier instead of one per module
    return tiny_model_128


def _engine(model, params, window=8):
    return InferenceEngineV2(
        model, RaggedInferenceEngineConfig(
            state_manager=DSStateManagerConfig(
                max_tracked_sequences=8, max_seq_len=128, num_blocks=65,
                block_size=16),
            dtype="float32", prefill_bucket=16, decode_window=window),
        params=params)


def _compiles(reg, program):
    fam = reg.get("xla_compile_events_total")
    return fam.labels(program=program).value if fam else 0.0


def _steady_total(reg):
    fam = reg.get("xla_steady_state_recompiles_total")
    return sum(s.value for _, s in fam.series()) if fam else 0.0


def test_watchdog_matches_bucket_cache_behavior(tiny, _fresh):
    """Watchdog compile counts mirror the jit cache exactly: one fused
    program per power-of-two batch bucket, and the shape signature of
    each compile is recorded (the test_fused_decode cache assertions,
    observable through telemetry)."""
    model, params = tiny
    eng = _engine(model, params, window=4)
    prompts3 = [[2, 4, 6], [3, 5, 7], [4, 6, 8]]
    eng.generate(prompts3, max_new_tokens=6)        # batch 3 -> bucket 4
    reg = _fresh
    assert _compiles(reg, "decode_window_greedy") == \
        eng._fused_greedy_jit._cache_size() == 1
    eng.generate(prompts3 + [[5, 7, 9]], max_new_tokens=6,
                 uids=[10, 11, 12, 13])             # batch 4 -> bucket 4
    assert _compiles(reg, "decode_window_greedy") == 1   # cache reuse
    eng.generate(prompts3[:2], max_new_tokens=6,
                 uids=[20, 21])                     # batch 2 -> bucket 2
    assert _compiles(reg, "decode_window_greedy") == \
        eng._fused_greedy_jit._cache_size() == 2
    # the prompt phase compiled its (ragged) bucket program too, and
    # every event carries its shapes
    assert _compiles(reg, "ragged_step") >= 1
    assert all(e["signature"] for e in watchdog.events())


def test_zero_steady_state_recompiles_on_fused_path(tiny, _fresh):
    """The acceptance bar: after warmup passes over the workload's
    buckets, steady-state serving compiles NOTHING — repeat traffic and
    a same-bucket batch-size change stay on cached programs. Warmup
    replays each bucket twice: a bucket's first call compiles against
    the fresh (unsharded) KV pool and its repeat against the donated
    sharded cache, a one-time respecialization steady state must not
    see."""
    model, params = tiny
    eng = _engine(model, params, window=8)
    prompts = [[2, 4, 6, 8], [3, 5, 7]]
    eng.generate(prompts, max_new_tokens=12)            # bucket-2 warmup
    eng.generate(prompts[:1], max_new_tokens=12, uids=[5])  # bucket 1
    eng.generate(prompts, max_new_tokens=12, uids=[6, 7])   # 2nd warm
    eng.generate(prompts[:1], max_new_tokens=12, uids=[8])
    events = _fresh.family_total("xla_compile_events_total")
    watchdog.mark_steady(True)
    try:
        eng.generate(prompts, max_new_tokens=12, uids=[10, 11])
        eng.generate(prompts[:1], max_new_tokens=12, uids=[20])
    finally:
        watchdog.mark_steady(False)
    assert _steady_total(_fresh) == 0
    # nor a compile of any other kind: the event count stood still
    assert _fresh.family_total("xla_compile_events_total") == events
    # and a genuinely new bucket AT steady state is loudly counted
    watchdog.mark_steady(True)
    try:
        eng.generate([[1, 2], [3, 4], [5, 6]], max_new_tokens=4,
                     uids=[30, 31, 32])             # bucket 4: new program
    finally:
        watchdog.mark_steady(False)
    assert _steady_total(_fresh) >= 1


def test_debug_timeline_and_statusz_endpoints(tiny, _fresh):
    """GET /debug/timeline returns valid Chrome trace JSON covering one
    request's lifeline (queue -> prefill -> decode -> finish) when
    filtered by uid; GET /statusz bundles health + watchdog + memory."""
    model, params = tiny
    eng = _engine(model, params)
    eng.memory_report()     # populate program/buffer forensics

    async def main():
        serving = ServingEngine(eng, ServingConfig(token_budget=64,
                                                   chunk=16))
        await serving.start()
        api = ServingAPI(serving)
        host, port = await api.start()

        async def http(target):
            reader, writer = await asyncio.open_connection(host, port)
            writer.write((f"GET {target} HTTP/1.1\r\nHost: t\r\n"
                          f"Content-Length: 0\r\n\r\n").encode())
            await writer.drain()
            raw = await reader.read()
            writer.close()
            head, _, rest = raw.partition(b"\r\n\r\n")
            return int(head.split()[1]), rest

        status, rest = await http("/generate")  # wrong method -> 404
        assert status == 404

        # run one request through the serving stack
        stream = await serving.submit([2, 4, 6, 8], 6)
        toks = await stream.drain()
        assert len(toks) == 6
        uid = stream.uid

        status, rest = await http(f"/debug/timeline?uid={uid}")
        assert status == 200
        tl = json.loads(rest)
        names = [e["name"] for e in tl["traceEvents"] if e["ph"] == "X"]
        for phase in ("request_queue", "request_prefill",
                      "request_decode", "request"):
            assert phase in names, names

        status, rest = await http("/debug/timeline")
        assert status == 200
        full = json.loads(rest)
        assert len(full["traceEvents"]) >= len(tl["traceEvents"])
        status, _ = await http("/debug/timeline?uid=notanint")
        assert status == 400

        status, rest = await http("/statusz")
        assert status == 200
        sz = json.loads(rest)
        assert sz["health"]["status"] == "ok"
        assert "programs" in sz["compile"]
        assert sz["memory"]["buffers"], sz["memory"]
        assert sz["memory"]["largest_program"]
        assert sz["metric_families"] > 0

        await api.stop()
        await serving.stop()

    asyncio.run(main())


def test_serving_drain_closes_bridge(tiny, _fresh):
    """The ServingLoop final-flushes an attached TelemetryBridge on
    drain: metrics recorded since the last flush interval reach the
    monitor even when the interval never elapsed."""
    from deepspeed_tpu.telemetry import TelemetryBridge

    class Mon:
        enabled = True

        def __init__(self):
            self.events = []

        def write_events(self, evs):
            self.events.extend(evs)

    model, params = tiny
    eng = _engine(model, params)
    mon = Mon()
    bridge = TelemetryBridge(mon, flush_interval=1000)  # never on cadence

    async def main():
        serving = ServingEngine(eng, ServingConfig(token_budget=64,
                                                   chunk=16),
                                bridge=bridge)
        await serving.start()
        stream = await serving.submit([2, 4, 6], 4)
        await stream.drain()
        assert not mon.events          # cadence never reached
        await serving.stop()           # graceful drain -> close()

    asyncio.run(main())
    tags = {t for t, _, _ in mon.events}
    assert "serving_requests_finished_total" in tags
    # close() is idempotent: a second close writes nothing more
    n = len(mon.events)
    assert bridge.close() is False and len(mon.events) == n
