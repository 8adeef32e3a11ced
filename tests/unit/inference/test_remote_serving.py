"""Remote serving plane: socket-backed replicas (serve/remote.py +
serve/worker.py).

Tier-1 runs everything over LOOPBACK sockets in one process — a real
HTTP hop (serialization, framing, trace headers) without subprocess
spawn cost; the true subprocess spawn/drain/kill smoke is ``-m slow``.

Pinned contracts (ISSUE 12 acceptance):
  * a routed request served through a RemoteReplica produces a token
    stream bit-identical to the in-process replica path (greedy AND
    seeded sampling);
  * ONE trace id crosses the socket: the worker continues the caller's
    traceparent, the tail NDJSON line echoes it, and the worker-side
    engine spans carry it;
  * health/load/heartbeat map from /healthz; drain-over-socket finishes
    in-flight streams then sheds; a vanished worker reads as dead;
  * the router's federated /metrics includes the remote replica's
    series under its replica label.
"""

import asyncio
import os
import signal

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.config_v2 import DSStateManagerConfig
from deepspeed_tpu.inference.v2.serve import (OverloadedError,
                                              RemoteReplica,
                                              ReplicaRouter, ReplicaWorker,
                                              RouterConfig, ServingConfig,
                                              ServingEngine)
from deepspeed_tpu.telemetry import context as trace_context


@pytest.fixture(scope="module")
def model_and_params(tiny_model_256):
    return tiny_model_256


def _engine(model, params, **sm_kw):
    sm = dict(max_tracked_sequences=8, max_seq_len=256, num_blocks=65,
              block_size=16, max_ragged_batch_size=512)
    sm.update(sm_kw)
    return InferenceEngineV2(
        model, RaggedInferenceEngineConfig(
            state_manager=DSStateManagerConfig(**sm), dtype="float32",
            prefill_bucket=16), params=params)


def _serving_config(**kw):
    kw.setdefault("token_budget", 64)
    kw.setdefault("chunk", 16)
    return ServingConfig(**kw)


def _prompts(ns, seed=0):
    rng = np.random.default_rng(seed)
    return [list(map(int, rng.integers(1, 127, n))) for n in ns]


_REQ_KW = [dict(temperature=0.0), dict(temperature=0.0),
           dict(temperature=0.8, top_p=0.9, seed=11),
           dict(temperature=0.7, top_k=20, seed=5)]


async def _start_worker(model, params, name="rw0", **serving_kw):
    worker = ReplicaWorker(_engine(model, params),
                           _serving_config(**serving_kw), name=name)
    host, port = await worker.start()
    return worker, host, port


async def _drive_single(model, params, prompts, kws, max_new=12):
    serving = ServingEngine(_engine(model, params), _serving_config())
    await serving.start()
    streams = [await serving.submit(p, max_new, **kw)
               for p, kw in zip(prompts, kws)]
    outs = [await s.drain() for s in streams]
    await serving.stop()
    return outs


# -- routed-through-a-socket streams bit-identical -------------------------
def test_remote_routed_streams_bit_identical(model_and_params):
    model, params = model_and_params
    prompts = _prompts((20, 7, 33, 12))

    async def remote_routed():
        w0, h0, p0 = await _start_worker(model, params, "rw0")
        w1, h1, p1 = await _start_worker(model, params, "rw1")
        router = ReplicaRouter(
            [RemoteReplica("rw0", h0, p0),
             RemoteReplica("rw1", h1, p1)],
            RouterConfig(monitor_interval_s=0.0))
        await router.start()
        try:
            streams = [await router.submit(p, 12, **kw)
                       for p, kw in zip(prompts, _REQ_KW)]
            outs = [await s.drain() for s in streams]
            names = {s.replica for s in streams}
            health = router.health()
        finally:
            await router.stop()
            await w0.stop()
            await w1.stop()
        return outs, names, health

    single = asyncio.run(_drive_single(model, params, prompts, _REQ_KW))
    outs, names, health = asyncio.run(remote_routed())
    assert outs == single, \
        "socket-routed streams must be bit-identical to in-process"
    assert names <= {"rw0", "rw1"}
    assert set(health["replicas"]) == {"rw0", "rw1"}


# -- one trace id across the socket ----------------------------------------
def test_trace_id_continuous_across_socket(model_and_params):
    model, params = model_and_params

    async def run():
        worker, host, port = await _start_worker(model, params, "rw0")
        replica = RemoteReplica("rw0", host, port)
        await replica.start()
        ctx = trace_context.new_context(tenant="remote-test")
        try:
            with trace_context.use(ctx):
                stream = await replica.submit(_prompts((18,))[0], 6)
            toks = await stream.drain()
            assert len(toks) == 6
            # the tail line echoes the CALLER's trace id — the worker
            # continued it rather than minting a root
            assert stream.trace_id == ctx.trace_id
            # and the worker-side engine spans carry it
            spans = await replica.fetch_spans()
        finally:
            await worker.stop()
        return ctx.trace_id, spans

    tid, spans = asyncio.run(run())
    carried = [s for s in spans
               if tid in str(s.get("attrs", {}).get("trace_ids", ""))
               or s.get("attrs", {}).get("trace_id") == tid]
    assert carried, \
        "worker-side spans must carry the caller's trace id"
    assert all(s.get("lane") == "rw0" for s in carried)


# -- health / load / heartbeat mapping + drain over the socket -------------
def test_remote_health_and_drain(model_and_params):
    model, params = model_and_params

    async def run():
        worker, host, port = await _start_worker(model, params, "rw0")
        replica = RemoteReplica("rw0", host, port,
                                probe_interval_s=0.0)
        await replica.start()
        assert replica.alive()
        assert replica.block_size == 16
        assert replica.load() == 0.0
        assert replica.health()["status"] == "ok"
        # an in-flight stream survives drain; post-drain submits shed
        stream = await replica.submit(_prompts((10,))[0], 8)
        drainer = asyncio.ensure_future(stream.drain())
        await replica.drain()
        toks = await drainer
        assert len(toks) == 8 and stream.status == "completed"
        with pytest.raises(OverloadedError) as ei:
            await replica.submit(_prompts((5,))[0], 4)
        assert ei.value.reason == "draining"
        await replica.refresh(force=True)
        assert replica.health()["status"] == "draining"
        await worker.stop()
        # a vanished worker reads as not-alive on the next refresh
        await replica.refresh(force=True)
        assert not replica.alive()

    asyncio.run(run())


# -- federated /metrics includes the remote replica ------------------------
def test_federated_metrics_include_remote(model_and_params):
    model, params = model_and_params

    async def run():
        worker, host, port = await _start_worker(model, params, "rwm")
        router = ReplicaRouter([RemoteReplica("rwm", host, port)],
                               RouterConfig(monitor_interval_s=0.0))
        await router.start()
        try:
            stream = await router.submit(_prompts((12,))[0], 4)
            await stream.drain()
            text = await router.federated_metrics_async()
        finally:
            await router.stop()
            await worker.stop()
        return text

    text = asyncio.run(run())
    assert 'replica="rwm"' in text, \
        "remote replica series must federate under its replica label"
    assert "serving_admission_admitted_total" in text


# -- true subprocess spawn / drain / kill (slow tier) ----------------------
@pytest.mark.slow
def test_worker_subprocess_spawn_drain_kill(tmp_path):
    from deepspeed_tpu.inference.v2.serve import spawn_worker
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # ISOLATED compile cache: a worker SIGKILLed on a failure path must
    # never be able to poison the shared suite cache
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "xla-cache")
    # the spawn helper owns the handshake: ready-line wait under an
    # explicit timeout, stderr surfaced if the worker dies first
    proc, info = spawn_worker(
        ["--name", "sub0", "--jax-platform", "cpu"],
        timeout_s=120.0, env=env)
    try:
        assert info["name"] == "sub0" and info["block_size"] == 16

        async def run():
            replica = RemoteReplica("sub0", info["host"], info["port"],
                                    probe_timeout_s=30.0)
            await replica.start()
            stream = await replica.submit(list(range(1, 13)), 5)
            toks = await stream.drain()
            assert len(toks) == 5
            await replica.drain()
            with pytest.raises(OverloadedError):
                await replica.submit([1, 2, 3], 2)
            await replica.stop()     # process exits on /stop

        asyncio.run(run())
        assert proc.wait(timeout=30) == 0
    finally:
        if proc.poll() is None:
            proc.send_signal(signal.SIGKILL)
            proc.wait(timeout=10)
