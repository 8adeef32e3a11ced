"""Multi-host serving tier tests (`inference/v2/serve/router.py`).

Chip-free e2e over in-process replicas (ISSUE 8 acceptance): routed
streams bit-identical to single-engine serving (greedy AND fixed-seed
sampled), prefix-affinity placement beating random placement on a
shared-prefix workload, drain finishing in-flight streams while new
traffic diverts, heartbeat-expiry failover re-enqueueing queued
requests, and the disaggregated prefill->decode KV handoff pinned
bit-identical to colocated serving."""

import asyncio

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.config_v2 import DSStateManagerConfig
from deepspeed_tpu.inference.v2.serve import (AdmissionConfig,
                                              OverloadedError,
                                              PrefillReplica,
                                              ReplicaRouter, RouterConfig,
                                              ServingAPI, ServingConfig,
                                              ServingEngine,
                                              build_replicas)
from deepspeed_tpu.inference.v2.serve import handoff
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.telemetry import get_registry
from deepspeed_tpu.telemetry.anomaly import DiagnosticsConfig


@pytest.fixture(scope="module")
def model_and_params(tiny_model_256):
    # session-shared tiny model (tests/unit/conftest.py): one
    # init_params for the whole tier instead of one per module
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


# the mixed request shapes every parity test reuses: greedy and
# fixed-seed sampled requests composed into the same traffic
_REQ_KW = [dict(temperature=0.0), dict(temperature=0.0),
           dict(temperature=0.8, top_p=0.9, seed=11),
           dict(temperature=0.7, top_k=20, seed=5)]


async def _drive_single(model, params, prompts, kws, max_new=12):
    serving = ServingEngine(_engine(model, params), _serving_config())
    await serving.start()
    streams = [await serving.submit(p, max_new, **kw)
               for p, kw in zip(prompts, kws)]
    outs = [await s.drain() for s in streams]
    await serving.stop()
    return outs


# -- bit-identical routed streams (acceptance a) ---------------------------
def test_routed_streams_bit_identical_to_single_engine(model_and_params):
    model, params = model_and_params
    prompts = _prompts((20, 7, 33, 12))

    async def routed():
        replicas = build_replicas(
            [_engine(model, params), _engine(model, params)],
            _serving_config())
        router = ReplicaRouter(replicas, RouterConfig())
        await router.start()
        streams = [await router.submit(p, 12, **kw)
                   for p, kw in zip(prompts, _REQ_KW)]
        outs = [await s.drain() for s in streams]
        names = {s.replica for s in streams}
        health = router.health()
        await router.stop()
        return outs, names, health

    single = asyncio.run(_drive_single(model, params, prompts, _REQ_KW))
    outs, names, health = asyncio.run(routed())
    assert all(len(o) == 12 for o in outs)
    assert outs == single, \
        "routed token streams must be bit-identical to single-engine"
    assert names <= {"replica0", "replica1"}
    assert set(health["replicas"]) == {"replica0", "replica1"}


# -- prefix affinity beats random placement (acceptance b) -----------------
def _shared_prefix_workload(groups=2, per_group=4, prefix_len=32,
                            tail_len=6, seed=3):
    """G groups of requests sharing a block-aligned per-group prefix
    with distinct tails — the workload where placement decides the
    prefix-cache hit rate."""
    rng = np.random.default_rng(seed)
    prompts = []
    for g in range(groups):
        prefix = list(map(int, rng.integers(1, 127, prefix_len)))
        for _ in range(per_group):
            prompts.append(prefix
                           + list(map(int, rng.integers(1, 127, tail_len))))
    return prompts


def _run_placement(model, params, prompts, placement):
    """Sequential routed run (each request drains before the next is
    submitted, so flush-time prefix registration is visible to the next
    arrival); returns the prefix-cache hit fraction across replicas."""

    async def run():
        replicas = build_replicas(
            [_engine(model, params, enable_prefix_caching=True),
             _engine(model, params, enable_prefix_caching=True)],
            _serving_config())
        router = ReplicaRouter(replicas,
                               RouterConfig(placement=placement))
        reg = get_registry()
        hits0 = reg.family_total("inference_prefix_hits_total")
        await router.start()
        for p in prompts:
            stream = await router.submit(p, 4)
            await stream.drain()
        await router.stop()
        hits = reg.family_total("inference_prefix_hits_total") - hits0
        # fraction of REQUESTS that reused cached prefix blocks (a miss
        # probes the index twice — scheduler then engine — so lookups
        # over-count; requests are the stable denominator)
        return hits / len(prompts)

    return asyncio.run(run())


def test_prefix_affinity_beats_random_placement(model_and_params):
    model, params = model_and_params
    prompts = _shared_prefix_workload()
    affinity = _run_placement(model, params, prompts, "affinity")
    random_ = _run_placement(model, params, prompts, "round_robin")
    # affinity: only each group's FIRST request misses; round robin
    # spreads each group over both replicas, so each replica pays its
    # own first-miss per group
    assert affinity > random_, (affinity, random_)
    assert affinity >= 0.75 - 1e-9
    reg = get_registry()
    assert reg.family_total("router_affinity_hits_total") > 0


# -- drain without dropping in-flight streams (acceptance c) ---------------
def test_drained_replica_finishes_stream_and_traffic_diverts(
        model_and_params):
    model, params = model_and_params

    async def run():
        replicas = build_replicas(
            [_engine(model, params), _engine(model, params)],
            _serving_config())
        router = ReplicaRouter(replicas,
                               RouterConfig(placement="round_robin"))
        await router.start()
        prompts = _prompts((24, 18, 9, 15), seed=7)
        stream = await router.submit(prompts[0], 24)
        # the round-robin cursor sent the first request to replica0
        victim = stream.replica
        drain_task = asyncio.ensure_future(router.drain_replica(victim))
        await asyncio.sleep(0)      # drain marks the state immediately
        later = [await router.submit(p, 6) for p in prompts[1:]]
        assert all(s.replica != victim for s in later), \
            "new traffic must divert off the draining replica"
        toks = await stream.drain()
        await drain_task
        assert stream.status == "completed" and len(toks) == 24, \
            "the draining replica must finish its in-flight stream"
        assert router._by_name[victim].state == "drained"
        # a drained replica is out of rotation but the fleet still serves
        for s in later:
            assert (await s.drain()) and s.status == "completed"
        health = router.health()
        assert victim not in health["routable"]
        await router.stop()

    asyncio.run(run())


# -- dead-replica failover (satellite: lifecycle) --------------------------
def test_dead_replica_heartbeat_expiry_requeues_queued_requests(
        model_and_params):
    """Wedge one replica's scheduler mid-step: the router's heartbeat
    check declares it dead, re-enqueues its queued (not-yet-prefilled)
    requests onto the survivor, and they complete there."""
    import threading

    model, params = model_and_params
    eng0 = _engine(model, params)
    eng1 = _engine(model, params)
    # pre-compile the buckets so the wedge (not a first-compile stall)
    # is what the heartbeat sees
    eng0.generate(_prompts((20,)), max_new_tokens=4)
    release = threading.Event()

    async def run():
        cfg = _serving_config(
            max_inflight=1,
            diagnostics=DiagnosticsConfig(stall_min_deadline_s=0.05,
                                          stall_check_interval_s=0.02))
        replicas = build_replicas([eng0, eng1], cfg)
        # the survivor compiles its first buckets inside this test (~0.5 s
        # a bucket alone, over a second beside five other xdist workers):
        # the heartbeat has to outlast that, the wedge outlasts both
        router = ReplicaRouter(
            replicas, RouterConfig(placement="round_robin",
                                   heartbeat_timeout_s=3.0,
                                   monitor_interval_s=0.0))
        await router.start()
        real_step = replicas[0].serving.scheduler.step

        def wedged_step():
            release.wait(timeout=20.0)
            return real_step()

        replicas[0].serving.scheduler.step = wedged_step
        prompts = _prompts((20, 16, 12), seed=9)
        # round robin: A -> replica0 (wedges mid-step), B -> replica1,
        # C -> replica0 (stays queued behind max_inflight=1)
        a = await router.submit(prompts[0], 6)
        b = await router.submit(prompts[1], 6)
        c = await router.submit(prompts[2], 6)
        assert a.replica == c.replica == "replica0"
        # wait out the heartbeat, then run the check the monitor would
        import time as _time
        deadline = _time.monotonic() + 10.0
        died = []
        while not died and _time.monotonic() < deadline:
            await asyncio.sleep(0.05)
            died = await router.check_replicas()
        assert died == ["replica0"]
        assert replicas[0].state == "dead"
        # every stream still ends: A and C re-ran on the survivor
        # (0 tokens were emitted on the dead replica), B was never there
        outs = [await s.drain() for s in (a, b, c)]
        release.set()
        assert all(s.status == "completed" for s in (a, b, c))
        assert all(len(o) == 6 for o in outs)
        assert a.replica == c.replica == "replica1"
        reg = get_registry()
        assert reg.family_total("router_requeued_total") >= 2
        assert reg.family_total("router_dead_replicas_total") >= 1
        await router.stop()

    asyncio.run(run())


def test_dead_replica_mid_stream_requests_fail_explicitly(
        model_and_params):
    """A request that already streamed tokens on the dead replica ends
    with an explicit error (its KV lives only there) instead of being
    silently re-run."""
    import threading

    from deepspeed_tpu.inference.v2.serve import RequestFailed

    model, params = model_and_params
    eng0 = _engine(model, params)
    eng0.generate(_prompts((20,)), max_new_tokens=4)
    release = threading.Event()

    async def run():
        cfg = _serving_config(
            diagnostics=DiagnosticsConfig(stall_min_deadline_s=0.05,
                                          stall_check_interval_s=0.02))
        replicas = build_replicas([eng0], cfg)
        router = ReplicaRouter(
            replicas, RouterConfig(heartbeat_timeout_s=0.5,
                                   monitor_interval_s=0.0))
        await router.start()
        state = {"n": 0}
        real_step = replicas[0].serving.scheduler.step

        def wedged_step():
            state["n"] += 1
            if state["n"] > 2:      # let a couple of tokens out first
                release.wait(timeout=20.0)
            return real_step()

        replicas[0].serving.scheduler.step = wedged_step
        stream = await router.submit(_prompts((20,), seed=4)[0], 8)
        got = []
        async for tok in stream:
            got.append(tok)
            if len(got) >= 1:
                break
        import time as _time
        died = []
        deadline = _time.monotonic() + 10.0
        while not died and _time.monotonic() < deadline:
            await asyncio.sleep(0.05)
            died = await router.check_replicas()
        assert died == ["replica0"]
        with pytest.raises(RequestFailed, match="died mid-stream"):
            await stream.drain()
        release.set()
        await router.stop()

    asyncio.run(run())


# -- overload re-routing and router-level shed (satellite 1 rider) ---------
def test_overload_reroutes_with_backoff_then_sheds(model_and_params):
    model, params = model_and_params

    async def run():
        # replica0 admits nothing (queue bound 0 effectively: pending=1
        # and prefill blocked by a parked request is overkill — just
        # bound the queued-token budget below any request's cost)
        cfg0 = _serving_config(
            admission=AdmissionConfig(max_pending=64, max_queued_tokens=4,
                                      retry_after_s=7.5))
        cfg1 = _serving_config()
        replicas = [
            *build_replicas([_engine(model, params)], cfg0,
                            name_prefix="tight"),
            *build_replicas([_engine(model, params)], cfg1,
                            name_prefix="roomy"),
        ]
        router = ReplicaRouter(replicas,
                               RouterConfig(placement="round_robin"))
        await router.start()
        # round robin targets tight0 first; its token budget sheds and
        # the router re-routes to roomy0 with tight0 backed off
        s = await router.submit(_prompts((12,), seed=2)[0], 6)
        assert s.replica == "roomy0"
        reg = get_registry()
        assert reg.family_total("router_reroutes_total") >= 1
        assert router._backoff_until.get("tight0", 0) > router.clock()
        statusz = router.replica_statusz()
        assert statusz["tight0"]["backoff_remaining_s"] > 0
        assert (await s.drain()) and s.status == "completed"
        # both overloaded -> the router itself sheds with the soonest hint
        router._backoff_until["roomy0"] = router.clock() + 30.0
        with pytest.raises(OverloadedError) as ei:
            await router.submit(_prompts((12,), seed=8)[0], 6)
        assert ei.value.retry_after_s is not None
        assert reg.family_total("router_shed_total") >= 1
        await router.stop()

    asyncio.run(run())


# -- disaggregated prefill/decode (acceptance d) ---------------------------
def test_disaggregated_handoff_bit_identical(model_and_params):
    model, params = model_and_params
    prompts = _prompts((20, 7, 33, 12))

    async def disagg():
        replicas = build_replicas(
            [_engine(model, params), _engine(model, params)],
            _serving_config())
        pw = PrefillReplica("prefill0", _engine(model, params))
        router = ReplicaRouter(replicas,
                               RouterConfig(disaggregated=True),
                               prefill_replicas=[pw])
        await router.start()
        streams = [await router.submit(p, 12, **kw)
                   for p, kw in zip(prompts, _REQ_KW)]
        outs = [await s.drain() for s in streams]
        await router.stop()
        return outs

    single = asyncio.run(_drive_single(model, params, prompts, _REQ_KW))
    reg = get_registry()
    h0 = reg.family_total("router_handoffs_total")
    outs = asyncio.run(disagg())
    assert outs == single, \
        "disaggregated prefill->decode streams must be bit-identical " \
        "to colocated serving"
    assert reg.family_total("router_handoffs_total") - h0 == len(prompts)
    assert reg.family_total("router_handoff_bytes_total") > 0


def test_disaggregated_eos_and_one_token_finish_at_prefill(
        model_and_params):
    """A request whose budget is one token (or whose first token is
    eos) completes at the prefill replica — no handoff, one token."""
    model, params = model_and_params
    prompt = _prompts((20,), seed=6)[0]

    async def run(max_new, eos):
        replicas = build_replicas([_engine(model, params)],
                                  _serving_config())
        pw = PrefillReplica("prefill0", _engine(model, params))
        router = ReplicaRouter(replicas,
                               RouterConfig(disaggregated=True),
                               prefill_replicas=[pw])
        await router.start()
        stream = await router.submit(prompt, max_new, eos_token_id=eos)
        toks = await stream.drain()
        await router.stop()
        return toks, stream.status

    single = asyncio.run(_drive_single(model, params, [prompt],
                                       [dict()], max_new=1))[0]
    reg = get_registry()
    h0 = reg.family_total("router_handoffs_total")
    toks, status = asyncio.run(run(1, None))
    assert toks == single and status == "completed"
    # eos at the first token: same one-token completion
    toks2, status2 = asyncio.run(run(12, int(single[0])))
    assert toks2 == single and status2 == "completed"
    assert reg.family_total("router_handoffs_total") == h0, \
        "finished-at-prefill requests must not hand off"


# -- handoff unit: export/serialize/restore roundtrip ----------------------
def test_handoff_roundtrip_restores_kv_bit_exact(model_and_params):
    model, params = model_and_params
    src = _engine(model, params)
    dst = _engine(model, params)
    prompt = _prompts((37,), seed=12)[0]
    src.put([5], [np.asarray(prompt, np.int64)])
    pack = handoff.export_sequence(src, 5)
    payload = handoff.serialize(pack)
    assert isinstance(payload, bytes) and len(payload) > 0
    back = handoff.deserialize(payload)
    assert back["seen_tokens"] == len(prompt)
    assert back["n_blocks"] == pack["n_blocks"]
    handoff.restore_sequence(dst, back, uid=77)
    seq_s = src.state_manager.seqs[5]
    seq_d = dst.state_manager.seqs[77]
    assert seq_d.seen_tokens == seq_s.seen_tokens
    assert len(seq_d.blocks) == len(seq_s.blocks)
    for key in src.kv_cache:
        a = np.asarray(src.kv_cache[key])[:, seq_s.blocks]
        b = np.asarray(dst.kv_cache[key])[:, seq_d.blocks]
        np.testing.assert_array_equal(a, b)
    # mismatched layouts are rejected loudly
    other = _engine(model, params, block_size=32, num_blocks=33)
    with pytest.raises(ValueError, match="block-size mismatch"):
        handoff.restore_sequence(other, back, uid=1)


# -- routed HTTP frontend (api.py routed mode) -----------------------------
def test_routed_http_frontend_serves_and_aggregates_statusz(
        model_and_params):
    import json

    model, params = model_and_params

    async def run():
        replicas = build_replicas(
            [_engine(model, params), _engine(model, params)],
            _serving_config())
        router = ReplicaRouter(replicas, RouterConfig())
        await router.start()
        api = ServingAPI(router)
        host, port = await api.start()

        async def http(method, path, body=b""):
            reader, writer = await asyncio.open_connection(host, port)
            req = (f"{method} {path} HTTP/1.1\r\n"
                   f"Content-Length: {len(body)}\r\n\r\n").encode() + body
            writer.write(req)
            await writer.drain()
            raw = await reader.read()
            writer.close()
            head, _, payload = raw.partition(b"\r\n\r\n")
            return head.decode(), payload

        head, payload = await http(
            "POST", "/generate",
            json.dumps({"prompt": _prompts((10,), seed=1)[0],
                        "max_new_tokens": 4}).encode())
        assert "200 OK" in head
        lines = [json.loads(x) for x in payload.decode().splitlines()]
        assert lines[-1]["done"] and lines[-1]["n"] == 4
        head, payload = await http("GET", "/healthz")
        health = json.loads(payload)
        assert set(health["replicas"]) == {"replica0", "replica1"}
        head, payload = await http("GET", "/statusz")
        statusz = json.loads(payload)
        assert set(statusz["replicas"]) == {"replica0", "replica1"}
        assert statusz["router"]["placement"] == "affinity"
        await api.stop()
        await router.stop()

    asyncio.run(run())


def test_resume_rejects_oversized_request_up_front(model_and_params):
    """scheduler.resume() enforces the same KV-slot precheck as
    submit(): an oversized handed-off request fails loudly at adoption,
    not mid-decode as a misleading pool error that would take every
    in-flight request on the decode replica down. The router sheds it
    even earlier — before burning prefill flops."""
    from deepspeed_tpu.inference.v2.scheduler import \
        DynamicSplitFuseScheduler
    from deepspeed_tpu.inference.v2.serve import RequestFailed

    model, params = model_and_params
    sched = DynamicSplitFuseScheduler(_engine(model, params),
                                      token_budget=64, chunk=16)
    with pytest.raises(RuntimeError, match="over.*max_seq_len"):
        sched.resume(1, list(range(1, 241)), [7], max_new_tokens=32)

    async def run():
        replicas = build_replicas([_engine(model, params)],
                                  _serving_config())
        pw = PrefillReplica("prefill0", _engine(model, params))
        router = ReplicaRouter(replicas,
                               RouterConfig(disaggregated=True),
                               prefill_replicas=[pw])
        await router.start()
        stream = await router.submit(list(range(1, 241)), 32)
        with pytest.raises(RequestFailed, match="KV slots"):
            await stream.drain()
        # no prefill ran, no handoff happened
        reg = get_registry()
        assert reg.get("router_prefill_requests_total") is None or \
            pw.engine.state_manager.tracked_sequences() == 0
        await router.stop()

    asyncio.run(run())
