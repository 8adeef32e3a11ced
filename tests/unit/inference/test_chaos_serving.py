"""Chaos scenario suite: scripted fault schedules through the routed
loopback fleet (ISSUE 14 acceptance).

The invariant under EVERY schedule: a submitted request either
completes with a token stream bit-identical to the fault-free run
(greedy AND seeded sampling, including mid-stream reconnects under one
trace id) or fails with an explicit typed reason — never silent
corruption, never a hung stream, zero steady-state recompiles.

Everything runs over loopback sockets with the deterministic
serve/faults.py plane (seeded, scripted — no wall-clock-heavy
schedules; injected latencies are a few hundred ms at most)."""

import asyncio

import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.config_v2 import DSStateManagerConfig
from deepspeed_tpu.inference.v2.serve import (BreakerConfig, FaultPlane,
                                              FaultSpec, PrefillReplica,
                                              RemoteReplica,
                                              ReplicaRouter,
                                              ReplicaWorker,
                                              RequestFailed,
                                              RouterConfig,
                                              ServingConfig,
                                              ServingEngine)
from deepspeed_tpu.telemetry import context as trace_context
from deepspeed_tpu.telemetry import get_registry, watchdog


@pytest.fixture(scope="module")
def model_and_params(tiny_model_256):
    return tiny_model_256


def _engine(model, params):
    return InferenceEngineV2(
        model, RaggedInferenceEngineConfig(
            state_manager=DSStateManagerConfig(
                max_tracked_sequences=8, max_seq_len=256, num_blocks=65,
                block_size=16, max_ragged_batch_size=512),
            dtype="float32", prefill_bucket=16), params=params)


def _serving_config():
    return ServingConfig(token_budget=64, chunk=16)


def _prompts(ns, seed=0):
    rng = np.random.default_rng(seed)
    return [list(map(int, rng.integers(1, 127, n))) for n in ns]


_REQ_KW = [dict(temperature=0.0), dict(temperature=0.0),
           dict(temperature=0.8, top_p=0.9, seed=11),
           dict(temperature=0.7, top_k=20, seed=5)]


async def _worker(model, params, name, plane=None, **api_kw):
    worker = ReplicaWorker(_engine(model, params), _serving_config(),
                           name=name, **api_kw)
    host, port = await worker.start()
    replica = RemoteReplica(name, host, port, faults=plane,
                            probe_interval_s=0.0,
                            reconnect_backoff_s=0.01)
    return worker, replica


# -- mid-stream reconnect: bit-identical, one trace id, typed corruption,
# zero steady-state recompiles -----------------------------------------
def test_reconnect_bit_identical_and_corruption_typed(model_and_params):
    model, params = model_and_params
    prompts = _prompts((12, 18, 9, 15))
    fam = get_registry().family_total

    async def run():
        plane = FaultPlane()
        worker, replica = await _worker(model, params, "cw0",
                                        plane=plane,
                                        resume_linger_s=5.0)
        await replica.start()

        async def wave():
            outs, traces = [], []
            for p, kw in zip(prompts, _REQ_KW):
                ctx = trace_context.new_context()
                with trace_context.use(ctx):
                    s = await replica.submit(p, 8, **kw)
                outs.append(await asyncio.wait_for(s.drain(), 60))
                traces.append((s.trace_id, ctx.trace_id, s.reconnects))
            return outs, traces

        # fault-free double warm (bucket respecialization discipline)
        base, _ = await wave()
        base2, _ = await wave()
        assert base == base2, "warmup itself must be deterministic"

        rec0 = fam("remote_stream_reconnects_total")
        st0 = fam("xla_steady_state_recompiles_total")
        watchdog.mark_steady(True)
        try:
            # every request loses its connection after 2 tokens; the
            # stream must re-attach via /resume and stay bit-identical
            plane.script(FaultSpec(kind="reset", op="read",
                                   target="/generate", skip=2, every=3,
                                   times=None))
            faulted, traces = await wave()
        finally:
            watchdog.mark_steady(False)
        steady = fam("xla_steady_state_recompiles_total") - st0
        reconnects = fam("remote_stream_reconnects_total") - rec0

        # corruption is NOT a reconnect: a complete-but-malformed frame
        # fails typed immediately
        plane.clear()
        plane.script(FaultSpec(kind="corrupt", op="read",
                               target="/generate", skip=1, times=1))
        with pytest.raises(RequestFailed) as ei:
            s = await replica.submit(prompts[0], 8)
            await asyncio.wait_for(s.drain(), 60)
        # and the fleet still serves clean traffic afterwards
        plane.clear()
        s = await replica.submit(prompts[0], 8)
        clean = await asyncio.wait_for(s.drain(), 60)
        await worker.stop()
        return base, faulted, traces, steady, reconnects, \
            str(ei.value), clean

    base, faulted, traces, steady, reconnects, corrupt_msg, clean = \
        asyncio.run(run())
    assert faulted == base, \
        "resumed streams must be bit-identical to uninterrupted ones " \
        "(greedy AND seeded)"
    assert clean == base[0]
    assert reconnects >= 4, f"every request should reconnect once " \
                            f"(saw {reconnects})"
    for tail_tid, ctx_tid, recs in traces:
        assert recs >= 1
        assert tail_tid == ctx_tid, \
            "the resumed stream must stay under the request's ONE " \
            "trace id"
    assert "malformed frame" in corrupt_msg
    assert steady == 0, "reconnect must be host-side only: zero " \
                        "steady-state recompiles"


# -- retry amplification is what the schedule asks for, no more ----------
def test_one_reset_a_probe_costs_two_attempts(model_and_params):
    """Every other dial of /healthz is reset: each forced probe fails
    once and succeeds on its one retry. Two attempts a probe exactly; a
    retry storm (attempts racing to max_attempts) shows here."""
    model, params = model_and_params
    fam = get_registry().family_total

    async def run():
        plane = FaultPlane()
        worker, replica = await _worker(model, params, "amp0", plane)
        try:
            await replica.refresh(force=True)         # first dial, clean
            plane.script(FaultSpec(kind="reset", op="connect",
                                   target="/healthz", skip=0, every=2,
                                   times=None))
            before = fam("remote_call_attempts_total")
            for _ in range(8):
                await replica.refresh(force=True)
            return fam("remote_call_attempts_total") - before
        finally:
            plane.clear()
            await worker.stop()

    assert asyncio.run(run()) == 16


# -- probe timeout: suspected (route around, streams keep) vs dead ------
def test_probe_timeout_suspected_not_dead_then_breaker_exhaustion(
        model_and_params):
    model, params = model_and_params
    fam = get_registry().family_total
    prompts = _prompts((10, 11, 13), seed=3)

    async def run():
        planes = {n: FaultPlane() for n in ("pw0", "pw1")}
        w0, r0 = await _worker(model, params, "pw0", plane=planes["pw0"])
        w1, r1 = await _worker(model, params, "pw1", plane=planes["pw1"])
        for r in (r0, r1):
            r.probe_timeout_s = 0.2
        router = ReplicaRouter(
            [r0, r1],
            RouterConfig(monitor_interval_s=0.0,
                         breaker=BreakerConfig(failure_threshold=1,
                                               open_s=0.05,
                                               max_open_cycles=3)))
        await router.start()
        dead0 = fam("router_dead_replicas_total")
        req0 = fam("router_requeued_total")

        stream = await router.submit(prompts[0], 16)
        victim = stream.replica
        other = "pw1" if victim == "pw0" else "pw0"
        # every /healthz dial to the victim now stalls past the probe
        # budget — the timeout-only fault schedule
        planes[victim].script(FaultSpec(kind="latency", op="connect",
                                        target="/healthz", delay_s=0.5,
                                        times=None))
        died = await router.check_replicas()
        # ONE delayed probe: suspected, NOT dead, nothing re-enqueued
        assert died == []
        assert victim in router._suspected
        assert fam("router_dead_replicas_total") - dead0 == 0
        assert fam("router_requeued_total") - req0 == 0
        # the mid-stream request on the suspected replica keeps
        # streaming to completion
        toks = await asyncio.wait_for(stream.drain(), 60)
        assert len(toks) == 16 and stream.status == "completed"
        # new traffic routes around the suspect
        s2 = await router.submit(prompts[1], 4)
        assert s2.replica == other
        await asyncio.wait_for(s2.drain(), 60)

        # recovery: a clean probe closes the breaker and re-admits
        planes[victim].clear()
        await asyncio.sleep(0.06)        # past the half-open window
        await router.check_replicas()
        assert victim not in router._suspected

        # sustained blackout: half-open probes keep failing until the
        # breaker EXHAUSTS — only then is the replica declared dead
        planes[victim].script(FaultSpec(kind="latency", op="connect",
                                        target="/healthz", delay_s=0.5,
                                        times=None))
        died_names = []
        for _ in range(12):
            await asyncio.sleep(0.06)
            died_names += await router.check_replicas()
            if died_names:
                break
        assert died_names == [victim], \
            "a sustained blackout must eventually exhaust the breaker"
        assert fam("router_dead_replicas_total") - dead0 == 1
        # the fleet still serves
        s3 = await router.submit(prompts[2], 4)
        assert s3.replica == other
        toks3 = await asyncio.wait_for(s3.drain(), 60)
        assert len(toks3) == 4
        await router.stop()
        await w0.stop()
        await w1.stop()

    asyncio.run(run())


# -- server-side hard stop: typed failure, dead verdict, fleet survives -
def test_worker_hard_stop_fails_typed_and_fleet_survives(
        model_and_params):
    model, params = model_and_params
    prompts = _prompts((14, 10), seed=5)

    async def run():
        w0, r0 = await _worker(model, params, "kw0")
        w1, r1 = await _worker(model, params, "kw1")
        workers = {"kw0": w0, "kw1": w1}
        router = ReplicaRouter([r0, r1],
                               RouterConfig(monitor_interval_s=0.0))
        await router.start()
        stream = await router.submit(prompts[0], 200)
        # consume a couple of tokens so the request is provably
        # mid-stream, then hard-stop its worker's runtime
        await stream.__anext__()
        await stream.__anext__()
        victim = stream.replica
        await workers[victim].replica.stop()
        with pytest.raises(RequestFailed) as ei:
            await asyncio.wait_for(stream.drain(), 60)
        # server-initiated cancellation is TYPED, never a silent
        # truncation dressed as a completed stream
        assert "cancelled by the server" in str(ei.value)
        died = await router.check_replicas()
        assert died == [victim]
        s2 = await router.submit(prompts[1], 4)
        assert s2.replica != victim
        toks = await asyncio.wait_for(s2.drain(), 60)
        assert len(toks) == 4
        await router.stop()
        await w0.stop()
        await w1.stop()

    asyncio.run(run())


# -- handoff frame faults: retransmit rides the idempotent protocol ----
def test_handoff_partial_write_retries_and_corruption_typed(
        model_and_params):
    model, params = model_and_params
    prompt = _prompts((49,), seed=9)[0]
    fam = get_registry().family_total

    async def run():
        # colocated baseline: the full greedy stream
        serving = ServingEngine(_engine(model, params),
                                _serving_config())
        await serving.start()
        s = await serving.submit(prompt, 8)
        expected = await s.drain()
        await serving.stop()

        plane = FaultPlane()
        worker, replica = await _worker(model, params, "hw0",
                                        plane=plane)
        await replica.start()
        pw = PrefillReplica("hp0", _engine(model, params))

        async def disagg():
            tok, payloads, rng_state, fin = await pw.prefill(
                prompt, 8, chunk_blocks=2)
            assert not fin
            stream = await replica.resume_handoff(
                payloads, chunked=True, prompt=prompt, generated=[tok],
                max_new_tokens=8, rng_state=rng_state)
            return [tok] + await asyncio.wait_for(stream.drain(), 60)

        # a frame send that dies half-way retries the WHOLE transfer
        # (worker aborts the partial restore; chunks are
        # idempotent-retransmit), bit-identical to colocated
        retr0 = fam("remote_call_retries_total")
        plane.script(FaultSpec(kind="partial_write", op="write",
                               target="/handoff", skip=2, times=1))
        assert await disagg() == expected
        assert fam("remote_call_retries_total") - retr0 >= 1

        # corrupted chunk bytes: the worker's CRC check rejects with a
        # typed verdict — never silently restored garbage
        plane.clear()
        plane.script(FaultSpec(kind="corrupt", op="write",
                               target="/handoff", skip=2, times=1))
        with pytest.raises(RequestFailed):
            await disagg()
        # and a clean handoff still works afterwards
        plane.clear()
        assert await disagg() == expected
        await worker.stop()

    asyncio.run(run())


# -- the invariant, under a mixed scripted schedule --------------------
def test_chaos_invariant_every_request_completes_or_fails_typed(
        model_and_params):
    model, params = model_and_params
    prompts = _prompts((8, 12, 16, 10, 14, 9, 11, 13), seed=7)

    async def run():
        planes = [FaultPlane(seed=1), FaultPlane(seed=2)]
        w0, r0 = await _worker(model, params, "iw0", plane=planes[0])
        w1, r1 = await _worker(model, params, "iw1", plane=planes[1])
        router = ReplicaRouter([r0, r1],
                               RouterConfig(monitor_interval_s=0.0))
        await router.start()

        async def drive(i):
            try:
                s = await router.submit(prompts[i], 6)
                toks = await s.drain()
                return ("completed", toks)
            except Exception as e:
                return ("failed", type(e).__name__, str(e))

        # fault-free baseline (greedy: replica-independent)
        baseline = await asyncio.wait_for(
            asyncio.gather(*[drive(i) for i in range(len(prompts))]),
            120)
        assert all(o[0] == "completed" for o in baseline)

        # the scripted schedule: dial latency, mid-stream resets, one
        # corrupted frame — across both replicas
        for plane in planes:
            plane.script(
                FaultSpec(kind="latency", op="connect",
                          target="/generate", delay_s=0.05, every=4,
                          times=None),
                FaultSpec(kind="reset", op="read", target="/generate",
                          skip=3, every=6, times=None),
                FaultSpec(kind="corrupt", op="read", target="/generate",
                          skip=17, times=1))
        outcomes = await asyncio.wait_for(
            asyncio.gather(*[drive(i) for i in range(len(prompts))]),
            120)
        await router.stop()
        await w0.stop()
        await w1.stop()
        return baseline, outcomes

    baseline, outcomes = asyncio.run(run())
    # the invariant: everything is accounted for — completed streams
    # bit-identical to the fault-free run, or failed with a TYPED
    # reason; nothing hung (the asyncio.wait_for above is the no-hang
    # bound)
    completed = failed = 0
    for i, o in enumerate(outcomes):
        if o[0] == "completed":
            completed += 1
            assert o[1] == baseline[i][1], \
                f"request {i} survived the schedule but drifted: " \
                f"{o[1]} vs {baseline[i][1]}"
        else:
            failed += 1
            assert o[1] in ("RequestFailed", "DeadlineExceeded",
                            "OverloadedError"), f"untyped failure: {o}"
    assert completed + failed == len(outcomes)
    assert completed >= len(outcomes) // 2, \
        f"the schedule should mostly recover, got {outcomes}"
