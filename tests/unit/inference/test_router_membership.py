"""Router dynamic membership (ISSUE 12 satellite): `_HashRing` rebuild
preserves surviving placement, `add_replica`/`remove_replica` rebuild
the ring and remap the affinity table, and death verdicts compose with
autoscaler-initiated drains (no double re-enqueue)."""

import asyncio
import threading

import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.config_v2 import DSStateManagerConfig
from deepspeed_tpu.inference.v2.serve import (Replica, ReplicaRouter,
                                              RouterConfig,
                                              ServingConfig)
from deepspeed_tpu.inference.v2.serve.router import _HashRing
from deepspeed_tpu.telemetry import get_registry
from deepspeed_tpu.telemetry.anomaly import DiagnosticsConfig


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


# -- _HashRing rebuild: only the moved node's keys remap -------------------
def test_hash_ring_rebuild_preserves_surviving_placement():
    keys = [f"key-{i}".encode() for i in range(400)]
    allowed3 = {"a", "b", "c"}
    ring3 = _HashRing(["a", "b", "c"], points=32)
    owner3 = {k: ring3.pick(k, allowed3) for k in keys}

    # removal: every key NOT owned by the removed node keeps its owner
    ring2 = _HashRing(["a", "c"], points=32)
    for k in keys:
        got = ring2.pick(k, {"a", "c"})
        if owner3[k] != "b":
            assert got == owner3[k], \
                "removing b must not move keys owned by a/c"
        else:
            assert got in ("a", "c")

    # addition: keys either keep their owner or move to the NEW node
    ring4 = _HashRing(["a", "b", "c", "d"], points=32)
    moved = 0
    for k in keys:
        got = ring4.pick(k, allowed3 | {"d"})
        assert got == owner3[k] or got == "d", \
            "adding d may only move keys TO d"
        moved += got == "d"
    assert 0 < moved < len(keys)


# -- add/remove replica ----------------------------------------------------
def test_add_remove_replica_membership(model_and_params):
    model, params = model_and_params

    async def run():
        router = ReplicaRouter(
            [Replica("r0", _engine(model, params), _serving_config())],
            RouterConfig(monitor_interval_s=0.0))
        await router.start()
        try:
            s = await router.submit(_prompts((20,))[0], 4)
            await s.drain()
            assert s.replica == "r0"
            # grow: the new replica starts, joins the ring, serves
            await router.add_replica(
                Replica("r1", _engine(model, params), _serving_config()))
            assert set(router._by_name) == {"r0", "r1"}
            assert {r.name for r in router._routable()} == {"r0", "r1"}
            with pytest.raises(ValueError):
                await router.add_replica(
                    Replica("r1", _engine(model, params),
                            _serving_config()))
            # force traffic onto r1 by draining r0, then shrink
            await router.drain_replica("r0")
            s = await router.submit(_prompts((12,))[0], 4)
            await s.drain()
            assert s.replica == "r1"
            # affinity entries for the drained replica purge on removal
            router.remove_replica("r0")
            assert set(router._by_name) == {"r1"}
            assert "r0" not in set(router._affinity.values())
            with pytest.raises(KeyError):
                router.remove_replica("r0")
            # an 'up' replica cannot be removed without draining
            with pytest.raises(RuntimeError):
                router.remove_replica("r1")
            s = await router.submit(_prompts((8,))[0], 3)
            await s.drain()
            assert s.replica == "r1"
        finally:
            await router.stop()

    asyncio.run(run())


# -- death verdicts compose with drains (no double re-enqueue) -------------
def test_death_and_drain_compose_without_double_requeue(model_and_params):
    model, params = model_and_params
    eng0 = _engine(model, params)
    eng1 = _engine(model, params)
    # pre-compile BOTH so the wedge (not a first-compile stall) is what
    # the heartbeat check sees
    eng0.generate(_prompts((20,)), max_new_tokens=4)
    eng1.generate(_prompts((16,)), max_new_tokens=4)
    release = threading.Event()

    async def run():
        cfg = _serving_config(
            max_inflight=1,
            diagnostics=DiagnosticsConfig(stall_min_deadline_s=0.05,
                                          stall_check_interval_s=0.02))
        replicas = [Replica("m0", eng0, cfg),
                    Replica("m1", eng1, _serving_config())]
        # the deadlines leave a HEALTHY replica the slack a host with six
        # busy test workers needs: m1's loop may stall a second or two
        # there, and at a timeout of 1.0 s it was declared dead beside
        # m0 (PR 54's take-up run); the wedge outlasts every deadline
        router = ReplicaRouter(
            replicas, RouterConfig(placement="round_robin",
                                   heartbeat_timeout_s=4.0,
                                   monitor_interval_s=0.0))
        await router.start()
        real_step = replicas[0].serving.scheduler.step

        def wedged_step():
            release.wait(timeout=120.0)
            return real_step()

        replicas[0].serving.scheduler.step = wedged_step
        prompts = _prompts((20, 16, 12), seed=9)
        a = await router.submit(prompts[0], 4)   # m0, wedges
        b = await router.submit(prompts[1], 4)   # m1
        c = await router.submit(prompts[2], 4)   # m0, queued
        reg = get_registry()
        rq0 = reg.family_total("router_requeued_total")
        import time as _time
        deadline = _time.monotonic() + 60.0
        died = []
        while not died and _time.monotonic() < deadline:
            await asyncio.sleep(0.05)
            died = await router.check_replicas()
        assert died == ["m0"]
        requeued_once = reg.family_total("router_requeued_total") - rq0
        # a second verdict pass and an autoscaler-style drain of the
        # SAME (now dead) replica must not re-enqueue again
        assert await router.check_replicas() == []
        await router.drain_replica("m0")     # no-op: not 'up'
        assert reg.family_total("router_requeued_total") - rq0 \
            == requeued_once
        outs = [await s.drain() for s in (a, b, c)]
        release.set()
        assert all(len(o) == 4 for o in outs)
        assert a.replica == c.replica == "m1"
        # and a replica draining BEFORE it would be declared dead is
        # never a death verdict (drain owns its in-flight work)
        await router.drain_replica("m1")
        assert await router.check_replicas() == []
        await router.stop()

    asyncio.run(run())
