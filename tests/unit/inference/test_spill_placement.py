"""Spill-aware global placement + session resurrection (ISSUE 19).

The fleet-visible spill tier: replicas advertise a bloom summary of
their spilled digests over /healthz; the router prefers a replica
whose summary CLAIMS a request's prefix digests when no replica holds
it hot (restore-over-recompute); a bloom false positive silently
degrades to a recompute; and when a replica dies, a survivor adopts
its disk spill namespace so re-enqueued conversations restore on the
failover target instead of recomputing — all bit-identical, greedy
AND seeded sampling.

Plus the satellite regression: two replicas sharing one kv_spill_dir
land in DISTINCT namespaces (no silent clobber), an explicit
namespace collision is a typed config error, and a reaped replica's
scratch is cleaned up."""

import asyncio
import os
import threading
import time as _time

import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.config_v2 import DSStateManagerConfig
from deepspeed_tpu.inference.v2.ragged.ragged_manager import prefix_digest
from deepspeed_tpu.inference.v2.ragged.spill import (SpillSummary,
                                                     build_summary)
from deepspeed_tpu.inference.v2.serve import (ReplicaRouter,
                                              RouterConfig,
                                              ServingConfig,
                                              ServingEngine,
                                              build_replicas)
from deepspeed_tpu.telemetry import get_registry
from deepspeed_tpu.telemetry.anomaly import DiagnosticsConfig


@pytest.fixture(scope="module")
def tiny(tiny_model_256):
    return tiny_model_256


def _engine(model, params, *, spill=False, num_blocks=65, **sm_kw):
    sm = dict(max_tracked_sequences=8, max_seq_len=256,
              num_blocks=num_blocks, block_size=16,
              max_ragged_batch_size=512, enable_prefix_caching=True,
              enable_kv_spill=spill)
    sm.update(sm_kw)
    return InferenceEngineV2(
        model, RaggedInferenceEngineConfig(
            state_manager=DSStateManagerConfig(**sm), dtype="float32",
            prefill_bucket=16), params=params)


def _serving_config(**kw):
    kw.setdefault("token_budget", 64)
    kw.setdefault("chunk", 16)
    return ServingConfig(**kw)


def _pressure(eng, rng, uid, tokens=120):
    p = list(map(int, rng.integers(1, 127, tokens)))
    eng.generate([p], max_new_tokens=4, uids=[uid])


# ---------------------------------------------------------------------------
# bloom summary: exact-positive, rare-false-positive, wire roundtrip
# ---------------------------------------------------------------------------
def test_bloom_summary_roundtrip_and_false_positive_rate():
    rng = np.random.default_rng(0)
    present = [bytes(rng.integers(0, 256, 20, dtype=np.uint8))
               for _ in range(200)]
    absent = [bytes(rng.integers(0, 256, 20, dtype=np.uint8))
              for _ in range(2000)]
    s = build_summary(present, seq=7, namespace="ns0")
    # no false negatives, ever
    assert all(s.claims(d) for d in present)
    # false positives are the DESIGN tradeoff, but rare (~16 bits/key)
    fp = sum(1 for d in absent if s.claims(d))
    assert fp / len(absent) < 0.02, fp
    # health-document roundtrip decodes to the same answers
    d = SpillSummary.from_doc(s.to_doc())
    assert d.seq == 7 and d.namespace == "ns0" and d.entries == 200
    assert all(d.claims(x) for x in present)
    # empty tier claims nothing; malformed docs decode to None
    assert not build_summary([]).claims(present[0])
    assert SpillSummary.from_doc(None) is None
    assert SpillSummary.from_doc({"bits": 8}) is None
    assert SpillSummary.from_doc(
        {"bits": "x", "hashes": 4, "entries": 1, "bloom": "!"}) is None


# ---------------------------------------------------------------------------
# shared kv_spill_dir: distinct namespaces, typed collision, reap cleanup
# ---------------------------------------------------------------------------
def test_shared_spill_dir_namespacing_and_collision(tiny, tmp_path):
    model, params = tiny
    root = str(tmp_path / "spill")
    rng = np.random.default_rng(1)
    e0 = _engine(model, params, spill=True, num_blocks=11,
                 kv_spill_host_bytes=1, kv_spill_dir=root)
    e1 = _engine(model, params, spill=True, num_blocks=11,
                 kv_spill_host_bytes=1, kv_spill_dir=root)
    # auto namespaces never collide; each tier owns its own subdir
    assert e0.spill.namespace != e1.spill.namespace
    assert e0.spill.disk_dir != e1.spill.disk_dir
    pA = list(map(int, rng.integers(1, 127, 50)))
    e0.generate([pA], max_new_tokens=4, uids=[1])
    e1.generate([pA], max_new_tokens=4, uids=[1])
    _pressure(e0, rng, uid=2)
    _pressure(e1, rng, uid=2)
    dA = prefix_digest(pA[:48], 16)
    # the SAME digests spilled on both replicas into DISJOINT files —
    # before namespacing the second writer clobbered the first
    f0 = {f for f in os.listdir(e0.spill.disk_dir) if f.endswith(".npz")}
    f1 = {f for f in os.listdir(e1.spill.disk_dir) if f.endswith(".npz")}
    assert f0 and f0 == f1         # same digest-named entries...
    assert any(e0.spill.has(d) for d in dA)
    assert any(e1.spill.has(d) for d in dA)
    # ...in different directories: closing one leaves the other whole
    e0.spill.close()
    assert not os.path.exists(e0.spill.disk_dir)
    assert all(os.path.exists(os.path.join(e1.spill.disk_dir, f))
               for f in f1)
    e1.spill.close()

    # an EXPLICIT namespace collision is a typed config error
    _engine(model, params, spill=True, num_blocks=11,
            kv_spill_dir=root, kv_spill_namespace="pinned")
    with pytest.raises(ValueError, match="pinned.*already.*claimed"):
        _engine(model, params, spill=True, num_blocks=11,
                kv_spill_dir=root, kv_spill_namespace="pinned")
    # a path-escaping namespace is rejected at config load
    with pytest.raises(ValueError, match="single path component"):
        DSStateManagerConfig(enable_prefix_caching=True,
                             enable_kv_spill=True,
                             kv_spill_namespace="../escape")


# ---------------------------------------------------------------------------
# placement: the router prefers the spill claimant; restore bit-identical
# ---------------------------------------------------------------------------
def test_spill_placement_routes_to_claimant_and_restores(tiny, tmp_path):
    """Turn 2 of a conversation whose turn-1 prefix was spilled on
    replica0: the affinity map is empty (fresh router), so ONLY the
    advertised spill summary can steer placement — and it must, with
    the restored stream bit-identical to the never-pressured reference
    for greedy and seeded sampling."""
    model, params = tiny
    rng = np.random.default_rng(2)
    pA = list(map(int, rng.integers(1, 127, 50)))
    ref = _engine(model, params, num_blocks=200)
    refA = ref.generate([pA], max_new_tokens=6, uids=[1])[0]

    e0 = _engine(model, params, spill=True, num_blocks=11,
                 kv_spill_dir=str(tmp_path / "s"))
    e1 = _engine(model, params, num_blocks=65)
    outA = e0.generate([pA], max_new_tokens=6, uids=[1])[0]
    np.testing.assert_array_equal(outA, refA)
    _pressure(e0, rng, uid=2)
    dA = prefix_digest(pA[:48], 16)
    assert any(e0.spill.has(d) for d in dA), "pressure spilled nothing"

    turn2 = list(map(int, outA)) + [3, 5, 7]
    ref2 = ref.generate([turn2], max_new_tokens=6, uids=[11])[0]
    fam = get_registry().family_total
    base = {n: fam(n) for n in
            ("router_spill_placement_hits_total",
             "router_spill_placement_restored_blocks_total",
             "router_spill_placement_false_positives_total")}

    async def run():
        replicas = build_replicas([e0, e1], _serving_config())
        router = ReplicaRouter(replicas, RouterConfig())
        await router.start()
        # placement decision alone: fresh router => no affinity, the
        # spill claim is the only signal — and it picks replica0
        name, _, via = router.pick_replica(turn2)
        assert (name, via) == ("replica0", "spill")
        s = await router.submit(turn2, 6)
        out = await s.drain()
        assert s.replica == "replica0"
        # seeded sampling through the same spill/restore placement;
        # the reference runs through the SERVING surface (a seeded
        # request draws the scheduler's per-request rng, a different
        # deterministic stream than generate()'s jitted sampler)
        _pressure(e0, rng, uid=3)
        router._affinity.clear()     # isolate the spill signal again
        s2 = await router.submit(turn2, 6, temperature=0.8, seed=42)
        outS = await s2.drain()
        await router.stop()
        serving = ServingEngine(ref, _serving_config())
        await serving.start()
        sref = await serving.submit(turn2, 6, temperature=0.8, seed=42)
        refS = await sref.drain()
        await serving.stop()
        return out, outS, refS

    out, outS, refS = asyncio.run(run())
    assert out == list(map(int, ref2[len(turn2):]))
    assert outS == refS
    assert fam("router_spill_placement_hits_total") \
        - base["router_spill_placement_hits_total"] >= 2
    assert fam("router_spill_placement_restored_blocks_total") \
        - base["router_spill_placement_restored_blocks_total"] >= 3
    assert fam("router_spill_placement_false_positives_total") \
        - base["router_spill_placement_false_positives_total"] == 0


# ---------------------------------------------------------------------------
# bloom false positive: silent degrade to recompute, counted, never typed
# ---------------------------------------------------------------------------
def test_bloom_false_positive_degrades_to_recompute(tiny):
    model, params = tiny
    rng = np.random.default_rng(3)
    p = list(map(int, rng.integers(1, 127, 40)))
    ref = _engine(model, params, num_blocks=200)
    want = ref.generate([p], max_new_tokens=6, uids=[1])[0]
    e0 = _engine(model, params, num_blocks=65)
    e1 = _engine(model, params, spill=True, num_blocks=65)
    digests = prefix_digest(p[:32], 16)
    fam = get_registry().family_total
    base = {n: fam(n) for n in
            ("router_spill_placement_false_positives_total",
             "router_spill_placement_restored_blocks_total")}

    async def run():
        replicas = build_replicas([e0, e1], _serving_config())
        # forge replica1's advertisement: the bloom CLAIMS the prompt's
        # digests but the tier holds nothing (the false-positive case,
        # indistinguishable to the router from a real claim)
        replicas[1].spill_summary = \
            lambda: build_summary(digests, seq=1, namespace="forged")
        router = ReplicaRouter(replicas, RouterConfig())
        await router.start()
        name, _, via = router.pick_replica(p)
        assert (name, via) == ("replica1", "spill")
        s = await router.submit(p, 6)
        out = await s.drain()
        await router.stop()
        return out, s.status

    out, status = asyncio.run(run())
    # the stream completed normally (recompute), bit-identical — the
    # false positive cost time, never correctness, never a typed error
    assert status == "completed"
    assert out == list(map(int, want[len(p):]))
    assert fam("router_spill_placement_false_positives_total") \
        - base["router_spill_placement_false_positives_total"] >= 1
    assert fam("router_spill_placement_restored_blocks_total") \
        - base["router_spill_placement_restored_blocks_total"] == 0


# ---------------------------------------------------------------------------
# session resurrection: death -> namespace adoption -> restore on survivor
# ---------------------------------------------------------------------------
def test_session_resurrection_restores_on_failover_target(tiny, tmp_path):
    """Replica0 spilled a conversation to the SHARED disk tier, then
    dies with the turn-2 request still queued (zero tokens). The
    router has the survivor adopt replica0's spill namespace before
    the reap, re-dispatches the request there, and the stream
    completes BIT-IDENTICAL via restore — the session survived its
    replica."""
    model, params = tiny
    rng = np.random.default_rng(4)
    root = str(tmp_path / "shared")
    pA = list(map(int, rng.integers(1, 127, 50)))
    ref = _engine(model, params, num_blocks=200)
    refA = ref.generate([pA], max_new_tokens=6, uids=[1])[0]

    # host budget 1 byte => every spilled block demotes to DISK, the
    # tier a survivor can actually adopt
    e0 = _engine(model, params, spill=True, num_blocks=11,
                 kv_spill_host_bytes=1, kv_spill_dir=root)
    e1 = _engine(model, params, spill=True, num_blocks=65,
                 kv_spill_host_bytes=1, kv_spill_dir=root)
    outA = e0.generate([pA], max_new_tokens=6, uids=[1])[0]
    np.testing.assert_array_equal(outA, refA)
    # two pressure rounds: eviction is lazy (blocks spill only as the
    # pool actually needs them), the second round pushes ALL of pA's
    # oldest-touched blocks through the 1-byte host tier onto disk
    _pressure(e0, rng, uid=2)
    _pressure(e0, rng, uid=3, tokens=110)
    dA = prefix_digest(pA[:48], 16)
    assert sum(e0.spill.has(d) for d in dA) >= 3
    assert e0.spill.stats()["disk_entries"] >= 3
    ns0 = e0.spill.namespace

    turn2 = list(map(int, outA)) + [3, 5, 7]
    ref2 = ref.generate([turn2], max_new_tokens=6, uids=[11])[0]
    fam = get_registry().family_total
    base = {n: fam(n) for n in
            ("router_session_resurrections_total",
             "router_resurrected_requests_total",
             "kv_spill_adopted_blocks_total",
             "kv_restore_blocks_total",
             "router_requeued_total")}
    release = threading.Event()

    async def run():
        cfg = _serving_config(
            max_inflight=1,
            diagnostics=DiagnosticsConfig(stall_min_deadline_s=0.05,
                                          stall_check_interval_s=0.02))
        replicas = build_replicas([e0, e1], cfg)
        router = ReplicaRouter(
            replicas, RouterConfig(heartbeat_timeout_s=1.0,
                                   monitor_interval_s=0.0))
        await router.start()
        real_step = replicas[0].serving.scheduler.step

        def wedged_step():
            release.wait(timeout=20.0)
            return real_step()

        replicas[0].serving.scheduler.step = wedged_step
        # the spill claim routes turn 2 onto replica0 — which wedges
        s = await router.submit(turn2, 6)
        assert s.replica == "replica0"
        deadline = _time.monotonic() + 10.0
        died = []
        while not died and _time.monotonic() < deadline:
            await asyncio.sleep(0.05)
            died = await router.check_replicas()
        assert died == ["replica0"]
        out = await s.drain()
        release.set()
        await router.stop()
        return out, s.replica, s.status

    out, where, status = asyncio.run(run())
    assert status == "completed" and where == "replica1"
    assert out == list(map(int, ref2[len(turn2):])), \
        "resurrected stream must be bit-identical to the reference"
    assert fam("router_session_resurrections_total") \
        - base["router_session_resurrections_total"] == 1
    assert fam("router_resurrected_requests_total") \
        - base["router_resurrected_requests_total"] >= 1
    assert fam("kv_spill_adopted_blocks_total") \
        - base["kv_spill_adopted_blocks_total"] >= 3
    # and restored there, not recomputed (replica0 is wedged: every
    # restore since the baseline is the survivor's)
    assert fam("kv_restore_blocks_total") \
        - base["kv_restore_blocks_total"] >= 3
    assert fam("router_requeued_total") \
        - base["router_requeued_total"] >= 1
    # the dead replica's namespace was adopted (moved), not clobbered:
    # its scratch dir is gone, the survivor's tier held the digests
    assert not os.path.exists(os.path.join(root, ns0))


# ---------------------------------------------------------------------------
# composition: spill + router + autoscaler + chaos over loopback workers
# ---------------------------------------------------------------------------
# slow: tier-1 siblings are the placement/FP/resurrection tests above
# (each composed subsystem pinned individually, and the restore's
# steady state in test_program_counts.py); the full composition also
# runs as the slow city sweep below. Both are run by nothing.
@pytest.mark.slow
def test_composition_spill_router_autoscaler_chaos(tiny, tmp_path):
    """The tier-1 twin of the city-scale sweep: a seeded fault
    schedule over a spill-enabled ROUTED fleet (loopback workers, so
    the bloom summary travels over real /healthz) with the autoscaler
    attached. Every turn completes-or-typed, the completed sample is
    bit-identical to the fault-free reference, and at least one
    placement was a spill-restore."""
    from deepspeed_tpu.benchmarks.load_bench import run_city_open_loop

    model, params = tiny
    rng = np.random.default_rng(5)
    root = str(tmp_path / "city")

    def spill_engine():
        return _engine(model, params, spill=True, num_blocks=11,
                       kv_spill_dir=root)

    e0 = spill_engine()
    ref = _engine(model, params, num_blocks=200)
    # pre-spill a conversation prefix on the seed replica so the sweep
    # contains a guaranteed restore-over-recompute placement
    pA = list(map(int, rng.integers(1, 127, 50)))
    outA = e0.generate([pA], max_new_tokens=4, uids=[1])[0]
    _pressure(e0, rng, uid=2)
    assert len(e0.spill) >= 1
    turn2 = list(map(int, outA)) + [9, 11]

    workload = [
        {"start_s": 0.0, "turns": [turn2], "idles": [0.01],
         "kw": dict(temperature=0.0)},
        {"start_s": 0.05,
         "turns": [list(map(int, rng.integers(1, 127, 24))),
                   list(map(int, rng.integers(1, 127, 8)))],
         "idles": [0.05, 0.01], "kw": dict(temperature=0.0)},
        {"start_s": 0.1,
         "turns": [list(map(int, rng.integers(1, 127, 30)))],
         "idles": [0.01],
         "kw": dict(temperature=0.8, top_p=0.9, seed=77)},
    ]
    report = run_city_open_loop(
        [e0], workload, reply_tokens=4, budget=64, chunk=16,
        max_pending=8, placement="affinity",
        engine_factory=spill_engine, autoscale_max=2,
        chaos_seed=11, reset_p=0.3, latency_p=0.2, latency_s=0.01,
        reference_engine=ref, parity_sample=3, max_history=250)
    assert report["invariant_ok"], report
    assert report["bit_identical_ok"], report
    assert report["parity_sessions_checked"] >= 1
    assert report["spill_placement_hits"] >= 1, report
    assert report["spill_restored_blocks"] >= 1, report
    assert report["completed_turns"] >= 1


# ---------------------------------------------------------------------------
# the full city-scale sweep (slow tier; numeric twin lives in the perf
# gate's _spill_placement_gate)
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_city_scale_sweep_full_composition(tiny, tmp_path):
    from deepspeed_tpu.benchmarks.load_bench import (make_city_workload,
                                                     run_city_open_loop)

    model, params = tiny
    root = str(tmp_path / "city_full")

    def spill_engine():
        # 4 tracked seqs x ~10 blocks of capped history fit the pool;
        # the DISTINCT session prefixes across 24 conversations do not
        # — that churn is what drives spill + restore
        return _engine(model, params, spill=True, num_blocks=44,
                       max_tracked_sequences=4,
                       kv_spill_host_bytes=1 << 16,
                       kv_spill_dir=root)

    engines = [spill_engine(), spill_engine()]
    ref = _engine(model, params, num_blocks=200)
    rng = np.random.default_rng(9)
    # anchor conversation: turn 1 runs and its prefix is pushed into
    # replica0's spill tier BEFORE the fleet starts — its turn 2 in
    # the workload MUST be served restore-over-recompute (the organic
    # sessions below exercise the same path opportunistically)
    pA = list(map(int, rng.integers(1, 127, 50)))
    outA = engines[0].generate([pA], max_new_tokens=4, uids=[1])[0]
    for uid in range(2, 8):      # fill the 44-block pool past capacity
        _pressure(engines[0], rng, uid=uid, tokens=200)
    dA = prefix_digest(pA[:48], 16)
    assert any(engines[0].spill.has(d) for d in dA)
    turn2 = list(map(int, outA)) + [9, 11]
    workload = [{"start_s": 0.0, "turns": [turn2], "idles": [0.01],
                 "kw": dict(temperature=0.0)}]
    workload += make_city_workload(32, 3, rate_rps=8.0, seed=0,
                                   first_len=48, turn_len=10,
                                   idle_mean_s=0.1, idle_sigma=1.0)
    report = run_city_open_loop(
        engines, workload, reply_tokens=6, budget=64, chunk=16,
        max_pending=16, placement="affinity",
        engine_factory=spill_engine, autoscale_max=3,
        chaos_seed=7, reset_p=0.1, latency_p=0.1, latency_s=0.01,
        reference_engine=ref, parity_sample=4, max_history=150)
    assert report["invariant_ok"], report
    assert report["bit_identical_ok"], report
    assert report["parity_sessions_checked"] >= 2
    # the capacity story: conversations spilled and came back
    assert report["restore_fraction"] > 0.0, report
    assert report["capacity_tok_per_mib"] > 0
