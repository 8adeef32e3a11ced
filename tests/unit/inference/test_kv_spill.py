"""KV/prefix-cache spill-to-host tier (ragged/spill.py).

The serving acceptance invariants: spilled-then-restored prefixes serve
BIT-identical streams (greedy and seeded sampling) to never-spilled
serving; eviction spills in last-touch LRU order; a request whose
prefix is spilled is admitted as a prefix HIT; restore rides the
double-warmed donated-pool scatter with ZERO steady-state recompiles;
corruption degrades to a recompute, never to poisoned KV."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.config_v2 import DSStateManagerConfig
from deepspeed_tpu.inference.v2.ragged.ragged_manager import prefix_digest


@pytest.fixture(scope="module")
def tiny(tiny_model_256):
    return tiny_model_256


def _engine(model, params, *, spill=False, num_blocks=65, prefix=True,
            kv_quant=False, **spill_kw):
    return InferenceEngineV2(
        model, RaggedInferenceEngineConfig(
            state_manager=DSStateManagerConfig(
                max_tracked_sequences=8, max_seq_len=256,
                num_blocks=num_blocks, block_size=16,
                enable_prefix_caching=prefix, enable_kv_spill=spill,
                **spill_kw),
            dtype="float32", prefill_bucket=16, kv_quant=kv_quant),
        params=params)


def _pressure(eng, rng, uid, tokens=120):
    """Serve one long request so its allocation evicts retained blocks."""
    p = list(map(int, rng.integers(1, 127, tokens)))
    eng.generate([p], max_new_tokens=4, uids=[uid])


def test_spill_restore_stream_parity_greedy_and_sampled(tiny):
    """Conversation turn 2 after the turn-1 prefix was evicted-to-spill:
    greedy AND fixed-seed sampled streams equal a never-pressured
    engine's, and the reuse counters show the spilled prefix was a HIT."""
    model, params = tiny
    rng = np.random.default_rng(0)
    pA = list(map(int, rng.integers(1, 127, 50)))

    ref = _engine(model, params, num_blocks=200)   # never pressured
    refA = ref.generate([pA], max_new_tokens=6, uids=[1])[0]

    se = _engine(model, params, spill=True, num_blocks=11)
    outA = se.generate([pA], max_new_tokens=6, uids=[1])[0]
    np.testing.assert_array_equal(outA, refA)
    _pressure(se, rng, uid=2)                      # evicts A's prefix
    dA = prefix_digest(pA[:48], 16)
    assert any(se.spill.has(d) for d in dA), "pressure spilled nothing"
    spilled_before = sum(1 for d in dA if se.spill.has(d))

    turn2 = list(map(int, outA)) + [3, 5, 7]
    ref2 = ref.generate([turn2], max_new_tokens=6, uids=[11])[0]
    reused0 = se.state_manager._m_reused_tokens.value
    hits0 = se.state_manager._m_hits.value
    out2 = se.generate([turn2], max_new_tokens=6, uids=[3])[0]
    np.testing.assert_array_equal(out2, ref2)
    # the spilled prefix was ADMITTED as a hit: full turn-1 KV reused
    assert se.state_manager._m_reused_tokens.value - reused0 == 48
    assert se.state_manager._m_hits.value - hits0 == 1
    from deepspeed_tpu.telemetry import get_registry
    assert get_registry().counter("kv_restore_blocks_total").value >= \
        spilled_before

    # seeded sampling through the spill/restore cycle
    _pressure(se, rng, uid=4)
    refS = ref.generate([turn2], max_new_tokens=6, uids=[12],
                        temperature=0.8, seed=42)[0]
    outS = se.generate([turn2], max_new_tokens=6, uids=[5],
                       temperature=0.8, seed=42)[0]
    np.testing.assert_array_equal(outS, refS)


def test_lru_eviction_spills_least_recently_touched_first(tiny):
    """Two retained prefixes; the one matched (touched) most recently
    survives eviction longest — the spill tier receives the COLD one."""
    model, params = tiny
    eng = _engine(model, params, spill=True, num_blocks=30)
    sm = eng.state_manager
    pA = list(range(1, 40))     # 2 full blocks
    pB = list(range(60, 99))    # 2 full blocks
    eng.generate([pA], max_new_tokens=4, uids=[1])
    eng.generate([pB], max_new_tokens=4, uids=[2])
    # touch A: it becomes the most recently used prefix
    _, n = sm.match_prefix(90, np.asarray(pA))
    assert n == 32
    eng.flush(90)
    dA = prefix_digest(pA[:32], 16)
    dB = prefix_digest(pB[:32], 16)
    sm._evict_retained(sm.allocator.free_blocks + 2)   # evict exactly 2
    assert all(eng.spill.has(d) for d in dB[:2] if d not in sm._prefix)
    # B (cold) spilled before A (hot)
    assert sum(1 for d in dB if eng.spill.has(d)) >= 1
    assert all(d in sm._prefix for d in dA)
    # allocator last-touch metadata orders the demotion
    assert all(sm.allocator.last_touch(sm._prefix[d]) > 0 for d in dA)


def test_disk_tier_roundtrip_and_drain_cleanup(tiny, tmp_path):
    """A host budget too small for one entry demotes to the disk tier;
    restore reads it back bit-exact; close() (the loop's drain/stop
    hook) unlinks the scratch files."""
    import os
    model, params = tiny
    rng = np.random.default_rng(1)
    pA = list(map(int, rng.integers(1, 127, 50)))
    ref = _engine(model, params, num_blocks=200)
    refA = ref.generate([pA], max_new_tokens=6, uids=[1])[0]

    se = _engine(model, params, spill=True, num_blocks=11,
                 kv_spill_host_bytes=1,      # force immediate demotion
                 kv_spill_dir=str(tmp_path / "spill"))
    outA = se.generate([pA], max_new_tokens=6, uids=[1])[0]
    np.testing.assert_array_equal(outA, refA)
    _pressure(se, rng, uid=2)
    stats = se.spill.stats()
    assert stats["disk_entries"] >= 1 and stats["host_entries"] <= 1
    assert any(os.scandir(tmp_path / "spill"))

    turn2 = list(map(int, outA)) + [3, 5, 7]
    ref2 = ref.generate([turn2], max_new_tokens=6, uids=[11])[0]
    out2 = se.generate([turn2], max_new_tokens=6, uids=[3])[0]
    np.testing.assert_array_equal(out2, ref2)

    se.spill.close()
    assert not any(os.scandir(tmp_path / "spill"))
    assert len(se.spill) == 0


def test_corrupt_spill_entry_degrades_to_recompute(tiny):
    """A corrupted entry fails its crc32 and is DROPPED: the request
    recomputes the prefix and still streams correctly."""
    model, params = tiny
    rng = np.random.default_rng(2)
    pA = list(map(int, rng.integers(1, 127, 50)))
    ref = _engine(model, params, num_blocks=200)
    refA = ref.generate([pA], max_new_tokens=6, uids=[1])[0]

    se = _engine(model, params, spill=True, num_blocks=11)
    outA = se.generate([pA], max_new_tokens=6, uids=[1])[0]
    _pressure(se, rng, uid=2)
    assert len(se.spill._host) >= 1
    victim = next(iter(se.spill._host))
    buf = bytearray(se.spill._host[victim])
    buf[len(buf) // 2] ^= 0xFF
    se.spill._host[victim] = bytes(buf)

    from deepspeed_tpu.telemetry import get_registry
    dropped0 = get_registry().counter(
        "kv_spill_dropped_blocks_total").value
    turn2 = list(map(int, outA)) + [3, 5, 7]
    ref2 = ref.generate([turn2], max_new_tokens=6, uids=[11])[0]
    out2 = se.generate([turn2], max_new_tokens=6, uids=[3])[0]
    np.testing.assert_array_equal(out2, ref2)     # recompute, not poison
    assert get_registry().counter(
        "kv_spill_dropped_blocks_total").value > dropped0
    assert not se.spill.has(victim)


@pytest.mark.parametrize("kv_quant", [False, True], ids=["plain", "int8"])
def test_an_entry_of_the_per_head_layout_is_dropped_by_shape(tiny, kv_quant):
    """A spilled block comes back into the stored ``[L, nb, bs, kvh *
    hd]`` leaves bit for bit. An entry written when a page was ``[bs,
    kvh, hd]`` (a dead peer's disk tier from before the layout changed:
    the same bytes, a sound crc) is refused by its shape and dropped,
    the pool untouched: a recompute, never a scatter."""
    from deepspeed_tpu.inference.v2.serve import handoff
    model, params = tiny
    rng = np.random.default_rng(5)
    se = _engine(model, params, spill=True, num_blocks=11,
                 kv_quant=kv_quant)
    pA = list(map(int, rng.integers(1, 127, 50)))
    se.generate([pA], max_new_tokens=4, uids=[1])
    held = {key: np.asarray(leaf) for key, leaf in se.kv_cache.items()}
    block = {d: se.state_manager._prefix[d]
             for d in prefix_digest(pA[:48], 16)}
    _pressure(se, rng, uid=2, tokens=150)          # evicts all three
    sound, stale = [d for d in block if se.spill.has(d)][:2]
    # the sound entry: every leaf of the block as it was before eviction
    assert se.spill.restore_block(sound, 3)
    for key, leaf in se.kv_cache.items():
        assert leaf.shape[2:] == held[key].shape[2:]
        np.testing.assert_array_equal(np.asarray(leaf)[:, 3],
                                      held[key][:, block[sound]])
    # the stale one: the same content, its pages cut into heads
    chunk = handoff.parse_chunk(se.spill._host[stale])
    kv = {key: (leaf.reshape(leaf.shape[:3] + (2, -1)) if key in "kv"
                else leaf) for key, leaf in chunk["kv"].items()}
    assert kv["k"].shape == (2, 1, 16, 2, 16)
    se.spill._host[stale] = handoff._npz_chunk(
        dict(chunk["descriptor"], crc32=handoff._chunk_crc(kv)), kv)
    before = {key: np.asarray(leaf) for key, leaf in se.kv_cache.items()}
    assert not se.spill.restore_block(stale, 4)
    assert not se.spill.has(stale)
    for key, leaf in se.kv_cache.items():
        np.testing.assert_array_equal(np.asarray(leaf), before[key])


def test_spill_restore_zero_steady_state_recompiles(tiny):
    """Restore rides the double-warmed donated-pool scatter: after one
    full spill->restore cycle warmed both executable signatures, a
    steady engine spills and restores with zero recompiles."""
    from deepspeed_tpu.telemetry import (MetricsRegistry, get_registry,
                                         set_registry, watchdog)
    model, params = tiny
    rng = np.random.default_rng(3)
    pA = list(map(int, rng.integers(1, 127, 50)))

    prev = set_registry(MetricsRegistry())
    watchdog.reset()
    try:
        se = _engine(model, params, spill=True, num_blocks=11)

        def cycle(base):
            out = se.generate([pA], max_new_tokens=6, uids=[base])[0]
            _pressure(se, rng, uid=base + 1)
            turn2 = list(map(int, out)) + [3, 5, 7]
            se.generate([turn2], max_new_tokens=6, uids=[base + 2])

        cycle(100)
        cycle(200)   # absorb the fresh-pool respecialization
        base = get_registry().family_total(
            "xla_steady_state_recompiles_total")
        watchdog.mark_steady(True)
        try:
            cycle(300)
        finally:
            watchdog.mark_steady(False)
        steady = get_registry().family_total(
            "xla_steady_state_recompiles_total") - base
        assert get_registry().counter(
            "kv_restore_blocks_total").value > 0
    finally:
        set_registry(prev)
        watchdog.reset()
    assert steady == 0


def test_spill_capacity_strictly_more_conversations(tiny):
    """The capacity acceptance criterion at fixed HBM pool bytes: serve
    more conversations than the pool can retain; with spill every
    conversation's prefix stays AVAILABLE (hot or restorable), without
    it the overflow is simply gone."""
    model, params = tiny
    rng = np.random.default_rng(4)
    prompts = [list(map(int, rng.integers(1, 127, 40))) for _ in range(5)]

    def available(spill):
        # 8 usable blocks cannot retain 5 conversations x 2 full blocks
        eng = _engine(model, params, spill=spill, num_blocks=9)
        for i, p in enumerate(prompts):
            eng.generate([p], max_new_tokens=4, uids=[10 + i])
        sm = eng.state_manager
        count = 0
        for p in prompts:
            digests = prefix_digest(p[:32], 16)
            ok = all(d in sm._prefix
                     or (eng.spill is not None and eng.spill.has(d))
                     for d in digests)
            count += bool(ok)
        return count

    with_spill = available(True)
    without = available(False)
    assert with_spill == len(prompts)
    assert with_spill > without


def test_spill_composes_with_kv_quant(tiny):
    """The int8 pool spills per-(block, head) scale leaves alongside the
    int8 pages (PR 9 halves every spilled byte): spill->restore parity
    holds under kv_quant."""
    model, params = tiny
    rng = np.random.default_rng(5)
    pA = list(map(int, rng.integers(1, 127, 50)))
    ref = _engine(model, params, num_blocks=200, kv_quant=True)
    refA = ref.generate([pA], max_new_tokens=6, uids=[1])[0]

    se = _engine(model, params, spill=True, num_blocks=11, kv_quant=True)
    outA = se.generate([pA], max_new_tokens=6, uids=[1])[0]
    np.testing.assert_array_equal(outA, refA)
    _pressure(se, rng, uid=2)
    assert len(se.spill) >= 1
    turn2 = list(map(int, outA)) + [3, 5, 7]
    ref2 = ref.generate([turn2], max_new_tokens=6, uids=[11])[0]
    out2 = se.generate([turn2], max_new_tokens=6, uids=[3])[0]
    np.testing.assert_array_equal(out2, ref2)


def test_restore_eviction_never_steals_the_in_progress_chain(tiny):
    """A restore's own eviction must not pick a block matched EARLIER in
    the same match_prefix walk (those are refcount-1 until the walk
    share()s them): the protected walk degrades to a shorter match
    instead of freeing-and-reusing a block already in the chain."""
    model, params = tiny
    eng = _engine(model, params, spill=True, num_blocks=8)
    sm = eng.state_manager
    pA = list(range(1, 40))                         # 2 full blocks
    eng.generate([pA], max_new_tokens=4, uids=[1])
    dA = prefix_digest(pA[:32], 16)
    # demote BOTH of A's digests, then re-heat only the first
    sm._evict_retained(sm.allocator.free_blocks + 2)
    assert all(eng.spill.has(d) for d in dA)
    _, n = sm.match_prefix(90, np.asarray(pA[:17]))
    assert n == 16 and dA[0] in sm._prefix and eng.spill.has(dA[1])
    sm.flush_sequence(90)
    b1 = sm._prefix[dA[0]]
    # exhaust the pool: every other block owned by "live" work, so the
    # only refcount-1 index entry is dA[0] — the chain's own first block
    hold = [int(b) for b in sm.allocator.allocate(sm.allocator.free_blocks)]
    blocks, n = sm.match_prefix(91, np.asarray(pA))
    # the walk matched block 1, could NOT restore block 2 (its eviction
    # candidate was protected), and must NOT have reused b1
    assert n == 16 and blocks == [b1]
    assert dA[0] in sm._prefix and sm._prefix[dA[0]] == b1
    assert sm.seqs[91].seen_tokens == 16
    assert eng.spill.has(dA[1])                     # still cold, intact
    sm.flush_sequence(91)
    sm.allocator.free(hold)


def test_spill_config_rejects():
    with pytest.raises(ValueError, match="enable_prefix_caching"):
        DSStateManagerConfig(enable_kv_spill=True)
    with pytest.raises(ValueError, match="kv_spill_host_bytes"):
        DSStateManagerConfig(enable_prefix_caching=True,
                             enable_kv_spill=True, kv_spill_host_bytes=0)
