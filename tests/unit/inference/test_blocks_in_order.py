"""The allocator's order is the only thing a run rests on (PR 67):
``BlockedAllocator.allocate(n)`` hands out the ``n`` lowest free blocks,
ascending, after any history; an engine's second ``generate()`` lays its
rows' tables as its first did; the copy counters read what the tables
hold; and the host does nothing new between launches."""

import jax
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import (DSStateManagerConfig,
                                        InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2 import engine_v2
from deepspeed_tpu.inference.v2.ragged.blocked_allocator import (
    NULL_BLOCK, BlockedAllocator)
from deepspeed_tpu.telemetry import get_registry, trace


@pytest.mark.parametrize("seed", range(8))
def test_allocate_hands_out_the_lowest_free_blocks_ascending(seed):
    """After any seeded sequence of ``allocate`` / ``share`` / ``free``:
    ``allocate(n)`` is the ``n`` lowest free blocks, ascending, never
    block 0; the reference counts, ``free_blocks`` and ``version`` are
    what a plain model of them says (a block is free again when its last
    reference drops, ``version`` grows by one an operation)."""
    rng = np.random.default_rng(seed)
    size = int(rng.integers(8, 80))
    al = BlockedAllocator(size)
    free, refs, version = set(range(1, size)), {}, 0
    for _ in range(300):
        op = rng.choice(["allocate", "share", "free"], p=[0.4, 0.2, 0.4])
        if op == "allocate" and free:
            n = int(rng.integers(1, min(len(free), 12) + 1))
            got = al.allocate(n)
            want = sorted(free)[:n]
            assert got.dtype == np.int32 and list(got) == want
            assert NULL_BLOCK not in got
            free -= set(want)
            refs.update({b: 1 for b in want})
            version += 1
        elif op == "share" and refs:
            b = int(rng.choice(sorted(refs)))
            al.share(b)
            refs[b] += 1
            version += 1
        elif op == "free" and refs:
            held = sorted(refs)
            some = [int(b) for b in rng.choice(
                held, int(rng.integers(1, min(len(held), 9) + 1)),
                replace=False)]
            rng.shuffle(some)               # freed in any order
            al.free(some + [NULL_BLOCK])    # the null block is no one's
            for b in some:
                refs[b] -= 1
                if not refs[b]:
                    del refs[b]
                    free.add(b)
            version += 1
        assert al.free_blocks == len(free) and al.version == version
        assert all(al.refcount(b) == refs.get(b, 0) for b in range(size))
    with pytest.raises(RuntimeError, match="exhausted"):
        al.allocate(len(free) + 1)
    assert al.free_blocks == len(free)      # a refusal takes nothing


def test_the_blocks_of_one_call_lie_together_after_a_scrambled_free():
    """What the kernels' runs rest on: rows that took their blocks a
    chunk step at a time, flushed in another order, take the same blocks
    again, each call's ascending and together where the pool has the
    room (a LIFO list handed them back descending and interleaved)."""
    al = BlockedAllocator(200)
    first = [[al.allocate(8) for _ in range(4)] for _ in range(3)]
    for step in first:
        for got in step:
            assert list(got) == list(range(got[0], got[0] + 8))
    for r in (2, 0, 3, 1):                  # rows flushed out of order
        for step in reversed(first):
            al.free(step[r][::-1])
    again = [[al.allocate(8) for _ in range(4)] for _ in range(3)]
    for a, b in zip(first, again):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
    # a hole is filled before higher blocks are touched
    al.free([5, 17])
    assert list(al.allocate(3)) == [5, 17, 97]


@pytest.fixture(scope="module")
def tiny(tiny_model_128):
    return tiny_model_128


def _engine(model, params, window=4, **sm):
    smc = dict(max_tracked_sequences=8, max_seq_len=128, num_blocks=65,
               block_size=16)
    smc.update(sm)
    return InferenceEngineV2(
        model, RaggedInferenceEngineConfig(
            state_manager=DSStateManagerConfig(**smc),
            dtype="float32", prefill_bucket=16, decode_window=window),
        params=params)


PROMPTS = [list(range(3, 40)), [2, 4, 6], list(range(40, 62))]


def _tables_of(eng, monkeypatch):
    """Every decode launch's tables, as the engine assembles them"""
    seen = []
    assemble = eng._assemble_decode_rows

    def spy(*a, **kw):
        out = assemble(*a, **kw)
        seen.append(out[3].copy())
        return out
    monkeypatch.setattr(eng, "_assemble_decode_rows", spy)
    return seen


def test_two_calls_on_one_engine_lay_the_same_tables(tiny, monkeypatch):
    """``generate()`` twice on one engine: the second call's block tables
    are the first's, launch by launch (the first call's blocks came back
    in flush order; the allocator hands out the lowest again), its tokens
    too; and a row's prompt blocks are consecutive."""
    model, params = tiny
    eng = _engine(model, params)
    seen = _tables_of(eng, monkeypatch)
    kw = dict(max_new_tokens=21, temperature=0.0, eos_token_id=None)
    one = eng.generate(PROMPTS, **kw)
    first, seen[:] = list(seen), []
    two = eng.generate(PROMPTS, **kw)
    assert len(first) == len(seen) > 1
    for a, b in zip(first, seen):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(one, two):
        np.testing.assert_array_equal(a, b)
    # the first prompt's three pages are one ensure_blocks call's
    row = first[0][0]
    assert list(row[:3]) == list(range(row[0], row[0] + 3))
    assert eng.state_manager.free_blocks() == 64


def _copies():
    reg = get_registry()
    return (reg.get("inference_attention_copy_pages_total"),
            reg.get("inference_attention_copy_descriptors_total"))


def test_the_copy_counters_read_what_the_tables_hold(tiny, monkeypatch):
    """``inference_attention_copy_pages_total`` /
    ``..._descriptors_total``: 0 on the CPU; where the tiled kernel's two
    forms serve (asked of the engine here as the chip would answer) a
    page a layer, walk and place; descriptors fewer than pages over the
    allocator's tables (a prompt's pages are one run), and equal to
    pages, 1.0, over a pool whose rows' blocks are shuffled."""
    import importlib
    ra = importlib.import_module(       # (the package exports a function
        "deepspeed_tpu.inference.v2.kernels.ragged_attention")  # so named)
    model, params = tiny
    eng = _engine(model, params)
    pages, starts = _copies()
    before = pages.value, starts.value
    kw = dict(max_new_tokens=9, temperature=0.0, eos_token_id=None)
    eng.generate(PROMPTS, **kw)
    assert (pages.value, starts.value) == before             # the CPU
    monkeypatch.setattr(engine_v2, "one_token_tile_serves", lambda *a: True)
    monkeypatch.setattr(engine_v2, "token_tile_serves", lambda *a: True)
    monkeypatch.setattr(engine_v2, "token_tile", lambda tokens, *a: tokens)
    # (a toy row holds three pages: read a run of two as a run)
    monkeypatch.setattr(ra, "_RUN_PAGES", 2)
    eng.generate(PROMPTS, **kw)
    L = model.cfg.num_layers
    # the ragged step walks each row's prompt pages; 8 decode steps a row
    want = L * (sum(-(-len(p) // 16) for p in PROMPTS) + sum(
        -(-(len(p) + s + 1) // 16) for p in PROMPTS for s in range(8)))
    got = pages.value - before[0], starts.value - before[1]
    assert got[0] == want and 0 < got[1] < got[0]
    # the same traffic over tables that lie in no order: a start a page
    allocate = BlockedAllocator.allocate
    monkeypatch.setattr(        # descending: no two neighbours linked
        BlockedAllocator, "allocate",
        lambda self, n: allocate(self, n)[::-1].copy())
    before = pages.value, starts.value
    eng.generate(PROMPTS, **kw)
    got = pages.value - before[0], starts.value - before[1]
    assert got == (want, want)
    # and a pool of large pages is handed no runs: a start a page again
    monkeypatch.undo()
    monkeypatch.setattr(engine_v2, "one_token_tile_serves", lambda *a: True)
    monkeypatch.setattr(ra, "_RUN_PAGE_BYTES", 1)
    before = pages.value, starts.value
    eng.generate(PROMPTS, max_new_tokens=9, temperature=0.0,
                 eos_token_id=None)
    got = pages.value - before[0], starts.value - before[1]
    assert got[0] == got[1] > 0


def test_a_decode_window_uploads_what_it_did_and_counts_behind_its_call(
        tiny, monkeypatch):
    """Nothing new on the host between launches: a window's dispatch
    holds its two leaves and no other span, its upload makes the one
    ``device_put`` of a call's first window and none behind a window in
    flight, and the copy counter's arithmetic runs behind ``window_call``
    (the launch is queued), never under an upload; a ragged step's
    behind its ``ragged_call``."""
    model, params = tiny
    eng = _engine(model, params)
    monkeypatch.setattr(engine_v2, "one_token_tile_serves", lambda *a: True)
    monkeypatch.setattr(engine_v2, "token_tile_serves", lambda *a: True)
    monkeypatch.setattr(engine_v2, "token_tile", lambda tokens, *a: tokens)
    kw = dict(max_new_tokens=14, temperature=0.0, eos_token_id=None)
    eng.generate(PROMPTS, **kw)                              # warm
    import time
    puts, counts = [], []
    device_put, launch_copies = jax.device_put, engine_v2.launch_copies

    def put(*a, **k):
        puts.append(time.perf_counter())
        return device_put(*a, **k)

    def counted(*a, **k):
        counts.append(time.perf_counter())
        return launch_copies(*a, **k)
    monkeypatch.setattr(jax, "device_put", put)
    monkeypatch.setattr(engine_v2, "launch_copies", counted)
    trace.clear()
    eng.generate(PROMPTS, **kw)
    ring = trace.export()

    def inside(span, t):
        return span["start"] <= t <= span["start"] + span["duration_s"]
    windows = sorted((s for s in ring if s["name"] == "decode_window"),
                     key=lambda s: s["start"])
    assert len(windows) == 4 and len(counts) > len(windows)
    uploads = [s for s in ring if s["name"] in ("window_upload",
                                                "ragged_upload")]
    calls = [s for s in ring if s["name"] in ("window_call", "ragged_call")]
    assert len(uploads) == len(calls) == len(windows) + 1
    for up in uploads:
        assert not any(s["parent"] == up["id"] for s in ring)
        assert not any(inside(up, t) for t in counts)
    for t in counts:            # behind a call of the same launch
        holder, = (s for s in ring if s["name"] in (
            "window_dispatch", "ragged_bookkeeping") and inside(s, t))
        call = max((c for c in calls if c["start"] < t),
                   key=lambda c: c["start"])
        assert call["start"] + call["duration_s"] <= t
        if holder["name"] == "window_dispatch":
            assert call["parent"] == holder["id"]
    first = [s for s in uploads if s["name"] == "window_upload"
             and sum(inside(s, t) for t in puts)]
    assert len(first) == 1 and sum(inside(first[0], t) for t in puts) == 1
