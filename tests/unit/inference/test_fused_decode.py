"""Fused multi-token decode window (paged_model.paged_decode_window).

The contract under test: with ``decode_window=K`` the decode loop runs
up to K steps per device dispatch — cache write, paged attention,
sampling, EOS masking and block-table advancement all on device, one
[N, K] int32 transfer per window — and the token streams are
BIT-IDENTICAL to the per-token fallback (``decode_window=1``) under
greedy and fixed-seed sampled decoding, including mid-window EOS and KV
block boundaries crossed inside a window. Plus the two resource bounds:
at most one fresh compile per batch bucket, and host syncs per generated
token <= 1/K.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.config_v2 import DSStateManagerConfig
from deepspeed_tpu.models import TransformerConfig, TransformerLM


@pytest.fixture(scope="module")
def tiny(tiny_model_128):
    # session-shared tiny model (tests/unit/conftest.py): one
    # init_params for the whole tier instead of one per module
    return tiny_model_128


def _engine(model, params, window, **sm_kw):
    smc = dict(max_tracked_sequences=8, max_seq_len=128, num_blocks=33,
               block_size=16)
    smc.update(sm_kw)
    return InferenceEngineV2(
        model, RaggedInferenceEngineConfig(
            state_manager=DSStateManagerConfig(**smc),
            dtype="float32", prefill_bucket=16, decode_window=window),
        params=params)


def test_fused_greedy_parity_crossing_block_boundary(tiny):
    """Bit-identical greedy streams, with the 14-token prompt crossing
    the 16-token KV block boundary INSIDE the first window (positions
    14..21): the on-device pos//block_size advancement must pick the
    pre-allocated second block mid-window."""
    model, params = tiny
    prompts = [list(range(3, 17)), [2, 4, 6]]   # 14 tokens / 3 tokens
    ref = _engine(model, params, 1).generate(prompts, max_new_tokens=25)
    out = _engine(model, params, 8).generate(prompts, max_new_tokens=25)
    for a, b in zip(ref, out):
        np.testing.assert_array_equal(a, b)


def test_fused_greedy_parity_mid_window_eos(tiny):
    """A row hitting EOS mid-window goes inactive on device (EOS emitted,
    never fed — the per-token invariant) while the other row keeps
    decoding; both rows' streams stay identical to the per-token path."""
    model, params = tiny
    prompts = [[3, 5, 7, 9, 11, 13], [2, 4, 6]]
    ref_free = _engine(model, params, 1).generate(prompts,
                                                  max_new_tokens=25)
    # pick the token the first row emits 5 tokens in: EOS lands at
    # window position 4 of the first fused window (mid-window, not at
    # a boundary)
    eos = int(ref_free[0][6 + 4])
    ref = _engine(model, params, 1).generate(prompts, max_new_tokens=25,
                                             eos_token_id=eos)
    out = _engine(model, params, 8).generate(prompts, max_new_tokens=25,
                                             eos_token_id=eos)
    assert len(ref[0]) < len(ref_free[0])   # the EOS actually cut row 0
    for a, b in zip(ref, out):
        np.testing.assert_array_equal(a, b)


def test_fused_sampled_parity_fixed_seed(tiny):
    """Fixed-seed sampled decoding: per-row PRNG keys (stable row seed +
    generated-token index) make the fused window and the per-token path
    draw the exact same tokens."""
    model, params = tiny
    prompts = [[3, 5, 7, 9, 11, 13, 15, 2, 4, 8], [2, 4, 6]]
    kw = dict(max_new_tokens=14, temperature=0.8, top_p=0.9, top_k=20,
              seed=5)
    a = _engine(model, params, 1).generate(prompts, **kw)
    b = _engine(model, params, 8).generate(prompts, **kw)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    # different seed actually changes the stream (the parity above is
    # not argmax in disguise)
    c = _engine(model, params, 8).generate(
        prompts, max_new_tokens=14, temperature=0.8, top_p=0.9,
        top_k=20, seed=6)
    assert any(not np.array_equal(x, y) for x, y in zip(b, c))


def test_fused_sampled_eos_parity(tiny):
    """Sampled decoding with an EOS cut inside a window still matches
    the per-token path (budget/EOS masking composes with sampling)."""
    model, params = tiny
    prompts = [[3, 5, 7, 9]]
    kw = dict(max_new_tokens=20, temperature=0.9, top_p=0.95, seed=11)
    ref_free = _engine(model, params, 1).generate(prompts, **kw)
    eos = int(ref_free[0][4 + 3])
    a = _engine(model, params, 1).generate(prompts, eos_token_id=eos,
                                           **kw)
    b = _engine(model, params, 8).generate(prompts, eos_token_id=eos,
                                           **kw)
    np.testing.assert_array_equal(a[0], b[0])


def test_fused_compile_cache_one_program_per_bucket(tiny):
    """Varying batch sizes inside one power-of-two bucket reuse ONE
    compiled fused body — the shape-bucketing layer that keeps the
    compile cache bounded and warm across continuous-batching churn."""
    model, params = tiny
    eng = _engine(model, params, 4)
    prompts3 = [[2, 4, 6], [3, 5, 7], [4, 6, 8]]
    eng.generate(prompts3, max_new_tokens=6)          # batch 3 -> bucket 4
    n1 = eng._fused_greedy_jit._cache_size()
    assert n1 == 1
    prompts4 = prompts3 + [[5, 7, 9]]
    eng.generate(prompts4, max_new_tokens=6,
                 uids=[10, 11, 12, 13])               # batch 4 -> bucket 4
    eng.generate(prompts3[:2], max_new_tokens=6,
                 uids=[20, 21])                       # batch 2 -> bucket 2
    assert eng._fused_greedy_jit._cache_size() == n1 + 1  # bucket-2 only


def test_fused_host_syncs_leq_one_per_window(tiny):
    """The dispatch win, asserted through the telemetry counter: host
    syncs per generated token <= 1/K (one [N, K] transfer per window;
    the first token comes from the prefill logits)."""
    from deepspeed_tpu.telemetry import get_registry
    model, params = tiny
    K = 8
    eng = _engine(model, params, K, num_blocks=65)
    syncs = get_registry().counter("inference_decode_host_syncs_total")
    before = syncs.value
    new_tokens = 32
    outs = eng.generate([list(range(2, 10))], max_new_tokens=new_tokens)
    assert len(outs[0]) == 8 + new_tokens
    delta = syncs.value - before
    # 31 post-prefill tokens in windows of <=8 -> 4 windows
    assert delta * K <= new_tokens
    # the gauge documents the configured K for scrapes
    assert get_registry().gauge(
        "inference_decode_window_size").value == K


def test_decode_host_syncs_per_token(tiny):
    """Exactly, on a warm engine: two rows of 16 new tokens are 15
    decoded tokens a row after the prefill's own, in windows of 8: two
    [N, K] transfers for 30 tokens, 1/15 of a sync a token."""
    from deepspeed_tpu.telemetry import get_registry
    model, params = tiny
    eng = _engine(model, params, 8, num_blocks=65)
    prompts = [[2, 4, 6, 8], [3, 5, 7]]
    eng.generate(prompts, max_new_tokens=16)
    reg = get_registry()
    syncs = reg.family_total("inference_decode_host_syncs_total")
    toks = reg.family_total("inference_decode_tokens_total")
    eng.generate(prompts, max_new_tokens=16, uids=[10, 11])
    assert reg.family_total("inference_decode_host_syncs_total") - syncs == 2
    assert reg.family_total("inference_decode_tokens_total") - toks == 30


def test_per_token_fallback_still_selectable(tiny):
    """decode_window=1 keeps the per-token hot loop (no fused dispatch):
    the acceptance fallback knob."""
    from deepspeed_tpu.telemetry import get_registry
    model, params = tiny
    eng = _engine(model, params, 1)
    assert eng.decode_window == 1
    syncs = get_registry().counter("inference_decode_host_syncs_total")
    before = syncs.value
    eng.generate([[2, 4, 6]], max_new_tokens=8)
    # one transfer per decoded token (7 decode steps after the prefill
    # token) — the counter tells the two paths apart
    assert syncs.value - before == 7


def test_scheduler_fused_window_parity_and_streaming(tiny):
    """The SplitFuse fast path hands the fused window a stable greedy
    decode set; every token still streams through on_token in order, and
    results match the per-token engine exactly."""
    from deepspeed_tpu.inference.v2.scheduler import \
        DynamicSplitFuseScheduler
    model, params = tiny
    ref = _engine(model, params, 1).generate(
        [[2, 4, 6, 8], [3, 5, 7]], max_new_tokens=10, uids=[90, 91])
    eng = _engine(model, params, 8)
    seen = {101: [], 102: []}
    sched = DynamicSplitFuseScheduler(eng, token_budget=32, chunk=16)
    sched.submit(101, [2, 4, 6, 8], max_new_tokens=10,
                 on_token=lambda u, t, f: seen[u].append((t, f)))
    sched.submit(102, [3, 5, 7], max_new_tokens=10,
                 on_token=lambda u, t, f: seen[u].append((t, f)))
    sched.run()
    outs = sched.results()
    np.testing.assert_array_equal(outs[101], ref[0])
    np.testing.assert_array_equal(outs[102], ref[1])
    # streaming: every generated token fired exactly once, in order,
    # finished flag on the last only
    assert [t for t, _ in seen[101]] == list(ref[0][4:])
    assert [t for t, _ in seen[102]] == list(ref[1][3:])
    for uid in (101, 102):
        flags = [f for _, f in seen[uid]]
        assert flags[-1] and not any(flags[:-1])


def test_scheduler_window_respects_per_request_budget_and_eos(tiny):
    """Heterogeneous budgets/eos inside one window: rows mask out at
    their own limits on device (no overshoot past max_new_tokens, EOS
    included then the row stops)."""
    from deepspeed_tpu.inference.v2.scheduler import \
        DynamicSplitFuseScheduler
    model, params = tiny
    ref = _engine(model, params, 1).generate(
        [[2, 4, 6, 8]], max_new_tokens=20, uids=[77])
    eos = int(ref[0][4 + 5])
    eng = _engine(model, params, 8)
    sched = DynamicSplitFuseScheduler(eng, token_budget=32, chunk=16)
    sched.submit(1, [2, 4, 6, 8], max_new_tokens=3)          # budget cut
    sched.submit(2, [2, 4, 6, 8], max_new_tokens=20,
                 eos_token_id=eos)                           # eos cut
    sched.run()
    outs = sched.results()
    np.testing.assert_array_equal(outs[1], ref[0][:4 + 3])
    np.testing.assert_array_equal(outs[2], ref[0][:4 + 6])
    assert outs[2][-1] == eos


def test_scheduler_window_runs_at_saturation(tiny):
    """Sequence slots full with a queued backlog: no prefill can be
    composed anyway, so the fused window must still run (the dispatch
    win must not vanish at exactly server saturation). Results stay
    identical to the per-token engine; step count shows windows engaged
    while the backlog waited."""
    from deepspeed_tpu.inference.v2.scheduler import \
        DynamicSplitFuseScheduler
    model, params = tiny
    ref_eng = _engine(model, params, 1)
    refs = [ref_eng.generate([p], max_new_tokens=12, uids=[90 + i])[0]
            for i, p in enumerate([[2, 4, 6, 8], [3, 5, 7], [9, 11]])]
    eng = _engine(model, params, 8, max_tracked_sequences=2)
    sched = DynamicSplitFuseScheduler(eng, token_budget=32, chunk=16)
    sched.submit(1, [2, 4, 6, 8], max_new_tokens=12)
    sched.submit(2, [3, 5, 7], max_new_tokens=12)
    sched.submit(3, [9, 11], max_new_tokens=12)   # waits on a slot
    sched.run()
    outs = sched.results()
    for uid, ref in zip((1, 2, 3), refs):
        np.testing.assert_array_equal(outs[uid], ref)
    # 3 requests x 12 tokens with K=8 windows: far fewer steps than the
    # ~36 the per-token path would need — windows ran under backlog
    assert sched.steps < 14, sched.steps


def test_window_budget_not_cut_by_ragged_batch_cap(tiny):
    """_window_steps_left halves only against the KV block pool:
    max_ragged_batch_size is put()'s prefill cap (one pass over that
    many tokens), and a window is K sequential steps of N tokens — a
    batch whose N*K exceeds the cap must still get the full window."""
    model, params = tiny
    eng = _engine(model, params, 8, max_ragged_batch_size=16,
                  num_blocks=65)
    uids = [1, 2, 3]
    eng.put(uids, [[2, 4, 6]] * 3)
    # 3 rows x K=8 = 24 > max_ragged_batch_size=16; blocks are plentiful
    sl = eng._window_steps_left(uids, [8, 8, 8])
    assert sl == [8, 8, 8]
    for u in uids:
        eng.flush(u)


def test_serving_runtime_streams_fused_window(tiny):
    """End-to-end wiring through serve/: the async ServingEngine over a
    fused-window engine streams the same tokens the per-token engine
    produces (the runtime changes WHEN work runs, never what it
    computes)."""
    import asyncio

    from deepspeed_tpu.inference.v2.serve import (ServingConfig,
                                                  ServingEngine)
    model, params = tiny
    ref = _engine(model, params, 1).generate(
        [[2, 4, 6, 8]], max_new_tokens=10, uids=[90])

    async def drive():
        serving = ServingEngine(_engine(model, params, 8),
                                ServingConfig(token_budget=32, chunk=16))
        await serving.start()
        try:
            stream = await serving.submit([2, 4, 6, 8], 10)
            toks = [t async for t in stream]
        finally:
            await serving.stop()
        return toks

    toks = asyncio.run(drive())
    assert toks == list(ref[0][4:])


# -- launch-ahead: generate() queues window w + 1 before it fetches w ------
def _ahead_total():
    from deepspeed_tpu.telemetry import get_registry
    return get_registry().family_total(
        "inference_decode_windows_ahead_total")


@pytest.fixture(scope="module")
def engines(tiny):
    """(per-token engine, window-8 engine) shared by the cases below: a
    call flushes its rows, so one engine serves them all and its
    programs compile once."""
    model, params = tiny
    return (_engine(model, params, 1, num_blocks=65),
            _engine(model, params, 8, num_blocks=65))


def _synchronous(eng, monkeypatch):
    """The same engine, every window collected before the next is
    scheduled: generate()'s own fallback, the loop the scheduler runs."""
    monkeypatch.setattr(eng, "_window_steps_ahead", lambda *a, **k: None)


def _eos_after(per_token, prompts, row, nth, **kw):
    """The token row ``row`` emits ``nth`` tokens in (per-token engine),
    which it must not have emitted before."""
    free = per_token.generate(prompts, **kw)
    made = [int(t) for t in free[row][len(prompts[row]):]]
    assert made[nth] not in made[:nth], made
    return made[nth]


SAMPLED = dict(temperature=0.8, top_p=0.9, top_k=20, seed=5)
AHEAD_CASES = {
    # name: (prompts, generate kwargs, (row, nth) of the EOS or None)
    "greedy_block_boundary": (
        [list(range(3, 17)), [2, 4, 6]], dict(max_new_tokens=25), None),
    "greedy_eos_some_rows": (
        [[3, 5, 7, 9, 11, 13], [2, 4, 6]], dict(max_new_tokens=25), (0, 7)),
    # identical rows stop together: the window queued behind runs no step
    "greedy_eos_all_rows": (
        [[3, 5, 7, 9], [3, 5, 7, 9]], dict(max_new_tokens=30), (0, 11)),
    "sampled_fixed_seed": (
        [[3, 5, 7, 9, 11, 13, 15, 2, 4, 8], [2, 4, 6]],
        dict(max_new_tokens=26, **SAMPLED), None),
    "sampled_eos": (
        [[3, 5, 7, 9], [2, 4, 6, 8]], dict(max_new_tokens=26, **SAMPLED),
        (0, 9)),
    "budget_not_a_multiple": (
        [[2, 4, 6, 8], [3, 5, 7]], dict(max_new_tokens=21), None),
    "prompt_lengths_differ": (
        [list(range(2, 33)), [5], list(range(40, 57))],
        dict(max_new_tokens=28), None),
}


@pytest.mark.parametrize("case", sorted(AHEAD_CASES))
def test_launch_ahead_tokens_equal_the_synchronous_loops(engines, case,
                                                         monkeypatch):
    """generate() launches a window before it fetches the one before;
    the tokens are those of the loop that collects every window first
    (same engine, same code), and of the per-token engine."""
    per_token, eng = engines
    prompts, kw, stop = AHEAD_CASES[case]
    kw = dict(kw)
    if stop is not None:
        kw["eos_token_id"] = _eos_after(per_token, prompts, *stop, **kw)
    per_token = per_token.generate(prompts, **kw)
    if stop is not None:        # the EOS cut the row it was read from
        assert len(per_token[stop[0]]) \
            == len(prompts[stop[0]]) + stop[1] + 1
    before = _ahead_total()
    ahead = eng.generate(prompts, **kw)
    assert _ahead_total() > before      # a window was queued ahead
    _synchronous(eng, monkeypatch)
    before = _ahead_total()
    sync = eng.generate(prompts, uids=[10, 11, 12][:len(prompts)], **kw)
    assert _ahead_total() == before
    for a, b, c in zip(ahead, sync, per_token):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)
    assert eng.state_manager.tracked_sequences() == 0


def test_window_behind_dead_rows_runs_no_step(engines):
    """Every row emits its EOS inside window w: window w + 1, queued
    before the host knew, carries them as masked rows and emits
    nothing (no token, no cache position, no decode token counted)."""
    from deepspeed_tpu.telemetry import get_registry
    per_token, eng = engines
    prompts, kw, stop = AHEAD_CASES["greedy_eos_all_rows"]
    eos = _eos_after(per_token, prompts, *stop, **kw)
    reg = get_registry()
    toks = reg.family_total("inference_decode_tokens_total")
    syncs = reg.family_total("inference_decode_host_syncs_total")
    out = eng.generate(prompts, eos_token_id=eos, **kw)
    # 12 tokens a row: the prefill's, 8 of window 1, 3 of window 2 (the
    # EOS); window 3 was in flight when the host saw it
    assert [len(o) for o in out] == [4 + 12, 4 + 12]
    assert reg.family_total("inference_decode_tokens_total") - toks == 22
    assert reg.family_total("inference_decode_host_syncs_total") \
        - syncs == 3


TIGHT = {
    # name: (prompts, state-manager limits, new tokens, (row, nth) of the
    #        EOS or None, windows launched ahead, how the call ends)
    # four usable blocks, two rows of 14: window 2's blocks fit behind
    # window 1, window 3's do not; collected first it is halved to 2
    # steps, and the step after has no block
    "pool_raises": ([list(range(3, 17)), list(range(20, 34))],
                    dict(num_blocks=5), 30, None, 1, "raises"),
    # the same pool holds a budget that ends inside it: nothing falls back
    "pool_finishes": ([list(range(3, 17)), list(range(20, 34))],
                      dict(num_blocks=5), 18, None, 2, "finishes"),
    # 22 positions: windows of 8, 8 and 2 fit; a fourth has no room, so
    # the loop collects the third first and raises as it always did ...
    "max_seq_len_raises": ([[3, 5, 7, 9]], dict(max_seq_len=22), 40, None,
                           2, "raises"),
    # ... unless the row emits its EOS in the third, which only the
    # collected tokens say
    "max_seq_len_finishes": ([[3, 5, 7, 9]], dict(max_seq_len=22), 40,
                             (0, 18), 2, "finishes"),
}


@pytest.fixture(scope="module")
def tight_engines(tiny):
    """One window-8 engine a set of limits in TIGHT, made on demand."""
    model, params = tiny
    made = {}
    return lambda **limits: made.setdefault(
        tuple(sorted(limits.items())), _engine(model, params, 8, **limits))


@pytest.mark.parametrize("case", sorted(TIGHT))
def test_no_room_for_two_windows_falls_back(engines, tight_engines,
                                            monkeypatch, case):
    """Where the window in flight plus the next do not fit (the pool,
    ``max_seq_len``), generate() collects first and schedules as the
    synchronous loop does: the same tokens, or the same RuntimeError
    where that loop raises, and nothing left behind either way."""
    prompts, limits, new_tokens, stop, n_ahead, ends = TIGHT[case]
    kw = dict(max_new_tokens=new_tokens)
    if stop is not None:
        kw["eos_token_id"] = _eos_after(engines[0], prompts, *stop,
                                        max_new_tokens=stop[1] + 1)

    def run(eng, uids):
        start = eng.state_manager.free_blocks()
        try:
            return eng.generate(prompts, uids=uids, **kw)
        except RuntimeError as e:
            return str(e)
        finally:
            assert eng.state_manager.free_blocks() == start
            assert eng.state_manager.tracked_sequences() == 0

    eng = tight_engines(**limits)
    before = _ahead_total()
    ahead = run(eng, list(range(len(prompts))))
    assert _ahead_total() - before == n_ahead
    _synchronous(eng, monkeypatch)
    sync = run(eng, list(range(10, 10 + len(prompts))))
    if ends == "finishes":
        for a, b in zip(ahead, sync):
            np.testing.assert_array_equal(a, b)
        want = stop[1] + 1 if stop is not None else new_tokens
        assert [len(a) - len(p) for a, p in zip(ahead, prompts)] \
            == [want] * len(prompts)
    else:
        assert ahead == sync
        assert ahead.startswith("generation not schedulable")


def test_exception_with_a_window_in_flight_leaves_nothing_behind(engines):
    """The third launch fails while the second window is in flight:
    generate() drops it and frees the call's blocks and sequence slots,
    and the engine serves the next call as if nothing had happened."""
    _, eng = engines
    prompts = [list(range(3, 17)), [2, 4, 6]]
    want = eng.generate(prompts, max_new_tokens=40)
    sm = eng.state_manager
    start = sm.free_blocks()
    launch, calls = eng._launch_window, []

    def failing(*a, **k):
        calls.append(k.get("behind"))
        if len(calls) == 3:
            raise ValueError("boom")
        return launch(*a, **k)

    eng._launch_window = failing
    try:
        with pytest.raises(ValueError, match="boom"):
            eng.generate(prompts, max_new_tokens=40, uids=[7, 8])
    finally:
        del eng._launch_window      # the class's again
    assert calls[0] is None and calls[2] is not None   # one was in flight
    assert sm.free_blocks() == start
    assert sm.tracked_sequences() == 0
    for a, b in zip(want, eng.generate(prompts, max_new_tokens=40,
                                       uids=[7, 8])):
        np.testing.assert_array_equal(a, b)


def test_rows_seen_dead_leave_at_a_smaller_bucket(engines):
    """Three of four rows stop early: once the host has seen them the
    one left fits the batch bucket of 1, so the loop collects, the rows
    leave, and launch-ahead goes on with the row that is left."""
    from deepspeed_tpu.telemetry import trace
    per_token, eng = engines
    prompts = [[3, 5, 7, 9]] * 3 + [[2, 4, 6]]
    kw = dict(max_new_tokens=60)
    eos = _eos_after(per_token, prompts, 0, 5, **kw)
    ref = per_token.generate(prompts, eos_token_id=eos, **kw)
    assert len(ref[3]) == 3 + 60        # the odd row runs to its budget
    trace.clear()
    out = eng.generate(prompts, eos_token_id=eos, **kw)
    for a, b in zip(ref, out):
        np.testing.assert_array_equal(a, b)
    batches = [(s["attrs"]["batch"], s["attrs"]["ahead"])
               for s in trace.export("decode_window")]
    assert batches[:2] == [(4, 0), (4, 1)]
    assert (1, 0) in batches and batches[-1] == (1, 1)


def test_windows_ahead_counter_says_where_it_engaged(engines):
    """windows - 1 a generate() call; none under speculation, on the
    per-token path, or through the scheduler, which collects every
    window it launches before it returns."""
    from deepspeed_tpu.inference.v2.scheduler import \
        DynamicSplitFuseScheduler
    per_token, eng = engines
    prompts = [[2, 4, 6, 8], [3, 5, 7]]
    before = _ahead_total()
    eng.generate(prompts, max_new_tokens=33)    # 32 decoded: 4 windows
    assert _ahead_total() - before == 3
    before = _ahead_total()
    eng.generate(prompts, max_new_tokens=33, speculative=True)
    per_token.generate(prompts, max_new_tokens=33)
    sched = DynamicSplitFuseScheduler(eng, token_budget=32, chunk=16)
    for uid, p in enumerate(prompts):
        sched.submit(100 + uid, p, max_new_tokens=33)
    flying = []
    launch = eng._launch_window
    collect = eng._collect_window
    eng._launch_window = lambda *a, **k: (
        flying.append(1), launch(*a, **k))[1]
    eng._collect_window = lambda *a, **k: (
        flying.pop(), collect(*a, **k))[1]
    try:
        while sched.pending():
            sched.step()
            assert not flying       # collected before step() returned
    finally:
        del eng._launch_window, eng._collect_window
    assert _ahead_total() == before
    assert sched.steps < 10     # the windows did run


def test_launch_ahead_spans_read_as_one_window_each(engines):
    """What the benchmark's readers rest on (benchmark/readers: imported,
    not edited): a ``decode_window`` span a window launched, in launch
    order and never nested, ``ahead`` on each; every leaf under
    ``generate`` or a ``decode_window``; ``host_ms.gen``'s reader finds
    its four leaves for every window."""
    import json
    from pathlib import Path

    from benchmark.readers import gen_span_time
    from deepspeed_tpu.telemetry import trace
    _, eng = engines
    trace.clear()
    eng.generate([[2, 4, 6, 8], [3, 5, 7]], max_new_tokens=41)
    ring = trace.export()
    by_id = {s["id"]: s for s in ring}
    windows = sorted((s for s in ring if s["name"] == "decode_window"),
                     key=lambda s: s["start"])
    assert [s["attrs"]["ahead"] for s in windows] == [0, 1, 1, 1, 1]
    for a, b in zip(windows, windows[1:]):      # disjoint, in order
        assert a["start"] + a["duration_s"] <= b["start"]
    root, = (s for s in ring if s["name"] == "generate")
    # a dispatch span holds its two parts, the upload and the call
    parts = {"ragged_upload": "ragged_dispatch",
             "ragged_call": "ragged_dispatch",
             "window_upload": "window_dispatch",
             "window_call": "window_dispatch"}
    holders = ("generate", "decode_window", "ragged_step",
               "ragged_dispatch", "window_dispatch")
    for s in ring:
        if s is root:
            continue
        parent = by_id[s["parent"]]
        if s["name"] in parts:
            assert parent["name"] == parts[s["name"]]
        else:
            assert parent["name"] in holders[:3], (s["name"], parent)
        if s["name"] in ("decode_window", "ragged_step"):
            assert parent is root
        elif s["name"] in holders:
            assert sorted(c["name"] for c in ring
                          if c["parent"] == s["id"]) == sorted(
                n for n, p in parts.items() if p == s["name"])
        else:                                   # a leaf: nothing under it
            assert not any(c["parent"] == s["id"] for c in ring)
    # leaves of one thread do not overlap
    leaves = sorted((s for s in ring if s["name"] not in holders),
                    key=lambda s: s["start"])
    for a, b in zip(leaves, leaves[1:]):
        assert a["start"] + a["duration_s"] <= b["start"]
    fetches = [s for s in ring if s["name"] == "window_fetch"]
    assert len(fetches) == len(windows)
    metric = json.loads((Path(gen_span_time.__file__).parents[1]
                         / "layer_metrics" / "host_ms.gen.json").read_text())
    sums = gen_span_time.per_window(ring, metric["params"]["spans"],
                                    metric["params"]["before"])
    assert len(sums) == len(windows) and min(sums) > 0


def test_token_log_follows_windows_queued_ahead(tiny):
    """Prefix caching logs every FED token; a window queued ahead is fed
    a token the host had not seen at its launch (the last emit of the
    window before). At flush the log is the row without its last token."""
    model, params = tiny
    eng = _engine(model, params, 8, enable_prefix_caching=True)
    logs = {}
    register = eng.state_manager._register_prefix
    eng.state_manager._register_prefix = lambda seq: (
        logs.__setitem__(seq.uid, list(seq.token_log)), register(seq))[1]
    before = _ahead_total()
    outs = eng.generate([[2, 4, 6, 8], [3, 5, 7]], max_new_tokens=22)
    assert _ahead_total() - before == 2
    for uid, out in enumerate(outs):
        assert logs[uid] == [int(t) for t in out[:-1]]
