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
