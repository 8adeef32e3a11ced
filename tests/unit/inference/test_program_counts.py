"""Exact counts the serving programs make, asserted where they are made.

Every serving path buckets its shapes so that, once each bucket has been
visited twice (a bucket's first call compiles against the fresh,
unsharded KV pool and its repeat against the donated, sharded one), the
path compiles NOTHING more: ``xla_steady_state_recompiles_total`` stays
at zero under ``watchdog.mark_steady``. One case a path here; the paths
that had such a test already keep it where it was:
``test_perf_forensics.py`` (fused decode, two buckets),
``test_ragged_attention.py`` (the mixed sweep and its program counts),
``test_kv_quant_serving.py`` (int8 KV through the scheduler),
``test_kv_spill.py`` (spill and restore), ``test_weight_push.py`` (hot
swap), ``test_chaos_serving.py`` (reconnect), ``test_online.py`` (window
adaptation), ``tests/unit/rl/test_rl_loop.py`` (learner step).
"""

import asyncio
import contextlib

import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.config_v2 import DSStateManagerConfig
from deepspeed_tpu.inference.v2.serve import (RemoteReplica, ReplicaRouter,
                                              ReplicaWorker, RouterConfig,
                                              ServingConfig, build_replicas)
from deepspeed_tpu.telemetry import (FlightRecorder, MetricsRegistry,
                                     get_registry, set_recorder,
                                     set_registry, watchdog)
from deepspeed_tpu.telemetry import context as trace_context

SEQ_LEN, NEW_TOKENS, WINDOW = 64, 16, 8
PROMPTS = [[2, 4, 6, 8], [3, 5, 7]]


@pytest.fixture(autouse=True)
def _fresh():
    prev = set_registry(MetricsRegistry())
    prev_rec = set_recorder(FlightRecorder())
    watchdog.reset()
    yield
    watchdog.reset()
    set_recorder(prev_rec)
    set_registry(prev)


@pytest.fixture(scope="module")
def tiny(tiny_model_128):
    return tiny_model_128


def _engine(tiny, num_blocks=65, sm=None, **kw):
    model, params = tiny
    return InferenceEngineV2(
        model, RaggedInferenceEngineConfig(
            state_manager=DSStateManagerConfig(
                max_tracked_sequences=8, max_seq_len=SEQ_LEN,
                num_blocks=num_blocks, block_size=16, **(sm or {})),
            dtype="float32", prefill_bucket=16, decode_window=WINDOW, **kw),
        params=params)


def _total(name):
    return get_registry().family_total(name)


@contextlib.contextmanager
def _steady(seen):
    """The steady window: what compiles inside it is a recompile."""
    watchdog.mark_steady(True)
    try:
        yield
    finally:
        watchdog.mark_steady(False)
    seen.append(_total("xla_steady_state_recompiles_total"))


def _shared_prefix_prompts():
    """Two groups of three prompts over a shared 32-token prefix."""
    rng = np.random.default_rng(7)
    out = []
    for _ in range(2):
        prefix = list(map(int, rng.integers(1, 127, 32)))
        out += [prefix + list(map(int, rng.integers(1, 127, 6)))
                for _ in range(3)]
    return out


def _caching_engine(tiny):
    return _engine(tiny, sm=dict(enable_prefix_caching=True))


async def _wave(front, prompts, traced=False):
    for p in prompts:
        ctx = trace_context.use(trace_context.new_context(tenant="t")) \
            if traced else contextlib.nullcontext()
        with ctx:     # the context is read at submit, as a header would be
            stream = await front.submit(p, 2)
        await stream.drain()


# -- the paths ---------------------------------------------------------------
def _kv_quant(tiny, steady, tmp_path):
    """int8 KV through generate() (the scheduler's mix is held by
    test_kv_quant_serving.py)."""
    eng = _engine(tiny, kv_quant=True)
    eng.generate(PROMPTS, max_new_tokens=NEW_TOKENS)
    eng.generate(PROMPTS, max_new_tokens=NEW_TOKENS, uids=[20, 21])
    with steady():
        eng.generate(PROMPTS, max_new_tokens=NEW_TOKENS, uids=[10, 11])


def _spec(tiny, steady, tmp_path):
    """Draft-model speculation lives inside the window's while_loop: a
    request brings no program of its own."""
    model, params = tiny
    rng = np.random.default_rng(8)
    unit = [5, 9, 17, 23]
    replay = [unit * 6, list(map(int, rng.integers(1, 127, 24))),
              [3] + unit * 4, list(map(int, rng.integers(1, 127, 17)))]
    eng = _engine(tiny)
    eng.load_draft_model(model, params)       # self-draft
    kw = dict(max_new_tokens=NEW_TOKENS, speculative=True, spec_mode="draft")
    eng.generate(replay, **kw)
    eng.generate(replay, uids=[40, 41, 42, 43], **kw)
    with steady():
        eng.generate(replay, uids=[50, 51, 52, 53], **kw)


def _routed(tiny, steady, traced):
    async def run():
        router = ReplicaRouter(
            build_replicas([_caching_engine(tiny), _caching_engine(tiny)],
                           ServingConfig(token_budget=24, chunk=16)),
            RouterConfig(placement="affinity", monitor_interval_s=0.0))
        await router.start()
        try:
            prompts = _shared_prefix_prompts()
            await _wave(router, prompts)
            await _wave(router, prompts)
            with steady():
                await _wave(router, prompts, traced=traced)
        finally:
            await router.stop()

    asyncio.run(run())


def _router(tiny, steady, tmp_path):
    _routed(tiny, steady, traced=False)


def _routed_trace(tiny, steady, tmp_path):
    """Every request continues an explicit TraceContext: a trace id is
    span metadata on the host and never part of a program's signature."""
    _routed(tiny, steady, traced=True)


def _remote_replica(tiny, steady, tmp_path):
    """Through a loopback socket: the wire adds serialization, never a
    program."""
    async def run():
        worker = ReplicaWorker(_caching_engine(tiny),
                               ServingConfig(token_budget=24, chunk=16),
                               name="remote0")
        host, port = await worker.start()
        router = ReplicaRouter([RemoteReplica("remote0", host, port)],
                               RouterConfig(monitor_interval_s=0.0))
        await router.start()
        try:
            prompts = _shared_prefix_prompts()
            await _wave(router, prompts)
            await _wave(router, prompts)
            with steady():
                await _wave(router, prompts)
        finally:
            await router.stop()
            await worker.stop()

    asyncio.run(run())


def _spill_placement(tiny, steady, tmp_path):
    """A turn-2 prompt whose prefix lives only in one replica's spill
    tier goes there on the advertised claim and is served by restore,
    through the donated-pool scatter the two warm conversations
    specialised."""
    spiller = _engine(tiny, num_blocks=11, sm=dict(
        enable_prefix_caching=True, enable_kv_spill=True,
        kv_spill_dir=str(tmp_path)))

    def conversation(seed):
        """Turn 1, then ~16 blocks of other prompts through the 11-block
        pool, so that every block of turn 1 is evicted to the spill
        tier; returns the turn-2 prompt."""
        r = np.random.default_rng(seed)
        first = list(map(int, r.integers(1, 127, 48)))
        turn1 = spiller.generate([first], max_new_tokens=2,
                                 uids=[seed * 100])[0]
        for k in range(4):
            spiller.generate([list(map(int, r.integers(1, 127, 56)))],
                             max_new_tokens=2, uids=[seed * 100 + 1 + k])
        return list(map(int, turn1)) + [3, 5]

    async def run():
        warm = [conversation(2), conversation(3)]
        turn2 = conversation(4)
        router = ReplicaRouter(
            build_replicas([spiller, _caching_engine(tiny)],
                           ServingConfig(token_budget=24, chunk=16)),
            RouterConfig())
        await router.start()
        try:
            for p in warm:
                await (await router.submit(p, 4)).drain()
            restored = _total("router_spill_placement_restored_blocks_total")
            with steady():
                await (await router.submit(turn2, 4)).drain()
            # 3 of the prompt's blocks came back from the spill tier
            # (48 of its 52 tokens), none was recomputed
            assert _total("router_spill_placement_restored_blocks_total") \
                - restored == 3
        finally:
            await router.stop()

    asyncio.run(run())


@pytest.mark.parametrize("path", [
    _kv_quant, _spec, _router, _routed_trace, _remote_replica,
    _spill_placement], ids=lambda f: f.__name__[1:])
def test_steady_state_recompiles(tiny, tmp_path, path):
    seen = []
    path(tiny, lambda: _steady(seen), tmp_path)
    assert seen == [0], f"recompiles counted after the steady window: " \
                        f"{seen} (empty: the path never reached one)"


def test_compile_events_fused_decode(tiny):
    """One fused decode program a batch bucket, compiled twice: once
    against the fresh pool, once against the donated one. A third event
    is a bucket that leaked a shape."""
    eng = _engine(tiny)
    eng.generate(PROMPTS, max_new_tokens=NEW_TOKENS)
    eng.generate(PROMPTS, max_new_tokens=NEW_TOKENS, uids=[20, 21])
    eng.generate(PROMPTS, max_new_tokens=NEW_TOKENS, uids=[10, 11])
    fused = [e for e in watchdog.events()
             if e["program"] == "decode_window_greedy"]
    assert len(fused) == 2
