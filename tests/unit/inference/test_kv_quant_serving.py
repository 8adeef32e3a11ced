"""int8 KV serving through the Pallas kernel family (kv_quant).

The contract under test (paged_model per-block scales + the quant
kernel variants in kernels/paged_attention.py / ragged_attention.py +
the dropped ``use_kernel_decode`` gate in engine_v2):

* the quant ragged kernel matches the jnp gather-dequant reference on
  mixed rows, and a pure-decode quant ragged batch is bit-identical to
  the quant decode kernel (shared ``_page_update`` + ``_dequant_tile``);
* kernel-vs-fallback token streams are BIT-identical under kv_quant —
  greedy and fixed-seed sampled, fused windows 1 and 8, through
  generate() and through the SplitFuse scheduler's mixed traffic;
* kv_quant no longer forfeits the kernels: the ragged quant kernel
  actually runs (not the gather fallback), with ZERO steady-state
  recompiles under mixed traffic after the double-warm discipline;
* the disaggregated handoff carries the per-(block, head) scale leaves
  bit-exactly at the new granularity, and routed prefill->decode
  streams stay bit-identical to colocated serving with kv_quant on.
"""

import asyncio

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import (DynamicSplitFuseScheduler,
                                        InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.config_v2 import DSStateManagerConfig
from deepspeed_tpu.models import TransformerConfig, TransformerLM


@pytest.fixture(scope="module")
def tiny(tiny_model_128):
    # session-shared tiny model (tests/unit/conftest.py): one
    # init_params for the whole tier instead of one per module
    return tiny_model_128


def _engine(model, params, kernel=True, window=8, **kw):
    smc = dict(max_tracked_sequences=8, max_seq_len=128, num_blocks=65,
               block_size=16)
    smc.update(kw.pop("sm", {}))
    return InferenceEngineV2(
        model, RaggedInferenceEngineConfig(
            state_manager=DSStateManagerConfig(**smc),
            dtype="float32", prefill_bucket=16, decode_window=window,
            kv_quant=True, use_paged_kernel=kernel, **kw),
        params=params)


# ---------------------------------------------------------------------------
# kernel level
# ---------------------------------------------------------------------------
def _quant_pool(rng, nb, bs, kvh, hd):
    """Random int8 layer [nb, bs, kvh, hd] + per-(block, head) scales."""
    q = rng.integers(-127, 128, size=(nb, bs, kvh, hd)).astype(np.int8)
    s = rng.uniform(0.01, 0.2, size=(nb, kvh)).astype(np.float32)
    return jnp.asarray(q), jnp.asarray(s)


def test_quant_ragged_kernel_matches_gather_dequant_reference(stored_pool):
    from deepspeed_tpu.inference.v2.kernels.ragged_attention import \
        ragged_attention

    rng = np.random.default_rng(0)
    nb, bs, kvh, hd, nh = 9, 16, 2, 16, 4
    kq, ks = _quant_pool(rng, nb, bs, kvh, hd)
    vq, vs = _quant_pool(rng, nb, bs, kvh, hd)
    tables = np.array([[1, 2], [3, 4], [5, 0]], np.int32)
    row_ids, lengths = [], []
    for r, positions in enumerate([range(10), [30], [5]]):
        for p in positions:
            row_ids.append(r)
            lengths.append(p + 1)
    T = 16
    pad = T - len(row_ids)
    row_ids += [0] * pad
    lengths += [0] * pad
    q = jnp.asarray(rng.normal(size=(T, nh, hd)), jnp.float32)
    # the pool as stored: [L, nb, bs, kvh * hd], layer 1 of two attended
    kp, vp = stored_pool(kq, 2, 1), stored_pool(vq, 2, 1)
    out = np.asarray(ragged_attention(
        q, kp, vp, 1, jnp.asarray(row_ids, jnp.int32),
        jnp.asarray(lengths, jnp.int32), jnp.asarray(tables),
        k_scale=ks, v_scale=vs))
    # reference: dequantize like paged_model._kv_read, dense softmax
    kd = np.asarray(kq, np.float32) * np.asarray(ks)[:, None, :, None]
    vd = np.asarray(vq, np.float32) * np.asarray(vs)[:, None, :, None]
    ctx = tables.shape[1] * bs
    group = nh // kvh
    ref = np.zeros_like(out)
    for t in range(T):
        if lengths[t] == 0:
            continue
        kt = np.repeat(kd[tables[row_ids[t]]].reshape(ctx, kvh, hd),
                       group, axis=1)
        vt = np.repeat(vd[tables[row_ids[t]]].reshape(ctx, kvh, hd),
                       group, axis=1)
        mask = np.arange(ctx) < lengths[t]
        for h in range(nh):
            s = (np.asarray(q[t, h]) @ kt[:, h].T) / np.sqrt(hd)
            s = np.where(mask, s, -1e30)
            p = np.exp(s - s.max())
            ref[t, h] = (p / p.sum()) @ vt[:, h]
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_quant_ragged_pure_decode_matches_quant_decode_kernel(stored_pool):
    from deepspeed_tpu.inference.v2.kernels.paged_attention import \
        paged_attention
    from deepspeed_tpu.inference.v2.kernels.ragged_attention import \
        ragged_attention

    rng = np.random.default_rng(1)
    nb, bs, kvh, hd, nh = 9, 16, 2, 16, 4
    kq, ks = _quant_pool(rng, nb, bs, kvh, hd)
    vq, vs = _quant_pool(rng, nb, bs, kvh, hd)
    tables = jnp.asarray(np.array([[1, 2], [3, 4], [5, 6], [7, 8]],
                                  np.int32))
    lengths = jnp.asarray([17, 30, 5, 32], jnp.int32)
    q = jnp.asarray(rng.normal(size=(4, nh, hd)), jnp.float32)
    kp, vp = stored_pool(kq, 2, 1), stored_pool(vq, 2, 1)
    ragged = np.asarray(ragged_attention(
        q, kp, vp, 1, jnp.arange(4, dtype=jnp.int32), lengths, tables,
        k_scale=ks, v_scale=vs))
    decode = np.asarray(paged_attention(q, kp, vp, 1, tables, lengths,
                                        k_scale=ks, v_scale=vs))
    np.testing.assert_array_equal(ragged, decode)


# ---------------------------------------------------------------------------
# the stored layout against the per-head one it replaced
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("against", ["reference", "parent"])
def test_a_decode_launch_over_partial_chunks_of_an_int8_pool(against):
    """PR 50 over an int8 pool (``walk_cases``' ``tiled-int8``: rows
    whose last chunk holds 1, 2, 3, cp - 1 and cp pages, contexts that
    end on a chunk): the pages are waited for by their bytes (an int8
    page's), the scales' copies keep their own waits; against the gathering
    reference's dequant at today's tolerance, and against the parent's
    output on the same inputs to the bit."""
    from tests.unit.inference import walk_cases
    got = walk_cases.output("tiled-int8")
    if against == "parent":
        np.testing.assert_array_equal(
            got, walk_cases.parent_output("tiled-int8"))
        return
    np.testing.assert_allclose(got, walk_cases.reference("tiled-int8"),
                               rtol=0, atol=2e-5)
    lens, _ = walk_cases.lengths("tiled-int8")
    assert not got[lens == 0].any()


def _per_head_write(kc, ksc, l, blocks, offs, k):
    """``paged_model._kv_write`` as it was over a ``[L, nb, bs, kvh,
    hd]`` pool (PR 36's tree), kept here as the semantics the stored
    layout must reproduce bit for bit."""
    if ksc is None:
        return kc.at[l, blocks, offs].set(k.astype(kc.dtype)), None
    xf = k.astype(jnp.float32)
    tok_scale = jnp.max(jnp.abs(xf), axis=-1) / 127.0
    old = ksc[l]
    new = old.at[blocks].max(tok_scale)
    ratio = jnp.where(new > 0, old / jnp.where(new > 0, new, 1.0), 0.0)
    pages = jnp.round(kc[l, blocks].astype(jnp.float32)
                      * ratio[blocks][:, None, :, None])
    kc = jnp.where(jnp.any(tok_scale > old[blocks]),
                   kc.at[l, blocks].set(pages.astype(jnp.int8)), kc)
    s_tok = jnp.where(new > 0, new, 1.0)[blocks]
    q = jnp.clip(jnp.round(xf / s_tok[..., None]), -127, 127)
    return kc.at[l, blocks, offs].set(q.astype(jnp.int8)), \
        ksc.at[l].set(new)


def _per_head_read(kc, ksc, l, table, dtype):
    pages = kc[l][table]
    if ksc is None:
        return pages
    return (pages.astype(jnp.float32)
            * ksc[l][table][..., None, :, None]).astype(dtype)


@pytest.mark.parametrize("case", ["bf16", "int8-requant", "int8-steady"])
def test_write_then_read_is_the_per_head_pools(case):
    """Two write-sets into layer 1 of 3 through ``_kv_write`` and the
    pages back through ``_kv_read``: the stored ``[L, nb, bs, kvh * hd]``
    pool is the per-head pool's bytes reshaped, and the gathered pages
    equal, bit for bit. int8: the second write either grows a block's
    absmax (the requant branch rescales the block's earlier rows, a
    (block, head) ratio over the head's lanes) or stays under it."""
    from deepspeed_tpu.inference.v2 import paged_model as pm

    L, nb, bs, kvh, hd, l = 3, 6, 16, 4, 8, 1
    quant = case != "bf16"
    dt = jnp.int8 if quant else jnp.bfloat16
    rng = np.random.default_rng(7)
    new = {"k": jnp.zeros((L, nb, bs, kvh * hd), dt)}
    old = {"k": jnp.zeros((L, nb, bs, kvh, hd), dt)}
    if quant:
        new["ks"] = old["ks"] = jnp.zeros((L, nb, kvh), jnp.float32)
    # a 20-token chunk over blocks 2 and 4, then one decode token in 4
    writes = [(np.repeat([2, 4], [16, 4]), np.r_[0:16, 0:4], 1.0),
              (np.array([4]), np.array([4]),
               8.0 if case == "int8-requant" else 0.1)]
    for blocks, offs, gain in writes:
        x = jnp.asarray(rng.standard_normal((len(blocks), kvh, hd)) * gain,
                        jnp.bfloat16)
        grows = quant and bool(jnp.any(
            jnp.max(jnp.abs(x.astype(jnp.float32)), -1) / 127.0
            > new["ks"][l][blocks]))
        b, o = jnp.asarray(blocks), jnp.asarray(offs)
        new["k"], ks = jax.jit(pm._kv_write)(new["k"], new.get("ks"), l,
                                            b, o, x)
        old["k"], ks0 = jax.jit(_per_head_write)(old["k"], old.get("ks"), l,
                                                 b, o, x)
        if quant:
            new["ks"], old["ks"] = ks, ks0
    if quant:       # the second write took the branch the case names
        assert grows == (case == "int8-requant")
        np.testing.assert_array_equal(new["ks"], old["ks"])
    np.testing.assert_array_equal(
        np.asarray(new["k"]).reshape(old["k"].shape), np.asarray(old["k"]))
    assert np.asarray(new["k"])[l, 4, 4].any()
    assert not np.asarray(new["k"])[[0, 2]].any()      # the layer's alone
    table = jnp.asarray([[2, 4, 0], [4, 0, 0]])
    got = pm._kv_read(new["k"], new.get("ks"), l, table, kvh, jnp.bfloat16)
    want = _per_head_read(old["k"], old.get("ks"), l, table, jnp.bfloat16)
    assert got.shape == (2, 3, bs, kvh, hd)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


# ---------------------------------------------------------------------------
# engine: kernel-vs-fallback stream parity (the bit-identity acceptance)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("window", [
    # slow tier: the window-1 (per-token) sweep doubles the parity
    # run; the fused window-8 path keeps tier-1 coverage
    pytest.param(1, marks=pytest.mark.slow), 8])
def test_generate_streams_kernel_vs_fallback_bit_identical(tiny, window):
    """Greedy AND fixed-seed sampled streams through generate() — the
    quant kernels vs the jnp gather-dequant fallback — must match to the
    bit at fused windows 1 and 8 (the write path is shared jnp; only
    the read dequant differs, and _dequant_tile mirrors _kv_read)."""
    model, params = tiny
    prompts = [list(range(3, 17)), [2, 4, 6], [5]]
    e_k = _engine(model, params, kernel=True, window=window)
    e_f = _engine(model, params, kernel=False, window=window)
    for i, kw in enumerate((dict(max_new_tokens=16),
                            dict(max_new_tokens=12, temperature=0.8,
                                 top_p=0.9, top_k=20, seed=5))):
        a = e_k.generate(prompts, uids=[10 * i + j for j in range(3)],
                         **kw)
        b = e_f.generate(prompts, uids=[10 * i + j for j in range(3)],
                         **kw)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)


def test_scheduler_mixed_traffic_parity_and_zero_steady_recompiles(tiny):
    """The acceptance criterion end-to-end: kv_quant mixed traffic
    (chunked prefill + interleaved fused decode through SplitFuse) runs
    the ragged quant kernel with ZERO steady-state recompiles after the
    double warmup, and its streams equal the gather fallback's."""
    from deepspeed_tpu.telemetry import (MetricsRegistry, get_registry,
                                         set_registry, watchdog)

    model, params = tiny
    rng = np.random.default_rng(3)
    prompts = [list(map(int, rng.integers(1, 127, n)))
               for n in (40, 7, 22, 3)]

    def traffic(sched, base):
        for i, p in enumerate(prompts[:2]):
            sched.submit(base + i, p, 8,
                         temperature=0.7 if i else 0.0, top_p=0.9,
                         seed=5)
        for _ in range(2):
            sched.step()
        for i, p in enumerate(prompts[2:]):
            sched.submit(base + 50 + i, p, 8)
        sched.run()
        return {uid: list(map(int, t))
                for uid, t in sched.results().items()}

    results, steady = {}, None
    for kernel in (True, False):
        prev = set_registry(MetricsRegistry())
        watchdog.reset()
        try:
            eng = _engine(model, params, kernel=kernel, window=8)
            sched = DynamicSplitFuseScheduler(eng, token_budget=24,
                                              chunk=16)
            traffic(sched, 100)
            traffic(sched, 200)   # absorb the fresh-pool respecialization
            if kernel:
                watchdog.mark_steady(True)
                try:
                    results[kernel] = traffic(sched, 300)
                finally:
                    watchdog.mark_steady(False)
                steady = get_registry().family_total(
                    "xla_steady_state_recompiles_total")
            else:
                results[kernel] = traffic(sched, 300)
        finally:
            set_registry(prev)
            watchdog.reset()
    assert steady == 0
    assert results[True] == results[False]


def test_quant_kernel_actually_runs_not_the_fallback(tiny, monkeypatch):
    """The gate is GONE: under kv_quant the ragged program traces the
    quant kernel (scales passed through), not the materializing gather."""
    import importlib
    # the kernels package re-exports the function under the same name,
    # shadowing the submodule attribute — resolve the module explicitly
    rk = importlib.import_module(
        "deepspeed_tpu.inference.v2.kernels.ragged_attention")

    model, params = tiny
    seen = {}
    orig = rk.ragged_attention

    def spy(q, kc, vc, layer, rows, lens, bt, k_scale=None, v_scale=None,
            runs=None):
        seen["called"] = True
        seen["scales"] = k_scale is not None
        seen["whole"] = kc.shape == eng.kv_cache["k"].shape
        assert runs is None     # off the TPU a launch is handed no runs
        return orig(q, kc, vc, layer, rows, lens, bt, k_scale=k_scale,
                    v_scale=v_scale)

    monkeypatch.setattr(rk, "ragged_attention", spy)
    eng = _engine(model, params, kernel=True)
    eng.put([1, 2], [list(range(3, 17)), [40]])
    assert seen.get("called") and seen.get("scales"), \
        "kv_quant must serve through the quant ragged kernel"
    assert seen["whole"], "the kernel takes the pool whole, not a layer"


def test_kv_pool_layout_and_capacity_gauge(tiny):
    """Per-(block, head) scale granularity and the capacity gauge: the
    int8 pool frees ~half the serving-dtype pool bytes."""
    from deepspeed_tpu.telemetry import MetricsRegistry, set_registry

    model, params = tiny
    prev = set_registry(MetricsRegistry())
    try:
        eng = _engine(model, params)
        L, nb, kvh = 2, 65, 2
        assert eng.kv_cache["k"].dtype == jnp.int8
        # one stored layout: a position is one lane-dense row of its heads
        assert eng.kv_cache["k"].shape == eng.kv_cache["v"].shape \
            == (L, nb, 16, kvh * 16)
        assert eng.kv_cache["ks"].shape == (L, nb, kvh)
        assert eng.kv_cache["vs"].shape == (L, nb, kvh)
        from deepspeed_tpu.telemetry import get_registry
        saved = get_registry().gauge(
            "inference_kv_pool_quant_bytes_saved", "").value
        pool_elems = sum(int(np.prod(eng.kv_cache[k].shape))
                         for k in ("k", "v"))
        # fp32 serving dtype here: 4 bytes -> int8 saves ~3/4
        assert saved > pool_elems * 2
    finally:
        set_registry(prev)


# ---------------------------------------------------------------------------
# handoff + routed disaggregation under kv_quant
# ---------------------------------------------------------------------------
def test_handoff_roundtrip_quant_scales_bit_exact(tiny):
    """export -> serialize -> restore moves the int8 pages AND the
    per-(block, head) scale rows bit-exactly at the new granularity
    (the gather runs along the pool's block axis for every leaf), and
    rejects a pool-leaf mismatch against a non-quant engine."""
    from deepspeed_tpu.inference.v2.serve import handoff

    model, params = tiny
    src = _engine(model, params)
    dst = _engine(model, params)
    prompt = list(map(int, np.random.default_rng(12).integers(1, 127, 37)))
    src.put([5], [np.asarray(prompt, np.int64)])
    pack = handoff.export_sequence(src, 5)
    assert set(pack["kv"]) == {"k", "v", "ks", "vs"}
    # scale leaves travel at per-(block, head) granularity
    assert pack["kv"]["ks"].shape == (2, pack["n_blocks"], 2)
    back = handoff.deserialize(handoff.serialize(pack))
    handoff.restore_sequence(dst, back, uid=77)
    seq_s = src.state_manager.seqs[5]
    seq_d = dst.state_manager.seqs[77]
    for key in src.kv_cache:
        a = np.asarray(src.kv_cache[key])[:, seq_s.blocks]
        b = np.asarray(dst.kv_cache[key])[:, seq_d.blocks]
        np.testing.assert_array_equal(a, b)
    # a bf16/fp32 (non-quant) pool must refuse the quant payload loudly
    from deepspeed_tpu.inference.v2 import RaggedInferenceEngineConfig
    plain = InferenceEngineV2(
        model, RaggedInferenceEngineConfig(
            state_manager=DSStateManagerConfig(
                max_tracked_sequences=8, max_seq_len=128, num_blocks=65,
                block_size=16),
            dtype="float32", prefill_bucket=16), params=params)
    with pytest.raises(ValueError, match="pool-leaf mismatch"):
        handoff.restore_sequence(plain, back, uid=1)


@pytest.mark.parametrize("kv_quant", [False, True], ids=["plain", "int8"])
def test_a_pack_of_the_per_head_layout_is_refused_by_shape(tiny, kv_quant):
    """A pack written when the pool was ``[L, nb, bs, kvh, hd]`` holds
    the same bytes and no longer fits: both the whole-pack restore and
    the chunked one refuse it with the two shapes in the message, adopt
    nothing, and leave the pool as it was; the pack as exported today
    still goes in."""
    from deepspeed_tpu.inference.v2.serve import handoff

    model, params = tiny

    def build():
        return InferenceEngineV2(
            model, RaggedInferenceEngineConfig(
                state_manager=DSStateManagerConfig(
                    max_tracked_sequences=8, max_seq_len=128, num_blocks=65,
                    block_size=16),
                dtype="float32", prefill_bucket=16, kv_quant=kv_quant),
            params=params)

    src, dst = build(), build()
    src.put([5], [np.arange(3, 40)])
    pack = handoff.export_sequence(src, 5)
    n = pack["n_blocks"]
    assert pack["kv"]["k"].shape == (2, n, 16, 2 * 16)
    stale = dict(pack, kv={
        key: leaf.reshape(2, n, 16, 2, 16) if key in "kv" else leaf
        for key, leaf in pack["kv"].items()})
    before = {key: np.asarray(leaf) for key, leaf in dst.kv_cache.items()}
    shapes = r"\(2, 3, 16, 2, 16\).*\(2, 65, 16, 32\)"
    with pytest.raises(ValueError, match=shapes):
        handoff.restore_sequence(
            dst, handoff.deserialize(handoff.serialize(stale)), uid=9)
    header, *chunks = handoff.chunk_pack(stale, chunk_blocks=2)
    restore = handoff.ChunkedRestore(dst, 10, handoff.parse_header(header))
    restore.begin()
    with pytest.raises(ValueError, match=r"\(2, 2, 16, 2, 16\)"):
        restore.apply(handoff.parse_chunk(chunks[0]))
    restore.abort()
    assert not {9, 10} & set(dst.state_manager.seqs)
    for key, leaf in dst.kv_cache.items():
        np.testing.assert_array_equal(np.asarray(leaf), before[key])
    handoff.restore_sequence(
        dst, handoff.deserialize(handoff.serialize(pack)), uid=11)
    took, gave = (e.state_manager.seqs[u].blocks
                  for e, u in ((dst, 11), (src, 5)))
    for key in src.kv_cache:
        np.testing.assert_array_equal(np.asarray(dst.kv_cache[key])[:, took],
                                      np.asarray(src.kv_cache[key])[:, gave])


def test_disaggregated_streams_parity_with_kv_quant(tiny):
    """Routed prefill->decode serving with kv_quant on: streams are
    bit-identical to colocated single-engine serving (scale rows ride
    the handoff payload, the decode side resumes on the quant kernels)."""
    from deepspeed_tpu.inference.v2.serve import (PrefillReplica,
                                                  ReplicaRouter,
                                                  RouterConfig,
                                                  ServingConfig,
                                                  ServingEngine,
                                                  build_replicas)

    model, params = tiny
    prompts = [list(map(int, np.random.default_rng(s).integers(1, 127, n)))
               for s, n in ((0, 20), (1, 7))]
    kws = [dict(temperature=0.0), dict(temperature=0.8, top_p=0.9,
                                       seed=11)]
    scfg = dict(token_budget=32, chunk=16)

    async def colocated():
        serving = ServingEngine(_engine(model, params),
                                ServingConfig(**scfg))
        await serving.start()
        streams = [await serving.submit(p, 10, **kw)
                   for p, kw in zip(prompts, kws)]
        outs = [await s.drain() for s in streams]
        await serving.stop()
        return outs

    async def disagg():
        replicas = build_replicas([_engine(model, params)],
                                  ServingConfig(**scfg))
        pw = PrefillReplica("prefill0", _engine(model, params))
        router = ReplicaRouter(replicas, RouterConfig(disaggregated=True),
                               prefill_replicas=[pw])
        await router.start()
        streams = [await router.submit(p, 10, **kw)
                   for p, kw in zip(prompts, kws)]
        outs = [await s.drain() for s in streams]
        await router.stop()
        return outs

    assert asyncio.run(disagg()) == asyncio.run(colocated()), \
        "disaggregated kv_quant streams must match colocated serving"
