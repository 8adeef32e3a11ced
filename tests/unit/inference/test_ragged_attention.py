"""Ragged paged attention: one kernel / one program for mixed batches.

The contract under test (kernels/ragged_attention.py + ragged/batch.py +
engine_v2.step_ragged + the SplitFuse scheduler's RaggedBatch emission):

* the ragged kernel matches a dense reference for mixed rows, and is
  BIT-IDENTICAL to the decode kernel on pure-decode batches (shared
  ``_page_update``); both variants read the pool as it is stored,
  ``[L, nb, bs, kvh * hd]`` whole with the layer a scalar, and touch no
  other layer;
* ragged vs stitched token streams are bit-identical — greedy and
  fixed-seed sampled — for prefill-only, decode-only and interleaved
  batches, through put() and through the scheduler (chip-free: the
  kernels run in interpret mode on CPU);
* the mixed-traffic compiled-program count under ragged is strictly
  lower than the stitched prefill+decode program count it replaces,
  with ZERO steady-state recompiles (the watchdog pins it);
* ``ragged_attention="off"`` reproduces the stitched dispatch exactly
  (the CI-visible rollback guarantee).
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import (DynamicSplitFuseScheduler,
                                        InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.config_v2 import DSStateManagerConfig
from deepspeed_tpu.inference.v2.kernels.paged_attention import \
    paged_attention
from deepspeed_tpu.inference.v2.kernels.ragged_attention import \
    ragged_attention
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.telemetry import (MetricsRegistry, get_registry,
                                     set_registry, watchdog)


# ---------------------------------------------------------------------------
# kernel level
# ---------------------------------------------------------------------------
def _reference_ragged(q, k_cache, v_cache, row_ids, lengths, tables):
    """Dense jnp reference: gather each token's row pages, mask to its
    causal bound, plain (non-online) softmax."""
    T, nh, hd = q.shape
    nb, bs, kvh, _ = k_cache.shape
    ctx = tables.shape[1] * bs
    group = nh // kvh
    out = np.zeros_like(np.asarray(q))
    for t in range(T):
        kt = np.asarray(k_cache[tables[row_ids[t]]]).reshape(ctx, kvh, hd)
        vt = np.asarray(v_cache[tables[row_ids[t]]]).reshape(ctx, kvh, hd)
        kt = np.repeat(kt, group, axis=1)
        vt = np.repeat(vt, group, axis=1)
        mask = np.arange(ctx) < lengths[t]
        for h in range(nh):
            s = (np.asarray(q[t, h], np.float32) @ kt[:, h].T
                 ) / np.sqrt(hd)
            s = np.where(mask, s, -1e30)
            if lengths[t] == 0:
                continue  # padding token: kernel outputs zeros
            p = np.exp(s - s.max())
            p = p / p.sum()
            out[t, h] = p @ vt[:, h]
    return out


def test_ragged_kernel_matches_reference_mixed_rows(stored_pool):
    rng = np.random.default_rng(0)
    nb, bs, kvh, hd, nh = 9, 16, 2, 16, 4
    k_cache = jnp.asarray(rng.normal(size=(nb, bs, kvh, hd)), jnp.float32)
    v_cache = jnp.asarray(rng.normal(size=(nb, bs, kvh, hd)), jnp.float32)
    # 3 rows: a 10-token prefill chunk (positions 0..9), a decode row at
    # position 30 (2 pages + partial), a decode row at position 5
    tables = np.array([[1, 2], [3, 4], [5, 0]], np.int32)
    row_ids, lengths = [], []
    for r, positions in enumerate([range(10), [30], [5]]):
        for p in positions:
            row_ids.append(r)
            lengths.append(p + 1)
    # pad the flat buffer (padding points at row 0 with length 0)
    T = 16
    pad = T - len(row_ids)
    row_ids += [0] * pad
    lengths += [0] * pad
    q = jnp.asarray(rng.normal(size=(T, nh, hd)), jnp.float32)
    out = np.asarray(ragged_attention(
        q, stored_pool(k_cache), stored_pool(v_cache), 0,
        jnp.asarray(row_ids, jnp.int32),
        jnp.asarray(lengths, jnp.int32), jnp.asarray(tables)))
    ref = _reference_ragged(q, k_cache, v_cache, row_ids, lengths, tables)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
    # padding tokens attend over nothing and output exact zeros
    assert (out[-pad:] == 0.0).all()


def test_ragged_kernel_pure_decode_matches_decode_kernel(stored_pool):
    """row per token, per-token lengths == the decode kernel's lengths:
    the shared page-walk math makes the outputs bit-identical."""
    rng = np.random.default_rng(1)
    nb, bs, kvh, hd, nh = 9, 16, 2, 16, 4
    k_cache = jnp.asarray(rng.normal(size=(nb, bs, kvh, hd)), jnp.float32)
    v_cache = jnp.asarray(rng.normal(size=(nb, bs, kvh, hd)), jnp.float32)
    tables = jnp.asarray(np.array([[1, 2], [3, 4], [5, 6], [7, 8]],
                                  np.int32))
    lengths = jnp.asarray([17, 30, 5, 32], jnp.int32)
    q = jnp.asarray(rng.normal(size=(4, nh, hd)), jnp.float32)
    kp, vp = stored_pool(k_cache, 2, 1), stored_pool(v_cache, 2, 1)
    ragged = np.asarray(ragged_attention(
        q, kp, vp, 1, jnp.arange(4, dtype=jnp.int32), lengths, tables))
    decode = np.asarray(paged_attention(q, kp, vp, 1, tables, lengths))
    np.testing.assert_array_equal(ragged, decode)
    assert np.isfinite(ragged).all()


# ---------------------------------------------------------------------------
# the tiled variant (the TPU's kernel wherever a page row is lane-dense),
# whole, asked for by name under the TPU interpreter: DMAs, semaphores,
# the chunked walk (off the TPU the engine's default is the pipelined one)
# ---------------------------------------------------------------------------
def _gather_reference(q, kc, vc, rows, lens, tables, ks=None, vs=None):
    """The jnp gather path's arithmetic (paged_model._kv_read's dequant,
    masked float32 softmax) over each row's pages; padding gives zeros."""
    T, nh, hd = q.shape
    _, bs, kvh, _ = kc.shape
    R, MB = tables.shape

    def pages(c, s):
        p = c[tables]                                # [R, MB, bs, kvh, hd]
        if s is not None:
            p = (p.astype(jnp.float32)
                 * s[tables][:, :, None, :, None]).astype(q.dtype)
        p = p.reshape(R, MB * bs, kvh, hd)[rows]     # [T, ctx, kvh, hd]
        return jnp.repeat(p, nh // kvh, axis=2).astype(jnp.float32)

    s = jnp.einsum("thd,tchd->thc", q.astype(jnp.float32),
                   pages(kc, ks)) / (hd ** 0.5)
    mask = jnp.arange(MB * bs)[None, :] < lens[:, None]
    p = jax.nn.softmax(jnp.where(mask[:, None, :], s, -1e30), axis=-1)
    out = jnp.einsum("thc,tchd->thd", p, pages(vc, vs))
    return np.asarray(jnp.where((lens > 0)[:, None, None], out, 0.0))


def _tiled_case(kvh, nh, pool, seed=0):
    """A mixed batch at head width 64 over a pool whose pages are out of
    order: a 150-token prefill chunk (longer than one query tile), a
    40-token continuation over a cached prefix of 600 (its context ends
    mid-page and in its second chunk of pages), three decode rows (one
    at the table's last position), padding tokens, three padding rows."""
    rng = np.random.default_rng(seed)
    nb, bs, hd, R, MB, T = 128, 16, 64, 8, 64, 256
    positions = [range(150), range(600, 640), [77], [5], [1023]]
    rows = [r for r, ps in enumerate(positions) for _ in ps]
    lens = [p + 1 for ps in positions for p in ps]
    pad = T - len(rows)
    tables = np.zeros((R, MB), np.int32)
    free = iter(rng.permutation(np.arange(1, nb)))
    for r, ps in enumerate(positions):
        for j in range(max(ps) // bs + 1):
            tables[r, j] = next(free)
    io = jnp.float32 if pool == "int8" else jnp.bfloat16

    def one():
        if pool == "int8":
            return (jnp.asarray(rng.integers(-127, 128, (nb, bs, kvh, hd)),
                                jnp.int8),
                    jnp.asarray(rng.uniform(0.005, 0.03, (nb, kvh)),
                                jnp.float32))
        return jnp.asarray(rng.standard_normal((nb, bs, kvh, hd)), io), None

    (kc, ks), (vc, vs) = one(), one()
    q = jnp.asarray(rng.standard_normal((T, nh, hd)), io)
    return dict(q=q, kc=kc, vc=vc, ks=ks, vs=vs, pad=pad,
                rows=jnp.asarray(rows + [0] * pad, jnp.int32),
                lens=jnp.asarray(lens + [0] * pad, jnp.int32),
                tables=jnp.asarray(tables))


TILED_CASES = [(2, 8, "bf16"), (4, 4, "bf16"), (2, 8, "int8"),
               (4, 4, "int8")]


@pytest.mark.parametrize("kvh,nh,pool", TILED_CASES)
def test_tiled_kernel_matches_gather_reference(stored_pool, kvh, nh, pool):
    from deepspeed_tpu.inference.v2.kernels.ragged_attention import \
        kernel_variant
    assert kernel_variant(64, kvh, pool == "int8") == "tiled"
    c = _tiled_case(kvh, nh, pool)
    out = np.asarray(jax.jit(functools.partial(
        ragged_attention, variant="tiled"))(
        c["q"], stored_pool(c["kc"]), stored_pool(c["vc"]), 0, c["rows"],
        c["lens"], c["tables"], k_scale=c["ks"], v_scale=c["vs"]),
        np.float32)
    ref = _gather_reference(c["q"], c["kc"], c["vc"], c["rows"], c["lens"],
                            c["tables"], c["ks"], c["vs"])
    assert np.isfinite(out).all()
    # bf16: the output's own rounding and p's before p.v; int8 pools are
    # served in float32 here, where only the summation order differs
    tol = 2e-2 if pool == "bf16" else 2e-5
    np.testing.assert_allclose(out, ref, rtol=tol, atol=tol)
    assert (out[-c["pad"]:] == 0.0).all()


@pytest.mark.parametrize("kvh,nh,pool", TILED_CASES)
def test_tiled_pure_decode_is_the_decode_kernel(stored_pool, kvh, nh, pool):
    """One token a row through ``paged_attention()`` and through
    ``ragged_attention(one_token=True)``: bit-equal (one program, the
    one-token form), the token tile's output to the order of its sums
    (the same chunks in the same order; float32 products add up in
    another order where a matmul has 8 rows for 128), and all three the
    reference's."""
    c = _tiled_case(kvh, nh, pool, seed=1)
    lens = jnp.asarray([150, 640, 78, 6, 1024, 0, 17, 513], jnp.int32)
    tables = c["tables"].at[6, :2].set(jnp.asarray([3, 1])) \
        .at[7, :33].set(jnp.arange(40, 73, dtype=jnp.int32))
    q = c["q"][:8]
    kw = dict(k_scale=c["ks"], v_scale=c["vs"], variant="tiled")
    kp, vp = stored_pool(c["kc"], 2, 1), stored_pool(c["vc"], 2, 1)
    rows = jnp.arange(8, dtype=jnp.int32)
    tile, ragged = (np.asarray(jax.jit(functools.partial(
        ragged_attention, one_token=one, **kw))(
        q, kp, vp, 1, rows, lens, tables), np.float32)
        for one in (False, True))
    decode = np.asarray(jax.jit(functools.partial(paged_attention, **kw))(
        q, kp, vp, 1, tables, lens), np.float32)
    np.testing.assert_array_equal(ragged, decode)
    np.testing.assert_allclose(tile, decode, rtol=0, atol=2e-6)
    assert not decode[5].any()                       # a row of no length
    ref = _gather_reference(q, c["kc"], c["vc"], rows, lens, tables,
                            c["ks"], c["vs"])
    tol = 2e-2 if pool == "bf16" else 2e-5
    np.testing.assert_allclose(decode, ref, rtol=tol, atol=tol)


# hpb 2 / group 1 (OPT's: two 64-wide heads a lane block, 2 query rows
# padded to a sublane tile) and hpb 1 / group 8 (GQA at width 128)
ONE_TOKEN_GEOMETRIES = {"hpb2-group1": (4, 4, 64), "hpb1-group8": (16, 2, 128)}


@pytest.mark.parametrize("geometry", sorted(ONE_TOKEN_GEOMETRIES))
def test_the_one_token_form_is_the_token_tile_and_the_reference(geometry):
    """A decode batch through the tiled kernel's one-token form (a row's
    chunks against that row's own query rows) against the token tile on
    the same inputs and against the gathering reference: rows of unequal
    contexts over pages out of order, one that crosses a 512-position
    chunk (and one that ends on its edge), rows of length 0 among them
    and behind, more rows than the 16 one grid step walks."""
    from deepspeed_tpu.inference.v2.kernels.ragged_attention import \
        ragged_attention_reference
    nh, kvh, hd = ONE_TOKEN_GEOMETRIES[geometry]
    rng = np.random.default_rng(3)
    lens = [150, 640, 78, 6, 512, 0, 17, 513] + [33] * 9 + [0, 7, 0]
    R, MB, bs = len(lens), 48, 16
    nb = 1 + R * MB
    k, v = (jnp.asarray(rng.normal(size=(2, nb, bs, kvh * hd)), jnp.float32)
            for _ in range(2))
    tables = jnp.asarray(rng.permutation(np.arange(1, nb)).reshape(R, MB),
                         jnp.int32)
    args = (jnp.asarray(rng.normal(size=(R, nh, hd)), jnp.float32), k, v, 1,
            jnp.arange(R, dtype=jnp.int32), jnp.asarray(lens, jnp.int32),
            tables)
    tile, one = (np.asarray(jax.jit(functools.partial(
        ragged_attention, variant="tiled", one_token=flag))(*args))
        for flag in (False, True))
    want = np.asarray(ragged_attention_reference(*args))
    np.testing.assert_allclose(one, want, rtol=0, atol=2e-6)
    np.testing.assert_allclose(one, tile, rtol=0, atol=2e-6)
    assert not one[np.asarray(lens) == 0].any()


@pytest.mark.parametrize("pool", ["bf16", "int8"])
@pytest.mark.parametrize("variant", ["tiled", "pipelined"])
def test_a_launch_reads_its_own_layer_of_the_whole_pool(stored_pool, variant,
                                                        pool):
    """Three layers, the other two NaN (int8: -128 under the attended
    layer's scales): layer 1 through either variant is the gather
    reference on layer 1 alone, finite everywhere, and the layer is a
    traced scalar (one program for every layer)."""
    c = _tiled_case(2, 8, pool, seed=2)
    kp, vp = stored_pool(c["kc"], 3, 1), stored_pool(c["vc"], 3, 1)
    run = jax.jit(functools.partial(
        ragged_attention, variant=variant, k_scale=c["ks"], v_scale=c["vs"]))
    out = np.asarray(run(c["q"], kp, vp, jnp.int32(1), c["rows"], c["lens"],
                         c["tables"]), np.float32)
    ref = _gather_reference(c["q"], c["kc"], c["vc"], c["rows"], c["lens"],
                            c["tables"], c["ks"], c["vs"])
    assert np.isfinite(out).all()
    tol = 2e-2 if pool == "bf16" else 2e-5
    np.testing.assert_allclose(out, ref, rtol=tol, atol=tol)
    if pool == "bf16":      # another layer's pages are NaN: it read them
        assert not np.isfinite(np.asarray(run(
            c["q"], kp, vp, jnp.int32(2), c["rows"], c["lens"], c["tables"]),
            np.float32)[:-c["pad"]]).any()
    assert run._cache_size() == 1


# ---------------------------------------------------------------------------
# RaggedBatch packing
# ---------------------------------------------------------------------------
def test_ragged_batch_packing_layout():
    from deepspeed_tpu.inference.v2.ragged import batch as rbatch
    from deepspeed_tpu.inference.v2.ragged.ragged_manager import \
        DSStateManager

    sm = DSStateManager(DSStateManagerConfig(
        max_tracked_sequences=8, max_ragged_batch_size=64,
        max_seq_len=128, num_blocks=17, block_size=16))
    # existing sequence at position 20 (decode row) + a fresh 10-token
    # prefill row
    seq = sm.ensure_blocks(1, 20)
    seq.seen_tokens = 20
    b = rbatch.pack([(1, np.array([7])), (2, np.arange(10))], sm)
    assert b.token_bucket == 16          # pow2(11)
    assert b.row_bucket == 2
    assert b.new_lens == [1, 10]
    assert b.total_tokens == 11
    assert 0 < b.pad_fraction < 1
    # decode row: one token at position 20 -> block 2 of its table
    assert b.positions[0] == 20
    assert b.lengths[0] == 21
    assert b.write_blocks[0] == sm.seqs[1].blocks[1]
    assert b.write_offsets[0] == 4
    # prefill row: positions 0..9 in its first block
    np.testing.assert_array_equal(b.positions[1:11], np.arange(10))
    np.testing.assert_array_equal(b.lengths[1:11], np.arange(10) + 1)
    assert (b.row_ids[1:11] == 1).all()
    # padding: zero lengths, null-block writes
    assert (b.lengths[11:] == 0).all()
    assert (b.write_blocks[11:] == 0).all()
    # last-token gather points at each row's final valid token
    assert list(b.last_index[:2]) == [0, 10]
    # table width sliced to the pow2 used-page bucket (2 pages used)
    assert b.block_tables.shape == (2, 2)


# ---------------------------------------------------------------------------
# engine + scheduler parity
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny(tiny_model_128):
    # session-shared tiny model (tests/unit/conftest.py): one
    # init_params for the whole tier instead of one per module
    return tiny_model_128


def _engine(model, params, mode, window=1, **kw):
    smc = dict(max_tracked_sequences=8, max_seq_len=128, num_blocks=65,
               block_size=16)
    smc.update(kw.pop("sm", {}))
    return InferenceEngineV2(
        model, RaggedInferenceEngineConfig(
            state_manager=DSStateManagerConfig(**smc),
            dtype="float32", prefill_bucket=16, decode_window=window,
            ragged_attention=mode, **kw),
        params=params)


def test_put_parity_prefill_only(tiny):
    model, params = tiny
    prompts = [list(range(3, 17)), [2, 4, 6], list(range(40, 62))]
    on = _engine(model, params, "on").put([1, 2, 3], prompts)
    off = _engine(model, params, "off").put([1, 2, 3], prompts)
    np.testing.assert_allclose(on, off, rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(on.argmax(-1), off.argmax(-1))


def test_put_parity_decode_only_and_interleaved(tiny):
    model, params = tiny
    prompts = [list(range(3, 17)), [2, 4, 6]]
    e_on = _engine(model, params, "on")
    e_off = _engine(model, params, "off")
    e_on.put([1, 2], prompts)
    e_off.put([1, 2], prompts)
    # decode-only batch
    d_on = e_on.put([1, 2], [[40], [41]])
    d_off = e_off.put([1, 2], [[40], [41]])
    np.testing.assert_allclose(d_on, d_off, rtol=2e-4, atol=2e-4)
    # interleaved: decode + fresh prefill + continuation chunk
    m_on = e_on.put([1, 3, 2], [[50], list(range(20, 31)), [51, 52, 53]])
    m_off = e_off.put([1, 3, 2], [[50], list(range(20, 31)),
                                  [51, 52, 53]])
    np.testing.assert_allclose(m_on, m_off, rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(m_on.argmax(-1), m_off.argmax(-1))


def test_generate_stream_parity_greedy_and_sampled(tiny):
    """Bit-identical token streams, ragged vs stitched, through the full
    generate() loop (ragged prefill put + fused decode window)."""
    model, params = tiny
    prompts = [list(range(3, 17)), [2, 4, 6], [5]]
    for kw in (dict(max_new_tokens=20),
               dict(max_new_tokens=14, temperature=0.8, top_p=0.9,
                    top_k=20, seed=5)):
        a = _engine(model, params, "on", window=8).generate(prompts, **kw)
        b = _engine(model, params, "off", window=8).generate(prompts,
                                                             **kw)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)


def _mixed_traffic(sched, prompts, base, new_tokens=10):
    """Staggered submissions so steps interleave prompt chunks with
    running decodes (the SplitFuse mixed-batch shape)."""
    for i, p in enumerate(prompts[:2]):
        sched.submit(base + i, p, new_tokens,
                     temperature=0.7 if i == 1 else 0.0, top_p=0.9,
                     seed=5)
    for _ in range(3):
        sched.step()
    for i, p in enumerate(prompts[2:]):
        sched.submit(base + 100 + i, p, new_tokens,
                     temperature=0.9 if i % 2 else 0.0, top_k=30, seed=9)
    sched.run()
    return {uid: list(map(int, toks))
            for uid, toks in sched.results().items()}


def _mixed_prompts():
    rng = np.random.default_rng(3)
    return [list(map(int, rng.integers(1, 127, n)))
            for n in (40, 7, 22, 3, 30, 11)]


@pytest.mark.parametrize("window", [1, 8])
def test_scheduler_stream_parity_mixed_traffic(tiny, window):
    """The scheduler emits RaggedBatch steps (ragged on) vs sequenced
    put() dispatch (off): greedy AND fixed-seed sampled streams must be
    bit-identical under chunked prefill + interleaved decode."""
    model, params = tiny
    prompts = _mixed_prompts()
    results = {}
    for mode in ("on", "off"):
        eng = _engine(model, params, mode, window=window)
        sched = DynamicSplitFuseScheduler(eng, token_budget=24, chunk=16)
        results[mode] = _mixed_traffic(sched, prompts, 100)
    assert results["on"] == results["off"]


def _greedy_mixed_traffic(sched, prompts, base, new_tokens=10):
    """All-greedy staggered mix (the serving_bench --mixed sweep shape):
    steps interleave prompt chunks with running decodes, and pure-decode
    steps take the fused-window fast path in BOTH modes."""
    for i, p in enumerate(prompts[:2]):
        sched.submit(base + i, p, new_tokens)
    for _ in range(3):
        sched.step()
    for i, p in enumerate(prompts[2:]):
        sched.submit(base + 50 + i, p, new_tokens)
    sched.run()


def test_mixed_traffic_fewer_programs_zero_steady_recompiles(tiny):
    """The acceptance criterion, chip-free: ONE ragged program family
    serves the mixed sweep with zero steady-state recompiles, and its
    compiled-program count is strictly lower than the stitched
    prefill+decode program count it replaces."""
    model, params = tiny
    prompts = _mixed_prompts()
    counts, steady, families = {}, {}, {}
    for mode in ("on", "off"):
        prev = set_registry(MetricsRegistry())
        watchdog.reset()
        try:
            eng = _engine(model, params, mode, window=8)
            sched = DynamicSplitFuseScheduler(eng, token_budget=24,
                                              chunk=16)
            # warm the bucket set TWICE: a bucket's first call compiles
            # against the unsharded fresh pool, its repeats against the
            # donated (sharded) one — the second wave absorbs that
            # one-time respecialization for buckets the first wave
            # visited only once (same discipline as bench/gate)
            _greedy_mixed_traffic(sched, prompts, 100)
            _greedy_mixed_traffic(sched, prompts, 200)
            reg = get_registry()
            counts[mode] = reg.family_total("xla_compile_events_total")
            watchdog.mark_steady(True)
            try:
                _greedy_mixed_traffic(sched, prompts, 300)
            finally:
                watchdog.mark_steady(False)
            steady[mode] = reg.family_total(
                "xla_steady_state_recompiles_total")
            families[mode] = {v[0] for v, _ in
                              reg.get("xla_compile_events_total").series()}
        finally:
            set_registry(prev)
            watchdog.reset()
    assert steady["on"] == 0
    # exact, so that a bucket either side gains shows here: 6 ragged_step
    # + 2 decode_window_greedy, against 2 prefill + 1 continue + 4 decode
    # + the same 2 windows
    assert counts == {"on": 8, "off": 9}
    # the stitched families are gone from the ragged sweep entirely
    assert "ragged_step" in families["on"]
    assert not families["on"] & {"prefill", "continue", "decode"}


# ---------------------------------------------------------------------------
# config + fallback
# ---------------------------------------------------------------------------
def test_off_mode_reproduces_stitched_dispatch(tiny):
    """ragged_attention='off' must reproduce today's behavior exactly:
    the stitched program families run (and no ragged program ever
    compiles), and the streams match the ragged path bit-for-bit."""
    model, params = tiny
    prompts = [list(range(3, 17)), [2, 4, 6]]
    prev = set_registry(MetricsRegistry())
    watchdog.reset()
    try:
        eng = _engine(model, params, "off", window=8)
        assert eng.ragged_enabled is False
        out_off = eng.generate(prompts, max_new_tokens=12)
        progs = {v[0] for v, _ in
                 get_registry().get("xla_compile_events_total").series()}
        assert "prefill" in progs
        assert "ragged_step" not in progs
    finally:
        set_registry(prev)
        watchdog.reset()
    out_on = _engine(model, params, "on", window=8).generate(
        prompts, max_new_tokens=12)
    for x, y in zip(out_off, out_on):
        np.testing.assert_array_equal(x, y)


def test_ragged_mode_validation_and_runtime_flip(tiny):
    model, params = tiny
    with pytest.raises(ValueError):
        _engine(model, params, "maybe")
    eng = _engine(model, params, "auto")
    assert eng.ragged_enabled is True     # auto == on today
    eng.set_ragged_mode("off")
    assert eng.ragged_enabled is False
    eng.set_ragged_mode("on")
    assert eng.ragged_enabled is True
    with pytest.raises(ValueError):
        eng.set_ragged_mode("sometimes")


def test_serving_config_ragged_knob(tiny):
    """ServingConfig.ragged_attention overrides the engine's dispatch at
    runtime construction (the serve-level rollback knob)."""
    from deepspeed_tpu.inference.v2.serve.frontend import (ServingConfig,
                                                           ServingEngine)
    from deepspeed_tpu.telemetry.anomaly import DiagnosticsConfig

    model, params = tiny
    eng = _engine(model, params, "auto")
    serving = ServingEngine(eng, ServingConfig(
        ragged_attention="off",
        diagnostics=DiagnosticsConfig(enabled=False)))
    try:
        assert eng.ragged_enabled is False
    finally:
        serving.diagnostics.close()
