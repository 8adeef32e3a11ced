"""Ragged paged attention: one kernel / one program for mixed batches.

The contract under test (kernels/ragged_attention.py + ragged/batch.py +
engine_v2.step_ragged + the SplitFuse scheduler's RaggedBatch emission):

* the ragged kernel matches a dense reference for mixed rows, and is
  BIT-IDENTICAL to the decode kernel on pure-decode batches (shared
  ``_page_update``); both variants read the pool as it is stored,
  ``[L, nb, bs, kvh * hd]`` whole with the layer a scalar, and touch no
  other layer;
* put() — prefill-only, decode-only and interleaved batches — returns
  the model's own dense float32 forward's logits, and a greedy
  generate() stream its best tokens (chip-free: the kernels run in
  interpret mode on CPU);
* a row's stream does not depend on what it was packed with: a
  fixed-seed sampled generate() row and every request of the
  scheduler's mixed traffic equal the same request served ALONE;
* the mixed traffic compiles 8 programs, of the families that remain
  and no other, with ZERO steady-state recompiles (the watchdog pins
  it), and put() has one way in: every row of it runs ``ragged_step``;
* a configuration that still names ``ragged_attention`` (the option
  that selected the stitched prefill / continue / decode dispatch,
  gone with it) is refused by name.
"""

import contextlib
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
    # (a bf16 output: the other order may round one value the other way)
    np.testing.assert_allclose(tile, decode, atol=2e-6,
                               rtol=2 ** -7 if pool == "bf16" else 0)
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


# ---------------------------------------------------------------------------
# PR 50: what a chunk costs beside its bytes. One wait a chunk (by bytes),
# two starts a trip, and a decode launch's lane blocks updated at once
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cp", [1, 2, 3, 12, 16, 32, 64])
def test_a_chunks_waits_add_up_to_the_pages_it_started(cp):
    """The wait decomposition itself (``_chunk_waits`` on whole
    numbers): for every n in 1..cp the descriptors waited on hold
    exactly n pages, as many bytes as the n copies that were started
    put on the semaphore, ONE for a whole chunk and at most log2(cp)
    for a partial one, each no larger than the chunk."""
    from tests.unit.inference.walk_cases import ra
    for n in range(1, cp + 1):
        sizes = []
        ra()._chunk_waits(n, cp, sizes.append)
        assert sum(sizes) == n, (n, sizes)
        assert len(sizes) <= max(1, (cp - 1).bit_length()), (n, sizes)
        assert all(1 <= size <= cp for size in sizes)
    sizes = []
    ra()._chunk_waits(cp, cp, sizes.append)
    assert sizes == [cp]


WALK_CASES = ["tiled-hpb2", "tiled-group8"]


@pytest.mark.parametrize("case", WALK_CASES)
def test_a_decode_launch_over_partial_chunks_is_the_reference(case):
    """Rows whose last chunk holds 1, 2, 3, cp - 1 and cp pages, in a
    row's only chunk and in its second, contexts that end exactly on a
    chunk (``walk_cases.contexts``), through the one-token form under
    the TPU interpreter (which counts a wait in its descriptor's bytes,
    as the chip does: a wrong decomposition hangs or reads a page too
    early) against the gathering reference at today's tolerance."""
    from tests.unit.inference import walk_cases
    got, want = walk_cases.output(case), walk_cases.reference(case)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)
    lens, axis = walk_cases.lengths(case)
    assert not np.take(got, np.flatnonzero(lens == 0), axis=axis).any()


@pytest.mark.parametrize("case", WALK_CASES)
def test_a_decode_launch_over_partial_chunks_is_the_parents(case):
    """The same launches against what the PARENT of PR 50 returned for
    the same inputs (a wait a page, the products over the whole chunk;
    the committed fixture): a wait a page, a lane block at a time. The
    same sums in the same order, so the outputs are equal to the bit
    under the interpreter."""
    from tests.unit.inference import walk_cases
    np.testing.assert_array_equal(walk_cases.output(case),
                                  walk_cases.parent_output(case))


@pytest.mark.parametrize("case", WALK_CASES + ["tiled-int8", "window-ring"])
def test_the_lane_blocks_at_once_are_the_blocks_one_by_one(case, monkeypatch):
    """``_blocks_update`` (every lane block's scores stacked, one
    softmax, the state read and written once) against the parent's
    form kept as the test's own reference, a lane block at a time on
    the same chunk: a row's max and sum run over its
    own scores and each product is its block's own either way, so the
    outputs are equal to the bit."""
    from tests.unit.inference import walk_cases
    ra = walk_cases.ra()

    def block_update(q, k, v, visible, acc_sc, m_sc, l_sc, b, *, scale):
        """A lane block's update as the parent of PR 50 made it (the
        token tile's own until PR 59 transposed that one)."""
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(visible, s, 2 * ra.NEG_INF)
        m_prev = m_sc[b, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_sc[b] = jnp.broadcast_to(
            l_sc[b, :, :1] * corr + jnp.sum(p, axis=1, keepdims=True),
            l_sc.shape[1:])
        acc_sc[b] = acc_sc[b] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[b] = jnp.broadcast_to(m_new, m_sc.shape[1:])

    def one_by_one(q, k, v, visible, acc_sc, m_sc, l_sc, *, scale):
        M = q[0].shape[0]
        for b in range(len(q)):
            block_update(q[b], k[b], v[b], visible[:M], acc_sc, m_sc, l_sc,
                         b, scale=scale)

    monkeypatch.setattr(ra, "_blocks_update", one_by_one)
    np.testing.assert_array_equal(walk_cases.launch(case),
                                  walk_cases.output(case))


@pytest.mark.parametrize("unroll", [1, 8])
def test_the_start_loop_unrolled_starts_the_same_copies(unroll, monkeypatch):
    """``_START_UNROLL`` pages a trip with a guarded tail (1, 2, 3, 31
    and 32 pages: whole trips, a tail alone, both) brings the same
    pages: a page a trip (the parent's loop) and eight a trip give the
    two-a-trip launch's output to the bit."""
    from tests.unit.inference import walk_cases
    ra = walk_cases.ra()
    monkeypatch.setattr(ra, "_START_UNROLL", unroll)
    np.testing.assert_array_equal(walk_cases.launch("tiled-hpb2"),
                                  walk_cases.output("tiled-hpb2"))


@pytest.mark.parametrize("name,contexts,table,window,share", [
    ("rollout-256", range(257, 512), 32, 0, (0.74, 0.78)),
    ("latent, a table of 16", range(129, 257), 16, 0, (0.74, 0.80)),
    ("latent, a table of 32", range(257, 384), 32, 0, (0.61, 0.65)),
    ("trinity full", range(8193, 8704), 544, 0, (0.96, 0.98)),
    ("trinity window", range(8193, 8704), 193, 2048, (0.80, 0.83)),
])
def test_decode_positions_held_and_chunked(name, contexts, table, window,
                                           share):
    """``decode_positions`` on the cells' decode contexts: held to whole
    pages, chunked to whole chunks of the table the launch sees, held <=
    chunked, and the share of a chunk that is there about what ISSUE 50
    reckoned (0.75 on rollout-256; a window's 129 or 130 pages are four
    chunks and a page or two: 0.81)."""
    from tests.unit.inference.walk_cases import ra
    ctx = np.asarray(list(contexts))
    held, chunked = ra().decode_positions(ctx, 16, table, 10_000, window)
    assert held % 16 == 0 and chunked % 16 == 0 and 0 < held <= chunked
    if not window:
        assert held == int((-(-ctx // 16) * 16).sum())
    assert share[0] <= held / chunked <= share[1], held / chunked
    assert ra().decode_positions([], 16, table, 10_000, window) == (0, 0)


# ---------------------------------------------------------------------------
# the token tile (PR 59): up to 1,024 query rows a lane block
# ---------------------------------------------------------------------------
from tests.unit.inference.walk_cases import PROMPT_CASES  # noqa: E402


def _prompt_geometry(case):
    """``(token tile, table pages, pool blocks, window)`` of a case's
    launch, as the kernel reads them off its operands."""
    from tests.unit.inference import walk_cases
    c = PROMPT_CASES[case]
    (q, k, *_, tables), _, _ = walk_cases.build_prompt(case)
    return (walk_cases.ra().token_tile(q.shape[0], c["nh"], c["hd"],
                                       c["kvh"]),
            tables.shape[1], k.shape[1], c.get("window", 0))


@pytest.mark.parametrize("case,tile", [
    ("hpb2-bf16", 128), ("hpb2-int8", 128), ("group4-bf16", 128),
    ("group7-float32", 128), ("group8-bf16", 128), ("group8-int8", 128),
    ("group16-float32", 64), ("window-wraps", 128),
    ("window-first-not-last", 128), ("short", 64)])
def test_the_token_tile_is_the_reference_at_the_new_tile_sizes(case, tile):
    """The token tile under the TPU interpreter against the gathering
    reference where a tile holds up to 1,024 query rows a lane block
    (two 64-wide heads a block, groups of 4, 7, 8 and 16; bf16 and int8
    pools; a window whose ring wraps inside a tile's walk; a chunk whole
    for a tile's first token and not its last; a tile of two rows; a
    prompt shorter than a tile)."""
    from tests.unit.inference import walk_cases
    assert _prompt_geometry(case)[0] == tile
    got, want = walk_cases.prompt_output(case), \
        walk_cases.prompt_reference(case)
    assert np.isfinite(got).all()
    c = PROMPT_CASES[case]
    # bf16: the output's own rounding and p's before p.v; float32 (the
    # int8 pools are served in it): the order of the sums alone
    tol = 2e-2 if c.get("dtype") == "bfloat16" \
        else 2e-5 if c.get("int8") else 2e-6
    np.testing.assert_allclose(got, want, rtol=tol if tol > 2e-6 else 0,
                               atol=tol)
    fed = sum(new for new, _ in walk_cases.prompt_rows(case))
    assert not got[fed:].any()                           # the padding


def _brute_chunks(ra, rows, bs, table_pages, pool_blocks, window, tq):
    """``prompt_chunks`` by visiting every (tile, row, chunk) as
    ``_walk_rows`` does and asking ``_visible`` itself whether every
    query row of the tile sees every position of the chunk."""
    P = ra._chunk_pages(table_pages, pool_blocks, bs) * bs
    ids = [r for r, (new, _) in enumerate(rows) for _ in range(new)]
    bounds = [b for new, ctx in rows for b in range(ctx - new + 1, ctx + 1)]
    pad = -len(ids) % tq
    ids, bounds = np.asarray(ids + [-1] * pad), np.asarray(bounds + [0] * pad)
    whole = masked = 0
    for t0 in range(0, len(ids), tq):
        mine = ids[t0:t0 + tq]
        for r in sorted(set(mine[mine >= 0])):
            at = np.flatnonzero(mine == r) + t0
            first, last = int(at[0]), int(at[-1])
            base = max(int(bounds[first]) - window, 0) // bs * bs \
                if window else 0
            for c in range(-(-(int(bounds[last]) - base) // P)):
                seen = ra._visible(
                    jnp.asarray(bounds[t0:t0 + tq], jnp.int32)[:, None], t0,
                    first, last, c, tq, 1, P, base, window)
                if bool(seen.all()):
                    whole += 1
                else:
                    masked += 1
    return whole, masked


@pytest.mark.parametrize("case", sorted(PROMPT_CASES))
def test_prompt_chunks_counts_what_visible_shows(case):
    """The host's ``prompt_chunks`` (the engine's
    ``inference_attention_prompt_chunks_total``) against a brute-force
    count over ``_visible`` of the same launch: the chunk visits a tile
    sees whole and the others, tile by tile and row by row."""
    from tests.unit.inference import walk_cases
    ra = walk_cases.ra()
    rows, bs = walk_cases.prompt_rows(case), walk_cases.PROMPT_BS
    tq, MB, nb, window = _prompt_geometry(case)
    got = ra.prompt_chunks([n for n, _ in rows], [c for _, c in rows], bs,
                           MB, nb, window, tq)
    assert got == _brute_chunks(ra, rows, bs, MB, nb, window, tq)
    if case in ("short", "window-first-not-last"):
        assert got[0] == 0 and got[1] > 0
    else:
        assert got[0] > 0 and got[1] > 0
    assert ra.prompt_chunks([], [], bs, MB, nb, window, tq) == (0, 0)
    # a decode launch through the token tile: no chunk is whole
    assert ra.prompt_chunks([1] * 5, [700, 40, 513, 9, 1024], bs, MB, nb,
                            window, tq)[0] == 0


@pytest.mark.parametrize("tokens,rpb,tile", [
    (16384, 8, 128), (16384, 7, 128), (16384, 16, 64), (16384, 4, 128),
    (4096, 2, 128), (16, 8, 16), (40, 8, 64), (16384, 64, 16),
    (16384, 2048, 16)])
def test_the_tile_follows_the_query_rows_a_lane_block(tokens, rpb, tile):
    """``_token_tile``: a power of two of 16 to 128 tokens, at most
    1,024 query rows a lane block where that leaves 16, no more than the
    launch's bucket: the two 8k cells' groups of 8 and 7 and granite's 4
    at 128, nemotron's 16 at 64, OPT's two heads a block at 128, a
    launch of 16 tokens at 16."""
    from tests.unit.inference.walk_cases import ra
    assert ra()._token_tile(tokens, rpb) == tile


# what the launches that are NOT the token tile are made of: the jaxprs of
# the one-token form's and the latent kernel's launches of ``walk_cases``
# were read on the parent of PR 59 (d747e90) and on the change with their
# addresses struck out and are the same text (PERF.md section 6, PR 59);
# these counts are that text's, so that a change to the token tile that
# reaches the walk they share shows here
OTHER_LAUNCHES = {
    "tiled-hpb2": dict(dot_general=4, exp=2, select_n=10, cond=15,
                       dma_start=12, dma_wait=12),
    "tiled-group8": dict(dot_general=4, exp=2, select_n=8, cond=15,
                         dma_start=12, dma_wait=12),
    "tiled-int8": dict(dot_general=4, exp=2, select_n=13, cond=15,
                       dma_start=16, dma_wait=14),
    "window-ring": dict(dot_general=4, exp=2, select_n=10, cond=15,
                        dma_start=12, dma_wait=12),
    "latent": dict(dot_general=2, exp=2, select_n=8, cond=16, dma_start=6,
                   dma_wait=7),
}


@pytest.mark.parametrize("case", sorted(OTHER_LAUNCHES))
def test_the_one_token_and_latent_launches_are_made_of_what_they_were(case):
    """The one-token form and the latent kernel are not the token
    tile's: their launches hold the products, exponentials, selects,
    branches and copies the parent's held, and the token tile's own
    launch of the same geometry one masked update a lane block and
    chunk, as it did (no second body beside it)."""
    import re
    from tests.unit.inference import walk_cases
    ra = walk_cases.ra()
    args, kw, _ = walk_cases.build(case)
    latent = walk_cases.CASES[case]["kernel"] == "latent"
    fn = ra.latent_attention if latent else ra.ragged_attention

    def count(text):
        return {w: len(re.findall(rf"\b{w}\b", text))
                for w in OTHER_LAUNCHES[case]}
    text = str(jax.make_jaxpr(functools.partial(fn, **kw))(*args))
    assert count(text) == OTHER_LAUNCHES[case]
    if not latent:
        tile = count(str(jax.make_jaxpr(functools.partial(
            fn, **dict(kw, one_token=False)))(*args)))
        # a lane block at a time where the one-token form stacks them
        blocks = walk_cases.CASES[case]["kvh"] * walk_cases.CASES[case][
            "hd"] // 128
        assert tile["dot_general"] == 2 * blocks
        assert tile["exp"] == 2 * blocks


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


def _engine(model, params, window=1, **kw):
    smc = dict(max_tracked_sequences=8, max_seq_len=128, num_blocks=65,
               block_size=16)
    smc.update(kw.pop("sm", {}))
    return InferenceEngineV2(
        model, RaggedInferenceEngineConfig(
            state_manager=DSStateManagerConfig(**smc),
            dtype="float32", prefill_bucket=16, decode_window=window,
            **kw),
        params=params)


def _dense_logits(model, params, seq):
    """The model's own dense forward over one whole sequence, float32:
    [len(seq), vocab]. No pool, no packing, no kernel."""
    return np.asarray(model.forward_logits(
        params, jnp.asarray([seq], jnp.int32)))[0]


def _assert_put_is_dense(model, params, got, seqs):
    want = np.stack([_dense_logits(model, params, s)[-1] for s in seqs])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(got.argmax(-1), want.argmax(-1))


def test_put_parity_prefill_only(tiny):
    model, params = tiny
    prompts = [list(range(3, 17)), [2, 4, 6], list(range(40, 62))]
    got = _engine(model, params).put([1, 2, 3], prompts)
    _assert_put_is_dense(model, params, got, prompts)


def test_put_parity_decode_only_and_interleaved(tiny):
    model, params = tiny
    a, b = list(range(3, 17)), [2, 4, 6]
    eng = _engine(model, params)
    eng.put([1, 2], [a, b])
    # decode-only batch
    got = eng.put([1, 2], [[40], [41]])
    a, b = a + [40], b + [41]
    _assert_put_is_dense(model, params, got, [a, b])
    # interleaved: decode + fresh prefill + continuation chunk
    c = list(range(20, 31))
    got = eng.put([1, 3, 2], [[50], c, [51, 52, 53]])
    _assert_put_is_dense(model, params, got,
                         [a + [50], c, b + [51, 52, 53]])


@pytest.mark.parametrize("window", [1, 4], ids=["per-token", "fused"])
def test_the_engine_counts_the_positions_under_the_decode_launches(
        tiny, window, monkeypatch):
    """``inference_attention_decode_positions_total`` {held, chunked}: 0
    and 0 on the CPU (its decode programs run no kernel with a chunk),
    and where the one-token form serves (asked of the engine here as the
    chip would answer) the positions the rows' pages hold, a layer, row
    and step, from the contexts the manager holds at a launch (a window
    launched behind one in flight counts from that one's writes), held
    <= chunked and both whole pages."""
    from deepspeed_tpu.inference.v2 import engine_v2
    from deepspeed_tpu.telemetry import get_registry
    model, params = tiny
    eng = _engine(model, params, window=window)
    family = get_registry().get("inference_attention_decode_positions_total")
    held, chunked = family.labels(kind="held"), family.labels(kind="chunked")
    before = held.value, chunked.value
    prompts = [list(range(3, 17)), [2, 4, 6], list(range(40, 62))]
    eng.generate(prompts, max_new_tokens=9, temperature=0.0,
                 eos_token_id=None)
    assert (held.value, chunked.value) == before             # the CPU
    monkeypatch.setattr(engine_v2, "one_token_tile_serves",
                        lambda *a: True)
    eng.generate(prompts, max_new_tokens=9, temperature=0.0,
                 eos_token_id=None)       # 8 decode steps a row
    # a row's bound at step s is its prompt + s + 1 (the fed token too)
    want = model.cfg.num_layers * sum(
        -(-(len(p) + s + 1) // 16) * 16 for p in prompts for s in range(8))
    assert held.value - before[0] == want
    grown = chunked.value - before[1]
    assert grown >= want and grown % 16 == 0
    # a table of one or two pages is one chunk: every row's is whole
    assert grown <= model.cfg.num_layers * 3 * 8 * 32


def test_the_engine_counts_the_chunk_visits_of_its_ragged_steps(
        tiny, monkeypatch):
    """``inference_attention_prompt_chunks_total`` {whole, masked}: 0 on
    the CPU (its ragged step runs the pipelined variant: no tile), and
    where the token tile serves (asked of the engine here as the chip
    would answer) a visit a layer and (tile, row) for rows whose
    contexts fit one chunk: three prompts in one tile of the step's
    bucket are three visits a layer, none of them whole (a tile of
    several rows), and a continuation of one row one more."""
    from deepspeed_tpu.inference.v2 import engine_v2
    model, params = tiny
    eng = _engine(model, params)
    family = get_registry().get("inference_attention_prompt_chunks_total")
    whole, masked = family.labels(kind="whole"), family.labels(kind="masked")
    before = whole.value, masked.value
    prompts = [list(range(3, 17)), [2, 4, 6], list(range(40, 62))]
    eng.put([1, 2, 3], prompts)
    assert (whole.value, masked.value) == before             # the CPU
    # (the toy widths are no geometry of the tiled variant: the tile is
    # told too)
    monkeypatch.setattr(engine_v2, "token_tile_serves", lambda *a: True)
    monkeypatch.setattr(engine_v2, "token_tile", lambda tokens, *a: tokens)
    eng.put([4, 5, 6], prompts)
    L = model.cfg.num_layers
    assert (whole.value, masked.value) == (before[0], before[1] + 3 * L)
    eng.put([5], [[7, 8, 9]])
    assert (whole.value, masked.value) == (before[0], before[1] + 4 * L)


def test_generate_stream_parity_greedy_and_sampled(tiny):
    """The full generate() loop (ragged prefill put + fused decode
    window). Greedy: every generated token is the dense forward's best
    at its position. Sampled, fixed seed: a row's stream is the stream
    of that request served alone by another engine (``generate()``
    seeds a row by its place, so each request takes row 0 in turn)."""
    model, params = tiny
    prompts = [list(range(3, 17)), [2, 4, 6], [5]]
    outs = _engine(model, params, window=8).generate(
        prompts, max_new_tokens=20)
    for p, out in zip(prompts, outs):
        assert len(out) == len(p) + 20
        ref = _dense_logits(model, params, list(map(int, out)))
        np.testing.assert_array_equal(
            out[len(p):], ref[len(p) - 1:-1].argmax(-1))
    kw = dict(max_new_tokens=14, temperature=0.8, top_p=0.9, top_k=20,
              seed=5)
    mixed, alone = (_engine(model, params, window=8) for _ in range(2))
    for r in range(len(prompts)):
        order = prompts[r:] + prompts[:r]
        np.testing.assert_array_equal(
            mixed.generate(order, **kw)[0],
            alone.generate(order[:1], **kw)[0])


def _mixed_traffic(sched, prompts, base, new_tokens=10, only=None):
    """Staggered submissions so steps interleave prompt chunks with
    running decodes (the SplitFuse mixed-batch shape). ``only``: submit
    that one request of the mix, with its uid and sampling, and no
    other."""
    def submit(i, uid, p, **kw):
        if only is None or only == i:
            sched.submit(uid, p, new_tokens, **kw)
    for i, p in enumerate(prompts[:2]):
        submit(i, base + i, p, temperature=0.7 if i == 1 else 0.0,
               top_p=0.9, seed=5)
    for _ in range(3):
        sched.step()
    for i, p in enumerate(prompts[2:]):
        submit(2 + i, base + 100 + i, p,
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
    """The scheduler's RaggedBatch steps under chunked prefill +
    interleaved decode: every request's stream, greedy AND fixed-seed
    sampled, is bit-identical to the same request (same uid, same seed)
    served alone by another engine, whose steps hold that row only."""
    model, params = tiny
    prompts = _mixed_prompts()
    mixed = _mixed_traffic(DynamicSplitFuseScheduler(
        _engine(model, params, window=window), token_budget=24, chunk=16),
        prompts, 100)
    assert len(mixed) == len(prompts)
    alone_engine = _engine(model, params, window=window)
    alone = {}
    for i in range(len(prompts)):
        alone.update(_mixed_traffic(DynamicSplitFuseScheduler(
            alone_engine, token_budget=24, chunk=16), prompts, 100,
            only=i))
    assert mixed == alone


def _greedy_mixed_traffic(sched, prompts, base, new_tokens=10):
    """All-greedy staggered mix: steps interleave prompt chunks with
    running decodes, and pure-decode steps take the fused-window fast
    path."""
    for i, p in enumerate(prompts[:2]):
        sched.submit(base + i, p, new_tokens)
    for _ in range(3):
        sched.step()
    for i, p in enumerate(prompts[2:]):
        sched.submit(base + 50 + i, p, new_tokens)
    sched.run()


@contextlib.contextmanager
def _own_registry():
    """A registry and a watchdog of the test's own: what compiled and
    what was counted inside is what the test ran."""
    prev = set_registry(MetricsRegistry())
    watchdog.reset()
    try:
        yield get_registry()
    finally:
        set_registry(prev)
        watchdog.reset()


def _compiled(reg):
    """{program name: compile events} the watchdog has seen."""
    fam = reg.get("xla_compile_events_total")
    return {v[0]: int(s.value) for v, s in fam.series()} if fam else {}


def test_mixed_traffic_fewer_programs_zero_steady_recompiles(tiny):
    """The acceptance criterion, chip-free: ONE ragged program family
    (and the fused window) serves the mixed sweep, 8 programs in all,
    with zero steady-state recompiles."""
    model, params = tiny
    prompts = _mixed_prompts()
    with _own_registry() as reg:
        eng = _engine(model, params, window=8)
        sched = DynamicSplitFuseScheduler(eng, token_budget=24, chunk=16)
        # warm the bucket set TWICE: a bucket's first call compiles
        # against the unsharded fresh pool, its repeats against the
        # donated (sharded) one — the second wave absorbs that
        # one-time respecialization for buckets the first wave
        # visited only once (same discipline as bench/gate)
        _greedy_mixed_traffic(sched, prompts, 100)
        _greedy_mixed_traffic(sched, prompts, 200)
        count = reg.family_total("xla_compile_events_total")
        watchdog.mark_steady(True)
        try:
            _greedy_mixed_traffic(sched, prompts, 300)
        finally:
            watchdog.mark_steady(False)
        steady = reg.family_total("xla_steady_state_recompiles_total")
        families = _compiled(reg)
    assert steady == 0
    # exact, so that a bucket gained shows here
    assert count == 8
    assert families == {"ragged_step": 6, "decode_window_greedy": 2}


# ---------------------------------------------------------------------------
# one way in: the option is gone, and so are the programs it selected
# ---------------------------------------------------------------------------
def test_a_config_dict_that_names_ragged_attention_is_refused(tiny):
    model, params = tiny
    for value in ("off", "auto"):
        with pytest.raises(ValueError, match="ragged_attention is gone"):
            InferenceEngineV2(model, {"ragged_attention": value,
                                      "dtype": "float32"}, params=params)


def test_a_serving_config_that_names_ragged_attention_is_refused():
    from deepspeed_tpu.inference.v2.serve.worker import _serving_config
    with pytest.raises(TypeError, match="ragged_attention"):
        _serving_config({"serving": {"ragged_attention": "off"}})


PLAIN_FAMILIES = {"ragged_step", "first_token_greedy", "first_token_sample",
                  "decode_greedy", "decode_sample",
                  "decode_window_greedy", "decode_window_sample"}
SPEC_FAMILIES = {"spec_verify_w", "draft_catchup", "spec_decode_window"}


def _family(program):
    return "spec_verify_w" if program.startswith("spec_verify_w") \
        else program


def _engine_families(eng):
    """The family of every watched jit the engine holds, built or
    cached: what it COULD compile, whatever the traffic was."""
    held = list(vars(eng).values())
    held += [j for pair in eng._fused_jit_cache.values() for j in pair]
    held += list(eng._continue_spec_jits.values())
    held += list(eng._spec_window_jits.values())
    return {_family(j.program) for j in held
            if isinstance(j, watchdog.WatchedFunction)}


@pytest.mark.parametrize("speculative", [False, True],
                         ids=["plain", "speculative"])
def test_the_engine_compiles_the_families_that_remain_and_no_other(
        tiny, speculative):
    """After the mixed traffic (greedy and sampled rows, all-greedy
    rows, a sampled and a greedy generate(); windows of 1 and of 8) the
    programs are the seven plain families (generate()'s pick of the
    first token is two of them: the ragged step's stays put()'s).
    With a draft model loaded, speculative generate() through both
    draft sources adds the three speculative ones, and no eleventh; the
    engine holds no watched jit of another name either."""
    model, params = tiny
    prompts = _mixed_prompts()
    with _own_registry() as reg:
        for window in (1, 8):
            eng = _engine(model, params, window=window)
            sched = DynamicSplitFuseScheduler(eng, token_budget=24,
                                              chunk=16)
            _mixed_traffic(sched, prompts, 100)
            _greedy_mixed_traffic(sched, prompts, 300)
            eng.generate(prompts[:2], max_new_tokens=4, temperature=0.8,
                         seed=3)
            eng.generate(prompts[:2], max_new_tokens=4)
        want = set(PLAIN_FAMILIES)
        if speculative:
            eng.load_draft_model(model, params)
            # greedy text of this model soon cycles: n-gram drafts hit
            rep = [[5, 9, 17, 23] * 6]
            for mode in ("ngram", "draft"):
                eng.generate(rep, max_new_tokens=40, speculative=True,
                             spec_mode=mode)
            want |= SPEC_FAMILIES
        assert {_family(p) for p in _compiled(reg)} == want
        assert _engine_families(eng) <= want


@pytest.mark.parametrize("tokens", [[40], [40, 41, 42]],
                         ids=["one_token", "continuation"])
def test_put_of_a_known_sequence_runs_the_ragged_step(tiny, tokens):
    """put() of one token for a KNOWN sequence and of a multi-token
    continuation are both ONE ragged step: its span, its counters, its
    program, and no decode step beside it."""
    from deepspeed_tpu.telemetry import trace
    model, params = tiny
    prompt = list(range(3, 17))
    with _own_registry() as reg:
        eng = _engine(model, params)
        eng.put([1], [prompt])
        steps0 = reg.family_total("inference_ragged_steps_total")
        tokens0 = reg.family_total("inference_ragged_tokens_total")
        trace.clear()
        got = eng.put([1], [tokens])
        names = {s["name"] for s in trace.export()}
        assert reg.family_total("inference_ragged_steps_total") \
            == steps0 + 1
        assert reg.family_total("inference_ragged_tokens_total") \
            == tokens0 + len(tokens)
        assert reg.family_total("inference_decode_steps_total") == 0
        assert set(_compiled(reg)) == {"ragged_step"}
    assert "ragged_step" in names
    assert not names & {"prefill", "continue", "decode_step"}
    _assert_put_is_dense(model, params, got, [prompt + tokens])
