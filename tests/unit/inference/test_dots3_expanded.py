"""A prompt's launch of a full latent layer that selects, in the EXPANDED
form (PR 69): a row's cached latent rows turned into per-head keys and
values once a launch, the launch's tokens packed in tiles of one row
each, a per-head kernel under the picks' mask
(``kernels/ragged_attention.picked_heads_attention``,
``paged_model._expanded_index_attention``).

The kernel runs under the TPU interpreter where a test asks for it; the
rest reads the jnp form against the ABSORBED path (the masked latent
kernel's gathering reference carried through ``Wv``), on the same pool
and the same flags. The form is chosen from a launch's static shapes
(``paged_model.index_prompt_form``): the served case reaches the
expanded one through its shapes (one row, launches of 256 tokens), not
through a switch.
"""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import reference_dots3 as reference
from deepspeed_tpu.inference.v2 import paged_model
from deepspeed_tpu.telemetry import get_registry

from tests.unit.inference.test_dots3_serving import (F32, SEED, TOY, _err,
                                                     build)

ra = importlib.import_module(
    "deepspeed_tpu.inference.v2.kernels.ragged_attention")

NH, DC, DR, DN, DV, W, BS = 4, 32, 16, 24, 16, 128, 8
SCALE = float(DN + DR) ** -0.5


def _launch(dtype=jnp.float32, rows=((60, 150), (0, 40)), places=48, T=256,
            topk=64, seed=0):
    """Two rows in one launch over a latent pool and its index keys: a
    continuation of 150 tokens behind 60 cached (a whole tile of 128 and
    a tail) and a prompt of 40 from an empty row (every bound under
    ``topk``: all picked), padding tokens behind. The index keys take
    five values, so the scores tie by the dozen. A score is ``DN + DR``
    = 40 wide and a value ``DV`` = 16."""
    rng = np.random.default_rng(seed)
    R = len(rows)
    nb = 1 + R * places
    pool = np.zeros((2, nb, BS, W), np.float32)
    pool[..., :DC + DR] = rng.normal(size=(2, nb, BS, DC + DR))
    keys = np.zeros((2, nb, BS, 8), np.float32)
    keys[..., 0] = rng.integers(-2, 3, (2, nb, BS))
    bt = rng.permutation(np.arange(1, nb)).reshape(R, places).astype(np.int32)
    row_ids = [r for r, (c, n) in enumerate(rows) for _ in range(n)]
    lengths = [c + i + 1 for c, n in rows for i in range(n)]
    T0 = len(row_ids)
    row_ids = np.array(row_ids + [0] * (T - T0), np.int32)
    lengths = np.array(lengths + [0] * (T - T0), np.int32)
    qi = np.zeros((T, 2, 8), np.float32)
    qi[:, :, 0] = rng.integers(1, 3, (T, 2))
    wi = rng.choice([-1.0, 1.0], (T, 2)).astype(np.float32)
    return dict(
        q=jnp.asarray(rng.normal(size=(T, NH, DN + DR)), dtype),
        wkv_b=jnp.asarray(rng.normal(size=(DC, NH, DN + DV)) * DC ** -0.5,
                          dtype),
        pool={"latent": jnp.asarray(pool, dtype),
              "index_k": jnp.asarray(keys, dtype)},
        qi=jnp.asarray(qi, dtype), wi=jnp.asarray(wi), l=jnp.int32(1),
        row_ids=jnp.asarray(row_ids), lengths=jnp.asarray(lengths),
        bt=jnp.asarray(bt), T0=T0, topk=topk)


def _absorbed(c, picked):
    """The absorbed form by the gathering reference, carried through
    ``Wv``: ``[T, nh, dv]``."""
    q, wkv_b = c["q"], c["wkv_b"]
    q_lat = jnp.einsum("thd,chd->htc", q[..., :DN], wkv_b[..., :DN])
    qx = jnp.pad(jnp.concatenate([q_lat, q[..., DN:].transpose(1, 0, 2)],
                                 -1), ((0, 0), (0, 0), (0, W - DC - DR)))
    o_lat = ra.latent_attention_reference(
        qx, c["pool"]["latent"], c["l"], c["row_ids"], c["lengths"], c["bt"],
        dc=DC, scale=SCALE, **({} if picked is None else {"picked": picked}))
    return jnp.einsum("htc,chd->thd", o_lat, wkv_b[..., DN:])


def _flags(c):
    return paged_model.index_mask(
        c["qi"], c["wi"], c["pool"]["index_k"], c["l"], c["row_ids"],
        c["lengths"], c["bt"], c["topk"])


# ---------------------------------------------------------------------------
# (a) the per-head kernel under the interpreter
# ---------------------------------------------------------------------------
def test_the_per_head_kernel_is_the_absorbed_reference_through_wv():
    """Two rows in a launch, a row's tail tile, an empty tile, a row
    whose every bound is under ``topk`` (all picked: the dense result),
    flags with ties; a score 40 wide against a value of 16; DMAs and
    grid and all, two heads a step, chunks of 128 positions."""
    c = _launch()
    T, T0 = c["q"].shape[0], c["T0"]
    picked = _flags(c)
    counts = np.asarray(picked).sum(-1)
    np.testing.assert_array_equal(
        counts, np.minimum(np.asarray(c["lengths"]), c["topk"]))
    want = np.asarray(_absorbed(c, picked))
    tq = 128
    src, slot, tile_rows = paged_model._row_tiles(
        c["row_ids"], c["lengths"], 2, tq)
    # row 0: a whole tile and a tail of 22; row 1: 40 of a tile; one empty
    np.testing.assert_array_equal(tile_rows, [0, 0, 1, 0])
    took = np.asarray(src) < T
    assert took.reshape(4, tq).sum(-1).tolist() == [128, 22, 40, 0]
    at = jnp.minimum(src, T - 1)
    # a row's 384 positions in three pieces of 128 (a chunk each); the
    # rows reach 210 and 40, so the grid's chunk axis ends at two
    lat = c["pool"]["latent"][c["l"]][c["bt"]].reshape(2, 3, 128, W)
    k = jnp.einsum("rpcd,dhn->rphcn", lat[..., :DC], c["wkv_b"][..., :DN])
    v = jnp.einsum("rpcd,dhn->rphcn", lat[..., :DC], c["wkv_b"][..., DN:])
    k, v = (a.at[:, 2].set(jnp.nan) for a in (k, v))    # never read
    args = (c["q"][at].transpose(1, 0, 2), k, lat[..., DC:DC + DR], v,
            # (the flags a tile of 128 tokens, a token a lane)
            (picked[at] & jnp.asarray(took)[:, None]).reshape(
                4, tq, -1).transpose(0, 2, 1), tile_rows,
            jnp.where(jnp.asarray(took), c["lengths"][at], 0))
    got = ra.picked_heads_attention(*args, scale=SCALE, tq=tq,
                                    interpret=True, heads_a_step=2,
                                    chunk=128)
    ref = ra.picked_heads_attention_reference(
        args[0], *(jnp.nan_to_num(a) for a in args[1:4]), *args[4:],
        scale=SCALE, tq=tq)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-6)
    assert not np.asarray(got)[~took].any()
    got = np.asarray(got).reshape(-1, NH, DV)[np.asarray(slot)[:T0]]
    np.testing.assert_allclose(got, want[:T0], atol=1e-5)
    # the short row picked every position under its bounds: the dense read
    dense = np.asarray(_absorbed(c, None))
    np.testing.assert_allclose(got[150:T0], dense[150:T0], atol=1e-5)
    assert np.abs(got[:150] - dense[:150]).max() > 1e-3


def test_a_rows_keys_and_values_are_made_under_its_reach_alone():
    """``expand_latent_rows`` under the interpreter: the pieces under a
    row's reach are the einsum's, a piece past it and a row with no
    token are NEVER WRITTEN (the interpreter's uninitialised memory is
    NaN), two heads a step."""
    rng = np.random.default_rng(2)
    R, C, E, nh = 3, 4, 64, 8
    lat = jnp.asarray(rng.normal(size=(R, C, E, W)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(DC, nh, DN + DV)), jnp.float32)
    reach = jnp.asarray([130, 0, 64], jnp.int32)
    want = ra.expand_latent_rows_reference(lat, w, dc=DC, dn=DN)
    got = ra.expand_latent_rows(lat, w, reach, dc=DC, dn=DN, interpret=True,
                                heads_a_step=2)
    under = np.arange(C)[None] * E < np.asarray(reach)[:, None]
    assert under.sum() == 4
    for g, w_, width in zip(got, want, (DN, DV)):
        assert g.shape == (R, C, nh, E, width)
        np.testing.assert_allclose(np.asarray(g)[under],
                                   np.asarray(w_)[under], atol=2e-5)
        assert np.isnan(np.asarray(g)[~under]).all()


# ---------------------------------------------------------------------------
# (b) the expanded prompt path against the absorbed one
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 3e-2)])
def test_the_expanded_prompt_path_is_the_absorbed_one(dtype, tol):
    """The same pool, the same flags (``index_mask`` over the same
    tokens): float32 to 1e-5; bf16 inside the rounding of the operands
    that differ between the forms (a key ``c^kv W^UK`` rounded against a
    query ``q_nope Wk^T`` rounded: 3e-2 of the largest output)."""
    c = _launch(dtype)
    T0 = c["T0"]
    common = dict(dc=DC, scale=SCALE, topk=c["topk"], use_kernel=False)
    got = paged_model._expanded_index_attention(
        c["q"], c["qi"], c["wi"], c["pool"], c["l"], c["wkv_b"],
        c["row_ids"], c["lengths"], c["bt"], dn=DN, **common)
    q_lat = jnp.einsum("thd,chd->htc", c["q"][..., :DN],
                       c["wkv_b"][..., :DN])
    qx = jnp.pad(jnp.concatenate(
        [q_lat, c["q"][..., DN:].transpose(1, 0, 2)], -1),
        ((0, 0), (0, 0), (0, W - DC - DR)))
    o_lat = paged_model._indexed_latent_attention(
        qx, c["qi"], c["wi"], c["pool"], c["l"], c["row_ids"], c["lengths"],
        c["bt"], one_token=False, **common)
    want = jnp.einsum("htc,chd->thd", o_lat, c["wkv_b"][..., DN:])
    assert got.shape == want.shape == (256, NH, DV) and got.dtype == dtype
    assert _err(got[:T0], want[:T0]) <= tol
    assert not np.asarray(got[T0:], np.float32).any()


def test_heads_go_a_group_at_a_time_inside_the_bytes(monkeypatch):
    """``_EXPAND_BYTES`` bounds the keys and values made at once: four
    heads at the least, and the groups' outputs stand head by head
    where one group's would."""
    c = _launch()
    common = dict(dc=DC, dn=DN, scale=SCALE, topk=c["topk"],
                  use_kernel=False)
    args = (c["q"], c["qi"], c["wi"], c["pool"], c["l"], c["wkv_b"],
            c["row_ids"], c["lengths"], c["bt"])
    whole = paged_model._expanded_index_attention(*args, **common)
    c8 = {**c, "q": jnp.concatenate([c["q"], c["q"][:, ::-1]], 1),
          "wkv_b": jnp.concatenate([c["wkv_b"], c["wkv_b"][:, ::-1]], 1)}
    monkeypatch.setattr(paged_model, "_EXPAND_BYTES", 1)
    args8 = (c8["q"], c["qi"], c["wi"], c["pool"], c["l"], c8["wkv_b"],
             c["row_ids"], c["lengths"], c["bt"])
    grouped = paged_model._expanded_index_attention(*args8, **common)
    assert "while" in str(jax.make_jaxpr(
        lambda *a: paged_model._expanded_index_attention(*a, **common))(
        *args8))
    np.testing.assert_allclose(grouped[:, :NH], whole, atol=1e-6)
    np.testing.assert_allclose(grouped[:, NH:], whole[:, ::-1], atol=1e-6)


# ---------------------------------------------------------------------------
# (c) who takes the path: the launch's static shapes
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("tokens,rows,positions,form", [
    (4096, 4, 16384, "expanded"),       # the cell's chunk step
    (4096, 4, 4096, "expanded"),
    (8192, 8, 32768, "expanded"),
    (256, 1, 4096, "expanded"),         # at the threshold
    (1024, 4, 16384, "expanded"),
    (512, 4, 16384, "absorbed"),        # 128 tokens a row: under it
    (128, 1, 4096, "absorbed"),
    (64, 4, 32768, "absorbed"),         # a ragged step's tail
    (8, 8, 32768, "absorbed"),
    (4096, 4, 2048, None),              # nothing selects: the dense launch
    (4096, 4, 1024, None),
    (64, 4, 2048, None),
])
def test_the_form_follows_the_launchs_shapes(tokens, rows, positions, form):
    assert paged_model.index_prompt_form(tokens, rows, positions, 2048) \
        == form
    assert paged_model.index_prompt_form(tokens, rows, positions, 0) is None


def test_a_tile_is_a_rows_share_of_the_launch():
    tile = ra.picked_heads_tile
    assert [tile(4096, 4), tile(8192, 8), tile(256, 1), tile(1024, 4),
            tile(4096, 1), tile(64, 4)] == [512, 512, 256, 256, 512, 128]


def test_tokens_pack_into_tiles_of_one_row_each():
    """Rows of 300, 0, 5 and 130 tokens, padding behind: every tile one
    row's, a token's place found again, nothing twice."""
    counts, tq, T = [300, 0, 5, 130], 128, 512
    row_ids = np.repeat(np.arange(4), counts)
    live = len(row_ids)
    row_ids = np.pad(row_ids, (0, T - live)).astype(np.int32)
    lengths = np.pad(np.arange(1, live + 1), (0, T - live)).astype(np.int32)
    src, slot, tile_rows = (np.asarray(a) for a in paged_model._row_tiles(
        jnp.asarray(row_ids), jnp.asarray(lengths), 4, tq))
    assert src.shape == ((T // tq + 4) * tq,)
    np.testing.assert_array_equal(src[slot[:live]], np.arange(live))
    assert (slot[live:] == len(src)).all() and (src < T).sum() == live
    tiles = src.reshape(-1, tq)
    assert tile_rows[:6].tolist() == [0, 0, 0, 2, 3, 3]
    for i, tile in enumerate(tiles):
        held = tile[tile < T]
        assert (row_ids[held] == tile_rows[i]).all()
    assert [(t < T).sum() for t in tiles] == [128, 128, 44, 5, 128, 2, 0, 0]


# ---------------------------------------------------------------------------
# (d) the counter, (e) served: chunks, then decode
# ---------------------------------------------------------------------------
ONE_ROW = {"max_tracked_sequences": 1, "max_ragged_batch_size": 256,
           "max_seq_len": 384, "num_blocks": 81}


def _launches():
    fam = get_registry().get("inference_index_prompt_launches_total")
    return {f: fam.labels(form=f).value for f in ("expanded", "absorbed")}


def test_the_counter_of_the_prompt_launches_forms():
    """A launch a full layer, by the launch's static shapes: four rows
    of 40 tokens select (tables of 64 positions over ``index_topk`` 32)
    in the absorbed form, one row's 256-token launch in the expanded
    one, and a launch under ``index_topk`` positions counts nothing.
    Read as what ONE call adds."""
    layers = 2
    rng = np.random.default_rng(11)

    def fed(eng, rows, n):
        before = _launches()
        eng.put(list(range(rows)),
                [rng.integers(0, TOY["vocab_size"], n) for _ in range(rows)])
        return {f: v - before[f] for f, v in _launches().items()}
    eng = build()
    # 160 tokens in steps of 64: tables of 16 pages = 128 positions, then 32
    assert fed(eng, 4, 40) == {"expanded": 0, "absorbed": 3 * layers}
    one = build(state_manager=ONE_ROW)
    # 300 tokens: a launch of 256 (expanded), the tail of 44 (absorbed)
    assert fed(one, 1, 300) == {"expanded": layers, "absorbed": layers}
    short = build(state_manager=ONE_ROW)
    assert fed(short, 1, 24) == {"expanded": 0, "absorbed": 0}


def test_chunks_in_the_expanded_form_then_decode_is_the_reference():
    """One row, launches of 256 tokens: the prompt's first chunk step
    takes the expanded form (256 tokens a row over a table of 256
    positions, ``index_topk`` 32), its tail the absorbed one, then
    decode. Logits of the chunked prompt at 1e-4 and every decoded token
    the reference's best, as the absorbed path is held."""
    from benchmark import weights_dots3 as weights
    params = weights.make(TOY, SEED, jnp.float32)
    prompt = np.random.default_rng(SEED + 1).integers(
        0, TOY["vocab_size"], 300)
    new = 12
    eng = build(state_manager=ONE_ROW)
    before = _launches()
    logits = np.asarray(eng.put([0], [prompt]))
    assert {f: v - before[f] for f, v in _launches().items()} \
        == {"expanded": 2, "absorbed": 2}
    eng.flush(0)
    want = reference.logits(params, TOY, prompt)[-1]
    assert _err(logits[0], want) <= 1e-4
    out = np.asarray(eng.generate([prompt], max_new_tokens=new,
                                  temperature=0.0, eos_token_id=None)[0])
    assert len(out) == len(prompt) + new
    lg = np.asarray(reference.logits(params, TOY, out[:-1]))
    at = lg[len(prompt) - 1:]
    gap = (at.max(-1) - at[np.arange(len(at)), out[len(prompt):]]) \
        / np.abs(at).max(-1)
    assert gap.max() <= F32


# ---------------------------------------------------------------------------
# (f) the indexer scores a tile's keys as far as its bounds reach (PR 71)
# ---------------------------------------------------------------------------
CHUNK = 32          # four pages: toy tables hold several chunks


def _whole_scores(qi, wi, keys):
    """The indexer's scores over a row's keys at their full width, as
    the program made them before it stopped at a tile's reach: chunks of
    ``CHUNK`` keys, the last padded with zeros."""
    def chunk(k):
        s = jnp.einsum("tjd,cd->tjc", qi, k,
                       preferred_element_type=jnp.float32)
        return jnp.einsum("tjc,tj->tc", jax.nn.relu(s), wi)
    c = keys.shape[0]
    n = -(-c // CHUNK)
    keys = jnp.pad(keys, ((0, n * CHUNK - c), (0, 0)))
    out = jax.lax.map(chunk, keys.reshape(n, CHUNK, -1))
    return out.transpose(1, 0, 2).reshape(qi.shape[0], -1)[:, :c]


def _tile(bounds, rows=None, places=17, tables=2, seed=3, values=None):
    """A tile of query tokens over ``tables`` rows' index keys, 17 pages
    = 136 positions a row: four chunks of 32 and a quarter of a fifth,
    so the last chunk lies against the table's end. ``values``: the
    keys' first lane takes whole numbers of that many values (scores
    that tie); else normal draws."""
    rng = np.random.default_rng(seed)
    t, heads, d = len(bounds), 3, 8
    nb = 1 + tables * places
    keys = rng.normal(size=(2, nb, BS, d)).astype(np.float32)
    qi = rng.normal(size=(t, heads, d)).astype(np.float32)
    wi = rng.normal(size=(t, heads)).astype(np.float32)
    if values:
        keys[:] = 0
        keys[..., 0] = rng.integers(0, values, (2, nb, BS))
        qi[:] = 0
        qi[:, :, 0] = 1.0
        wi = np.ones_like(wi)
    bt = rng.permutation(np.arange(1, nb)).reshape(tables, places)
    rows = np.zeros(t, np.int32) if rows is None else np.asarray(rows)
    return dict(qi=jnp.asarray(qi), wi=jnp.asarray(wi),
                keys=jnp.asarray(keys), l=jnp.int32(1),
                rows=jnp.asarray(rows, jnp.int32),
                bounds=jnp.asarray(bounds, jnp.int32),
                bt=jnp.asarray(bt, jnp.int32))


def _args(c, keys=None):
    return (c["qi"], c["wi"], c["keys"] if keys is None else keys, c["l"],
            c["rows"], c["bounds"], c["bt"])


def _sorted_selection(scores, bounds, topk):
    """The ``topk`` largest scores under each token's bound, ties to the
    lower position, by a stable sort: position sets."""
    out = []
    for s, b in zip(np.asarray(scores), np.asarray(bounds)):
        order = np.argsort(-s[:b], kind="stable")[:topk]
        out.append(sorted(order.tolist()))
    return out


@pytest.mark.parametrize("reach", [20, 32, 33, 64, 65, 136])
def test_scores_under_a_tiles_reach_are_the_whole_tables(monkeypatch, reach):
    """A tile whose largest bound reaches one chunk, exactly a chunk's
    edge, an edge + 1 and the whole table: every position under a
    token's bound reads bit for bit what the full-width product gave it
    (a chunk's product is the same computation), the chunks past the
    reach are zeros nobody made, and those under it are all there."""
    monkeypatch.setattr(paged_model, "_INDEX_CHUNK", CHUNK)
    c = _tile(np.arange(reach - 7, reach + 1))
    got, seen = (np.asarray(a) for a in paged_model._token_scores(
        *_args(c), one_token=False))
    want = np.asarray(_whole_scores(
        c["qi"], c["wi"], c["keys"][1, c["bt"][0]].reshape(17 * BS, -1)))
    assert seen.sum(-1).tolist() == list(range(reach - 7, reach + 1))
    np.testing.assert_array_equal(got[seen], want[seen])
    chunks = int(paged_model.index_chunks(reach, 136))
    assert chunks == -(-reach // CHUNK)
    made = min(chunks * CHUNK, 136)
    np.testing.assert_array_equal(got[:, :made], want[:, :made])
    assert (got[:, made:] == 0).all()
    # a decode step's batched product stops at the same edge
    rows = _tile([reach, max(reach - 40, 1), 0], rows=[0, 1, 0])
    one, seen = (np.asarray(a) for a in paged_model._token_scores(
        *_args(rows), one_token=True))
    for i, r in enumerate([0, 1]):
        want = np.asarray(_whole_scores(
            rows["qi"][i:i + 1], rows["wi"][i:i + 1],
            rows["keys"][1, rows["bt"][r]].reshape(17 * BS, -1)))[0]
        np.testing.assert_allclose(one[i][seen[i]], want[seen[i]],
                                   rtol=1e-6, atol=1e-6)
    assert (one[:, made:] == 0).all() and not seen[2].any()


@pytest.mark.parametrize("poison", [np.nan, np.inf])
@pytest.mark.parametrize("reach", [20, 32, 70])
def test_keys_past_the_reach_are_never_read(monkeypatch, reach, poison):
    """Every cached key at or past the tile's reach poisoned (NaN,
    +inf): the flags of a prompt's launch and the positions of a decode
    step are what the clean keys gave; past the reach's last chunk not
    even a score was made."""
    monkeypatch.setattr(paged_model, "_INDEX_CHUNK", CHUNK)
    topk = 16
    c = _tile([reach, reach - 9, 5, 0], rows=[0, 0, 0, 0])
    flat = np.array(c["keys"])
    at = np.asarray(c["bt"][0]).repeat(BS)[reach:], \
        np.tile(np.arange(BS), 17)[reach:]
    flat[:, at[0], at[1]] = poison
    bad = jnp.asarray(flat)
    clean = np.asarray(paged_model.index_mask(*_args(c), topk))
    np.testing.assert_array_equal(
        np.asarray(paged_model.index_mask(*_args(c, bad), topk)), clean)
    assert clean.sum(-1).tolist() == [min(b, topk)
                                      for b in (reach, reach - 9, 5, 0)]
    for one_token in (False, True):
        idx, ok = paged_model.index_select(*_args(c), topk, one_token)
        idx2, ok2 = paged_model.index_select(*_args(c, bad), topk, one_token)
        np.testing.assert_array_equal(np.asarray(ok), np.asarray(ok2))
        np.testing.assert_array_equal(np.asarray(idx)[np.asarray(ok)],
                                      np.asarray(idx2)[np.asarray(ok2)])
    scores = np.asarray(paged_model._token_scores(
        *_args(c, bad), one_token=False)[0])
    assert np.isfinite(scores[:, -(-reach // CHUNK) * CHUNK:]).all()


@pytest.mark.parametrize("case", ["two_rows", "ties_over_an_edge",
                                  "ties_over_the_last_chunk"])
def test_the_flags_under_a_reach_are_the_sorted_selection(monkeypatch, case):
    """``index_mask`` against a stable sort of the full-width scores: a
    tile of two rows of different bounds (the absorbed form: each row's
    keys scored as far as the TILE reaches, a token keeps its own
    row's), and scores of three values whose ``topk``-th ties straddle a
    chunk's edge, or the edge of the last chunk, which lies against the
    table's end."""
    monkeypatch.setattr(paged_model, "_INDEX_CHUNK", CHUNK)
    topk = 24
    c = {"two_rows": lambda: _tile([100, 101, 30, 31, 32, 0],
                                   rows=[0, 0, 1, 1, 1, 0]),
         "ties_over_an_edge": lambda: _tile([70, 64, 65, 33], values=3),
         "ties_over_the_last_chunk": lambda: _tile([136, 130, 129, 97],
                                                   values=3)}[case]()
    flags = np.asarray(paged_model.index_mask(*_args(c), topk))
    for i, (r, b) in enumerate(zip(np.asarray(c["rows"]),
                                   np.asarray(c["bounds"]))):
        whole = np.asarray(_whole_scores(
            c["qi"][i:i + 1], c["wi"][i:i + 1],
            c["keys"][1, c["bt"][r]].reshape(17 * BS, -1)))
        want = _sorted_selection(whole, [b], topk)[0]
        assert np.flatnonzero(flags[i]).tolist() == want, (case, i)
        assert len(want) == min(b, topk)
    if case != "two_rows":
        # the ties are there: the topk-th score is shared past the set
        s = np.asarray(_whole_scores(
            c["qi"][:1], c["wi"][:1],
            c["keys"][1, c["bt"][0]].reshape(17 * BS, -1)))[0]
        b = int(c["bounds"][0])
        kth = np.sort(s[:b])[-topk]
        assert (s[:b] == kth).sum() > (s[:b] >= kth).sum() - topk + 1


def _tiles_swept(form, row_ids, bounds, tokens, table_rows, ctx):
    """The count by brute force over the tiles the PROGRAM makes: the
    expanded form's packing (``_row_tiles``), the others' tiles of the
    launch as it lies; a token in a tile counts the whole chunks under
    the tile's largest bound."""
    chunk = min(paged_model._INDEX_CHUNK, ctx)
    pad = tokens - len(bounds)
    rows_p = np.pad(row_ids, (0, pad)).astype(np.int32)
    bounds_p = np.pad(bounds, (0, pad)).astype(np.int32)
    if form == "expanded":
        tq = ra.picked_heads_tile(tokens, table_rows)
        src = np.asarray(paged_model._row_tiles(
            jnp.asarray(rows_p), jnp.asarray(bounds_p), table_rows, tq)[0])
        tt = min(paged_model._INDEX_TILE, tq)
        tiles = [t[t < tokens] for t in src.reshape(-1, tt)]
    else:
        tt = min(paged_model._INDEX_TILE, tokens)
        tiles = [np.arange(i, min(i + tt, tokens))
                 for i in range(0, tokens, tt)]
    total = 0
    for at in tiles:
        live = at[bounds_p[at] > 0]
        if not len(live):
            continue
        scored = sum(1 for c in range(-(-ctx // chunk))
                     if c * chunk < bounds_p[live].max())
        each = len(set(rows_p[live])) if form == "absorbed" else 1
        total += len(live) * each * scored * chunk
    return total


@pytest.mark.parametrize("form,counts,cached,tokens,table_rows", [
    ("expanded", [300, 0, 5, 130], [40, 0, 0, 90], 512, 4),
    ("expanded", [256], [100], 256, 1),
    ("expanded", [1024], [0], 1024, 1),
    ("absorbed", [20, 7, 30], [100, 0, 60], 64, 4),
    ("absorbed", [300, 40], [0, 200], 512, 4),
    ("decode", [1, 1, 1], [135, 3, 64], 4, 4),
])
def test_the_positions_swept_by_the_tiles(monkeypatch, form, counts, cached,
                                          tokens, table_rows):
    """``index_positions_swept`` (host arithmetic on the rows' tokens
    and contexts) against the count over the program's own tiles; never
    under the bounds' sum, which is the least the equations ask."""
    monkeypatch.setattr(paged_model, "_INDEX_CHUNK", CHUNK)
    ctx = 1400
    row_ids = np.repeat(np.arange(len(counts)), counts)
    bounds = np.concatenate([c + 1 + np.arange(n)
                             for c, n in zip(cached, counts)])
    got = paged_model.index_positions_swept(form, row_ids, bounds, tokens,
                                            table_rows, ctx)
    assert got == _tiles_swept(form, row_ids, bounds, tokens, table_rows,
                               ctx)
    assert got >= bounds.sum()


def test_bounds_that_reach_the_last_chunk_sweep_the_table(monkeypatch):
    """Every tile's largest bound in the table's last chunk: the
    products cover the table's width in whole chunks for every token,
    as they did for every tile before the reach bounded them."""
    monkeypatch.setattr(paged_model, "_INDEX_CHUNK", CHUNK)
    ctx, n = 136, 300
    bounds = np.full(n, 130)
    assert paged_model.index_positions_swept(
        "expanded", np.zeros(n, int), bounds, 512, 1, ctx) \
        == n * 5 * CHUNK
    assert paged_model.index_positions_swept(
        "decode", np.arange(4), bounds[:4], 4, 4, ctx) == 4 * 5 * CHUNK


def test_the_counter_of_the_positions_swept():
    """What ONE call adds: a launch that selects sweeps whole chunks
    (here one: the toy tables hold fewer positions than a chunk) for
    every query and full layer, its decode steps too, and a launch under
    ``index_topk`` positions sweeps and scores nothing."""
    def fed(eng, n, new):
        fam = {k: get_registry().get(f"inference_index_positions_{k}_total")
               for k in ("swept", "scored")}
        before = {(k, p): f.labels(program=p).value for k, f in fam.items()
                  for p in ("ragged_step", "decode")}
        eng.generate([np.random.default_rng(3).integers(
            0, TOY["vocab_size"], n)], max_new_tokens=new, temperature=0.0,
            eos_token_id=None)
        return {k: f.labels(program=k[1]).value - before[k]
                for k in before for f in (fam[k[0]],)}
    layers, n, new = 2, 300, 4
    added = fed(build(state_manager=ONE_ROW), n, new)
    ctx = 384                       # a row's table: max_seq_len
    assert added["swept", "ragged_step"] == layers * n * ctx
    assert added["scored", "ragged_step"] \
        == layers * np.arange(1, n + 1).sum()
    assert added["swept", "decode"] == layers * (new - 1) * ctx
    assert added["scored", "decode"] \
        == layers * (n + 1 + np.arange(new - 1)).sum()
    short = fed(build(state_manager=ONE_ROW), 24, 1)
    assert set(short.values()) == {0}
