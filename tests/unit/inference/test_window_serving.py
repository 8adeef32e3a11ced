"""The ``afmoe`` block's own: its pattern walked as runs, ``put()``'s chunk
steps under leaves of their own, a mixed step, the scheduler's share of
a step, the positions counter over a pattern, the ragged kernels with a
WINDOW against a dense masked softmax, the cache of two geometries (the
window layers' keys and values in a ring) and its manager, the int8
control on what a ring holds, and the old trees' seeds. What every
served block is held to (the engine against the plain reference
``benchmark/reference_trinity.py``: every position against every key, no
cache, no ring, no chunks; its refusals) is the contract's
(``test_served_block_contract.py``), on this block's row of
``served_blocks.py``, where the limits are justified.

A kernel against a dense masked softmax, both float32: 2e-5 of the
largest output (they read 3e-6 and under). An int8 pool (the cell's
control) reads 5e-2 to 1.4e-1.
"""

import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.config_v2 import DSStateManagerConfig
from deepspeed_tpu.inference.v2.kernels.ragged_attention import (
    ragged_attention, ragged_attention_reference)
from deepspeed_tpu.inference.v2.paged_model import (_layer_runs,
                                                    init_paged_kv_cache)
from deepspeed_tpu.inference.v2.ragged.ragged_manager import DSStateManager
from deepspeed_tpu.inference.v2.scheduler import DynamicSplitFuseScheduler
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.telemetry import get_registry, trace
from tests.unit.inference import served_block_contract as contract
from tests.unit.inference import served_blocks as sb
from tests.unit.inference.served_blocks import F32 as F32_TIGHT, err as _err

BLOCK = sb.BLOCKS["trinity-mini"]
globals().update(contract.clauses(BLOCK))     # the contract's cases of this row
TOY, reference_trinity = BLOCK.toy, BLOCK.reference
WINDOW = TOY["attn_window"]                 # 16


# ---------------------------------------------------------------------------
# (a) the pattern, and the steps of a call (blocks of 8: a row's share of a
# step of 32 tokens is 8, half the window; its ring the window, that share
# and one block)
# ---------------------------------------------------------------------------
def test_the_pattern_is_walked_as_runs_of_one_mixer_and_one_mlp():
    cfg = TransformerConfig(**TOY)
    assert cfg.layer_kinds == ("window", "window", "full", "window",
                               "window")
    assert cfg.walks_runs and cfg.pattern and not cfg.has_state
    # dense + sliding x1; expert + sliding x1; expert + full x1;
    # expert + sliding x2
    assert _layer_runs(cfg) == [("window", False, 0, 1),
                                ("window", True, 1, 1),
                                ("full", True, 2, 1),
                                ("window", True, 3, 2)]
    for what in ("layer_types", "qk_norm", "attn_gate", "rope_sliding_only",
                 "norm_scheme='sandwich'", "moe_shared_experts"):
        assert what in cfg.served_only
    with pytest.raises(NotImplementedError, match="served by"):
        TransformerLM(cfg).forward_logits(
            TransformerLM(cfg).init_params(jax.random.PRNGKey(0)),
            jnp.zeros((1, 8), jnp.int32))


def _ring_of_a_call(eng, lengths):
    prompts = sb.prompts(BLOCK, lengths)
    eng.generate(prompts, max_new_tokens=2, temperature=0.0,
                 eos_token_id=None)                # compiles
    trace.clear()
    eng.generate(prompts, max_new_tokens=2, temperature=0.0,
                 eos_token_id=None)
    return sorted(trace.export(), key=lambda s: s["start"])


@pytest.fixture(scope="module")
def chunked_call(served):
    """The ring of one ``generate()`` whose prompts go in in three chunk
    steps (one engine for the cases below)."""
    return _ring_of_a_call(served.lend(), (24, 17))   # a row's share: 8


@pytest.fixture(scope="module")
def one_step_call(served):
    """The ring of one ``generate()`` whose prompts fit one step."""
    return _ring_of_a_call(served.lend(), (5, 7, 6))


@pytest.mark.parametrize("what", ["one a chunk step", "a leaf of the call",
                                  "behind the step's bookkeeping"])
def test_put_chunk_is_puts_own_work_behind_a_chunk_step(chunked_call, what):
    """``_put_chunks`` notes which rows a step ended (and behind the
    last step brings their logits together, on the device) under a leaf
    of its own, ``put_chunk``: no part of a chunked ``put()`` runs
    outside a leaf, and the next step's pack follows at once: nothing
    waits for the step just launched."""
    ring = chunked_call
    chunks = [s for s in ring if s["name"] == "put_chunk"]
    root, = (s for s in ring if s["name"] == "generate")
    if what == "one a chunk step":
        steps = [s for s in ring if s["name"] == "ragged_step"]
        assert len(chunks) == len(steps) == 3
        assert [(s["attrs"]["chunk"], s["attrs"]["chunks"])
                for s in steps] == [(0, 3), (1, 3), (2, 3)]
    elif what == "a leaf of the call":
        for s in chunks:
            assert s["parent"] == root["id"] and "attrs" not in s
            assert not any(c["parent"] == s["id"] for c in ring)
    else:
        names = [s["name"] for s in ring if s["parent"] == root["id"]]
        at = [i for i, n in enumerate(names) if n == "put_chunk"]
        assert [names[i - 1] for i in at] == ["ragged_bookkeeping"] * 3
        assert names[at[-1] + 1] == "gen_first_token"
        assert [names[i + 1] for i in at[:-1]] == ["ragged_pack"] * 2


@pytest.mark.parametrize("metric", [
    "gap_host_ms.gen", "gap_launch_ms.gen", "gap_upload_ms.gen",
    "gap_call_ms.gen", "gap_fetch_ms.gen"])
@pytest.mark.parametrize("call", ["chunked", "one step"])
def test_every_leaf_the_gap_metrics_read_is_still_emitted(
        chunked_call, one_step_call, call, metric):
    """A call whose prompt steps are launched ahead, and one whose
    prompts fit one step, still emit every leaf a ``gap_*`` metric's
    file lists but the per-token path's ``step_*`` (one that is gone
    reads null there, not 0.0), and each step's wait stands where the
    readers look for it: ``ragged_fetch`` inside the ``ragged_step``
    span of the step launched behind it (empty in a call's first), the
    last step's under ``gen_first_token``, where the first tokens
    arrive."""
    ring = chunked_call if call == "chunked" else one_step_call
    spec = json.loads((sb.REPO / "benchmark/layer_metrics"
                       / f"{metric}.json").read_text())["params"]
    listed = {name for key in ("spans", "host_spans", "launch_spans")
              for name in spec.get(key, ()) if not name.startswith("step_")}
    assert listed and listed <= {s["name"] for s in ring}
    steps = [s for s in ring if s["name"] == "ragged_step"]
    assert len(steps) == (3 if call == "chunked" else 1)
    for step in steps:
        inner = [s["name"] for s in ring if s["parent"] == step["id"]]
        assert inner == ["ragged_dispatch", "ragged_fetch"]


def test_a_mixed_step_decodes_some_rows_and_feeds_chunks_of_others(lend):
    """Row 0 is fed whole and decodes one token in the very step that
    feeds rows 1 and 2 their first chunks (8 tokens each and the decode
    row's one), and a put() of a decode token beside a long prompt runs
    the decode row in the first chunk step only."""
    eng = lend()
    a, b = sb.prompts(BLOCK, (40, 30))
    first = eng.put([0], [a])
    tok = int(np.argmax(first[0]))
    got = eng.put([0, 1], [[tok], b])
    assert _err(got[0], sb.reference(BLOCK, np.append(a, tok))[-1]) \
        <= F32_TIGHT
    assert _err(got[1], sb.reference(BLOCK, b)[-1]) <= F32_TIGHT
    assert eng.state_manager.seqs[0].seen_tokens == 41
    eng.flush(0), eng.flush(1)


def test_the_scheduler_keeps_to_a_rows_share_of_a_step(lend):
    """The SplitFuse scheduler's chunk is clipped to what the ring
    leaves room for, and its streams equal generate()'s."""
    eng = lend()
    prompts = sb.prompts(BLOCK, (50, 21))
    want = eng.generate(prompts, max_new_tokens=9, temperature=0.0,
                        eos_token_id=None)
    sched = DynamicSplitFuseScheduler(eng, chunk=64)
    assert sched.chunk == eng.max_row_chunk == 8
    for uid, p in enumerate(prompts):
        sched.submit(uid, p, max_new_tokens=9)
    sched.run()
    for uid, row in sched.results().items():
        np.testing.assert_array_equal(row, np.asarray(want[uid]))


def test_the_positions_counter_takes_a_window_layer_from_its_first_page(
        lend, monkeypatch):
    """``inference_attention_decode_positions_total`` over a pattern: a
    full layer's rows hold their whole context, a window layer's the
    pages from its window's first (window 16 over blocks of 8: two or
    three pages whatever the context), each kind over its own table;
    held <= chunked, and nothing on the CPU until the engine is told the
    one-token form serves."""
    from deepspeed_tpu.inference.v2 import engine_v2
    eng = lend()
    family = get_registry().get("inference_attention_decode_positions_total")
    held, chunked = family.labels(kind="held"), family.labels(kind="chunked")
    prompts = sb.prompts(BLOCK, (50, 21))
    before = held.value, chunked.value
    eng.generate(prompts, max_new_tokens=5, temperature=0.0,
                 eos_token_id=None)
    assert (held.value, chunked.value) == before             # the CPU
    monkeypatch.setattr(engine_v2, "one_token_tile_serves",
                        lambda *a: True)
    eng.generate(prompts, max_new_tokens=5, temperature=0.0,
                 eos_token_id=None)       # 4 decode steps a row
    kinds = eng.model.cfg.layer_kinds
    bounds = [len(p) + s + 1 for p in prompts for s in range(4)]
    full = sum(-(-n // 8) * 8 for n in bounds)
    ring = sum((-(-n // 8) - max(n - WINDOW, 0) // 8) * 8 for n in bounds)
    assert held.value - before[0] == \
        kinds.count("full") * full + kinds.count("window") * ring
    assert chunked.value - before[1] >= held.value - before[0]


# ---------------------------------------------------------------------------
# (b) the kernels with a window, against a dense masked softmax
# ---------------------------------------------------------------------------
def _kernel_case(window=64, seed=0):
    """Group 8 at head width 128 (32 query heads on 4 kv heads), pages
    of 16, three rows whose tables are RINGS of ``window + 48 + 16``
    positions: a chunk of 40 tokens at positions 200-239, a decode row
    at 333, and a fresh chunk of 23."""
    rng = np.random.default_rng(seed)
    nh, kvh, hd, bs, R = 32, 4, 128, 16, 3
    ring_blocks = (window + 48 + 16) // bs
    nb = 1 + R * ring_blocks
    k, v = (jnp.asarray(rng.normal(size=(2, nb, bs, kvh * hd)), jnp.float32)
            for _ in range(2))
    tables = jnp.arange(1, nb, dtype=jnp.int32).reshape(R, ring_blocks)
    row_ids, lengths = [], []
    for r, first, n in ((0, 200, 40), (1, 333, 1), (2, 0, 23)):
        row_ids += [r] * n
        lengths += list(range(first + 1, first + n + 1))
    T, live = 64, len(row_ids)
    q = jnp.asarray(rng.normal(size=(T, nh, hd)), jnp.float32)
    pad = [0] * (T - live)
    return (q, k, v, 1, jnp.asarray(row_ids + pad, jnp.int32),
            jnp.asarray(lengths + pad, jnp.int32), tables), live


def _dense_masked_softmax(q, k, v, layer, row_ids, lengths, tables, window):
    """By hand: every position put back in order from the ring, then a
    softmax over j <= p and j > p - window."""
    bs, hd = k.shape[2], q.shape[-1]
    ring = tables.shape[1] * bs
    out = np.zeros(q.shape, np.float32)
    for t in range(q.shape[0]):
        n = int(lengths[t])
        if not n:
            continue
        pos = np.arange(max(0, n - window), n)
        place = pos % ring
        pages = np.asarray(tables)[int(row_ids[t]), place // bs]
        kk = np.asarray(k)[layer, pages, place % bs].reshape(len(pos), -1, hd)
        vv = np.asarray(v)[layer, pages, place % bs].reshape(len(pos), -1, hd)
        group = q.shape[1] // kk.shape[1]
        for h in range(q.shape[1]):
            s = kk[:, h // group] @ np.asarray(q)[t, h] / hd ** 0.5
            p = np.exp(s - s.max())
            out[t, h] = (p / p.sum()) @ vv[:, h // group]
    return out


@pytest.mark.parametrize("variant", ["tiled", "pipelined"])
def test_the_kernel_with_a_window_against_a_dense_masked_softmax(variant):
    """Tiled (under the TPU interpreter: DMAs, semaphores and all) and
    pipelined, group 8: the walk over a ring from the window's first
    page, decode rows and prompt chunks in one launch."""
    args, live = _kernel_case()
    want = _dense_masked_softmax(*args, window=64)
    got = ragged_attention(*args, variant=variant, window=64)
    assert np.abs(np.asarray(got) - want)[:live].max() \
        <= F32_TIGHT * np.abs(want).max()
    ref = ragged_attention_reference(*args, window=64)
    assert np.abs(np.asarray(ref) - want)[:live].max() \
        <= F32_TIGHT * np.abs(want).max()


@pytest.mark.parametrize("variant", ["tiled", "pipelined"])
def test_a_window_that_holds_the_context_is_no_window_bit_for_bit(variant):
    """``window >= context`` over a table that holds every position
    equals ``window=0``, today's kernel, to the last bit."""
    (q, k, v, layer, row_ids, _, tables), live = _kernel_case()
    lengths = []
    for first, n in ((60, 40), (99, 1), (0, 23)):
        lengths += list(range(first + 1, first + n + 1))
    lengths = jnp.asarray(lengths + [0] * (64 - live), jnp.int32)
    args = (q, k, v, layer, row_ids, lengths, tables)
    plain = ragged_attention(*args, variant=variant)
    wide = ragged_attention(*args, variant=variant, window=4096)
    np.testing.assert_array_equal(np.asarray(plain)[:live],
                                  np.asarray(wide)[:live])


def test_the_one_token_form_over_a_ring_that_wraps():
    """A decode batch through the window launch's one-token form: group
    8 at width 128, rings of 8 pages (128 positions) under a window of
    64, contexts that have gone round the ring many times, one inside
    its first lap, one of no length, more rows than one grid step
    walks; against the token tile on the same inputs and the gathering
    reference."""
    rng = np.random.default_rng(5)
    nh, kvh, hd, bs, ring = 16, 2, 128, 16, 8
    lens = [333, 90, 1000, 0, 64, 65, 5000] + [777] * 11
    R = len(lens)
    nb = 1 + R * ring
    k, v = (jnp.asarray(rng.normal(size=(2, nb, bs, kvh * hd)), jnp.float32)
            for _ in range(2))
    tables = jnp.asarray(rng.permutation(np.arange(1, nb)).reshape(R, ring),
                         jnp.int32)
    args = (jnp.asarray(rng.normal(size=(R, nh, hd)), jnp.float32), k, v, 1,
            jnp.arange(R, dtype=jnp.int32), jnp.asarray(lens, jnp.int32),
            tables)
    tile, one = (np.asarray(ragged_attention(
        *args, variant="tiled", window=64, one_token=flag))
        for flag in (False, True))
    want = np.asarray(ragged_attention_reference(*args, window=64))
    assert np.abs(one - want).max() <= F32_TIGHT * np.abs(want).max()
    assert np.abs(one - tile).max() <= F32_TIGHT * np.abs(want).max()
    assert not one[3].any()


@pytest.mark.parametrize("against", ["reference", "parent"])
def test_a_window_launch_over_partial_chunks_of_a_wrapped_ring(against):
    """PR 50 over rings (``walk_cases``' ``window-ring``: rings of 40
    pages under a window of 520, contexts inside the first lap whose
    only chunk holds 1, 2, 3, 31 and 32 pages and contexts far round
    the ring whose 33 or 34 pages are a whole chunk and a page or two):
    a place is found by one remainder a chunk and a subtraction a page,
    the chunk waited for by its bytes; against the gathering reference and against the parent's
    output on the same inputs to the bit."""
    from tests.unit.inference import walk_cases
    got = walk_cases.output("window-ring")
    if against == "parent":
        np.testing.assert_array_equal(
            got, walk_cases.parent_output("window-ring"))
        return
    want = walk_cases.reference("window-ring")
    assert np.abs(got - want).max() <= F32_TIGHT * np.abs(want).max()
    lens, _ = walk_cases.lengths("window-ring")
    assert not got[lens == 0].any()


def test_the_tiled_kernel_refuses_a_window_over_an_int8_pool():
    (q, k, v, layer, row_ids, lengths, tables), _ = _kernel_case()
    scale = jnp.ones((k.shape[1], 4), jnp.float32)
    with pytest.raises(NotImplementedError, match="dequantise the layer"):
        ragged_attention(q, k.astype(jnp.int8), v.astype(jnp.int8), layer,
                         row_ids, lengths, tables, k_scale=scale,
                         v_scale=scale, variant="tiled", window=64)


# ---------------------------------------------------------------------------
# (c) the cache of two geometries and its manager
# ---------------------------------------------------------------------------
def test_a_leaf_a_kind_and_the_window_leaf_a_ring():
    cfg = TransformerConfig(**TOY)
    cache = jax.eval_shape(lambda: init_paged_kv_cache(
        cfg, 100, 8, jnp.float32, window_blocks=17))
    F = TOY["num_kv_heads"] * TOY["head_dim_override"]
    assert {k: v.shape for k, v in cache.items()} == {
        "k_full": (1, 100, 8, F), "v_full": (1, 100, 8, F),
        "k_window": (4, 17, 8, F), "v_window": (4, 17, 8, F)}
    quant = jax.eval_shape(lambda: init_paged_kv_cache(
        cfg, 100, 8, jnp.float32, kv_quant=True, window_blocks=17))
    assert quant["k_window"].dtype == jnp.int8 \
        and quant["ks_window"].shape == (4, 17, TOY["num_kv_heads"]) \
        and quant["vs_full"].shape == (1, 100, TOY["num_kv_heads"])
    eng = sb.engine(BLOCK)      # its own: the gauge is the last one built's
    bytes_of = {kind: sum(int(np.prod(v.shape)) * 4
                          for k, v in eng.kv_cache.items()
                          if k.endswith("_" + kind))
                for kind in ("full", "window")}
    fam = get_registry().get("inference_kv_pool_bytes")
    assert {labels[0]: s.value for labels, s in fam.series()} == bytes_of


def test_ring_blocks_never_pass_rows_x_ring_and_both_pools_empty_after_flush(
        lend):
    eng = lend()
    sm = eng.state_manager
    ring = sm.ring_blocks                          # 4 blocks of 8
    assert sm.window_allocator.num_blocks == 4 * ring + 1
    prompts = sb.prompts(BLOCK, (50, 70, 80, 20))
    free0 = sm.allocator.free_blocks
    reused = get_registry().family_total(
        "inference_window_blocks_reused_total")
    outs = eng.generate(prompts, max_new_tokens=30, temperature=0.0,
                        eos_token_id=None, keep_sequences=True)
    assert len(outs) == 4
    assert get_registry().family_total(
        "inference_window_blocks_reused_total") > reused
    in_use = get_registry().get("inference_kv_blocks_in_use")
    used = {labels[0]: s.value for labels, s in in_use.series()}
    assert sm.window_blocks_in_use() == used["window"] == 4 * ring
    assert used["full"] == sum(-(-(len(o) - 1) // 8) for o in outs)
    for uid, seq in sm.seqs.items():
        assert len(seq.window_blocks) == ring, uid
    assert eng.query(0)["free_window_blocks"] == 0
    # the ring holds the last positions, in order, of the right tokens
    kv = eng.sequence_kv(2)
    n = len(outs[2]) - 1
    assert kv["positions"][-1] == n - 1 and len(kv["positions"]) >= WINDOW
    want_k, want_v = reference_trinity.leading_kv(sb.params(BLOCK), TOY,
                                                  outs[2][:-1])
    at = kv["positions"]
    for got, want in ((kv["k"], want_k), (kv["v"], want_v)):
        want = np.asarray(want)[:, at]
        assert np.linalg.norm(got[:2] - want) / np.linalg.norm(want) \
            <= F32_TIGHT
    full = eng.sequence_kv(2, "full")
    assert len(full["positions"]) == n and full["k"].shape[0] == 1
    for uid in range(4):
        eng.flush(uid)
    assert sm.window_blocks_in_use() == 0
    assert sm.window_allocator.free_blocks == 4 * ring
    assert sm.allocator.free_blocks == free0


def test_can_schedule_counts_both_geometries(lend):
    """A manager whose full pool is ample and whose rings are taken
    refuses a new sequence's tokens, and a row of more than its share of
    a step is no single step."""
    sm = DSStateManager(DSStateManagerConfig(
        max_tracked_sequences=2, max_ragged_batch_size=64, max_seq_len=256,
        num_blocks=200, block_size=8), window_ring=32)
    assert sm.ring_blocks == 4 and sm.window_allocator.free_blocks == 8
    sm.ensure_blocks(0, 100)
    assert len(sm.seqs[0].window_blocks) == 4 and len(sm.seqs[0].blocks) == 13
    assert sm.can_schedule(1, 100)
    sm.window_allocator.allocate(3)             # someone else's
    assert not sm.can_schedule(1, 100) and sm.can_schedule(1, 8)
    with pytest.raises(NotImplementedError, match="no ring"):
        sm.adopt_sequence(5, 2, 10, [0] * 10)
    with pytest.raises(ValueError, match="whole blocks"):
        DSStateManager(DSStateManagerConfig(block_size=8), window_ring=20)
    eng = lend()
    assert eng.can_schedule([0], [8]) and not eng.can_schedule([0], [9])
    with pytest.raises(RuntimeError, match="not schedulable"):
        eng.put([0], [np.zeros(200, np.int64)])      # over max_seq_len
    assert eng.state_manager.tracked_sequences() == 0


# ---------------------------------------------------------------------------
# (d) what the block's parts do not describe
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fields,error", [
    ({"layer_types": ["full_attention"] * 4}, ValueError),
    ({"layer_types": ["sliding_attention", "global"] + ["full_attention"] * 3},
     ValueError),
    ({"attn_window": 0}, ValueError),
    ({"positional": "learned"}, NotImplementedError),
    ({"layer_types": None}, NotImplementedError),
], ids=["a-kind-a-layer", "an-unknown-kind", "no-window", "not-rope",
        "parts-without-a-pattern"])
def test_the_blocks_parts_describe_a_pattern_or_are_refused(fields, error):
    with pytest.raises(error):
        TransformerConfig(**{**TOY, **fields})


# ---------------------------------------------------------------------------
# (e) the control, and what stays as it was
# ---------------------------------------------------------------------------
def test_an_int8_pool_in_both_leaves_is_the_lower_precision_control(lend):
    """``kv_quant``: int8 keys and values in BOTH leaves (a layer
    dequantised at a time for the kernel). The same prompts read far
    over the float32 limit, and what a ring holds is off by percents."""
    prompts = sb.prompts(BLOCK, (50, 70))
    eng = lend(kv_quant=True)
    assert eng.kv_cache["k_window"].dtype == jnp.int8 \
        and eng.kv_cache["k_full"].dtype == jnp.int8
    outs = eng.generate(prompts, max_new_tokens=4, temperature=0.0,
                        eos_token_id=None, keep_sequences=True)
    kv = eng.sequence_kv(1)
    want_k, want_v = reference_trinity.leading_kv(sb.params(BLOCK), TOY,
                                                  outs[1][:-1])
    want = np.asarray(want_v)[:, kv["positions"]]
    assert np.linalg.norm(kv["v"][:2] - want) / np.linalg.norm(want) > 5e-3
    for uid in range(2):
        eng.flush(uid)
    got = eng.put([0, 1], prompts)
    assert max(_err(got[i], sb.reference(BLOCK, p)[-1])
               for i, p in enumerate(prompts)) > 100 * F32_TIGHT
    for uid in range(2):
        eng.flush(uid)


def test_the_old_trees_seeded_sums_are_unchanged():
    """The parameter trees that existed are seeded as they were (sums
    read at commit 63bfad3, ``PRNGKey(3)``): the per-head block's one
    stack and the latent block's two."""
    per_head = TransformerLM(TransformerConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=2,
        num_heads=4, max_seq_len=32)).init_params(jax.random.PRNGKey(3))
    latent = TransformerLM(TransformerConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=3,
        num_heads=4, max_seq_len=32, attention="mla", q_lora_rank=16,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
        v_head_dim=8, moe_num_experts=4, moe_top_k=2,
        moe_intermediate_size=16, moe_first_dense_layers=1,
        moe_shared_experts=1, moe_scoring="sigmoid",
        moe_selection_bias=True)).init_params(jax.random.PRNGKey(3))
    sums = {name: float(np.asarray(leaf, np.float64).sum())
            for tree, tag in ((per_head, "mha"), (latent, "mla"))
            for name, leaf in (
                (f"{tag}/{'/'.join(str(getattr(k, 'key', k)) for k in p)}",
                 x) for p, x in jax.tree_util.tree_leaves_with_path(tree))}
    want = json.loads((Path(__file__).parent
                       / "seeded_sums_63bfad3.json").read_text())
    assert sums.keys() == want.keys()
    for name, value in want.items():
        assert sums[name] == value, name
