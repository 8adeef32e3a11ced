"""The ``dots3_note`` block served at toy widths on the CPU against the
benchmark's plain float32 reference (``benchmark/reference_dots3.py``):
full latent layers that read only the positions an indexer picks beside
latent layers of another head count and rank over a ring, a share of
the experts held.

Every case reads ``benchmark/configs/dots3-note-prev.json`` (its fields
at its toy widths) and serves through ``InferenceEngineV2``: the prompt
in chunks, then decode. What only this block has: the selected SETS
(compared as sets, not through logits), contexts under ``index_topk``
that must be the dense result, the ring of latent rows turning over,
the leaves a kind, the shares adding up, the tree's count at published
widths, and what the engine and the configuration refuse.
"""

import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import reference_dots3 as reference
from benchmark import weights_dots3 as weights
from benchmark.runners.generate_sparse import half_split
from deepspeed_tpu.inference.v2 import InferenceEngineV2, paged_model
from deepspeed_tpu.models import TransformerConfig, TransformerLM

REPO = Path(__file__).resolve().parents[3]
CONFIG = json.loads(
    (REPO / "benchmark/configs/dots3-note-prev.json").read_text())
TOY = {**CONFIG["fields"], **CONFIG["toy_fields"]}
SEED = 6800000001
# a float32 engine differs from the reference by the order of its sums
# and by its form (absorbed against expanded): served_blocks.F32
F32 = 2e-5
PROMPT, NEW, BLOCK = 96, 24, 8
MANAGER = {"max_tracked_sequences": 4, "max_ragged_batch_size": 64,
           "max_seq_len": 160, "block_size": BLOCK, "num_blocks": 81}


def build(fields=None, program=None, **options):
    """An engine of the toy: ``fields`` laid on the model and its
    weights, ``program`` on the program alone."""
    made = {**TOY, **(fields or {})}
    cfg = TransformerConfig(**{**made, **(program or {})})
    manager = {**MANAGER, **options.pop("state_manager", {})}
    return InferenceEngineV2(
        TransformerLM(cfg),
        {"dtype": "float32", "use_paged_kernel": True, **options,
         "state_manager": manager},
        params=weights.make(made, SEED, jnp.float32))


@pytest.fixture(scope="module")
def params():
    return weights.make(TOY, SEED, jnp.float32)


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(SEED)
    return [rng.integers(0, TOY["vocab_size"], PROMPT) for _ in range(4)]


@pytest.fixture(scope="module")
def served(prompts):
    """One engine, the prompts fed in chunks (96 tokens a row under a
    budget of 64: 16 a row a step) and decoded, the sequences kept."""
    eng = build()
    logits = np.asarray(eng.put(list(range(4)), list(prompts)))
    for uid in range(4):
        eng.flush(uid)
    outs = eng.generate(list(prompts), max_new_tokens=NEW, temperature=0.0,
                        eos_token_id=None, keep_sequences=True)
    return eng, logits, [np.asarray(o) for o in outs]


def _err(got, want):
    want = np.asarray(want, np.float32)
    return float(np.abs(np.asarray(got, np.float32) - want).max()
                 / np.abs(want).max())


# ---------------------------------------------------------------------------
# the served path against the reference
# ---------------------------------------------------------------------------
def test_the_prompt_in_chunks_then_decode_is_the_reference(
        served, params, prompts):
    """Logits of the chunked prompt at 1e-4, and every decoded token is
    the reference's best at its position, over contexts of 3 x
    ``index_topk`` and 7 windows."""
    eng, logits, outs = served
    assert eng.attention_impl == "pallas:latent+window+indexed"
    for row in (0, 3):
        want = reference.logits(params, TOY, prompts[row])[-1]
        assert _err(logits[row], want) <= 1e-4
        lg = np.asarray(reference.logits(params, TOY, outs[row][:-1]))
        at = lg[PROMPT - 1:]
        gap = (at.max(-1) - at[np.arange(len(at)), outs[row][PROMPT:]]) \
            / np.abs(at).max(-1)
        assert gap.max() <= F32
    assert all(len(o) == PROMPT + NEW for o in outs)


def test_contexts_under_index_topk_are_the_dense_result(params):
    """A short row beside a long one rides tables wider than
    ``index_topk``, so its tokens go through the indexer and pick every
    position under their bound; served alone its tables hold no more
    than ``index_topk`` and the launch is the dense one. Both are the
    reference's logits."""
    rng = np.random.default_rng(3)
    short = rng.integers(0, TOY["vocab_size"], TOY["index_topk"] - 8)
    long = rng.integers(0, TOY["vocab_size"], 64)
    eng = build()
    beside = np.asarray(eng.put([0, 1], [long, short]))[1]
    for uid in (0, 1):
        eng.flush(uid)
    read = eng._m_index_read.labels(program="ragged_step").value
    alone = np.asarray(eng.put([0], [short]))[0]
    # the dense launch reads every position under each token's bound
    n = len(short)
    assert eng._m_index_read.labels(program="ragged_step").value - read \
        == 2 * n * (n + 1) // 2
    want = reference.logits(params, TOY, short)[-1]
    assert _err(beside, want) <= F32 and _err(alone, want) <= F32
    assert _err(beside, alone) <= F32


def test_contexts_over_index_topk_read_exactly_the_references_sets(
        served, params):
    """The SETS, not the logits: at positions past ``index_topk``, in
    the prompt's chunks and among the decoded tokens, the program's own
    query path and selection against the index keys the sequence cached
    pick the positions the reference's whole masked matrix and
    ``jax.lax.top_k`` pick; the cached rows and keys are the
    reference's."""
    eng, _, outs = served
    cfg = eng.model.cfg
    dc = TOY["kv_lora_rank"]
    probe = [40, 77, 95, 100, PROMPT + NEW - 2]
    for row in (1, 2):
        fed = outs[row][:-1]
        want = reference.leading_layers(params, TOY, fed, 2, probe=probe)
        held = eng.sequence_kv(row, "full")
        assert held["positions"][0] == 0 \
            and held["positions"][-1] == len(fed) - 1
        for layer, ref in enumerate(want):
            # (the program stores the rotated lanes half-split)
            rows = half_split(np.asarray(ref["rows"]), dc)
            assert _err(held["latent"][layer][:, :rows.shape[1]],
                        rows) <= F32
            assert not held["latent"][layer][:, rows.shape[1]:].any()
            assert _err(held["index_k"][layer], ref["index_k"]) <= F32
            lp = jax.tree.map(lambda a: a[layer],
                              eng.params["mla_layers"])
            table = jnp.asarray(eng.state_manager.block_table_for(row),
                                jnp.int32)
            for form in (False, True):      # a prompt's launch, a decode's
                mine = np.asarray(paged_model.index_picks(
                    cfg, lp, jnp.asarray(np.asarray(ref["x"])[probe]),
                    jnp.asarray(probe, jnp.int32), eng.kv_cache["index_k"],
                    jnp.int32(layer), table, form))
                for j, t in enumerate(probe):
                    assert mine[j].sum() == TOY["index_topk"]
                    np.testing.assert_array_equal(
                        np.flatnonzero(mine[j]),
                        np.flatnonzero(np.asarray(ref["picked"][j])),
                        err_msg=str((row, layer, t, form)))


def test_the_flags_are_the_sorted_selection_ties_and_short_rows_too():
    """``index_mask`` (no sort: the k-th largest score by its bits, ties
    at it filled in position order) is ``index_select`` (``top_k``) as a
    set, on scores with many exact ties, a bound under ``topk`` and a
    padding token."""
    rng = np.random.default_rng(5)
    t, ctx, heads, d, topk = 6, 64, 2, 8, 16
    keys = np.zeros((1, ctx // 8, 8, d), np.float32)
    keys[0, :, :, 0] = rng.integers(-2, 3, (ctx // 8, 8))   # five values
    qi = np.zeros((t, heads, d), np.float32)
    qi[:, :, 0] = 1.0
    wi = np.ones((t, heads), np.float32)
    wi[1] = -1.0                        # negative scores and zeros
    table = jnp.arange(ctx // 8, dtype=jnp.int32)[None]
    rows = jnp.zeros((t,), jnp.int32)
    bounds = jnp.asarray([64, 64, 40, 16, 5, 0], jnp.int32)
    args = (jnp.asarray(qi), jnp.asarray(wi), jnp.asarray(keys),
            jnp.int32(0), rows, bounds, table, topk)
    idx, ok = paged_model.index_select(*args)
    flags = np.asarray(paged_model.index_mask(*args))
    for i, bound in enumerate(np.asarray(bounds)):
        want = sorted(np.asarray(idx[i])[np.asarray(ok[i])].tolist())
        assert np.flatnonzero(flags[i]).tolist() == want, i
        assert len(want) == min(bound, topk)


def test_the_ring_of_latent_rows_turns_over(served, params):
    """A context of 119 positions over a ring of 48 (the window 17 to
    whole blocks, a row's chunk 16, one block): the ring's blocks were
    written over, it holds the last positions in order, and they are the
    reference's rows of the sliding layers ahead of any doubt (layer 2,
    behind two full layers and one expert layer, at 1e-4)."""
    eng, _, outs = served
    sm = eng.state_manager
    assert sm.ring_blocks * BLOCK == 24 + 16 + 8
    assert sm._m_ring_reused.value > 0
    ring = eng.sequence_kv(0, "window")
    pos = ring["positions"]
    assert pos[-1] == PROMPT + NEW - 2 and (np.diff(pos) == 1).all()
    assert len(pos) >= TOY["attn_window"]
    assert ring["latent_window"].shape[0] == 3
    kept = reference.leading_layers(params, TOY, outs[0][:-1], 3)
    dc = TOY["swa_kv_lora_rank"]
    rows = half_split(np.asarray(kept[2]["rows"])[pos], dc)
    assert _err(ring["latent_window"][0][:, :rows.shape[1]], rows) <= 1e-4


def test_a_leaf_a_latent_kind_and_the_index_leaf(served):
    eng, _, _ = served
    cache = eng.kv_cache
    sm = eng.state_manager
    assert set(cache) == {"latent", "latent_window", "index_k"}
    assert cache["latent"].shape == (2, 81, BLOCK, 128)       # 32 + 16
    assert cache["index_k"].shape == (2, 81, BLOCK, 32)
    assert cache["latent_window"].shape == (
        3, 4 * sm.ring_blocks + 1, BLOCK, 128)                # 48 + 16
    cfg = eng.model.cfg
    assert cfg.layer_kinds == ("mla", "mla", "mla_window", "mla_window",
                               "mla_window")
    assert cfg.latent_kind().heads == 4 \
        and cfg.latent_kind("mla_window").heads == 2
    assert paged_model.latent_pool_row(cfg, "mla_window") == 128
    tree = eng.params
    assert tree["mla_layers"]["wg"].shape == (2, 64, 4)
    assert tree["mla_window_layers"]["wg"].shape == (3, 64, 2)
    assert tree["mla_window_layers"]["wkv_b"].shape == (3, 48, 2 * 40)
    assert "index_wq" not in tree["mla_window_layers"]
    assert tree["mla_layers"]["index_wq"].shape == (2, 32, 4 * 32)
    assert tree["layers"]["e_gate"].shape[:2] == (4, 4)       # 4 of 16


def test_the_counters_of_the_selection():
    """A query a token and full layer; it attends min(bound,
    ``index_topk``) positions; a decode step reads as many, a prompt's
    launch its whole bound (the picks are a mask there). Read as what
    ONE call adds: the registry is the process's (an engine of its own:
    the lent one keeps its sequences)."""
    eng = build()
    topk, n, new, rows, layers = TOY["index_topk"], 40, 5, 2, 2
    families = {"queries": eng._m_index_queries,
                "attended": eng._m_index_attended,
                "read": eng._m_index_read, "scored": eng._m_index_scored}

    def totals():
        return {(k, p): f.labels(program=p).value
                for k, f in families.items()
                for p in ("ragged_step", "decode")}
    before = totals()
    rng = np.random.default_rng(7)
    eng.generate([rng.integers(0, TOY["vocab_size"], n) for _ in range(rows)],
                 max_new_tokens=new, temperature=0.0, eos_token_id=None)
    added = {k: v - before[k] for k, v in totals().items()}
    bounds = np.arange(1, n + 1)
    each = rows * layers
    assert added["queries", "ragged_step"] == each * n
    assert added["attended", "ragged_step"] \
        == each * np.minimum(bounds, topk).sum()
    assert added["read", "ragged_step"] == each * bounds.sum() \
        == added["scored", "ragged_step"]
    steps = new - 1                     # the last token is never fed
    assert added["queries", "decode"] == each * steps
    assert added["read", "decode"] == added["attended", "decode"] \
        == topk * each * steps
    assert added["scored", "decode"] == each * sum(
        n + 1 + i for i in range(steps))


# ---------------------------------------------------------------------------
# the latent kernel's two new forms under the TPU interpreter
# ---------------------------------------------------------------------------
def _mixed_launch(ring=0):
    """A prompt chunk from an empty row, a continuation, a long row and a
    decode row in one launch over a latent pool, padding tokens behind;
    ``ring``: the rows' tables are rings of that many places."""
    nh, dc, dr, L, bs, W = 4, 32, 16, 3, 8, 128
    rng = np.random.default_rng(0)
    rows = [(0, 11), (9, 5), (60, 7), (16, 1)]      # (cached, new tokens)
    places = ring or 9
    nb = 1 + len(rows) * places
    pool = np.zeros((L, nb, bs, W), np.float32)
    pool[..., :dc + dr] = rng.normal(size=(L, nb, bs, dc + dr))
    bt = rng.permutation(np.arange(1, nb)).reshape(
        len(rows), places).astype(np.int32)
    row_ids = [r for r, (c, n) in enumerate(rows) for _ in range(n)]
    lengths = [c + i + 1 for c, n in rows for i in range(n)]
    T0, T = len(row_ids), 32
    row_ids = np.array(row_ids + [0] * (T - T0), np.int32)
    lengths = np.array(lengths + [0] * (T - T0), np.int32)
    q = np.zeros((nh, T, W), np.float32)
    q[..., :dc + dr] = rng.normal(size=(nh, T, dc + dr))
    return (jnp.asarray(q), jnp.asarray(pool), jnp.int32(1),
            jnp.asarray(row_ids), jnp.asarray(lengths),
            jnp.asarray(bt)), dc, T0


def test_the_latent_kernel_over_a_ring_matches_the_gather():
    """A window of 13 over rings of 5 pages of 8: the kernel's walk
    starts at the window's first page and turns the ring (a row of 67
    positions holds its last 40), DMAs and semaphores included."""
    from deepspeed_tpu.inference.v2.kernels.ragged_attention import (
        latent_attention, latent_attention_reference)
    args, dc, T0 = _mixed_launch(ring=5)
    want = latent_attention_reference(*args, dc=dc, scale=0.2, window=13)
    got = latent_attention(*args, dc=dc, scale=0.2, interpret=True,
                           window=13)
    np.testing.assert_allclose(np.asarray(got)[:, :T0],
                               np.asarray(want)[:, :T0], atol=2e-6)
    assert not np.asarray(got)[:, T0:].any()
    whole = latent_attention_reference(*args, dc=dc, scale=0.2)
    assert np.abs(np.asarray(whole) - np.asarray(want))[:, :T0].max() > 1e-3


def test_the_latent_kernel_reads_what_each_token_picked():
    """``picked`` flags a token and position, laid on the causal mask: a
    chunk's flags ride in beside its pages. Against the gather; with
    every position picked it is the dense launch to the bit."""
    from deepspeed_tpu.inference.v2.kernels.ragged_attention import (
        latent_attention, latent_attention_reference)
    args, dc, T0 = _mixed_launch()
    ctx = args[5].shape[1] * 8
    # (position 0 picked by all: a token attends something, as the top
    # of its scores always is)
    picked = np.random.default_rng(1).random((args[0].shape[1], ctx)) < 0.4
    picked[:, 0] = True
    picked = jnp.asarray(picked)
    want = latent_attention_reference(*args, dc=dc, scale=0.2,
                                      picked=picked)
    got = latent_attention(*args, dc=dc, scale=0.2, interpret=True,
                           picked=picked)
    np.testing.assert_allclose(np.asarray(got)[:, :T0],
                               np.asarray(want)[:, :T0], atol=2e-6)
    dense = latent_attention(*args, dc=dc, scale=0.2, interpret=True)
    every = latent_attention(*args, dc=dc, scale=0.2, interpret=True,
                             picked=jnp.ones_like(picked))
    np.testing.assert_array_equal(np.asarray(every), np.asarray(dense))
    assert np.abs(np.asarray(got) - np.asarray(dense))[:, :T0].max() > 1e-3


# ---------------------------------------------------------------------------
# the cut
# ---------------------------------------------------------------------------
def test_the_shares_add_up_to_the_uncut_layer():
    """The guide's test of the cut: 16 experts over four chips of 4.
    Each chip's expert layer (the program's, told which experts it
    holds) routes over all 16 and computes its own experts' part and
    the shared expert; the four parts, with the shared expert counted
    ONCE, are what the uncut reference layer gives with all 16, and each
    part is the reference's given the same share."""
    fields = {**TOY, "moe_experts_held": 0, "moe_experts_first": 0}
    whole = weights.make(fields, SEED, jnp.float32)["layers"]
    assert whole["e_gate"].shape[1] == 16
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (37, TOY["hidden_size"])), jnp.float32)
    experts = ("e_gate", "e_up", "e_down")
    with jax.default_matmul_precision("highest"):
        uncut = reference._expert_mlp(x, whole, 1, fields) - x
        small = {k: v[1] for k, v in whole.items() if k not in experts}
        hn = reference._rms_norm(x, small["mlp_norm"], TOY["norm_eps"])
        shared = reference._swiglu(hn, small["shared_gate"],
                                   small["shared_up"], small["shared_down"])
        parts = []
        for chip in range(4):
            cfg = TransformerConfig(**{**TOY, "moe_experts_held": 4,
                                       "moe_experts_first": 4 * chip})
            held = tuple(whole[k][:, 4 * chip:4 * chip + 4]
                         for k in experts)
            out, _ = paged_model._moe_routed(
                cfg, small, hn, held, 1,
                router_precision=jax.lax.Precision.HIGHEST)
            parts.append(out - shared)
            ref = reference._expert_mlp(x, whole, 1, fields,
                                        held=(4 * chip, 4)) - x
            np.testing.assert_allclose(out, ref, atol=2e-5)
        np.testing.assert_allclose(sum(parts) + shared, uncut, atol=2e-5)
    assert max(float(jnp.abs(p).max()) for p in parts) > 0.01


def test_the_tree_at_published_widths_counts_4087_million():
    """8.17 GB of bf16 weights, counted from ``fields``."""
    cfg = TransformerConfig(**CONFIG["fields"])
    tree = jax.eval_shape(TransformerLM(cfg).init_params,
                          jax.random.PRNGKey(0))

    def count(t, layer=False):
        return sum(int(np.prod(a.shape[1:] if layer else a.shape))
                   for a in jax.tree.leaves(t))
    assert count(tree) == 4_087_154_176
    assert count(tree["mla_layers"], True) == 144_055_040
    assert count({k: v for k, v in tree["mla_layers"].items()
                  if k.startswith("index_")}, True) == 9_371_904
    assert count(tree["mla_window_layers"], True) == 90_840_064
    assert count(tree["lead_layers"]) == 212_341_760
    assert count(tree["embed"]) + count(tree["lm_head"]) == 194_641_920
    # the seeded tree is the program's, leaf for leaf
    made = weights.shapes(CONFIG["fields"])
    flat = {**made.pop("top"), **made}
    assert jax.tree.map(lambda a: a.shape, tree) == {
        k: (v[0] if isinstance(v, tuple) else {n: s[0] for n, s in v.items()})
        for k, v in flat.items()}


# ---------------------------------------------------------------------------
# what is refused
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("options,says", [
    ({"state_manager": {"enable_prefix_caching": True}},
     "enable_prefix_caching"),
    ({"state_manager": {"enable_prefix_caching": True,
                        "enable_kv_spill": True}}, "enable_kv_spill"),
    ({"kv_quant": True}, "kv_quant (an int8 latent pool"),
    ({"tensor_parallel_size": 2}, "tensor_parallel_size > 1"),
    ({"quant_bits": 8}, "quant_bits"),
])
def test_the_engine_says_what_it_refuses(options, says):
    cfg = TransformerConfig(**TOY)
    with pytest.raises(NotImplementedError) as e:
        InferenceEngineV2(TransformerLM(cfg), {
            "dtype": "float32", **{k: v for k, v in options.items()
                                   if k != "state_manager"},
            "state_manager": {**MANAGER,
                              **options.get("state_manager", {})}})
    assert "a layer_types pattern over latent attention" in str(e.value)
    assert says in str(e.value)


def test_speculation_is_refused(served):
    eng, _, _ = served
    with pytest.raises(NotImplementedError, match="speculative"):
        eng.generate([np.arange(8)], max_new_tokens=4, temperature=0.0,
                     speculative=True)


@pytest.mark.parametrize("fields,error,says", [
    ({"swa_num_heads": 0}, ValueError, "swa_num_heads"),
    ({"attn_window": 0}, ValueError, "attn_window > 0"),
    ({"index_topk": 0}, ValueError, "an indexer needs"),
    ({"index_head_dim": 8}, ValueError, "no less than qk_rope_head_dim"),
    ({"linear_attn_period": 2, "linear_head_dim": 16}, NotImplementedError,
     "two patterns"),
    ({"layer_types": ["full_attention", "mamba", "sliding_attention",
                      "sliding_attention", "sliding_attention"]},
     ValueError, "layer_types names a mixer a layer"),
    ({"qk_norm": True}, NotImplementedError, "PER-HEAD"),
    ({"layer_types": None, "attn_window": 0}, NotImplementedError,
     "give attention='mla' and layer_types"),
])
def test_the_configuration_says_what_it_refuses(fields, error, says):
    with pytest.raises(error) as e:
        TransformerConfig(**{**TOY, **fields})
    assert says in str(e.value)


def test_the_trainer_and_the_dense_forward_refuse_the_block():
    cfg = TransformerConfig(**TOY)
    assert "a second latent kind over a window" in cfg.served_only
    with pytest.raises(NotImplementedError, match="InferenceEngineV2 only"):
        cfg.refuse_served_only("the trainer")
