"""The DeepSeek-V3 block's own: the latent kernel against the gather path,
the pool's row, the router as deployed (sigmoid scores, a selection-only
bias, the chosen weights normalised and scaled), the trees (a leading
dense stack held apart), a latent row handed to another engine, and the
expert counters. What every served block is held to (the engine against
the plain reference ``benchmark/reference_joyai.py``: float32,
non-absorbed, no cache; the int8 control, the three faults in the expert
layer, the refusals) is the contract's
(``test_served_block_contract.py``), on this block's row of
``served_blocks.py``, where the limits are justified.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.kernels.ragged_attention import (
    latent_attention, latent_attention_reference)
from deepspeed_tpu.inference.v2.paged_model import (init_paged_kv_cache,
                                                    latent_pool_row)
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.moe.sharded_moe import topk_routing
from deepspeed_tpu.telemetry import get_registry
from tests.unit.inference import served_block_contract as contract
from tests.unit.inference import served_blocks as sb

BLOCK = sb.BLOCKS["joyai-llm-flash"]
globals().update(contract.clauses(BLOCK))     # the contract's cases of this row
CONFIG, TOY, weights_joyai = BLOCK.config, BLOCK.toy, BLOCK.weights


# ---------------------------------------------------------------------------
# (a) the kernel against the gather path
# ---------------------------------------------------------------------------
def test_latent_kernel_matches_the_gather_on_a_mixed_launch():
    """A prefill chunk from an empty row, a continuation, and two decode
    rows in one launch, a row ending mid-page, padding tokens behind:
    the kernel under the TPU interpreter (DMAs and semaphores included)
    against ``latent_attention_reference``, at a row width that is not
    whole lane blocks before padding."""
    nh, dc, dr, L, nb, bs = 4, 32, 16, 3, 20, 8
    W = 128                                 # latent_pool_row of 48
    rng = np.random.default_rng(0)
    pool = np.zeros((L, nb, bs, W), np.float32)
    pool[..., :dc + dr] = rng.normal(size=(L, nb, bs, dc + dr))
    rows = [(0, 11), (9, 5), (12, 1), (16, 1)]     # (cached, new tokens)
    bt = np.zeros((4, 4), np.int32)
    nxt = 1
    for r, (c, n) in enumerate(rows):
        for j in range(-(-(c + n) // bs)):
            bt[r, j] = nxt
            nxt += 1
    row_ids = [r for r, (c, n) in enumerate(rows) for _ in range(n)]
    lengths = [c + i + 1 for c, n in rows for i in range(n)]
    T0, T = len(row_ids), 32
    row_ids = np.array(row_ids + [0] * (T - T0), np.int32)
    lengths = np.array(lengths + [0] * (T - T0), np.int32)
    q = np.zeros((nh, T, W), np.float32)
    q[..., :dc + dr] = rng.normal(size=(nh, T, dc + dr))
    args = (jnp.asarray(q), jnp.asarray(pool), jnp.int32(1),
            jnp.asarray(row_ids), jnp.asarray(lengths), jnp.asarray(bt))
    want = latent_attention_reference(*args, dc=dc, scale=0.2)
    got = latent_attention(*args, dc=dc, scale=0.2, interpret=True)
    np.testing.assert_allclose(np.asarray(got)[:, :T0],
                               np.asarray(want)[:, :T0], atol=2e-6)
    assert not np.asarray(got)[:, T0:].any()        # padding: zeros


def test_the_one_token_form_of_the_latent_kernel():
    """A decode batch through the latent kernel's one-token form (a
    row's chunks against that row's own ``nh`` query rows, the state one
    row's) against ``latent_attention_reference`` and against the token
    tile on the same inputs: rows of unequal contexts over pages out of
    order, one that crosses a 512-position chunk, rows of length 0
    among them and behind, more rows than the 16 one grid step walks."""
    nh, dc, dr, L, bs, W, MB = 4, 32, 16, 3, 8, 128, 80
    rng = np.random.default_rng(0)
    lens = [11, 70, 1, 0, 600, 64, 512] + [23] * 11 + [0, 5]
    R = len(lens)
    nb = 1 + R * MB
    pool = np.zeros((L, nb, bs, W), np.float32)
    pool[..., :dc + dr] = rng.normal(size=(L, nb, bs, dc + dr))
    bt = rng.permutation(np.arange(1, nb)).reshape(R, MB).astype(np.int32)
    q = np.zeros((nh, R, W), np.float32)
    q[..., :dc + dr] = rng.normal(size=(nh, R, dc + dr))
    args = (jnp.asarray(q), jnp.asarray(pool), jnp.int32(1),
            jnp.arange(R, dtype=jnp.int32), jnp.asarray(lens, jnp.int32),
            jnp.asarray(bt))
    want = np.asarray(latent_attention_reference(*args, dc=dc, scale=0.2))
    tile, one = (np.asarray(latent_attention(
        *args, dc=dc, scale=0.2, interpret=True, one_token=flag))
        for flag in (False, True))
    np.testing.assert_allclose(one, want, atol=2e-6)
    np.testing.assert_allclose(one, tile, atol=2e-6)
    assert not one[:, np.asarray(lens) == 0].any()


@pytest.mark.parametrize("against", ["reference", "parent"])
def test_a_latent_decode_launch_over_partial_chunks(against):
    """PR 50 over a latent pool (``walk_cases``' ``latent``: pages of 8,
    64 a chunk; rows whose last chunk holds 1, 2, 3, 63 and 64 pages, in
    their only chunk and in their second, contexts that end exactly on a
    chunk): one wait a chunk by its bytes; against the gathering reference
    at today's tolerance and against the parent's output on the same
    inputs to the bit."""
    from tests.unit.inference import walk_cases
    got = walk_cases.output("latent")
    if against == "parent":
        np.testing.assert_array_equal(got,
                                      walk_cases.parent_output("latent"))
        return
    np.testing.assert_allclose(got, walk_cases.reference("latent"),
                               rtol=0, atol=2e-6)
    lens, _ = walk_cases.lengths("latent")
    assert not got[:, lens == 0].any()


def test_the_pool_row_is_padded_to_whole_lane_blocks():
    cfg = TransformerConfig(**TOY)
    assert cfg.latent_row == 48 and latent_pool_row(cfg) == 128
    real = TransformerConfig(**CONFIG["fields"])
    assert real.latent_row == 576 and latent_pool_row(real) == 640
    cache = jax.eval_shape(lambda: init_paged_kv_cache(cfg, 5, 16,
                                                       jnp.bfloat16))
    assert cache["latent"].shape == (3, 5, 16, 128)
    assert set(cache) == {"latent"}              # the pool alone


# ---------------------------------------------------------------------------
# (b) the router
# ---------------------------------------------------------------------------
def test_the_bias_chooses_and_does_not_weigh():
    logits = jnp.asarray([[2.0, 1.0, 0.5, -1.0]])
    bias = jnp.asarray([0.0, 0.0, 0.0, 5.0])
    plain_i, plain_w = topk_routing(logits, 2, "sigmoid", None, True, 2.5)
    bias_i, bias_w = topk_routing(logits, 2, "sigmoid", bias, True, 2.5)
    assert sorted(np.asarray(plain_i)[0]) == [0, 1]
    assert sorted(np.asarray(bias_i)[0]) == [0, 3]      # the bias chose 3
    s = jax.nn.sigmoid(logits)[0]
    want = np.asarray([s[3], s[0]]) / (s[3] + s[0]) * 2.5
    got = np.asarray(bias_w)[0][np.argsort(-np.asarray(bias_i)[0])]
    np.testing.assert_allclose(got, want, rtol=1e-6)    # weights: no bias
    for w in (plain_w, bias_w):
        assert float(jnp.sum(w)) == pytest.approx(2.5, rel=1e-6)


def test_one_group_of_experts_makes_the_group_limit_the_identity():
    """``n_group`` = ``topk_group`` = 1: the published code masks the
    experts outside the best ``topk_group`` groups, which with one group
    masks none; the choice is the plain top k of score + bias."""
    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.normal(size=(5, 8)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(8,)) * 0.1, jnp.float32)
    chosen, _ = topk_routing(logits, 2, "sigmoid", bias, True, 2.5)
    scores = np.asarray(jax.nn.sigmoid(logits) + bias)
    group_scores = np.sort(scores, axis=-1)[:, -2:].sum(-1)   # one group
    assert group_scores.shape == (5,)          # nothing to choose among
    want = np.argsort(-scores, axis=-1)[:, :2]
    np.testing.assert_array_equal(np.sort(np.asarray(chosen), -1),
                                  np.sort(want, -1))


def test_the_old_routing_is_what_it_was():
    """Softmax scores, no bias, no scale: top-1 keeps its raw
    probability, top-2 is renormalised over the chosen two."""
    logits = jnp.asarray([[2.0, 1.0, 0.5, -1.0]])
    p = np.asarray(jax.nn.softmax(logits))[0]
    i1, w1 = topk_routing(logits, 1)
    assert int(i1[0, 0]) == 0 and float(w1[0, 0]) == pytest.approx(p[0])
    i2, w2 = topk_routing(logits, 2)
    np.testing.assert_allclose(np.asarray(w2)[0], p[:2] / p[:2].sum(),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# (c) what was there is what it was
# ---------------------------------------------------------------------------
def test_the_old_moe_tree_and_defaults_are_unchanged():
    cfg = TransformerConfig(vocab_size=64, hidden_size=32,
                            intermediate_size=64, num_layers=2,
                            num_heads=4, max_seq_len=64, moe_num_experts=4,
                            moe_top_k=2)
    assert (cfg.attention, cfg.moe_scoring, cfg.moe_first_dense_layers,
            cfg.moe_shared_experts, cfg.served_only) == (
        "mha", "softmax", 0, 0, None)
    tree = jax.eval_shape(TransformerLM(cfg).init_params,
                          jax.random.PRNGKey(0))
    assert sorted(tree) == ["embed", "final_norm", "layers", "lm_head"]
    assert sorted(tree["layers"]) == [
        "attn_norm", "e_down", "e_gate", "e_up", "mlp_norm", "moe_gate_w",
        "wk", "wo", "wq", "wv"]
    assert tree["layers"]["e_gate"].shape == (2, 4, 32, 64)
    cache = jax.eval_shape(lambda: init_paged_kv_cache(cfg, 5, 16,
                                                       jnp.float32))
    assert sorted(cache) == ["k", "v"]


def test_the_latent_tree_holds_the_leading_stack_apart():
    tree = jax.eval_shape(TransformerLM(TransformerConfig(**TOY))
                          .init_params, jax.random.PRNGKey(0))
    assert sorted(tree) == ["embed", "final_norm", "layers", "lead_layers",
                            "lm_head"]
    assert tree["lead_layers"]["w_gate"].shape == (1, 64, 128)
    assert tree["layers"]["e_gate"].shape == (2, 8, 64, 32)
    assert tree["layers"]["moe_gate_bias"].shape == (2, 8)
    assert tree["layers"]["shared_down"].shape == (2, 32, 64)
    assert tree["layers"]["wkv_a"].shape == (2, 64, 48)
    made = jax.eval_shape(lambda: weights_joyai.make(TOY, 1, "float32"))
    assert jax.tree.structure(made) == jax.tree.structure(tree)
    assert jax.tree.map(lambda a: a.shape, made) == jax.tree.map(
        lambda a: a.shape, tree)


# ---------------------------------------------------------------------------
# (d) what else is refused, a handoff, and what the engine counts
# ---------------------------------------------------------------------------
def test_speculation_training_and_the_v1_engine_are_refused():
    """(Speculation and the other forward: the contract's.)"""
    cfg = TransformerConfig(**TOY)
    with pytest.raises(NotImplementedError, match="served by"):
        cfg.refuse_served_only("the trainer")
    with pytest.raises(NotImplementedError, match="leading dense"):
        TransformerConfig(**{**TOY, "attention": "mha"})


def test_a_latent_row_is_handed_to_another_engine(lend):
    """The pool is block pools alone (what the experts routed is an
    output of the programs, not a leaf of the cache), so the handoff
    that gathers every leaf along its block axis moves latent rows as it
    moves keys and values: the other engine decodes what this one would
    have."""
    from deepspeed_tpu.inference.v2.serve import handoff
    src = lend()
    dst = sb.engine(BLOCK)      # its own: ANOTHER engine's pool
    prompt = sb.prompts(BLOCK, (21,))              # ends mid-page
    first = int(np.argmax(src.put([5], prompt)[0]))
    pack = handoff.deserialize(handoff.serialize(
        handoff.export_sequence(src, 5)))
    assert set(pack["kv"]) == {"latent"}
    handoff.restore_sequence(dst, pack, uid=9)
    a, b = src.state_manager.seqs[5], dst.state_manager.seqs[9]
    np.testing.assert_array_equal(
        np.asarray(src.kv_cache["latent"])[:, a.blocks],
        np.asarray(dst.kv_cache["latent"])[:, b.blocks])
    np.testing.assert_array_equal(dst.put([9], [[first]])[0],
                                  src.put([5], [[first]])[0])
    src.flush(5)


def test_the_expert_counters_count_valid_rows(lend):
    reg = get_registry()

    def read():
        return {name: reg.get(name).labels(program=prog).value
                for name in ("moe_launches_total", "moe_routed_rows_total",
                             "moe_experts_touched_total")
                for prog in ("ragged_step",)}

    eng = lend()
    before = read()
    eng.put([0, 1, 2], sb.prompts(BLOCK, (5, 5, 5)))  # 15 in a bucket of 16
    after = read()
    for uid in range(3):
        eng.flush(uid)
    passes = after["moe_launches_total"] - before["moe_launches_total"]
    rows = after["moe_routed_rows_total"] - before["moe_routed_rows_total"]
    touched = (after["moe_experts_touched_total"]
               - before["moe_experts_touched_total"])
    assert passes == 2                         # two expert layers
    assert rows == 2 * 15 * TOY["moe_top_k"]   # padding tokens not counted
    assert 2 * TOY["moe_top_k"] <= touched <= 2 * TOY["moe_num_experts"]
    share = reg.get("moe_fullest_expert_share").labels(
        program="ragged_step").value
    assert 1 / TOY["moe_num_experts"] <= share <= 1.0
