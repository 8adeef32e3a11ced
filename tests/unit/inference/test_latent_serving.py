"""The DeepSeek-V3 block served: a latent (MLA) pool and its kernel, a
leading dense stack before the scanned expert stack, and the expert
layer as deployed (sigmoid scores, a selection-only bias, the chosen
weights normalised and scaled, a shared expert), at toy widths on the
CPU, against the benchmark's plain reference
(``benchmark/reference_joyai.py``: float32, non-absorbed, no cache).

Tolerances. A float32 engine differs from the reference by the order of
its sums and by the absorbed form: 2e-5 of the largest logit is twenty
times what it reads (9e-7). A bf16 engine rounds every
activation to 8 bits: the OPT block's toy limit, 4e-2, on a seed whose
routing the rounding does not flip (with two experts of eight a token, a
flipped choice swaps half the routed output, and one seed in twelve
flips one at the compared position; PERF.md section 4). A fault is held
to 3e-2 and more: over a thousand times the float32 limit.
"""

import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import reference_joyai, weights_joyai
from benchmark import run as harness
from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.kernels.ragged_attention import (
    latent_attention, latent_attention_reference)
from deepspeed_tpu.inference.v2.paged_model import (init_paged_kv_cache,
                                                    latent_pool_row)
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.moe.sharded_moe import topk_routing
from deepspeed_tpu.telemetry import get_registry

REPO = Path(__file__).resolve().parents[3]
CONFIG = json.loads(
    (REPO / "benchmark/configs/joyai-llm-flash.json").read_text())
TOY = harness.merge(CONFIG["fields"], CONFIG["toy_fields"])
F32_TIGHT, BF16_LIMIT, A_FAULT = 2e-5, 4e-2, 3e-2
SEED = 7


def _engine(dtype="float32", fields=TOY, params=None, **engine):
    cfg = TransformerConfig(**fields)
    if params is None:
        params = weights_joyai.make(fields, SEED, dtype)
    return InferenceEngineV2(TransformerLM(cfg), {
        "dtype": dtype, "use_paged_kernel": True, **engine,
        "state_manager": {"max_tracked_sequences": 4,
                          "max_ragged_batch_size": 64, "max_seq_len": 256,
                          "block_size": 16, "num_blocks": 40}}, params=params)


def _prompts(n=3, length=16):
    rng = np.random.default_rng(0)
    return [rng.integers(0, TOY["vocab_size"], length) for _ in range(n)]


def _reference_last(fields, dtype, prompts, params=None):
    if params is None:
        params = weights_joyai.make(fields, SEED, dtype)
    return np.stack([np.asarray(reference_joyai.logits(params, fields, p)[-1])
                     for p in prompts])


def _err(got, want):
    return float(np.abs(np.asarray(got, np.float32) - want).max()
                 / np.abs(want).max())


# ---------------------------------------------------------------------------
# (a) the engine against the plain reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype,limit", [("float32", F32_TIGHT),
                                         ("bfloat16", BF16_LIMIT)])
def test_put_logits_match_the_reference(dtype, limit):
    eng = _engine(dtype)
    assert eng.attention_impl == "pallas:latent"
    prompts = _prompts()
    got = eng.put([0, 1, 2], prompts)
    assert _err(got, _reference_last(TOY, dtype, prompts)) <= limit


def test_decode_through_the_latent_pool_matches_the_reference():
    """The ragged step writes the prompt's rows, then decode windows
    read and extend them: at EVERY generated position the engine's token
    is the reference's best on the same prefix (float32: no near-tie is
    within rounding), so a row, a rope position or a page read wrong
    shows. Rows of different lengths, one ending mid-page."""
    eng = _engine("float32")
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, TOY["vocab_size"], n) for n in (16, 21, 9)]
    outs = eng.generate(prompts, max_new_tokens=20, temperature=0.0,
                        eos_token_id=None)
    params = weights_joyai.make(TOY, SEED, "float32")
    for prompt, out in zip(prompts, outs):
        out = np.asarray(out)
        assert len(out) == len(prompt) + 20
        ref = np.asarray(reference_joyai.logits(params, TOY, out[:-1]))
        want = ref[len(prompt) - 1:].argmax(-1)
        np.testing.assert_array_equal(out[len(prompt):], want)


def test_the_int8_latent_pool_is_the_lower_precision_control():
    """``kv_quant`` stores the rows in 8 bits against each row's largest
    value; the benchmark's weights carry outlier channels in the latent,
    so it reads far over the float32 limit."""
    prompts = _prompts()
    want = _reference_last(TOY, "float32", prompts)
    eng = _engine("float32", kv_quant=True)
    assert eng.kv_cache["latent"].dtype == jnp.int8
    err = _err(eng.put([0, 1, 2], prompts), want)
    assert err > 500 * F32_TIGHT, err


# ---------------------------------------------------------------------------
# (b) the kernel against the gather path
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
# (c) the router
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


def _fault(name):
    """The toy engine's float32 put() logits with one fault in the
    program's expert layer, against the sound reference."""
    from deepspeed_tpu.inference.v2 import paged_model
    from deepspeed_tpu.moe import sharded_moe
    real_routing = sharded_moe.topk_routing
    real_experts = paged_model._moe_experts

    def bf16_router(logits, *a, **kw):
        return real_routing(logits.astype(jnp.bfloat16)
                            .astype(jnp.float32), *a, **kw)

    def bias_in_weights(logits, k, scoring, bias, normalize, scale):
        scores = jax.nn.sigmoid(logits) + bias
        topv, topi = jax.lax.top_k(scores, k)
        return topi, topv / jnp.sum(topv, -1, keepdims=True) * scale

    def no_shared(cfg, lp, xt, *a, **kw):
        return real_experts(dataclasses.replace(cfg, moe_shared_experts=0),
                            lp, xt, *a, **kw)

    patch = {"bf16_router": (sharded_moe, "topk_routing", bf16_router),
             "bias_in_weights": (sharded_moe, "topk_routing",
                                 bias_in_weights),
             "no_shared_expert": (paged_model, "_moe_experts",
                                  no_shared)}[name]
    return patch


@pytest.mark.parametrize("fault,at_least", [
    ("bf16_router", 3 * F32_TIGHT), ("bias_in_weights", 50 * F32_TIGHT),
    ("no_shared_expert", A_FAULT)])
def test_a_fault_in_the_expert_layer_fails_logit_err(monkeypatch, fault,
                                                     at_least):
    """A router computed in bf16 (its logits rounded to 8 bits: the
    sigmoid's weights move in their third digit; it reads 8e-5), a
    bias of spread 0.02 leaking into weights of 1.25 (it reads 2.5e-3) and
    a missing shared expert each read over the float32 limit, the two
    faults of kind by a hundred times and more."""
    prompts = _prompts()
    want = _reference_last(TOY, "float32", prompts)
    module, attr, fn = _fault(fault)
    monkeypatch.setattr(module, attr, fn)
    err = _err(_engine("float32").put([0, 1, 2], prompts), want)
    assert err > at_least > F32_TIGHT, (fault, err)


# ---------------------------------------------------------------------------
# (d) what was there is what it was
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
# what the engine refuses, and what it counts
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("engine,word", [
    ({"tensor_parallel_size": 2}, "tensor_parallel_size"),
    ({"quant_bits": 8}, "quant_bits"),
    ({"max_lora_adapters": 2}, "max_lora_adapters"),
    ({"state_manager": {"enable_prefix_caching": True}},
     "enable_prefix_caching")])
def test_the_engine_refuses_at_construction(engine, word):
    cfg = TransformerConfig(**TOY)
    with pytest.raises(NotImplementedError, match=word):
        InferenceEngineV2(TransformerLM(cfg), {"dtype": "float32", **engine})


def test_speculation_training_and_the_v1_engine_are_refused():
    cfg = TransformerConfig(**TOY)
    eng = _engine("float32")
    with pytest.raises(NotImplementedError, match="speculative"):
        eng.generate(_prompts(1), max_new_tokens=2, speculative=True)
    model = TransformerLM(cfg)
    with pytest.raises(NotImplementedError, match="served by"):
        model.forward_hidden({}, jnp.zeros((1, 4), jnp.int32))
    with pytest.raises(NotImplementedError, match="served by"):
        cfg.refuse_served_only("the trainer")
    with pytest.raises(NotImplementedError, match="leading dense"):
        TransformerConfig(**{**TOY, "attention": "mha"})


def test_a_latent_row_is_handed_to_another_engine():
    """The pool is block pools alone (what the experts routed is an
    output of the programs, not a leaf of the cache), so the handoff
    that gathers every leaf along its block axis moves latent rows as it
    moves keys and values: the other engine decodes what this one would
    have."""
    from deepspeed_tpu.inference.v2.serve import handoff
    params = weights_joyai.make(TOY, SEED, "float32")
    src, dst = _engine(params=params), _engine(params=params)
    prompt = _prompts(1, 21)                       # ends mid-page
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


def test_the_expert_counters_count_valid_rows():
    reg = get_registry()

    def read():
        return {name: reg.get(name).labels(program=prog).value
                for name in ("moe_launches_total", "moe_routed_rows_total",
                             "moe_experts_touched_total")
                for prog in ("ragged_step",)}

    eng = _engine("float32")
    before = read()
    eng.put([0, 1, 2], _prompts(3, 5))        # 15 tokens in a bucket of 16+
    after = read()
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
