"""The ``lfm2_moe`` block's own: a layer whose WHOLE mixer is a doubly gated
short convolution, as the configuration writes it down; its parameter
stack, its one cache leaf and its runs (two leading dense layers whose
mixers are conv, expert layers that open with attention); prompts fed in
pieces shorter than the taps; the slot's two inputs and the pool after
decoding; the convolution's forms without an activation; the router's
guard and the bias that chooses; the scopes and the counter a trace and
the registry read it by; and the plain reference
(``benchmark/reference_lfm2.py``) against the model's PUBLISHED code. What
every served block is held to (the engine against the reference through
``put()`` in 1, 2 and 5 chunk steps and through decode windows, rows of
unequal length, the kept state, the controls, the refusals) is the
contract's (``served_block_contract.py``), on this block's row of
``served_blocks.py``, where the limits are justified.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import paged_model
from deepspeed_tpu.inference.v2.kernels import linear_attention as la
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.telemetry import get_registry
from tests.unit.inference import served_block_contract as contract
from tests.unit.inference import served_blocks as sb

BLOCK = sb.BLOCKS["lfm2-8b-a1b"]
globals().update(contract.clauses(BLOCK))     # the contract's cases of this row
TOY = BLOCK.toy


# ---------------------------------------------------------------------------
# (a) the configuration, the tree, the cache and the runs
# ---------------------------------------------------------------------------
def test_the_conv_kind_is_written_down():
    cfg = TransformerConfig(**TOY)
    assert cfg.layer_kinds == ("conv", "conv", "full", "conv", "conv", "conv")
    assert cfg.has_state and cfg.caches_positions and cfg.walks_runs
    assert cfg.leaf_places("conv") == 5 and cfg.leaf_places("full") == 1
    assert [cfg.leaf_places("conv", i) for i in range(7)] \
        == [0, 1, 2, 2, 3, 4, 5]
    # two LED conv layers, then expert layers that open with attention
    assert paged_model._layer_runs(cfg) == [
        ("conv", False, 0, 2), ("full", True, 2, 1), ("conv", True, 3, 3)]
    # the toy keeps what is new in the block
    assert cfg.conv_taps == 3 and cfg.moe_first_dense_layers == 2
    assert cfg.num_heads // cfg.kv_heads == 4
    assert cfg.head_dim == cfg.hidden_size // cfg.num_heads
    assert cfg.moe_norm_topk_eps == 1e-6 and cfg.tie_embeddings
    for word in ("conv layers", "moe_norm_topk_eps", "qk_norm",
                 "moe_selection_bias"):
        assert word in cfg.served_only, word
    with pytest.raises(ValueError, match="a conv layer"):
        TransformerConfig(**{**TOY, "conv_taps": 0})
    with pytest.raises(ValueError, match="a conv layer"):
        TransformerConfig(**{**TOY, "conv_bias": True})
    with pytest.raises(NotImplementedError, match="give layer_types"):
        TransformerConfig(hidden_size=64, num_heads=4, conv_taps=3)
    with pytest.raises(ValueError, match="moe_norm_topk_eps"):
        TransformerConfig(**{**TOY, "moe_norm_topk_eps": 0.0})
    # the guard's default is the accepted sparse blocks' own
    assert TransformerConfig().moe_norm_topk_eps == 1e-20
    assert "moe_norm_topk_eps" not in (TransformerConfig(
        **sb.BLOCKS["joyai-llm-flash"].toy).served_only)


def test_the_stack_the_leaf_and_the_gauges(lend):
    """``conv_layers`` holds the mixer's two projections and its taps
    beside ``full_layers``; the cache gives the kind ONE leaf (a slot:
    taps - 1 inputs) and the attention layer the pool; both gauges are
    set."""
    cfg = TransformerConfig(**TOY)
    tree = jax.eval_shape(TransformerLM(cfg).init_params,
                          jax.random.PRNGKey(0))
    assert set(tree) == {"embed", "final_norm", "conv_layers",
                         "full_layers", "lead_layers", "layers"}
    assert {k: v.shape for k, v in tree["conv_layers"].items()} == {
        "attn_norm": (5, 128), "w_in": (5, 128, 384), "conv": (5, 3, 128),
        "w_out": (5, 128, 128)}
    assert set(tree["full_layers"]) == {"attn_norm", "wq", "wk", "wv", "wo",
                                        "q_norm", "k_norm"}
    assert set(tree["lead_layers"]) == {"mlp_norm", "w_gate", "w_up",
                                        "w_down"}
    shapes = BLOCK.weights.shapes(TOY)
    for stack in ("conv_layers", "full_layers", "lead_layers", "layers"):
        assert {k: v.shape for k, v in tree[stack].items()} == {
            k: s for k, (s, _) in shapes[stack].items()}, stack
    eng = sb.engine(BLOCK)      # its own: the gauges are the last one built's
    assert {k: v.shape for k, v in eng.kv_cache.items()} == {
        "k_full": (1, 60, 16, 2 * 16), "v_full": (1, 60, 16, 2 * 16),
        "conv_state": (5, BLOCK.seqs + 1, 2, 1, 128)}
    assert la.conv_leaf_shape(11, 257, 3, 2048) == (11, 257, 2, 16, 128)
    reg = get_registry()
    assert reg.get("inference_state_bytes").value \
        == eng.kv_cache["conv_state"].nbytes
    assert reg.get("inference_kv_pool_bytes").labels(kind="full").value \
        == sum(eng.kv_cache[k].nbytes for k in ("k_full", "v_full"))


# ---------------------------------------------------------------------------
# (b) pieces shorter than the taps, and what a row keeps
# ---------------------------------------------------------------------------
def test_pieces_of_one_and_of_two_tokens_carry_the_taps(lend):
    """A prompt fed through ``put()`` in pieces of 1, 2, 1, 5, 2 tokens
    and the rest (a piece shorter than the taps reaches behind the piece
    before it into the slot), beside a row fed whole in the same steps:
    every piece's last logits are the reference's at that position, and
    the slot then holds the reference's last two gated inputs."""
    eng = lend()
    long, short = sb.prompts(BLOCK, (37, 9), seed=4)
    a, b = sb.uids(2)
    ref = sb.reference(BLOCK, long)
    at = 0
    for n, piece in enumerate((1, 2, 1, 5, 2, 26)):
        rows, toks = [a], [long[at:at + piece]]
        if n == 1:              # a second row joins, whole, in this step
            rows, toks = rows + [b], toks + [short]
        got = eng.put(rows, toks)
        at += piece
        assert sb.err(got[0], ref[at - 1]) <= sb.F32, (n, piece)
        if n == 1:
            assert sb.err(got[1], sb.reference(BLOCK, short)[-1]) <= sb.F32
    for uid, tokens in ((a, long), (b, short)):
        assert sb.layer_err(
            eng.sequence_state(uid)["conv_state"],
            BLOCK.reference.leading_states(sb.params(BLOCK), TOY,
                                           tokens)) <= sb.F32
        eng.flush(uid)
    # a sequence of ONE token holds a zero ahead of its one input
    c, = sb.uids(1)
    eng.put([c], [long[:1]])
    state = eng.sequence_state(c)["conv_state"]
    assert not state[:, 0].any() and state[:, 1].any()
    eng.flush(c)


def test_the_slot_and_the_pool_after_decoding_are_the_references(lend):
    """After a prompt in chunk steps and decode windows a row's SLOT
    holds, layer by layer, the last two gated inputs and its BLOCKS the
    keys (normed a head, then rotated) and the values of every token but
    the last: both against the reference after the same tokens."""
    eng = lend(budget=32)
    prompts = sb.prompts(BLOCK, (40, 23))
    uids = sb.uids(len(prompts))
    outs = eng.generate(prompts, max_new_tokens=9, temperature=0.0,
                        eos_token_id=None, uids=uids, keep_sequences=True)
    for uid, out in zip(uids, outs):
        fed = np.asarray(out)[:-1]
        kv = eng.sequence_kv(uid, "full")
        np.testing.assert_array_equal(kv["positions"], np.arange(len(fed)))
        keys, values = BLOCK.reference.leading_kv(sb.params(BLOCK), TOY, fed)
        assert sb.err(kv["k"], keys) <= sb.F32
        assert sb.err(kv["v"], values) <= sb.F32
        assert sb.layer_err(
            eng.sequence_state(uid)["conv_state"],
            BLOCK.reference.leading_states(sb.params(BLOCK), TOY,
                                           fed)) <= sb.F32
        eng.flush(uid)


# ---------------------------------------------------------------------------
# (c) the convolution's forms without an activation
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("act", ["none", "silu"])
def test_the_kernel_is_the_step_with_and_without_silu(act):
    """``conv_update`` under the TPU interpreter on ONE part of the
    published width, 3 taps, rows that are fresh and continued, against
    ``causal_conv_step``: the activation is a static argument, SiLU the
    default the accepted blocks trace."""
    rng = np.random.default_rng(0)
    N, D, K, L, S = 16, 2048, 3, 2, 20
    leaf = jnp.asarray(rng.normal(size=la.conv_leaf_shape(L, S, K, D)),
                       jnp.float32)
    g = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
    taps = jnp.asarray(rng.normal(size=(K, D)), jnp.float32)
    slots = jnp.asarray(rng.permutation(np.arange(1, S))[:N], jnp.int32)
    fresh = jnp.asarray(rng.random(N) < 0.3)
    held = jnp.where(fresh[:, None, None], 0,
                     leaf[1, slots].reshape(N, K - 1, D))
    want, kept = la.causal_conv_step(
        g, taps, held, jax.nn.silu if act == "silu" else None)
    kw = {} if act == "silu" else {"act": "none"}
    (got,), out = la.conv_update(leaf, jnp.int32(1), slots, fresh, (g,),
                                 taps, name="short_conv_update",
                                 interpret=True, **kw)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(out[1, slots].reshape(N, K - 1, D), kept,
                               rtol=0, atol=0)
    np.testing.assert_array_equal(out[0], leaf[0])


def test_rows_shorter_than_the_taps_in_the_rows_form():
    """``causal_conv_rows`` with no activation on rows of 1, 2, 0 and 7
    tokens under 3 taps: a row's first tokens reach into its state, a
    row of one token keeps one old input."""
    rng = np.random.default_rng(1)
    counts = np.array([1, 2, 0, 7])
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    T, D, K = int(counts.sum()), 8, 3
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    taps = jnp.asarray(rng.normal(size=(K, D)), jnp.float32)
    state = jnp.asarray(rng.normal(size=(4, K - 1, D)), jnp.float32)
    row_ids = jnp.asarray(np.repeat(np.arange(4), counts), jnp.int32)
    y, new = la.causal_conv_rows(x, taps, state, row_ids,
                                 jnp.asarray(starts, jnp.int32),
                                 jnp.asarray(counts, jnp.int32))
    for r, (s, n) in enumerate(zip(starts, counts)):
        seq = np.concatenate([state[r], x[s:s + n]])
        want = sum(np.asarray(taps[j]) * seq[j:j + n] for j in range(K))
        np.testing.assert_allclose(y[s:s + n], want, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(new[r], seq[-(K - 1):], rtol=0, atol=0)


# ---------------------------------------------------------------------------
# (d) the router: the guard, and a bias that chooses
# ---------------------------------------------------------------------------
def test_the_guard_is_a_field_and_the_bias_changes_picks():
    from deepspeed_tpu.moe.sharded_moe import topk_routing
    logits = jnp.full((1, 8), -40.0)            # scores that sum to ~1e-17
    _, w6 = topk_routing(logits, 2, "sigmoid", norm_eps=1e-6)
    _, w20 = topk_routing(logits, 2, "sigmoid")
    assert float(w6.sum()) < 1e-10 and float(w20.sum()) > 0.99
    # the seeded bias changes which experts a token takes, often, and is
    # no part of a weight
    p = sb.params(BLOCK)
    f = BLOCK.reference._Frozen(TOY)
    m = jax.random.normal(jax.random.PRNGKey(0), (512, TOY["hidden_size"]))
    gate, bias = p["layers"]["moe_gate_w"][0], p["layers"]["moe_gate_bias"][0]
    assert bias.dtype == jnp.float32 and float(jnp.abs(bias).max()) > 0
    with_bias, w = BLOCK.reference.route(m, gate, bias, f)
    without, w0 = BLOCK.reference.route(m, gate, 0 * bias, f)
    moved = np.asarray((jnp.sort(with_bias, -1) != jnp.sort(without, -1))
                       .any(-1))
    assert 0.05 < moved.mean() < 0.9, moved.mean()
    same = ~moved
    np.testing.assert_allclose(np.sort(w[same], -1), np.sort(w0[same], -1),
                               rtol=1e-6)
    # the program's routing is the reference's on the same scores
    topi, topv = topk_routing(m @ gate, 2, "sigmoid", bias, True, 1.0,
                              norm_eps=1e-6)
    np.testing.assert_array_equal(np.sort(topi, -1), np.sort(with_bias, -1))
    np.testing.assert_allclose(np.sort(topv, -1), np.sort(w, -1), rtol=1e-6)


# ---------------------------------------------------------------------------
# (e) the scopes and the counter
# ---------------------------------------------------------------------------
def test_the_mixers_scopes_and_the_counter(lend):
    """``short_conv`` wraps ``conv_proj``, ``conv_gate`` and
    ``conv_out``; the program's table reads every operation of the layer
    by a word it has (no ``other``); the counter counts tokens x conv
    layers a program."""
    from deepspeed_tpu.telemetry import memory
    from deepspeed_tpu.utils.xla_profile import (SERVE_PHASES, serve_phase,
                                                 serve_scope)
    assert "short_conv" in SERVE_PHASES
    eng = lend()
    reg = get_registry()
    count = reg.get("inference_conv_state_tokens_total")

    def read():
        return {p: count.labels(program=p).value
                for p in ("ragged_step", "decode_window")}
    before = read()
    prompts = sb.prompts(BLOCK)
    eng.generate(prompts, max_new_tokens=5, temperature=0.0,
                 eos_token_id=None, uids=sb.uids(len(prompts)))
    after = read()
    fed = sum(len(p) for p in prompts)
    assert after["ragged_step"] - before["ragged_step"] == 5 * fed
    # 4 tokens a row behind the first (which the prompt's step picks)
    assert after["decode_window"] - before["decode_window"] \
        == 5 * 4 * len(prompts)
    for program in ("ragged_step", "decode_window_greedy"):
        paths = {p for m in memory.scopes_offered(program) if m
                 for p in m.values()}
        inside = {p for p in paths if "/short_conv/" in p}
        assert inside, program
        for word in ("conv_proj", "conv_gate", "conv_out"):
            assert any(f"/short_conv/{word}/" in p for p in inside), \
                (program, word)
        assert {serve_scope(p) for p in inside} <= {
            "short_conv", "conv_proj", "conv_gate", "conv_out"}
        assert {serve_phase(p) for p in inside} == {"short_conv"}
        under_mlp = {p for p in paths if "/mlp/" in p}
        assert {"mlp", "experts", "router"} >= {
            serve_phase(p) for p in under_mlp} >= {"mlp", "experts"}


# ---------------------------------------------------------------------------
# (f) the reference is the published code's
# ---------------------------------------------------------------------------
def _torch_state(torch, p, f, dense):
    """The row's weights under the published names; ``dense``: every
    layer's MLP is a dense one (``lead_layers``)."""
    def t(a, transpose=False):
        a = torch.tensor(np.asarray(a, np.float32))
        return (a.T if transpose else a).contiguous()

    state = {"model.embed_tokens.weight": t(p["embed"]),
             "model.embedding_norm.weight": t(p["final_norm"]),
             "lm_head.weight": t(p["embed"])}
    seen = {"conv": 0, "full": 0}
    lead = f["moe_first_dense_layers"]
    for i, kind in enumerate(BLOCK.reference.layer_kinds(f)):
        at, m = f"model.layers.{i}.", seen[kind]
        seen[kind] += 1
        mix = jax.tree.map(lambda a, m=m: a[m], p[kind + "_layers"])
        state[at + "operator_norm.weight"] = t(mix["attn_norm"])
        if kind == "conv":
            state.update({
                at + "conv.in_proj.weight": t(mix["w_in"], True),
                at + "conv.conv.weight":
                    t(mix["conv"], True)[:, None, :].contiguous(),
                at + "conv.out_proj.weight": t(mix["w_out"], True)})
        else:
            state.update({
                **{at + f"self_attn.{name}.weight": t(mix[leaf], True)
                   for name, leaf in (("q_proj", "wq"), ("k_proj", "wk"),
                                      ("v_proj", "wv"), ("out_proj", "wo"))},
                at + "self_attn.q_layernorm.weight": t(mix["q_norm"]),
                at + "self_attn.k_layernorm.weight": t(mix["k_norm"])})
        stack, j = (p["lead_layers"], i) if i < lead \
            else (p["layers"], i - lead)
        state[at + "ffn_norm.weight"] = t(stack["mlp_norm"][j])
        if dense:
            state.update({at + f"feed_forward.{name}.weight":
                          t(stack[leaf][j], True) for name, leaf in (
                              ("w1", "w_gate"), ("w3", "w_up"),
                              ("w2", "w_down"))})
    return state


def test_the_reference_is_the_published_modelling_code():
    """``Lfm2ForCausalLM`` (transformers' ``modeling_lfm2.py``, its
    ``slow_forward`` path, float32, CPU) built from a toy ``Lfm2Config``
    (``block_auto_adjust_ff_dim`` false, a conv / full_attention
    pattern) with the row's weights copied in gives the reference's
    logits with every layer dense: the mixer's chunks and gates, the
    taps' order, the head norms ahead of the rotation, the norms' names,
    the tied head."""
    torch = pytest.importorskip("torch")
    hf = pytest.importorskip("transformers.models.lfm2")
    types = ["conv", "conv", "full_attention", "conv"]
    # every layer a LEADING dense layer: the block without its experts
    f = {**TOY, "num_layers": 4, "layer_types": types,
         "moe_first_dense_layers": 4}
    config = hf.Lfm2Config(
        vocab_size=f["vocab_size"], hidden_size=f["hidden_size"],
        intermediate_size=f["intermediate_size"], num_hidden_layers=4,
        num_attention_heads=f["num_heads"],
        num_key_value_heads=f["num_kv_heads"], max_position_embeddings=256,
        norm_eps=f["norm_eps"], rope_theta=f["rope_theta"],
        conv_bias=False, conv_L_cache=f["conv_taps"],
        block_auto_adjust_ff_dim=False, layer_types=types,
        tie_word_embeddings=True, attn_implementation="eager")
    model = hf.Lfm2ForCausalLM(config).float().eval()
    p = BLOCK.weights.make(f, 3, jnp.float32)
    assert "layers" not in p and p["lead_layers"]["w_up"].shape[0] == 4
    missing, unexpected = model.load_state_dict(
        _torch_state(torch, p, f, dense=True), strict=False)
    assert not missing and not unexpected, (missing, unexpected)
    ids = np.random.default_rng(0).integers(0, f["vocab_size"], 45)
    with torch.no_grad():
        got = model(torch.tensor(ids)[None], use_cache=False,
                    logits_to_keep=0).logits[0].numpy()
    assert sb.err(got, BLOCK.reference.logits(p, f, ids)) <= 1e-5


def test_the_reference_is_the_published_sparse_block():
    """The same with the experts, against ``Lfm2MoeForCausalLM``:
    skipped where ``transformers.models.lfm2_moe`` is not installed (it
    is not on this machine; the router's lines are then the
    configuration's ``assumed``)."""
    torch = pytest.importorskip("torch")
    hf = pytest.importorskip("transformers.models.lfm2_moe")
    f = TOY
    config = hf.Lfm2MoeConfig(
        vocab_size=f["vocab_size"], hidden_size=f["hidden_size"],
        intermediate_size=f["intermediate_size"],
        moe_intermediate_size=f["moe_intermediate_size"],
        num_hidden_layers=f["num_layers"],
        num_attention_heads=f["num_heads"],
        num_key_value_heads=f["num_kv_heads"], max_position_embeddings=256,
        norm_eps=f["norm_eps"], rope_theta=f["rope_theta"],
        conv_bias=False, conv_L_cache=f["conv_taps"],
        num_dense_layers=f["moe_first_dense_layers"],
        num_experts=f["moe_num_experts"],
        num_experts_per_tok=f["moe_top_k"], use_expert_bias=True,
        norm_topk_prob=True, routed_scaling_factor=1.0,
        layer_types=f["layer_types"], tie_word_embeddings=True,
        attn_implementation="eager")
    model = hf.Lfm2MoeForCausalLM(config).float().eval()
    p = sb.params(BLOCK)
    state = _torch_state(torch, p, f, dense=False)
    lead = f["moe_first_dense_layers"]

    def t(a, transpose=False):
        a = torch.tensor(np.asarray(a, np.float32))
        return (a.T if transpose else a).contiguous()

    for i in range(f["num_layers"]):
        at = f"model.layers.{i}.feed_forward."
        if i < lead:
            state.update({at + f"{name}.weight":
                          t(p["lead_layers"][leaf][i], True)
                          for name, leaf in (("w1", "w_gate"), ("w3", "w_up"),
                                             ("w2", "w_down"))})
            continue
        mlp = jax.tree.map(lambda a, j=i - lead: a[j], p["layers"])
        state[at + "gate.weight"] = t(mlp["moe_gate_w"], True)
        state[at + "expert_bias"] = t(mlp["moe_gate_bias"])
        for e in range(f["moe_num_experts"]):
            state.update({at + f"experts.{e}.{name}.weight":
                          t(mlp[leaf][e], True) for name, leaf in (
                              ("w1", "e_gate"), ("w3", "e_up"),
                              ("w2", "e_down"))})
    missing, unexpected = model.load_state_dict(state, strict=False)
    assert not missing and not unexpected, (missing, unexpected)
    ids = np.random.default_rng(0).integers(0, f["vocab_size"], 45)
    with torch.no_grad():
        got = model(torch.tensor(ids)[None], use_cache=False,
                    logits_to_keep=0).logits[0].numpy()
    assert sb.err(got, BLOCK.reference.logits(p, f, ids)) <= 1e-4
