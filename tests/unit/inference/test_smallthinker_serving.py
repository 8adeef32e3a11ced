"""The SmallThinker block's own: the source's lists and the pattern as
runs, a ROUTER THAT READS THE MIXER'S NORMED INPUT (what it reads, how it
weighs, where its scope stands in the programs), the ReGLU experts'
body, a query group of SEVEN in the ragged kernels, the ring, the int8
control on what a ring holds, and what does not serve the block. What
every served block is held to (the engine against the plain reference
``benchmark/reference_smallthinker.py``: every position against every
key, no cache, no ring, no chunks; the five programs that are another
block's on the same weights) is the contract's
(``test_served_block_contract.py``), on this block's row of
``served_blocks.py``, where the limits are justified.

A kernel against the gathering reference, both float32: 2e-5 of the
largest output.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2 import paged_model
from deepspeed_tpu.inference.v2.kernels.ragged_attention import (
    ragged_attention, ragged_attention_reference)
from deepspeed_tpu.inference.v2.paged_model import (_layer_runs,
                                                    init_paged_kv_cache)
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.moe import sharded_moe
from tests.unit.inference import served_block_contract as contract
from tests.unit.inference import served_blocks as sb
from tests.unit.inference.served_blocks import F32 as F32_TIGHT

BLOCK = sb.BLOCKS["smallthinker-21ba3b-instruct"]
globals().update(contract.clauses(BLOCK))     # the contract's cases of this row
CONFIG, TOY = BLOCK.config, BLOCK.toy
reference, weights = BLOCK.reference, BLOCK.weights
WINDOW = TOY["attn_window"]                 # 16


# ---------------------------------------------------------------------------
# (a) the configuration
# ---------------------------------------------------------------------------
def test_the_source_lists_agree_and_the_pattern_is_walked_as_runs():
    """``rope_layout`` equals ``sliding_window_layout`` entry for entry
    (which is ``rope_sliding_only``), the served ``layer_types`` are
    entries 1-8 in the program's words, and the walk is four runs."""
    assert CONFIG["rope_layout"] == CONFIG["sliding_window_layout"]
    assert len(CONFIG["rope_layout"]) == CONFIG["published"][
        "num_hidden_layers"] == 52
    assert CONFIG["sliding_window_layout"][:4] == [0, 1, 1, 1]
    assert CONFIG["fields"]["layer_types"] == [
        "sliding_attention" if s else "full_attention"
        for s in CONFIG["sliding_window_layout"][1:9]]
    cfg = TransformerConfig(**TOY)
    assert cfg.num_heads // cfg.kv_heads == 7 == \
        CONFIG["fields"]["num_heads"] // CONFIG["fields"]["num_kv_heads"]
    assert cfg.layer_kinds == ("window",) * 3 + ("full",) \
        + ("window",) * 3 + ("full",)
    assert cfg.walks_runs and cfg.pattern and not cfg.has_state
    assert _layer_runs(cfg) == [("window", True, 0, 3), ("full", True, 3, 1),
                                ("window", True, 4, 3), ("full", True, 7, 1)]
    assert cfg.expert_keys == ("e_gate", "e_up", "e_down")
    tree = jax.eval_shape(TransformerLM(cfg).init_params,
                          jax.random.PRNGKey(0))
    assert sorted(tree["layers"]) == ["e_down", "e_gate", "e_up",
                                      "mlp_norm", "moe_gate_w"]
    assert sorted(tree["window_layers"]) == ["attn_norm", "wk", "wo", "wq",
                                             "wv"]
    assert {s: set(leaves) for s, leaves in weights.shapes(TOY).items()
            if s != "top"} == {s: set(tree[s]) for s in (
                "window_layers", "full_layers", "layers")}


# ---------------------------------------------------------------------------
# (b) the router: what it reads, and how it weighs
# ---------------------------------------------------------------------------
def _first_layer():
    """(the first layer's leaves in float32, a stream [37, H])."""
    params = sb.params(BLOCK)
    lp = {**jax.tree.map(lambda a: a[0], params["window_layers"]),
          **jax.tree.map(lambda a: a[0], params["layers"])}
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (37, TOY["hidden_size"])), jnp.float32)
    return lp, x


def test_the_router_reads_the_mixers_normed_input():
    """``attn_norm`` and ``mlp_norm`` are seeded apart (1 +- 10 % each):
    routed on ``norm(x, attn_norm)`` the program picks the reference's
    experts with the reference's weights; routed on ``norm(x,
    mlp_norm)``, the router behind, it picks others."""
    lp, x = _first_layer()
    assert float(jnp.abs(lp["attn_norm"] - lp["mlp_norm"]).max()) > 0.05
    cfg = TransformerConfig(**TOY)
    hi = jax.lax.Precision.HIGHEST
    h = reference._rms_norm(x, lp["attn_norm"], TOY["norm_eps"])
    with jax.default_matmul_precision("highest"):
        want_i, want_w = reference.route(h, lp["moe_gate_w"], TOY["moe_top_k"])
    topi, topv = paged_model._moe_route(
        cfg, lp, paged_model._norm(cfg, x, lp["attn_norm"]), hi)
    np.testing.assert_array_equal(topi, want_i)
    np.testing.assert_allclose(topv, want_w, rtol=2e-6)
    g = reference._rms_norm(x, lp["mlp_norm"], TOY["norm_eps"])
    behind, _ = paged_model._moe_route(cfg, lp, g, hi)
    assert (np.asarray(behind) != np.asarray(want_i)).any(axis=-1).sum() > 5
    # the one composition of the two halves is the router behind
    out, picks = paged_model._moe_routed(cfg, lp, g, router_precision=hi)
    np.testing.assert_array_equal(picks, behind)
    np.testing.assert_array_equal(out, paged_model._moe_experts(
        cfg, lp, g, *paged_model._moe_route(cfg, lp, g, hi)))


def test_softmax_over_the_six_is_softmax_over_all_then_normalised():
    """The published form (softmax over the chosen logits) and the
    served one (``topk_routing``: softmax over every expert, the chosen
    over their sum): the same experts, the same weights to float32
    rounding, at the published 64 experts top-6."""
    logits = jnp.asarray(1.5 * np.random.default_rng(2).standard_normal(
        (256, 64)), jnp.float32)
    topi, topv = sharded_moe.topk_routing(logits, 6, "softmax", None, True,
                                          1.0)
    z, chosen = jax.lax.top_k(logits, 6)
    np.testing.assert_array_equal(topi, chosen)
    np.testing.assert_allclose(topv, jax.nn.softmax(z, axis=-1), rtol=3e-6)
    np.testing.assert_allclose(np.asarray(topv).sum(-1), 1.0, rtol=1e-6)


def test_the_relu_gate_is_the_three_matrix_body_with_another_gate():
    """One body, the gate a parameter: "reglu" through the ragged form
    and the plain one equals ``down(relu(gate x) * (up x))`` by hand,
    and differs from SwiGLU's on the same leaves."""
    rng = np.random.default_rng(3)
    E, H, F, T = 4, 32, 16, 24
    wg, wu = (jnp.asarray(rng.standard_normal((E, H, F)) / H ** 0.5,
                          jnp.float32) for _ in range(2))
    wd = jnp.asarray(rng.standard_normal((E, F, H)) / F ** 0.5, jnp.float32)
    xs = jnp.asarray(rng.standard_normal((T, H)), jnp.float32)
    sizes = jnp.asarray([5, 0, 12, 7], jnp.int32)
    ragged, gmm, one = sharded_moe.expert_forms("reglu")
    for fn, base in ((ragged, sharded_moe.ragged_swiglu_experts),
                     (gmm, sharded_moe.gmm_swiglu_experts),
                     (one, sharded_moe._swiglu_expert)):
        assert fn.func is base and fn.keywords == {"gate": jax.nn.relu}
    assert sharded_moe.expert_forms("swiglu")[0] \
        is sharded_moe.ragged_swiglu_experts
    got = np.asarray(ragged((wg, wu, wd), xs, sizes))
    of = np.repeat(np.arange(E), np.asarray(sizes))
    want = np.stack([(np.maximum(x @ wg[e], 0) * (x @ wu[e])) @ wd[e]
                     for x, e in zip(np.asarray(xs), of)])
    np.testing.assert_allclose(got, want, atol=2e-6)
    np.testing.assert_allclose(one(xs[:5], wg[0], wu[0], wd[0]), want[:5],
                               atol=2e-6)
    silu = np.asarray(sharded_moe.ragged_swiglu_experts((wg, wu, wd), xs,
                                                        sizes))
    assert np.abs(silu - want).max() > 0.05 * np.abs(want).max()


@pytest.mark.parametrize("program", ["ragged_step", "decode_window"])
def test_the_routers_scope_stands_ahead_of_attention(program):
    """In the program's equations, a layer's router (``moe_router``: its
    float32 matmul, the top k) comes BEFORE the mixer's projections and
    under no ``mlp`` scope; with the router behind it comes after, under
    ``mlp``; the counters stay under ``mlp/moe_router`` in both. The
    norm the router ahead reads is the MIXER's, made once under
    ``attention`` and handed to both: ``moe_router`` holds no norm, and
    the layer has as many as with the router behind."""
    def scans(cfg):
        params = jax.eval_shape(TransformerLM(cfg).init_params,
                                jax.random.PRNGKey(0))
        cache = jax.eval_shape(lambda: init_paged_kv_cache(
            cfg, 9, 16, jnp.float32, window_blocks=9))
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
        if program == "decode_window":
            jaxpr = jax.make_jaxpr(
                lambda p, t, pos, bt, c, sl, eos, wt:
                paged_model.paged_decode_window(
                    cfg, p, t, pos, bt, c, sl, eos, 16, 4,
                    window_tables=wt))(
                params, i32(2), i32(2), i32(2, 4), cache, i32(2), i32(2),
                i32(2, 4))
        else:
            jaxpr = jax.make_jaxpr(
                lambda p, ids, rows, pos, ln, wb, wo, bt, li, c, wt:
                paged_model.paged_ragged_step(
                    cfg, p, ids, rows, pos, ln, wb, wo, bt, li, c, 16,
                    window_tables=wt))(
                params, i32(16), i32(16), i32(16), i32(16), i32(16),
                i32(16), i32(2, 4), i32(2), cache, i32(2, 4))
        found = []

        def walk(jp):
            for eqn in jp.eqns:
                if eqn.primitive.name == "scan" and str(
                        eqn.source_info.name_stack).endswith("layers"):
                    found.append([
                        (str(e.source_info.name_stack), e.primitive.name)
                        for e in eqn.params["jaxpr"].jaxpr.eqns])
                else:
                    for sub in jax.core.jaxprs_in_params(eqn.params):
                        walk(sub)
        walk(jaxpr.jaxpr)
        return found

    norms = {}
    for ahead in (True, False):
        runs = scans(TransformerConfig(**{**TOY, "moe_router_ahead": ahead}))
        assert len(runs) == 4
        norms[ahead] = [sum(p == "rsqrt" for _, p in body) for body in runs]
        for body in runs:
            stacks = [s for s, _ in body]
            router = [i for i, (s, p) in enumerate(body)
                      if "moe_router" in s and p == "top_k"]
            mixer = [i for i, s in enumerate(stacks) if "attention" in s]
            assert len(router) == 1 and mixer
            dots = [i for i, (s, p) in enumerate(body)
                    if "moe_router" in s and p == "dot_general"]
            assert len(dots) == 1
            assert not [s for s, p in body
                        if "moe_router" in s and p == "rsqrt"]
            if ahead:
                proj = [i for i, s in enumerate(stacks) if "qkv_proj" in s]
                assert router[0] < proj[0] and dots[0] < proj[0]
                assert mixer[0] < dots[0]       # the mixer's norm, once
                assert "mlp" not in stacks[router[0]]
            else:
                assert router[0] > mixer[-1] and "mlp" in stacks[router[0]]
            counted = [s for s, p in body
                       if "moe_router" in s and p == "scatter-add"]
            assert counted and all("mlp" in s for s in counted)
    assert norms[True] == norms[False] == [2] * 4


# ---------------------------------------------------------------------------
# (c) a query group of seven in the kernels
# ---------------------------------------------------------------------------
def _group7_case(window, one_token, seed=0):
    """28 query heads on 4 kv heads of 128 (groups of SEVEN), pages of
    16, tables of 10 pages (rings where ``window``): a decode batch of
    contexts inside and far round the ring, or a mixed launch of two
    prompt chunks and a decode row."""
    rng = np.random.default_rng(seed)
    nh, kvh, hd, bs, MB = 28, 4, 128, 16, 10
    if one_token:
        lens = [333, 90, 1000, 0, 64, 65, 5000, 159, 160] if window \
            else [150, 90, 1, 0, 64, 65, 160, 17, 128]
        R = len(lens)
        row_ids = np.arange(R)
        T = R
    else:
        rows = ((0, 200, 40), (1, 333, 1), (2, 0, 23)) if window \
            else ((0, 100, 40), (1, 159, 1), (2, 0, 23))
        R, row_ids, lens = 3, [], []
        for r, first, n in rows:
            row_ids += [r] * n
            lens += list(range(first + 1, first + n + 1))
        T = 64
        row_ids += [0] * (T - len(lens))
        lens += [0] * (T - len(lens))
    nb = 1 + R * MB
    k, v = (jnp.asarray(rng.normal(size=(2, nb, bs, kvh * hd)), jnp.float32)
            for _ in range(2))
    tables = jnp.asarray(rng.permutation(np.arange(1, nb)).reshape(R, MB),
                         jnp.int32)
    q = jnp.asarray(rng.normal(size=(T, nh, hd)), jnp.float32)
    return (q, k, v, 1, jnp.asarray(row_ids, jnp.int32),
            jnp.asarray(lens, jnp.int32), tables)


@pytest.mark.parametrize("window", [0, 64], ids=["full", "window"])
@pytest.mark.parametrize("variant,one_token", [
    ("tiled", False), ("tiled", True), ("pipelined", False)],
    ids=["tiled-token-tile", "tiled-one-token", "pipelined"])
def test_a_group_of_seven_against_the_gathering_reference(variant,
                                                          one_token, window):
    """Both variants' interpreters (the tiled one under the TPU
    interpreter: DMAs, semaphores and all), the token tile and the
    one-token form, with and without a window, at the published head
    geometry: a lane block's seven query rows are padded to one sublane
    tile of eight, and the padding row reaches no output."""
    args = _group7_case(window, one_token)
    want = np.asarray(ragged_attention_reference(*args, window=window))
    got = np.asarray(ragged_attention(*args, variant=variant, window=window,
                                      one_token=one_token))
    live = np.asarray(args[5]) > 0
    assert live.sum() >= 8
    assert np.abs(got - want)[live].max() <= F32_TIGHT * np.abs(want).max()
    assert not got[~live].any()
    # every query head reads ITS group's keys: head n against head n // 7
    q, k, v, layer, row_ids, lens, tables = args
    t = int(np.flatnonzero(live)[-1])
    n, bs, hd = int(lens[t]), 16, 128
    pos = np.arange(max(0, n - window) if window else 0, n)
    place = pos % (tables.shape[1] * bs)
    pages = np.asarray(tables)[int(row_ids[t]), place // bs]
    kk = np.asarray(k)[layer, pages, place % bs].reshape(len(pos), 4, hd)
    vv = np.asarray(v)[layer, pages, place % bs].reshape(len(pos), 4, hd)
    for h in (0, 6, 7, 27):
        s = kk[:, h // 7] @ np.asarray(q)[t, h] / hd ** 0.5
        p = np.exp(s - s.max())
        np.testing.assert_allclose(got[t, h], (p / p.sum()) @ vv[:, h // 7],
                                   atol=F32_TIGHT * np.abs(want).max())


# ---------------------------------------------------------------------------
# (d) the ring
# ---------------------------------------------------------------------------
def test_the_ring_wraps_and_holds_the_right_positions(lend):
    """Prompts of more than two windows (50, 70, 80, 20 at a window of
    16), 30 decode steps: every row's ring is full, holds the LAST
    positions in order, and its keys and values are the reference's at
    those positions (the first layer's, ahead of every routed expert);
    the full leaves hold every position; both pools empty after
    flush."""
    eng = lend()
    sm = eng.state_manager
    ring = sm.ring_blocks
    prompts = sb.prompts(BLOCK, (50, 70, 80, 20))
    free0 = sm.allocator.free_blocks
    outs = eng.generate(prompts, max_new_tokens=30, temperature=0.0,
                        eos_token_id=None, keep_sequences=True)
    assert sm.window_blocks_in_use() == 4 * ring
    for row in (1, 2):
        kv = eng.sequence_kv(row, "window")
        n = len(outs[row]) - 1
        at = kv["positions"]
        assert at[-1] == n - 1 and len(at) >= WINDOW \
            and (np.diff(at) == 1).all() and n > 2 * WINDOW + 30
        assert kv["k"].shape[0] == 6
        want_k, want_v = reference.leading_kv(sb.params(BLOCK), TOY,
                                              outs[row][:-1])
        for got, want in ((kv["k"], want_k), (kv["v"], want_v)):
            want = np.asarray(want)[:, at]
            assert want.shape[0] == 1
            assert np.linalg.norm(got[:1] - want) / np.linalg.norm(want) \
                <= F32_TIGHT
        full = eng.sequence_kv(row, "full")
        assert len(full["positions"]) == n and full["k"].shape[0] == 2
    for uid in range(4):
        eng.flush(uid)
    assert sm.window_blocks_in_use() == 0
    assert sm.allocator.free_blocks == free0


def test_an_int8_pool_in_both_leaves_is_the_lower_precision_control(lend):
    prompts = sb.prompts(BLOCK, (50, 70))
    eng = lend(kv_quant=True)
    assert eng.kv_cache["k_window"].dtype == jnp.int8 \
        and eng.kv_cache["k_full"].dtype == jnp.int8
    outs = eng.generate(prompts, max_new_tokens=4, temperature=0.0,
                        eos_token_id=None, keep_sequences=True)
    kv = eng.sequence_kv(1)
    _, want_v = reference.leading_kv(sb.params(BLOCK), TOY, outs[1][:-1])
    want = np.asarray(want_v)[:, kv["positions"]]
    assert np.linalg.norm(kv["v"][:1] - want) / np.linalg.norm(want) > 5e-3
    for uid in range(2):
        eng.flush(uid)


# ---------------------------------------------------------------------------
# (e) what does not serve the block says so
# ---------------------------------------------------------------------------
def test_the_refusals_by_their_lines():
    """(Speculation: the contract's.)"""
    from deepspeed_tpu.inference.engine import InferenceEngine
    cfg = TransformerConfig(**TOY)
    for what in ("layer_types", "rope_sliding_only",
                 "moe_expert_form='reglu'", "moe_router_ahead"):
        assert what in cfg.served_only
    model = TransformerLM(cfg)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="moe_router_ahead"):
        model.forward_hidden(params, jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(NotImplementedError,
                       match="v1 inference engine.*moe_router_ahead"):
        InferenceEngine(model, {"dtype": "float32"})
    with pytest.raises(NotImplementedError,
                       match="paged_continue has no form for the walk"):
        paged_model.paged_continue(cfg, params, None, None, None, None,
                                   None, None, None, 8)
    # ep > 1 is refused ahead of ``_pattern_refusals``, by the line that
    # names everything served only (this block's two fields with it)
    with pytest.raises(AssertionError,
                       match=r"expert-parallel serving is top-1/top-2 only"
                             r".*moe_expert_form='reglu'.*moe_router_ahead"
                             r".*at ep=1"):
        InferenceEngineV2(model, {"dtype": "float32",
                                  "expert_parallel_size": 2}, params={})


@pytest.mark.parametrize("fields,line", [
    ({"layer_types": None, "attn_window": 0, "rope_sliding_only": False,
      "moe_expert_form": "swiglu"}, "moe_router_ahead"),
    ({"moe_num_experts": 0, "moe_top_k": 1, "moe_expert_form": "swiglu"},
     "moe_router_ahead"),
    ({"layer_types": None, "attn_window": 0, "rope_sliding_only": False,
      "moe_router_ahead": False}, "'relu2' or 'reglu'"),
    ({"moe_expert_form": "geglu"}, "'relu2' or 'reglu'"),
], ids=["ahead-without-a-pattern", "ahead-without-experts",
        "reglu-without-a-pattern", "an-unknown-form"])
def test_the_new_fields_are_refused_where_nothing_serves_them(fields, line):
    with pytest.raises(NotImplementedError, match=line):
        TransformerConfig(**{**TOY, **fields})


def test_the_router_is_not_put_ahead_of_a_mixer_that_is_not_per_head():
    fields = sb.BLOCKS["granite-4.0-h-small"].toy
    TransformerConfig(**fields)
    with pytest.raises(NotImplementedError, match="per-head attention"):
        TransformerConfig(**{**fields, "moe_router_ahead": True})


def test_defaults_leave_every_other_model_as_it_was():
    cfg = TransformerConfig(vocab_size=64, hidden_size=32,
                            intermediate_size=64, num_layers=2,
                            num_heads=4, max_seq_len=64, moe_num_experts=4,
                            moe_top_k=2)
    assert (cfg.moe_expert_form, cfg.moe_router_ahead, cfg.served_only) \
        == ("swiglu", False, None)
    assert dataclasses.replace(
        TransformerConfig(**TOY), moe_router_ahead=False
    ).served_only.count("moe_router_ahead") == 0
