"""The ``nemotron_h`` block's own: its pattern as the source spells it
(layers of ONE sub-layer behind one norm), patterns that start with
experts and end with a mixer, the recurrence under GROUPS of heads and
its kernels against the token scan, the group-wise gated norm, the
relu^2 dispatch, the router and the shares of the experts, and the four
older pattern configurations' programs as the jaxprs they were. What
every served block is held to (the engine against the plain reference
``benchmark/reference_nemotron.py``, its controls, its refusals) is the
contract's (``test_served_block_contract.py``), on this block's row of
``served_blocks.py``, where the limits are justified.

Two forms of one recurrence, both float32: 2e-5 of the largest output
(they read 2e-6).
"""

import hashlib
import json
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import paged_model
from deepspeed_tpu.inference.v2.kernels import state_space as ss
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.moe import sharded_moe
from deepspeed_tpu.telemetry import get_registry
from tests.unit.inference import served_block_contract as contract
from tests.unit.inference import served_blocks as sb
from tests.unit.inference import state_space_cases as cases
from tests.unit.inference.served_blocks import (F32 as F32_TIGHT, REPO,
                                                err as _err)

BLOCK = sb.BLOCKS["nemotron-3-nano-30b-a3b"]
globals().update(contract.clauses(BLOCK))     # the contract's cases of this row
CONFIG, TOY = BLOCK.config, BLOCK.toy
reference_nemotron, weights_nemotron = BLOCK.reference, BLOCK.weights


def _cut(pattern):
    """Another pattern of as many layers, laid on the toy."""
    return {"layer_types": list(pattern), "num_layers": len(pattern)}


# ---------------------------------------------------------------------------
# (a) the configuration, and other patterns against the plain reference
# ---------------------------------------------------------------------------
def test_the_pattern_is_written_down_as_the_source_spells_it():
    """The first sixteen characters of ``hybrid_override_pattern``,
    spelt out; a layer is ONE sub-layer, so the walk has a run a layer
    here (sixteen alternating layers) and the parameter tree no MLP
    beside a mixer and no mixer beside an expert layer."""
    pattern = CONFIG["hybrid_override_pattern"]
    word = {"M": "mamba", "E": "moe", "*": "attention"}
    assert CONFIG["fields"]["layer_types"] == [word[c] for c in pattern[:16]]
    cfg = TransformerConfig(**TOY)
    assert cfg.one_sublayer and cfg.has_state and cfg.walks_runs
    assert cfg.layer_kinds.count("ssm") == 7 \
        and cfg.layer_kinds.count("moe") == 7 \
        and cfg.layer_kinds.count("full") == 2
    assert cfg.expert_keys == ("e_up", "e_down")
    # heads x head width, whatever mamba_expand x hidden_size comes to
    assert cfg.mamba_d_inner == 8 * 16
    assert TransformerConfig(**{**TOY, "mamba_d_head": 8}).mamba_d_inner \
        == 64 != cfg.mamba_expand * cfg.hidden_size
    assert cfg.mamba_conv_dim == 8 * 16 + 2 * 2 * 32
    for word in ("'moe' layers", "mamba_n_groups", "moe_expert_form='relu2'",
                 "positional='none'", "moe_experts_held"):
        assert word in cfg.served_only, word
    runs = paged_model._layer_runs(cfg)
    assert len(runs) == 16 and all(n == 1 for *_, n in runs)
    assert [(kind, routed) for kind, routed, *_ in runs[:6]] == [
        ("ssm", False), ("moe", True), ("ssm", False), ("moe", True),
        ("ssm", False), ("full", False)]
    shapes = jax.eval_shape(TransformerLM(cfg).init_params,
                            jax.random.PRNGKey(0))
    assert set(shapes) == {"embed", "lm_head", "final_norm", "ssm_layers",
                           "full_layers", "layers"}
    assert "mlp_norm" not in shapes["ssm_layers"] \
        and "mlp_norm" not in shapes["full_layers"]
    assert set(shapes["layers"]) == {
        "mlp_norm", "moe_gate_w", "moe_gate_bias", "e_up", "e_down",
        "shared_up", "shared_down"}
    # both expert leaves keep the model's width last (up: out x in)
    assert shapes["layers"]["e_up"].shape == (7, 8, 24, 64) \
        == shapes["layers"]["e_down"].shape
    made = weights_nemotron.make(TOY, 1, "float32")
    assert jax.tree.map(lambda a: a.shape, made) \
        == jax.tree.map(lambda a: a.shape, shapes)


def test_the_toys_cache_keeps_a_leaf_a_kind_by_its_layers(lend):
    """Seven mixers and two attention layers of the toy's sixteen."""
    eng = lend()
    assert eng.kv_cache["ssm_state"].shape[0] == 7 \
        and eng.kv_cache["k_full"].shape[0] == 2


def test_the_routed_rows_are_counted_by_the_form_that_brought_them_back(
        lend, monkeypatch):
    """``moe_rows_combined_total{program, form}``: what a launch routed,
    under the form its static shape selects
    (``paged_model.moe_rows_form``, no device read). Off the TPU that is
    the gather for every program: a ``put()`` raises it by what
    ``moe_routed_rows_total`` rises; where the shape says kernel (a TPU's
    share of bfloat16 rows, a prompt's launch) the rows count there."""
    from deepspeed_tpu.inference.v2 import engine_v2
    eng = lend()
    reg = get_registry()

    def read(name, **labels):
        return reg.get(name).labels(**labels).value

    def combined(program):
        return {form: read("moe_rows_combined_total", program=program,
                           form=form) for form in ("kernel", "gather")}

    before = combined("ragged_step")
    routed = read("moe_routed_rows_total", program="ragged_step")
    uid, = sb.uids(1)
    eng.put([uid], sb.prompts(BLOCK, (9,)))
    eng.flush(uid)
    routed = read("moe_routed_rows_total", program="ragged_step") - routed
    after = combined("ragged_step")
    assert routed > 0 and after["gather"] - before["gather"] == routed
    assert after["kernel"] == before["kernel"]
    monkeypatch.setattr(
        engine_v2, "moe_rows_form",
        lambda cfg, tokens, dtype: "kernel" if tokens >= 2048 else "gather")
    stats = np.asarray([7.0, 1000.0, 8.0, 0.5], np.float32)
    for program, tokens, form in (("ragged_step", 16384, "kernel"),
                                  ("decode_window", 128, "gather")):
        was = combined(program)
        eng._note_moe(program, tokens, stats)
        now = combined(program)
        assert {f: now[f] - was[f] for f in now} == {
            form: 1000.0, {"kernel": "gather", "gather": "kernel"}[form]: 0.0}


@pytest.mark.parametrize("fields,word", [
    ({"mamba_n_groups": 3}, "whole multiple of mamba_n_groups"),
    ({"mamba_n_groups": 0}, "whole multiple of mamba_n_groups"),
    ({"moe_first_dense_layers": 1}, "no leading dense stack"),
    ({"norm_scheme": "sandwich"}, "served pre-norm"),
    ({"moe_expert_form": "gelu"}, "moe_expert_form is 'swiglu' or"),
    ({"moe_use_residual": True}, "moe_expert_form is 'swiglu' or")])
def test_configurations_the_block_does_not_describe_are_refused(fields, word):
    with pytest.raises((ValueError, NotImplementedError), match=word):
        TransformerConfig(**{**TOY, **fields})


@pytest.mark.parametrize("pattern", [
    ("moe", "mamba", "attention", "moe", "mamba"),      # ends: a lone mixer
    ("moe", "moe", "mamba", "mamba", "attention", "attention", "moe")])
def test_patterns_that_start_with_experts_and_end_with_a_mixer(lend, pattern):
    """A pattern whose first layer is an expert layer (no mixer ahead of
    it, and no state-space layer ahead of every routed expert), runs of
    two of a kind, and a last layer that is a lone mixer; the cache keeps
    a leaf a kind by ITS layers, and the decode windows count the relu^2
    form a step an expert layer and never the other."""
    fields = _cut(pattern)
    cfg = TransformerConfig(**{**TOY, **fields})
    kinds = cfg.layer_kinds
    runs = paged_model._layer_runs(cfg)
    assert sum(n for *_, n in runs) == len(pattern)
    assert [k for k, *_ in runs] == [k for i, k in enumerate(kinds)
                                     if i == 0 or kinds[i - 1] != k]
    eng = lend(fields=fields, budget=32)
    reg = get_registry()
    assert eng.kv_cache["ssm_state"].shape[0] == kinds.count("ssm") \
        and eng.kv_cache["k_full"].shape[0] == kinds.count("full")
    assert reg.get("inference_ssm_groups").value == 2
    prompts = sb.prompts(BLOCK, (40, 9))
    uids = sb.uids(2)
    got = eng.put(uids, prompts)
    for i, p in enumerate(prompts):
        assert _err(got[i], sb.reference(BLOCK, p, fields)[-1]) \
            <= F32_TIGHT, i
    for uid in uids:
        eng.flush(uid)

    def launches():
        return {form: reg.get("moe_form_launches_total").labels(
            program="decode_window", form=form).value
            for form in ("relu2", "swiglu")}

    before = launches()
    outs = eng.generate(prompts, max_new_tokens=6, temperature=0.0,
                        eos_token_id=None, uids=sb.uids(2))
    after = launches()
    # 5 decode steps x the pattern's expert layers ran the relu2 form
    assert after["relu2"] - before["relu2"] == 5 * kinds.count("moe")
    assert after["swiglu"] == before["swiglu"]
    for prompt, out in zip(prompts, outs):
        ref = sb.reference(BLOCK, np.asarray(out)[:-1],
                           fields)[len(prompt) - 1:]
        np.testing.assert_array_equal(np.asarray(out)[len(prompt):],
                                      ref.argmax(-1))


# ---------------------------------------------------------------------------
# (b) the two forms of the recurrence under groups, and their kernels
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("groups", [1, 2, 8])
@pytest.mark.parametrize("nh,p,n", [(8, 16, 32), (16, 8, 16)])
def test_chunked_form_is_the_token_scan_at_any_group_count(nh, p, n, groups):
    """Rows of 5, 0, 37, 16 and 1 tokens, fresh and continued from their
    slots, through ``ssm_chunked`` with B and C a group of heads: each
    row's outputs and final state are the token scan's. (At these widths
    a lane block holds every group: the XLA forms serve that.)"""
    case = cases.case(nh, p, n, (5, 0, 37, 16, 1), 62, groups)
    y, leaf = ss.ssm_chunked(**case, chunk=16)
    cases.against_the_scan(case, y, leaf, nh, groups)


@pytest.mark.parametrize("groups,nh,p,lengths,T,chunk", [
    (2, 16, 64, (40, 3, 0, 100), 256, 32),  # a group a grid step
    (1, 16, 64, (7, 70), 128, 64),          # one group, two grid steps
    (2, 32, 64, (33, 20), 64, 32)])         # a group two grid steps
def test_chunk_kernel_is_the_token_scan_under_groups(groups, nh, p, lengths,
                                                     T, chunk):
    """``ssm_chunk_fwd`` under the TPU interpreter: a grid step (four
    lane blocks, 512 channels) takes ITS group's B and C out of the one
    token buffer."""
    case = cases.case(nh, p, 128, lengths, T, groups)
    y, leaf = ss.ssm_chunk_fwd(**case, chunk=chunk, interpret=True)
    cases.against_the_scan(case, y, leaf, nh, groups)


@pytest.mark.parametrize("groups,nh,p,n", [
    (1, 4, 16, 32), (2, 4, 16, 32), (8, 8, 16, 32),     # ssm_step alone
    # 8 lane blocks, fewer than a grid step's 16: one step a row
    (1, 16, 64, 128), (2, 16, 64, 128), (8, 16, 64, 128),
    (4, 64, 64, 128),       # 32 lane blocks: a grid step two groups
    (8, 64, 64, 128),       # the published geometry: a grid step four
    (1, 128, 64, 128),      # 64 lane blocks: a group four grid steps
    (2, 128, 64, 128)])     # 64 lane blocks: a group two grid steps
def test_one_token_forms_are_the_token_scan_under_groups(groups, nh, p, n):
    """``cases.one_token_forms`` with B and C a group of heads', the
    kernel where the channels are whole (8, 128) tiles. The kernel takes
    the pairs a group a row and spreads them in VMEM, so the cases are
    the block shapes that makes delicate: a grid step that spans several
    groups (8 groups over 8 and over 32 lane blocks), a group that spans
    several grid steps (1 and 2 groups over 64), fewer lane blocks than
    a step's 16."""
    cases.one_token_forms(nh, p, n, groups, whole=1024)


def test_the_kernels_say_no_where_a_lane_block_would_straddle_groups(
        monkeypatch):
    """The choice is the backend's and the widths': at the published
    geometry (32 lane blocks, 8 groups of 4) both kernels serve; where a
    group is half a lane block, or a chunk step would span two groups,
    the XLA forms do."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sds = jax.ShapeDtypeStruct
    leaf = sds(ss.state_leaf_shape(7, 129, 64 * 64, 128), jnp.float32)
    assert leaf.shape == (7, 129, 32, 128, 128)
    assert ss.state_kernel_serves(leaf, 8) and ss.state_kernel_serves(leaf)
    assert ss.chunk_kernel_serves(leaf, 64, 8) \
        and ss.chunk_kernel_serves(leaf, 64)
    assert not ss.state_kernel_serves(leaf, 64)     # a group: 64 channels
    assert not ss.chunk_kernel_serves(leaf, 64, 16)  # a group: 2 blocks
    assert ss.state_kernel_serves(leaf, 16)         # 2 blocks a group
    assert not ss.state_kernel_serves(leaf, 3)


def test_the_gated_norm_is_a_groups(lend):
    """``y * silu(z)`` normed over each GROUP's channels under one
    weight of all the channels: a pattern of one mamba layer at two
    groups against the reference's mixer, whose group-wise norm is not
    the norm over all the channels."""
    cut = _cut(("mamba", "moe"))
    fields = {**TOY, **cut}
    params = sb.params(BLOCK, cut)
    prompt = sb.prompts(BLOCK, (24,))[0]
    lp = jax.tree.map(lambda a: a[0], params["ssm_layers"])
    x = params["embed"][prompt]
    w = np.asarray(lp["gate_norm"])
    y = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (5, 128)))
    grouped = reference_nemotron._rms_norm(
        y.reshape(5, 2, 64) * np.asarray([1.0, 3.0])[None, :, None],
        w.reshape(2, 64), 1e-5).reshape(5, 128)
    whole = reference_nemotron._rms_norm(
        y * np.repeat([1.0, 3.0], 64), w, 1e-5)
    assert _err(grouped, np.asarray(whole)) > 0.1
    with jax.default_matmul_precision("highest"):
        want, _ = reference_nemotron._mamba_mixer(x, lp, fields)
        hidden, _ = reference_nemotron._layers(params, fields, prompt,
                                               layers=1)
    np.testing.assert_allclose(hidden, x + want, atol=1e-5)
    uid, = sb.uids(1)
    eng = lend(fields=cut)
    assert _err(eng.put([uid], [prompt])[0],
                sb.reference(BLOCK, prompt, cut)[-1]) <= F32_TIGHT
    eng.flush(uid)


# ---------------------------------------------------------------------------
# (c) the expert layer: the relu^2 dispatch, the router, and the shares
# ---------------------------------------------------------------------------
def _expert_stack(fields, seed=4):
    shapes = weights_nemotron.shapes(fields)
    rng = np.random.default_rng(seed)
    return {k: jnp.asarray(
        rng.normal(size=s) / s[-1 if k == "e_up" else -2] ** 0.5
        if len(s) > 2 else 0.02 * rng.normal(size=s)
        if k == "moe_gate_bias" else 1.0 + 0.1 * rng.normal(size=s),
        jnp.float32) for k, (s, _) in shapes["layers"].items()}


def test_the_relu2_dispatch_is_a_loop_over_experts():
    """``dropless_topk_dispatch`` with the two-matrix form against a
    loop over the experts, whole and as a share (``held_from``), and one
    layer of a stack by where its groups lie (``stack_layer``)."""
    rng = np.random.default_rng(6)
    T, H, F, E, k = 40, 32, 24, 8, 3
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    x, wu, wd = f(T, H), f(2, E, F, H) / H ** 0.5, f(2, E, F, H) / F ** 0.5
    topi = jnp.asarray(np.stack([rng.choice(E, k, replace=False)
                                 for _ in range(T)]), jnp.int32)
    topv = jnp.asarray(rng.uniform(0.1, 1.0, (T, k)), jnp.float32)
    ragged, _, one = sharded_moe.expert_forms("relu2")
    assert ragged is sharded_moe.ragged_relu2_experts

    def loop(layer, first, held):
        out = np.zeros((T, H), np.float32)
        for t in range(T):
            for j in range(k):
                e = int(topi[t, j]) - first
                if 0 <= e < held:
                    out[t] += float(topv[t, j]) * np.asarray(one(
                        x[t:t + 1], wu[layer, first + e].T,
                        wd[layer, first + e]))[0]
        return out

    with jax.default_matmul_precision("highest"):
        for layer, first, held in ((0, 0, E), (1, 0, E // 2),
                                   (1, E // 2, E // 2)):
            got = sharded_moe.dropless_topk_dispatch(
                x, topi, topv,
                (wu[:, first:first + held], wd[:, first:first + held]),
                held, ragged, stack_layer=jnp.int32(layer),
                held_from=None if held == E else first)
            np.testing.assert_allclose(got, loop(layer, first, held),
                                       atol=2e-5)


def test_the_router_is_the_references():
    """Sigmoid scores, the six largest of score + bias, the chosen
    scores over their sum times 2.5: ``topk_routing`` and the
    reference's ``route`` choose and weigh alike at 128 experts."""
    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.normal(size=(64, 128)) * 1.5, jnp.float32)
    bias = jnp.asarray(rng.normal(size=(128,)) * 0.02, jnp.float32)
    chosen, w = reference_nemotron.route(
        logits, bias, {"moe_top_k": 6, "moe_routed_scale": 2.5})
    topi, topv = sharded_moe.topk_routing(logits, 6, "sigmoid", bias, True,
                                          2.5)
    np.testing.assert_array_equal(chosen, topi)
    np.testing.assert_allclose(w, topv, rtol=2e-6)
    np.testing.assert_allclose(np.asarray(topv).sum(-1), 2.5, rtol=1e-6)


def test_the_shares_add_up_to_the_uncut_layer():
    """``cases.shares_add_up`` on a stack of all 16 experts."""
    stack = _expert_stack({**TOY, "moe_experts_held": TOY["moe_num_experts"]})
    cases.shares_add_up(BLOCK, stack, ("e_up", "e_down"))


# ---------------------------------------------------------------------------
# (e) the four older pattern configurations' programs are the jaxprs they were
# ---------------------------------------------------------------------------
# sha256 (16 hex) of the program's jaxpr text at the configuration's toy
# widths, object addresses struck out, read by this function: on PR 52's
# parent commit (e16ef18) first, so that the walk, the state-space forms
# and the expert dispatch made for them the operations they had made; read
# again in PR 55, whose pick-major ``dropless_topk_dispatch`` is in all
# eight (with the parent's dispatch laid over that tree the eight digests
# were PR 52's to the digit: nothing else had moved)
PARENT_JAXPRS = {
    ("granite-4.0-h-small", "ragged_step"): "5de0c03281f18034",
    ("granite-4.0-h-small", "decode_window"): "5c6d2d136a7a4be5",
    ("trinity-mini", "ragged_step"): "a1d9b31452d67d2c",
    ("trinity-mini", "decode_window"): "6fac32732400fc53",
    ("ling-3.0-flash", "ragged_step"): "1cbe3a214edb6fd4",
    ("ling-3.0-flash", "decode_window"): "4c56d08286393d76",
    ("joyai-llm-flash", "ragged_step"): "3c076d8f7d440e1c",
    ("joyai-llm-flash", "decode_window"): "f29cfc684a88e169",
}


def _digest(jaxpr):
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("name,program", sorted(PARENT_JAXPRS))
def test_the_older_pattern_programs_are_the_jaxprs_they_were(name, program):
    from deepspeed_tpu.inference.v2.paged_model import (
        init_paged_kv_cache, paged_decode_window, paged_ragged_step)
    f = json.loads((REPO / "benchmark/configs" / f"{name}.json").read_text())
    cfg = TransformerConfig(**{**f["fields"], **f["toy_fields"]})
    params = jax.eval_shape(TransformerLM(cfg).init_params,
                            jax.random.PRNGKey(0))
    ring = 5 if "window" in cfg.layer_kinds else 0
    cache = jax.eval_shape(lambda: init_paged_kv_cache(
        cfg, 33, 8, jnp.float32, state_slots=4 if cfg.has_state else 0,
        window_blocks=4 * ring + 1))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    T, R, MB = 32, 4, 8
    extra = {}
    if cfg.has_state:
        extra["state_slots"] = i32(R)
    if ring:
        extra["window_tables"] = i32(R, ring)
    if program == "ragged_step":
        jaxpr = jax.make_jaxpr(
            lambda p, ids, rows, pos, ln, wb, wo, bt, li, c, kw:
            paged_ragged_step(cfg, p, ids, rows, pos, ln, wb, wo, bt, li, c,
                              8, use_kernel=True, **kw))(
            params, i32(T), i32(T), i32(T), i32(T), i32(T), i32(T),
            i32(R, MB), i32(R), cache, extra)
    else:
        jaxpr = jax.make_jaxpr(
            lambda p, t, pos, bt, c, sl, eos, alive, kw: paged_decode_window(
                cfg, p, t, pos, bt, c, sl, eos, 8, 4, use_kernel=True,
                alive=alive, **kw))(
            params, i32(R), i32(R), i32(R, MB), cache, i32(R), i32(R),
            jax.ShapeDtypeStruct((R,), jnp.bool_), extra)
    assert _digest(jaxpr) == PARENT_JAXPRS[name, program]
