"""The ``nemotron_h`` block served: layers of ONE sub-layer behind one norm
(a Mamba-2 mixer whose B and C are a GROUP of heads' | an expert layer of
two-matrix relu^2 experts that holds a SHARE of them | per-head attention
without positions), state slots AND a per-head pool in one cache, at toy
widths on the CPU, against the benchmark's plain reference
(``benchmark/reference_nemotron.py``: float32, a token at a time through
the recurrence, no cache).

Tolerances. A float32 engine differs from the reference by the order of
its sums and the chunked form of the recurrence (matmuls over a chunk's
pairs in place of rank-one updates a token): 2e-5 of the largest logit
is the other blocks' float32 limit and over five times what it reads
(3e-6; the state 6e-7). A state kept in bfloat16 and a dropped shared
expert each read over five times it (the test below shows both). The
toy's hard top-4 of 16 under a bf16 engine swaps an expert for its
runner-up against the float32 reference (0.05 to 0.7 of the largest
logit on the granite toy, whose router is this one's size), which is
why the cell's rehearsal runs a float32 engine; no bf16 engine is held
here. Two forms of one recurrence, both float32: 2e-5 of the largest
output (they read 2e-6).
"""

import hashlib
import json
import re
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import reference_nemotron, weights_nemotron
from benchmark import run as harness
from deepspeed_tpu.inference.v2 import InferenceEngineV2, paged_model
from deepspeed_tpu.inference.v2.kernels import state_space as ss
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.moe import sharded_moe
from deepspeed_tpu.telemetry import get_registry

REPO = Path(__file__).resolve().parents[3]
CONFIG = json.loads(
    (REPO / "benchmark/configs/nemotron-3-nano-30b-a3b.json").read_text())
TOY = harness.merge(CONFIG["fields"], CONFIG["toy_fields"])
F32_TIGHT = 2e-5
SEED = 5


def _engine(fields=TOY, seqs=4, budget=256, **engine):
    cfg = TransformerConfig(**fields)
    return InferenceEngineV2(TransformerLM(cfg), {
        "dtype": "float32", "use_paged_kernel": True, "decode_window": 4,
        **engine,
        "state_manager": {"max_tracked_sequences": seqs,
                          "max_ragged_batch_size": budget,
                          "max_seq_len": 256, "block_size": 16,
                          "num_blocks": 60}},
        params=weights_nemotron.make(fields, SEED, "float32"))


def _prompts(lengths=(20, 70, 5), seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, TOY["vocab_size"], n) for n in lengths]


def _params(fields=TOY):
    return weights_nemotron.make(fields, SEED, "float32")


def _reference(prompt, fields=TOY):
    return np.asarray(reference_nemotron.logits(_params(fields), fields,
                                                prompt))


def _err(got, want):
    return float(np.abs(np.asarray(got, np.float32) - want).max()
                 / np.abs(want).max())


def _cut(pattern):
    """The toy with another pattern of as many layers."""
    return {**TOY, "layer_types": list(pattern), "num_layers": len(pattern)}


# ---------------------------------------------------------------------------
# (a) the configuration, and the engine against the plain reference
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


def test_put_logits_match_the_reference_in_one_step_and_in_chunks():
    """Rows of 20, 70 and 5 tokens in one ragged step; the same rows
    with a step's budget of 32 tokens (a row's prompt in chunks, its
    state carried in its slot and its keys in the pool)."""
    prompts = _prompts()
    reg = get_registry()
    for budget in (256, 32):
        eng = _engine(budget=budget)
        assert set(eng.kv_cache) == {"k_full", "v_full", "ssm_state",
                                     "ssm_conv"}
        assert eng.kv_cache["ssm_state"].shape[0] == 7 \
            and eng.kv_cache["k_full"].shape[0] == 2
        assert reg.get("inference_ssm_groups").value == 2
        before = reg.family_total("inference_prefill_chunks_total")
        got = eng.put([0, 1, 2], prompts)
        chunks = reg.family_total("inference_prefill_chunks_total") - before
        # a put() that fits one step counts no chunk; 95 tokens under a
        # budget of 32 (a row's share 8) go in as eight steps
        assert chunks == (0 if budget == 256 else 8), chunks
        for i, p in enumerate(prompts):
            assert _err(got[i], _reference(p)[-1]) <= F32_TIGHT, (budget, i)


def test_decode_through_slot_and_pool_matches_the_reference():
    """The ragged step leaves each row's state in its slot and its keys
    and values in the pool; decode windows of 4 read and extend both. At
    EVERY generated position the engine's token is the reference's best
    on the same prefix and the slot then holds the reference's state
    (layer 0, ahead of every routed expert, and the next two), so a
    state, a group, a slot, a conv tap or a page read wrong shows."""
    eng = _engine()
    prompts = _prompts((37, 20, 70))
    reg = get_registry()
    before = {form: reg.get("moe_form_launches_total").labels(
        program="decode_window", form=form).value
        for form in ("relu2", "swiglu")}
    outs = eng.generate(prompts, max_new_tokens=13, temperature=0.0,
                        eos_token_id=None, keep_sequences=True)
    after = {form: reg.get("moe_form_launches_total").labels(
        program="decode_window", form=form).value
        for form in ("relu2", "swiglu")}
    # 12 decode steps x 7 expert layers ran the relu2 form, none the other
    assert after["relu2"] - before["relu2"] == 12 * 7
    assert after["swiglu"] == before["swiglu"]
    assert eng.state_manager.state_slots_in_use() == 3
    for uid, (prompt, out) in enumerate(zip(prompts, outs)):
        out = np.asarray(out)
        assert len(out) == len(prompt) + 13
        ref = _reference(out[:-1])[len(prompt) - 1:]
        np.testing.assert_array_equal(out[len(prompt):], ref.argmax(-1))
        state = eng.sequence_state(uid)
        assert state["ssm_state"].shape == (7, 8, 16, 32)
        assert state["ssm_conv"].shape == (7, 3, 8 * 16 + 2 * 2 * 32)
        want = np.asarray(reference_nemotron.leading_states(
            _params(), TOY, out[:-1], layers=3))
        for layer in range(3):
            err = np.linalg.norm(state["ssm_state"][layer] - want[layer]) \
                / np.linalg.norm(want[layer])
            assert err <= F32_TIGHT, (uid, layer, err)
        eng.flush(uid)
    assert eng.state_manager.state_slots_in_use() == 0


def test_the_tolerance_is_tight_enough_for_its_controls():
    """A state kept in bfloat16 (``state_dtype``, the cell's control)
    and a shared expert that is dropped each fail the float32 limit that
    the engine as it stands passes."""
    prompts = _prompts((70,))
    want = _reference(prompts[0])[-1]
    assert _err(_engine().put([0], prompts)[0], want) <= F32_TIGHT
    control = _engine(state_dtype="bfloat16", budget=32)
    assert control.kv_cache["ssm_state"].dtype == jnp.bfloat16
    # the state is rounded where a launch hands it on: three chunks
    assert _err(control.put([0], prompts)[0], want) > 5 * F32_TIGHT
    dropped = _engine()
    dropped.params["layers"]["shared_down"] = jnp.zeros_like(
        dropped.params["layers"]["shared_down"])
    assert _err(dropped.put([0], prompts)[0], want) > 5 * F32_TIGHT


@pytest.mark.parametrize("pattern", [
    ("moe", "mamba", "attention", "moe", "mamba"),      # ends: a lone mixer
    ("moe", "moe", "mamba", "mamba", "attention", "attention", "moe")])
def test_patterns_that_start_with_experts_and_end_with_a_mixer(pattern):
    """A pattern whose first layer is an expert layer (no mixer ahead of
    it, and no state-space layer ahead of every routed expert), runs of
    two of a kind, and a last layer that is a lone mixer."""
    fields = _cut(pattern)
    cfg = TransformerConfig(**fields)
    runs = paged_model._layer_runs(cfg)
    assert sum(n for *_, n in runs) == len(pattern)
    assert [k for k, *_ in runs] == [k for i, k in enumerate(
        cfg.layer_kinds) if i == 0 or cfg.layer_kinds[i - 1] != k]
    eng = _engine(fields, budget=32)
    prompts = _prompts((40, 9))
    got = eng.put([0, 1], prompts)
    for i, p in enumerate(prompts):
        assert _err(got[i], _reference(p, fields)[-1]) <= F32_TIGHT, i
    for uid in (0, 1):
        eng.flush(uid)
    outs = eng.generate(prompts, max_new_tokens=6, temperature=0.0,
                        eos_token_id=None)
    for prompt, out in zip(prompts, outs):
        ref = _reference(np.asarray(out)[:-1], fields)[len(prompt) - 1:]
        np.testing.assert_array_equal(np.asarray(out)[len(prompt):],
                                      ref.argmax(-1))


# ---------------------------------------------------------------------------
# (b) the two forms of the recurrence under groups, and their kernels
# ---------------------------------------------------------------------------
def _scan(x, dt, a, b, c, s0, groups):
    """The recurrence a token at a time: x [T, nh, p], dt [T, nh], b and
    c [T, groups * n], s0 [nh, p, n]; head h reads group h // (nh /
    groups)."""
    nh = x.shape[1]
    of = jnp.arange(nh) // (nh // groups)

    def token(s, t):
        xt, dtt, bt, ct = t
        bt, ct = (v.reshape(groups, -1)[of] for v in (bt, ct))   # [nh, n]
        s = jnp.exp(dtt * a)[:, None, None] * s \
            + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, ct)
    return jax.lax.scan(token, s0, (x, dt, b, c))


def _case(nh, p, n, groups, lengths, T, seed=0, slots=6):
    rng = np.random.default_rng(seed)
    C = nh * p
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(2.0),
                                        (T, nh))), jnp.float32)
    counts = jnp.asarray(lengths, jnp.int32)
    return dict(
        leaf=f(*ss.state_leaf_shape(2, slots, C, n)), layer=jnp.int32(1),
        slots=jnp.asarray([i % (slots - 1) + 1 if n_ else 0
                           for i, n_ in enumerate(lengths)], jnp.int32),
        fresh=jnp.asarray([i % 2 == 0 for i in range(len(lengths))]),
        starts=jnp.cumsum(counts) - counts, counts=counts,
        xbc=f(T, C + 2 * groups * n), dt=dt,
        a=-jnp.asarray(rng.uniform(1, 16, (nh,)), jnp.float32))


def _against_the_scan(case, y, leaf, nh, groups):
    n = case["leaf"].shape[3]
    C = case["xbc"].shape[1] - 2 * groups * n
    x, b, c = (case["xbc"][:, :C], case["xbc"][:, C:C + groups * n],
               case["xbc"][:, C + groups * n:])
    with jax.default_matmul_precision("highest"):
        for r, n_ in enumerate(np.asarray(case["counts"])):
            if not n_:
                continue
            at = slice(int(case["starts"][r]), int(case["starts"][r]) + n_)
            slot = case["slots"][r]
            s0 = jnp.where(case["fresh"][r], 0.0,
                           ss.heads_of(case["leaf"][1, slot], nh))
            s1, want = _scan(x[at].reshape(n_, nh, -1), case["dt"][at],
                             case["a"], b[at], c[at], s0, groups)
            assert _err(y[at], np.asarray(want).reshape(n_, -1)) \
                <= F32_TIGHT, r
            assert _err(ss.heads_of(leaf[1, slot], nh),
                        np.asarray(s1)) <= F32_TIGHT, r
    np.testing.assert_array_equal(leaf[0], case["leaf"][0])


@pytest.mark.parametrize("groups", [1, 2, 8])
@pytest.mark.parametrize("nh,p,n", [(8, 16, 32), (16, 8, 16)])
def test_chunked_form_is_the_token_scan_at_any_group_count(nh, p, n, groups):
    """Rows of 5, 0, 37, 16 and 1 tokens, fresh and continued from their
    slots, through ``ssm_chunked`` with B and C a group of heads: each
    row's outputs and final state are the token scan's. (At these widths
    a lane block holds every group: the XLA forms serve that.)"""
    case = _case(nh, p, n, groups, (5, 0, 37, 16, 1), 62)
    y, leaf = ss.ssm_chunked(**case, chunk=16)
    _against_the_scan(case, y, leaf, nh, groups)


@pytest.mark.parametrize("groups,nh,p,lengths,T,chunk", [
    (2, 16, 64, (40, 3, 0, 100), 256, 32),  # a group a grid step
    (1, 16, 64, (7, 70), 128, 64),          # one group, two grid steps
    (2, 32, 64, (33, 20), 64, 32)])         # a group two grid steps
def test_chunk_kernel_is_the_token_scan_under_groups(groups, nh, p, lengths,
                                                     T, chunk):
    """``ssm_chunk_fwd`` under the TPU interpreter: a grid step (four
    lane blocks, 512 channels) takes ITS group's B and C out of the one
    token buffer."""
    case = _case(nh, p, 128, groups, lengths, T)
    y, leaf = ss.ssm_chunk_fwd(**case, chunk=chunk, interpret=True)
    _against_the_scan(case, y, leaf, nh, groups)


def _one_token_case(groups, nh, p, n):
    """(leaf, layer, slots, fresh, x, dt, a, b, c) of three rows, the
    middle one fresh between two kept ones."""
    rng = np.random.default_rng(1)
    C, N = nh * p, 3
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    leaf0 = f(*ss.state_leaf_shape(2, 5, C, n))
    x, b, c = f(N, C), f(N, groups * n), f(N, groups * n)
    dt = jnp.asarray(rng.uniform(0.01, 1.0, (N, nh)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, (nh,)), jnp.float32)
    return (leaf0, jnp.int32(1), jnp.asarray([2, 4, 1]),
            jnp.asarray([False, True, False]), x, dt, a, b, c)


@pytest.mark.parametrize("groups,nh,p,n", [
    (1, 4, 16, 32), (2, 4, 16, 32), (8, 8, 16, 32),     # ssm_step alone
    # 8 lane blocks, fewer than a grid step's 16: one step a row
    (1, 16, 64, 128), (2, 16, 64, 128), (8, 16, 64, 128),
    (4, 64, 64, 128),       # 32 lane blocks: a grid step two groups
    (8, 64, 64, 128),       # the published geometry: a grid step four
    (1, 128, 64, 128),      # 64 lane blocks: a group four grid steps
    (2, 128, 64, 128)])     # 64 lane blocks: a group two grid steps
def test_one_token_forms_are_the_token_scan_under_groups(groups, nh, p, n):
    """``ssm_step`` and, where the channels are whole lane blocks, the
    kernel ``ssm_state_update`` (interpreted) on three rows' slots, a
    fresh row between two kept ones: one token of the scan with each
    head reading ITS group's B and C, the other slots and the other
    layer untouched. The kernel takes the pairs a group a row and
    spreads them in VMEM, so the cases are the block shapes that makes
    delicate: a grid step that spans several groups (8 groups over 8
    and over 32 lane blocks), a group that spans several grid steps (1
    and 2 groups over 64), fewer lane blocks than a step's 16. Its
    state and ``y`` are ``ssm_step``'s too (the same float32 operations
    on the same values, a compiled product and sum rounding once where
    the eager ones round twice), and the leaf goes in aliased to the
    leaf that comes out."""
    args = _one_token_case(groups, nh, p, n)
    leaf0, _, slots, fresh, x, dt, a, b, c = args
    C, N = nh * p, x.shape[0]
    forms = [ss.ssm_step] + [
        lambda *args: ss.ssm_state_update(*args, interpret=True)
    ] * (C % 1024 == 0)
    out = []
    for form in forms:
        y, leaf = form(*args)
        out.append((y, leaf))
        for r in range(N):
            s0 = jnp.where(fresh[r], 0.0, ss.heads_of(leaf0[1, slots[r]], nh))
            s1, want = _scan(x[r:r + 1].reshape(1, nh, p), dt[r:r + 1], a,
                             b[r:r + 1], c[r:r + 1], s0, groups)
            assert _err(y[r], np.asarray(want).reshape(C)) <= F32_TIGHT
            assert _err(ss.heads_of(leaf[1, slots[r]], nh),
                        np.asarray(s1)) <= F32_TIGHT
        np.testing.assert_array_equal(leaf[0], leaf0[0])
        np.testing.assert_array_equal(leaf[1, 3], leaf0[1, 3])
    if len(out) == 2:
        for step, kernel in zip(*out):
            assert _err(kernel, np.asarray(step)) <= F32_TIGHT
        (call,) = [e for e in jax.make_jaxpr(forms[1])(*args).eqns
                   if e.primitive.name == "pallas_call"]
        assert call.params["input_output_aliases"] == ((3, 0),)


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


def test_the_gated_norm_is_a_groups():
    """``y * silu(z)`` normed over each GROUP's channels under one
    weight of all the channels: a pattern of one mamba layer at two
    groups against the reference's mixer, whose group-wise norm is not
    the norm over all the channels."""
    fields = _cut(("mamba", "moe"))
    params = _params(fields)
    prompt = _prompts((24,))[0]
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
    assert _err(_engine(fields).put([0], [prompt])[0],
                _reference(prompt, fields)[-1]) <= F32_TIGHT


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
    """The guide's test of a cut in experts: the routed output of the
    share that holds experts 0 .. E/2 - 1 plus that of the share that
    holds the rest, the shared expert counted once, is the uncut
    reference's expert layer; and the program's expert layer on either
    share is that share's reference."""
    E = TOY["moe_num_experts"]
    whole = {**TOY, "moe_experts_held": E}
    stack = _expert_stack(whole)
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(24, TOY["hidden_size"])), jnp.float32)
    experts, half = ("e_up", "e_down"), E // 2

    def share(first):
        cut = {k: v[:, first:first + half] if k in experts else v
               for k, v in stack.items()}
        return cut, {**TOY, "moe_experts_held": half,
                     "moe_experts_first": first}

    with jax.default_matmul_precision("highest"):
        routed, shared = reference_nemotron.expert_layer(x, stack, 3, whole)
        parts = []
        for first in (0, half):
            cut, fields = share(first)
            r, s = reference_nemotron.expert_layer(x, cut, 3, fields)
            np.testing.assert_allclose(s, shared, atol=1e-6)
            parts.append(r)
            cfg = TransformerConfig(**fields)
            lp = {k: v[3] for k, v in cut.items()}
            hn = paged_model._norm(cfg, x, lp["mlp_norm"])
            got, _ = paged_model._moe_routed(
                cfg, lp, hn, router_precision=jax.lax.Precision.HIGHEST)
            np.testing.assert_allclose(
                got, r + s, atol=F32_TIGHT * float(jnp.abs(r + s).max()))
        assert float(jnp.abs(parts[0]).max()) > 0 \
            and float(jnp.abs(parts[1]).max()) > 0
        np.testing.assert_allclose(
            parts[0] + parts[1], routed,
            atol=F32_TIGHT * float(jnp.abs(routed).max()))


# ---------------------------------------------------------------------------
# (d) what is not served is refused, each by the new descriptions' names
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("engine,word", [
    ({"tensor_parallel_size": 2}, "tensor_parallel_size"),
    ({"max_lora_adapters": 2}, "max_lora_adapters"),
    ({"kv_quant": True}, "kv_quant"),
    ({"state_manager": {"enable_prefix_caching": True}},
     "wrong recurrent state")])
def test_refusals_at_construction(engine, word):
    cfg = TransformerConfig(**TOY)
    with pytest.raises(NotImplementedError,
                       match="state-space layers.*" + word):
        InferenceEngineV2(TransformerLM(cfg), {"dtype": "float32", **engine})


def test_speculation_handoff_and_the_other_forwards_refuse_by_name():
    eng = _engine()
    prompts = _prompts((12,))
    with pytest.raises(NotImplementedError, match="verify pass"):
        eng.generate(prompts, max_new_tokens=2, speculative=True)
    eng.put([7], prompts)
    from deepspeed_tpu.inference.v2.serve import handoff
    with pytest.raises(NotImplementedError, match="no state slot"):
        handoff.export_sequence(eng, 7)
    model = TransformerLM(TransformerConfig(**TOY))
    params = model.init_params(jax.random.PRNGKey(0))
    batch = {"input_ids": jnp.zeros((1, 8), jnp.int32)}
    names = ("'moe' layers", "mamba_n_groups", "moe_expert_form='relu2'")
    for call in (lambda: model.apply(params, batch),
                 lambda: model.forward_hidden(params, batch["input_ids"]),
                 lambda: model.forward_cached(params, batch["input_ids"],
                                              None, 0)):
        with pytest.raises(NotImplementedError) as e:
            call()
        for name in names:
            assert name in str(e.value), (name, str(e.value))


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
