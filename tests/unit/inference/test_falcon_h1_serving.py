"""The ``falcon_h1`` block's own: a layer whose mixer is TWO mixers on one
norm, as the configuration writes it down; its one parameter stack and
its place in BOTH families of cache leaves; the keys its attention half
leaves in the pool beside the state its other half leaves in the slot;
the recurrence's kernels at what is new in it (a head a lane block, a
state of 256, two groups); the scopes a trace reads it by; and the plain
reference (``benchmark/reference_falcon_h1.py``) against the model's
PUBLISHED code. What every served block is held to (the engine against
the reference through ``put()`` in 1, 2 and 5 chunk steps and through
decode windows, rows of unequal length, the kept state, the controls,
the refusals) is the contract's (``test_served_block_contract.py``), on
this block's row of ``served_blocks.py``, where the limits are justified.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import paged_model
from deepspeed_tpu.inference.v2.kernels import state_space as ss
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.telemetry import get_registry
from tests.unit.inference import served_block_contract as contract
from tests.unit.inference import served_blocks as sb
from tests.unit.inference import state_space_cases as cases

BLOCK = sb.BLOCKS["falcon-h1-34b-instruct"]
globals().update(contract.clauses(BLOCK))     # the contract's cases of this row
TOY = BLOCK.toy
SCALES = ("embed_scale", "logit_scale", "attn_in_scale", "key_scale",
          "attn_out_scale", "ssm_in_scale", "ssm_z_scale", "ssm_x_scale",
          "ssm_b_scale", "ssm_c_scale", "ssm_dt_scale", "ssm_out_scale",
          "mlp_gate_scale", "mlp_down_scale")


# ---------------------------------------------------------------------------
# (a) the configuration, the tree and the cache
# ---------------------------------------------------------------------------
def test_the_two_mixer_layer_is_written_down():
    cfg = TransformerConfig(**TOY)
    n = cfg.num_layers
    assert cfg.layer_kinds == ("hybrid",) * n
    assert cfg.has_state and cfg.caches_positions and cfg.walks_runs
    assert paged_model._layer_runs(cfg) == [("hybrid", False, 0, n)]
    # a place in BOTH families of leaves, found a family and not a kind
    assert cfg.leaf_places("full") == cfg.leaf_places("ssm") == n
    assert cfg.leaf_places("full", 2) == cfg.leaf_places("ssm", 2) == 2
    mixed = TransformerConfig(**{**TOY, "num_layers": 4, "layer_types": [
        "mamba", "mamba_attention", "attention", "mamba_attention"]})
    assert [mixed.leaf_places("ssm", i) for i in range(5)] == [0, 1, 2, 2, 3]
    assert [mixed.leaf_places("full", i) for i in range(5)] == [0, 0, 1, 2, 3]
    # the toy keeps what is new in the block
    assert cfg.mamba_n_groups == 2 and cfg.mamba_d_state != cfg.mamba_d_head
    assert cfg.mamba_d_inner != cfg.mamba_expand * cfg.hidden_size
    assert cfg.num_heads // cfg.kv_heads == 5
    values = [TOY[k] for k in SCALES]
    assert 1.0 not in values and len(set(values)) == len(values)
    for word in ("mamba_attention layers", "key_scale", "mlp_down_scale",
                 "mamba_n_groups", "logit_scale"):
        assert word in cfg.served_only, word
    with pytest.raises(ValueError, match="a mamba layer needs"):
        TransformerConfig(**{**TOY, "mamba_d_state": 0})
    with pytest.raises(ValueError, match="two mixers on one norm"):
        TransformerConfig(**{**TOY, "norm_scheme": "sandwich"})
    with pytest.raises(NotImplementedError, match="give layer_types"):
        TransformerConfig(hidden_size=64, num_heads=4, ssm_out_scale=0.5)
    with pytest.raises(NotImplementedError, match="not by an expert layer"):
        TransformerConfig(**{**TOY, "moe_num_experts": 4})
    # a retention mixer takes none of the attention's three multipliers
    for scale in ("attn_in_scale", "key_scale", "attn_out_scale"):
        with pytest.raises(NotImplementedError, match="power_retention ones"):
            TransformerConfig(**{**sb.BLOCKS["brumby-14b-base"].toy,
                                 scale: 0.5})


def test_one_stack_holds_both_halves_and_one_norm(lend):
    """The kind's ONE parameter stack has both halves' leaves under the
    names the halves read and one ``attn_norm``; the cache gives every
    layer a place in the full pool and in the state leaves; both gauges
    are set for the same layers."""
    cfg = TransformerConfig(**TOY)
    tree = jax.eval_shape(TransformerLM(cfg).init_params,
                          jax.random.PRNGKey(0))
    assert set(tree) == {"embed", "lm_head", "final_norm", "hybrid_layers",
                         "layers"}
    assert set(tree["hybrid_layers"]) == {
        "attn_norm", "w_in", "conv", "conv_b", "dt_bias", "a_log", "d_skip",
        "gate_norm", "w_out", "wq", "wk", "wv", "wo"}
    assert set(tree["layers"]) == {"mlp_norm", "w_gate", "w_up", "w_down"}
    assert {k: v.shape for k, v in tree["hybrid_layers"].items()} == {
        k: s for k, (s, _) in
        BLOCK.weights.shapes(TOY)["hybrid_layers"].items()}
    eng = sb.engine(BLOCK)      # its own: the gauges are the last one built's
    n, slots = cfg.num_layers, BLOCK.seqs + 1
    assert {k: v.shape for k, v in eng.kv_cache.items()} == {
        "k_full": (n, 60, 16, 2 * 16), "v_full": (n, 60, 16, 2 * 16),
        "ssm_state": (n, slots, 1, 32, 96),
        "ssm_conv": (n, slots, 3, 1, 96 + 2 * 2 * 32)}
    reg = get_registry()
    assert reg.get("inference_state_bytes").value == sum(
        eng.kv_cache[k].nbytes for k in ("ssm_state", "ssm_conv"))
    assert reg.get("inference_kv_pool_bytes").labels(kind="full").value \
        == sum(eng.kv_cache[k].nbytes for k in ("k_full", "v_full"))
    assert reg.get("inference_ssm_groups").value == 2


def test_the_pool_holds_the_references_keys_beside_the_slots_state(lend):
    """After a prompt in chunk steps and decode windows a row's BLOCKS
    hold, layer by layer, the keys the source caches (times
    ``key_scale``, rotated) and the values, and its SLOT the state, of
    every token but the last: both halves of every layer against the
    reference after the same tokens."""
    eng = lend(budget=32)
    prompts = sb.prompts(BLOCK, (40, 23))
    uids = sb.uids(len(prompts))
    outs = eng.generate(prompts, max_new_tokens=9, temperature=0.0,
                        eos_token_id=None, uids=uids, keep_sequences=True)
    n = TOY["num_layers"]
    for uid, out in zip(uids, outs):
        fed = np.asarray(out)[:-1]
        kv = eng.sequence_kv(uid, "full")
        np.testing.assert_array_equal(kv["positions"], np.arange(len(fed)))
        keys, values = BLOCK.reference.leading_kv(
            sb.params(BLOCK), TOY, fed, layers=n)
        assert sb.err(kv["k"], keys) <= sb.F32
        assert sb.err(kv["v"], values) <= sb.F32
        assert sb.layer_err(
            eng.sequence_state(uid)["ssm_state"],
            BLOCK.reference.leading_states(sb.params(BLOCK), TOY, fed,
                                           layers=n)) <= sb.F32
        eng.flush(uid)


# ---------------------------------------------------------------------------
# (b) the recurrence's kernels at what is new: a head a lane block, a
# state of 256, two groups
# ---------------------------------------------------------------------------
def test_chunk_kernel_is_the_token_scan_at_a_head_a_lane_block():
    """``ssm_chunk_fwd`` under the TPU interpreter at heads of 128 (a
    head's scalars a whole lane block's: the kernel's own path), a state
    of 256, two groups of four lane blocks: rows that share windows,
    fresh and continued."""
    case = cases.case(8, 128, 256, (40, 3, 0, 100), 256, 2)
    y, leaf = ss.ssm_chunk_fwd(**case, chunk=32, interpret=True)
    cases.against_the_scan(case, y, leaf, 8, 2)


def test_one_token_forms_are_the_token_scan_at_a_state_of_256():
    """``ssm_step`` and the kernel ``ssm_state_update`` (interpreted) at
    the published geometry, 32 lane blocks of a state of 256 in two
    groups: a grid step takes 8 lane blocks (1 MB of states, as 16 are
    at a state of 128), a group two grid steps."""
    leaf = jax.ShapeDtypeStruct(ss.state_leaf_shape(4, 65, 32 * 128, 256),
                                jnp.float32)
    assert leaf.shape == (4, 65, 32, 256, 128)
    assert ss._blocks_a_step(32, 256) == 8 and ss._blocks_a_step(64, 128) \
        == ss._blocks_a_step(16, 128) == 16 and ss._blocks_a_step(8, 128) == 8
    cases.one_token_forms(32, 128, 256, 2, whole=1024)


# ---------------------------------------------------------------------------
# (c) the scopes a trace reads the layer by
# ---------------------------------------------------------------------------
def test_both_halves_and_the_join_stand_under_the_two_mixer_scope(lend):
    """``hybrid_mixer`` wraps ``attention`` and ``ssm_mixer`` with their
    own scopes inside, and the join stands under ``attention`` >
    ``hybrid_join``: the program's table reads every operation of the
    layer by a word it has (no ``other``), and a reader of the wrapping
    scope finds both halves by their path."""
    from deepspeed_tpu.telemetry import memory
    from deepspeed_tpu.utils.xla_profile import serve_phase, serve_scope
    eng = lend()
    prompts = sb.prompts(BLOCK)
    eng.generate(prompts, max_new_tokens=5, temperature=0.0,
                 eos_token_id=None, uids=sb.uids(len(prompts)))
    for program, form in (("ragged_step", "ssm_scan"),
                          ("decode_window_greedy", "ssm_state")):
        # every engine of the process offers its maps under the name:
        # this block's operations are the ones under the wrapping scope
        paths = {p for m in memory.scopes_offered(program) if m
                 for p in m.values()}
        inside = {p for p in paths if "/hybrid_mixer/" in p}
        assert inside, program
        for words in (("ssm_mixer", "ssm_proj"), ("ssm_mixer", "ssm_conv"),
                      ("ssm_mixer", form), ("ssm_mixer", "ssm_gate_norm"),
                      ("ssm_mixer", "ssm_out"), ("attention", "qkv_proj"),
                      ("attention", "attn_kernel"), ("attention", "out_proj"),
                      ("attention", "hybrid_join")):
            assert any("/hybrid_mixer/" + "/".join(words) + "/" in p
                       for p in inside), (program, words)
        assert {serve_scope(p) for p in inside} >= {"attention", form}
        assert "other" not in {serve_phase(p) for p in inside}
        join = {p for p in inside if "/hybrid_join/" in p}
        assert {serve_phase(p) for p in join} == {"attn_proj"}


# ---------------------------------------------------------------------------
# (d) the reference is the published code's
# ---------------------------------------------------------------------------
def test_the_reference_is_the_published_modelling_code():
    """``FalconH1ForCausalLM`` (transformers' ``modeling_falcon_h1.py``,
    its naive ``torch_forward`` path, float32, CPU) built from a toy
    ``FalconH1Config`` with the row's weights copied in gives the
    reference's logits: the equations, the order of the projection's
    segments, the grouped gated norm, which group a head reads, where
    every multiplier stands."""
    torch = pytest.importorskip("torch")
    hf = pytest.importorskip("transformers.models.falcon_h1")
    f = {**TOY, "num_layers": 2, "layer_types": TOY["layer_types"][:2]}
    config = hf.FalconH1Config(
        vocab_size=f["vocab_size"], hidden_size=f["hidden_size"],
        intermediate_size=f["intermediate_size"], num_hidden_layers=2,
        num_attention_heads=f["num_heads"],
        num_key_value_heads=f["num_kv_heads"],
        head_dim=f["head_dim_override"], rms_norm_eps=f["norm_eps"],
        rope_theta=f["rope_theta"], max_position_embeddings=256,
        tie_word_embeddings=False, attention_bias=False, mlp_bias=False,
        projectors_bias=False, mamba_proj_bias=False, mamba_conv_bias=True,
        mamba_d_ssm=f["mamba_n_heads"] * f["mamba_d_head"],
        mamba_n_heads=f["mamba_n_heads"], mamba_d_head=f["mamba_d_head"],
        mamba_d_state=f["mamba_d_state"], mamba_n_groups=f["mamba_n_groups"],
        mamba_d_conv=f["mamba_d_conv"], mamba_expand=f["mamba_expand"],
        mamba_chunk_size=32, mamba_norm_before_gate=False,
        mamba_rms_norm=True, embedding_multiplier=f["embed_scale"],
        lm_head_multiplier=1.0 / f["logit_scale"],
        attention_in_multiplier=f["attn_in_scale"],
        attention_out_multiplier=f["attn_out_scale"],
        key_multiplier=f["key_scale"], ssm_in_multiplier=f["ssm_in_scale"],
        ssm_out_multiplier=f["ssm_out_scale"],
        ssm_multipliers=[f[f"ssm_{k}_scale"] for k in "zxbc"]
        + [f["ssm_dt_scale"]],
        mlp_multipliers=[f["mlp_gate_scale"], f["mlp_down_scale"]],
        attn_implementation="eager")
    model = hf.FalconH1ForCausalLM(config).float().eval()
    p = BLOCK.weights.make(f, 3, jnp.float32)

    def t(a, transpose=False):
        a = torch.tensor(np.asarray(a, np.float32))
        return (a.T if transpose else a).contiguous()

    mix, mlp = p["hybrid_layers"], p["layers"]
    state = {"model.embed_tokens.weight": t(p["embed"]),
             "model.final_layernorm.weight": t(p["final_norm"]),
             "lm_head.weight": t(p["lm_head"], True)}
    for i in range(2):
        at = f"model.layers.{i}."
        state.update({
            at + "input_layernorm.weight": t(mix["attn_norm"][i]),
            at + "pre_ff_layernorm.weight": t(mlp["mlp_norm"][i]),
            at + "mamba.in_proj.weight": t(mix["w_in"][i], True),
            at + "mamba.conv1d.weight":
                t(mix["conv"][i], True)[:, None, :].contiguous(),
            at + "mamba.conv1d.bias": t(mix["conv_b"][i]),
            at + "mamba.dt_bias": t(mix["dt_bias"][i]),
            at + "mamba.A_log": t(mix["a_log"][i]),
            at + "mamba.D": t(mix["d_skip"][i]),
            at + "mamba.norm.weight": t(mix["gate_norm"][i]),
            at + "mamba.out_proj.weight": t(mix["w_out"][i], True),
            **{at + f"self_attn.{name}_proj.weight": t(mix[leaf][i], True)
               for name, leaf in (("q", "wq"), ("k", "wk"), ("v", "wv"),
                                  ("o", "wo"))},
            **{at + f"feed_forward.{name}_proj.weight":
               t(mlp["w_" + name][i], True)
               for name in ("gate", "up", "down")}})
    missing, unexpected = model.load_state_dict(state, strict=False)
    assert not missing and not unexpected, (missing, unexpected)
    ids = np.random.default_rng(0).integers(0, f["vocab_size"], 45)
    with torch.no_grad():
        got = model(torch.tensor(ids)[None], use_cache=False,
                    logits_to_keep=0).logits[0].numpy()
    assert sb.err(got, BLOCK.reference.logits(p, f, ids)) <= 1e-5
