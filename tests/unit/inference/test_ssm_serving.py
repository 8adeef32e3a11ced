"""The ``granitemoehybrid`` block's own: its pattern as the source spells
it, the two forms of the Mamba-2 recurrence and their kernels against the
token scan, the router's two routes and the shares of the experts. What
every served block is held to (the engine against the plain reference
``benchmark/reference_granite.py``, its controls, its refusals) is the
contract's (``test_served_block_contract.py``), on this block's row of
``served_blocks.py``, where the limits are justified.

Two forms of one recurrence, both float32: 2e-5 of the largest output
(they read 2e-6).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import paged_model
from deepspeed_tpu.inference.v2.kernels import linear_attention as la
from deepspeed_tpu.inference.v2.kernels import state_space as ss
from deepspeed_tpu.models import TransformerConfig
from deepspeed_tpu.moe.sharded_moe import topk_routing
from tests.unit.inference import served_block_contract as contract
from tests.unit.inference import served_blocks as sb
from tests.unit.inference import state_space_cases as cases

BLOCK = sb.BLOCKS["granite-4.0-h-small"]
globals().update(contract.clauses(BLOCK))     # the contract's cases of this row
TOY = BLOCK.toy
reference_granite = BLOCK.reference


# ---------------------------------------------------------------------------
# (a) the configuration
# ---------------------------------------------------------------------------
def test_the_pattern_is_written_down_as_the_source_spells_it():
    cfg = TransformerConfig(**TOY)
    assert cfg.layer_kinds == ("ssm",) * 5 + ("full",) + ("ssm",) * 4
    assert cfg.has_state and cfg.walks_runs and cfg.pattern
    for word in ("mamba layers", "positional='none'", "attn_scale",
                 "residual_scale", "logit_scale"):
        assert word in cfg.served_only, word
    assert paged_model._layer_runs(cfg) == [
        ("ssm", True, 0, 5), ("full", True, 5, 1), ("ssm", True, 6, 4)]
    # the inner width is heads x head width whatever mamba_expand says
    # (PR 52); what is held is heads a whole multiple of the groups
    assert TransformerConfig(**{**TOY, "mamba_d_head": 8}).mamba_d_inner \
        == 64
    with pytest.raises(ValueError, match="multiple of mamba_n_groups"):
        TransformerConfig(**{**TOY, "mamba_n_groups": 3})
    with pytest.raises(NotImplementedError, match="give layer_types"):
        TransformerConfig(hidden_size=64, num_heads=4, residual_scale=0.5)


# ---------------------------------------------------------------------------
# (b) the two forms of the recurrence, and their kernels
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("nh,p,n,chunk", [(4, 16, 32, 16), (2, 64, 128, 32)])
def test_chunked_form_is_the_token_scan(nh, p, n, chunk):
    """Rows of 5, 0, 37, 16 and 1 tokens, fresh and continued from their
    slots, through ``ssm_chunked``: each row's outputs and final state
    are the token scan's."""
    case = cases.case(nh, p, n, (5, 0, 37, 16, 1), 62)
    y, leaf = ss.ssm_chunked(**case, chunk=chunk)
    cases.against_the_scan(case, y, leaf, nh)


@pytest.mark.parametrize("nh,p,lengths,T,chunk", [
    (8, 64, (40, 3, 0, 100), 256, 32),      # rows share windows
    (16, 32, (7, 70), 128, 64),             # four heads a lane block
    (4, 128, (33, 20), 64, 32),             # a head a lane block
    (8, 64, (64, 64), 128, 64)])            # the cell's: a row a window
def test_chunk_kernel_is_the_token_scan(nh, p, lengths, T, chunk):
    """``ssm_chunk_fwd`` under the TPU interpreter (its DMAs and
    semaphores included): rows that start and end inside windows, share
    a window, span several, fresh and continued."""
    case = cases.case(nh, p, 128, lengths, T)
    y, leaf = ss.ssm_chunk_fwd(**case, chunk=chunk, interpret=True)
    cases.against_the_scan(case, y, leaf, nh)


@pytest.mark.parametrize("nh,p,n", [
    (4, 16, 32),            # half a lane block: ssm_step alone
    (16, 64, 128),          # 8 lane blocks, fewer than a grid step's 16
    (32, 64, 128),          # 16: one whole grid step a row
    (128, 64, 128)])        # the cell's 64: the ONE group four grid steps
def test_one_token_forms_are_the_token_scan(nh, p, n):
    """``ssm_step`` and the kernel ``ssm_state_update`` (interpreted)
    against one token of the scan (``cases.one_token_forms``). The
    kernel takes B and C as ONE row of ``d_state`` a token and spreads
    it in VMEM every grid step of the row."""
    cases.one_token_forms(nh, p, n)


@pytest.mark.parametrize("N", [3, 8, 12, 24])
def test_conv_kernel_takes_one_input_and_a_bias(N):
    """The convolution's kernel as the state-space layers use it: ONE
    input of 66 lane blocks (8,448 channels: x, B and C), a bias, SiLU,
    under the interpreter against ``causal_conv_step``; the rows' slots
    1, 8, 4 and 8 a grid step (``SLOTS_A_STEP``'s largest divisor that
    divides the rows), in three steps, one, three and three: the first
    step's copies in, a next step's started ahead, a buffer's copies
    back waited for two steps on and at the end."""
    rng = np.random.default_rng(2)
    D, K, S = 66 * 128, 4, 2 * N
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    leaf0 = f(*la.conv_leaf_shape(2, S, K, D))
    assert leaf0.shape == (2, S, 3, 66, 128)
    x, taps, bias = f(N, D).astype(jnp.bfloat16), f(K, D), f(D)
    slots = jnp.asarray(rng.permutation(S - 1)[:N] + 1)
    fresh = jnp.asarray(rng.random(N) < 0.4)
    (y,), leaf = la.conv_update(leaf0, jnp.int32(1), slots, fresh, (x,),
                                taps, bias, name="ssm_conv_update",
                                interpret=True)
    held = jnp.where(fresh[:, None, None], 0.0,
                     leaf0[1, slots].reshape(N, K - 1, D))
    want, kept = la.causal_conv_step(
        x, taps, held, lambda v: jax.nn.silu(v + bias))
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)
    np.testing.assert_array_equal(leaf[1, slots].reshape(N, K - 1, D), kept)
    others = np.setdiff1d(np.arange(S), np.asarray(slots))
    np.testing.assert_array_equal(leaf[1, others], leaf0[1, others])
    np.testing.assert_array_equal(leaf[0], leaf0[0])


# ---------------------------------------------------------------------------
# (c) the expert layer: the router's two routes, and the shares
# ---------------------------------------------------------------------------
def test_softmax_over_the_chosen_is_the_renormalised_softmax_over_all():
    """The source weighs a token's picks by a softmax over THEIR logits
    (the reference's ``route``); the program takes a softmax over all
    the experts and renormalises over the chosen (``topk_routing``): the
    same picks, the same weights, at 72 experts top-10."""
    logits = jnp.asarray(np.random.default_rng(3).normal(size=(64, 72))
                         * 1.5, jnp.float32)
    chosen, w = reference_granite.route(logits, {"moe_top_k": 10})
    topi, topv = topk_routing(logits, 10, "softmax", None, True)
    np.testing.assert_array_equal(chosen, topi)
    np.testing.assert_allclose(w, topv, rtol=2e-6)
    np.testing.assert_allclose(np.asarray(topv).sum(-1), 1.0, rtol=1e-6)


def test_the_shares_add_up_to_the_uncut_layer():
    """``cases.shares_add_up`` on a stack of all 16 experts."""
    shapes = BLOCK.weights.shapes({**TOY,
                                   "moe_experts_held": TOY["moe_num_experts"]})
    rng = np.random.default_rng(4)
    stack = {k: jnp.asarray(rng.normal(size=s) / s[-2] ** 0.5 if len(s) > 2
                            else 1.0 + 0.1 * rng.normal(size=s),
                            jnp.float32)
             for k, (s, _) in shapes["layers"].items()}
    cases.shares_add_up(BLOCK, stack, ("e_gate", "e_up", "e_down"))
