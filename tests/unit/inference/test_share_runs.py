"""A share's prompt launch goes through its experts in RUNS of tokens
(``paged_model._moe_experts``), and a run is sized by the rows an
expert the grouped matmul needs, inside a byte budget
(``paged_model._share_tokens``, PR 64): the rule at the three share
cells' shapes, the routed output whatever the run, what the engine
counts (``moe_share_runs_total``), and the launches the rule does not
reach (a decode step, a tree that holds every expert) as the jaxprs
they were on the parent commit.
"""

import dataclasses
import hashlib
import json
import re
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import paged_model
from deepspeed_tpu.models import TransformerConfig
from deepspeed_tpu.telemetry import get_registry
from tests.unit.inference import served_blocks as sb
from tests.unit.inference.served_blocks import REPO

BLOCK = sb.BLOCKS["granite-4.0-h-small"]
TOY = BLOCK.toy


def _cell(name):
    return TransformerConfig(**json.loads(
        (REPO / "benchmark/configs" / f"{name}.json").read_text())["fields"])


def _runs_of(monkeypatch, tokens):
    """A run is ``tokens`` tokens whatever the widths (a toy's rows an
    expert never reach the target, its bytes never the budget)."""
    monkeypatch.setattr(paged_model, "_SHARE_TOKENS", tokens)
    monkeypatch.setattr(paged_model, "_SHARE_ROWS", 1)


# ---------------------------------------------------------------------------
# the rule
# ---------------------------------------------------------------------------
# cell: (the run of its ragged step's 16,384 tokens, the runs, the mean
# rows a held expert a run; the run before PR 64)
CELLS = {"granite-4.0-h-small": (8192, 2, 1137, 2048),
         "nemotron-3-nano-30b-a3b": (16384, 0, 768, 4096),
         "ling-3.0-flash": (16384, 0, 256, 4096),
         "trinity-mini": (16384, 0, 1024, None)}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_run_is_sized_by_the_rows_an_expert_inside_the_bytes(cell):
    """Granite's ten picks over 72 experts reach 1,024 rows an expert
    at 8,192 tokens, which is the budget to the byte; nemotron's and
    ling's targets (32,768 and 65,536 tokens) are over it and the budget
    gives them 16,384, their whole launch in one dispatch; a tree that
    holds every expert has no runs. A run is a power of two, never under
    what bytes alone gave it before, its sorted rows never over
    ``_SHARE_RUN_BYTES``; a decode step (under any run) is one
    dispatch."""
    cfg = _cell(cell)
    run, runs, rows, was = CELLS[cell]
    assert paged_model.moe_share_runs(cfg, 16384, jnp.bfloat16) \
        == (runs, run)
    assert run * cfg.moe_top_k // cfg.moe_num_experts == rows
    for decode in (64, 128):
        assert paged_model.moe_share_runs(cfg, decode, jnp.bfloat16) \
            == (0, decode)
    if was is None:
        assert cfg.experts_held == cfg.moe_num_experts
        return
    tokens = paged_model._share_tokens(cfg, jnp.bfloat16)
    assert tokens == run and tokens & (tokens - 1) == 0
    picks = cfg.moe_top_k * cfg.hidden_size * 2
    assert tokens * picks <= paged_model._SHARE_RUN_BYTES < 2 * tokens * picks
    # what bytes alone gave: 4,096 tokens at 8 picks of 2,560
    old = 4096 * 8 * 2560 * 2 // picks
    assert was == min(4096, 1 << (old.bit_length() - 1)) <= tokens
    # a launch of a run or under it is not cut
    assert paged_model.moe_share_runs(cfg, run, jnp.bfloat16) == (0, run)
    assert paged_model.moe_share_runs(cfg, 3 * run, jnp.bfloat16) \
        == (3, run)
    assert paged_model.moe_share_runs(cfg, run + 1, jnp.bfloat16) \
        == (2, run)


@pytest.mark.parametrize("k,H,experts,want", [
    (8, 2560, 16, 4096),        # 2,048 tokens reach the rows: the floor
    (2, 1024, 8, 4096),
    (8, 16384, 64, 2048),       # the bytes under the floor
    (1, 64, 4, 4096),           # a toy: the floor, far inside the bytes
    (8, 512, 64, 8192),         # the rows, between the floor and the bytes
    (8, 1024, 512, 32768)])     # the bytes over the floor, under the rows
def test_the_floor_the_rows_and_the_bytes_in_turn(k, H, experts, want):
    shape = types.SimpleNamespace(moe_top_k=k, hidden_size=H,
                                  moe_num_experts=experts)
    assert paged_model._share_tokens(shape, jnp.bfloat16) == want
    # float32 rows are twice the bytes: half the run where bytes decide
    assert paged_model._share_tokens(shape, jnp.float32) \
        in (want, want // 2)


# ---------------------------------------------------------------------------
# the output whatever the run
# ---------------------------------------------------------------------------
def _layer():
    """The toy's first expert layer and a share's configuration."""
    made = sb.params(BLOCK)
    return TransformerConfig(**TOY), jax.tree.map(lambda a: a[0],
                                                  made["layers"])


@pytest.mark.parametrize("tokens,run,runs", [
    (32, None, 0), (32, 32, 0), (32, 16, 2), (32, 8, 4), (21, 8, 3)])
def test_a_shares_launch_gives_the_same_output_whatever_the_run(
        monkeypatch, tokens, run, runs):
    """One dispatch for all (the constants as they are: a toy's launch
    is under any run), a run as long as the launch, 2 and 4 runs, and a
    last run padded: the routed output is the one dispatch's, row by
    row (a row's product does not depend on which rows share its
    launch)."""
    cfg, lp = _layer()
    assert cfg.experts_held < cfg.moe_num_experts
    x = jnp.asarray(np.random.default_rng(tokens).standard_normal(
        (tokens, TOY["hidden_size"])), jnp.float32)
    monkeypatch.setattr(paged_model, "moe_share_runs",
                        lambda cfg, tokens, dtype: (0, tokens))
    at_once, picks = paged_model._moe_routed(cfg, lp, x)
    monkeypatch.undo()
    if run is not None:
        _runs_of(monkeypatch, run)
    assert paged_model.moe_share_runs(cfg, tokens, jnp.float32)[0] == runs
    in_runs, picks_runs = paged_model._moe_routed(cfg, lp, x)
    np.testing.assert_array_equal(picks, picks_runs)
    np.testing.assert_allclose(in_runs, at_once, atol=1e-6)
    assert float(jnp.abs(at_once).max()) > 0.01


@pytest.mark.parametrize("cell", sorted(c for c in CELLS
                                        if CELLS[c][3] is not None))
def test_the_form_and_the_runs_are_what_the_experts_trace(monkeypatch,
                                                          cell):
    """``moe_rows_form`` and ``moe_share_runs`` against the jaxpr of
    ``_moe_experts`` at a share cell's published widths on a TPU (traced
    here, lowered nowhere): a ``scan`` over the runs, the sorted rows of
    ONE run, and the kernels that bring them back."""
    cfg = _cell(cell)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    T, H, k = 16384, cfg.hidden_size, cfg.moe_top_k
    runs, run = paged_model.moe_share_runs(cfg, T, jnp.bfloat16)
    assert paged_model.moe_rows_form(cfg, T, jnp.bfloat16) == "kernel"

    def bf16(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16)

    F, E = cfg.moe_intermediate_size, cfg.experts_held
    experts = {"e_gate": bf16(E, H, F), "e_up": bf16(E, H, F),
               "e_down": bf16(E, F, H)} if cfg.moe_expert_form != "relu2" \
        else {"e_up": bf16(E, F, H), "e_down": bf16(E, F, H)}
    lp = {"moe_gate_w": bf16(H, cfg.moe_num_experts), **experts}
    cfg = dataclasses.replace(cfg, moe_shared_experts=0)
    text = str(jax.make_jaxpr(
        lambda lp, x, topi, topv: paged_model._moe_experts(
            cfg, lp, x, topi, topv))(
        lp, bf16(T, H), jax.ShapeDtypeStruct((T, k), jnp.int32),
        jax.ShapeDtypeStruct((T, k), jnp.float32)))
    assert f"bf16[{k * run},{H}]" in text
    if runs:
        assert re.search(rf"\blength={runs}\b", text)
        assert f"bf16[{k * T},{H}]" not in text
    assert "moe_rows_whole" in text and "moe_rows_combine" in text
    assert len(re.findall(r"name=gmm\b", text)) == len(experts)


# ---------------------------------------------------------------------------
# what the engine counts
# ---------------------------------------------------------------------------
def _counted(program):
    """(expert-layer passes, runs) counted under ``program`` so far."""
    return tuple(get_registry().get(name).labels(program=program).value
                 for name in ("moe_launches_total", "moe_share_runs_total"))


def test_the_engine_counts_the_runs_a_pass(monkeypatch):
    """``moe_share_runs_total`` over ``moe_launches_total``: 4 where a
    toy share's ragged step of 32 tokens goes through in runs of 8, 0 on
    its decode windows, 0 on every program of a tree that holds every
    expert. Engines of their own: the programs are traced under the
    patched constants."""
    _runs_of(monkeypatch, 8)
    share = sb.engine(BLOCK, budget=32)
    whole = sb.engine(BLOCK, budget=32,
                      fields={"moe_experts_held": TOY["moe_num_experts"]})
    assert paged_model._held_from(whole.model.cfg) is None
    for eng, runs in ((share, 4), (whole, 0)):
        before = {p: _counted(p) for p in ("ragged_step", "decode_window")}
        eng.generate(sb.prompts(BLOCK, (20, 12)), max_new_tokens=3,
                     uids=sb.uids(2))
        (passes, counted), (steps, at_decode) = (
            tuple(now - was for now, was in zip(_counted(p), before[p]))
            for p in ("ragged_step", "decode_window"))
        kinds = eng.model.cfg.layer_kinds
        assert passes == kinds.count("ssm") + kinds.count("full")
        assert counted == runs * passes
        assert steps > 0 and at_decode == 0


# ---------------------------------------------------------------------------
# the launches the rule does not reach
# ---------------------------------------------------------------------------
def _digest(jaxpr):
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


# block -> (tokens, the digest of ``_moe_routed``'s jaxpr at the toy's
# widths, read by this function on the parent commit 69168a8): the three
# shares at a decode step's rows, the three that hold every expert at a
# prompt's
UNTOUCHED = {
    "granite-4.0-h-small": (8, "34904a8f955276e4"),
    "nemotron-3-nano-30b-a3b": (8, "8b279fa4399870a5"),
    "ling-3.0-flash": (8, "ebe3d63103f20edf"),
    "trinity-mini": (64, "b2c60c81d67f1cb3"),
    "joyai-llm-flash": (64, "5703a8344ecfbc14"),
    "smallthinker-21ba3b-instruct": (64, "f19d7bb06499e9f2"),
}


def routed_digest(block, tokens):
    row = sb.BLOCKS[block]
    cfg = TransformerConfig(**row.toy)
    lp = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                      sb.params(row)["layers"])
    x = jax.ShapeDtypeStruct((tokens, row.toy["hidden_size"]), jnp.float32)
    return _digest(jax.make_jaxpr(
        lambda lp, x: paged_model._moe_routed(cfg, lp, x))(lp, x))


@pytest.mark.parametrize("block", sorted(UNTOUCHED))
def test_the_launches_under_a_run_are_the_jaxprs_they_were(block):
    """A share's decode step and every launch of a tree that holds all
    its experts are one dispatch, and their expert layer traces to the
    text it was before a run was sized by rows."""
    tokens, want = UNTOUCHED[block]
    cfg = TransformerConfig(**sb.BLOCKS[block].toy)
    assert paged_model.moe_share_runs(cfg, tokens, jnp.float32) \
        == (0, tokens)
    assert routed_digest(block, tokens) == want
