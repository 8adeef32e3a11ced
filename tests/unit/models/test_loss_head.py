"""The loss head (``models/transformer._chunked_ce_loss``): under
differentiation its forward walk makes each chunk's logits once and forms
the gradients of the hidden states, the head and the bias from them; the
backward only scales what was kept. Checked against ``jax.value_and_grad``
of a plain float32 full-logits cross-entropy, and counted in the lowered
text: three vocabulary-wide matmuls differentiated, one in eval."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.models import transformer
from deepspeed_tpu.models.transformer import _chunked_ce_loss
from deepspeed_tpu.telemetry import registry

B, H, V = 2, 16, 97         # 97: no other dimension of the toy is


def reference_loss(x, head, bias, targets, mask, scale=1.0):
    """Masked mean nll over full float32 logits, times ``scale``."""
    logits = x.astype(jnp.float32) @ head.astype(jnp.float32)
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                               targets[..., None], axis=-1)[..., 0]
    return scale * jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def chunked_loss(x, head, bias, targets, mask, chunk, scale=1.0):
    total, count = _chunked_ce_loss(x, targets, mask, head, chunk, bias=bias)
    return scale * total / jnp.maximum(count, 1.0)


def checkpointed_ce_loss(x, targets, mask, head, chunk, bias=None):
    """The formula the step ran before: each chunk's logits under
    ``jax.checkpoint``, made again in the backward; autodiff does the
    rest. Kept here as the reference the model's gradients are held to."""
    xc, tc, mc = transformer._sequence_chunks(chunk, x, targets, mask)

    @jax.checkpoint
    def chunk_nll(x_c, t_c, m_c):
        logits = (x_c @ head.astype(x_c.dtype)).astype(jnp.float32)
        if bias is not None:
            logits = logits + bias.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, t_c[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - tgt) * m_c)

    total, _ = jax.lax.scan(
        lambda total, inputs: (total + chunk_nll(*inputs), None),
        jnp.zeros((), jnp.float32), (xc, tc, mc))
    return total, jnp.sum(mask)


def _mask(kind, seq, key):
    if kind == "ones":
        return jnp.ones((B, seq), jnp.float32)
    if kind == "none":
        return jnp.zeros((B, seq), jnp.float32)
    if kind == "zero_rows":     # a whole sequence contributes nothing
        return jnp.ones((B, seq), jnp.float32).at[0].set(0.0)
    return (jax.random.uniform(key, (B, seq)) > 0.4).astype(jnp.float32)


CASES = {
    # name: (seq, chunk, mask, bias, tied, dtype, upstream scale)
    "tied_causal": (12, 4, "ones", False, True, jnp.float32, 1.0),
    "mlm_with_bias": (12, 4, "random", True, False, jnp.float32, 1.0),
    "mask_with_zero_rows": (12, 4, "zero_rows", False, False, jnp.float32,
                            1.0),
    "all_zero_mask": (12, 4, "none", True, False, jnp.float32, 1.0),
    "seq_not_divisible_by_chunk": (13, 4, "random", True, False,
                                   jnp.float32, 1.0),
    "loss_chunk_0": (12, 0, "random", False, False, jnp.float32, 1.0),
    "chunk_longer_than_seq": (12, 512, "random", False, True, jnp.float32,
                              1.0),
    "bf16": (12, 4, "random", True, True, jnp.bfloat16, 1.0),
    "fp16_loss_scale_2_16": (13, 4, "random", True, False, jnp.float16,
                             2.0 ** 16),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_loss_and_gradients_against_full_logits(case):
    seq, chunk, mask_kind, with_bias, tied, dtype, scale = CASES[case]
    k = jax.random.split(jax.random.PRNGKey(len(case)), 5)
    x = jax.random.normal(k[0], (B, seq, H)).astype(dtype)
    embed = (0.3 * jax.random.normal(k[1], (V, H))).astype(dtype)
    bias = jax.random.normal(k[2], (V,)).astype(dtype) if with_bias else None
    targets = jax.random.randint(k[3], (B, seq), 0, V)
    mask = _mask(mask_kind, seq, k[4])

    def head_of(w):             # tied: the embedding, transposed
        return w.T if tied else w
    weight = embed if tied else embed.T
    wrt = (0, 1, 2) if with_bias else (0, 1)
    want_loss, want = jax.value_and_grad(
        lambda x, w, b: reference_loss(x, head_of(w), b, targets, mask),
        wrt)(x.astype(jnp.float32), weight.astype(jnp.float32),
             None if bias is None else bias.astype(jnp.float32))
    got_loss, got = jax.jit(jax.value_and_grad(
        lambda x, w, b: chunked_loss(x, head_of(w), b, targets, mask, chunk,
                                     scale), wrt))(x, weight, bias)
    # what a 16-bit product rounds to; float32 is held to float32
    tol = {jnp.float32: 2e-6, jnp.bfloat16: 2e-2, jnp.float16: 4e-3}[dtype]
    np.testing.assert_allclose(got_loss / scale, want_loss, rtol=tol,
                               atol=tol)
    for g, w, primal in zip(got, want, (x, weight, bias)):
        assert g.dtype == primal.dtype and g.shape == primal.shape
        g = np.asarray(g.astype(jnp.float32)) / scale
        assert np.all(np.isfinite(g))
        np.testing.assert_allclose(g, w, rtol=tol,
                                   atol=tol * float(jnp.abs(w).max()))
    if mask_kind == "none":
        assert got_loss == 0 and not any(np.any(np.asarray(g)) for g in got)
    # the undifferentiated walk gives the same loss
    primal = jax.jit(lambda: chunked_loss(x, head_of(weight), bias, targets,
                                          mask, chunk, scale))()
    np.testing.assert_allclose(primal, got_loss, rtol=1e-6)


def test_model_gradients_equal_the_checkpointed_formula(monkeypatch):
    """``TransformerLM.apply`` end to end on a seeded toy model: loss and
    every parameter's gradient against the same model with the loss head
    it had before (logits recomputed in the backward, autodiff throughout)."""
    cfg = TransformerConfig(vocab_size=V, hidden_size=32, num_layers=2,
                            num_heads=4, intermediate_size=64,
                            max_seq_len=24, loss_chunk=8, remat=True,
                            tie_embeddings=True)
    model = TransformerLM(cfg)
    params = model.init_params(jax.random.PRNGKey(7))
    rng = np.random.default_rng(7)
    batch = {"input_ids": jnp.asarray(rng.integers(0, V, (3, 24))),
             "loss_mask": jnp.asarray(rng.random((3, 24)) > 0.2)}
    got = jax.jit(jax.value_and_grad(model.apply))(params, batch)
    monkeypatch.setattr(transformer, "_chunked_ce_loss", checkpointed_ce_loss)
    want = jax.jit(jax.value_and_grad(model.apply))(params, batch)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    flat_got, tree = jax.tree.flatten(got[1])
    flat_want, tree_want = jax.tree.flatten(want[1])
    assert tree == tree_want
    for g, w in zip(flat_got, flat_want):
        np.testing.assert_allclose(g, w, rtol=2e-5,
                                   atol=2e-6 * float(jnp.abs(w).max()))


def _vocab_wide_dots(lowered):
    """``stablehlo.dot_general``s of the lowered text with the vocabulary
    among their dimensions."""
    wide = re.compile(rf"[<x]{V}[x>]")
    return [line for line in lowered.as_text().splitlines()
            if "stablehlo.dot_general" in line
            and wide.search(line.split(" : ", 1)[1])]


@pytest.mark.parametrize("with_bias", [False, True])
def test_three_vocabulary_wide_matmuls_differentiated_one_in_eval(with_bias):
    """The exact count the change exists for, in the program text before
    the compiler has touched it, and the gauge that reports it; no
    ``jax.checkpoint`` is left in the loss head."""
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(k[0], (B, 12, H))
    head = jax.random.normal(k[1], (H, V))
    bias = jax.random.normal(k[2], (V,)) if with_bias else None
    targets = jax.random.randint(k[3], (B, 12), 0, V)
    mask = jnp.ones((B, 12), jnp.float32)

    def loss(x, head, bias):
        return chunked_loss(x, head, bias, targets, mask, 4)

    old = registry.set_registry(registry.MetricsRegistry())
    try:
        grad = jax.jit(jax.grad(loss, (0, 1))).lower(x, head, bias)
        series = registry.get_registry().snapshot()["metrics"][
            "loss_head_logit_matmuls"]["series"]
        assert {r["labels"]["mode"]: r["value"] for r in series} \
            == {"grad": 3}
        registry.set_registry(registry.MetricsRegistry())
        evaluated = jax.jit(loss).lower(x, head, bias)
        series = registry.get_registry().snapshot()["metrics"][
            "loss_head_logit_matmuls"]["series"]
        assert {r["labels"]["mode"]: r["value"] for r in series} \
            == {"eval": 1}
    finally:
        registry.set_registry(old)
    assert len(_vocab_wide_dots(grad)) == 3
    assert len(_vocab_wide_dots(evaluated)) == 1
    for lowered in (grad, evaluated):
        text = lowered.as_text(debug_info=True)
        assert "checkpoint" not in text and "rematted" not in text
