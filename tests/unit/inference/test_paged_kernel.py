"""Pallas paged-attention kernel parity tests (reference
tests/unit/inference/v2/kernels/ragged_ops blocked-flash parity): the kernel
must match the materializing-gather reference on ragged block tables."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.kernels.paged_attention import paged_attention


def _reference(q, kc, vc, bt, lengths):
    N, nh, hd = q.shape
    nb, bs, kvh, _ = kc.shape
    MB = bt.shape[1]
    ctx = MB * bs
    kp = kc[bt].reshape(N, ctx, kvh, hd)
    vp = vc[bt].reshape(N, ctx, kvh, hd)
    if kvh != nh:
        kp = jnp.repeat(kp, nh // kvh, axis=2)
        vp = jnp.repeat(vp, nh // kvh, axis=2)
    s = jnp.einsum("nhd,nchd->nhc", q, kp).astype(jnp.float32) / np.sqrt(hd)
    mask = jnp.arange(ctx)[None, :] < lengths[:, None]
    s = jnp.where(mask[:, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("nhc,nchd->nhd", p, vp)


@pytest.mark.parametrize("kvh,nh", [(4, 4), (2, 8)])
def test_paged_attention_matches_gather(stored_pool, kvh, nh):
    N, hd, nb, bs, MB = 3, 64, 12, 16, 4
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((N, nh, hd)) * 0.3, jnp.float32)
    kc = jnp.asarray(rng.standard_normal((nb, bs, kvh, hd)) * 0.3,
                     jnp.float32)
    vc = jnp.asarray(rng.standard_normal((nb, bs, kvh, hd)) * 0.3,
                     jnp.float32)
    # distinct non-null blocks per sequence, ragged lengths
    bt = jnp.asarray(
        np.stack([rng.choice(np.arange(1, nb), MB, replace=False)
                  for _ in range(N)]), jnp.int32)
    lengths = jnp.asarray([5, 33, 64], jnp.int32)

    # the pool as stored: [L, nb, bs, kvh * hd], layer 1 of two attended
    out = paged_attention(q, stored_pool(kc, 2, 1), stored_pool(vc, 2, 1), 1,
                          bt, lengths)
    ref = _reference(q, kc, vc, bt, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


def test_paged_attention_in_decode_path():
    """Full decode with the kernel enabled must match kernel-off decode."""
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.config_v2 import DSStateManagerConfig
    from deepspeed_tpu.models import TransformerConfig, TransformerLM

    cfg = TransformerConfig(vocab_size=64, hidden_size=32,
                            intermediate_size=64, num_layers=2, num_heads=4,
                            num_kv_heads=2, max_seq_len=64, remat=False,
                            use_flash=False)
    model = TransformerLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))

    def build(use_kernel):
        return InferenceEngineV2(model, RaggedInferenceEngineConfig(
            state_manager=DSStateManagerConfig(
                max_tracked_sequences=2, max_seq_len=64, num_blocks=9,
                block_size=16),
            dtype="float32", prefill_bucket=16,
            use_paged_kernel=use_kernel), params=params)

    prompt = [3, 9, 27, 5, 11]
    with_kernel = build(True)
    without = build(False)
    l1 = with_kernel.put([1], [prompt])
    l0 = without.put([1], [prompt])
    np.testing.assert_allclose(l1, l0, rtol=1e-5, atol=1e-5)
    s1 = with_kernel.put([1], [[7]])
    s0 = without.put([1], [[7]])
    np.testing.assert_allclose(s1, s0, rtol=1e-4, atol=1e-4)
