"""Ring attention: parity vs dense reference + end-to-end training.

Mirrors the reference's sequence-parallel coverage (Ulysses) and extends it:
ring attention is the long-context strategy absent from the reference
snapshot (SURVEY.md §5).
"""

import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.ops.flash_attention import mha_reference
from deepspeed_tpu.parallel.topology import TopologyConfig, MeshTopology
from deepspeed_tpu.sequence import ring_attention_sharded


def make_qkv(b=1, h=4, s=64, d=8, hkv=None, seed=0):
    rng = np.random.default_rng(seed)
    hkv = hkv or h
    q = rng.standard_normal((b, h, s, d), dtype=np.float32)
    k = rng.standard_normal((b, hkv, s, d), dtype=np.float32)
    v = rng.standard_normal((b, hkv, s, d), dtype=np.float32)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_dense(causal):
    topo = MeshTopology(TopologyConfig(seq=4))
    q, k, v = make_qkv()
    out = ring_attention_sharded(q, k, v, topo, causal=causal)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ring_gqa():
    topo = MeshTopology(TopologyConfig(seq=4))
    q, k, v = make_qkv(h=4, hkv=2)
    out = ring_attention_sharded(q, k, v, topo, causal=True)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ring_with_tp_and_dp():
    """seq=2 x model=2 x data=2: the ring only touches the sequence dim."""
    topo = MeshTopology(TopologyConfig(seq=2, model=2))
    q, k, v = make_qkv(b=2, h=4, s=32, d=8)
    out = ring_attention_sharded(q, k, v, topo, causal=True)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ring_grads_match_dense():
    """Gradients flow through scan + ppermute + remat correctly."""
    topo = MeshTopology(TopologyConfig(seq=4))
    q, k, v = make_qkv(s=32)

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention_sharded(q, k, v, topo, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5, rtol=3e-5)


def test_ring_end_to_end_training():
    """TransformerLM with seq_parallel_impl='ring' trains on a seq=2 mesh."""
    import deepspeed_tpu
    from deepspeed_tpu.models import TransformerConfig, TransformerLM

    mcfg = TransformerConfig(vocab_size=64, hidden_size=32,
                             intermediate_size=64, num_layers=2, num_heads=4,
                             max_seq_len=32, use_flash=False,
                             seq_parallel=True, seq_parallel_impl="ring")
    model = TransformerLM(mcfg)
    config = {
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 1,
        "sequence_parallel_size": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "steps_per_print": 100,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)
    gm = engine.micro_batch_size * engine.ds_config.dp_world_size
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, mcfg.vocab_size, (1, gm, mcfg.max_seq_len),
                                       dtype=np.int64)}
    losses = [engine.train_batch(batch=batch) for _ in range(4)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


@pytest.mark.parametrize("causal", [True, False])
def test_ring_chunked_matches_dense(causal):
    """Blockwise within-step chunking (q_chunk/kv_chunk) is numerically
    the unchunked online softmax; it bounds each ring step's score block
    to [H, qb, kb] — the enabler for the 1M-token proof
    (artifacts/longcontext_1m_v5e64.json)."""
    from functools import partial
    from jax import shard_map
    from deepspeed_tpu.sequence.ring_attention import ring_attention

    topo = MeshTopology(TopologyConfig(seq=4))
    q, k, v = make_qkv(s=128, hkv=2)
    spec = P(None, None, "seq", None)
    fn = shard_map(
        partial(ring_attention, causal=causal, q_chunk=8, kv_chunk=16),
        mesh=topo.mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    out = fn(q, k, v)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ring_chunked_grads_match_dense():
    from functools import partial
    from jax import shard_map
    from deepspeed_tpu.sequence.ring_attention import ring_attention

    topo = MeshTopology(TopologyConfig(seq=4))
    q, k, v = make_qkv(s=64)
    spec = P(None, None, "seq", None)
    fn = shard_map(partial(ring_attention, causal=True, q_chunk=8,
                           kv_chunk=8),
                   mesh=topo.mesh, in_specs=(spec, spec, spec),
                   out_specs=spec, check_vma=False)
    g = jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) ** 2),
                 argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(mha_reference(q, k, v, causal=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5, rtol=3e-5)
