"""ZeRO-3 comm/compute overlap analysis (VERDICT r2 task 7): the HLO-level
overlap report that replaces the reference's two-stream eyeballing
(stage3.py:1151)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.utils.xla_profile import (OverlapReport, analyze_hlo,
                                             overlap_report)


def test_analyze_hlo_async_pairs_and_distances():
    hlo = """
ENTRY main {
  %p0 = f32[8]{0} parameter(0)
  %ag = (f32[8],f32[64]) all-gather-start(%p0)
  %c1 = f32[8]{0} add(%p0, %p0)
  %c2 = f32[8]{0} multiply(%c1, %c1)
  %agd = f32[64]{0} all-gather-done(%ag)
  %rs = (f32[64],f32[8]) reduce-scatter-start(%agd)
  %rsd = f32[8]{0} reduce-scatter-done(%rs)
  %ar = f32[64]{0} all-reduce(%agd)
  ROOT %out = f32[64]{0} add(%ar, %ar)
}
"""
    rep = analyze_hlo(hlo)
    assert rep.async_pairs == {"all-gather": 1, "reduce-scatter": 1}
    assert rep.distances["all-gather"] == [3]   # two compute ops between
    assert rep.distances["reduce-scatter"] == [1]  # done right after: exposed
    assert rep.sync_collectives == {"all-reduce": 1}
    assert rep.exposed_pairs == 1
    # (1 exposed pair + 1 sync) / (2 pairs + 1 sync)
    np.testing.assert_allclose(rep.exposed_fraction, 2 / 3)


def test_overlap_report_on_sharded_grad():
    """A ZeRO-3-shaped sharded gradient program compiles with the expected
    collectives and the report captures them (async on TPU, sync on the CPU
    backend — either way they are counted)."""
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("data",))

    def loss(x, w):
        return jnp.sum(jnp.square(x @ w))

    x = jax.device_put(jnp.ones((64, 128)),
                       NamedSharding(mesh, P("data", None)))
    w = jax.device_put(jnp.ones((128, 128)),
                       NamedSharding(mesh, P("data", None)))
    rep = overlap_report(lambda a, b: jax.grad(loss, argnums=1)(a, b), x, w)
    total = (sum(rep.async_pairs.values())
             + sum(rep.sync_collectives.values()))
    assert total >= 1           # param gather and/or grad reduce present
    assert rep.total_instructions > 0
    assert "exposed fraction" in rep.summary()


def test_zero3_overlap_comm_unrolls_layer_scan():
    """stage 3 + overlap_comm widens the layer-scan scheduling window
    (scan_unroll_hint=2) and training stays numerically identical to the
    un-unrolled scan."""
    import deepspeed_tpu
    from deepspeed_tpu.models import TransformerConfig, TransformerLM

    cfg = TransformerConfig(vocab_size=64, hidden_size=32,
                            intermediate_size=64, num_layers=4, num_heads=4,
                            max_seq_len=32, use_flash=False, remat=False)
    losses = {}
    for overlap in (False, True):
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=TransformerLM(cfg),
            config={"train_micro_batch_size_per_gpu": 1,
                    "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                    "zero_optimization": {
                        "stage": 3, "overlap_comm": overlap,
                        "stage3_param_persistence_threshold": 0},
                    "steps_per_print": 10 ** 9})
        assert getattr(engine.model, "scan_unroll_hint", 1) == \
            (2 if overlap else 1)
        gm = engine.micro_batch_size * engine.ds_config.dp_world_size
        batch = {"input_ids": np.random.default_rng(0).integers(
            0, 64, (1, gm, 32), dtype=np.int64)}
        losses[overlap] = [float(engine.train_batch(batch=batch))
                           for _ in range(2)]
    np.testing.assert_allclose(losses[True], losses[False], rtol=1e-6)


