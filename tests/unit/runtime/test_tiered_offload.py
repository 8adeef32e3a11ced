"""Tiered optimizer offload (runtime/offload.py): host-resident state,
bucket-streamed device update, bit-identical to resident training.

The acceptance invariant is exact: ``offload_optimizer {device: cpu,
pin_memory: true}`` shares the resident path's gradient program (the
bucketed ppermute ring on these pure-dp meshes) and applies the
resident optimizer math per prefetch bucket, so params, master weights
and moments must be BIT-equal to a resident run — across ZeRO stages
1/2 and gradient accumulation."""

import numpy as np
import pytest

import jax

import deepspeed_tpu
from deepspeed_tpu.runtime.config import ConfigError, DeepSpeedConfig
from tests.unit.simple_model import SimpleModel, base_config, random_batches

HIDDEN = 32


def _train(config, steps=3, seed=3):
    model = SimpleModel(hidden_dim=HIDDEN)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)
    micro = engine.micro_batch_size * engine.ds_config.dp_world_size
    losses = []
    for b in random_batches(steps, micro * engine.gas, HIDDEN, seed=seed):
        batch = {k: v.reshape(engine.gas, micro, HIDDEN)
                 for k, v in b.items()}
        losses.append(engine.train_batch(batch=batch))
    return engine, losses


def _cfg(stage, gas, tiered=False, dtype="bf16", prefetch=None):
    cfg = base_config(micro=2, stage=stage, dtype=dtype, lr=1e-2)
    cfg["gradient_accumulation_steps"] = gas
    if tiered:
        cfg["zero_optimization"]["offload_optimizer"] = {
            "device": "cpu", "pin_memory": True}
    if prefetch is not None:
        cfg["zero_optimization"]["stage3_prefetch_bucket_size"] = prefetch
    return cfg


@pytest.mark.parametrize("stage,gas", [(1, 1), (1, 2), (2, 1), (2, 2)])
def test_tiered_offload_bit_identical_to_resident(stage, gas):
    eng_r, loss_r = _train(_cfg(stage, gas))
    eng_t, loss_t = _train(_cfg(stage, gas, tiered=True, prefetch=600))
    assert eng_t.offload_tiered and eng_t.host_opt is not None
    # losses, master weights, compute params AND moments: exact equality,
    # not allclose — the tiered path is the same math, streamed
    assert loss_t == loss_r
    for a, b in zip(jax.tree.leaves(eng_r.master_params),
                    eng_t.host_opt.get_master_leaves()):
        np.testing.assert_array_equal(np.asarray(a, np.float32), b)
    for a, b in zip(jax.tree.leaves(eng_r.params),
                    jax.tree.leaves(eng_t.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    state_t = eng_t.host_opt.get_state_leaves()
    for key in eng_t.host_opt.state_keys:
        for a, b in zip(jax.tree.leaves(eng_r.opt_state[key]), state_t[key]):
            np.testing.assert_array_equal(np.asarray(a, np.float32), b)


def test_prefetch_buckets_honor_knob_and_overlap_gauges():
    """stage3_prefetch_bucket_size is the streaming granularity: a cap
    below the largest leaf yields one-leaf buckets; a huge cap collapses
    to one bucket. The overlap is measured, not assumed: every fetch
    after the pre-grad prefetch is a hit, and the exposed fraction is a
    real wall-clock ratio."""
    from deepspeed_tpu.runtime.offload import plan_prefetch_buckets
    assert plan_prefetch_buckets([32, 1024, 32, 1024], 600) == \
        [[0], [1], [2], [3]]
    assert plan_prefetch_buckets([32, 1024, 32, 1024], 10 ** 9) == \
        [[0, 1, 2, 3]]
    assert plan_prefetch_buckets([32, 1024], 1056) == [[0, 1]]
    with pytest.raises(ValueError, match="> 0"):
        plan_prefetch_buckets([1], 0)

    from deepspeed_tpu.telemetry import get_registry
    eng, _ = _train(_cfg(2, 1, tiered=True, prefetch=600), steps=2)
    # hidden=32, 2 layers: leaves 32/1024/32/1024 -> 4 one-leaf buckets
    assert len(eng.host_opt.buckets) == len(jax.tree.leaves(eng.params))
    reg = get_registry()
    assert reg.gauge("offload_prefetch_hit_fraction").value == 1.0
    assert 0.0 <= reg.gauge("offload_prefetch_exposed_fraction").value <= 1.0
    state_bytes = sum(
        np.asarray(l).size for l in jax.tree.leaves(eng.params)) * 4 * 3
    assert reg.gauge("optimizer_offload_bytes").value == state_bytes
    assert reg.counter("offload_h2d_bytes_total").value >= state_bytes
    assert reg.counter("offload_d2h_bytes_total").value >= state_bytes


def test_tiered_offload_update_programs():
    """The streamed update compiles one executable a bucket signature and
    then no more: a 2-layer transformer under a 16 KiB prefetch bucket has
    six signatures, at the first step and at the third."""
    from deepspeed_tpu.models import TransformerConfig, TransformerLM
    cfg = TransformerConfig(vocab_size=128, hidden_size=64,
                            intermediate_size=128, num_layers=2,
                            num_heads=4, num_kv_heads=2, max_seq_len=64,
                            remat=False, use_flash=False)
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=TransformerLM(cfg),
        config={"train_micro_batch_size_per_gpu": 1,
                "bf16": {"enabled": True},
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "zero_optimization": {
                    "stage": 2,
                    "offload_optimizer": {"device": "cpu",
                                          "pin_memory": True},
                    "stage3_prefetch_bucket_size": 1 << 14},
                "steps_per_print": 10 ** 9})
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (1, 8, 32), dtype=np.int64)
    programs = []
    for _ in range(3):
        eng.train_batch(batch={"input_ids": ids})
        programs.append(len(eng.host_opt._update_fns))
    eng.destroy()
    assert programs == [6, 6, 6]


def test_tiered_checkpoint_roundtrip(tmp_path):
    cfg = _cfg(2, 1, tiered=True)
    engine, _ = _train(cfg, steps=3)
    engine.save_checkpoint(str(tmp_path / "ckpt"))
    master_before = [l.copy() for l in engine.host_opt.get_master_leaves()]

    engine2, _ = _train(cfg, steps=1, seed=99)
    engine2.load_checkpoint(str(tmp_path / "ckpt"))
    for a, b in zip(master_before, engine2.host_opt.get_master_leaves()):
        np.testing.assert_array_equal(a, b)
    assert int(engine2._step_arr) == int(engine._step_arr)

    # the restored engine continues BIT-identically to the donor
    micro = engine.micro_batch_size * engine.ds_config.dp_world_size
    b = random_batches(1, micro * engine.gas, HIDDEN, seed=7)[0]
    batch = {k: v.reshape(engine.gas, micro, HIDDEN) for k, v in b.items()}
    assert engine.train_batch(batch=batch) == engine2.train_batch(batch=batch)


def test_tiered_fp16_skip_leaves_host_state_untouched():
    cfg = _cfg(2, 1, tiered=True, dtype="fp16")
    cfg["fp16"].update({"initial_scale_power": 32, "hysteresis": 1})
    model = SimpleModel(hidden_dim=HIDDEN)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=cfg)
    micro = engine.micro_batch_size * engine.ds_config.dp_world_size
    b = random_batches(1, micro * engine.gas, HIDDEN, seed=1)[0]
    batch = {k: v.reshape(engine.gas, micro, HIDDEN) for k, v in b.items()}
    engine.train_batch(batch=batch)
    assert engine.skipped_steps >= 1
    assert engine.loss_scale < 2.0 ** 32
    assert int(engine._step_arr) == 0
    # a skipped step never reaches the streaming update: moments stay 0
    for key in engine.host_opt.state_keys:
        for leaf in engine.host_opt.get_state_leaves()[key]:
            assert not leaf.any()


def test_tiered_config_rejects():
    base = {"train_micro_batch_size_per_gpu": 1}

    def cfg(zero, opt=None):
        d = dict(base, zero_optimization=zero)
        if opt:
            d["optimizer"] = opt
        return d

    # tiered pins the HOST tier: nvme + pin_memory contradicts it
    with pytest.raises(ConfigError, match="pin_memory"):
        DeepSpeedConfig(cfg({"stage": 2, "offload_optimizer": {
            "device": "nvme", "nvme_path": "/tmp/x", "pin_memory": True}}))
    # tiered targets ZeRO 1/2
    with pytest.raises(ConfigError, match="stages 1/2"):
        DeepSpeedConfig(cfg({"stage": 0, "offload_optimizer": {
            "device": "cpu", "pin_memory": True}}))
    # buffer-count style fields reject nonsense at load (satellite: they
    # used to accept anything)
    with pytest.raises(ConfigError, match="buffer_count"):
        DeepSpeedConfig(cfg({"stage": 2, "offload_optimizer": {
            "device": "cpu", "buffer_count": 0}}))
    with pytest.raises(ConfigError, match="buffer_size"):
        DeepSpeedConfig(cfg({"stage": 2, "offload_optimizer": {
            "device": "cpu", "buffer_size": -1}}))
    with pytest.raises(ConfigError, match="ratio"):
        DeepSpeedConfig(cfg({"stage": 2, "offload_optimizer": {
            "device": "cpu", "ratio": 0.0}}))
    # unknown device / pathless nvme fail at LOAD now, not engine init
    with pytest.raises(ConfigError, match="cpu.*nvme|nvme.*cpu"):
        DeepSpeedConfig(cfg({"stage": 2, "offload_optimizer": {
            "device": "disk"}}))
    with pytest.raises(ConfigError, match="nvme_path"):
        DeepSpeedConfig(cfg({"stage": 2, "offload_optimizer": {
            "device": "nvme"}}))
    # quantized_reduce x offload: rejected at config load (PR 9 rejected
    # it at engine init, after the expensive state build)
    with pytest.raises(ConfigError, match="quantized_reduce"):
        DeepSpeedConfig(cfg({"stage": 2, "quantized_reduce": "int8",
                             "offload_optimizer": {"device": "cpu"}}))
    # 1-bit optimizers own their state/communication: no offload backend
    with pytest.raises(ConfigError, match="1-bit"):
        DeepSpeedConfig(cfg({"stage": 1, "offload_optimizer":
                             {"device": "cpu"}},
                            opt={"type": "onebitadam",
                                 "params": {"lr": 1e-3}}))
