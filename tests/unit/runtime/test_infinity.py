"""ZeRO-Infinity NVMe parameter tier (per-layer streamed executor).

Reference: runtime/swap_tensor/partitioned_param_swapper.py:36 (fp16
params live on NVMe and are async-swapped around each submodule) and
runtime/zero/parameter_offload.py:201 (the hooks that drive it). The
TPU-native design is runtime/zero/infinity.py: per-layer jitted
forward/VJP programs with double-buffered AIO reads, host-fp32 grad
accumulation, and the C++ host optimizer sweeping the per-layer NVMe
state files.
"""

import glob
import os

import numpy as np
import pytest
import jax

import deepspeed_tpu
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.runtime.config import ConfigError


def _cfg(**kw):
    base = dict(vocab_size=128, hidden_size=64, intermediate_size=128,
                num_layers=4, num_heads=4, max_seq_len=64,
                use_flash=False, remat=True)
    base.update(kw)
    return TransformerConfig(**base)


def _engine(model_cfg, zero_extra=None, config_extra=None):
    zconf = {"stage": 3, "stage3_param_persistence_threshold": 0}
    zconf.update(zero_extra or {})
    config = {"train_micro_batch_size_per_gpu": 1,
              "bf16": {"enabled": True},
              "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
              "zero_optimization": zconf, "steps_per_print": 10 ** 9}
    config.update(config_extra or {})
    engine, _, _, _ = deepspeed_tpu.initialize(model=TransformerLM(model_cfg),
                                               config=config)
    return engine


def _batch(cfg, seed=0, gas=1, gm=8):
    return {"input_ids": np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (gas, gm, cfg.max_seq_len), dtype=np.int64)}


def _nvme(tmp_path, extra=None):
    d = {"offload_param": {"device": "nvme", "nvme_path": str(tmp_path)}}
    d.update(extra or {})
    return d


def test_infinity_loss_parity_and_files(tmp_path):
    """nvme-param training matches the standard ZeRO-3 path (per-layer
    VJP + C++ host AdamW vs fused scan + device optimizer differ only in
    bf16 reduction order), param/optim files land on disk, and the device
    holds no layer-stack params (engine.params is None)."""
    cfg = _cfg()
    losses = {}
    for mode in ("std", "inf"):
        engine = _engine(cfg, _nvme(tmp_path) if mode == "inf" else None)
        losses[mode] = [float(engine.train_batch(batch=_batch(cfg, i)))
                        for i in range(3)]
        if mode == "inf":
            pdir = engine._infinity.param_dir
            assert len(glob.glob(os.path.join(pdir, "layer_*.params"))) == \
                cfg.num_layers
            # optimizer state stays in host RAM unless offload_optimizer
            # is nvme too (ZeRO-Offload params-on-NVMe states-in-RAM)
            assert engine._infinity._optim_ram[0] is not None
            assert engine.params is None
            ev = float(engine.eval_batch(batch=_batch(cfg, 99)))
            assert np.isfinite(ev)
    # the streamed path's bf16 sums depend on how the host's threads fall:
    # twelve runs side by side on a busy host (PR 60) read three outcomes,
    # 8.9e-5 (eight runs), 9.4e-4 (three) and 2.06e-3 (one: over the old
    # 2e-3, the failure ROADMAP D3 named); a step moves the loss by 2e-2,
    # so a lost update still reads over three times this limit
    np.testing.assert_allclose(losses["inf"], losses["std"], atol=5e-3)


@pytest.mark.slow  # tier-1 sibling: test_infinity_loss_parity_and_files (same streamed update; nvme tier = dir-backed host path)
def test_infinity_full_nvme_optimizer_states(tmp_path):
    """offload_optimizer nvme + offload_param nvme = full ZeRO-Infinity:
    per-layer optim files on disk, still parity with the standard path."""
    cfg = _cfg(num_layers=3)
    std = _engine(cfg)
    inf = _engine(cfg, _nvme(tmp_path, {
        "offload_optimizer": {"device": "nvme",
                              "nvme_path": str(tmp_path)}}))
    for i in range(2):
        ls = float(std.train_batch(batch=_batch(cfg, i)))
        li = float(inf.train_batch(batch=_batch(cfg, i)))
        np.testing.assert_allclose(li, ls, atol=2e-3)
    assert len(glob.glob(os.path.join(
        inf._infinity.optim_dir, "layer_*.optim"))) == cfg.num_layers


def test_infinity_gradient_accumulation(tmp_path):
    """gas>1: host-accumulated per-layer grads match the fused scan."""
    cfg = _cfg(num_layers=2)
    extra = {"gradient_accumulation_steps": 2}
    std = _engine(cfg, config_extra=extra)
    inf = _engine(cfg, _nvme(tmp_path), config_extra=extra)
    for i in range(2):
        ls = float(std.train_batch(batch=_batch(cfg, i, gas=2)))
        li = float(inf.train_batch(batch=_batch(cfg, i, gas=2)))
        np.testing.assert_allclose(li, ls, atol=2e-3)


@pytest.mark.slow  # tier-1 sibling: test_infinity_loss_parity_and_files (same streamed-layer path, dp-only)
def test_infinity_tensor_parallel(tmp_path):
    """dp x tp: each streamed layer is device_put with its TP sharding."""
    cfg = _cfg(num_layers=2)
    extra = {"tensor_parallel_size": 2}
    std = _engine(cfg, config_extra=extra)
    inf = _engine(cfg, _nvme(tmp_path), config_extra=extra)
    for i in range(2):
        ls = float(std.train_batch(batch=_batch(cfg, i, gm=4)))
        li = float(inf.train_batch(batch=_batch(cfg, i, gm=4)))
        np.testing.assert_allclose(li, ls, atol=2e-3)


def test_infinity_checkpoint_roundtrip(tmp_path):
    """save -> fresh engine -> load -> continue: same losses as an
    uninterrupted run (master + moments + step restored from the
    per-layer NVMe files)."""
    cfg = _cfg(num_layers=2)
    ck = tmp_path / "ckpt"
    a = _engine(cfg, _nvme(tmp_path / "a"))
    for i in range(2):
        a.train_batch(batch=_batch(cfg, i))
    a.save_checkpoint(str(ck))
    cont_a = [float(a.train_batch(batch=_batch(cfg, 10 + i)))
              for i in range(2)]

    b = _engine(cfg, _nvme(tmp_path / "b"))
    b.load_checkpoint(str(ck))
    cont_b = [float(b.train_batch(batch=_batch(cfg, 10 + i)))
              for i in range(2)]
    np.testing.assert_allclose(cont_b, cont_a, atol=1e-5)


def test_infinity_rejects():
    import tempfile
    tmp = tempfile.mkdtemp()
    # missing nvme_path
    with pytest.raises(ConfigError, match="nvme_path"):
        _engine(_cfg(), {"offload_param": {"device": "nvme"}})
    # fp16 loss scaling not threaded through the executor
    with pytest.raises(NotImplementedError, match="bf16"):
        _engine(_cfg(), _nvme(tmp),
                {"bf16": {"enabled": False}, "fp16": {"enabled": True}})
    # MoE needs the full stack resident
    with pytest.raises(NotImplementedError, match="MoE"):
        _engine(_cfg(moe_num_experts=2, moe_top_k=1), _nvme(tmp))
    # ZeRO++ composition rejected
    with pytest.raises(NotImplementedError, match="ZeRO"):
        _engine(_cfg(), _nvme(tmp, {"zero_quantized_weights": True}))
    # stage-3 only (reference: param offload is a stage-3 feature)
    with pytest.raises(ConfigError, match="stage 3"):
        _engine(_cfg(), {"offload_param": {"device": "nvme",
                                           "nvme_path": tmp}, "stage": 2})


def test_infinity_device_param_bytes_bounded(tmp_path):
    """Only persistent (non-layer) params are device-resident: the layer
    stack's bytes live on NVMe, not in HBM."""
    cfg = _cfg(num_layers=8)
    engine = _engine(cfg, _nvme(tmp_path))
    inf = engine._infinity
    dev_bytes = inf.device_param_bytes()
    layer_bytes = inf.layer_elems * inf.L * inf._np_cdtype.itemsize
    # embed dominates persistents for the tiny config; the layer stack
    # must not be part of the device-resident set at all
    total_dev = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                    for x in jax.tree.leaves(inf.pp_dev))
    assert total_dev == dev_bytes
    on_disk = sum(os.path.getsize(p) for p in inf.param_files)
    assert on_disk == layer_bytes
