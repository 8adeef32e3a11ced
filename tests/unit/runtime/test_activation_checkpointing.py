"""Activation checkpointing tests (reference
tests/unit/runtime/activation_checkpointing/test_activation_checkpointing.py:
checkpointed forward/backward must match the non-checkpointed one)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.runtime.activation_checkpointing import checkpointing as ckpt


@pytest.fixture(autouse=True)
def _reset():
    ckpt.reset()
    yield
    ckpt.reset()


def _layer(w, x):
    return jnp.tanh(x @ w)


def test_checkpoint_matches_plain_grads():
    rng = jax.random.PRNGKey(0)
    w = jax.random.normal(rng, (16, 16), jnp.float32) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16), jnp.float32)

    def loss_plain(w):
        return jnp.sum(_layer(w, _layer(w, x)))

    def loss_ckpt(w):
        h = ckpt.checkpoint(lambda w_: _layer(w_, x), w)
        return jnp.sum(ckpt.checkpoint(lambda w_: _layer(w_, h), w))

    g_plain = jax.grad(loss_plain)(w)
    g_ckpt = jax.grad(loss_ckpt)(w)
    np.testing.assert_allclose(np.asarray(g_ckpt), np.asarray(g_plain),
                               rtol=1e-6)


def test_configure_policy_applied():
    ckpt.configure(policy="dots_saveable")
    assert ckpt.is_configured()
    assert ckpt.get_config()["policy"] == "dots_saveable"
    # wrapped function still computes correctly
    w = jnp.eye(8)
    out = ckpt.checkpoint(lambda w_: _layer(w_, jnp.ones((2, 8))), w)
    np.testing.assert_allclose(np.asarray(out), np.tanh(np.ones((2, 8))),
                               rtol=1e-6)


def test_unknown_policy_raises():
    ckpt.configure(policy="not_a_policy")
    with pytest.raises(ValueError, match="policy"):
        ckpt.active_policy()


def test_configure_from_engine_config():
    from deepspeed_tpu.runtime.config import DeepSpeedConfig

    cfg = DeepSpeedConfig({
        "train_micro_batch_size_per_gpu": 1,
        "activation_checkpointing": {"partition_activations": True,
                                     "policy": "dots_saveable"},
    })
    ckpt.configure(deepspeed_config=cfg.cfg)
    c = ckpt.get_config()
    assert c["partition_activations"] is True
    assert c["policy"] == "dots_saveable"


def test_rng_tracker_fork_deterministic():
    tracker = ckpt.get_cuda_rng_tracker()
    tracker.reset()
    tracker.add("model-parallel-rng", 123)
    k1 = tracker.fork()
    k2 = tracker.fork()
    assert not np.array_equal(np.asarray(k1), np.asarray(k2))
    tracker.reset()
    tracker.add("model-parallel-rng", 123)
    k1b = tracker.fork()
    np.testing.assert_array_equal(np.asarray(k1), np.asarray(k1b))


def test_cpu_offload_policy_resolves():
    ckpt.configure(checkpoint_in_cpu=True)
    pol = ckpt.active_policy()  # must construct without error
    assert pol is not None


def test_save_attn_policies_resolve_and_train():
    """The save_attn / save_dots_and_attn composite policies resolve (to
    the names the attention paths put on their output and row statistics),
    and a training step under them matches nothing_saveable exactly
    (selective remat changes memory, not math)."""
    import deepspeed_tpu
    from deepspeed_tpu.models import TransformerConfig, TransformerLM

    ckpt.configure(policy="save_attn")
    assert ckpt.active_policy() is not None
    ckpt.reset()
    # the default waits for an engine's choice, and is nothing_saveable
    # to whatever traces a model without one
    assert ckpt.get_config()["policy"] == ckpt.AUTO
    assert ckpt.active_policy() is jax.checkpoint_policies.nothing_saveable

    cfg = TransformerConfig(vocab_size=64, hidden_size=32,
                            intermediate_size=64, num_layers=2, num_heads=4,
                            max_seq_len=32, use_flash=False, loss_chunk=0)
    import jax as _jax
    gm = 2 * _jax.device_count()  # micro x dp over the CPU test mesh
    batch = {"input_ids": np.random.default_rng(0).integers(
        0, 64, (1, gm, 32), dtype=np.int64)}
    losses = {}
    for policy in ("nothing_saveable", "save_dots_and_attn"):
        ckpt.reset()
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=TransformerLM(cfg),
            config={"train_micro_batch_size_per_gpu": 2,
                    "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                    "activation_checkpointing": {"policy": policy},
                    "steps_per_print": 10 ** 9})
        losses[policy] = float(engine.train_batch(batch=batch))
    assert np.isclose(losses["nothing_saveable"],
                      losses["save_dots_and_attn"], rtol=1e-5)


def test_policy_reduces_backward_recompute_in_hlo():
    """The remat policies change the COMPILED program, not just intent:
    counting dot ops in the optimized grad HLO, selective policies must
    recompute strictly less than full recompute (the round-3 MFU lever)."""
    from deepspeed_tpu.models import TransformerConfig, TransformerLM

    cfg = TransformerConfig(vocab_size=64, hidden_size=64,
                            intermediate_size=128, num_layers=2, num_heads=4,
                            max_seq_len=64, use_flash=False, loss_chunk=0)
    model = TransformerLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    ids = jnp.zeros((2, 64), jnp.int32)

    def count_dots(policy):
        ckpt.reset()
        ckpt.configure(policy=policy)
        hlo = jax.jit(jax.grad(
            lambda p: model.apply(p, {"input_ids": ids}))
        ).lower(params).compile().as_text()
        ckpt.reset()
        return hlo.count(" dot(")

    full = count_dots("nothing_saveable")
    dots = count_dots("dots_with_no_batch_dims_saveable")
    both = count_dots("save_dots_and_attn")
    assert dots < full
    assert both <= dots


# ---------------------------------------------------------------------------
# policy: auto -- what the engine keeps is chosen from the chip's memory
# ---------------------------------------------------------------------------
GB = 10 ** 9
SETS = [("save_attn", 1 * GB), ("save_more", 3 * GB)]


@pytest.mark.parametrize("limit,state,sets,want", [
    # budget = SAVE_SHARE x (limit - state) = 3.5 GB: the richest set fits
    (16 * GB, 2 * GB, SETS, ("save_more", 3 * GB)),
    # ... exactly at its threshold, and not a byte over it
    (14 * GB, 2 * GB, SETS, ("save_more", 3 * GB)),
    (14 * GB - 4, 2 * GB, SETS, ("save_attn", 1 * GB)),
    # the smaller set at its own threshold, and under it
    (6 * GB, 2 * GB, SETS, ("save_attn", 1 * GB)),
    (6 * GB - 4, 2 * GB, SETS, ("nothing_saveable", 0)),
    # a chip the state fills, or overfills
    (16 * GB, 15.5 * GB, SETS, ("nothing_saveable", 0)),
    (16 * GB, 17 * GB, SETS, ("nothing_saveable", 0)),
    # a backend that reports no limit (the CPU), a model that offers none
    (0, 2 * GB, SETS, ("nothing_saveable", 0)),
    (16 * GB, 2 * GB, [], ("nothing_saveable", 0)),
    # the benchmark's cells on a v5e (bytes_limit 16,909,336,064)
    (16_909_336_064, 1_754_000_000, [("save_attn", 1_245_708_288)],
     ("save_attn", 1_245_708_288)),
    (16_909_336_064, 4_605_000_000, [("save_attn", 830_472_192)],
     ("save_attn", 830_472_192)),
])
def test_choose_policy_thresholds(limit, state, sets, want):
    assert ckpt.SAVE_SHARE == 0.25
    assert ckpt.choose_policy(limit, state, sets) == want


@pytest.mark.parametrize("cell,fields,micro,tp,want", [
    # the benchmark's cells: layers x tokens a chip x heads x (head width
    # x bytes + a float32 row statistic)
    ("opt-125m.train-dense", dict(hidden_size=768, intermediate_size=3072,
                                  num_layers=12, num_heads=12,
                                  activation="relu"), 32, 1,
     [("save_attn", 12 * 65536 * 12 * (64 * 2 + 4))]),
    ("opt-1.3b.zero3-dp4", dict(hidden_size=2048, intermediate_size=8192,
                                num_layers=24, num_heads=32,
                                activation="relu"), 4, 1,
     [("save_attn", 24 * 8192 * 32 * 132)]),
    # heads split over the model axis
    ("tp2", dict(hidden_size=768, intermediate_size=3072, num_layers=12,
                 num_heads=12, activation="gelu"), 32, 2,
     [("save_attn", 6 * 65536 * 12 * 132)]),
    # below flash_min_seq the XLA path keeps no row statistics
    ("xla", dict(hidden_size=768, intermediate_size=2048, num_layers=2,
                 num_heads=12, flash_min_seq=4096), 2, 1,
     [("save_attn", 2 * 4096 * 12 * 128)]),
    ("no-remat", dict(hidden_size=768, intermediate_size=3072, num_layers=2,
                      num_heads=12, remat=False), 2, 1, []),
])
def test_model_save_sets_from_shapes(cell, fields, micro, tp, want):
    from deepspeed_tpu.models import TransformerConfig, TransformerLM
    from deepspeed_tpu.parallel.topology import MeshTopology, TopologyConfig

    model = TransformerLM(TransformerConfig(vocab_size=50272,
                                            max_seq_len=2048, **fields))
    if tp > 1:
        model.set_topology(MeshTopology(TopologyConfig(model=tp),
                                        devices=jax.devices()[:tp]))
    batch = {"input_ids": jax.ShapeDtypeStruct((1, micro, 2048), jnp.int32)}
    assert model.activation_save_sets(batch, micro, 2) == want


def _flash_engine(monkeypatch, devices, policy=None, limit=16 * GB):
    """Two flash-attention layers at toy widths behind an engine whose
    device reports ``limit`` bytes (the CPU reports none)."""
    import deepspeed_tpu
    from deepspeed_tpu.models import TransformerConfig, TransformerLM
    from deepspeed_tpu.parallel.topology import build_topology
    from deepspeed_tpu.runtime.config import DeepSpeedConfig
    from deepspeed_tpu.runtime.engine import DeepSpeedTpuEngine

    monkeypatch.setattr(DeepSpeedTpuEngine, "_device_bytes_limit",
                        lambda self: limit)
    config = {"train_micro_batch_size_per_gpu": 2,
              "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
              "zero_optimization": {
                  "stage": 3 if devices > 1 else 0,
                  "stage3_param_persistence_threshold": 0},
              "steps_per_print": 10 ** 9}
    if policy is not None:
        config["activation_checkpointing"] = {"policy": policy}
    cfg = TransformerConfig(vocab_size=64, hidden_size=64,
                            intermediate_size=128, num_layers=2, num_heads=2,
                            max_seq_len=128, flash_min_seq=128, loss_chunk=0,
                            norm="layernorm", activation="relu",
                            positional="learned")
    topo = build_topology(DeepSpeedConfig(config, world_size=devices),
                          devices=jax.devices()[:devices])
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=TransformerLM(cfg), config=config, topology=topo, seed=0)
    ids = np.random.default_rng(0).integers(0, 64, (1, 2 * devices, 128))
    return engine, {"input_ids": ids}


def _kernel_calls(engine, batch):
    """pallas_calls by name in the step's jaxpr. The layers are scanned:
    one forward body, one backward body, so a count is a count a layer."""
    import re
    dev_batch = engine._shard_batch(batch)
    engine._settle_remat_policy(dev_batch)
    text = str(engine._train_step.trace(
        engine.params, engine.master_params, engine.opt_state,
        engine.scale_state, engine._step_arr, engine._model_rng, dev_batch,
        engine.quant_reduce_state).jaxpr)
    return {k: len(re.findall(rf"name=flash_attention_{k}\b", text))
            for k in ("fwd", "bwd", "bwd_dq", "bwd_dkv")}, text


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("policy,fwd_calls", [
    (None, 1), ("save_attn", 1), ("save_dots_and_attn", 1),
    ("nothing_saveable", 2)])
def test_flash_forward_runs_once_a_layer(monkeypatch, devices, policy,
                                         fwd_calls):
    """With the kernel's output and row statistics kept, the recomputed
    layer holds no forward kernel; through ``sharded_attention``'s
    shard_map on four devices as on one. The default keeps them when the
    memory is there."""
    engine, batch = _flash_engine(monkeypatch, devices, policy)
    calls, text = _kernel_calls(engine, batch)
    # one backward call a layer: the fused kernel, not the dq + dk/dv pair
    assert calls == {"fwd": fwd_calls, "bwd": 1, "bwd_dq": 0, "bwd_dkv": 0}
    assert "shard_map" in text      # sharded_attention's, around the kernel
    assert engine.remat_policy[0] == (policy or "save_attn")
    # one name on a kernel call's output: a second would save it twice
    assert text.count("name=attn_out") == fwd_calls
    engine.destroy()


@pytest.mark.parametrize("devices", [1, 4])
def test_kept_residuals_leave_the_gradients_alone(monkeypatch, devices):
    """Two steps under the default (memory there) and under
    nothing_saveable: the backward reads a saved o and lse instead of an
    identical recomputed pair, so losses and updated weights agree."""
    out = {}
    for policy in (None, "nothing_saveable"):
        engine, batch = _flash_engine(monkeypatch, devices, policy)
        losses = [float(engine.train_batch(batch=batch)) for _ in range(2)]
        assert engine.remat_policy[0] == (policy or "save_attn")
        out[policy] = (losses, jax.tree.map(np.asarray, engine.params))
        engine.destroy()
    np.testing.assert_allclose(out[None][0], out["nothing_saveable"][0],
                               rtol=1e-6)
    for a, b in zip(jax.tree.leaves(out[None][1]),
                    jax.tree.leaves(out["nothing_saveable"][1])):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("limit,policy,want", [
    # the toy's state is 995,328 B a device, its set 135,168 B: kept from
    # a limit of 995,328 + 4 x 135,168 = 1,536,000 B up
    (16 * GB, None, "save_attn"),
    (1_536_000, None, "save_attn"),
    (1_535_999, None, "nothing_saveable"),
    (1_000_000, None, "nothing_saveable"),    # the state fills the chip
    (0, None, "nothing_saveable"),            # no limit reported
    # a policy written in the config wins, whatever the memory
    (16 * GB, "nothing_saveable", "nothing_saveable"),
    (1_000_000, "save_attn", "save_attn"),
    (16 * GB, "dots_saveable", "dots_saveable"),
])
def test_engine_settles_the_policy_once(monkeypatch, limit, policy, want):
    from deepspeed_tpu.telemetry import get_registry

    engine, batch = _flash_engine(monkeypatch, 1, policy, limit=limit)
    state = engine._placed_state_bytes()
    assert state == 995_328        # float32 weights and two Adam moments
    engine._settle_remat_policy(engine._shard_batch(batch))
    name, saved = engine.remat_policy
    assert name == want
    assert ckpt.get_config()["policy"] == want
    sets = dict(engine.model.activation_save_sets(batch, 2, 4))
    if policy is None:
        assert saved == sets.get(want, 0)
        reg = get_registry()
        chosen = {labels: s.value
                  for labels, s in reg.get("remat_policy").series()}
        assert chosen[(want,)] == 1 and sum(chosen.values()) == 1
        assert [s.value for _, s in
                reg.get("remat_saved_bytes").series()] == [saved]
    else:
        assert saved is None
    # settled once: a later, fuller chip does not move it
    monkeypatch.setattr(type(engine), "_device_bytes_limit", lambda self: 1)
    engine._settle_remat_policy(engine._shard_batch(batch))
    assert engine.remat_policy == (name, saved)
    engine.destroy()


def test_a_step_that_does_not_fit_falls_back_once(monkeypatch, caplog):
    """The compiler refusing the chosen set (RESOURCE_EXHAUSTED before
    anything ran) costs one more compile, with nothing_saveable, and a
    line in the log; any other error is the caller's."""
    from deepspeed_tpu.utils.logging import logger as ds_logger

    engine, batch = _flash_engine(monkeypatch, 1)
    calls = []

    def refuse(*args):
        calls.append("refused")
        raise jax.errors.JaxRuntimeError(
            "RESOURCE_EXHAUSTED: Ran out of memory in memory space hbm")

    engine._train_step = refuse
    ds_logger.addHandler(caplog.handler)    # the logger does not propagate
    try:
        loss = engine.train_batch(batch=batch)
    finally:
        ds_logger.removeHandler(caplog.handler)
    assert np.isfinite(loss) and calls == ["refused"]
    assert engine.remat_policy == ("nothing_saveable", 0)
    assert "did not fit with save_attn" in caplog.text
    assert _kernel_calls(engine, batch)[0]["fwd"] == 2
    # a second refusal is not caught: nothing smaller is left to try
    engine._train_step = refuse
    with pytest.raises(jax.errors.JaxRuntimeError):
        engine.train_batch(batch=batch)
    engine.destroy()
