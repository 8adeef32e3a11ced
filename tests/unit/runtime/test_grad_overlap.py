"""Overlapped bucketed gradient reduction (runtime/grad_overlap.py).

Covers the PR's acceptance bars: bucketed and monolithic reduction are
BIT-identical across ZeRO stages, gradient accumulation, and fp16
loss-scale skip steps; the bucket plan honors (and loudly validates) the
previously-dead ``reduce_bucket_size``/``allgather_bucket_size`` knobs;
one compiled program per bucket layout; and the fused ``grads_finite``
graph shape.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.runtime.grad_overlap import (ALL_REDUCE, REDUCE_SCATTER,
                                                GradUnit, build_bucket_plan,
                                                order_units)
from tests.unit.simple_model import SimpleModel, base_config, random_batches

HIDDEN = 32


def _train(stage, mode, gas=1, dtype=None, rbs=None, steps=3, seed=0,
           scale_power=None):
    cfg = base_config(micro=2, gas=gas, stage=stage, dtype=dtype, lr=1e-2)
    zc = cfg["zero_optimization"]
    zc["overlap_grad_reduce"] = mode
    zc["stage3_param_persistence_threshold"] = 0
    if rbs:
        zc["reduce_bucket_size"] = rbs
        zc["allgather_bucket_size"] = rbs
    if scale_power is not None:
        cfg["fp16"]["initial_scale_power"] = scale_power
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=HIDDEN, nlayers=3), config=cfg,
        seed=seed)
    gm = engine.micro_batch_size * engine.ds_config.dp_world_size
    losses = []
    for b in random_batches(steps, gm * engine.gas, HIDDEN, seed=7):
        gb = {k: v.reshape(engine.gas, gm, HIDDEN) for k, v in b.items()}
        losses.append(engine.train_batch(batch=gb))
    params = jax.tree.map(lambda x: np.asarray(jax.device_get(x)),
                          engine.params)
    return engine, losses, params


def _assert_trees_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ----------------------------------------------------------------------
# Parity: bucketed vs monolithic reduction is BIT-identical
# ----------------------------------------------------------------------
@pytest.mark.parametrize("stage", [0, 2, 3])
@pytest.mark.parametrize("gas", [1, 2])
def test_bucketed_matches_monolithic_bit_identical(stage, gas):
    """Small reduce_bucket_size (many buckets) vs effectively-infinite
    (one bucket = the monolithic collective): same losses, same final
    params, to the BIT. Bucketing only changes message scheduling."""
    eng_b, loss_b, p_b = _train(stage, "bucketed", gas=gas, rbs=600)
    eng_m, loss_m, p_m = _train(stage, "bucketed", gas=gas, rbs=10 ** 9)
    if stage in (0, 2):  # stage 3 reduces via the gather VJP, no buckets
        assert eng_b.grad_bucket_plan.num_buckets > \
            eng_m.grad_bucket_plan.num_buckets
    assert loss_b == loss_m
    _assert_trees_equal(p_b, p_m)


@pytest.mark.parametrize("stage", [0, 2])
def test_bucketed_tracks_legacy_gspmd(stage):
    """Against the legacy GSPMD-inserted reduction the match is fp-exact
    up to summation order (the ring fixes a deterministic device order;
    GSPMD's fused collective uses its own)."""
    _, loss_b, p_b = _train(stage, "bucketed", gas=2, rbs=600)
    eng, loss_l, p_l = _train(stage, "off", gas=2)
    assert eng.grad_overlap_mode == "off"
    np.testing.assert_allclose(loss_b, loss_l, rtol=1e-5)
    for x, y in zip(jax.tree.leaves(p_b), jax.tree.leaves(p_l)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-4, atol=1e-5)


def test_fp16_skip_steps_parity():
    """fp16 with an absurd initial scale: every step overflows and is
    skipped identically on both layouts — params untouched, scale state
    equal, skip counters equal (the functional skip-step rides the shared
    epilogue, reference stage3.py:2018)."""
    eng_b, loss_b, p_b = _train(2, "bucketed", gas=2, dtype="fp16",
                                rbs=600, scale_power=24)
    eng_m, loss_m, p_m = _train(2, "bucketed", gas=2, dtype="fp16",
                                rbs=10 ** 9, scale_power=24)
    assert eng_b.skipped_steps > 0
    assert eng_b.skipped_steps == eng_m.skipped_steps
    assert loss_b == loss_m
    _assert_trees_equal(p_b, p_m)
    _assert_trees_equal(eng_b.scale_state, eng_m.scale_state)


def test_fp16_training_parity_no_overflow():
    """fp16 at a sane scale: steps apply, and bucketed == monolithic to
    the bit through the scale/unscale path too."""
    eng_b, loss_b, p_b = _train(2, "bucketed", gas=2, dtype="fp16",
                                rbs=600, scale_power=8)
    eng_m, loss_m, p_m = _train(2, "bucketed", gas=2, dtype="fp16",
                                rbs=10 ** 9, scale_power=8)
    assert eng_b.global_steps == 3 and eng_b.skipped_steps == 0
    assert loss_b == loss_m
    _assert_trees_equal(p_b, p_m)


# ----------------------------------------------------------------------
# One compiled program per bucket layout
# ----------------------------------------------------------------------
def test_one_program_per_bucket_layout():
    """Repeated steps reuse ONE executable (the bucket plan is static
    Python baked into the trace, not per-bucket programs or per-step
    retraces); a different layout is a different program."""
    eng, _, _ = _train(2, "bucketed", rbs=600, steps=3)
    assert eng.grad_bucket_plan.num_buckets >= 2
    assert eng._train_step._cache_size() == 1
    eng2, _, _ = _train(2, "bucketed", rbs=10 ** 9, steps=2)
    assert eng2.grad_bucket_plan.num_buckets == 1
    assert eng2._train_step._cache_size() == 1
    assert eng.grad_bucket_plan.layout_key() != \
        eng2.grad_bucket_plan.layout_key()


# ----------------------------------------------------------------------
# Bucket plan semantics (the once-dead config knobs, now consumed)
# ----------------------------------------------------------------------
def _units(numels, kinds, names=None):
    names = names or [f"leaf{i}" for i in range(len(numels))]
    return [GradUnit(i, -1, n, names[i], k)
            for i, (n, k) in enumerate(zip(numels, kinds))]


def test_plan_honors_reduce_bucket_size_cap():
    units = _units([100, 100, 100, 250, 50], [REDUCE_SCATTER] * 5)
    plan = build_bucket_plan(units, reduce_bucket_size=200,
                             allgather_bucket_size=10 ** 9)
    assert plan.num_buckets >= 3
    for b in plan.buckets:
        assert b.numel <= 200 or len(b.indices) == 1  # oversize unit alone
    covered = sorted(u for b in plan.buckets for u in b.indices)
    assert covered == list(range(5))


def test_plan_allgather_cap_bounds_allreduce_buckets():
    units = _units([100, 100, 100, 100], [ALL_REDUCE] * 4)
    plan = build_bucket_plan(units, reduce_bucket_size=10 ** 9,
                             allgather_bucket_size=150)
    # min(reduce, allgather) = 150 caps all-reduce buckets -> one per unit
    assert plan.num_buckets == 4
    assert plan.allreduce_bucket_numel == 150


def test_plan_rejects_nonpositive_caps():
    units = _units([10], [ALL_REDUCE])
    with pytest.raises(ValueError, match="bucket sizes"):
        build_bucket_plan(units, reduce_bucket_size=0,
                          allgather_bucket_size=100)


def test_order_units_reversed_and_layer_major():
    """Backward produces the tree's tail first and deep layers first: the
    unit order is reversed tree order with the stacked block expanded
    layer-major in reversed layer order."""
    names = ["['embed']", "['layers']['w1']", "['layers']['w2']",
             "['head']"]
    numels = [80, 40, 40, 80]
    kinds = [ALL_REDUCE] * 4
    layers = [0, 2, 2, 0]
    stacked = [False, True, True, False]
    units = order_units(names, numels, kinds, layers, stacked)
    assert [u.name for u in units] == [
        "['head']",
        "['layers']['w2'][1]", "['layers']['w1'][1]",
        "['layers']['w2'][0]", "['layers']['w1'][0]",
        "['embed']"]
    assert all(u.numel == 20 for u in units if u.layer >= 0)


def test_config_validates_bucket_knobs():
    from deepspeed_tpu.runtime.config import ConfigError, DeepSpeedConfig
    for key in ("reduce_bucket_size", "allgather_bucket_size",
                "stage3_prefetch_bucket_size"):
        with pytest.raises(ConfigError, match=key):
            DeepSpeedConfig({"train_micro_batch_size_per_gpu": 1,
                             "zero_optimization": {key: 0}})
    with pytest.raises(ConfigError, match="overlap_grad_reduce"):
        DeepSpeedConfig({"train_micro_batch_size_per_gpu": 1,
                         "zero_optimization":
                             {"overlap_grad_reduce": "sideways"}})


def test_forced_mode_rejects_unsupported_composition():
    from deepspeed_tpu.runtime.config import ConfigError
    cfg = base_config(micro=2, stage=2)
    cfg["zero_optimization"]["overlap_grad_reduce"] = "bucketed"
    cfg["compression_training"] = {
        "weight_quantization": {"shared_parameters": {"enabled": True},
                                "different_groups": {}}}
    with pytest.raises((ConfigError, NotImplementedError)):
        deepspeed_tpu.initialize(model=SimpleModel(hidden_dim=HIDDEN),
                                 config=cfg)


def test_auto_mode_gates_off_non_dp_meshes():
    cfg = base_config(micro=2, stage=2, tensor_parallel_size=2)
    from tests.unit.simple_model import SimpleTPModel
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=SimpleTPModel(hidden_dim=HIDDEN), config=cfg)
    assert engine.grad_overlap_mode == "off"
    assert engine.grad_bucket_plan is None


# ----------------------------------------------------------------------
# Telemetry gauges
# ----------------------------------------------------------------------
def test_bucket_telemetry_gauges():
    from deepspeed_tpu.telemetry import MetricsRegistry, set_registry
    prev = set_registry(MetricsRegistry())
    try:
        eng, _, _ = _train(2, "bucketed", rbs=600, steps=1)
        snap = eng.telemetry.snapshot()
        names = {s["name"] for s in snap["series"]} \
            if isinstance(snap, dict) and "series" in snap else None
        bucket_bytes = eng.telemetry.gauge(
            "training_reduce_bucket_bytes", "").value
        assert bucket_bytes == eng.grad_bucket_plan.max_bucket_bytes > 0
        gm = eng.micro_batch_size * eng.ds_config.dp_world_size
        b = random_batches(1, gm * eng.gas, HIDDEN)[0]
        gb = {k: v.reshape(eng.gas, gm, HIDDEN) for k, v in b.items()}
        # what the compiler scheduled is a report of its own, asked for
        # directly; it is no longer published under the name of a time
        from deepspeed_tpu.utils.xla_profile import \
            grad_exchange_report_from_compiled
        rep = grad_exchange_report_from_compiled(eng.lower_train_step(gb))
        assert rep.total >= 0 and 0.0 <= rep.exposed_fraction <= 1.0
        assert "training_comm_exposed_fraction" not in \
            eng.telemetry.render_prometheus()
    finally:
        set_registry(prev)


# ----------------------------------------------------------------------
# grads_finite: one fused reduction, not an O(n) logical_and chain
# ----------------------------------------------------------------------
def test_grads_finite_correct():
    from deepspeed_tpu.runtime.fp16.loss_scaler import grads_finite
    clean = {"a": jnp.ones((4, 4)), "b": jnp.zeros((3,))}
    assert bool(grads_finite(clean))
    assert not bool(grads_finite({**clean, "c": jnp.asarray([jnp.inf])}))
    assert not bool(grads_finite({**clean, "c": jnp.asarray([jnp.nan])}))
    assert bool(grads_finite({}))


def test_grads_finite_graph_has_no_and_chain():
    from deepspeed_tpu.runtime.fp16.loss_scaler import grads_finite
    tree = {f"l{i}": jnp.ones((8,)) for i in range(32)}
    jaxpr = jax.make_jaxpr(grads_finite)(tree)
    n_and = sum(1 for e in jaxpr.jaxpr.eqns if e.primitive.name == "and")
    assert n_and == 0, f"expected fused reduction, found {n_and} and-ops"


def test_forced_mode_rejects_pipeline_mesh():
    """'bucketed' on a pipe>1 mesh must raise like every other hard
    blocker, not silently train with the legacy reduction."""
    from deepspeed_tpu.runtime.config import ConfigError
    from deepspeed_tpu.runtime.pipe.module import LayerSpec, PipelineModule

    class Lin:
        def __init__(self, d):
            self.d = d
        def init(self, rng):
            return {"w": jax.random.normal(rng, (self.d, self.d)) * 0.02}
        def apply(self, params, x):
            return x @ params["w"]

    def loss(h, batch):
        return jnp.mean((h - batch["y"]) ** 2)

    pm = PipelineModule([LayerSpec(Lin, HIDDEN) for _ in range(4)], loss,
                        input_ndim=2)
    cfg = base_config(micro=2, gas=2, stage=0)
    cfg["pipeline"] = {"stages": 2}
    cfg["zero_optimization"]["overlap_grad_reduce"] = "bucketed"
    with pytest.raises(ConfigError, match="pipe"):
        deepspeed_tpu.initialize(model=pm, config=cfg)
