"""Quantized ring gradient reduction (zero_optimization.quantized_reduce).

The contract under test (comm/quantized.py ring_*_quant +
runtime/grad_overlap.py quant plumbing + the engine's threaded
error-feedback state):

* the quantized ring primitives reduce/gather EXACTLY when the values
  are representable on the int8 grid, and within per-hop quantization
  error otherwise; the quantized all-gather leaves every device with
  IDENTICAL rows (a source keeping its exact fp32 row would silently
  diverge the replicas);
* int8-ring training tracks the fp32 ring closely and the int8 a2a
  (ZeRO++ qgZ) reference within tolerance, across stages 0-2 and
  gradient accumulation;
* the error-feedback residual is threaded through the jitted step
  (nonzero after a step, finite-gated on fp16 skip steps so overflow
  garbage can never poison it) and drives a toy-model loss curve to
  within tolerance of fp32;
* config validation: bad values, stage 3, and the qgZ conflict reject
  loudly at load; one compiled program per run (no per-step retraces).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from tests.unit.simple_model import SimpleModel, base_config, random_batches

HIDDEN = 32


def _train(stage, qr, gas=1, dtype=None, steps=3, block=64, rbs=600,
           mode="bucketed", scale_power=None, zpp_g=False, seed=0):
    cfg = base_config(micro=2, gas=gas, stage=stage, dtype=dtype, lr=1e-2)
    zc = cfg["zero_optimization"]
    zc["overlap_grad_reduce"] = mode
    zc["reduce_bucket_size"] = rbs
    zc["allgather_bucket_size"] = rbs
    if qr:
        zc["quantized_reduce"] = qr
        zc["quant_block"] = block
    if zpp_g:
        zc["zero_quantized_gradients"] = True
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


# ----------------------------------------------------------------------
# primitive level: the quantized ring collectives
# ----------------------------------------------------------------------
def _mesh():
    from jax.sharding import Mesh
    return Mesh(np.asarray(jax.devices()), ("d",))


def test_ring_reduce_scatter_quant_errors_account_for_deviation():
    """The EF contract at the primitive: row r's ring result deviates
    from the exact sum by EXACTLY the errors the senders recorded for
    row r (each hop's quantization error is sender-side knowledge), so
    result + sum-over-devices(err) reconstructs the true sum. Zeros ride
    the scale=1 guard and come out exact with zero error."""
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.comm.quantized import (ring_reduce_scatter_quant,
                                              shard_map_unchecked)

    n = jax.device_count()
    M = 256
    rng = np.random.default_rng(0)
    fuzz = rng.normal(size=(n, n, M)).astype(np.float32)

    def body(buf):
        row, err = ring_reduce_scatter_quant(buf[0], "d", n, block=64)
        return row[None], err[None]

    fn = jax.jit(shard_map_unchecked(
        body, _mesh(), in_specs=P("d", None, None),
        out_specs=(P("d", None), P("d", None, None))))
    rows, errs = fn(jnp.asarray(fuzz))
    want = fuzz.sum(axis=0)        # true per-row sums, row r on device r
    got = np.asarray(rows)
    # within per-hop quantization error...
    np.testing.assert_allclose(got, want, atol=(n - 1) * 0.2)
    assert float(np.abs(np.asarray(errs)).max()) > 0.0
    # ...and the recorded errors close the gap (up to f32 rounding of
    # the subtraction chain)
    np.testing.assert_allclose(got + np.asarray(errs).sum(axis=0), want,
                               rtol=1e-5, atol=1e-4)
    # zeros: scale guard path, exact, no error
    z_rows, z_errs = fn(jnp.zeros((n, n, M), jnp.float32))
    assert float(np.abs(np.asarray(z_rows)).max()) == 0.0
    assert float(np.abs(np.asarray(z_errs)).max()) == 0.0


def test_ring_all_gather_quant_replicated_identical():
    """Every device reconstructs the SAME dequantized rows — including
    the source's own row (kept dequantized on purpose: an exact local
    copy would diverge the replicas)."""
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.comm.quantized import (ring_all_gather_quant,
                                              shard_map_unchecked)

    n = jax.device_count()
    M = 128
    rng = np.random.default_rng(1)
    rows = rng.normal(size=(n, M)).astype(np.float32)

    def body(row):
        full, err = ring_all_gather_quant(row[0], "d", n, block=64)
        return full[None], err[None]

    fn = jax.jit(shard_map_unchecked(
        body, _mesh(), in_specs=P("d", None),
        out_specs=(P("d", None, None), P("d", None))))
    full, err = fn(jnp.asarray(rows))
    full = np.asarray(full)          # [n devices, n rows, M]
    for d in range(1, n):
        np.testing.assert_array_equal(full[d], full[0])
    # err is the source's quantization error: full + err == input rows
    np.testing.assert_allclose(full[0] + np.asarray(err), rows,
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(full[0], rows, atol=0.05)


# ----------------------------------------------------------------------
# engine level: parity across stages / GAS / transports
# ----------------------------------------------------------------------
@pytest.mark.parametrize("stage,gas", [(0, 1), (1, 2), (2, 2)])
def test_int8_ring_tracks_fp32_across_stages(stage, gas):
    """Stages 0-2 x gradient accumulation: the int8 ring with error
    feedback stays within tight tolerance of the fp32 ring on the same
    bucket plan (the loss-curve proxy the EF residual exists for)."""
    eng_q, loss_q, p_q = _train(stage, "int8", gas=gas)
    eng_f, loss_f, p_f = _train(stage, None, gas=gas)
    assert eng_q.quant_reduce_state, "EF state missing"
    np.testing.assert_allclose(loss_q, loss_f, rtol=2e-3, atol=2e-3)
    # params are looser than losses: Adam turns a tiny grad perturbation
    # into an O(lr)-sized update (sign-sensitive), so per-element drift
    # up to a few lr is expected while the loss curve stays tight
    for x, y in zip(jax.tree.leaves(p_q), jax.tree.leaves(p_f)):
        np.testing.assert_allclose(x, y, atol=5e-2)
    # the residual is live (quantization happened, EF is carrying it)
    assert eng_q._last_metrics.get("quant_error_norm", 0.0) > 0.0
    # one compiled program: the EF threading must not retrace per step
    assert eng_q._train_step._cache_size() == 1


def test_int8_ring_vs_int8_a2a_reference():
    """Stage 2: the ring transport vs the ZeRO++ qgZ int8 all-to-all —
    two quantized exchanges of the same gradients agree within combined
    quantization tolerance (the a2a is the in-tree reference)."""
    _, loss_ring, p_ring = _train(2, "int8")
    _, loss_a2a, p_a2a = _train(2, None, zpp_g=True)
    np.testing.assert_allclose(loss_ring, loss_a2a, rtol=5e-3, atol=5e-3)
    for x, y in zip(jax.tree.leaves(p_ring), jax.tree.leaves(p_a2a)):
        np.testing.assert_allclose(x, y, atol=5e-2)


def test_fp8_ring_trains():
    """fp8 wire: same plumbing, e4m3 payloads; the toy loss curve stays
    within (looser) tolerance of fp32."""
    _, loss_q, _ = _train(0, "fp8", gas=2)
    _, loss_f, _ = _train(0, None, gas=2)
    np.testing.assert_allclose(loss_q, loss_f, rtol=5e-2, atol=5e-2)


def test_fp16_skip_keeps_residual_clean():
    """fp16 with an absurd scale: every step overflows. The finite gate
    must keep the EF residual at its pre-step value (zeros) — overflow
    garbage absorbed into the residual would poison every later step —
    and params stay untouched like the unquantized skip path."""
    eng_q, _, p_q = _train(2, "int8", gas=2, dtype="fp16",
                           scale_power=24)
    eng_f, _, p_f = _train(2, None, gas=2, dtype="fp16", scale_power=24)
    assert eng_q.skipped_steps == eng_f.skipped_steps > 0
    for x, y in zip(jax.tree.leaves(p_q), jax.tree.leaves(p_f)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for leaf in jax.tree.leaves(eng_q.quant_reduce_state):
        arr = np.asarray(leaf)
        assert np.isfinite(arr).all()
        np.testing.assert_array_equal(arr, np.zeros_like(arr))


def test_quantized_bytes_gauge_and_plan_math():
    """training_reduce_quantized_bytes reports the plan's quantized ring
    wire bytes, >=3.5x below the fp32 ring's."""
    from deepspeed_tpu.runtime.grad_overlap import ring_wire_bytes
    from deepspeed_tpu.telemetry import MetricsRegistry, set_registry
    prev = set_registry(MetricsRegistry())
    try:
        eng, _, _ = _train(2, "int8", steps=1, block=2048)
        dp = eng.ds_config.dp_world_size
        wb = ring_wire_bytes(eng.grad_bucket_plan, dp)
        wb_q = ring_wire_bytes(eng.grad_bucket_plan, dp, quantized=True,
                               quant_block=2048)
        assert eng.telemetry.gauge(
            "training_reduce_quantized_bytes", "").value == wb_q > 0
        assert wb / wb_q >= 3.5
        assert eng.telemetry.gauge(
            "training_quant_error_feedback_norm", "").value > 0.0
    finally:
        set_registry(prev)


# ----------------------------------------------------------------------
# config validation
# ----------------------------------------------------------------------
def test_config_validates_quantized_reduce():
    from deepspeed_tpu.runtime.config import ConfigError, DeepSpeedConfig
    with pytest.raises(ConfigError, match="quantized_reduce"):
        DeepSpeedConfig({"train_micro_batch_size_per_gpu": 1,
                         "zero_optimization":
                             {"quantized_reduce": "int4"}})
    with pytest.raises(ConfigError, match="quant_block"):
        DeepSpeedConfig({"train_micro_batch_size_per_gpu": 1,
                         "zero_optimization":
                             {"quantized_reduce": "int8",
                              "quant_block": 0}})
    with pytest.raises(ConfigError, match="stages 0-2"):
        DeepSpeedConfig({"train_micro_batch_size_per_gpu": 1,
                         "zero_optimization":
                             {"stage": 3, "quantized_reduce": "int8"}})
    with pytest.raises(ConfigError, match="pick one transport"):
        DeepSpeedConfig({"train_micro_batch_size_per_gpu": 1,
                         "zero_optimization":
                             {"stage": 2, "quantized_reduce": "int8",
                              "zero_quantized_gradients": True}})
