"""ZeRO-3's gather-on-use on the CPU mesh (four virtual devices):
``zero/partition.scanned_gather_on_use`` handed to ``TransformerLM`` by
the engine. The placement changes and the sums do not: stage 3 at dp 4
trains as stage 0 does; where no leaf is sharded (stage 0, a gather
world of 1, a tree under the persistence threshold) the step is the
program it was; the manual program never receives the function. What
the function does to the COMPILED backward on the chip's compiler is
``tests/unit/ops/test_kernels_lower_tpu.py``'s."""

import dataclasses

import numpy as np
import pytest

import jax

import deepspeed_tpu
from deepspeed_tpu.models import TransformerLM
from deepspeed_tpu.models.transformer import tiny_test
from deepspeed_tpu.parallel.topology import build_topology
from deepspeed_tpu.runtime.config import DeepSpeedConfig
from deepspeed_tpu.telemetry import get_registry

SEQ = 64
STEPS = 3
# OPT's block (the benchmark cell's): layernorm, relu, biases, learned
# positions, a tied head: 16 leaves a layer
CFG = dataclasses.replace(
    tiny_test(seq=SEQ), norm="layernorm", activation="relu",
    positional="learned", attn_bias=True, tie_embeddings=True)
LAYER_LEAVES = 16

# name: (stage, devices, overlap_grad_reduce, persistence threshold)
ENGINES = {
    "stage0": (0, 4, "off", 0),
    "stage3": (3, 4, "auto", 0),
    "stage3-dp1": (3, 1, "auto", 0),
    "stage3-persistent": (3, 4, "auto", 10 ** 9),
    "stage2-bucketed": (2, 4, "bucketed", 0),
    "stage3-bucketed": (3, 4, "bucketed", 0),
}


@pytest.fixture(scope="module")
def engines():
    """Built on first use and shared by the cases: (engine, batch, what
    the registry's two gauges read right after the build)."""
    built = {}

    def get(name):
        if name not in built:
            stage, devices, overlap, threshold = ENGINES[name]
            config = {
                "train_micro_batch_size_per_gpu": 2 * 4 // devices,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "zero_optimization": {
                    "stage": stage, "overlap_grad_reduce": overlap,
                    "stage3_param_persistence_threshold": threshold},
                "steps_per_print": 10 ** 9}
            topo = build_topology(
                DeepSpeedConfig(config, world_size=devices),
                devices=jax.devices()[:devices])
            engine, _, _, _ = deepspeed_tpu.initialize(
                model=TransformerLM(CFG), config=config, topology=topo)
            reg = get_registry()
            gauges = (reg.get("training_gather_on_use_leaves").value,
                      reg.get("training_gather_on_use_layer_bytes").value)
            ids = np.random.default_rng(0).integers(
                0, CFG.vocab_size, (1, 8, SEQ))
            built[name] = engine, {"input_ids": ids}, gauges
        return built[name]

    yield get
    for engine, _, _ in built.values():
        engine.destroy()


def _layer_bytes(engine):
    return sum(int(np.prod(a.shape[1:])) * a.dtype.itemsize
               for a in jax.tree.leaves(engine.params["layers"]))


def _parity(engines):
    """Stage 3 at dp 4 against stage 0 on the same batches: losses and
    parameters after three steps, inside the tolerance
    ``test_zero_stages_match_baseline`` holds the stages to, with every
    leaf of a layer taking the function."""
    ref, batch, _ = engines("stage0")
    got, _, gauges = engines("stage3")
    assert gauges == (LAYER_LEAVES, _layer_bytes(got))
    assert got.gather_on_use_leaves == LAYER_LEAVES
    assert got.model.layer_param_gather is not None
    eval_ref, eval_got = ref.eval_batch(batch=batch), got.eval_batch(
        batch=batch)       # forward only: the gather, no transpose
    np.testing.assert_allclose(eval_got, eval_ref, rtol=2e-5)
    losses = [[e.train_batch(batch=batch) for _ in range(STEPS)]
              for e in (ref, got)]
    np.testing.assert_allclose(losses[1], losses[0], rtol=2e-5)
    assert losses[0][-1] < losses[0][0]
    for a, b in zip(jax.tree.leaves(ref.params), jax.tree.leaves(got.params)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-5, atol=2e-5)
    p = got.params["layers"]["w_down"]
    assert not p.sharding.is_fully_replicated


def _lowered(engine, batch, withheld=False):
    """The step's lowered text, as built or with the function withheld
    from the model. The step is built anew each time: a jit that has
    traced these arguments once does not trace them again."""
    handed = engine.model.layer_param_gather
    try:
        if withheld:
            engine.model.layer_param_gather = None
        engine._build_train_step()
        return engine._lower_train_step(batch).as_text()
    finally:
        engine.model.layer_param_gather = handed


def _identity(name):
    def case(engines):
        """No leaf is sharded: nothing takes the function, both counts
        read 0, and the step lowers to the text it lowers to with the
        function withheld from the model."""
        engine, batch, gauges = engines(name)
        assert gauges == (0, 0)
        assert engine.model.layer_param_gather is None
        assert _lowered(engine, batch) == _lowered(engine, batch, True)
    return case


def _withheld_differs(engines):
    """The comparison the identity cases make can see the function: the
    stage-3 dp-4 step carries a leaf's constraints (forward, recompute,
    transpose) only while the model holds it."""
    engine, batch, _ = engines("stage3")
    with_it = _lowered(engine, batch).count("sharding_constraint")
    without = _lowered(engine, batch, True).count("sharding_constraint")
    assert with_it >= without + 2 * LAYER_LEAVES, (with_it, without)
    engine._build_train_step()


def _manual(name):
    def case(engines):
        """The manual program's dp axes are manual inside its shard_map,
        where a constraint over them is an error: it is not handed the
        function, its counts read 0, and it still compiles and steps."""
        engine, batch, gauges = engines(name)
        assert engine.grad_overlap_mode == "bucketed"
        assert gauges == (0, 0)
        assert engine.model.layer_param_gather is None
        engine.lower_train_step(batch)
        assert np.isfinite(engine.train_batch(batch=batch))
    return case


CASES = {
    "stage3-dp4-matches-stage0": _parity,
    "stage0-is-the-program-it-was": _identity("stage0"),
    "stage3-dp1-is-the-program-it-was": _identity("stage3-dp1"),
    "under-the-persistence-threshold-is-the-program-it-was":
        _identity("stage3-persistent"),
    "withheld-the-stage3-step-differs": _withheld_differs,
    "the-manual-program-stage2-is-not-handed-it": _manual("stage2-bucketed"),
    "the-manual-program-stage3-is-not-handed-it": _manual("stage3-bucketed"),
}


@pytest.mark.parametrize("case", list(CASES))
def test_gather_on_use(engines, case):
    CASES[case](engines)
