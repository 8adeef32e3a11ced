"""The training step names itself: ``jax.named_scope``s in the step
builders and the model reach the compiled program's ``op_name``s,
``utils/xla_profile.scope_map`` reads them back per instruction and
``scope_phase`` sorts them into phases; the small jits carry function
names; ``train_batch`` runs inside five spans."""

import collections
import logging
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models import TransformerLM
from deepspeed_tpu.models.transformer import tiny_test
from deepspeed_tpu.parallel.topology import build_topology
from deepspeed_tpu.runtime.config import DeepSpeedConfig
from deepspeed_tpu.telemetry import memory, trace
from deepspeed_tpu.utils.xla_profile import PHASES, scope_map, scope_phase

SEQ = 128
COLLECTIVE = re.compile(
    r"all-gather|reduce-scatter|all-reduce|all-to-all|collective")


def _engine(stage, devices, offload=None):
    zero = {"stage": stage, "stage3_param_persistence_threshold": 0}
    if offload:
        zero["offload_optimizer"] = offload
    config = {"train_micro_batch_size_per_gpu": 2,
              "gradient_accumulation_steps": 1,
              "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
              "bf16": {"enabled": True}, "zero_optimization": zero,
              "steps_per_print": 10 ** 9}
    cfg = tiny_test()
    assert cfg.remat
    topo = build_topology(DeepSpeedConfig(config, world_size=devices),
                          devices=jax.devices()[:devices])
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=TransformerLM(cfg), config=config, topology=topo)
    rows = engine.micro_batch_size * engine.ds_config.dp_world_size
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size,
                                            (1, rows, SEQ))
    return engine, {"input_ids": ids}


def _resident(stage, devices):
    engine, batch = _engine(stage, devices)
    engine.lower_train_step(batch)
    # through the record the benchmark's reader asks: it outlives the engine
    engine.destroy()
    return memory.scopes("train_step")


def _grad_only(offload):
    """The two offload builders: the device program stops at the clipped
    gradients (``lower_train_step`` refuses them), so compile it here."""
    engine, batch = _engine(2, 4, offload=offload)
    compiled = engine._grad_step.lower(
        engine.params, engine.scale_state, engine._step_arr,
        engine._model_rng, engine._shard_batch(batch)).compile()
    return scope_map(compiled)


def _streamed():
    """forward()/backward()/step(): the gradient and the update are two
    programs; their maps laid together."""
    engine, batch = _engine(0, 1)
    micro = {"input_ids": batch["input_ids"][0]}
    engine.forward(micro)
    engine.backward()
    scale = jnp.asarray(1.0, jnp.float32)
    grads = engine._grad_jit.lower(engine.params, engine._model_rng, scale,
                                   micro).compile()
    grad_shapes = jax.tree.map(
        lambda g: jax.ShapeDtypeStruct(g.shape, g.dtype, sharding=g.sharding),
        engine._grad_buffer)
    engine.step()               # builds the update program, eats the buffer
    update = engine._apply_jit.lower(
        engine.params, engine.master_params, engine.opt_state,
        engine.scale_state, engine._step_arr, grad_shapes).compile()
    return {**scope_map(grads), **scope_map(update)}


BUILDERS = {
    "train_step-zero0-dp1": lambda: _resident(0, 1),
    "train_step-zero0-dp4-bucketed": lambda: _resident(0, 4),
    "train_step-zero3-dp4": lambda: _resident(3, 4),
    "offload_step": lambda: _grad_only({"device": "cpu"}),
    "tiered_offload_step": lambda: _grad_only({"device": "cpu",
                                               "pin_memory": True}),
    "streamed": _streamed,
}


@pytest.mark.parametrize("builder", sorted(BUILDERS))
def test_every_phase_of_the_compiled_step_is_named(builder):
    mapped = BUILDERS[builder]()
    phases = collections.Counter(scope_phase(op) for op in mapped.values())
    for phase in ("forward", "recompute", "backward", "loss_head",
                  "optimizer"):
        # (on the offload builders the optimizer phase is grad_clip: the
        # update itself runs on the host)
        assert phases[phase] > 0, (phase, phases)
    assert set(phases) <= set(PHASES)
    # the loss head keeps its gradients and makes nothing again
    assert not [op for op in mapped.values()
                if "loss_head" in op and "rematted_computation" in op]
    # what no scope and no autodiff mark names: the micro-batch slice, the
    # zeros a scan starts from, and the split of the rng key. The split is
    # a dozen scalar instructions of jax's own (``jit(_threefry_split)``)
    # and is left out of the count: on a two-layer toy it alone is 5 %
    unnamed = [op for op in mapped.values() if scope_phase(op) == "other"
               and "jit(_threefry_split)" not in op]
    assert len(unnamed) < 0.05 * len(mapped), unnamed
    if builder.endswith("dp4-bucketed"):
        assert phases["grad_reduce"] > 0
    if builder == "train_step-zero3-dp4":
        issued_by = {scope_phase(op) for name, op in mapped.items()
                     if COLLECTIVE.search(name)}
        # parameter gathers on the way down, and again for the backward
        assert issued_by & {"forward", "recompute", "param_gather"}
        assert issued_by & {"backward", "grad_reduce"}


def test_the_map_holds_what_a_trace_can_show():
    mapped = _resident(0, 1)
    assert mapped and memory.scopes("no_such_program") is None
    assert memory.scopes("train_step") is mapped      # built once, kept
    for name in mapped:
        assert not name.startswith(("constant", "parameter", "param_"))
    # a fusion is in the map, the instructions fused into it are not
    assert any(n.startswith("fusion") or "fusion" in n for n in mapped)
    memory.reset()
    assert memory.scopes("train_step") is None


@pytest.mark.parametrize("op_name,phase", [
    ("jit(train_step)/while/body/closed_call/jvp(layers)/while/body/"
     "closed_call/attention/dot_general", "forward"),
    ("jit(train_step)/jvp(embed)/gather", "forward"),
    ("jit(train_step)/transpose(jvp(layers))/while/body/closed_call/"
     "checkpoint/rematted_computation/attention/flash_attention_fwd/"
     "pallas_call", "recompute"),
    ("jit(train_step)/transpose(jvp(layers))/while/body/closed_call/"
     "checkpoint/mlp/dot_general", "backward"),
    ("jit(train_step)/transpose(jvp(embed))/scatter-add", "backward"),
    # a phase scope wins over the autodiff wrapper round it ...
    ("jit(train_step)/jvp(loss_head)/while/body/checkpoint/dot_general",
     "loss_head"),
    ("jit(train_step)/transpose(jvp(loss_head))/while/body/checkpoint/"
     "rematted_computation/reduce_max", "loss_head"),
    # the cross-entropy's own paths: its forward walk holds the two
    # matmuls of its gradients, its backward only their scaling
    ("jit(train_step)/while/body/closed_call/jvp(loss_head)/while/body/"
     "closed_call/bch,bcv->hv/dot_general", "loss_head"),
    ("jit(train_step)/while/body/closed_call/jvp(loss_head)/while/body/"
     "closed_call/bcv,hv->bch/dot_general", "loss_head"),
    ("jit(train_step)/while/body/closed_call/jvp(loss_head)/while/body/"
     "closed_call/jit(_take)/gather", "loss_head"),
    ("jit(train_step)/while/body/closed_call/transpose(jvp(loss_head))/mul",
     "loss_head"),
    ("jit(train_step)/transpose(jvp(moe))/grad_reduce/psum_scatter",
     "grad_reduce"),
    # ... and of two, the inner one
    ("jit(train_step)/shard_map/transpose(jvp(param_gather))/grad_reduce/"
     "psum_scatter", "grad_reduce"),
    ("jit(train_step)/shard_map/jvp(param_gather)/all_gather",
     "param_gather"),
    ("jit(train_step)/grad_clip/mul", "optimizer"),
    ("jit(train_step)/optimizer/convert_element_type", "optimizer"),
    # a forward-only program has no jvp round its model scopes
    ("jit(eval_step)/while/body/layers/while/body/mlp/dot_general",
     "forward"),
    # kernel names are scopes too, and are not mistaken for the model's
    ("jit(f)/flash_attention_fwd/pallas_call", "other"),
    ("jit(train_step)/jit(_threefry_split)/slice", "other"),
    ("", "other"),
])
def test_scope_phase(op_name, phase):
    assert scope_phase(op_name) == phase


HLO = """\
HloModule jit_step, entry_computation_layout={()->f32[]}

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  ROOT %add.9 = f32[] add(%a, %b), metadata={op_name="reduce_sum"}
}

%fused_computation.2 (p: f32[8]) -> f32[8] {
  %p = f32[8] parameter(0), metadata={op_name="x"}
  ROOT %multiply.3 = f32[8] multiply(%p, %p), metadata={op_name="jit(step)/jvp(layers)/mlp/mul"}
}

%body.4 (t: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t = (s32[], f32[8]) parameter(0)
  %get-tuple-element.5 = f32[8] get-tuple-element(%t), index=1, metadata={op_name="jit(step)/jvp(layers)/while"}
  %fusion.6 = f32[8] fusion(%get-tuple-element.5), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(step)/jvp(layers)/mlp/mul" source_file="m.py"}
  %flash_attention_fwd.7 = (bf16[8]{0:T(8,128)(2,1)}, f32[8]) custom-call(%fusion.6), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(layers)/attention/flash_attention_fwd/pallas_call"}
  %copy.8 = f32[8] copy(%fusion.6)
  %all-gather-start.14 = (f32[2], f32[8]) all-gather-start(%w), dimensions={0}
  %copy-start.16 = (f32[8], f32[8], u32[]) copy-start(%all-gather-done.15)
  %all-gather-done.15 = f32[8] all-gather-done(%all-gather-start.14), metadata={op_name="jit(step)/jvp(layers)/attention/dot_general"}
  ROOT %tuple.9 = (s32[], f32[8]) tuple(%c, %copy.8), metadata={op_name="jit(step)/jvp(layers)/while"}
}

ENTRY %main.10 () -> f32[] {
  %constant.11 = f32[] constant(0), metadata={op_name="jit(step)/optimizer"}
  %while.12 = (s32[], f32[8]) while(%init), condition=%cond, body=%body.4, metadata={op_name="jit(step)/jvp(layers)/while"}
  ROOT %reduce.13 = f32[] reduce(%x, %constant.11), dimensions={0}, to_apply=%region_0.1, metadata={op_name="jit(step)/optimizer/reduce_sum"}
}
"""


def test_scope_map_on_a_hand_written_module():
    class Compiled:
        def as_text(self):
            return HLO

    gather = "jit(step)/jvp(layers)/attention/dot_general"
    assert scope_map(Compiled()) == {
        "fusion.6": "jit(step)/jvp(layers)/mlp/mul",
        "flash_attention_fwd.7":
            "jit(step)/jvp(layers)/attention/flash_attention_fwd/pallas_call",
        "all-gather-done.15": gather,
        "while.12": "jit(step)/jvp(layers)/while",
        "reduce.13": "jit(step)/optimizer/reduce_sum",
        # what the compiler added carries no op_name of its own: the
        # start half is named after the done half it feeds, the copies
        # after what feeds them (nothing named reads them)
        "all-gather-start.14": gather,
        "copy-start.16": gather,
        "copy.8": "jit(step)/jvp(layers)/mlp/mul"}


def test_the_scope_text_is_not_printed_until_asked(monkeypatch):
    """``lower_train_step`` is on the benchmark's set-up path: recording
    keeps the executable and prints nothing."""
    engine, batch = _engine(0, 1)
    compiled = engine.lower_train_step(batch)
    printed = []
    monkeypatch.setattr(type(compiled), "as_text",
                        lambda self: printed.append(1) or "")
    memory.record_memory_analysis("train_step", compiled)
    assert not printed
    assert memory.scopes("train_step") == {} and printed == [1]
    calls = []
    memory.record_memory_analysis(
        "train_step", compiled,
        dispatched=lambda: calls.append(1) or compiled)
    assert not calls            # the executable that runs: on request only
    memory.scopes("train_step")
    memory.scopes("train_step")
    assert calls == [1]


def test_train_batch_runs_inside_five_spans():
    engine, batch = _engine(0, 1)
    engine.train_batch(batch=batch)
    trace.clear()
    engine.train_batch(batch=batch)
    ring = trace.export()
    assert sorted(s["name"] for s in ring) == [
        "train_bookkeeping", "train_data", "train_device_dispatch",
        "train_host_sync", "train_step"]
    by_name = {s["name"]: s for s in ring}
    step, book = by_name["train_step"], by_name["train_bookkeeping"]
    assert book["start"] >= step["start"] + step["duration_s"]
    # the same step; train_step, a launch span, also carries the host
    # thread's usage (telemetry/collector.py)
    assert book["depth"] == 0 and book["attrs"] == {
        "step": step["attrs"]["step"]}
    assert {"cpu_s", "runq_s", "nvcsw", "nivcsw", "majflt"} \
        == set(step["attrs"]) - {"step"}
    # with train_data, the three cover train_batch end to end
    assert by_name["train_data"]["start"] <= step["start"]
    assert engine._last_metrics["loss"] > 0


def test_no_jit_of_the_trainer_is_called_lambda(caplog):
    """The device's "XLA Modules" line names a program after its
    function: ``jit__lambda`` says nothing."""
    with jax.log_compiles(True), caplog.at_level(
            logging.WARNING, logger="jax._src.interpreters.pxla"):
        engine, batch = _engine(0, 1)
        for _ in range(2):
            engine.train_batch(batch=batch)
        micro = {"input_ids": batch["input_ids"][0]}
        for _ in range(2):      # the second backward adds into the buffer
            engine.forward(micro)
            engine.backward()
    names = [r.args[0] for r in caplog.records
             if str(r.msg).startswith("Compiling %s with global shapes")]
    # closures are traced anew for every engine; a module-level function
    # an earlier test compiled is not announced again
    assert "jit(train_step)" in names and "jit(cast_params)" in names
    # the small program that follows jit_train_step on every step
    assert engine._leaf_stack_fn.__name__ == "stack_grad_leaf_sqnorms"
    assert not [n for n in names if "lambda" in n], names
