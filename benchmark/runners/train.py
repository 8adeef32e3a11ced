"""Training: ``deepspeed_tpu.initialize()`` -> ``train_batch`` on seeded
token batches that differ per step and were made before the window.

``train_tok_s`` is all the work over all the time: the tokens of the
optimizer steps that FINISHED inside the window over the seconds from
the window's opening to the last of them, on the host clock, per chip. A
step is closed by its loss reaching the host (``train_batch`` returns a
float, which blocks on the device step). A stall of the host, a late
recompile, a checkpoint: whatever costs the user a second costs this
rate that second.

Beside it, judged by nothing, the run's detail says what a window spent
beyond ``steps`` median steps (``stall_s``), in which step
(``slowest_step``: its index and its seconds over the median; on the
one-chip machine a quiet run in four stalls one step by 0.06 to 0.15 s,
and it is not the collector, which takes 0.5 ms a window; my chip runs,
PR 32), and the rate of the median pass over the ``distinct_batches``
seeded batches (``steady_tok_s``, ``arith.steady_rate``; the per-layer
metric ``steady_tok_s.train`` in a traced run): a window mean below it
by more than the cell's spread was stalled, and ``stall_s`` says by how
much.
"""

import statistics
import time

import numpy as np

from .. import arith
from ..evidence import Evidence, Result, TraceSlice, program_bytes

# first-step loss, program (bf16 compute, chunked cross-entropy, flash
# attention) against the float32 reference on the same sequences. The
# loss is ~ln(vocab) = 10.8. The chip read |d| of 5e-6 to 5.7e-5 at
# OPT-125M (7 seeds) and 1.1e-5 to 2.5e-4 at OPT-1.3B (6 seeds; my chip
# runs, PR 24); the tolerance is four times the worst of those.
LOSS_TOL = 1e-3
CHECK_SEQUENCES = 4
SLICE_AFTER_STEPS = 2       # the traced slice starts after two steps


def make_batches(ctx, engine, vocab):
    """``distinct_batches`` seeded batches [gas, micro x dp, seq] of
    uniform token ids, plus the check batch: CHECK_SEQUENCES distinct
    sequences tiled to the same shape, so that the program's mean loss on
    it IS its mean loss on those sequences."""
    tr = ctx.traffic
    gm = engine.micro_batch_size * engine.ds_config.dp_world_size
    shape = (engine.gas, gm, tr["seq_len"])
    rng = np.random.default_rng(ctx.seed)
    batches = [{"input_ids": rng.integers(0, vocab, shape, dtype=np.int64)}
               for _ in range(tr["distinct_batches"])]
    n = min(CHECK_SEQUENCES, gm)
    distinct = rng.integers(0, vocab, (n, tr["seq_len"]), dtype=np.int64)
    reps = -(-gm // n)
    tiled = np.tile(distinct, (reps, 1))[:gm]
    if gm % n:
        raise ValueError(f"{gm} sequences a step do not tile {n} evenly")
    check = {"input_ids": np.broadcast_to(tiled, shape).copy()}
    return batches, check, distinct


def run(ctx):
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.models import TransformerLM

    cell, tr = ctx.cell, ctx.traffic
    reference = ctx.reference       # found before set-up is spent
    ctx.part("import_program")
    cfg = ctx.model_config()
    topo = None
    if len(ctx.devices) != jax.device_count():
        from deepspeed_tpu.parallel.topology import build_topology
        from deepspeed_tpu.runtime.config import DeepSpeedConfig
        topo = build_topology(
            DeepSpeedConfig(cell["deepspeed"], world_size=len(ctx.devices)),
            devices=ctx.devices)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=TransformerLM(cfg), config=cell["deepspeed"], topology=topo,
        seed=int(ctx.seed) % (2 ** 31))
    ctx.log(f"engine up: zero stage "
            f"{cell['deepspeed']['zero_optimization']['stage']}, micro "
            f"{engine.micro_batch_size} x dp {engine.ds_config.dp_world_size}")
    ctx.part("engine_and_weights")
    batches, check, distinct = make_batches(ctx, engine, cfg.vocab_size)
    tokens_per_step = int(np.prod(batches[0]["input_ids"].shape))
    ctx.part("batches")

    # reference first: the float32 loss of the initial weights on the
    # check sequences, before any step moves them
    master = engine.master_params if engine.master_params is not None \
        else engine.params
    ref_loss = float(np.mean([reference.next_token_loss(
        master, ctx.fields, seq) for seq in distinct]))
    ctx.part("reference_loss")
    # warm-up: two steps. The first is on the check batch and gives the
    # program's first-step loss; both compile or load what the window runs
    first_loss = float(engine.train_batch(batch=check))
    losses = [first_loss, float(engine.train_batch(batch=batches[-1]))]
    jax.block_until_ready(engine.params)
    ctx.part("warm_steps")
    loss_err = abs(first_loss - ref_loss)
    ctx.log(f"first-step loss {first_loss:.5f} vs reference "
            f"{ref_loss:.5f} (|d| {loss_err:.2e})")

    # what the step program needs on a chip, from the program's own
    # analysis entry point (evidence.program_bytes says why this source)
    step_bytes = program_bytes(engine.lower_train_step(check))
    ctx.part("memory_analysis")
    ctx.log(f"step program: {step_bytes / 1e9:.3f} GB a chip (compiler); "
            "set-up by part, s: "
            + ", ".join(f"{k} {v:.2f}" for k, v in ctx.setup_parts.items()))

    slicer = TraceSlice(ctx) if ctx.trace else None
    ctx.clock.mark()
    setup_s = ctx.setup_seconds()
    ctx.log("window open")
    t0 = time.perf_counter()
    close = t0 + ctx.seconds
    steps = slice_steps = 0
    t_last = t0
    step_s = []
    slicing = "waiting" if slicer is not None else "off"
    while True:
        if slicing == "waiting" and steps == SLICE_AFTER_STEPS:
            slicer.start()
            slicing = "on"
        loss = float(engine.train_batch(batch=batches[steps % len(batches)]))
        now = time.perf_counter()
        if now > close:
            break           # this step finished outside the window
        losses.append(loss)
        steps += 1
        step_s.append(now - t_last)
        t_last = now
        if slicing == "on":
            slice_steps += 1
            if slice_steps == tr["trace_steps"]:
                slicer.stop()
                slicing = "done"
    jax.block_until_ready(engine.params)
    if slicing == "on":
        slicer.stop()       # a window too short for the whole slice
    compiles = ctx.clock.since_mark()
    chips = len(ctx.devices)
    # all the work over all the time; beside it the rate of the median
    # pass and what the window spent beyond its steps' median, and where
    rate = steady = stall_s = median_step = step_rate = slowest = None
    if steps:
        rate = arith.rate(steps * tokens_per_step, t_last - t0) / chips
        steady = arith.steady_rate(step_s, len(batches), tokens_per_step)
        median_step = statistics.median(step_s)
        stall_s = (t_last - t0) - steps * median_step
        at = max(range(steps), key=step_s.__getitem__)
        slowest = [at, step_s[at] - median_step]
        # the rate of the window's median step, for mfu.train: it is
        # read in the traced run, where the profiler's start and stop
        # stall the steps round the slice by seconds
        step_rate = arith.rate(tokens_per_step, median_step) / chips
    finite = bool(np.all(np.isfinite(losses)))
    ev = Evidence(ctx=ctx, compiles_in_window=compiles,
                  slice_steps=slice_steps, tokens_per_step=tokens_per_step,
                  step_tok_s=step_rate, step_seconds=step_s,
                  memory_peak_bytes=step_bytes)
    if slicer is not None:
        ev.events = slicer.events()
    engine.destroy()
    return Result(
        attempted=steps, failed=0 if finite else 1,
        correct=bool(finite and loss_err <= LOSS_TOL and steps > 0),
        correct_detail={"first_step_loss": first_loss,
                        "reference_loss": ref_loss, "tolerance": LOSS_TOL,
                        "last_loss": losses[-1], "steps": steps,
                        "last_step_end_s": t_last - t0,
                        "median_step_s": median_step,
                        "steady_tok_s": steady and steady / chips,
                        "stall_s": stall_s, "slowest_step": slowest,
                        "setup_parts_s": dict(ctx.setup_parts),
                        "compiles_in_window": compiles},
        end_to_end={k: v for k, v in (("train_tok_s", rate),
                                      ("setup_s", setup_s))
                    if v is not None},
        evidence=ev)
