"""Benchmark: flagship-model training throughput on the available TPU chip(s).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Metric: model FLOPs utilization (MFU) of a full ZeRO training step (fwd+bwd+
optimizer) on the Llama-architecture flagship at the largest per-chip batch
that fits. vs_baseline compares against the north-star target of 45% MFU
(BASELINE.md: ZeRO-3 Llama-2-7B on v5e-64 at >=45% MFU; single-chip MFU is
the per-chip factor of that target).

No TPU is a failure, not a fallback: the run raises before measuring
anything, and any phase that fails ends the run with a non-zero exit.
"""

import json
import time

import numpy as np

# the peak-FLOPS table lives with the accelerator (serving_bench shares
# it for its MFU field)
from deepspeed_tpu.accelerator.tpu_accelerator import peak_flops


def _measure(cfg, micro, gas, steps, warmup, n_dev, zero_stage=None,
             remat_policy=None, profile_dir=None, phases=False):
    """One timed training run; returns (mfu, detail)."""
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.models import TransformerLM

    model = TransformerLM(cfg)
    config = {
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": (zero_stage if zero_stage is not None
                                        else (2 if n_dev > 1 else 0)),
                              "stage3_param_persistence_threshold": 0},
        "steps_per_print": 10**9,
    }
    if remat_policy:
        config["activation_checkpointing"] = {"policy": remat_policy}
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)
    gm = engine.micro_batch_size * engine.ds_config.dp_world_size
    seq = cfg.max_seq_len
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, cfg.vocab_size, (gas, gm, seq),
                                       dtype=np.int64)}

    for _ in range(warmup):
        engine.train_batch(batch=batch)
    jax.block_until_ready(engine.params)
    if profile_dir:  # committed trace artifact (VERDICT r2 task 1/7)
        with jax.profiler.trace(profile_dir):
            for _ in range(2):
                engine.train_batch(batch=batch)
            jax.block_until_ready(engine.params)
    t0 = time.perf_counter()
    for _ in range(steps):
        engine.train_batch(batch=batch)
    jax.block_until_ready(engine.params)
    dt = (time.perf_counter() - t0) / steps

    if phases:
        # phase breakdown (VERDICT r4 #1c): forward wall-clock via the
        # eval step on the same shapes; exposed-collective fraction from
        # the optimized HLO of the train step
        for _ in range(2):
            engine.eval_batch(batch=batch)
        t1 = time.perf_counter()
        for _ in range(max(steps, 3)):
            engine.eval_batch(batch=batch)
        fwd = (time.perf_counter() - t1) / max(steps, 3)
        from deepspeed_tpu.utils.xla_profile import (
            grad_exchange_report_from_compiled,
            overlap_report_from_compiled)
        compiled = engine.lower_train_step(batch)
        rep = overlap_report_from_compiled(compiled)
        gx = grad_exchange_report_from_compiled(compiled)
        # compiler-measured MFU (satellite of the flops profiler):
        # XLA's own flop count for the compiled step over the
        # measured wall time and the chip's peak — cross-checks the
        # analytic model.flops_per_token MFU headline. cost_analysis
        # reports the PER-DEVICE partitioned module's flops, so no
        # further /n_dev — peak is also per chip
        from deepspeed_tpu.telemetry.memory import cost_analysis_dict
        ca = cost_analysis_dict(compiled)
        step_flops = float(ca.get("flops", 0.0))
        step_bytes = float(ca.get("bytes accessed", 0.0))
        extra_phases = {
            "cost_analysis_flops": step_flops,
            "cost_analysis_bytes": step_bytes,
            "mfu_cost_analysis": (
                round(step_flops / dt
                      / peak_flops(jax.devices()[0]), 4)
                if step_flops else None),
            "fwd_s": round(fwd, 4),
            "fwd_frac": round(fwd / dt, 3),
            "bwd_opt_s": round(dt - fwd, 4),
            "async_pairs": rep.async_pairs,
            "sync_collectives": rep.sync_collectives,
            "exposed_collective_fraction": round(rep.exposed_fraction, 4),
            # gradient-exchange regression metric (grad_overlap.py):
            # share of grad collectives with no overlap window
            "grad_exposed_collective_fraction":
                round(gx.exposed_fraction, 4),
            "grad_overlap_mode": engine.grad_overlap_mode,
        }
        if engine.grad_bucket_plan is not None:
            extra_phases["reduce_buckets"] = \
                engine.grad_bucket_plan.num_buckets
            extra_phases["reduce_bucket_max_bytes"] = \
                engine.grad_bucket_plan.max_bucket_bytes
    tokens_per_step = gm * gas * seq
    tokens_per_sec = tokens_per_step / dt
    achieved = tokens_per_sec * model.flops_per_token(seq) / n_dev
    mfu = achieved / peak_flops(jax.devices()[0])
    detail = {
        "tokens_per_sec_per_chip": round(tokens_per_sec / n_dev, 1),
        "step_time_s": round(dt, 4),
        "params_no_embed": model.num_params(include_embed=False),
        "devices": n_dev,
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "seq_len": seq,
        "micro_batch": micro,
        "attention": "flash" if cfg.use_flash
                     and seq >= cfg.flash_min_seq else "xla",
        "attn_blocks": [cfg.attn_block_q, cfg.attn_block_kv],
        "loss_chunk": cfg.loss_chunk,
        "remat_policy": engine.remat_policy[0],
        "zero_stage": config["zero_optimization"]["stage"],
        "global_batch_tokens": tokens_per_step,
    }
    if phases:
        detail["phase_breakdown"] = extra_phases
    # free this trial's device state NOW: the ladder runs many configs in
    # one process and leaked buffers/compiled-executable constants starved
    # the later zero3/large-proxy phases into RESOURCE_EXHAUSTED on the
    # 16 GB chip (r05 first capture)
    engine.destroy()
    del engine, model, batch
    import gc
    gc.collect()
    jax.clear_caches()
    return mfu, detail


def large_proxy_cfg(base):
    """The second bench scale point (~780M total / ~680M non-embed,
    H=1536): closer to the 7B target's arithmetic intensity. kv-heads
    MUST divide heads — the r05 chip window lost this measurement to an
    inherited num_kv_heads=8 against num_heads=12 asserting mid-capture
    (`GQA requires h(12) % hk(8) == 0`); TransformerConfig now rejects
    the pairing at construction and tests/unit/models cover this exact
    config off-chip."""
    import dataclasses

    return dataclasses.replace(
        base, hidden_size=1536, intermediate_size=4096,
        num_heads=12, num_kv_heads=4, use_flash=True,
        flash_min_seq=2048)


def build_trials(base):
    """The on-chip mini-autotune ladder: (cfg, micro_batch, remat_policy)
    tuples, most-promising first (the wall-clock budget truncates the
    tail). Separated from main() so the construction is testable off-chip."""
    import dataclasses

    trials = []
    for policy in ("save_dots_and_attn",
                   "dots_with_no_batch_dims_saveable",
                   "nothing_saveable"):
        for use_flash in (True, False):
            for micro in (16, 8):
                trials.append((dataclasses.replace(
                    base, use_flash=use_flash, flash_min_seq=2048),
                    micro, policy))
        # flash block-size variant (default auto is 256x512): bigger q
        # blocks amortize the online-softmax bookkeeping further
        trials.insert(2 if policy == "save_dots_and_attn" else len(trials),
                      (dataclasses.replace(
                          base, use_flash=True, flash_min_seq=2048,
                          attn_block_q=512, attn_block_kv=512),
                       16, policy))
    # larger micro-batches: the r05 winner was mb=16 full-recompute; 24/32
    # amortize per-step overheads further if they fit the 16 GB chip
    # (OOM configs are skipped by the ladder)
    trials.insert(2, (dataclasses.replace(
        base, use_flash=True, flash_min_seq=2048, attn_block_q=512,
        attn_block_kv=512), 24, "nothing_saveable"))
    trials.insert(3, (dataclasses.replace(
        base, use_flash=True, flash_min_seq=2048, attn_block_q=512,
        attn_block_kv=512), 32, "nothing_saveable"))
    # unchunked CE: skips the backward recompute of the [*, V] logits
    # (~2HV per token, ~5% of step flops at vocab 32k) if the logits fit
    # now that selective remat freed activation memory
    trials.insert(4, (dataclasses.replace(
        base, use_flash=True, flash_min_seq=2048, loss_chunk=0),
        8, "save_dots_and_attn"))
    # long-sequence variant: seq 4096 raises the attention-flops fraction
    # where the flash kernel beats XLA hardest; MFU stays comparable (the
    # metric normalizes by model flops at the measured seq)
    trials.insert(4, (dataclasses.replace(
        base, max_seq_len=4096, use_flash=True, flash_min_seq=2048),
        4, "save_dots_and_attn"))
    # tall-q flash blocks: fewer online-softmax rescales per row
    trials.insert(5, (dataclasses.replace(
        base, use_flash=True, flash_min_seq=2048,
        attn_block_q=1024, attn_block_kv=512),
        16, "save_dots_and_attn"))
    return trials


def main(argv=None):
    import argparse
    import os

    ap = argparse.ArgumentParser(prog="bench")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the run's telemetry spans (training step "
                         "phases incl. train_data/device_dispatch/"
                         "host_sync) as Chrome-trace-event JSON to PATH "
                         "(open in Perfetto; see docs/PROFILING.md)")
    ap.add_argument("--postmortem-dir", default=None, metavar="DIR",
                    help="install the crash-handler hooks so an aborted "
                         "bench run leaves a post-mortem bundle "
                         "(metrics/timeline/recorder/anomalies) under "
                         "DIR; see docs/TELEMETRY.md")
    args, _ = ap.parse_known_args(argv)

    if args.postmortem_dir:
        from deepspeed_tpu.telemetry import DiagnosticsConfig, postmortem
        postmortem.install_crash_handler(
            DiagnosticsConfig(postmortem_dir=args.postmortem_dir))

    # collective-overlap XLA knobs (latency-hiding scheduler + async
    # collective fusion incl. reduce-scatter chaining for the bucketed
    # grad reduction) ride LIBTPU_INIT_ARGS — only the TPU runtime reads
    # them. Must be set before the TPU client initializes.
    from deepspeed_tpu.accelerator.tpu_accelerator import (
        apply_collective_overlap_flags, require_tpu)
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    apply_collective_overlap_flags()
    enable_compile_cache()
    n_dev = len(require_tpu())      # raises, naming the platform it found

    from __graft_entry__ import _flagship_cfg

    base = _flagship_cfg()  # the shipped flagship, not a local copy
    # mini-autotune: attention impl x micro-batch x remat-policy ladder;
    # configs that do not fit are skipped, the best-MFU measurement is
    # reported. save_dots_and_attn keeps matmul outputs AND the tagged
    # attention output (the Pallas call is opaque to dot policies, so
    # without the tag the flash forward re-runs in backward);
    # dots_with_no_batch_dims_saveable keeps matmul outputs only;
    # nothing_saveable is full per-layer recompute.
    trials = build_trials(base)
    steps, warmup = 10, 2

    best = None
    oom = []
    # wall-clock budget for the trial ladder: cold compiles cost ~40s per
    # config; stop opening new trials when the budget is spent so the
    # run always gets a number + the zero-3 variant
    budget_s = float(os.environ.get("DS_TPU_BENCH_BUDGET", "900"))
    t_start = time.perf_counter()
    skipped_trials = 0
    for i, (cfg, micro, policy) in enumerate(trials):
        if best is not None and time.perf_counter() - t_start > budget_s:
            skipped_trials = len(trials) - i
            break
        try:
            mfu, detail = _measure(cfg, micro, 1, steps, warmup, n_dev,
                                   remat_policy=policy)
        except Exception as exc:
            # a ladder rung that does not fit the chip is the ladder's
            # business; anything else is a broken program and ends the run
            if "RESOURCE_EXHAUSTED" not in str(exc):
                raise
            oom.append(f"micro={micro} flash={cfg.use_flash} "
                       f"remat={policy}")
            continue
        if best is None or mfu > best[0]:
            best = (mfu, detail, cfg, micro, policy)

    if best is None:
        raise RuntimeError("no bench config fits the chip: " + " | ".join(oom))
    mfu, detail, cfg, micro, policy = best
    if oom:
        detail["out_of_memory_trials"] = oom
    if skipped_trials:  # a truncated search must say so in the record
        detail["skipped_trials"] = skipped_trials

    # ZeRO-3 variant on the same (best) config: the sharding machinery runs
    # on the degenerate dp=1 mesh so regressions in the stage-3 path show up
    # in every bench, plus the profiler trace (profiles/ is gitignored).
    prof_dir = os.environ.get("DS_TPU_BENCH_PROFILE", "profiles/bench_trace")
    # phase breakdown costs a second AOT compile + eval-step compiles
    # (~80s cold on chip); only spend it if the trial ladder left room
    phases_ok = (time.perf_counter() - t_start) < budget_s * 0.8
    z3_mfu, z3_detail = _measure(cfg, micro, 1, max(steps // 2, 3),
                                 warmup, n_dev, zero_stage=3,
                                 remat_policy=policy,
                                 profile_dir=prof_dir or None,
                                 phases=phases_ok)
    detail["zero3_mfu"] = round(z3_mfu * 100, 2)
    detail["zero3_tokens_per_sec_per_chip"] = \
        z3_detail["tokens_per_sec_per_chip"]
    if "phase_breakdown" in z3_detail:
        detail["zero3_phase_breakdown"] = z3_detail["phase_breakdown"]
    else:  # a truncated record must say so
        detail["zero3_phase_breakdown"] = {"skipped": "budget"}
    if prof_dir:
        detail["profile_trace"] = prof_dir

    if time.perf_counter() - t_start < budget_s:
        # larger proxy (~780M total / ~680M non-embed): closer to the 7B
        # target's arithmetic intensity (H=1536); recorded as evidence, the
        # headline stays on the standard flagship so rounds stay comparable
        big = large_proxy_cfg(base)
        b_mfu, b_detail = _measure(big, 8, 1, max(steps // 2, 3),
                                   warmup, n_dev, remat_policy=policy)
        detail["large_proxy_mfu"] = round(b_mfu * 100, 2)
        detail["large_proxy_params_no_embed"] = \
            b_detail["params_no_embed"]

    # on-chip flash parity evidence in every bench record
    from deepspeed_tpu.ops.attention_autotune import (decode_parity_check,
                                                      parity_check)
    detail["flash_parity"] = parity_check(
        heads=cfg.num_heads, kv_heads=cfg.kv_heads,
        head_dim=cfg.head_dim, seq=512)
    detail["decode_parity"] = decode_parity_check(
        heads=cfg.num_heads, kv_heads=cfg.kv_heads,
        head_dim=cfg.head_dim)

    # pin the exact compiler configuration to the perf record so a
    # number is attributable to a jax/jaxlib/libtpu + flag set
    from deepspeed_tpu.env_report import compiler_fingerprint
    detail["compiler_config"] = compiler_fingerprint()
    # black-box summary: the flight recorder ran through the whole
    # bench (train_step events per batch), and any anomaly verdict
    # (NaN/spike/stall) belongs in the record next to the number
    from deepspeed_tpu.telemetry import anomaly, get_recorder
    detail["flight_recorder"] = get_recorder().stats()
    verdicts = anomaly.recent()
    if verdicts:
        detail["anomalies"] = [
            {"kind": v["kind"], "summary": v["summary"]}
            for v in verdicts]
    if args.trace_out:
        from deepspeed_tpu.telemetry import timeline
        detail["trace_out"] = timeline.write_chrome_trace(args.trace_out)
    result = {
        "metric": "train_mfu_llama_flagship",
        "value": round(mfu * 100, 2),
        "unit": "% MFU",
        "vs_baseline": round(mfu / 0.45, 3),
        "detail": detail,
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
