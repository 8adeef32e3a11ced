"""Serving throughput benchmark: ragged/paged v2 engine vs dense v1 engine.

The reference publishes FastGen-vs-baseline serving numbers
(blogs/deepspeed-fastgen/README.md: throughput/latency curves); this is the
in-tree microbenchmark: same model, same prompts, measure end-to-end
generation tokens/sec for

  * the v1 dense engine (padded static [B, S] KV cache, whole batch in one
    compiled generate loop), and
  * the v2 ragged engine (paged KV blocks + continuous batching put()).

Prints ONE JSON line. Usage:
  python -m deepspeed_tpu.benchmarks.serving_bench [--batch 8] [--prompt 64]
         [--new 64] [--layers 4] [--hidden 256]

``--router N`` switches to the routed fleet sweep: a shared-prefix
workload through N in-process replicas behind the prefix-affinity
router (``--disagg`` adds a dedicated prefill replica and the KV
handoff path), reporting routed tokens/s, affinity hits, handoffs and
steady-state recompiles. With ``--trace-out`` the run writes the
STITCHED fleet timeline — one Chrome-trace process row per lane
(router + each replica), every request's hops correlated by its
distributed trace id (docs/PROFILING.md § Distributed tracing).
"""

import argparse
import json
import sys
import time
from typing import Optional

import numpy as np


def build_model(layers: int, hidden: int, vocab: int = 2048,
                max_seq: int = 1024):
    from ..models.transformer import TransformerConfig, TransformerLM

    cfg = TransformerConfig(
        vocab_size=vocab, hidden_size=hidden, intermediate_size=2 * hidden,
        num_layers=layers, num_heads=max(hidden // 64, 1),
        max_seq_len=max_seq, use_flash=False)
    return TransformerLM(cfg)


def bench_dense(model, params, prompts: np.ndarray, new_tokens: int,
                repeats: int) -> dict:
    from ..inference.engine import InferenceEngine
    from ..inference.config import DeepSpeedInferenceConfig

    B, S = prompts.shape
    eng = InferenceEngine(model, DeepSpeedInferenceConfig.from_dict_or_kwargs(
        {"dtype": "bfloat16", "max_out_tokens": S + new_tokens + 8,
         "max_batch_size": B}, {}), params=params)
    # timed warm-up pass: compile cost is REPORTED, never mixed into the
    # steady-state tok/s
    w0 = time.perf_counter()
    eng.generate(prompts, max_new_tokens=new_tokens)
    warmup_s = time.perf_counter() - w0
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = eng.generate(prompts, max_new_tokens=new_tokens)
    dt = (time.perf_counter() - t0) / repeats
    assert out.shape == (B, S + new_tokens)
    return {"tok_s": B * new_tokens / dt, "warmup_s": warmup_s}


# pool geometry the paged benches run with — kv_capacity_report must
# describe the SAME pool bench_paged actually builds, or the --kv-quant
# capacity math silently drifts from the tok/s measured next to it
POOL_NUM_BLOCKS = 4096
POOL_BLOCK_SIZE = 64  # KVCacheConfig.block_size default


def _hist_delta(registry, name, before):
    """(count, sum) advance of a histogram family since ``before``."""
    fam = registry.get(name)
    if fam is None:
        return 0, 0.0
    c0, s0 = before.get(name, (0, 0.0))
    return fam.count - c0, fam.sum - s0


def kv_capacity_report(model_cfg, block_size: int, num_blocks: int,
                       max_seq_len: int, pool_dtype_bytes: int = 2) -> dict:
    """Capacity math of the int8 KV pool vs the same pool at the serving
    dtype: bytes per block both ways, and the max concurrent
    max_seq_len-length sequences a FIXED byte budget (the unquantized
    pool's size) admits under each layout — the 'how many more sequences
    before admission control sheds load' number."""
    L, kvh, hd = (model_cfg.num_layers, model_cfg.kv_heads,
                  model_cfg.head_dim)
    per_block = 2 * L * block_size * kvh * hd          # k + v elements
    block_bytes = per_block * pool_dtype_bytes
    block_bytes_q = per_block + 2 * L * kvh * 4        # int8 + scales
    pool_budget = num_blocks * block_bytes
    blocks_per_seq = -(-max_seq_len // block_size)
    return {
        "block_bytes": block_bytes,
        "block_bytes_quant": block_bytes_q,
        "pool_bytes_budget": pool_budget,
        "capacity_gain": round(block_bytes / block_bytes_q, 3),
        "max_seqs_fixed_bytes": (pool_budget // block_bytes)
        // blocks_per_seq,
        "max_seqs_fixed_bytes_quant": (pool_budget // block_bytes_q)
        // blocks_per_seq,
    }


def kv_spill_capacity_report(model_cfg, block_size: int, num_blocks: int,
                             blocks_per_conv: int, spill_block_bytes: int,
                             host_bytes: int, disk_bytes: int = 0,
                             pool_dtype_bytes: int = 2) -> dict:
    """Capacity math of the KV spill tier at a FIXED HBM pool budget:
    how many conversations keep their prefix KV *available* (resident
    in the pool, or restorable from the host/disk tier) each way. The
    pool-only number is what admission effectively caps a conversational
    fleet at today; the tiered number is bounded by host/disk budgets
    instead of HBM. ``spill_block_bytes`` is the MEASURED serialized
    size of one spilled block (int8 kv_quant pools halve it)."""
    L, kvh, hd = (model_cfg.num_layers, model_cfg.kv_heads,
                  model_cfg.head_dim)
    block_bytes = 2 * L * block_size * kvh * hd * pool_dtype_bytes
    pool_budget = num_blocks * block_bytes
    pool_convs = (num_blocks - 1) // blocks_per_conv
    # no measured spill bytes -> no claimed tier capacity (a silent
    # 1-byte substitute would report a millions-of-conversations "win"
    # exactly when spilling regressed to never happening)
    tier_blocks = ((host_bytes + disk_bytes) // spill_block_bytes
                   if spill_block_bytes > 0 else 0)
    spill_convs = pool_convs + tier_blocks // blocks_per_conv
    return {
        "block_bytes": block_bytes,
        "spill_block_bytes": spill_block_bytes,
        "pool_bytes_budget": pool_budget,
        "blocks_per_conv": blocks_per_conv,
        "max_convs_fixed_pool": pool_convs,
        "max_convs_with_spill": spill_convs,
        "capacity_gain": round(spill_convs / max(pool_convs, 1), 3),
    }


def bench_kv_spill(model, params, *, conversations: int, prompt: int,
                   new_tokens: int, num_blocks: Optional[int] = None,
                   block_size: int = 16,
                   host_bytes: int = 64 << 20) -> dict:
    """Conversation sweep through a pressure-sized pool, spill on vs
    off: every conversation runs turn 1, then (after the others evicted
    its prefix) turn 2. Reports the round-2 prefix reuse each way, the
    spill/restore flow counters, steady-state recompiles under the
    double-warm discipline, and the capacity report at the pool's byte
    budget."""
    from ..inference.v2 import (InferenceEngineV2,
                                RaggedInferenceEngineConfig)
    from ..inference.v2.config_v2 import DSStateManagerConfig
    from ..inference.v2.ragged.ragged_manager import prefix_digest
    from ..telemetry import get_registry, watchdog

    rng = np.random.default_rng(0)
    hi = max(model.cfg.vocab_size - 1, 2)
    prompts = [list(map(int, rng.integers(1, hi, prompt)))
               for _ in range(conversations)]
    full = (prompt // block_size) * block_size
    blocks_per_conv = max(full // block_size, 1)
    if num_blocks is None:
        # pressure-sized on purpose: one conversation's worth SHORT of
        # retaining every conversation, so the sweep actually evicts
        num_blocks = blocks_per_conv * conversations

    def sweep(spill: bool, uid_base: int, eng=None):
        if eng is None:
            eng = InferenceEngineV2(
                model, RaggedInferenceEngineConfig(
                    state_manager=DSStateManagerConfig(
                        max_tracked_sequences=8,
                        max_seq_len=min(1024, model.cfg.max_seq_len),
                        num_blocks=num_blocks, block_size=block_size,
                        enable_prefix_caching=True,
                        enable_kv_spill=spill,
                        kv_spill_host_bytes=host_bytes),
                    dtype="bfloat16", prefill_bucket=block_size),
                params=params)
        turn1 = {}
        for i, p in enumerate(prompts):
            turn1[i] = eng.generate([p], max_new_tokens=new_tokens,
                                    uids=[uid_base + i])[0]
        reused0 = eng.state_manager._m_reused_tokens.value
        for i in range(conversations):
            t2 = list(map(int, turn1[i])) + [3, 5, 7]
            eng.generate([t2], max_new_tokens=new_tokens,
                         uids=[uid_base + 100 + i])
        reused = eng.state_manager._m_reused_tokens.value - reused0
        avail = sum(
            all(d in eng.state_manager._prefix
                or (eng.spill is not None and eng.spill.has(d))
                for d in prefix_digest(p[:full], block_size))
            for p in prompts)
        return eng, reused, avail

    reg = get_registry()
    t0 = time.perf_counter()
    eng, _, _ = sweep(True, 10_000)              # compile every bucket
    _, _, _ = sweep(True, 20_000, eng=eng)       # absorb respecialization
    warmup_s = time.perf_counter() - t0
    base_steady = reg.family_total("xla_steady_state_recompiles_total")
    watchdog.mark_steady(True)
    try:
        _, reused_spill, avail_spill = sweep(True, 30_000, eng=eng)
    finally:
        watchdog.mark_steady(False)
    steady = reg.family_total("xla_steady_state_recompiles_total") \
        - base_steady
    _, reused_off, avail_off = sweep(False, 40_000)

    restore_fam = reg.get("kv_restore_seconds")
    spilled_blocks = reg.counter("kv_spill_blocks_total").value
    spill_bytes = reg.counter("kv_spill_bytes_total").value
    spill_block_bytes = int(spill_bytes / spilled_blocks) \
        if spilled_blocks else 0
    max_reuse = conversations * (((prompt + new_tokens - 1)
                                  // block_size) * block_size)
    return {
        "conversations": conversations,
        "warmup_s": round(warmup_s, 3),
        "kv_spill_steady_state_recompiles": int(steady),
        "spilled_blocks": int(spilled_blocks),
        "restored_blocks": int(
            reg.counter("kv_restore_blocks_total").value),
        "dropped_blocks": int(
            reg.counter("kv_spill_dropped_blocks_total").value),
        "restore_s_mean": (round(restore_fam.sum / restore_fam.count, 6)
                           if restore_fam and restore_fam.count else None),
        # round-2 reuse: with spill every conversation's turn-1 KV is
        # still available; without, evicted prefixes recompute
        "turn2_reused_tokens_spill": int(reused_spill),
        "turn2_reused_tokens_off": int(reused_off),
        "turn2_reuse_fraction_spill": round(reused_spill / max_reuse, 3),
        "turn2_reuse_fraction_off": round(reused_off / max_reuse, 3),
        "convs_available_spill": int(avail_spill),
        "convs_available_off": int(avail_off),
        "kv_spill_capacity_gain": round(
            avail_spill / max(avail_off, 1), 3),
        **{f"capacity_{k}": v for k, v in kv_spill_capacity_report(
            model.cfg, block_size=block_size, num_blocks=num_blocks,
            blocks_per_conv=blocks_per_conv,
            spill_block_bytes=spill_block_bytes,
            host_bytes=host_bytes).items()},
    }


def bench_paged(model, params, prompts: np.ndarray, new_tokens: int,
                repeats: int, decode_window: int = 8,
                uid_base: int = 1000, kv_quant: bool = False) -> dict:
    """Measure the v2 engine THROUGH the telemetry registry: the engine's
    own decode-step/TTFT series are the timers (the registry numbers ARE
    what a production scrape sees), not ad-hoc stopwatches around the
    call. The warmup pass is timed separately (compile cost never mixes
    into steady-state tok/s) and its series are snapshotted and
    subtracted. ``decode_window=1`` measures the per-token fallback —
    the fused-vs-per-token comparison is the dispatch-overhead story."""
    from ..accelerator.tpu_accelerator import peak_flops
    from ..inference.v2.engine_v2 import InferenceEngineV2
    from ..telemetry import get_registry, watchdog

    import jax

    B, S = prompts.shape
    eng = InferenceEngineV2(model, {
        "dtype": "bfloat16",
        "decode_window": decode_window,
        "kv_quant": kv_quant,
        "state_manager": {"max_tracked_sequences": max(B, 8),
                          "max_ragged_batch_size": max(B * S, 512),
                          "num_blocks": POOL_NUM_BLOCKS,
                          "block_size": POOL_BLOCK_SIZE},
    }, params=params)
    prompt_list = [list(map(int, p)) for p in prompts]
    w0 = time.perf_counter()
    # two warm passes: the first compiles every bucket, the second
    # absorbs the one-time respecialization of buckets whose first call
    # ran against the fresh (unsharded) KV pool
    eng.generate(prompt_list, max_new_tokens=new_tokens)
    eng.generate(prompt_list, max_new_tokens=new_tokens,
                 uids=list(range(uid_base + 500, uid_base + 500 + B)))
    warmup_s = time.perf_counter() - w0

    reg = get_registry()
    base_hist = {n: (reg.get(n).count, reg.get(n).sum) if reg.get(n) else
                 (0, 0.0)
                 for n in ("inference_decode_step_seconds",
                           "inference_ttft_seconds")}
    base_tokens = reg.counter("inference_decode_tokens_total").value
    base_syncs = reg.counter("inference_decode_host_syncs_total").value
    # warmup compiled every bucket this workload uses; the measured phase
    # must not compile AGAIN — the recompile watchdog enforces it and the
    # violation count lands in the bench record
    base_steady = reg.family_total("xla_steady_state_recompiles_total")
    watchdog.mark_steady(True)
    try:
        t0 = time.perf_counter()
        for r in range(repeats):
            outs = eng.generate(
                prompt_list, max_new_tokens=new_tokens,
                uids=list(range(uid_base + (r + 1) * 1000,
                                uid_base + (r + 1) * 1000 + B)))
        dt = (time.perf_counter() - t0) / repeats
    finally:
        watchdog.mark_steady(False)
    steady_recompiles = reg.family_total(
        "xla_steady_state_recompiles_total") - base_steady
    assert len(outs) == B

    decode_n, decode_s = _hist_delta(reg, "inference_decode_step_seconds",
                                     base_hist)
    ttft_n, ttft_s = _hist_delta(reg, "inference_ttft_seconds", base_hist)
    decode_tokens = reg.counter("inference_decode_tokens_total").value \
        - base_tokens
    host_syncs = reg.counter("inference_decode_host_syncs_total").value \
        - base_syncs
    # MFU from the compiler's own numbers (telemetry/memory.py records
    # the decode program's cost analysis chip-free): flops per generated
    # token x measured decode tok/s over the chip's peak
    flops_per_token = decode_peak_bytes = None
    try:
        rep = eng.memory_report(batch=B)
        N = eng._decode_bucket(B)
        if decode_window > 1:
            prog = rep["programs"]["decode_window_greedy"]
            flops_per_token = prog.get("flops", 0.0) / (N * decode_window)
        else:
            prog = rep["programs"]["decode_greedy"]
            flops_per_token = prog.get("flops", 0.0) / N
        decode_peak_bytes = prog.get("peak_bytes")
    except Exception:  # analysis is a bonus; the bench still reports
        pass
    decode_tok_s = (decode_tokens / decode_s) if decode_s else None
    mfu = (decode_tok_s * flops_per_token / peak_flops(jax.devices()[0])
           if decode_tok_s and flops_per_token else None)
    return {
        "decode_mfu": mfu,
        "decode_flops_per_token": flops_per_token,
        "decode_peak_bytes": decode_peak_bytes,
        "steady_state_recompiles": steady_recompiles,
        "tok_s": B * new_tokens / dt,
        "warmup_s": warmup_s,
        "decode_window": decode_window,
        "decode_tok_s": decode_tok_s,
        "decode_steps": int(decode_n),
        # the fused window's dispatch win, visible in one artifact: one
        # device->host transfer per window vs one per token
        "decode_host_syncs": int(host_syncs),
        "decode_host_syncs_per_token":
            (host_syncs / decode_tokens) if decode_tokens else None,
        "ttft_s": (ttft_s / ttft_n) if ttft_n else None,
        # the live gauge is 0 after generate() flushes its uids; the peak
        # is the number that says whether num_blocks has headroom
        "kv_pool_utilization_peak":
            reg.gauge("inference_kv_pool_utilization_peak").value,
    }


def bench_routed(model, params, *, replicas_n: int, requests: int,
                 prompt: int, new_tokens: int, budget: int,
                 disaggregated: bool, trace_out=None,
                 remote: bool = False, chunk_blocks: int = 4) -> dict:
    """Routed fleet sweep: a shared-prefix workload through N replicas
    behind the affinity router, double-warmed (every bucket compiles on
    wave 1, respecializes once on wave 2) before a steady wave under
    ``watchdog.mark_steady``. Runs in an isolated registry/recorder.
    ``trace_out`` writes the stitched fleet timeline of the run.
    ``remote=True`` puts every replica behind a LOOPBACK socket (an
    in-process worker + RemoteReplica shim — the remote serving plane's
    wire without subprocess spawn cost); ``chunk_blocks`` sets the
    streaming-handoff chunk width for the disaggregated path (0 = the
    legacy blocking transport)."""
    import asyncio

    from ..inference.v2.engine_v2 import InferenceEngineV2
    from ..inference.v2.serve import (PrefillReplica, RemoteReplica,
                                      ReplicaRouter, ReplicaWorker,
                                      RouterConfig, ServingConfig,
                                      build_replicas)
    from ..telemetry import (FlightRecorder, MetricsRegistry,
                             get_registry, set_recorder, set_registry,
                             timeline, watchdog)

    def _engine():
        return InferenceEngineV2(model, {
            "dtype": "bfloat16",
            "state_manager": {"max_tracked_sequences": max(requests, 8),
                              "max_ragged_batch_size": 512,
                              "num_blocks": POOL_NUM_BLOCKS,
                              "block_size": POOL_BLOCK_SIZE,
                              "enable_prefix_caching": True},
        }, params=params)

    # shared-prefix traffic (the workload affinity placement exists
    # for): one block-aligned prefix per group, distinct tails
    rng = np.random.default_rng(0)
    prompts = []
    for _g in range(max(replicas_n, 2)):
        prefix = list(map(int, rng.integers(0, 2047, prompt)))
        for _ in range(max(requests // max(replicas_n, 2), 1)):
            prompts.append(prefix
                           + list(map(int, rng.integers(0, 2047, 8))))

    prev = set_registry(MetricsRegistry())
    prev_rec = set_recorder(FlightRecorder())
    watchdog.reset()
    try:
        async def run():
            workers = []
            if remote:
                replicas = []
                for i in range(replicas_n):
                    worker = ReplicaWorker(
                        _engine(), ServingConfig(token_budget=budget),
                        name=f"replica{i}")
                    host, port = await worker.start()
                    workers.append(worker)
                    replicas.append(RemoteReplica(f"replica{i}", host,
                                                  port))
            else:
                replicas = build_replicas(
                    [_engine() for _ in range(replicas_n)],
                    ServingConfig(token_budget=budget))
            pws = ([PrefillReplica("prefill0", _engine())]
                   if disaggregated else [])
            router = ReplicaRouter(
                replicas,
                RouterConfig(disaggregated=disaggregated,
                             handoff_chunk_blocks=chunk_blocks,
                             monitor_interval_s=0.0),
                prefill_replicas=pws)
            await router.start()
            reg = get_registry()

            async def wave():
                streams = [await router.submit(p, new_tokens)
                           for p in prompts]
                for s in streams:
                    await s.drain()

            w0 = time.perf_counter()
            await wave()
            await wave()
            warmup_s = time.perf_counter() - w0
            st0 = reg.family_total("xla_steady_state_recompiles_total")
            watchdog.mark_steady(True)
            try:
                t0 = time.perf_counter()
                await wave()
                dt = time.perf_counter() - t0
            finally:
                watchdog.mark_steady(False)
            out = {
                "replicas": replicas_n,
                "remote": remote,
                "disaggregated": disaggregated,
                "handoff_chunk_blocks": chunk_blocks,
                "handoff_chunks": reg.family_total(
                    "handoff_chunks_total"),
                # the ACTUAL per-wave request count (group-rounded from
                # the requested batch), which tok_s is computed over
                "requests": len(prompts),
                "tok_s": len(prompts) * new_tokens / dt,
                "warmup_s": warmup_s,
                "steady_state_recompiles": reg.family_total(
                    "xla_steady_state_recompiles_total") - st0,
                "requests_per_replica": {
                    v[0]: s.value for v, s in
                    (reg.get("router_requests_total").series()
                     if reg.get("router_requests_total") else ())},
                "affinity_hits": reg.family_total(
                    "router_affinity_hits_total"),
                "handoffs": reg.family_total("router_handoffs_total"),
                "trace_contexts": reg.family_total(
                    "trace_contexts_total"),
            }
            if trace_out:
                # the stitched fleet form: every lane (router + each
                # replica) a process row, spans carrying trace ids
                out["trace_out"] = timeline.write_fleet_trace(trace_out)
            await router.stop()
            for worker in workers:
                await worker.stop()
            return out

        return asyncio.run(run())
    finally:
        watchdog.reset()
        set_registry(prev)
        set_recorder(prev_rec)


def main_router(args) -> int:
    """--router mode: the routed fleet sweep, one JSON line."""
    import jax

    model = build_model(args.layers, args.hidden)
    params = model.init_params(jax.random.PRNGKey(0))
    res = bench_routed(model, params, replicas_n=args.router,
                       requests=args.batch, prompt=args.prompt,
                       new_tokens=args.new, budget=args.budget,
                       disaggregated=args.disagg,
                       trace_out=args.trace_out, remote=args.remote,
                       chunk_blocks=args.chunk_blocks)
    print(json.dumps({
        "metric": "serving_routed_tokens_per_sec",
        "backend": jax.default_backend(),
        "requests": args.batch, "prompt": args.prompt,
        "new_tokens": args.new,
        **{k: (round(v, 2) if isinstance(v, float) else v)
           for k, v in res.items()},
    }))
    return 0


def main_kv_spill(args) -> int:
    import jax

    model = build_model(args.layers, args.hidden)
    params = model.init_params(jax.random.PRNGKey(0))
    rep = bench_kv_spill(model, params,
                         conversations=max(args.batch, 4),
                         prompt=min(args.prompt, 48),
                         new_tokens=min(args.new, 8))
    print(json.dumps({
        "metric": "kv_spill_capacity",
        "backend": jax.default_backend(),
        **rep,
    }))
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="ds_tpu_serving_bench")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--prompt", type=int, default=64)
    p.add_argument("--new", type=int, default=64)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--hidden", type=int, default=256)
    p.add_argument("--repeats", type=int, default=3)
    p.add_argument("--window", type=int, default=8,
                   help="fused decode window K (1 = per-token only)")
    p.add_argument("--kv-quant", action="store_true",
                   help="serve through the int8 KV pool (per-block "
                        "scales, in-kernel dequant): adds pool-capacity "
                        "math (max concurrent sequences at the bf16 "
                        "pool's byte budget), quantized-kernel decode "
                        "tok/s and steady-state recompiles under the "
                        "double-warm bucket discipline")
    p.add_argument("--kv-spill", action="store_true",
                   help="KV spill capacity mode: a conversation sweep "
                        "through a pressure-sized pool, spill tier on "
                        "vs off — reports round-2 prefix reuse each "
                        "way, spill/restore flow, steady-state "
                        "recompiles (double-warm discipline) and max "
                        "concurrent conversations at the fixed HBM "
                        "pool budget")
    p.add_argument("--budget", type=int, default=256,
                   help="scheduler token budget per step (--router)")
    p.add_argument("--router", type=int, default=0, metavar="N",
                   help="routed fleet mode: shared-prefix traffic "
                        "through N in-process replicas behind the "
                        "prefix-affinity router — reports routed tok/s, "
                        "affinity hits, handoffs and steady-state "
                        "recompiles")
    p.add_argument("--remote", action="store_true",
                   help="with --router: put every replica behind a "
                        "loopback socket (worker + RemoteReplica shim — "
                        "the remote serving plane's wire)")
    p.add_argument("--chunk-blocks", type=int, default=4,
                   help="with --router --disagg: KV blocks per chunk of "
                        "the streaming handoff (0 = legacy blocking "
                        "whole-sequence transport)")
    p.add_argument("--disagg", action="store_true",
                   help="with --router: add a dedicated prefill replica "
                        "and route through the prefill->handoff->decode "
                        "disaggregated path")
    p.add_argument("--trace-out", default=None, metavar="PATH",
                   help="write the run's telemetry spans (request "
                        "lifelines, decode windows) as Chrome-trace-event "
                        "JSON to PATH (open in Perfetto); with --router "
                        "this is the STITCHED fleet timeline — a process "
                        "row per lane, spans correlated by trace id")
    args = p.parse_args(argv)

    if args.router:
        return main_router(args)
    if args.kv_spill:
        return main_kv_spill(args)

    import jax

    model = build_model(args.layers, args.hidden)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, 2047, (args.batch, args.prompt), dtype=np.int64)

    # fused window (the serving hot path) AND the per-token fallback on
    # the same config: their ratio is the dispatch-overhead win the fused
    # decode loop exists for
    paged = bench_paged(model, params, prompts, args.new, args.repeats,
                        decode_window=args.window, kv_quant=args.kv_quant)
    per_tok = (bench_paged(model, params, prompts, args.new, args.repeats,
                           decode_window=1, uid_base=500000,
                           kv_quant=args.kv_quant)
               if args.window > 1 else paged)
    dense = bench_dense(model, params, prompts, args.new, args.repeats)
    paged_tok_s = paged["tok_s"]
    dense_tok_s = dense["tok_s"]
    trace_out = None
    if args.trace_out:
        from ..telemetry import timeline
        trace_out = timeline.write_chrome_trace(args.trace_out)
    # flight-recorder + anomaly summary (the black box ran through the
    # whole bench): events per decode step is the recorder's overhead
    # in events, and TTFT percentiles come from the histogram's
    # quantile() — no raw-sample lists
    from ..telemetry import anomaly, get_recorder, get_registry
    reg = get_registry()
    rec_stats = get_recorder().stats()
    decode_steps_total = reg.family_total("inference_decode_steps_total")
    ttft_fam = reg.get("inference_ttft_seconds")

    def _q(q):
        v = ttft_fam.quantile(q) if ttft_fam and ttft_fam.count else None
        return round(v, 4) if v is not None and v == v else None
    print(json.dumps({
        "metric": "serving_tokens_per_sec",
        "backend": jax.default_backend(),
        "batch": args.batch, "prompt": args.prompt, "new_tokens": args.new,
        "decode_window": args.window,
        "paged_tok_s": round(paged_tok_s, 2),
        # registry-derived (telemetry/): decode-only throughput, mean
        # TTFT, and the decode loop's host-sync count (fused window: one
        # transfer per K tokens; per-token: one per token)
        "paged_decode_tok_s": (round(paged["decode_tok_s"], 2)
                               if paged["decode_tok_s"] else None),
        "paged_decode_steps": paged["decode_steps"],
        "paged_decode_host_syncs": paged["decode_host_syncs"],
        "paged_syncs_per_token": (
            round(paged["decode_host_syncs_per_token"], 4)
            if paged["decode_host_syncs_per_token"] is not None else None),
        "paged_ttft_s": (round(paged["ttft_s"], 4)
                         if paged["ttft_s"] else None),
        "paged_warmup_s": round(paged["warmup_s"], 3),
        "paged_per_token_tok_s": round(per_tok["tok_s"], 2),
        "per_token_decode_tok_s": (round(per_tok["decode_tok_s"], 2)
                                   if per_tok["decode_tok_s"] else None),
        "per_token_decode_host_syncs": per_tok["decode_host_syncs"],
        # end-to-end ratio (prefill included) AND the decode-only ratio
        # from the registry timers — the latter isolates the dispatch
        # win even when a long prompt dominates end-to-end time
        "fused_over_per_token": (round(paged_tok_s / per_tok["tok_s"], 3)
                                 if per_tok["tok_s"] else None),
        "fused_over_per_token_decode": (
            round(paged["decode_tok_s"] / per_tok["decode_tok_s"], 3)
            if paged["decode_tok_s"] and per_tok["decode_tok_s"]
            else None),
        "kv_pool_utilization_peak": round(
            paged["kv_pool_utilization_peak"], 4),
        # forensics fields (this PR): compiler-measured MFU of the decode
        # hot path, its program footprint, and the watchdog's verdict
        # that steady-state serving compiled nothing
        "decode_mfu": (round(paged["decode_mfu"], 5)
                       if paged["decode_mfu"] else None),
        "decode_flops_per_token": (round(paged["decode_flops_per_token"])
                                   if paged["decode_flops_per_token"]
                                   else None),
        "decode_peak_bytes": paged["decode_peak_bytes"],
        "steady_state_recompiles": paged["steady_state_recompiles"],
        # --kv-quant: the capacity story (same pool BYTE budget, how
        # many max_seq_len sequences fit each layout) next to the
        # quantized-kernel throughput and the watchdog's recompile
        # verdict above — the "2x concurrency without leaving the fast
        # path" artifact
        **({"kv_quant": True,
            **{f"kv_{k}": v for k, v in kv_capacity_report(
                model.cfg, block_size=POOL_BLOCK_SIZE,
                num_blocks=POOL_NUM_BLOCKS,
                max_seq_len=min(1024, model.cfg.max_seq_len)).items()}}
           if args.kv_quant else {}),
        # active-observability summary (this PR): black-box coverage,
        # overhead, histogram-quantile TTFT percentiles, and any
        # anomaly verdict raised during the run
        "recorder_events": rec_stats["recorded"],
        "recorder_events_per_decode_step": (
            round(rec_stats["recorded"] / decode_steps_total, 2)
            if decode_steps_total else None),
        "ttft_p50_s": _q(0.5), "ttft_p95_s": _q(0.95),
        "ttft_p99_s": _q(0.99),
        "anomalies": [v["kind"] for v in anomaly.recent()],
        "trace_out": trace_out,
        "dense_tok_s": round(dense_tok_s, 2),
        "dense_warmup_s": round(dense["warmup_s"], 3),
        "paged_over_dense": (round(paged_tok_s / dense_tok_s, 3)
                             if dense_tok_s else None),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
