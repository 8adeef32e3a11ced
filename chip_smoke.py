#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

Drives the two main paths once, through the entry points a user calls, at
the published width of a model the repo supports, and checks what comes
out by the repo's own means. One process, JAX imported once, no child.

    python chip_smoke.py                 # one TPU chip: phases K, T1, S1
    python chip_smoke.py --chips 4       # four required: adds T4, S4
    python chip_smoke.py --rehearse      # toy widths on the CPU, labelled

Phases (each passes or the exit code is non-zero; nothing is caught):

  K   every Pallas kernel on the two paths, compiled by Mosaic and
      compared with its jax.numpy reference at small batch
  T1  deepspeed_tpu.initialize() -> train_batch: opt_125m, seq 2048,
      micro 8, bf16 + AdamW, six steps on one chip
  S1  InferenceEngineV2 -> DynamicSplitFuseScheduler ->
      ServingEngine.submit(): opt_1_3b, 8 concurrent greedy requests,
      streams equal engine.generate() token for token
  T4  opt_1_3b under ZeRO-3 at dp=4, then dp=2 x tp=2, on four chips
  S4  the S1 requests at tensor_parallel_size=4

Without a TPU the script exits non-zero before any phase; --rehearse is
the only CPU route and says so on its first and last line. Times printed
here are for the log; they are not metrics.

The last line of a chip run is one JSON object:
    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}
"""

import argparse
import asyncio
import contextlib
import dataclasses
import gc
import json
import os
import sys
import time

REHEARSAL_BANNER = "REHEARSAL (cpu) — not a chip result"
PHASES = ("K", "T1", "S1", "T4", "S4")
# bf16 parity bounds, max |err| over max |ref| (attention_autotune's
# metric): one bf16 rounding of the output is 4e-3; gradients sum many
OUT_TOL, GRAD_TOL = 2e-2, 5e-2
# the layouts' first-step losses must agree like __graft_entry__._run_tiny
LAYOUT_LOSS_TOL = 3e-2
# logits leave the head matmul in bf16 (8 significant bits): two tokens
# within this many bf16 steps of the top logit are a tie that reduction
# order decides
TIE_BF16_STEPS = 4


class CompileClock:
    """Seconds JAX spent in backend compiles (or persistent-cache reads
    in their place) and how many of them the cache served."""

    def __init__(self):
        import jax.monitoring as mon
        self.seconds = 0.0
        self.hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


@contextlib.contextmanager
def phase(name, clock):
    """Prints the pass line only if the body returns; a failure
    propagates and ends the run."""
    print(f"[{name}] start", flush=True)
    t0, c0, h0 = time.perf_counter(), clock.seconds, clock.hits
    yield
    print(f"[{name}] PASS wall={time.perf_counter() - t0:.1f}s "
          f"compile={clock.seconds - c0:.1f}s "
          f"cache_hits={clock.hits - h0}", flush=True)


def check(cond, message):
    if not cond:
        raise AssertionError(message)


def free_device_memory():
    import jax
    gc.collect()
    jax.clear_caches()


# ---------------------------------------------------------------------------
# K — kernels
# ---------------------------------------------------------------------------
def ragged_reference(q, kc, vc, rows, lens, tables, ks=None, vs=None):
    """Dense jax.numpy reference for the paged kernels over ONE layer,
    kc/vc ``[nb, bs, kvh, hd]``: gather each row's
    pages (dequantizing like paged_model._kv_read), mask to each token's
    causal bound, plain softmax in fp32. Padding tokens give zeros."""
    import jax
    import jax.numpy as jnp
    T, nh, hd = q.shape
    _, bs, kvh, _ = kc.shape
    R, MB = tables.shape

    def pages(c, s):
        p = c[tables]                                   # [R, MB, bs, kvh, hd]
        if s is not None:
            p = (p.astype(jnp.float32)
                 * s[tables][:, :, None, :, None]).astype(q.dtype)
        p = p.reshape(R, MB * bs, kvh, hd)[rows]        # [T, ctx, kvh, hd]
        return jnp.repeat(p, nh // kvh, axis=2).astype(jnp.float32)

    k, v = pages(kc, ks), pages(vc, vs)
    s = jnp.einsum("thd,tchd->thc", q.astype(jnp.float32), k) / (hd ** 0.5)
    mask = jnp.arange(MB * bs)[None, :] < lens[:, None]
    p = jax.nn.softmax(jnp.where(mask[:, None, :], s, -1e30), axis=-1)
    out = jnp.einsum("thc,tchd->thd", p, v)
    return jnp.where((lens > 0)[:, None, None], out, 0.0)


def rel_err(a, b):
    import jax.numpy as jnp
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    check(bool(jnp.all(jnp.isfinite(a))), "kernel output is not finite")
    return float(jnp.max(jnp.abs(a - b)) / jnp.maximum(jnp.max(jnp.abs(b)),
                                                       1e-6))


def phase_kernels(sizes):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.v2.kernels.paged_attention import \
        paged_attention
    from deepspeed_tpu.inference.v2.kernels.ragged_attention import (
        kernel_variant, ragged_attention)
    from deepspeed_tpu.ops.attention_autotune import parity_check

    # the last row is over the 4,096 rows of K/V one grid step keeps
    # resident: the chunked walk and its clamped index maps. At 2,048 rows
    # the two heads' dQ fits VMEM and the backward is the one fused kernel
    for hd, seq, fused in ((64, sizes["flash_seq"], True),
                           (128, sizes["flash_seq"], True),
                           (64, sizes["flash_seq_chunked"], False)):
        rep = parity_check(batch=1, heads=4, kv_heads=2, seq=seq,
                           head_dim=hd)
        print(f"  flash fwd+bwd hd={hd} seq={rep['seq']} "
              f"{'+'.join(rep['kernels'])}: "
              f"out={rep['out_rel_err']:.2e} dq={rep['dq_rel_err']:.2e} "
              f"dk={rep['dk_rel_err']:.2e} dv={rep['dv_rel_err']:.2e}",
              flush=True)
        check(rep["out_rel_err"] < OUT_TOL, f"flash fwd hd={hd}: {rep}")
        check(max(rep["dq_rel_err"], rep["dk_rel_err"], rep["dv_rel_err"])
              < GRAD_TOL, f"flash bwd hd={hd}: {rep}")
        if fused:
            check(rep["kernels"] == ["bwd", "fwd"],
                  f"flash hd={hd}: the fused backward did not run: {rep}")

    rng = np.random.default_rng(0)
    nb, bs, R, MB = 80, 16, 4, 16
    # ragged rows: a 150-token chunk at positions 20..169 (over a cached
    # prefix, longer than one query tile of the tiled kernel, ending
    # mid-page), decode rows deep into / at the start / at the end of
    # their tables, padding to T=256
    positions = [range(20, 170), [200], [5], [255]]
    rows = [r for r, ps in enumerate(positions) for _ in ps]
    lens = [p + 1 for ps in positions for p in ps]
    T = 256
    pad = T - len(rows)
    rows, lens = rows + [0] * pad, lens + [0] * pad
    for nh, kvh, hd in ((32, 32, 64), (32, 8, 128)):
        for quant in (False, True):
            variant = kernel_variant(hd, kvh, quant)

            def pool():
                if quant:
                    return (jnp.asarray(rng.integers(
                                -127, 128, (nb, bs, kvh, hd)), jnp.int8),
                            jnp.asarray(rng.uniform(
                                0.005, 0.03, (nb, kvh)), jnp.float32))
                return jnp.asarray(rng.standard_normal(
                    (nb, bs, kvh, hd)), jnp.bfloat16), None

            (kc, ks), (vc, vs) = pool(), pool()

            def stored(c):
                """The pool as the engine stores it, [L, nb, bs, kvh *
                hd], whole, ``c`` its layer 1 of 3; the kernel takes the
                layer as a scalar and may read no other (NaN; int8:
                -128)."""
                return jnp.full((3, nb, bs, kvh * hd),
                                -128 if quant else jnp.nan,
                                c.dtype).at[1].set(c.reshape(nb, bs, -1))

            kp, vp = stored(kc), stored(vc)
            tables = jnp.asarray(rng.permutation(np.arange(1, nb))
                                 [:R * MB].reshape(R, MB), jnp.int32)
            q = jnp.asarray(rng.standard_normal((T, nh, hd)), jnp.bfloat16)
            rows_a = jnp.asarray(rows, jnp.int32)
            lens_a = jnp.asarray(lens, jnp.int32)
            ragged = jax.jit(ragged_attention)(
                q, kp, vp, jnp.int32(1), rows_a, lens_a, tables,
                k_scale=ks, v_scale=vs)
            e_r = rel_err(ragged, ragged_reference(
                q, kc, vc, rows_a, lens_a, tables, ks, vs))
            dlen = jnp.asarray([1, 16, 77, 256], jnp.int32)
            decode = jax.jit(paged_attention)(
                q[:R], kp, vp, jnp.int32(1), tables, dlen,
                k_scale=ks, v_scale=vs)
            e_d = rel_err(decode, ragged_reference(
                q[:R], kc, vc, jnp.arange(R, dtype=jnp.int32), dlen,
                tables, ks, vs))
            print(f"  paged nh={nh} kvh={kvh} hd={hd} "
                  f"kv={'int8' if quant else 'bf16'}: engine picks "
                  f"pallas:{variant}; ragged err={e_r:.2e} "
                  f"decode err={e_d:.2e}", flush=True)
            check(max(e_r, e_d) < OUT_TOL,
                  f"paged kernel parity nh={nh} kvh={kvh} hd={hd} "
                  f"quant={quant} variant={variant}: {e_r} {e_d}")


# ---------------------------------------------------------------------------
# T — trainer
# ---------------------------------------------------------------------------
def train_steps(cfg, ds_config, steps, devices, label):
    """deepspeed_tpu.initialize() over ``devices`` -> ``steps``
    train_batch calls on one fixed seeded batch. Returns (losses, engine);
    the caller destroys the engine."""
    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import TransformerLM

    topo = None     # the user's call: every device JAX sees
    if len(devices) != jax.device_count():
        from deepspeed_tpu.parallel.topology import build_topology
        from deepspeed_tpu.runtime.config import DeepSpeedConfig
        topo = build_topology(
            DeepSpeedConfig(ds_config, world_size=len(devices)),
            devices=devices)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=TransformerLM(cfg), config=ds_config, topology=topo)
    gm = engine.micro_batch_size * engine.ds_config.dp_world_size
    batch = {"input_ids": np.random.default_rng(0).integers(
        0, cfg.vocab_size, (engine.gas, gm, cfg.max_seq_len),
        dtype=np.int64)}
    losses = []
    for i in range(steps):
        t0 = time.perf_counter()
        loss = float(engine.train_batch(batch=batch))
        jax.block_until_ready(engine.params)
        losses.append(loss)
        print(f"  {label} step {i}: loss={loss:.4f} "
              f"({time.perf_counter() - t0:.2f}s)", flush=True)
    check(all(np.isfinite(losses)), f"{label}: non-finite loss {losses}")
    check(losses[-1] < losses[0],
          f"{label}: loss did not fall on a fixed batch: {losses}")
    return losses, engine


def train_config(micro, gas=1, stage=0, tp=1):
    cfg = {"train_micro_batch_size_per_gpu": micro,
           "gradient_accumulation_steps": gas,
           "optimizer": {"type": "adamw", "params": {"lr": 3e-4}},
           "bf16": {"enabled": True},
           "zero_optimization": {"stage": stage,
                                 "stage3_param_persistence_threshold": 0},
           "steps_per_print": 10 ** 9}
    if tp > 1:
        cfg["tensor_parallel_size"] = tp
    return cfg


def phase_train_one_chip(sizes):
    import jax
    cfg = sizes["train_cfg_1"]
    check(cfg.use_flash and cfg.max_seq_len >= cfg.flash_min_seq,
          "T1 must put the flash kernel in the step")
    _, engine = train_steps(cfg, train_config(sizes["micro_1"]), 6,
                            jax.devices()[:1], "T1")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"  T1 peak_bytes_in_use={stats.get('peak_bytes_in_use')}",
          flush=True)
    engine.destroy()
    free_device_memory()


def bytes_in_use(devices):
    return [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]


def sharding_evidence(engine, devices, before):
    """ZeRO-3 at dp=4 really shards: the largest parameter leaf sits in
    four quarter-size shards on four devices, the devices hold similar
    bytes (over what they held ``before`` the engine), and none holds the
    whole model state."""
    import jax
    leaf = max(jax.tree.leaves(engine.params), key=lambda x: x.nbytes)
    shards = leaf.addressable_shards
    on = {s.device for s in shards}
    sizes = [s.data.nbytes for s in shards]
    print(f"  largest param leaf {tuple(leaf.shape)} {leaf.dtype} "
          f"{leaf.nbytes} B: {len(shards)} shards on {len(on)} devices, "
          f"shard bytes {sizes}", flush=True)
    check(len(shards) == 4 and len(on) == 4,
          f"largest leaf is not on four devices: {shards}")
    check(all(b * 4 == leaf.nbytes for b in sizes),
          f"shards are not a quarter of the leaf: {sizes} of {leaf.nbytes}")
    state = sum(x.nbytes for tree in (engine.params, engine.master_params,
                                      engine.opt_state)
                for x in jax.tree.leaves(tree) if hasattr(x, "nbytes"))
    in_use = bytes_in_use(devices)
    print(f"  model state (params+master+optimizer) {state} B; "
          f"per-device bytes_in_use {in_use}, before the engine {before}",
          flush=True)
    if all(b is not None for b in in_use):      # the CPU rehearsal has none
        in_use = [b - b0 for b, b0 in zip(in_use, before)]
        check(max(in_use) <= 1.25 * min(in_use),
              f"devices are not balanced: {in_use}")
        check(max(in_use) < state,
              f"a device holds the whole model state: {in_use} vs {state}")


def phase_train_four_chips(sizes):
    import jax
    cfg, devices = sizes["train_cfg_4"], jax.devices()[:4]
    micro = sizes["micro_4"]
    first = {}
    before = bytes_in_use(devices)
    # the same 8 sequences per step in both layouts: 4 x micro, or
    # 2 x micro x gas 2
    for label, steps, kw in (("T4 zero3 dp=4", 4, dict()),
                             ("T4 zero3 dp=2 x tp=2", 2,
                              dict(gas=2, tp=2))):
        losses, engine = train_steps(
            cfg, train_config(micro, stage=3, **kw), steps, devices, label)
        first[label] = losses[0]
        if not kw:
            sharding_evidence(engine, devices, before)
        engine.destroy()
        del engine
        free_device_memory()
    a, b = first.values()
    print(f"  first-step loss dp=4 {a:.4f} vs dp=2 x tp=2 {b:.4f} "
          f"(|d|={abs(a - b):.1e})", flush=True)
    check(abs(a - b) <= LAYOUT_LOSS_TOL,
          f"layouts disagree on the first-step loss: {first}")


# ---------------------------------------------------------------------------
# S — server
# ---------------------------------------------------------------------------
def pack_groups(prompts, cap):
    """Consecutive prompts grouped so that each group's tokens fit one
    put() of ``cap`` tokens."""
    groups, size = [[]], 0
    for p in prompts:
        if groups[-1] and size + len(p) > cap:
            groups.append([])
            size = 0
        groups[-1].append(p)
        size += len(p)
    return groups


def tied_in_reference(engine, prompt, prefix, a, b):
    """Do tokens ``a`` and ``b`` tie for the top of the engine's own
    logits after ``prompt + prefix``, within bf16 resolution?"""
    uid = 1 << 20
    logits = engine.put([uid], [list(prompt) + list(prefix)])[0]
    engine.flush(uid)
    top = float(logits.max())
    low = float(min(logits[a], logits[b]))
    return (top - low <= TIE_BF16_STEPS * 2.0 ** -8 * abs(top),
            f"logits {float(logits[a]):.4f} / {float(logits[b]):.4f}, "
            f"top {top:.4f}")


def serve_and_compare(sizes, tp, label):
    import numpy as np

    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.inference.v2.serve import ServingConfig, ServingEngine
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.telemetry import anomaly

    cfg, new = sizes["serve_cfg"], sizes["new_tokens"]
    cap = sizes["generate_tokens" if tp == 1 else "generate_tokens_tp"]
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in sizes["prompt_lens"]]
    engine = InferenceEngineV2(TransformerLM(cfg), {
        "dtype": "bfloat16", "use_paged_kernel": True,
        "tensor_parallel_size": tp,
        "state_manager": {
            "max_tracked_sequences": len(prompts),
            # generate() feeds each group of prompts in one put(); the
            # jnp gather path (tp > 1) materializes [tokens, context]
            # pages per head, so its groups are smaller
            "max_ragged_batch_size": cap,
            "max_seq_len": cfg.max_seq_len,
            "num_blocks": sizes["kv_tokens"] // 16 + 1, "block_size": 16}})
    print(f"  {label} attention_impl={engine.attention_impl}", flush=True)
    if tp == 1:
        check(engine.attention_impl.startswith("pallas:"),
              f"{label} resolved the jnp gather path: "
              f"{engine.attention_impl}")

    anomaly.reset()

    async def serve():
        serving = ServingEngine(engine, ServingConfig(
            token_budget=sizes["token_budget"], chunk=sizes["chunk"]))
        await serving.start()
        try:
            streams = [await serving.submit(p, max_new_tokens=new)
                       for p in prompts]
            return await asyncio.wait_for(
                asyncio.gather(*(s.drain() for s in streams)),
                timeout=sizes["serve_timeout_s"])
        finally:
            await serving.stop(drain=False, timeout=60)

    t0 = time.perf_counter()
    streams = asyncio.run(serve())
    print(f"  {label} served {len(streams)} requests "
          f"({time.perf_counter() - t0:.1f}s)", flush=True)
    for i, s in enumerate(streams):
        check(len(s) == new, f"{label} stream {i} returned {len(s)} of "
                             f"{new} tokens")
    bad = [v for v in anomaly.recent()
           if v["kind"] in ("serving_step_error", "kv_leak")]
    check(not bad, f"{label} anomaly verdicts: {bad}")
    other = sorted({v["kind"] for v in anomaly.recent()})
    if other:
        print(f"  {label} other anomaly kinds (cold start): {other}",
              flush=True)

    t0 = time.perf_counter()
    groups = pack_groups(prompts, cap)
    ref = [r for g in groups for r in engine.generate(g, max_new_tokens=new)]
    print(f"  {label} generate() reference in {len(groups)} call(s) "
          f"({time.perf_counter() - t0:.1f}s)", flush=True)
    # the Pallas path walks each token's pages in one order whatever the
    # batch around it, so its streams must equal generate()'s exactly. The
    # jnp gather path (tp > 1) reduces over a table width that depends on
    # the batch: there a stream may leave generate()'s at a bf16 tie of
    # the reference logits, and nowhere else
    exact = engine.attention_impl.startswith("pallas:")
    departs, ties = [], 0
    for i, (p, s, r) in enumerate(zip(prompts, streams, ref)):
        want = [int(t) for t in r[len(p):]]
        if list(s) == want:
            continue
        at = next(j for j, (x, y) in enumerate(zip(s, want)) if x != y)
        where = f"stream {i} (prompt {len(p)}) at token {at}: " \
                f"{s[at]} vs {want[at]}"
        if not exact:
            tied, how = tied_in_reference(engine, p, want[:at], s[at],
                                          want[at])
            print(f"  {label} {where}; {how}; bf16 tie: {tied}", flush=True)
            if tied:
                ties += 1
                continue
        departs.append(where)
    check(not departs, f"{label} streams depart from generate(): "
                       + "; ".join(departs))
    print(f"  {label} {len(streams)} streams x {new} tokens equal "
          f"generate()" + (f", {ties} up to a bf16 tie" if ties else ""),
          flush=True)
    del engine
    free_device_memory()


# ---------------------------------------------------------------------------
def sizes_for(rehearse):
    from deepspeed_tpu.models.transformer import opt_125m, opt_1_3b
    if not rehearse:
        return dict(
            flash_seq=2048, flash_seq_chunked=8192,
            train_cfg_1=opt_125m(), micro_1=8,
            train_cfg_4=opt_1_3b(), micro_4=2,
            serve_cfg=opt_1_3b(),
            prompt_lens=(16, 40, 96, 200, 330, 520, 900, 1500),
            new_tokens=64, kv_tokens=8192, generate_tokens=4096,
            generate_tokens_tp=2048,
            token_budget=512, chunk=256, serve_timeout_s=900)
    # the same OPT block at toy widths; seq 256 with the flash switch
    # lowered so the (interpret-mode) flash kernel is still in the step
    toy = dataclasses.replace(
        opt_125m(), vocab_size=512, hidden_size=128, intermediate_size=512,
        num_layers=2, num_heads=4, max_seq_len=256, flash_min_seq=256)
    return dict(
        flash_seq=256, flash_seq_chunked=512,
        train_cfg_1=toy, micro_1=2, train_cfg_4=toy, micro_4=2,
        serve_cfg=toy, prompt_lens=(4, 9, 17, 30, 41, 60, 90, 150),
        new_tokens=8, kv_tokens=1024, generate_tokens=512,
        generate_tokens_tp=256,
        token_budget=64, chunk=32, serve_timeout_s=600)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="TPU devices the run requires (4 adds T4 and S4)")
    ap.add_argument("--phases", default=None,
                    help="comma-separated subset of " + ",".join(PHASES))
    ap.add_argument("--rehearse", action="store_true",
                    help="toy widths on JAX_PLATFORMS=cpu, kernels in "
                         "interpret mode; not a chip result")
    args = ap.parse_args(argv)

    if args.rehearse:
        print(REHEARSAL_BANNER, flush=True)
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4")

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from deepspeed_tpu.accelerator.tpu_accelerator import (
        apply_collective_overlap_flags, peak_flops, require_tpu)
    from deepspeed_tpu.env_report import compiler_fingerprint
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    # libtpu reads these when the TPU client starts, i.e. at the first
    # jax.devices() below
    apply_collective_overlap_flags()
    import jax
    cache_dir = enable_compile_cache()
    clock = CompileClock()

    devices = jax.devices()
    versions = compiler_fingerprint()
    print(f"platform: {devices[0].platform}\n"
          f"device_kind: {devices[0].device_kind}\n"
          f"device_count: {len(devices)}\n"
          f"jax {versions['jax']} jaxlib {versions['jaxlib']} "
          f"libtpu {versions['libtpu'] or 'not installed'}\n"
          f"compile_cache: {cache_dir}", flush=True)
    if not args.rehearse:
        require_tpu(min_devices=args.chips)
        print(f"peak bf16 FLOP/s for this kind: "
              f"{peak_flops(devices[0]):.3g}", flush=True)

    wanted = (args.phases.split(",") if args.phases else
              [p for p in PHASES if not p.endswith("4") or len(devices) >= 4])
    unknown = set(wanted) - set(PHASES)
    check(not unknown, f"unknown phases {sorted(unknown)}")
    sizes = sizes_for(args.rehearse)
    bodies = {
        "K": lambda: phase_kernels(sizes),
        "T1": lambda: phase_train_one_chip(sizes),
        "S1": lambda: serve_and_compare(sizes, 1, "S1"),
        "T4": lambda: phase_train_four_chips(sizes),
        "S4": lambda: serve_and_compare(sizes, 4, "S4"),
    }
    t0 = time.perf_counter()
    for name in PHASES:
        if name in wanted:
            with phase(name, clock):
                bodies[name]()
    print(f"phases passed: {','.join(p for p in PHASES if p in wanted)} "
          f"wall={time.perf_counter() - t0:.1f}s "
          f"compile={clock.seconds:.1f}s cache_hits={clock.hits}",
          flush=True)
    if args.rehearse:
        print(REHEARSAL_BANNER, flush=True)
    else:
        print(json.dumps({"ok": True, "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
