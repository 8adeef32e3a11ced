"""Chip-free scale proofs: AOT compilation against TPU topology descriptions.

The libtpu compiler is a host library — ``jax.experimental.topologies`` can
describe a full v5e-64 pod slice and ``jit(...).lower(...).compile()`` runs
the REAL TPU compilation pipeline (SPMD partitioner, async collective fusion,
latency-hiding scheduler, memory assignment) with no device attached. Two
proofs ride on that:

1. **ZeRO-3 overlap at dp=8** (VERDICT r4 Next #2): compile the engine's
   actual jitted train step for a v5e 8-chip slice at stage 0 vs stage 3 and
   measure how many parameter all-gathers the TPU backend covers with async
   collective fusion chains (its equivalent of the reference's dedicated
   __allgather_stream, reference runtime/zero/stage3.py:1151). Writes
   ``<out>/overlap_dp8.json``.

2. **The Llama-2-7B / v5e-64 north star fits** (VERDICT r4 Next #3): compile
   the real 7B config under ZeRO-3 (and ZeRO-3+hpZ) on a v5e:8x8 topology and
   read per-chip argument+temp bytes out of the executable's memory analysis;
   assert they clear the 16 GB HBM of a v5e chip. Artifact:
   ``artifacts/flagship_7b_v5e64.json``.

Run: ``python -m deepspeed_tpu.benchmarks.aot_scale --out artifacts``.
"""

import argparse
import json
import os
from typing import Any, Dict, Optional

import numpy as np

V5E_HBM_BYTES = 16 * 1024 ** 3  # 16 GiB per v5e chip


def _require_cpu_backend():
    import jax

    from ..utils.compile_cache import enable_compile_cache
    # AOT topology compiles need no device, but tracing creates host
    # constants: those live on the CPU backend
    jax.config.update("jax_platforms", "cpu")
    enable_compile_cache()


def build_abstract_engine(model_cfg, ds_cfg: Dict[str, Any],
                          topology_name: str = "v5e:2x4",
                          topo_cfg=None, seed: int = 0):
    """Engine over a TPU topology mesh with ShapeDtypeStruct state (nothing
    executes; only lower_train_step is usable). Returns (engine, batch)."""
    import jax
    from jax.experimental import topologies

    from ..models import TransformerLM
    from ..parallel.topology import MeshTopology, TopologyConfig
    from ..runtime.config import DeepSpeedConfig
    from ..runtime.engine import DeepSpeedTpuEngine

    _require_cpu_backend()
    desc = topologies.get_topology_desc(topology_name, platform="tpu")
    topo = MeshTopology(topo_cfg or TopologyConfig(), devices=desc.devices)
    config = DeepSpeedConfig(dict(ds_cfg), world_size=len(desc.devices))
    engine = DeepSpeedTpuEngine(TransformerLM(model_cfg), config,
                                topology=topo, seed=seed, abstract_init=True)
    gas = config.gradient_accumulation_steps
    gm = config.train_micro_batch_size_per_gpu * config.dp_world_size
    batch = {"input_ids": np.zeros((gas, gm, model_cfg.max_seq_len),
                                   dtype=np.int64)}
    return engine, batch


def _mem_record(compiled) -> Dict[str, Any]:
    ma = compiled.memory_analysis()
    rec = {k: int(getattr(ma, k)) for k in
           ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes") if hasattr(ma, k)}
    # donated inputs alias outputs, so peak live state is arguments + temps
    rec["peak_bytes_per_chip"] = (rec.get("argument_size_in_bytes", 0)
                                  + rec.get("temp_size_in_bytes", 0)
                                  + rec.get("generated_code_size_in_bytes", 0))
    rec["peak_gib_per_chip"] = round(rec["peak_bytes_per_chip"] / 1024 ** 3, 3)
    return rec


def overlap_dp8(model_cfg=None, out_dir: Optional[str] = None,
                topology_name: str = "v5e:2x4") -> Dict[str, Any]:
    """Stage-0 vs stage-3 async-collective coverage on an 8-chip v5e slice.

    Three compiles: stage 0 (baseline — only gradient all-reduces), stage 3
    as the production step runs it (layer scan, unroll hint 2), and stage 3
    with the layer scan fully unrolled — the maximal scheduling window,
    where every per-layer parameter gather is visible to async collective
    fusion at once. The headline metric is the unrolled variant's
    ``param_gather_exposed_fraction``: the share of matmul-feeding
    all-gathers the TPU backend failed to cover with an async chain."""
    from ..utils.xla_profile import tpu_overlap_report_from_compiled

    if model_cfg is None:
        from ..models import TransformerConfig
        # the bench flagship proxy's geometry (374M class), full seq
        model_cfg = TransformerConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_layers=24, num_heads=8, num_kv_heads=8, max_seq_len=2048)
    record: Dict[str, Any] = {"topology": topology_name,
                              "num_layers": model_cfg.num_layers}
    variants = (("stage0", 0, False), ("stage3_scan", 3, False),
                ("stage3_unrolled", 3, True))
    for name, stage, unroll in variants:
        engine, batch = build_abstract_engine(
            model_cfg,
            {"train_micro_batch_size_per_gpu": 1,
             "bf16": {"enabled": True},
             "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
             "zero_optimization": {
                 "stage": stage, "overlap_comm": True,
                 # reference default (zero/config.py): small params stay
                 # persistent/replicated — no per-norm gathers
                 "stage3_param_persistence_threshold": 100000},
             "steps_per_print": 10 ** 9},
            topology_name=topology_name)
        if unroll:
            engine.model.scan_unroll_hint = model_cfg.num_layers
        compiled = engine.lower_train_step(batch)
        rep = tpu_overlap_report_from_compiled(compiled)
        record[name] = dict(rep.to_dict(), memory=_mem_record(compiled))
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "overlap_dp8.json"), "w") as fh:
            json.dump(record, fh, indent=1)
    return record


def grad_overlap_dp8(model_cfg=None, out_dir: Optional[str] = None,
                     topology_name: str = "v5e:2x4", stage: int = 2,
                     reduce_bucket_size: int = 1 << 19) -> Dict[str, Any]:
    """Gradient-reduction overlap at dp=8: monolithic vs bucketed.

    Compiles the engine's real train step twice on an 8-chip v5e topology —
    ``overlap_grad_reduce='off'`` (the seed behavior: GSPMD emits the
    reduction, in practice one fused collective after the full backward,
    a round-5 capture's ``exposed_collective_fraction: 1.0``) vs ``'bucketed'``
    (runtime/grad_overlap.py issues per-bucket collectives the TPU
    latency-hiding scheduler can float into the backward as async
    ppermute-ring hops). The headline regression metric is the bucketed
    variant's ``exposed_collective_fraction`` — the share of
    gradient-exchange collectives with no overlap window in the scheduled
    HLO. Chip-free: the libtpu compiler runs on the CPU host. Writes
    ``<out_dir>/grad_overlap_dp8.json`` when given an ``out_dir``."""
    from ..utils.xla_profile import (grad_exchange_report_from_compiled,
                                     tpu_overlap_report_from_compiled)

    if model_cfg is None:
        from ..models import TransformerConfig
        # proxy sized so tier-1 can afford the compile; the layer scan is
        # fully unrolled (scan_unroll) so the bucket plan slices the
        # stacked layer leaves per layer — a layer's bucket then reduces
        # while shallower layers are still in backward
        model_cfg = TransformerConfig(
            vocab_size=2048, hidden_size=256, intermediate_size=512,
            num_layers=4, num_heads=4, max_seq_len=128, use_flash=False,
            scan_unroll=4)
    from ..runtime.grad_overlap import ring_wire_bytes

    record: Dict[str, Any] = {"topology": topology_name, "stage": stage,
                              "num_layers": model_cfg.num_layers,
                              "reduce_bucket_size": int(reduce_bucket_size)}
    quant_block = 2048
    for name, mode, qr in (("monolithic", "off", "off"),
                           ("bucketed", "bucketed", "off"),
                           ("bucketed_int8", "bucketed", "int8")):
        engine, batch = build_abstract_engine(
            model_cfg,
            {"train_micro_batch_size_per_gpu": 1,
             "bf16": {"enabled": True},
             "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
             "zero_optimization": {
                 "stage": stage, "overlap_comm": True,
                 "overlap_grad_reduce": mode,
                 "quantized_reduce": qr,
                 "quant_block": quant_block,
                 "reduce_bucket_size": int(reduce_bucket_size),
                 "allgather_bucket_size": int(reduce_bucket_size),
                 "stage3_param_persistence_threshold": 100000},
             "steps_per_print": 10 ** 9},
            topology_name=topology_name)
        compiled = engine.lower_train_step(batch)
        gx = grad_exchange_report_from_compiled(compiled)
        acf = tpu_overlap_report_from_compiled(compiled)
        rec = gx.to_dict()
        rec["acf"] = {k: v for k, v in acf.to_dict().items()
                      if k != "bare_ops"}
        if engine.grad_bucket_plan is not None:
            rec["bucket_plan"] = engine.grad_bucket_plan.to_dict()
            dp = engine.ds_config.dp_world_size
            rec["ring_wire_bytes_fp32"] = ring_wire_bytes(
                engine.grad_bucket_plan, dp)
            rec["ring_wire_bytes_quant"] = ring_wire_bytes(
                engine.grad_bucket_plan, dp, quantized=True,
                quant_block=quant_block)
        record[name] = rec
    record["exposed_collective_fraction"] = \
        record["bucketed"]["exposed_collective_fraction"]
    record["exposed_collective_fraction_monolithic"] = \
        record["monolithic"]["exposed_collective_fraction"]
    record["exposed_collective_fraction_int8"] = \
        record["bucketed_int8"]["exposed_collective_fraction"]
    qrec = record["bucketed_int8"]
    record["quant_wire_ratio"] = (
        round(qrec["ring_wire_bytes_fp32"]
              / qrec["ring_wire_bytes_quant"], 3)
        if qrec.get("ring_wire_bytes_quant") else None)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "grad_overlap_dp8.json"), "w") as fh:
            json.dump(record, fh, indent=1)
    return record


def flagship_7b_fit(out_dir: Optional[str] = None,
                    topology_name: str = "v5e:8x8",
                    hbm_bytes: int = V5E_HBM_BYTES,
                    variants=("zero3", "zero3_hpz8")) -> Dict[str, Any]:
    """AOT-compile Llama-2-7B ZeRO-3 (and +hpZ) training on v5e-64; report
    per-chip memory against the 16 GiB HBM budget."""
    from ..models import llama2_7b
    from ..parallel.topology import TopologyConfig

    cfg = llama2_7b()
    record: Dict[str, Any] = {
        "topology": topology_name,
        "model": "llama2_7b",
        "model_params": int(cfg.param_count())
        if hasattr(cfg, "param_count") else None,
        "hbm_bytes_per_chip": int(hbm_bytes),
    }
    all_variants = {
        "zero3": TopologyConfig(),
        # hpZ: params keep a secondary partition inside an 8-chip group
        # (one v5e host's worth of fast links) while master/opt shard dp=64
        "zero3_hpz8": TopologyConfig(hpz_shard=8),
    }
    for name in variants:
        topo_cfg = all_variants[name]
        engine, batch = build_abstract_engine(
            cfg,
            {"train_micro_batch_size_per_gpu": 1,
             "bf16": {"enabled": True},
             "optimizer": {"type": "adamw", "params": {"lr": 3e-4}},
             "zero_optimization": dict(
                 {"stage": 3, "overlap_comm": True,
                  "stage3_param_persistence_threshold": 0},
                 **({"zero_hpz_partition_size": 8}
                    if topo_cfg.hpz_shard > 1 else {})),
             "steps_per_print": 10 ** 9},
            topology_name=topology_name, topo_cfg=topo_cfg)
        compiled = engine.lower_train_step(batch)
        mem = _mem_record(compiled)
        mem["fits_hbm"] = bool(mem["peak_bytes_per_chip"] < hbm_bytes)
        record[name] = mem
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "flagship_7b_v5e64.json"), "w") as fh:
            json.dump(record, fh, indent=1)
    return record


def longcontext_fit(out_dir: Optional[str] = None,
                    topology_name: str = "v5e:8x8",
                    hbm_bytes: int = V5E_HBM_BYTES,
                    seq_len: int = 1 << 20,
                    sp: int = 64) -> Dict[str, Any]:
    """The Ulysses headline at TPU scale: >1M-token training step fits.

    Reference claim: Ulysses trains at >1M tokens on 64 GPUs
    (blogs/deepspeed-ulysses/README.md:78-79). Proof here: AOT-compile a
    Llama-2-7B-geometry training step at ``seq_len`` (default 1,048,576
    tokens) with ring-attention sequence parallelism over all 64 chips of
    a v5e:8x8 topology — ring attention is the TPU-idiomatic long-context
    superset (SURVEY §5: Ulysses all-to-all caps sp at num_heads; the
    ring caps at num chips) — under ZeRO-3 with model state sharded over
    the seq axis as the reference does (sp ranks are dp ranks to ZeRO,
    stage3.py:1181). Assert per-chip memory clears v5e HBM."""
    import dataclasses

    from ..models import llama2_7b
    from ..parallel.topology import TopologyConfig

    cfg = dataclasses.replace(
        llama2_7b(), max_seq_len=seq_len, seq_parallel=True,
        seq_parallel_impl="ring", remat=True,
        # blockwise ring steps: without inner chunks each step builds an
        # [H, S/sp, S/sp] f32 score block (32 GB at 1M/64) — see
        # ring_attention q_chunk/kv_chunk
        attn_block_q=1024, attn_block_kv=1024)
    record: Dict[str, Any] = {
        "topology": topology_name,
        "model": "llama2_7b-geometry",
        "seq_len": int(seq_len),
        "sequence_parallel": {"impl": "ring", "size": sp},
        "hbm_bytes_per_chip": int(hbm_bytes),
    }
    engine, batch = build_abstract_engine(
        cfg,
        {"train_micro_batch_size_per_gpu": 1,
         "bf16": {"enabled": True},
         "sequence_parallel_size": sp,
         "optimizer": {"type": "adamw", "params": {"lr": 3e-4}},
         "zero_optimization": {"stage": 3, "overlap_comm": True,
                               "stage3_param_persistence_threshold": 0},
         "steps_per_print": 10 ** 9},
        topology_name=topology_name, topo_cfg=TopologyConfig(seq=sp))
    compiled = engine.lower_train_step(batch)
    mem = _mem_record(compiled)
    mem["fits_hbm"] = bool(mem["peak_bytes_per_chip"] < hbm_bytes)
    record["zero3_ring_sp"] = mem
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "longcontext_1m_v5e64.json"),
                  "w") as fh:
            json.dump(record, fh, indent=1)
    return record


def serving_7b_fit(out_dir: Optional[str] = None,
                   topology_name: str = "v5e:2x2",
                   hbm_bytes: int = V5E_HBM_BYTES,
                   batch: int = 4, ctx: int = 2048,
                   block_size: int = 64) -> Dict[str, Any]:
    """Single-chip 7B serving fit: bf16 vs int8 weight-only quant.

    Llama-2-7B weights are ~12.6 GiB in bf16 — with a KV pool they do NOT
    fit one 16 GiB v5e chip; at int8 WOQ (v2 ragged engine quant_bits=8)
    they halve and serving fits. Proof: AOT-compile the v2 paged decode
    step (batch x 1 token against a ``batch * ctx`` KV pool) against a
    v5e topology with everything REPLICATED (the smallest describable
    slice is 2x2; fully-replicated shardings make per-chip bytes equal
    single-chip serving) and read per-chip bytes from the executable's
    memory analysis. The jnp gather path is compiled (the Pallas kernel
    needs a device for its lowering mode pick), so temp bytes are an
    UPPER bound — the DMA kernel's temps are strictly smaller."""
    import jax
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ..inference.quantization import quantize_params
    from ..inference.v2.paged_model import (init_paged_kv_cache,
                                            paged_decode)
    from ..models import TransformerLM, llama2_7b

    _require_cpu_backend()
    desc = topologies.get_topology_desc(topology_name, platform="tpu")
    mesh = Mesh(np.asarray(desc.devices).reshape(-1), ("chip",))
    repl = NamedSharding(mesh, P())

    cfg = llama2_7b()
    model = TransformerLM(cfg)
    import jax.numpy as jnp
    params_f = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    params_bf16 = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16), params_f)
    params_q8 = jax.eval_shape(
        lambda p: quantize_params(p, bits=8)[0], params_bf16)
    # int4 is omitted: the stack-based unpack materializes a 7B-scale
    # temp the compiler rejects; int8 is the fits-one-chip headline and
    # int4 correctness is covered at small scale (serve_pipeline example)

    record: Dict[str, Any] = {
        "topology": topology_name, "model": "llama2_7b",
        "batch": batch, "ctx": ctx,
        "hbm_bytes_per_chip": int(hbm_bytes),
    }
    sds = jax.ShapeDtypeStruct
    # (name, params, kv_quant, batch): int8 KV (~0.53x pool bytes) buys
    # double the batch in the freed headroom
    variants = (("bf16", params_bf16, False, batch),
                ("int8_woq", params_q8, False, batch),
                ("int8_woq_kvq8", params_q8, True, batch * 2))
    for name, params, kvq, b_n in variants:
        nb = b_n * (ctx // block_size) + 1
        MB = ctx // block_size
        cache = jax.eval_shape(
            lambda: init_paged_kv_cache(cfg, nb, block_size,
                                        jnp.bfloat16, kv_quant=kvq))
        toks, pos = sds((b_n,), jnp.int32), sds((b_n,), jnp.int32)
        bt = sds((b_n, MB), jnp.int32)
        active = sds((b_n,), jnp.bool_)
        record.setdefault("kv_pool_blocks", {})[name] = nb

        # paged_decode dequantizes WOQ leaves itself: non-layer params at
        # entry, each scanned layer inside the scan body
        def step(p, t, po, b, c, a):
            return paged_decode(cfg, p, t, po, b, c, a, block_size,
                                use_kernel=False)

        flat_in = jax.tree.map(lambda _: repl,
                               (params, toks, pos, bt, cache, active))
        record[name] = {"batch": b_n}
        try:
            compiled = jax.jit(step, in_shardings=flat_in,
                               donate_argnums=(4,)
                               ).lower(params, toks, pos, bt, cache,
                                       active).compile()
        except Exception as exc:
            # the TPU compiler enforces HBM itself: an over-capacity
            # program fails with RESOURCE_EXHAUSTED ("Used XG of YG
            # hbm") — record the compiler's own verdict
            msg = repr(exc)
            assert "RESOURCE_EXHAUSTED" in msg or "memory" in msg, msg
            record[name].update(fits_hbm=False,
                                compiler_error=msg[:300])
            continue
        mem = _mem_record(compiled)
        mem["fits_hbm"] = bool(mem["peak_bytes_per_chip"] < hbm_bytes)
        record[name].update(mem)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "serving_7b_v5e.json"), "w") as fh:
            json.dump(record, fh, indent=1)
    return record


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="artifacts")
    ap.add_argument("--skip-overlap", action="store_true")
    ap.add_argument("--skip-grad-overlap", action="store_true")
    ap.add_argument("--skip-7b", action="store_true")
    ap.add_argument("--skip-longcontext", action="store_true")
    ap.add_argument("--skip-serving", action="store_true")
    args = ap.parse_args(argv)
    if not args.skip_overlap:
        rec = overlap_dp8(out_dir=args.out)
        u = rec["stage3_unrolled"]
        print(json.dumps({"overlap_dp8": {
            "param_gather_exposed_fraction":
                u["param_gather_exposed_fraction"],
            "exposed_bytes_fraction": u["exposed_bytes_fraction"],
            "async_chains": u["async_chains"]}}))
    if not args.skip_grad_overlap:
        rec = grad_overlap_dp8(out_dir=args.out)
        print(json.dumps({"grad_overlap_dp8": {
            "exposed_collective_fraction":
                rec["exposed_collective_fraction"],
            "monolithic":
                rec["exposed_collective_fraction_monolithic"],
            "int8": rec["exposed_collective_fraction_int8"],
            "quant_wire_ratio": rec["quant_wire_ratio"],
            "buckets": rec["bucketed"].get(
                "bucket_plan", {}).get("num_buckets")}}))
    if not args.skip_7b:
        rec = flagship_7b_fit(out_dir=args.out)
        print(json.dumps({"flagship_7b_v5e64": {
            k: v["peak_gib_per_chip"] for k, v in rec.items()
            if isinstance(v, dict) and "peak_gib_per_chip" in v}}))
    if not args.skip_longcontext:
        rec = longcontext_fit(out_dir=args.out)
        print(json.dumps({"longcontext_1m_v5e64": {
            "peak_gib_per_chip":
                rec["zero3_ring_sp"]["peak_gib_per_chip"],
            "fits_hbm": rec["zero3_ring_sp"]["fits_hbm"]}}))
    if not args.skip_serving:
        rec = serving_7b_fit(out_dir=args.out)
        print(json.dumps({"serving_7b_v5e": {
            k: {"peak_gib_per_chip": v["peak_gib_per_chip"],
                "fits_hbm": v["fits_hbm"]}
            for k, v in rec.items()
            if isinstance(v, dict) and "peak_gib_per_chip" in v}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
