"""Collective-communication micro-benchmark (ds_bench).

Reference: bin/ds_bench + benchmarks/communication/ — sweep message sizes
over allreduce/allgather/reduce-scatter/all-to-all and report latency plus
algorithmic and bus bandwidth (utils/comms_logging.py:34 calc_bw_log math).

CLI: python -m deepspeed_tpu.benchmarks.comm_bench [--ops all_reduce ...]
     [--maxsize 2**26] [--trials 20] [--mesh-axis data]
"""

import argparse
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from ..comm.quantized import shard_map_unchecked
from ..utils.comms_logging import calc_bw_log


def _collective_fn(op: str, axis: str):
    if op == "all_reduce":
        return lambda x: jax.lax.psum(x, axis)
    if op == "all_gather":
        return lambda x: jax.lax.all_gather(x, axis, tiled=True)
    if op == "reduce_scatter":
        return lambda x: jax.lax.psum_scatter(x, axis, tiled=True)
    if op == "all_to_all":
        from ..comm.quantized import _axis_size
        return lambda x: jax.lax.all_to_all(
            x.reshape(_axis_size(axis), -1), axis, 0, 0,
            tiled=False).reshape(-1)
    raise ValueError(f"unknown op {op}")


def run_op(op: str, size_bytes: int, trials: int = 20, warmups: int = 3,
           axis: str = "data", dtype=jnp.bfloat16) -> Dict[str, float]:
    from jax.sharding import Mesh, PartitionSpec as P

    n = jax.device_count()
    mesh = Mesh(np.asarray(jax.devices()), (axis,))
    elems = max(n * 8, size_bytes // np.dtype(dtype).itemsize)
    elems = (elems // (n * 8)) * (n * 8)
    x = jnp.ones((elems,), dtype)
    # all_reduce/all_gather produce identical (replicated) per-device results
    # -> P(); reduce_scatter/all_to_all produce per-device distinct shards
    # -> P(axis), so the declared global shape matches the op's semantics.
    out_spec = P(axis) if op in ("reduce_scatter", "all_to_all") else P()
    fn = shard_map_unchecked(_collective_fn(op, axis), mesh,
                             in_specs=P(axis), out_specs=out_spec)
    jfn = jax.jit(fn)
    for _ in range(warmups):
        jax.block_until_ready(jfn(x))
    t0 = time.perf_counter()
    for _ in range(trials):
        out = jfn(x)
    jax.block_until_ready(out)
    lat = (time.perf_counter() - t0) / trials
    algbw, busbw = calc_bw_log(op, elems * np.dtype(dtype).itemsize, lat, n)
    return {"op": op, "bytes": elems * np.dtype(dtype).itemsize,
            "latency_us": lat * 1e6, "algbw_gbps": algbw, "busbw_gbps": busbw}


def run_bucket_sweep(total_pw: int = 22, bucket_pws=(16, 18, 20, 22),
                     trials: int = 10, warmups: int = 2,
                     axis: str = "data", n_leaves: int = 32,
                     dtype=jnp.float32, quantized: str = None,
                     quant_block: int = 2048,
                     hierarchy: int = 0) -> List[Dict]:
    """Sweep ``reduce_bucket_size`` over a synthetic gradient tree and
    report achieved bandwidth per bucket layout.

    Runs the REAL bucketed reducer (runtime/grad_overlap.py: plan build +
    ring collectives inside shard_map) over ``n_leaves`` equal leaves
    totalling 2^total_pw bytes, once per bucket cap. Small caps mean many
    latency-bound collectives; large caps mean fewer, bandwidth-bound ones
    but a later start for the first reduce — this sweep is how a deployment
    picks the knob for its interconnect.

    ``quantized`` ("int8"|"fp8") ALSO runs each cap through the
    block-quantized ring (error-feedback state threaded, zeros) and adds
    the quantized step time, per-device wire bytes of both transports and
    their ratio — the bytes-on-wire story the ``quantized_reduce`` knob
    buys on this workload.

    ``hierarchy`` > 1 (with ``quantized``) runs the quantized leg
    through the two-level hierarchical rings
    (``zero_optimization.quantized_reduce_hierarchy`` — ``hierarchy``
    hosts, intra-host fp32 / inter-host quantized) and ASSERTS the
    inter-host wire-bytes ratio over the flat fp32 ring clears the
    quantization win (``comm.quantized.hier_wire_bytes``).
    """
    from jax.sharding import Mesh, PartitionSpec as P

    from ..runtime.grad_overlap import (ALL_REDUCE, GradUnit,
                                        apply_bucketed_reduction,
                                        build_bucket_plan,
                                        quant_reduce_layout,
                                        ring_wire_bytes)
    from ..utils.comms_logging import calc_bw_log

    n = jax.device_count()
    mesh = Mesh(np.asarray(jax.devices()), (axis,))
    itemsize = np.dtype(dtype).itemsize
    leaf_elems = max((1 << total_pw) // itemsize // n_leaves // n * n, n)
    leaves = [jnp.ones((leaf_elems,), dtype) for _ in range(n_leaves)]
    total_bytes = leaf_elems * itemsize * n_leaves
    rows: List[Dict] = []

    def timed(fn, *args):
        for _ in range(warmups):
            jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(trials):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / trials

    for pw in bucket_pws:
        cap = max((1 << pw) // itemsize, 1)
        units = [GradUnit(i, -1, leaf_elems, f"leaf{i}", ALL_REDUCE)
                 for i in range(n_leaves)]
        plan = build_bucket_plan(units, reduce_bucket_size=cap,
                                 allgather_bucket_size=cap)

        def body(*ls):
            out = apply_bucketed_reduction(
                list(ls), plan, [0] * n_leaves, (axis,), (), n, 1,
                axis_sizes={axis: n})
            return tuple(out)

        fn = jax.jit(shard_map_unchecked(
            body, mesh, in_specs=(P(),) * n_leaves,
            out_specs=(P(),) * n_leaves))
        lat = timed(fn, *leaves)
        algbw, busbw = calc_bw_log("all_reduce", total_bytes, lat, n)
        row = {"bucket_bytes": cap * itemsize,
               "num_buckets": plan.num_buckets,
               "total_bytes": total_bytes,
               "latency_us": lat * 1e6,
               "algbw_gbps": algbw, "busbw_gbps": busbw}
        if quantized:
            layout = quant_reduce_layout(plan, (axis,), n, {axis: n})
            qspecs = {k: {kk: P(*((axis,) + (None,) * len(shape)))
                          for kk, shape in v.items()}
                      for k, v in layout.items()}
            qzero = {k: {kk: jnp.zeros((n,) + shape, jnp.float32)
                         for kk, shape in v.items()}
                     for k, v in layout.items()}

            def body_q(qstate, *ls):
                qin = {k: {kk: a[0] for kk, a in v.items()}
                       for k, v in qstate.items()}
                out, qerr = apply_bucketed_reduction(
                    list(ls), plan, [0] * n_leaves, (axis,), (), n, 1,
                    axis_sizes={axis: n}, quant_reduce=quantized,
                    quant_reduce_block=quant_block,
                    quant_reduce_groups=hierarchy, qstate=qin)
                return tuple(out), {k: {kk: a[None] for kk, a in v.items()}
                                    for k, v in qerr.items()}

            fn_q = jax.jit(shard_map_unchecked(
                body_q, mesh, in_specs=(qspecs,) + (P(),) * n_leaves,
                out_specs=((P(),) * n_leaves, qspecs)))
            lat_q = timed(fn_q, qzero, *leaves)
            wb = ring_wire_bytes(plan, n)
            wb_q = ring_wire_bytes(plan, n, quantized=True,
                                   quant_block=quant_block)
            row.update({
                "quantized": quantized,
                "quant_latency_us": lat_q * 1e6,
                "wire_bytes_fp32": wb,
                "wire_bytes_quant": wb_q,
                "wire_ratio": round(wb / wb_q, 3) if wb_q else None})
            if hierarchy > 1:
                from ..comm.quantized import hier_wire_bytes
                # per-bucket message size on the ring (rows of M elems)
                hier = {"inter_fp32_flat": 0, "inter_quant": 0}
                for b in plan.buckets:
                    M = sum(-(-plan.units[u].numel // n)
                            for u in b.indices)
                    hwb = hier_wire_bytes(M, n, hierarchy,
                                          block=quant_block)
                    # ALL_REDUCE buckets pay RS + AG phases
                    hier["inter_fp32_flat"] += \
                        2 * hwb["inter_bytes_fp32_flat"]
                    hier["inter_quant"] += 2 * hwb["inter_bytes_quant"]
                ratio = (hier["inter_fp32_flat"] / hier["inter_quant"]
                         if hier["inter_quant"] else float("inf"))
                assert ratio >= 3.5, (
                    f"hierarchical ring inter-host wire ratio {ratio:.2f}"
                    f" lost the quantization win (bucket "
                    f"{cap * itemsize}B)")
                row.update({
                    "hierarchy": hierarchy,
                    "inter_wire_bytes_fp32_flat": hier["inter_fp32_flat"],
                    "inter_wire_bytes_quant": hier["inter_quant"],
                    "inter_wire_ratio": round(ratio, 3)})
        rows.append(row)
    return rows


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--ops", nargs="+", default=["all_reduce", "all_gather",
                                                "reduce_scatter",
                                                "all_to_all"])
    p.add_argument("--maxsize", type=int, default=24,
                   help="max message size as a power of two (default 2^24)")
    p.add_argument("--minsize", type=int, default=12)
    p.add_argument("--trials", type=int, default=20)
    p.add_argument("--mesh-axis", default="data")
    p.add_argument("--bucket-sweep", action="store_true",
                   help="sweep grad-reduction bucket sizes (the "
                        "reduce_bucket_size knob) instead of raw ops")
    p.add_argument("--sweep-total", type=int, default=22,
                   help="total synthetic grad bytes as a power of two")
    p.add_argument("--sweep-buckets", type=int, nargs="+",
                   default=[16, 18, 20, 22],
                   help="bucket caps to sweep, powers of two (bytes)")
    p.add_argument("--quantized", nargs="?", const="int8",
                   choices=["int8", "fp8"], default=None,
                   help="with --bucket-sweep: also run each cap through "
                        "the block-quantized ring reducer "
                        "(zero_optimization.quantized_reduce transport) "
                        "and report wire bytes + step time vs the fp32 "
                        "ring")
    p.add_argument("--quant-block", type=int, default=2048)
    p.add_argument("--hierarchy", type=int, default=0,
                   help="with --bucket-sweep --quantized: run the "
                        "two-level hierarchical ring (this many hosts, "
                        "intra-host fp32 / inter-host quantized — the "
                        "quantized_reduce_hierarchy knob) and assert "
                        "the inter-host wire-bytes win")
    args = p.parse_args(argv)
    if args.bucket_sweep:
        print(f"devices: {jax.device_count()} x "
              f"{getattr(jax.devices()[0], 'device_kind', '?')}")
        qcols = (f" {'qlat(us)':>10} {'wireMB':>8} {'qwireMB':>8} "
                 f"{'ratio':>6}" if args.quantized else "")
        print(f"{'bucket':>12} {'n_buckets':>10} {'lat(us)':>10} "
              f"{'algbw(GB/s)':>12} {'busbw(GB/s)':>12}" + qcols)
        rows = run_bucket_sweep(total_pw=args.sweep_total,
                                bucket_pws=tuple(args.sweep_buckets),
                                trials=args.trials, axis=args.mesh_axis,
                                quantized=args.quantized,
                                quant_block=args.quant_block,
                                hierarchy=args.hierarchy)
        for r in rows:
            extra = ""
            if args.quantized:
                extra = (f" {r['quant_latency_us']:>10.1f} "
                         f"{r['wire_bytes_fp32'] / 2 ** 20:>8.2f} "
                         f"{r['wire_bytes_quant'] / 2 ** 20:>8.2f} "
                         f"{r['wire_ratio'] or 0.0:>6.2f}")
            print(f"{r['bucket_bytes']:>12} {r['num_buckets']:>10} "
                  f"{r['latency_us']:>10.1f} {r['algbw_gbps']:>12.2f} "
                  f"{r['busbw_gbps']:>12.2f}" + extra)
        return rows
    print(f"devices: {jax.device_count()} x "
          f"{getattr(jax.devices()[0], 'device_kind', '?')}")
    header = f"{'op':>16} {'size':>12} {'lat(us)':>10} " \
             f"{'algbw(GB/s)':>12} {'busbw(GB/s)':>12}"
    print(header)
    rows: List[Dict] = []
    for op in args.ops:
        for pw in range(args.minsize, args.maxsize + 1, 2):
            r = run_op(op, 1 << pw, trials=args.trials, axis=args.mesh_axis)
            rows.append(r)
            print(f"{r['op']:>16} {r['bytes']:>12} {r['latency_us']:>10.1f} "
                  f"{r['algbw_gbps']:>12.2f} {r['busbw_gbps']:>12.2f}")
    return rows


if __name__ == "__main__":
    main()
