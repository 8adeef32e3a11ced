"""Overlapped bucketed gradient reduction.

The seed engine let GSPMD insert the data-parallel gradient reduction
wherever it liked — in practice one monolithic all-reduce/reduce-scatter
AFTER the full backward, fully exposed (a round-5 chip capture read
``exposed_collective_fraction: 1.0`` while the ZeRO-3 param gathers were 97%
overlapped). DeepCompile (arXiv:2504.09983) shows compiler-scheduled overlap
of exactly this collective is the dominant lever for distributed training
step time; the reference runtime buys the same overlap by hand with
bucketed reducers on a side stream (stage_1_and_2.py ``reduce_bucket_size``
ipg buckets, stage3.py:1135 ``__reduce_and_partition_ipg_grads``).

Here the training step instead *issues the reduction itself*, per bucket,
inside a ``shard_map`` over the data-parallel axes:

  * the gradient pytree is partitioned into size-capped **buckets**
    (``zero_optimization.reduce_bucket_size`` / ``allgather_bucket_size``,
    counted in elements like the reference), layer-ordered REVERSED so the
    buckets holding the last-produced grads (the loss-head end — backward
    emits those first) are ready, and reduce, first;
  * each bucket is ONE fused collective over a flat concatenation of its
    leaves — ``psum`` (grads that stay replicated: ZeRO-0/1) or a tiled
    ``reduce-scatter`` (ZeRO-2/3 dim-sharded grads), int8 all-to-all under
    ZeRO++ qgZ;
  * the last gradient-accumulation microbatch runs INLINE after the
    ``lax.scan`` over the first gas-1, so its per-layer backward is visible
    to XLA's latency-hiding scheduler alongside the bucket collectives —
    async collective fusion floats bucket k's reduce into the remaining
    backward and into bucket j's optimizer math instead of serializing the
    whole tree behind one fused reduce.

Numerics are bit-identical to a monolithic reduction by construction: a
bucket's collective computes exactly the same per-element cross-device sums
as one tree-wide collective (concatenation never mixes elements), and the
microbatch accumulation order is unchanged (scan over gas-1 then one inline
add is the same add sequence the full scan performs). Bucketing changes
*scheduling*, not math.

ZeRO-3 dim-sharded parameters are handled by ``make_zero3_gather``'s VJP
(the cotangent leaves the backward already reduce-scattered, per leaf, at
the exact point the reference's grad hooks would fire) — those leaves are
recorded on the plan as ``vjp`` and excluded from bucketing; only their
hpZ cross-group means and the replicated remainder ride buckets.
"""

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..comm.quantized import (all_to_all_quant_reduce, make_zero3_gather,
                              quant_wire_bytes, ring_all_gather_hier,
                              ring_all_gather_quant,
                              ring_reduce_scatter_hier,
                              ring_reduce_scatter_quant, shard_map_unchecked)

# leaf reduction categories
VJP = "vjp"                      # reduced by the stage-3 gather's VJP
REDUCE_SCATTER = "reduce_scatter"  # dim-sharded grad: bucketed reduce-scatter
ALL_REDUCE = "all_reduce"        # replicated grad: bucketed psum (mean)
CROSS_GROUP = "cross_group"      # hpZ: cross-group mean of a VJP-reduced leaf


@dataclass(frozen=True)
class GradUnit:
    """One reducible unit: a whole grad leaf, or one layer-slice of a
    stacked layer leaf (``layer >= 0`` — scanned models store layer params
    as ONE [L, ...] leaf; slicing restores per-layer granularity so a
    layer's bucket can reduce while earlier layers are still in backward).
    """

    leaf: int          # flat leaf index in the grad pytree
    layer: int         # -1 = whole leaf; else slice index along dim 0
    numel: int
    name: str
    kind: str


@dataclass(frozen=True)
class GradBucket:
    """One fused collective: the units (by position in plan.units) it
    carries."""

    kind: str
    indices: Tuple[int, ...]
    numel: int
    nbytes: int


@dataclass
class GradBucketPlan:
    """Static partition of the gradient pytree into collective buckets.

    The plan is pure Python config baked into the traced program: one
    program per layout (changing ``reduce_bucket_size`` retraces; repeated
    steps with the same layout reuse ONE executable).
    """

    buckets: Tuple[GradBucket, ...]
    units: Tuple[GradUnit, ...]
    vjp_leaves: Tuple[str, ...]
    reduce_bucket_numel: int
    allreduce_bucket_numel: int

    @property
    def num_buckets(self) -> int:
        return len(self.buckets)

    @property
    def max_bucket_bytes(self) -> int:
        return max((b.nbytes for b in self.buckets), default=0)

    @property
    def total_bucket_bytes(self) -> int:
        return sum(b.nbytes for b in self.buckets)

    def layout_key(self) -> Tuple:
        """Hashable identity of the traced collective layout."""
        return tuple(
            (b.kind, tuple((self.units[u].leaf, self.units[u].layer)
                           for u in b.indices))
            for b in self.buckets)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "reduce_bucket_size": self.reduce_bucket_numel,
            "allgather_bucket_size": self.allreduce_bucket_numel,
            "num_buckets": self.num_buckets,
            "max_bucket_bytes": self.max_bucket_bytes,
            "total_bucket_bytes": self.total_bucket_bytes,
            "vjp_leaves": list(self.vjp_leaves),
            "buckets": [{
                "kind": b.kind,
                "numel": b.numel,
                "bytes": b.nbytes,
                "leaves": [self.units[u].name for u in b.indices],
            } for b in self.buckets],
        }

    def summary(self) -> str:
        lines = [f"grad buckets: {self.num_buckets} "
                 f"(cap {self.reduce_bucket_numel} elems, "
                 f"largest {self.max_bucket_bytes / 2 ** 20:.1f} MiB)"]
        for b in self.buckets:
            lines.append(f"  [{b.kind:<14}] {b.numel:>10} elems x "
                         f"{len(b.indices)} units")
        if self.vjp_leaves:
            lines.append(f"  [vjp (stage-3) ] {len(self.vjp_leaves)} leaves "
                         f"reduced inside backward")
        return "\n".join(lines)


def order_units(names: Sequence[str], numels: Sequence[int],
                kinds: Sequence[str], layers: Sequence[int],
                stacked: Sequence[bool]) -> List[GradUnit]:
    """Production-ordered reducible units: reversed tree order (backward
    emits the loss-head end of the tree first), with the stacked layer
    block expanded LAYER-major in reversed layer order — layer L-1's
    backward completes first, so its units bucket together and their
    collective becomes issuable while layers L-2..0 are still computing
    (the reference reduces "last produced first" the same way).
    ``layers[i]`` is the slice count for leaf i (0 = not sliceable)."""
    units: List[GradUnit] = []
    n = len(names)
    stack_leaves = [i for i in range(n) if stacked[i]]
    emitted_stack = False
    for i in reversed(range(n)):
        if stacked[i]:
            if emitted_stack:
                continue
            emitted_stack = True
            depth = max(layers[j] for j in stack_leaves)
            for layer in reversed(range(depth)):
                for j in reversed(stack_leaves):
                    if layer < layers[j]:
                        units.append(GradUnit(
                            j, layer, numels[j] // layers[j],
                            f"{names[j]}[{layer}]", kinds[j]))
        else:
            units.append(GradUnit(i, -1, numels[i], names[i], kinds[i]))
    return units


def build_bucket_plan(units: Sequence[GradUnit],
                      reduce_bucket_size: int,
                      allgather_bucket_size: int,
                      grad_itemsize: int = 4) -> GradBucketPlan:
    """Greedy size-capped packing in the given (production) order.

    ``reduce_bucket_size`` caps reduce-scatter buckets;
    ``min(reduce_bucket_size, allgather_bucket_size)`` caps all-reduce
    buckets (an all-reduce is a reduce + the implicit allgather of the
    result, so BOTH knobs bound it — this is where the config keys the
    seed parsed but never consumed become live). Caps are element counts,
    matching the reference's ``reduce_bucket_size`` semantics. A single
    unit larger than its cap gets a bucket of its own (the reference
    overflows its ipg bucket the same way).
    """
    if reduce_bucket_size <= 0 or allgather_bucket_size <= 0:
        raise ValueError(
            f"bucket sizes must be > 0 (reduce_bucket_size="
            f"{reduce_bucket_size}, allgather_bucket_size="
            f"{allgather_bucket_size})")
    caps = {REDUCE_SCATTER: int(reduce_bucket_size),
            ALL_REDUCE: min(int(reduce_bucket_size),
                            int(allgather_bucket_size)),
            CROSS_GROUP: int(reduce_bucket_size)}
    open_buckets: Dict[str, List[int]] = {}
    buckets: List[GradBucket] = []
    vjp: List[str] = []

    def close(kind):
        idxs = open_buckets.pop(kind, None)
        if idxs:
            numel = sum(units[u].numel for u in idxs)
            buckets.append(GradBucket(kind, tuple(idxs), numel,
                                      numel * grad_itemsize))

    for u, unit in enumerate(units):
        if unit.kind == VJP:
            vjp.append(unit.name)
            continue
        cur = open_buckets.setdefault(unit.kind, [])
        cur_numel = sum(units[j].numel for j in cur)
        if cur and cur_numel + unit.numel > caps[unit.kind]:
            close(unit.kind)
            open_buckets[unit.kind] = [u]
        else:
            cur.append(u)
    for kind in list(open_buckets):
        close(kind)
    return GradBucketPlan(tuple(buckets), tuple(units), tuple(vjp),
                          int(reduce_bucket_size),
                          min(int(reduce_bucket_size),
                              int(allgather_bucket_size)))


def _leaf_paths(tree) -> List[str]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [jax.tree_util.keystr(path) for path, _ in flat]


# Why a hand-spelled ring and not jax.lax.psum/psum_scatter: on the TPU
# backend those lower to SYNCHRONOUS all-reduce/reduce-scatter HLO — the
# all-reduce combiner re-merges every bucket into one monolithic op, async
# collective fusion never chains reduce-type collectives (measured on v5e
# AOT compiles, even with the fuse_reduce_scatter flag), and a sync
# collective blocks the TensorCore. ``collective-permute``, by contrast,
# ALWAYS lowers to async start/done pairs the latency-hiding scheduler can
# pull compute between. So each bucket's reduction is the classic NCCL
# ring, spelled in ppermute hops with a local add per hop — the same
# primitive structure ring_attention uses to hide its KV exchange.


def _ring_reduce_rows(buf, axis: str, world: int):
    """[world, M] local partials -> flat [M]: device r ends with row r
    fully summed. world-1 async ppermute hops, one add per hop; the
    summation order per element is the fixed ring order (device r+1, r+2,
    ..., r), identical for every bucket layout — bucketed and monolithic
    reduction stay bit-identical."""
    if world == 1:
        return buf[0]
    perm = [(i, (i + 1) % world) for i in range(world)]
    idx = jax.lax.axis_index(axis)

    def take(b):
        return jax.lax.dynamic_index_in_dim(buf, b % world, 0,
                                            keepdims=False)

    acc = take(idx - 1)
    for s in range(world - 1):
        acc = jax.lax.ppermute(acc, axis, perm)
        acc = acc + take(idx - s - 2)
    return acc


def _ring_all_gather_rows(block, axis: str, world: int):
    """Per-device [M] block -> [world, M] full tensor (row r = device r's
    block) via world-1 async ppermute hops."""
    if world == 1:
        return block[None]
    perm = [(i, (i + 1) % world) for i in range(world)]
    idx = jax.lax.axis_index(axis)
    out = jnp.zeros((world,) + block.shape, block.dtype)
    out = jax.lax.dynamic_update_index_in_dim(out, block, idx, 0)
    cur = block
    for s in range(world - 1):
        cur = jax.lax.ppermute(cur, axis, perm)
        out = jax.lax.dynamic_update_index_in_dim(
            out, cur, (idx - s - 1) % world, 0)
    return out


def _unit_rows(flat, world: int):
    """Unit-flat [n] -> [world, ceil(n/world)] ring rows. The element->row
    assignment depends only on the UNIT (zero-padded to a world multiple),
    never on the bucket it rides in — the per-element ring summation order
    is therefore identical for every bucket layout, which is what makes
    bucketed and monolithic reduction bit-identical."""
    n = flat.shape[0]
    m = -(-n // world)
    if m * world != n:
        flat = jnp.pad(flat, (0, m * world - n))
    return flat.reshape(world, m)


def _rows_unit(rows_flat, numel: int):
    """Inverse of ``_unit_rows`` after the all-gather: [world * m] -> [n]."""
    return rows_flat[:numel]


def _reduce_axes(buf_2d, axes: Tuple[str, ...], sizes: Dict[str, int],
                 ring: bool = True):
    """Bucket reduce-scatter over possibly-multiple mesh axes. Single axis
    takes the async ring; multi-axis (MiCS/hpZ shard groups) and
    partial-manual programs (``ring=False`` — the SPMD partitioner rejects
    ppermute + dynamic indexing when auto axes remain) fall back to
    sequential fused scatters like ``reduce_scatter_leaf``."""
    live = [a for a in axes if sizes[a] > 1]
    if len(live) == 1 and ring:
        return _ring_reduce_rows(buf_2d, live[0], buf_2d.shape[0])
    out = buf_2d
    for a in live:
        out = jax.lax.psum_scatter(out, a, scatter_dimension=0, tiled=True)
    return out.reshape(-1)


def quant_reduce_layout(plan: GradBucketPlan, axes: Tuple[str, ...],
                        world: int, axis_sizes: Dict[str, int],
                        ring: bool = True,
                        a2a_quantized: bool = False) -> Dict[str, Dict]:
    """Which buckets the quantized ring transport carries, and the row
    shapes of their error-feedback residuals.

    Returns ``{"b<i>": {"rs": (world, M)[, "ag": (M,)]}}`` for every
    bucket that rides the single-axis ppermute ring: ALL_REDUCE buckets
    carry both phases' residuals (quantized reduce-scatter + quantized
    all-gather of the result), REDUCE_SCATTER buckets the reduce phase
    only. CROSS_GROUP (hpZ) and ZeRO++-a2a (``a2a_quantized``) buckets
    keep their existing transports. Empty when the mesh has no single
    live data-parallel axis (the ring precondition).
    """
    live = [a for a in axes if axis_sizes.get(a, 2) > 1]
    if len(live) != 1 or not ring or world <= 1:
        return {}
    out: Dict[str, Dict] = {}
    for i, b in enumerate(plan.buckets):
        if b.kind == ALL_REDUCE:
            M = sum(-(-plan.units[u].numel // world) for u in b.indices)
            out[f"b{i}"] = {"rs": (world, M), "ag": (M,)}
        elif b.kind == REDUCE_SCATTER and not a2a_quantized:
            out[f"b{i}"] = {"rs": (world, b.numel // world)}
    return out


def ring_wire_bytes(plan: GradBucketPlan, world: int,
                    quantized: bool = False,
                    quant_block: int = 2048) -> int:
    """Per-device bytes the bucket ring transports ship per step
    (world-1 hops per phase; ALL_REDUCE buckets pay reduce-scatter AND
    all-gather phases; vjp/CROSS_GROUP leaves are excluded — they do not
    ride the ring). The fp32/quantized ratio of this number is what
    test_quantized_reduce.py holds at >= 3.5x."""
    if world <= 1:
        return 0
    hops = world - 1
    total = 0
    for b in plan.buckets:
        if b.kind == REDUCE_SCATTER:
            M, phases = b.numel // world, 1
        elif b.kind == ALL_REDUCE:
            M = sum(-(-plan.units[u].numel // world) for u in b.indices)
            phases = 2
        else:
            continue
        per_hop = quant_wire_bytes(M, quant_block) if quantized else M * 4
        total += phases * hops * per_hop
    return total


def apply_bucketed_reduction(grads_flat: List[Any],
                             plan: GradBucketPlan,
                             grad_dims: Sequence[int],
                             axes: Tuple[str, ...],
                             cross_axes: Tuple[str, ...],
                             world: int,
                             cross_world: int,
                             axis_sizes: Optional[Dict[str, int]] = None,
                             quantized: bool = False,
                             quant_block: int = 2048,
                             quant_bits: int = 8,
                             ring: bool = True,
                             quant_reduce: Optional[str] = None,
                             quant_reduce_block: int = 2048,
                             quant_reduce_groups: int = 0,
                             qstate: Optional[Dict[str, Dict]] = None,
                             loss_scale=None):
    """Issue one fused collective per bucket over the flat leaf list.

    Must run inside shard_map over ``axes``. Every bucket is independent in
    the dataflow graph, so XLA's scheduler is free to start a bucket's
    collective the moment its leaves' cotangents exist and to run other
    buckets' compute (optimizer math, remaining backward) under it.
    Per-element sums are identical to per-leaf (and to monolithic)
    reduction: the bucket layout only changes how elements are packed into
    messages, never which values are summed.

    ``quant_reduce`` ("int8"|"fp8") reroutes the ring buckets through the
    block-quantized wire (comm/quantized.ring_*_quant) with per-bucket
    error feedback: ``qstate`` holds last step's residuals (the layout of
    :func:`quant_reduce_layout`), which are injected into the partials
    before transport; the call then returns ``(out, new_qstate)`` with
    this step's residuals. Residuals are stored UNSCALED (divided by
    ``loss_scale``) so fp16 dynamic-scale changes cannot stretch a stale
    residual. ``quant_reduce_groups`` > 1 routes the ring buckets
    through the two-level hierarchical rings instead (intra-host fp32 /
    inter-host quantized — ``zero_optimization.
    quantized_reduce_hierarchy``); the EF state layout is unchanged.
    """
    axis_sizes = axis_sizes or {}
    hier = int(quant_reduce_groups or 0) > 1

    def _ring_rs_quant(buf_q, ax, denom_q):
        if hier:
            return ring_reduce_scatter_hier(
                buf_q, ax, denom_q, quant_reduce_groups,
                block=quant_reduce_block, mode=quant_reduce)
        return ring_reduce_scatter_quant(
            buf_q, ax, denom_q, block=quant_reduce_block,
            mode=quant_reduce)

    def _ring_ag_quant(row_q, ax, denom_q):
        if hier:
            return ring_all_gather_hier(
                row_q, ax, denom_q, quant_reduce_groups,
                block=quant_reduce_block, mode=quant_reduce)
        return ring_all_gather_quant(
            row_q, ax, denom_q, block=quant_reduce_block,
            mode=quant_reduce)
    # accept the config-domain literal "off" (truthy) as disabled, so the
    # return arity matches what a caller forwarding the raw knob expects
    if quant_reduce == "off":
        quant_reduce = None
    out: List[Any] = list(grads_flat)
    slices: Dict[int, Dict[int, Any]] = {}  # leaf -> layer -> reduced slice
    qlayout = (quant_reduce_layout(plan, axes, world, axis_sizes,
                                   ring=ring, a2a_quantized=quantized)
               if quant_reduce else {})
    new_qstate: Dict[str, Dict] = {}
    ls = jnp.asarray(1.0, jnp.float32) if loss_scale is None else loss_scale

    def unit_value(u: GradUnit):
        g = grads_flat[u.leaf]
        return g if u.layer < 0 else g[u.layer]

    def unit_dim(u: GradUnit) -> int:
        d = grad_dims[u.leaf]
        return d if u.layer < 0 else d - 1

    def deliver(u: GradUnit, val):
        if u.layer < 0:
            out[u.leaf] = val
        else:
            slices.setdefault(u.leaf, {})[u.layer] = val

    for bi, b in enumerate(plan.buckets):
        us = [plan.units[i] for i in b.indices]
        key = f"b{bi}"
        if b.kind in (ALL_REDUCE, CROSS_GROUP):
            red_axes = axes if b.kind == ALL_REDUCE else cross_axes
            denom = world if b.kind == ALL_REDUCE else cross_world
            live = [a for a in red_axes if axis_sizes.get(a, 2) > 1]
            if denom > 1 and len(live) == 1 and ring:
                # ring all-reduce = ring reduce-scatter + ring all-gather
                # over per-UNIT row blocks (layout-invariant element order)
                parts = [_unit_rows(unit_value(u).reshape(-1), denom)
                         for u in us]
                buf = parts[0] if len(parts) == 1 else \
                    jnp.concatenate(parts, axis=1)
                if key in qlayout:
                    res = qstate[key]
                    buf = buf + res["rs"] * ls
                    red_sum, rs_err = _ring_rs_quant(buf, live[0],
                                                     denom)
                    red = red_sum / denom + res["ag"] * ls
                    full, ag_err = _ring_ag_quant(red, live[0], denom)
                    new_qstate[key] = {"rs": rs_err / ls, "ag": ag_err / ls}
                else:
                    red = _ring_reduce_rows(buf, live[0], denom) / denom
                    full = _ring_all_gather_rows(red, live[0], denom)
                off = 0
                for u, part in zip(us, parts):
                    m = part.shape[1]
                    piece = full[:, off:off + m].reshape(-1)
                    off += m
                    deliver(u, _rows_unit(piece, u.numel).reshape(
                        unit_value(u).shape))
                continue
            parts = [unit_value(u).reshape(-1) for u in us]
            buf = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
            if denom > 1:
                buf = jax.lax.psum(buf, red_axes) / denom
            off = 0
            for u in us:
                deliver(u, buf[off:off + u.numel].reshape(
                    unit_value(u).shape))
                off += u.numel
        else:  # REDUCE_SCATTER
            parts, metas = [], []
            for u in us:
                g, d = unit_value(u), unit_dim(u)
                moved = jnp.moveaxis(g, d, 0)
                parts.append(moved.reshape(world, -1))
                metas.append((u, d, moved.shape))
            buf = parts[0] if len(parts) == 1 else \
                jnp.concatenate(parts, axis=1)
            if key in qlayout:
                live = [a for a in axes if axis_sizes.get(a, 2) > 1]
                res = qstate[key]
                buf = buf + res["rs"] * ls
                row, rs_err = _ring_rs_quant(buf, live[0], world)
                buf = row / world
                new_qstate[key] = {"rs": rs_err / ls}
            elif quantized:
                buf = all_to_all_quant_reduce(buf, 0, axes, block=quant_block,
                                              bits=quant_bits,
                                              mean=True).reshape(-1)
            elif world > 1:
                buf = _reduce_axes(buf, axes, axis_sizes, ring=ring) / world
            else:
                buf = buf.reshape(-1)
            off = 0
            for u, d, mshape in metas:
                cols = u.numel // world
                piece = buf[off:off + cols]
                off += cols
                shard = piece.reshape((mshape[0] // world,) + mshape[1:])
                deliver(u, jnp.moveaxis(shard, 0, d))
    # restack layer-sliced leaves (slice-of-stack and stack-of-slice cancel
    # in XLA; only the collectives' granularity actually changes)
    for leaf, per_layer in slices.items():
        out[leaf] = jnp.stack([per_layer[l]
                               for l in range(len(per_layer))], axis=0)
    if quant_reduce:
        return out, new_qstate
    return out


# ----------------------------------------------------------------------
# Engine integration
# ----------------------------------------------------------------------
# compositions the manual shard_map program cannot express (or that the
# quantized-collective predecessor already rejected): these raise under
# overlap_grad_reduce="bucketed" and silently keep the legacy GSPMD path
# under "auto".
_HARD = "hard"
_SOFT = "soft"


def overlap_blockers(engine, forced: bool) -> List[Tuple[str, str]]:
    """(severity, reason) list; empty means the manual path can run."""
    topo = engine.topology
    out: List[Tuple[str, str]] = []
    for ax in ("expert", "pipe"):
        if topo.axis_size(ax) > 1:
            out.append((_HARD, f"'{ax}' mesh axis > 1 (needs a manual "
                               f"program for that axis inside shard_map)"))
    if engine.param_offload:
        out.append((_HARD, "offload_param streams the layer stack from "
                           "host memory"))
    if engine.compression_spec is not None:
        out.append((_HARD, "compression_training rewrites params per step "
                           "inside the auto-SPMD loss"))
    if not forced:
        # conservative auto gate: anything beyond a pure data-parallel
        # mesh keeps the legacy GSPMD reduction unless explicitly forced
        if not engine.config.zero_optimization.overlap_comm:
            out.append((_SOFT, "overlap_comm is disabled"))
        if engine.zero_stage == 3:
            # the manual program gathers every leaf before the model runs:
            # for a scanned stack that is the WHOLE stack ahead of the
            # scan (OPT-1.3B at dp 4, compiled for a v5e:2x2: all-gather
            # bf16[96,2048,2048] ..., 13.0 GB a chip against the GSPMD
            # step's 7.6; PERF.md section 6, PR 51), and the stacked
            # gradients are reduce-scattered after it: ZeRO-3's memory
            # given up and nothing left to overlap. The GSPMD step gathers
            # a layer where the layer uses it (zero/partition.
            # scanned_gather_on_use). Manual stage 3 stays opt-in
            # ('bucketed') / ZeRO++-only.
            out.append((_SOFT, "the manual program gathers the whole "
                               "layer stack ahead of the scan"))
        for ax in ("model", "seq", "shard"):
            if topo.axis_size(ax) > 1:
                out.append((_SOFT, f"'{ax}' mesh axis > 1"))
        dp = int(np.prod([topo.sizes[a] for a in topo.dp_axes]))
        if dp <= 1:
            out.append((_SOFT, "data-parallel world is 1 (nothing to "
                               "reduce)"))
        mcfg = getattr(engine.model, "cfg", None)
        if getattr(mcfg, "moe_num_experts", 0) or engine.config.moe.enabled:
            out.append((_SOFT, "MoE capacity routing depends on the global "
                               "batch view"))
    return out


def resolve_overlap_mode(engine, use_zeropp: bool) -> str:
    """'bucketed' | 'off' for this engine build.

    ``zero_optimization.overlap_grad_reduce``: 'auto' engages the bucketed
    program on pure-dp meshes with dp > 1; 'bucketed' forces it (hard
    blockers raise); 'off' keeps the legacy GSPMD reduction. ZeRO++
    (qwZ/qgZ) always runs the manual program — its quantized collectives
    cannot be compiler-inserted — and gains the bucketing.
    """
    from .config import ConfigError
    mode = engine.config.zero_optimization.overlap_grad_reduce
    if use_zeropp:
        return "bucketed"
    if mode == "off":
        return "off"
    if engine.topology.axis_size("pipe") > 1 and mode != "bucketed":
        # the 1F1B program owns its own gradient computation; forced mode
        # falls through to the hard-blocker ConfigError below
        return "off"
    blockers = overlap_blockers(engine, forced=(mode == "bucketed"))
    if mode == "bucketed":
        hard = [r for s, r in blockers if s == _HARD]
        if hard:
            raise ConfigError(
                "zero_optimization.overlap_grad_reduce='bucketed' is not "
                "supported here: " + "; ".join(hard))
        return "bucketed"
    return "off" if blockers else "bucketed"


def make_overlapped_grad_fn(engine, zpp_w: bool, zpp_g: bool):
    """The manual gradient program: shard_map over the DP axes, per-micro
    autodiff with explicit stage-3 gathers, local accumulation across
    gradient-accumulation microbatches (scan over the first gas-1, last one
    inline so its backward overlaps the reduction), then per-bucket
    collectives. Returns ``(grad_fn, plan, qtemplate)``:
    ``grad_fn(params, rng, batch, scale) -> (grads, loss)`` (plus a
    threaded error-feedback state when ``zero_optimization.
    quantized_reduce`` is on: ``grad_fn(params, rng, batch, scale,
    qstate) -> (grads, loss, new_qstate)``); grads are summed over
    microbatches and MEANED over the DP world (the engine divides by gas
    only, like the legacy manual path). ``qtemplate`` describes the
    error-feedback state the engine must allocate —
    ``{"b<i>": {"rs"|"ag": (global_shape, PartitionSpec)}}`` — or None
    when quantized_reduce is off.

    Generalizes the ZeRO++ qwZ/qgZ program the seed shipped: with both
    quant flags off this is the plain bucketed-overlap path; with them on,
    gathers ride int8 transport (qwZ) and bucket reduces ride the int8
    all-to-all (qgZ) — now fused per bucket instead of per leaf. The
    ``quantized_reduce`` knob instead quantizes the ring transport itself
    (per-hop int8/fp8 wire with per-bucket error feedback) — the
    EQuARX-style path for stages 0-2.
    """
    mesh = engine.mesh
    topo = engine.topology
    axes = topo.dp_axes
    axis_sizes = topo.sizes
    plan_z = engine.zero_plan
    stage3 = engine.zero_stage == 3
    model = engine.model
    gas = engine.gas
    zc = engine.config.zero_optimization
    hpz = stage3 and topo.hpz_enabled
    gather_axes = topo.secondary_axes if hpz else axes
    cross_group_axes = tuple(a for a in axes if a not in gather_axes)
    world = int(np.prod([axis_sizes[a] for a in axes]))
    cross_world = int(np.prod([axis_sizes[a] for a in cross_group_axes])) \
        if cross_group_axes else 1

    param_specs = jax.tree.map(lambda ns: ns.spec, plan_z.param_sharding)
    grad_specs = jax.tree.map(lambda ns: ns.spec, plan_z.grad_sharding)

    def dim_of(spec):
        # -1 sentinel (None collapses pytree structure)
        for i, e in enumerate(spec):
            entries = e if isinstance(e, tuple) else (e,)
            if any(a in axes for a in entries if a is not None):
                return i
        return -1

    param_dims = jax.tree.map(dim_of, param_specs)
    grad_dims = jax.tree.map(dim_of, grad_specs)
    identity = lambda x: x  # noqa: E731
    gather_fns = jax.tree.map(
        lambda d: (make_zero3_gather(d, gather_axes, fwd_quantized=zpp_w,
                                     bwd_quantized=zpp_g)
                   if stage3 and d >= 0 else identity),
        param_dims)

    # --- bucket plan over the flat grad leaves ------------------------
    shapes = engine._param_shapes
    names = _leaf_paths(shapes)
    leaf_shapes = [tuple(l.shape) for l in jax.tree.leaves(shapes)]
    numels = [int(np.prod(s)) if s else 1 for s in leaf_shapes]
    pd_flat = jax.tree.leaves(param_dims)
    gd_flat = jax.tree.leaves(grad_dims)

    def kind_of(pd, gd):
        # pd >= 0 MUST be checked before gd < 0: under hpZ a dim can divide
        # the small group but not the full world (pd >= 0, gd < 0), and its
        # cotangent was already reduce-scattered over the shard axis by the
        # gather's VJP — a psum over that axis would average different
        # shard halves into corrupt gradients
        if stage3 and pd >= 0:
            return CROSS_GROUP if (hpz and cross_group_axes) else VJP
        if gd < 0:
            return ALL_REDUCE
        return REDUCE_SCATTER

    kinds = [kind_of(pd, gd) for pd, gd in zip(pd_flat, gd_flat)]
    # hpZ cross-group leaves live secondary-SHARDED inside the program
    # (the gather's VJP already reduce-scattered them over the group), so
    # their bucket units carry the shard numel, not the full-leaf numel
    gather_world = int(np.prod([axis_sizes[a] for a in gather_axes]))
    numels = [n // gather_world if k == CROSS_GROUP else n
              for n, k in zip(numels, kinds)]

    # Layer slicing: scanned models hold layer params as ONE stacked
    # [L, ...] leaf, which would force every layer's gradient into the
    # same post-backward bucket. When the layer loop is fully unrolled
    # (the grads of layer l exist before the stack is assembled), slice
    # stacked leaves per layer so a deep layer's bucket can reduce WHILE
    # shallower layers are still in backward — DeepCompile's
    # reduction-interleaving, recovered at the bucket-plan level.
    stack_keys = tuple(getattr(model, "param_offload_keys", ()) or ())
    unroll = max(int(getattr(getattr(model, "cfg", None), "scan_unroll", 1)
                     or 1),
                 int(getattr(model, "scan_unroll_hint", 1) or 1))

    def sliceable(i):
        if kinds[i] in (VJP, CROSS_GROUP):
            return False
        sh = leaf_shapes[i]
        if len(sh) < 2 or sh[0] < 2 or unroll < sh[0]:
            return False
        if not any(f"['{k}']" in names[i] for k in stack_keys):
            return False
        # slicing removes dim 0; a leaf sharded ON dim 0 cannot slice
        if kinds[i] == REDUCE_SCATTER and gd_flat[i] == 0:
            return False
        return True

    stacked = [sliceable(i) for i in range(len(names))]
    layer_counts = [leaf_shapes[i][0] if stacked[i] else 0
                    for i in range(len(names))]
    units = order_units(names, numels, kinds, layer_counts, stacked)
    plan = build_bucket_plan(units, zc.reduce_bucket_size,
                             zc.allgather_bucket_size)

    def linear_index():
        idx = jnp.asarray(0, jnp.int32)
        for a in axes:
            idx = idx * axis_sizes[a] + jax.lax.axis_index(a)
        return idx

    def _split_loss_aux(out):
        if isinstance(out, tuple) and len(out) == 2:
            return out[0], out[1]
        return out, {}

    def body(params_l, rng, batch_l, scale, qstate):
        def apply_model(pshards, micro, sub):
            pf = (jax.tree.map(lambda f, p: f(p), gather_fns, pshards)
                  if stage3 else pshards)
            out = model.apply(pf, micro, train=True, rng=sub)
            loss, _aux = _split_loss_aux(out)
            loss = loss.astype(jnp.float32)
            return loss * scale, loss

        def micro_step(grads_acc, rng, micro):
            rng, sub = jax.random.split(rng)
            sub = jax.random.fold_in(sub, linear_index())
            (_, loss), g = jax.value_and_grad(
                apply_model, has_aux=True)(params_l, micro, sub)
            with jax.named_scope("grad_reduce"):
                grads_acc = jax.tree.map(
                    lambda a, x: a + x.astype(jnp.float32), grads_acc, g)
            return grads_acc, rng, loss

        with jax.named_scope("grad_reduce"):
            grads0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params_l)

        def scan_fn(carry, micro):
            acc, rng = carry
            acc, rng, loss = micro_step(acc, rng, micro)
            return (acc, rng), loss

        if inline_last:
            # last microbatch INLINE: its per-layer backward shares the
            # scheduling window with the bucket collectives below (inside
            # a scan the whole gradient only exists when the loop op
            # completes). The accumulation order is the same add sequence
            # the full scan performs — numerics unchanged.
            if gas > 1:
                head = jax.tree.map(lambda x: x[:-1], batch_l)
                (acc, rng), head_losses = jax.lax.scan(
                    scan_fn, (grads0, rng), head)
            else:
                acc, head_losses = grads0, None
            last = jax.tree.map(lambda x: x[-1], batch_l)
            acc, rng, last_loss = micro_step(acc, rng, last)
            losses = (last_loss[None] if head_losses is None
                      else jnp.concatenate([head_losses, last_loss[None]]))
        else:
            # partial-manual programs (auto tp/sp axes): the SPMD
            # partitioner rejects the scan-free inline backward
            # (IsManualSubgroup check), so every microbatch stays in the
            # scan as the ZeRO++ predecessor did
            (acc, rng), losses = jax.lax.scan(scan_fn, (grads0, rng),
                                              batch_l)

        flat, treedef = jax.tree_util.tree_flatten(acc)
        with jax.named_scope("grad_reduce"):
            if use_qr:
                # local residual rows ride shard_map with a leading
                # sharded dim of 1 (global dim0 = world); squeeze in,
                # unsqueeze out
                qin = {k: {kk: a[0] for kk, a in v.items()}
                       for k, v in qstate.items()}
                flat, qerr = apply_bucketed_reduction(
                    flat, plan, gd_flat, axes, cross_group_axes, world,
                    cross_world, axis_sizes=axis_sizes, quantized=zpp_g,
                    ring=not tp, quant_reduce=qr_mode,
                    quant_reduce_block=qr_block,
                    quant_reduce_groups=qr_groups, qstate=qin,
                    loss_scale=scale)
                qout = {k: {kk: a[None] for kk, a in v.items()}
                        for k, v in qerr.items()}
            else:
                flat = apply_bucketed_reduction(
                    flat, plan, gd_flat, axes, cross_group_axes, world,
                    cross_world, axis_sizes=axis_sizes, quantized=zpp_g,
                    ring=not tp)
                qout = qstate
        grads = jax.tree_util.tree_unflatten(treedef, flat)
        loss = jax.lax.pmean(jnp.mean(losses), axes)
        return grads, loss, qout

    # grads of hpZ-sharded params leave the program secondary-sharded
    out_grad_specs = grad_specs
    if hpz:
        out_grad_specs = jax.tree.map(
            lambda gs, ps, pd: ps if pd >= 0 else gs,
            grad_specs, param_specs, param_dims)

    # tensor/sequence parallelism ride the AUTO axes: the program is
    # manual over the DP axes only, and specs mention only those (GSPMD
    # keeps the "model"/"seq"-axis collectives inside model.apply)
    tp = (topo.axis_size("model") > 1 or topo.axis_size("seq") > 1)
    inline_last = not tp
    manual = tuple(axes)

    def strip_auto(spec):
        if not tp:
            return spec
        out = []
        for e in spec:
            ents = e if isinstance(e, tuple) else (e,)
            kept = tuple(a for a in ents if a in manual)
            out.append(kept if len(kept) > 1 else
                       (kept[0] if kept else None))
        return P(*out)

    if tp:
        param_specs_in = jax.tree.map(strip_auto, param_specs)
        out_grad_specs = jax.tree.map(strip_auto, out_grad_specs)
    else:
        param_specs_in = param_specs

    # --- quantized ring transport (zero_optimization.quantized_reduce):
    # per-hop int8/fp8 wire over the same ppermute ring, with per-bucket
    # error-feedback residuals threaded through the program
    qr_mode = getattr(zc, "quantized_reduce", "off")
    qr_block = int(getattr(zc, "quant_block", 2048))
    qr_groups = int(getattr(zc, "quantized_reduce_hierarchy", 0) or 0)
    # inert without a ring to quantize (the engine logs and drops the
    # knob at dp=1; this guard keeps direct callers consistent)
    use_qr = qr_mode not in (None, "off") and world > 1
    qtemplate = None
    if use_qr:
        from .config import ConfigError
        if tp:
            raise ConfigError(
                "zero_optimization.quantized_reduce does not compose with "
                "tensor/sequence parallelism: the quantized ring needs the "
                "fully-manual data-parallel program")
        live = [a for a in axes if axis_sizes[a] > 1]
        if len(live) > 1:
            raise ConfigError(
                "zero_optimization.quantized_reduce needs a single live "
                f"data-parallel mesh axis for the ring transport (got "
                f"{live})")
        if qr_groups > 1 and world % qr_groups != 0:
            raise ConfigError(
                f"zero_optimization.quantized_reduce_hierarchy="
                f"{qr_groups} must divide the data-parallel world "
                f"({world}): the two-level ring lays the ring out as "
                f"hosts x devices-per-host")
        qlayout = quant_reduce_layout(plan, axes, world, axis_sizes,
                                      ring=True, a2a_quantized=zpp_g)
        qdim0 = manual if len(manual) > 1 else manual[0]
        qtemplate = {
            key: {kk: ((world,) + shape,
                       P(*((qdim0,) + (None,) * len(shape))))
                  for kk, shape in shapes.items()}
            for key, shapes in qlayout.items()}

    bt = topo.batch_axes
    if use_qr:
        qspecs = {k: {kk: spec for kk, (_, spec) in v.items()}
                  for k, v in qtemplate.items()}
        fn = shard_map_unchecked(
            body, mesh=mesh,
            in_specs=(param_specs_in, P(), P(None, bt), P(), qspecs),
            out_specs=(out_grad_specs, P(), qspecs),
            axis_names=None)
        return fn, plan, qtemplate

    def body4(params_l, rng, batch_l, scale):
        return body(params_l, rng, batch_l, scale, {})[:2]

    fn = shard_map_unchecked(
        body4, mesh=mesh,
        in_specs=(param_specs_in, P(), P(None, bt), P()),
        out_specs=(out_grad_specs, P()),
        axis_names=manual if tp else None)
    return fn, plan, None
