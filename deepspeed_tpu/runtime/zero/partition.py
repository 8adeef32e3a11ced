"""ZeRO partitioning as sharding specs.

TPU-native re-design of the reference's ZeRO optimizers:
  - stage 1/2: runtime/zero/stage_1_and_2.py:96 (optimizer-state (+grad)
    partitioning with bucketed reduce)
  - stage 3:   runtime/zero/stage3.py:72 + partition_parameters.py:723
    (parameter partitioning with allgather-on-use and trace-based prefetch)

The torch implementation is ~7,000 lines of hook machinery because eager
execution forces manual gather/release/prefetch. Under XLA the placement of
the STATE is a sharding spec: each state tensor gets a `PartitionSpec` that
puts its ZeRO shard on the data-parallel mesh axes, and the SPMD partitioner
inserts collectives that make the program correct —

  stage 1: optimizer state sharded  -> allgather of updated params after step
  stage 2: + gradients sharded      -> the gradients reduced into shards
  stage 3: + parameters sharded     -> the weights gathered in fwd/bwd (XLA's
           latency-hiding scheduler overlaps these with compute, replacing the
           reference's __allgather_stream / prefetch coordinator,
           stage3.py:1151, partitioned_param_coordinator.py:256)

Correct is not "the collectives the reference issues by hand". A spec says
where a tensor lies, not where it is used, and to the partitioner a ZeRO
shard is a tensor-parallel shard like any other: given a sharded weight as
a matmul's operand it may move the ACTIVATIONS to the shards instead of
the weight to the batch, whatever the bytes. It did so for the backward of
OPT-1.3B's down projection at dp 4 (an all-gather of the cotangent over
the global batch and an all-to-all back: 268 MB of activations a layer in
place of a 33 MB weight; PERF.md section 6, PR 51), and it reduces the
weight gradients as whole-leaf all-reduces sliced afterwards, not as
reduce-scatters. So stage 3 STATES the reference's allgather-on-use and
its reduce-scatter hook where a layer uses its weights:
`scanned_gather_on_use` below, a function with its own transpose that the
model applies inside its checkpointed layer body, so that no matmul of the
body sees a sharded weight.

Parameters smaller than `stage3_param_persistence_threshold` stay replicated,
mirroring the reference's persistent-param optimization
(parameter_offload.py persistence thresholds).
"""

import math
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ...parallel.topology import MeshTopology


def _numel(shape) -> int:
    return int(np.prod(shape)) if len(shape) else 1


def add_zero_axes(shape: Tuple[int, ...],
                  base_spec: Optional[P],
                  zero_axes: Tuple[str, ...],
                  zero_size: int,
                  threshold: int = 0,
                  axis_sizes: Optional[dict] = None) -> P:
    """Extend `base_spec` (TP placement) with the ZeRO axes on the best free dim.

    Picks the largest dimension that is (a) not already sharded by the base
    spec and (b) divisible by the ZeRO world size. Returns the base spec
    unchanged when nothing qualifies or the tensor is below the persistence
    threshold (small params stay replicated: cheaper than gathering).
    """
    base = tuple(base_spec) if base_spec is not None else ()
    base = base + (None,) * (len(shape) - len(base))
    # axes already used by the base (TP/EP/PP) spec cannot be reused: an
    # expert-sharded param's ZeRO shard spans only the remaining data axes
    used = set()
    for entry in base:
        for a in (entry if isinstance(entry, tuple) else (entry,)):
            if a is not None:
                used.add(a)
    free_axes = tuple(a for a in zero_axes if a not in used)
    if axis_sizes is not None:
        zero_size = 1
        for a in free_axes:
            zero_size *= axis_sizes[a]
    if not free_axes or zero_size <= 1:
        return P(*base)
    if threshold and _numel(shape) < threshold:
        return P(*base)
    # candidate dims: unsharded in base, divisible by zero_size
    candidates = [(d, shape[d]) for d in range(len(shape))
                  if base[d] in (None, ()) and shape[d] % zero_size == 0]
    if not candidates:
        return P(*base)
    dim = max(candidates, key=lambda t: t[1])[0]
    new = list(base)
    new[dim] = free_axes if len(free_axes) > 1 else free_axes[0]
    return P(*new)


@dataclass
class ZeroPlan:
    """Per-pytree sharding plan for one training state.

    Fields are pytrees of NamedSharding matching the params pytree structure.
    """

    stage: int
    param_sharding: Any   # compute params (fwd/bwd)
    grad_sharding: Any    # accumulated gradients
    master_sharding: Any  # fp32 master weights + optimizer moments
    base_sharding: Any    # the TP/EP placement alone: no ZeRO axis

    def shardings_for_opt_state(self, opt_state_template):
        """Optimizer moments mirror master-weight sharding, leaf-for-leaf."""
        # opt_state is {name: params-like pytree}; map each sub-tree.
        return jax.tree.map(
            lambda _: None, opt_state_template)  # placeholder; engine uses master_sharding per subtree


def build_zero_plan(topo: MeshTopology,
                    stage: int,
                    param_shapes,
                    base_specs=None,
                    persistence_threshold: int = 0,
                    secondary_axes=None,
                    include_seq_axis: bool = False) -> ZeroPlan:
    """Construct the sharding plan for a given ZeRO stage.

    `param_shapes`: pytree of jax.ShapeDtypeStruct (or arrays).
    `base_specs`: optional pytree of PartitionSpec carrying TP/EP placement
    (the reference takes TP from an external mpu, engine.py:94; here the model
    supplies specs and ZeRO composes with them).
    `secondary_axes`: ZeRO++ hpZ (reference partition_parameters.py:639
    secondary tensors): stage-3 COMPUTE params shard over these axes only
    (the within-group sub-axis) while master/opt/grads keep the full
    `dp_axes` shard — the fwd/bwd gather then stays inside the group.
    `include_seq_axis`: shard model state over the "seq" axis too — the
    reference's Ulysses x ZeRO composition (sp ranks ARE dp ranks to ZeRO,
    stage3.py:1181); engine enables it for the standard auto-SPMD step.
    """
    mesh = topo.mesh
    zero_axes = (topo.zero_shard_axes if include_seq_axis
                 else topo.dp_axes)
    zero_size = topo.dp_world_size
    if include_seq_axis:
        zero_size *= topo.axis_size("seq")

    if base_specs is None:
        base_specs = jax.tree.map(lambda _: P(), param_shapes)

    def spec_of(threshold, axes=None):
        axes = axes if axes is not None else zero_axes

        def fn(leaf, base):
            shape = leaf.shape if hasattr(leaf, "shape") else tuple(leaf)
            return add_zero_axes(shape, base, axes, zero_size,
                                 threshold=threshold, axis_sizes=topo.sizes)
        return fn

    # Optimizer-state/master/grad shards always partition (no threshold);
    # stage-3 *compute* params below the persistence threshold stay gathered
    # (parameter_offload.py persistent params) — their master is still sharded.
    opt_specs = jax.tree.map(spec_of(0), param_shapes, base_specs)
    param3_specs = jax.tree.map(
        spec_of(persistence_threshold, axes=secondary_axes), param_shapes,
        base_specs)

    def ns(spec_tree):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                            is_leaf=lambda x: isinstance(x, P))

    base_ns = ns(base_specs)
    opt_ns = ns(opt_specs)

    if stage <= 0:
        return ZeroPlan(stage, base_ns, base_ns, base_ns, base_ns)
    if stage == 1:
        # grads replicated (all-reduced), optimizer state sharded
        return ZeroPlan(stage, base_ns, base_ns, opt_ns, base_ns)
    if stage == 2:
        # grads reduce-scattered into shards, params still gathered
        return ZeroPlan(stage, base_ns, opt_ns, opt_ns, base_ns)
    # stage 3: params sharded too (modulo persistence threshold)
    return ZeroPlan(stage, ns(param3_specs), opt_ns, opt_ns, base_ns)


def _gather_leaf(use: NamedSharding, shard: NamedSharding):
    """One leaf's gather-on-use: forward, the weight constrained to the
    placement its layer computes with; backward, its cotangent to the
    leaf's gradient shard. The scopes are the two collective phases
    ``utils/xla_profile.scope_phase`` already has."""

    @jax.custom_vjp
    def gather(w):
        with jax.named_scope("param_gather"):
            return jax.lax.with_sharding_constraint(w, use)

    def fwd(w):
        return gather(w), None

    def bwd(_, dw):
        with jax.named_scope("grad_reduce"):
            return (jax.lax.with_sharding_constraint(dw, shard),)

    gather.defvjp(fwd, bwd)
    return gather


def scanned_gather_on_use(plan: ZeroPlan, params, key: str):
    """Stage 3's allgather-on-use for a stack of layers that a model
    scans: ``(fn, leaves, bytes)``. ``params`` is the compute tree
    (arrays or shapes) and ``fn`` maps ONE layer's slice of
    ``params[key]`` (the scan's ``xs``) to the same tree with every leaf
    that ZeRO sharded passed through `_gather_leaf`: gathered to its base
    spec (the plan's spec less the ZeRO axes: replicated on a pure-dp
    mesh, the TP / EP placement kept where there is one) where the layer
    uses it, its cotangent constrained to the leaf's gradient spec. It
    belongs INSIDE the remat boundary of the scanned body: outside it the
    gathered weights are the checkpoint's saved inputs. Whether a leaf
    takes it is read from its own specs: one whose compute spec IS its
    base spec (stages 0-2, a gather world of 1, a leaf under the
    persistence threshold) is left as it is, and when none takes it
    ``fn`` is None. ``leaves`` and ``bytes`` count the leaves that do and
    what one layer of them holds gathered, over the whole mesh's view (the
    base spec's own shards are not divided out)."""
    if not (isinstance(plan.param_sharding, dict)
            and key in plan.param_sharding):
        return None, 0, 0

    def one_layer(sh):
        # the scan slices the stack's leading dimension away
        return NamedSharding(sh.mesh, P(*tuple(sh.spec)[1:]))

    def entries(sh, ndim):
        spec = tuple(sh.spec)
        return spec + (None,) * (ndim - len(spec))

    stack, treedef = jax.tree.flatten(params[key])
    fns, nbytes = [], 0
    for a, param, base, grad in zip(
            stack, *(treedef.flatten_up_to(sh[key]) for sh in (
                plan.param_sharding, plan.base_sharding,
                plan.grad_sharding))):
        if entries(param, a.ndim) == entries(base, a.ndim):
            fns.append(None)
            continue
        fns.append(_gather_leaf(one_layer(base), one_layer(grad)))
        nbytes += _numel(a.shape[1:]) * np.dtype(a.dtype).itemsize
    leaves = len(fns) - fns.count(None)
    if not leaves:
        return None, 0, 0

    def gather(lp):
        return treedef.unflatten(
            [w if f is None else f(w)
             for f, w in zip(fns, treedef.flatten_up_to(lp))])

    return gather, leaves, nbytes


def estimate_zero_memory(param_count: int, stage: int, dp: int,
                         bytes_per_param_low: int = 2) -> dict:
    """Model-state memory per device, the reference's 4+K breakdown
    (ZeRO paper / docs/_pages/training.md:67): 2-byte params, 2-byte grads,
    12-byte fp32 master+moments for Adam."""
    p, g, o = 2, 2, 12
    if stage >= 1:
        o /= dp
    if stage >= 2:
        g /= dp
    if stage >= 3:
        p /= dp
    total = param_count * (p + g + o)
    return {"params_bytes": param_count * p, "grads_bytes": param_count * g,
            "optstate_bytes": param_count * o, "total_bytes": total}
