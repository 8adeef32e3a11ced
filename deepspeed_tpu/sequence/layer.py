"""Sequence parallelism (Ulysses) + sharded attention dispatch.

TPU-native analogue of the reference's DeepSpeed-Ulysses
(deepspeed/sequence/layer.py: _SeqAllToAll :15, DistributedAttention :37):
activations are sequence-sharded between layers; around attention an
all-to-all re-partitions [*, heads, S/sp, D] -> [*, heads/sp, S, D] so each
device computes full-sequence attention for a subset of heads, then the
reverse all-to-all restores sequence sharding.

Because Pallas kernels are opaque to GSPMD, attention always runs inside a
`jax.shard_map` region: data parallelism maps the batch dim, tensor
parallelism maps the head dim over "model", and (when enabled) Ulysses adds
the "seq" axis all-to-alls inside the region. XLA lowers the all-to-alls onto
ICI (§2.4 of SURVEY.md).
"""

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..parallel.topology import MODEL_AXIS, SEQ_AXIS, MeshTopology


def seq_all_to_all(x, axis_name: str, scatter_dim: int, gather_dim: int):
    """The Ulysses primitive (reference sequence/layer.py:15 _SeqAllToAll):
    scatter `scatter_dim` across the axis, gather `gather_dim`."""
    return lax.all_to_all(x, axis_name, split_axis=scatter_dim,
                          concat_axis=gather_dim, tiled=True)


def _inner_attention(q, k, v, causal, use_flash, block_q, block_kv, sp_size,
                     impl="ulysses", scale=None):
    """Runs on local shards inside shard_map. q/k/v: [B_l, H_l, S_l, D]."""
    from ..ops.flash_attention import flash_attention, mha_reference

    if sp_size > 1 and impl == "ring":
        from .ring_attention import ring_attention
        return checkpoint_name(
            ring_attention(q, k, v, SEQ_AXIS, causal=causal, scale=scale,
                           q_chunk=block_q, kv_chunk=block_kv), "attn_out")

    if sp_size > 1:
        # Ulysses: heads -> heads/sp, seq/sp -> seq
        nh, nkv = q.shape[1], k.shape[1]
        if nkv < sp_size:
            rep = sp_size // nkv
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
        q = seq_all_to_all(q, SEQ_AXIS, scatter_dim=1, gather_dim=2)
        k = seq_all_to_all(k, SEQ_AXIS, scatter_dim=1, gather_dim=2)
        v = seq_all_to_all(v, SEQ_AXIS, scatter_dim=1, gather_dim=2)

    # the output carries the name "attn_out" for selective checkpointing
    # (runtime/activation_checkpointing: save_attn) exactly once: the flash
    # kernel names its own output and row statistics where its backward
    # reads them (ops/flash_attention._flash_core_fwd), so a second name
    # here would save the same array twice
    s = q.shape[2]
    if use_flash and s % 128 == 0 and k.shape[2] % 128 == 0:
        o = flash_attention(q, k, v, causal=causal, scale=scale,
                            block_q=block_q or None, block_kv=block_kv or None)
    else:
        o = checkpoint_name(mha_reference(q, k, v, causal=causal, scale=scale),
                            "attn_out")

    if sp_size > 1:
        o = seq_all_to_all(o, SEQ_AXIS, scatter_dim=2, gather_dim=1)
    return o


def sharded_attention(q, k, v, topo: Optional[MeshTopology], causal: bool = True,
                      use_flash: bool = True, block_q: int = 128,
                      block_kv: int = 128, impl: str = "ulysses", scale=None):
    """Attention over [B, H, S, D] with mesh-aware partitioning.

    Without a topology (single device / replicated), calls the kernel
    directly. With one, wraps in shard_map: batch over data axes, heads over
    "model", sequence over "seq". `impl` selects the sequence-parallel
    strategy when the "seq" axis is >1: "ulysses" (all-to-all head
    repartition, reference sequence/layer.py) or "ring" (blockwise ring
    attention, ring_attention.py).
    """
    # the mesh axes an enclosing shard_map already made manual (none
    # outside any shard_map)
    context_mesh = jax.sharding.get_abstract_mesh()
    manual = frozenset(context_mesh.manual_axes)
    if topo is None or manual >= frozenset(topo.mesh.axis_names):
        # no mesh, or already under a FULLY-manual shard_map (the pipeline
        # region or the bucketed gradient program on a pure-dp mesh):
        # arrays are local shards, call the kernel directly
        return _inner_attention(q, k, v, causal, use_flash, block_q, block_kv,
                                1, scale=scale)
    sp = topo.axis_size(SEQ_AXIS)
    # inside a partial-manual region (manual dp, auto tp/sp — the bucketed
    # gradient program) the batch is already local over the manual axes;
    # the nested shard_map maps only what is still auto
    dp_axes = tuple(a for a in topo.batch_axes if a not in manual)
    dp_total = 1
    for a in dp_axes:
        dp_total *= topo.axis_size(a)
    if dp_total > 1 and q.shape[0] % dp_total == 0:
        batch_spec = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    else:
        batch_spec = None  # batch replicated (e.g. single long sequence)
    tp = topo.axis_size(MODEL_AXIS)
    head_spec = MODEL_AXIS if tp > 1 else None
    qkv_spec = P(batch_spec, head_spec, SEQ_AXIS if sp > 1 else None, None)

    fn = partial(_inner_attention, causal=causal, use_flash=use_flash,
                 block_q=block_q, block_kv=block_kv, sp_size=sp, impl=impl,
                 scale=scale)
    if manual:
        # a nested shard_map must be handed the CONTEXT's abstract mesh
        # (its axis types record which axes are already manual), not the
        # concrete all-auto topo.mesh
        mesh = context_mesh
        axis_names = frozenset(mesh.axis_names) - manual
    else:
        mesh, axis_names = topo.mesh, None
    # replication checking off: pallas_call outputs don't carry vma metadata
    from ..comm.quantized import shard_map_unchecked
    return shard_map_unchecked(fn, mesh=mesh,
                               in_specs=(qkv_spec, qkv_spec, qkv_spec),
                               out_specs=qkv_spec,
                               axis_names=axis_names)(q, k, v)


def ulysses_attention(q, k, v, causal: bool = True, use_flash: bool = True,
                      block_q: int = 128, block_kv: int = 128,
                      topo: Optional[MeshTopology] = None):
    """Explicit-SP entry used by models with cfg.seq_parallel=True."""
    return sharded_attention(q, k, v, topo, causal=causal, use_flash=use_flash,
                             block_q=block_q, block_kv=block_kv)


class DistributedAttention:
    """Reference-parity wrapper (sequence/layer.py:37): wraps a local
    attention callable with the Ulysses scatter/gather all-to-alls.

    local_attn receives [B, H/sp, S, D] tensors and full sequence.
    """

    def __init__(self, local_attn: Callable, sequence_process_group=SEQ_AXIS,
                 scatter_idx: int = 1, gather_idx: int = 2):
        self.local_attn = local_attn
        self.axis = sequence_process_group
        self.scatter_idx = scatter_idx
        self.gather_idx = gather_idx

    def __call__(self, query, key, value, *args, **kwargs):
        q = seq_all_to_all(query, self.axis, self.scatter_idx, self.gather_idx)
        k = seq_all_to_all(key, self.axis, self.scatter_idx, self.gather_idx)
        v = seq_all_to_all(value, self.axis, self.scatter_idx, self.gather_idx)
        out = self.local_attn(q, k, v, *args, **kwargs)
        return seq_all_to_all(out, self.axis, self.gather_idx, self.scatter_idx)
