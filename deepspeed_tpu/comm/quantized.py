"""Quantized collectives for ZeRO++ (qwZ / qgZ).

TPU-native equivalent of the reference's ZeRO++ communication reducers:
  * qwZ — quantized weight all-gather: int8 blockwise-quantized parameter
    shards are gathered and dequantized on arrival (reference
    partition_parameters.py:1094 all_gather_coalesced quantized path +
    csrc/quantization/swizzled_quantize.cu).
  * qgZ — quantized gradient reduce: gradients are int8-quantized and
    exchanged with all-to-all, then dequantized and averaged locally, giving
    reduce-scatter semantics at a quarter of the bf16 all-to-all volume
    (reference runtime/comm/coalesced_collectives.py:31
    all_to_all_quant_reduce + csrc/quantization/quant_reduce.cu).

All functions are designed to run inside ``shard_map`` over the ZeRO mesh
axes: the caller passes the axis name(s) and the dimension the leaf shards
on; the (de)quantization is plain jnp so XLA fuses it into the collective's
producer/consumer — the role the hand-written CUDA kernels play on GPU.
"""

from typing import Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.quantizer import _blocked as quantizer_blocked
from ..ops.quantizer import quantize_symmetric

AxisNames = Union[str, Tuple[str, ...]]

# fp8 e4m3 wire format: same 1 byte/element as int8, but the exponent
# absorbs per-element dynamic range so block outliers clip less
FP8_MAX = 448.0  # largest finite float8_e4m3fn


def shard_map_unchecked(f, mesh, in_specs, out_specs, axis_names=None):
    """shard_map with the replication checker off: quantized collectives mix
    value-changing ops (round) with collectives, which the static
    varying-mesh-axes analysis cannot see through.

    axis_names: manual axes subset (partial-manual shard_map) — axes NOT
    listed stay in auto/GSPMD mode, so e.g. tensor parallelism keeps its
    compiler-inserted collectives inside the manual-DP program. None/empty
    means fully manual.
    """
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    kw = {"axis_names": frozenset(axis_names)} if axis_names else {}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False, **kw)


def _axis_size(axes: AxisNames) -> int:
    """Static size of the named axes inside shard_map."""
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for a in axes:
        size = size * int(jax.lax.axis_size(a))
    return size


def _chunked_quantize(x: jnp.ndarray, n: int, block: int, bits: int):
    """Split x's leading dim into n chunks and quantize each independently
    (per-chunk blocks so the all-to-all can route whole chunks).
    Returns (q [n, nb, block], scales [n, nb, 1], chunk_shape)."""
    chunk = x.reshape((n, -1) + x.shape[1:])
    chunk_shape = chunk.shape[1:]
    flat = chunk.reshape(n, -1)
    q, scale = jax.vmap(
        lambda row: quantize_symmetric(row, block=block, bits=bits))(flat)
    return q, scale, chunk_shape


def _dequantize_chunks(q, scale, chunk_shape, dtype):
    n = q.shape[0]
    vals = q.astype(jnp.float32) * scale  # [n, nb, block]
    flat = vals.reshape(n, -1)
    numel = int(np.prod(chunk_shape))
    return flat[:, :numel].reshape((n,) + tuple(chunk_shape)).astype(dtype)


def quantized_all_gather(shard: jnp.ndarray, dim: int, axes: AxisNames,
                         block: int = 2048, bits: int = 8,
                         dtype=None) -> jnp.ndarray:
    """qwZ: gather a parameter sharded on `dim` over `axes`, communicating
    int8 + per-block scales instead of the full-precision values.

    Must run inside shard_map; `shard` is the device-local shard.
    """
    dtype = dtype or shard.dtype
    moved = jnp.moveaxis(shard, dim, 0)
    q, scale = quantize_symmetric(moved, block=block, bits=bits)
    qg = jax.lax.all_gather(q, axes)        # [n, nb, block]
    sg = jax.lax.all_gather(scale, axes)    # [n, nb, 1]
    full = _dequantize_chunks(qg, sg, moved.shape, dtype)
    # [n, d_local, ...] -> [n * d_local, ...] -> original dim order
    full = full.reshape((-1,) + full.shape[2:])
    return jnp.moveaxis(full, 0, dim)


def all_to_all_quant_reduce(grad: jnp.ndarray, dim: int, axes: AxisNames,
                            block: int = 2048, bits: int = 8,
                            mean: bool = True) -> jnp.ndarray:
    """qgZ: reduce-scatter `grad` along `dim` over `axes` with int8 transport.

    Each device quantizes its full gradient split into world-size chunks,
    all-to-alls the chunks (every device receives its own partition from all
    peers), dequantizes and averages. Returns the device-local partition
    (grad.shape with dim divided by the axis size). Must run inside shard_map.
    """
    if isinstance(axes, str):
        axes = (axes,)
    n = _axis_size(axes)
    moved = jnp.moveaxis(grad, dim, 0)
    q, scale, chunk_shape = _chunked_quantize(moved, n, block, bits)
    # Route chunk i to device i (XLA lowers the multi-axis all-to-all
    # hierarchically over ICI, the same intra-then-inter-node hop structure
    # qgZ builds by hand). Afterwards out[p] = peer p's copy of my partition.
    q = jax.lax.all_to_all(q[:, None], axes, split_axis=0, concat_axis=0,
                           tiled=False)[:, 0]
    scale = jax.lax.all_to_all(scale[:, None], axes, split_axis=0,
                               concat_axis=0, tiled=False)[:, 0]
    vals = _dequantize_chunks(q, scale, chunk_shape, jnp.float32)
    red = jnp.mean(vals, axis=0) if mean else jnp.sum(vals, axis=0)
    return jnp.moveaxis(red.astype(grad.dtype), 0, dim)


def reduce_scatter_leaf(grad: jnp.ndarray, dim: int, axes: AxisNames,
                        mean: bool = True) -> jnp.ndarray:
    """Full-precision reduce-scatter of one leaf along `dim` (the non-ZeRO++
    baseline the quantized path is compared against)."""
    if isinstance(axes, str):
        axes = (axes,)
    out = grad
    for a in axes:
        if _axis_size(a) == 1:
            continue
        out = jax.lax.psum_scatter(out, a, scatter_dimension=dim, tiled=True)
    if mean:
        out = out / _axis_size(axes)
    return out


# ---------------------------------------------------------------------------
# Block-quantized ring transport (EQuARX-style, arXiv:2506.17615): the
# ppermute ring grad_overlap.py uses for async overlap, with every hop's
# payload shrunk to 1 byte/element + per-block fp32 scales. Each function
# ALSO returns the quantization error this device introduced (sender-side
# knowledge: dequant is deterministic, so the sender knows exactly what the
# receivers reconstruct) — the error-feedback residual the caller carries
# across steps so transport error does not bias convergence.
# ---------------------------------------------------------------------------
def _quantize_wire(x: jnp.ndarray, block: int, mode: str):
    """Flat [M] f32 -> (q [nb, block] int8|float8, scales [nb, 1] f32).
    The block clamps to the message size: shipping a 2048-padded block
    for a 100-element bucket would put more padding than payload on the
    wire (``quant_wire_bytes`` mirrors the clamp)."""
    block = max(1, min(int(block), int(x.size)))
    if mode == "fp8":
        blocks, _ = quantizer_blocked(x.astype(jnp.float32), block)
        absmax = jnp.max(jnp.abs(blocks), axis=1, keepdims=True)
        scale = jnp.where(absmax > 0, absmax / FP8_MAX, 1.0)
        return (blocks / scale).astype(jnp.float8_e4m3fn), scale
    return quantize_symmetric(x, block=block, bits=8)


def _dequantize_wire(q: jnp.ndarray, scale: jnp.ndarray,
                     numel: int) -> jnp.ndarray:
    """(q, scales) -> flat [numel] f32 (deterministic: sender and every
    receiver reconstruct the same values)."""
    return (q.astype(jnp.float32) * scale).reshape(-1)[:numel]


def ring_reduce_scatter_quant(buf: jnp.ndarray, axis: str, world: int,
                              block: int = 2048, mode: str = "int8"):
    """Quantized-wire ring reduce-scatter of [world, M] row partials.

    Same hop structure as grad_overlap._ring_reduce_rows (async ppermute
    the latency-hiding scheduler can overlap), but each hop ships the
    running partial as 1-byte values + per-block scales instead of fp32 —
    ~4x fewer wire bytes. The partial changes every hop, so it is
    requantized per hop (the EQuARX in-collective requant); the sender
    accumulates the error it introduced into the row it was carrying.

    Returns ``(row, err)``: device r's fully-summed row r [M] (never
    quantized on the final local add), and err [world, M] — THIS device's
    per-row quantization error, to be fed back into the next step's
    partials. Must run inside shard_map over ``axis``.
    """
    if world == 1:
        return buf[0], jnp.zeros_like(buf)
    M = buf.shape[1]
    perm = [(i, (i + 1) % world) for i in range(world)]
    idx = jax.lax.axis_index(axis)

    def take(b):
        return jax.lax.dynamic_index_in_dim(buf, b % world, 0,
                                            keepdims=False)

    err = jnp.zeros_like(buf)
    acc = take(idx - 1)
    for s in range(world - 1):
        q, scale = _quantize_wire(acc, block, mode)
        deq = _dequantize_wire(q, scale, M)
        # the row this device is about to send: its quantization error is
        # local knowledge (each row is quantized at most once per device,
        # so plain dynamic updates never collide)
        err = jax.lax.dynamic_update_index_in_dim(
            err, acc - deq, jnp.mod(idx - s - 1, world), 0)
        q = jax.lax.ppermute(q, axis, perm)
        scale = jax.lax.ppermute(scale, axis, perm)
        acc = _dequantize_wire(q, scale, M) + take(idx - s - 2)
    return acc, err


def ring_all_gather_quant(row: jnp.ndarray, axis: str, world: int,
                          block: int = 2048, mode: str = "int8"):
    """Quantized-wire ring all-gather of a per-device [M] row.

    The row never changes in flight, so it is quantized ONCE at the
    source and the same (q, scales) payload circulates world-1 hops.
    Every device — INCLUDING the source — reconstructs the dequantized
    values, so the gathered result stays replicated-identical across the
    ring (a source keeping its exact fp32 row would silently diverge the
    replicas). Returns ``(full [world, M], err [M])`` with err the
    source's own quantization error (the all-gather EF residual).
    """
    M = row.shape[0]
    if world == 1:
        return row[None], jnp.zeros_like(row)
    perm = [(i, (i + 1) % world) for i in range(world)]
    idx = jax.lax.axis_index(axis)
    q, scale = _quantize_wire(row, block, mode)
    deq = _dequantize_wire(q, scale, M)
    err = row - deq
    out = jnp.zeros((world, M), row.dtype)
    out = jax.lax.dynamic_update_index_in_dim(out, deq, idx, 0)
    for s in range(world - 1):
        q = jax.lax.ppermute(q, axis, perm)
        scale = jax.lax.ppermute(scale, axis, perm)
        out = jax.lax.dynamic_update_index_in_dim(
            out, _dequantize_wire(q, scale, M),
            jnp.mod(idx - s - 1, world), 0)
    return out, err


# ---------------------------------------------------------------------------
# Hierarchical (two-level) quantized rings — the EQuARX multi-pod shape
# (arXiv:2506.17615 §multi-pod): a dp world of ``world`` devices laid out
# as ``groups`` hosts x ``world // groups`` devices per host. Intra-host
# legs ride the fast wire and stay fp32 (exact, no error); ONLY the
# inter-host legs — the slow wire the quantization exists for — carry
# the 1-byte payload. Groups are contiguous index ranges (device
# g*H + h is member h of host g), matching how pods enumerate hosts.
# Selected by ``zero_optimization.quantized_reduce_hierarchy`` (the
# number of hosts; 0/1 = the flat single-level ring).
# ---------------------------------------------------------------------------
def _hier_shape(world: int, groups: int):
    groups = int(groups)
    if groups < 1 or world % groups != 0:
        raise ValueError(
            f"hierarchical ring needs groups to divide world "
            f"(got world={world}, groups={groups})")
    return groups, world // groups


def ring_reduce_scatter_hier(buf: jnp.ndarray, axis: str, world: int,
                             groups: int, block: int = 2048,
                             mode: str = "int8"):
    """Two-level ring reduce-scatter of [world, M] row partials.

    Phase 1 reduces each target row WITHIN the host at fp32 (an
    intra-host ppermute ring over the ``H = world // groups`` members,
    payload ``[groups, M]`` — the rows destined for this member index
    across every target host); phase 2 finishes the sum ACROSS hosts on
    a quantized ring over the ``groups`` same-member peers, requantizing
    the running partial per hop like :func:`ring_reduce_scatter_quant`.

    Same contract as the flat ring: returns ``(row, err)`` — device
    ``idx``'s fully-summed row (the final local add is never quantized)
    and err ``[world, M]``, THIS device's per-row quantization error
    (nonzero only at the ``groups - 1`` rows it quantized; zero
    everywhere when ``groups == 1`` — nothing rode the slow wire).
    ``groups == world`` degenerates to the flat quantized ring
    bit-for-bit. Must run inside shard_map over ``axis``.
    """
    G, H = _hier_shape(world, groups)
    if world == 1:
        return buf[0], jnp.zeros_like(buf)
    M = buf.shape[1]
    idx = jax.lax.axis_index(axis)
    g, h = idx // H, idx % H
    grouped = buf.reshape(G, H, M)

    def take_member(m):
        # rows destined for member m of EVERY target host: [G, M]
        return jax.lax.dynamic_index_in_dim(grouped, m % H, 1,
                                            keepdims=False)

    # phase 1: intra-host fp32 ring reduce-scatter over members
    perm_intra = [(gg * H + hh, gg * H + (hh + 1) % H)
                  for gg in range(G) for hh in range(H)]
    acc = take_member(h - 1)
    for s in range(H - 1):
        acc = jax.lax.ppermute(acc, axis, perm_intra) \
            + take_member(h - s - 2)
    # acc[gt] = sum over this host's members of row (gt*H + h)
    err = jnp.zeros_like(buf)
    if G == 1:
        return acc[0], err
    # phase 2: inter-host quantized ring over same-member peers
    perm_inter = [(gg * H + hh, ((gg + 1) % G) * H + hh)
                  for gg in range(G) for hh in range(H)]

    def take_group(b):
        return jax.lax.dynamic_index_in_dim(acc, b % G, 0,
                                            keepdims=False)

    err_g = jnp.zeros((G, M), buf.dtype)
    acc2 = take_group(g - 1)
    for s in range(G - 1):
        q, scale = _quantize_wire(acc2, block, mode)
        deq = _dequantize_wire(q, scale, M)
        err_g = jax.lax.dynamic_update_index_in_dim(
            err_g, acc2 - deq, jnp.mod(g - s - 1, G), 0)
        q = jax.lax.ppermute(q, axis, perm_inter)
        scale = jax.lax.ppermute(scale, axis, perm_inter)
        acc2 = _dequantize_wire(q, scale, M) + take_group(g - s - 2)
    # scatter this device's group-row errors back to global rows
    # gt*H + h — the [world, M] layout the EF residual state uses
    err = err.at[jnp.arange(G) * H + h].set(err_g)
    return acc2, err


def ring_all_gather_hier(row: jnp.ndarray, axis: str, world: int,
                         groups: int, block: int = 2048,
                         mode: str = "int8"):
    """Two-level ring all-gather of a per-device [M] row.

    Phase 1 gathers same-member rows ACROSS hosts on a quantized ring
    (each row quantized ONCE at its source; every device — including
    the source — uses the dequantized values, preserving the
    replicated-identical invariant of :func:`ring_all_gather_quant`);
    phase 2 gathers the per-member ``[groups, M]`` blocks WITHIN the
    host at fp32. Returns ``(full [world, M], err [M])`` with err the
    source's own quantization error (zero when ``groups == 1``).
    """
    G, H = _hier_shape(world, groups)
    M = row.shape[0]
    if world == 1:
        return row[None], jnp.zeros_like(row)
    idx = jax.lax.axis_index(axis)
    g, h = idx // H, idx % H
    if G == 1:
        deq_rows = row[None]                      # [1, M]
        err = jnp.zeros_like(row)
    else:
        perm_inter = [(gg * H + hh, ((gg + 1) % G) * H + hh)
                      for gg in range(G) for hh in range(H)]
        q, scale = _quantize_wire(row, block, mode)
        deq = _dequantize_wire(q, scale, M)
        err = row - deq
        deq_rows = jnp.zeros((G, M), row.dtype)
        deq_rows = jax.lax.dynamic_update_index_in_dim(deq_rows, deq,
                                                       g, 0)
        for s in range(G - 1):
            q = jax.lax.ppermute(q, axis, perm_inter)
            scale = jax.lax.ppermute(scale, axis, perm_inter)
            deq_rows = jax.lax.dynamic_update_index_in_dim(
                deq_rows, _dequantize_wire(q, scale, M),
                jnp.mod(g - s - 1, G), 0)
    # deq_rows[gt] = row of device (gt, h); gather across members fp32
    out = jnp.zeros((H, G, M), row.dtype)
    out = jax.lax.dynamic_update_index_in_dim(out, deq_rows, h, 0)
    if H > 1:
        perm_intra = [(gg * H + hh, gg * H + (hh + 1) % H)
                      for gg in range(G) for hh in range(H)]
        payload = deq_rows
        for s in range(H - 1):
            payload = jax.lax.ppermute(payload, axis, perm_intra)
            out = jax.lax.dynamic_update_index_in_dim(
                out, payload, jnp.mod(h - s - 1, H), 0)
    # out[ht, gt] = row of device (gt, ht) -> [world, M] global order
    full = jnp.moveaxis(out, 0, 1).reshape(world, M)
    return full, err


def hier_wire_bytes(numel: int, world: int, groups: int,
                    block: int = 2048) -> dict:
    """Aggregate wire bytes of ONE [world, numel]-row reduce-scatter,
    split by wire class — the comm_bench assertion that the hierarchy
    actually moves the quantization win onto the slow wire.

    Flat fp32 ring: every device ships its running partial every hop;
    with contiguous host grouping, ``groups`` of the ring's edges cross
    hosts, so per full reduce ``(world-1) hops x groups crossing
    messages x numel x 4`` bytes ride the slow wire. Hierarchical:
    every device does ``groups - 1`` quantized inter-host hops of
    :func:`quant_wire_bytes` each, and ``H - 1`` fp32 intra-host hops
    of ``groups x numel x 4``.
    """
    G, H = _hier_shape(world, groups)
    inter_fp32_flat = (world - 1) * G * numel * 4
    inter_quant = world * (G - 1) * quant_wire_bytes(numel, block)
    return {
        "inter_bytes_fp32_flat": inter_fp32_flat,
        "inter_bytes_quant": inter_quant,
        "intra_bytes_fp32": world * (H - 1) * G * numel * 4,
        "ratio": (inter_fp32_flat / inter_quant
                  if inter_quant else float("inf")),
    }


def quant_wire_bytes(numel: int, block: int = 2048) -> int:
    """Bytes on the wire for one quantized hop of a [numel] message:
    1 byte/element (block-padded) + fp32 scale per block, with the block
    clamped to the message size like ``_quantize_wire``."""
    block = max(1, min(int(block), int(numel)))
    nb = -(-int(numel) // block)
    return nb * block + nb * 4


def make_zero3_gather(dim: int, axes: AxisNames, fwd_quantized: bool,
                      bwd_quantized: bool, block: int = 2048, bits: int = 8):
    """Shard->full parameter gather with the ZeRO-3 gradient semantics baked
    into its VJP: forward all-gathers the shard (int8-quantized if qwZ),
    backward reduce-scatters the cotangent back to the shard (int8 all-to-all
    if qgZ), with a mean over the ZeRO world so the result is the gradient of
    the mean loss.

    This single primitive is the TPU-native collapse of the reference's
    stage3 machinery: fetch_sub_module's allgather on use
    (partitioned_param_coordinator.py:256) is the fwd; the grad-hook
    reduce/partition pipeline (stage3.py:1135 __reduce_and_partition_ipg_grads)
    is the bwd — autodiff places both exactly where the hooks would fire.
    Must run inside shard_map over `axes`.
    """

    @jax.named_scope("param_gather")
    def _gather_impl(shard):
        if fwd_quantized:
            return quantized_all_gather(shard, dim, axes, block=block,
                                        bits=bits, dtype=shard.dtype)
        g = jax.lax.all_gather(shard, axes)  # [n, ...shard shape...]
        g = jnp.moveaxis(g, 0, dim)          # [..., n, d_local, ...]
        return g.reshape(g.shape[:dim] + (-1,) + g.shape[dim + 2:])

    @jax.custom_vjp
    def gather(shard):
        return _gather_impl(shard)

    def fwd(shard):
        return _gather_impl(shard), None

    @jax.named_scope("grad_reduce")
    def bwd(_, cot):
        if bwd_quantized:
            g = all_to_all_quant_reduce(cot, dim, axes, block=block, bits=bits,
                                        mean=True)
        else:
            g = reduce_scatter_leaf(cot, dim, axes, mean=True)
        return (g,)

    gather.defvjp(fwd, bwd)
    return gather
