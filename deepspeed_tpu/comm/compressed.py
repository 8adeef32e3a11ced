"""Error-compensated 1-bit compressed allreduce.

TPU-native equivalent of the reference's 1-bit communication backends
(runtime/comm/nccl.py:51 NcclBackend.compressed_allreduce, runtime/comm/mpi.py
MpiBackend): the momentum tensor is communicated as sign bits + one scale per
worker chunk, with persistent worker/server error feedback so the compression
error is re-injected next step (the 1-bit Adam paper's algorithm).

Two-phase structure, identical to the reference:
  phase 1 (reduce-scatter shaped): every worker sign-compresses its
    error-compensated buffer, chunks it world-size ways, and all-to-alls the
    chunks; each worker averages the received signs into its server segment
    and updates its worker error.
  phase 2 (all-gather shaped): each worker sign-compresses its averaged
    server segment (updating server error) and all-gathers the result.

Sign bits travel packed 8-per-byte (jnp packbits/unpackbits) — the actual
32x wire compression the reference gets from its bit kernels; scales are one
fp32 per chunk. Designed to run inside shard_map over the DP mesh axes.
"""

from typing import Tuple, Union

import jax
import jax.numpy as jnp

from .quantized import _axis_size

AxisNames = Union[str, Tuple[str, ...]]


def _axes_tuple(axes: AxisNames) -> Tuple[str, ...]:
    return (axes,) if isinstance(axes, str) else tuple(axes)


def _sign_compress(x: jnp.ndarray):
    """x [k, m] -> (packed signs [k, ceil(m/8)] uint8, scale [k, 1]).

    scale is the L1 mean (reference uses norm(buffer)/sqrt(numel) variants;
    L1 mean minimizes the L2 error of sign*scale)."""
    scale = jnp.mean(jnp.abs(x), axis=1, keepdims=True)
    bits = (x >= 0)
    packed = jnp.packbits(bits, axis=1)
    return packed, scale


def _sign_decompress(packed: jnp.ndarray, scale: jnp.ndarray, m: int):
    bits = jnp.unpackbits(packed, axis=1, count=m)
    return (bits.astype(jnp.float32) * 2.0 - 1.0) * scale


def compressed_allreduce(buf: jnp.ndarray, worker_error: jnp.ndarray,
                         server_error: jnp.ndarray, axes: AxisNames):
    """1-bit averaged allreduce of `buf` (flat [numel], device-local value).

    worker_error: [numel] persistent per-worker compression error.
    server_error: [numel // n] persistent per-worker server-segment error.
    Returns (averaged buf [numel], new_worker_error, new_server_error).
    numel must be divisible by 8 * n (n = world size over `axes`).
    """
    n = _axis_size(axes)
    numel = buf.shape[0]
    seg = numel // n

    # ---- phase 1: compensate, compress, all-to-all, server average
    compensated = buf + worker_error
    chunks = compensated.reshape(n, seg)
    packed, scale = _sign_compress(chunks)
    new_worker_error = compensated - _sign_decompress(packed, scale,
                                                     seg).reshape(-1)
    # route chunk i to worker i
    packed = jax.lax.all_to_all(packed[:, None], axes, split_axis=0,
                                concat_axis=0, tiled=False)[:, 0]
    scale = jax.lax.all_to_all(scale[:, None], axes, split_axis=0,
                               concat_axis=0, tiled=False)[:, 0]
    received = _sign_decompress(packed, scale, seg)       # [n, seg]
    server_seg = jnp.mean(received, axis=0) + server_error

    # ---- phase 2: compress server segment, all-gather
    packed2, scale2 = _sign_compress(server_seg[None, :])
    new_server_error = server_seg - _sign_decompress(packed2, scale2,
                                                     seg)[0]
    packed_g = jax.lax.all_gather(packed2[0], axes)       # [n, seg//8]
    scale_g = jax.lax.all_gather(scale2[0], axes)         # [n, 1]
    out = _sign_decompress(packed_g, scale_g, seg).reshape(-1)
    return out, new_worker_error, new_server_error


def compressed_allreduce_padded(buf: jnp.ndarray, worker_error: jnp.ndarray,
                                server_error: jnp.ndarray, axes: AxisNames):
    """compressed_allreduce for arbitrary numel: pads to a multiple of 8*n.
    Error buffers must be sized with `padded_numel(numel, n)`."""
    n = _axis_size(axes)
    padded = worker_error.shape[0]
    flat = jnp.zeros(padded, buf.dtype).at[:buf.shape[0]].set(buf)
    out, we, se = compressed_allreduce(flat, worker_error, server_error, axes)
    return out[:buf.shape[0]], we, se


def padded_numel(numel: int, n: int) -> int:
    block = 8 * n
    return ((numel + block - 1) // block) * block
