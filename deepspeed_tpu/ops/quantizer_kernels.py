"""Pallas quant/dequant kernels.

TPU-native counterpart of the reference's CUDA quantizer kernels
(csrc/quantization/{quantize.cu,dequantize.cu,swizzled_quantize.cu}): the
blockwise symmetric (de)quantization that ZeRO++ qwZ/qgZ and weight-only
quant move over the wire. The jnp path (ops/quantizer.py) already fuses
into neighbouring ops via XLA; these kernels exist for the cases XLA does
NOT fuse well — standalone (de)quant of large flat buffers around manual
shard_map collectives — and run the reduction + scale + round in one VMEM
pass instead of separate absmax/divide/round HLOs.

Layout matches ops/quantizer.py exactly: [n_blocks, block] int8 values with
one fp32 scale per block; parity-tested against the jnp reference.
"""

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .flash_attention import _interpret
from .quantizer import INT4_QRANGE, INT8_QRANGE


def _quant_kernel(x_ref, q_ref, s_ref, *, qrange):
    x = x_ref[...].astype(jnp.float32)                    # (R, block)
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / qrange, 1.0)
    q = jnp.clip(jnp.round(x / scale), -qrange, qrange)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = jnp.broadcast_to(scale, s_ref.shape)


def _dequant_kernel(q_ref, s_ref, o_ref, *, out_dtype):
    q = q_ref[...].astype(jnp.float32)
    o_ref[...] = (q * s_ref[...]).astype(out_dtype)


def _row_tile(nb: int, target: int = 8) -> int:
    r = min(target, nb)
    while r > 1 and nb % r:
        r -= 1
    return max(r, 1)


@functools.partial(jax.jit, static_argnames=("bits",))
def quantize_blocks_pallas(blocks: jnp.ndarray, bits: int = 8
                           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """blocks [nb, block] -> (int8 [nb, block], fp32 scales [nb, 1]);
    one fused absmax+scale+round pass per block row."""
    nb, block = blocks.shape
    qrange = INT8_QRANGE if bits == 8 else INT4_QRANGE
    R = _row_tile(nb)
    q, s = pl.pallas_call(
        functools.partial(_quant_kernel, qrange=qrange),
        grid=(nb // R,),
        in_specs=[pl.BlockSpec((R, block), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((R, block), lambda i: (i, 0)),
                   pl.BlockSpec((R, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((nb, block), jnp.int8),
                   jax.ShapeDtypeStruct((nb, 1), jnp.float32)],
        name="quantize_blocks",
        interpret=_interpret(),
    )(blocks)
    return q, s


@functools.partial(jax.jit, static_argnames=("out_dtype",))
def dequantize_blocks_pallas(q: jnp.ndarray, scale: jnp.ndarray,
                             out_dtype=jnp.float32) -> jnp.ndarray:
    """(int8 [nb, block], fp32 [nb, 1]) -> values [nb, block]."""
    nb, block = q.shape
    R = _row_tile(nb)
    return pl.pallas_call(
        functools.partial(_dequant_kernel, out_dtype=out_dtype),
        grid=(nb // R,),
        in_specs=[pl.BlockSpec((R, block), lambda i: (i, 0)),
                  pl.BlockSpec((R, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((R, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, block), out_dtype),
        name="dequantize_blocks",
        interpret=_interpret(),
    )(q, scale)


def quantize_symmetric_pallas(x, block: int = 2048, bits: int = 8):
    """Drop-in for ops.quantizer.quantize_symmetric via the Pallas path."""
    from .quantizer import _blocked

    blocks, _ = _blocked(x.astype(jnp.float32), block)
    return quantize_blocks_pallas(blocks, bits=bits)


def dequantize_symmetric_pallas(q, scale, shape, dtype=jnp.float32):
    """Drop-in for ops.quantizer.dequantize_symmetric; the kernel writes
    the target dtype directly (no fp32 round trip through HBM)."""
    out = dequantize_blocks_pallas(q, scale, out_dtype=dtype)
    n = math.prod(shape)
    return out.reshape(-1)[:n].reshape(shape)
