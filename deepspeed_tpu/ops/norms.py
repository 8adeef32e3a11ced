"""Fused normalization kernels (RMSNorm / LayerNorm).

TPU-native equivalents of the reference's norm kernels
(csrc/transformer/inference/csrc/rms_norm.cu, layer_norm.cu and the training
normalize_kernels.cu). The Pallas path fuses the reduction + scale in VMEM;
a jnp reference is kept both for parity tests and as the XLA fallback (XLA
fuses these patterns well — the kernel exists for the cases where it doesn't,
e.g. when fusing with quantized residual adds).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _rms_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    o_ref[:] = (x * inv * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def rms_norm_pallas(x, weight, eps: float = 1e-6, block_rows: int = 256):
    """RMSNorm over the last dim of [rows, hidden] (leading dims flattened)."""
    orig_shape = x.shape
    h = x.shape[-1]
    rows = x.size // h
    xf = x.reshape(rows, h)
    br = min(block_rows, rows)
    if rows % br != 0:
        br = rows  # fall back to one block
    out = pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            pl.BlockSpec((h,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, h), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, h), x.dtype),
        name="rms_norm",
        interpret=_interpret(),
    )(xf, weight)
    return out.reshape(orig_shape)


def rms_norm_ref(x, weight, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)).astype(x.dtype)


def rms_norm(x, weight, eps: float = 1e-6, use_pallas: bool = False):
    """Differentiable entry: XLA path by default (fuses fine and is
    autodiff-able); pallas path for explicit fusion experiments."""
    if use_pallas:
        return rms_norm_pallas(x, weight, eps)
    return rms_norm_ref(x, weight, eps)


def layer_norm_ref(x, weight, bias=None, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    out = (xf - mean) * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(x.dtype)


layer_norm = layer_norm_ref
