"""Block-sparse attention — Pallas TPU kernels.

TPU-native replacement for the reference's Triton block-sparse attention
(ops/sparse_attention/matmul.py:819 sdd/dsd kernels + softmax.py:296): the
static per-head block layout (ops/sparse_attention.py SparsityConfig
family) is compiled into per-row ACTIVE-BLOCK index tables that are
scalar-prefetched into the kernels (the splash-attention technique), so

  * inactive blocks are never loaded or computed — compute scales with the
    number of active blocks, not S^2 (the reference's Triton lut plays the
    same role), and
  * the [S, S] score matrix is never materialized — the online-softmax
    running (m, l, acc) state lives in VMEM scratch, like the flash kernel.

Tables (host-built numpy, static per layout):
  kv_idx/kv_valid [H, n_q, Jmax]  — active kv blocks per q row (forward/dq)
  q_idx/q_valid   [H, n_kv, Imax] — active q blocks per kv column (dk/dv)
Padded slots repeat the last valid index with valid=0 and are skipped with
pl.when. Intra-block causality is applied on diagonal blocks from the
prefetched block id.
"""

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


_TABLE_CACHE: dict = {}


def build_tables(layout: np.ndarray, causal: bool
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """layout [H, n, n] (bool) -> (kv_idx, kv_valid, q_idx, q_valid).

    The reference builds the equivalent Triton look-up tables in
    make_lut (ops/sparse_attention/matmul.py). Tables are static per
    (layout, causal) and memoized — eager per-step callers would otherwise
    repeat the O(H * n^2) host scan every forward."""
    key = (np.asarray(layout, bool).tobytes(), np.shape(layout), causal)
    hit = _TABLE_CACHE.get(key)
    if hit is not None:
        return hit
    out = _build_tables(layout, causal)
    if len(_TABLE_CACHE) > 64:  # bound host memory for layout churn
        _TABLE_CACHE.clear()
    _TABLE_CACHE[key] = out
    return out


def _build_tables(layout: np.ndarray, causal: bool):
    lay = np.asarray(layout, bool)
    H, n_q, n_kv = lay.shape
    if causal:
        lay = lay & np.tril(np.ones((n_q, n_kv), bool))[None]

    def pack(rows):  # list of index-arrays -> padded [len(rows), max]
        width = max((len(r) for r in rows), default=1) or 1
        idx = np.zeros((len(rows), width), np.int32)
        valid = np.zeros((len(rows), width), np.int32)
        for i, r in enumerate(rows):
            if len(r):
                idx[i, :len(r)] = r
                idx[i, len(r):] = r[-1]
                valid[i, :len(r)] = 1
        return idx, valid

    kv_i, kv_v, q_i, q_v = [], [], [], []
    for h in range(H):
        a, b = pack([np.nonzero(lay[h, i])[0] for i in range(n_q)])
        kv_i.append(a), kv_v.append(b)
        a, b = pack([np.nonzero(lay[h, :, j])[0] for j in range(n_kv)])
        q_i.append(a), q_v.append(b)

    def stack(parts):  # pad ragged widths across heads
        width = max(p.shape[1] for p in parts)
        return np.stack([np.pad(p, ((0, 0), (0, width - p.shape[1])))
                         for p in parts])

    return stack(kv_i), stack(kv_v), stack(q_i), stack(q_v)


def _mask_block(s, causal, qi, kj, block):
    if not causal:
        return s
    q_pos = qi * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = kj * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(kv_idx, kv_valid, q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_sc, m_sc, l_sc, *, scale, causal, block, jmax, nheads):
    b = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    h = b % nheads

    @pl.when(j == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    @pl.when(kv_valid[h, i, j] == 1)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _mask_block(s, causal, i, kv_idx[h, i, j], block)
        m_prev = m_sc[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        m_safe = jnp.where(m_new <= NEG_INF * 0.5, 0.0, m_new)
        p = jnp.exp(s - m_safe)
        corr = jnp.exp(m_prev - m_new)
        l_sc[:] = jnp.broadcast_to(
            l_sc[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True),
            l_sc.shape)
        acc_sc[:] = acc_sc[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[:] = jnp.broadcast_to(m_new, m_sc.shape)

    @pl.when(j == jmax - 1)
    def _finish():
        l = l_sc[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_sc[:] / l_safe).astype(o_ref.dtype)
        m = m_sc[:, :1]
        lse_ref[0] = jnp.where(m <= NEG_INF * 0.5, NEG_INF,
                               m + jnp.log(l_safe))


def _sparse_fwd(q, k, v, kv_idx, kv_valid, scale, causal, block, nheads):
    bh, s, d = q.shape
    n_q = s // block
    jmax = kv_idx.shape[-1]
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block=block, jmax=jmax, nheads=nheads)
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, n_q, jmax),
            in_specs=[
                pl.BlockSpec((1, block, d), lambda b, i, j, *_: (b, i, 0)),
                pl.BlockSpec((1, block, d),
                             lambda b, i, j, tbl, _v: (b, tbl[b % nheads, i, j], 0)),
                pl.BlockSpec((1, block, d),
                             lambda b, i, j, tbl, _v: (b, tbl[b % nheads, i, j], 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block, d), lambda b, i, j, *_: (b, i, 0)),
                pl.BlockSpec((1, block, 1), lambda b, i, j, *_: (b, i, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block, d), jnp.float32),
                pltpu.VMEM((block, 128), jnp.float32),
                pltpu.VMEM((block, 128), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bh, s, 1), jnp.float32),
        ],
        name="sparse_flash_attention_fwd",
        interpret=_interpret(),
    )(kv_idx, kv_valid, q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(kv_idx, kv_valid, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, dq_sc, *, scale, causal, block, jmax,
                   nheads):
    b = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    h = b % nheads

    @pl.when(j == 0)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    @pl.when(kv_valid[h, i, j] == 1)
    def _body():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        lse, delta = lse_ref[0], delta_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _mask_block(s, causal, i, kv_idx[h, i, j], block)
        lse_safe = jnp.where(lse <= NEG_INF * 0.5, 0.0, lse)
        p = jnp.exp(s - lse_safe)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(k.dtype)
        dq_sc[:] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    @pl.when(j == jmax - 1)
    def _finish():
        dq_ref[0] = dq_sc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_idx, q_valid, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, dk_sc, dv_sc,
                    *, scale, causal, block, imax, nheads):
    b = pl.program_id(0)
    j = pl.program_id(1)
    e = pl.program_id(2)
    h = b % nheads

    @pl.when(e == 0)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    @pl.when(q_valid[h, j, e] == 1)
    def _body():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        lse, delta = lse_ref[0], delta_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _mask_block(s, causal, q_idx[h, j, e], j, block)
        lse_safe = jnp.where(lse <= NEG_INF * 0.5, 0.0, lse)
        p = jnp.exp(s - lse_safe)
        pc = p.astype(do.dtype)
        dv_sc[:] += jax.lax.dot_general(pc, do, (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dk_sc[:] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    @pl.when(e == imax - 1)
    def _finish():
        dk_ref[0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)


def _sparse_bwd(res, g, scale, causal, block, nheads):
    q, k, v, o, lse, kv_idx, kv_valid, q_idx, q_valid = res
    do = g
    bh, s, d = q.shape
    n_q = s // block
    jmax = kv_idx.shape[-1]
    imax = q_idx.shape[-1]
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block=block, jmax=jmax, nheads=nheads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, n_q, jmax),
            in_specs=[
                pl.BlockSpec((1, block, d), lambda b, i, j, *_: (b, i, 0)),
                pl.BlockSpec((1, block, d),
                             lambda b, i, j, tbl, _v: (b, tbl[b % nheads, i, j], 0)),
                pl.BlockSpec((1, block, d),
                             lambda b, i, j, tbl, _v: (b, tbl[b % nheads, i, j], 0)),
                pl.BlockSpec((1, block, d), lambda b, i, j, *_: (b, i, 0)),
                pl.BlockSpec((1, block, 1), lambda b, i, j, *_: (b, i, 0)),
                pl.BlockSpec((1, block, 1), lambda b, i, j, *_: (b, i, 0)),
            ],
            out_specs=pl.BlockSpec((1, block, d),
                                   lambda b, i, j, *_: (b, i, 0)),
            scratch_shapes=[pltpu.VMEM((block, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        name="sparse_flash_attention_bwd_dq",
        interpret=_interpret(),
    )(kv_idx, kv_valid, q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block=block, imax=imax, nheads=nheads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, s // block, imax),
            in_specs=[
                pl.BlockSpec((1, block, d),
                             lambda b, j, e, tbl, _v: (b, tbl[b % nheads, j, e], 0)),
                pl.BlockSpec((1, block, d), lambda b, j, e, *_: (b, j, 0)),
                pl.BlockSpec((1, block, d), lambda b, j, e, *_: (b, j, 0)),
                pl.BlockSpec((1, block, d),
                             lambda b, j, e, tbl, _v: (b, tbl[b % nheads, j, e], 0)),
                pl.BlockSpec((1, block, 1),
                             lambda b, j, e, tbl, _v: (b, tbl[b % nheads, j, e], 0)),
                pl.BlockSpec((1, block, 1),
                             lambda b, j, e, tbl, _v: (b, tbl[b % nheads, j, e], 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block, d), lambda b, j, e, *_: (b, j, 0)),
                pl.BlockSpec((1, block, d), lambda b, j, e, *_: (b, j, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block, d), jnp.float32),
                pltpu.VMEM((block, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        name="sparse_flash_attention_bwd_dkv",
        interpret=_interpret(),
    )(q_idx, q_valid, q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _sparse_core(q, k, v, kv_idx, kv_valid, q_idx, q_valid, scale, causal,
                 block, nheads):
    o, _ = _sparse_fwd(q, k, v, kv_idx, kv_valid, scale, causal, block,
                       nheads)
    return o


def _sparse_core_fwd(q, k, v, kv_idx, kv_valid, q_idx, q_valid, scale,
                     causal, block, nheads):
    o, lse = _sparse_fwd(q, k, v, kv_idx, kv_valid, scale, causal, block,
                         nheads)
    return o, (q, k, v, o, lse, kv_idx, kv_valid, q_idx, q_valid)


def _sparse_core_bwd(scale, causal, block, nheads, res, g):
    dq, dk, dv = _sparse_bwd(res, g, scale, causal, block, nheads)
    return dq, dk, dv, None, None, None, None


_sparse_core.defvjp(_sparse_core_fwd, _sparse_core_bwd)


def sparse_flash_attention(q, k, v, layout: np.ndarray, block: int,
                           causal: bool = False,
                           scale: Optional[float] = None):
    """Block-sparse attention over [B, H, S, D] with a static [H, n, n]
    block layout; only active blocks are computed (Pallas kernels above)."""
    B, H, S, D = q.shape
    assert S % block == 0, f"seq {S} not divisible by block {block}"
    scale = scale or 1.0 / float(np.sqrt(D))
    kv_i, kv_v, q_i, q_v = build_tables(layout, causal)
    fold = lambda x: x.reshape(B * H, S, D)  # noqa: E731
    o = _sparse_core(fold(q), fold(k), fold(v),
                     jnp.asarray(kv_i), jnp.asarray(kv_v),
                     jnp.asarray(q_i), jnp.asarray(q_v),
                     scale, causal, block, H)
    return o.reshape(B, H, S, D)
