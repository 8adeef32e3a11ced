"""Flash attention — Pallas TPU kernels.

TPU-native replacement for the reference's fused attention kernels
(csrc/transformer/ds_transformer_cuda.cpp softmax path, and the inference
attention kernels in csrc/transformer/inference). Implements the
memory-efficient online-softmax algorithm (never materializes the [S, S]
score matrix) as three Mosaic kernels:

  * forward:  grid (BH, Sq/bq, Skv/bk), running (m, l, acc) in VMEM scratch —
    the kv grid axis is innermost and TPU grids execute sequentially, so the
    scratch carries across kv steps.
  * backward dq: same grid, accumulates dq over kv blocks.
  * backward dk/dv: grid (BH, Skv/bk, Sq/bq), accumulates dk, dv over q blocks.

Supports causal masking (bottom-right aligned for sq != skv, matching the
usual decode convention; fully-masked blocks are skipped via pl.when) and
grouped-query attention (kv-head indexing in the BlockSpec index map). f32
accumulation on the MXU (preferred_element_type) with bf16 inputs.

On non-TPU backends (the CPU test mesh) kernels run in interpret mode;
parity is tested against the jnp reference in tests/unit/ops.
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _cost(bh, sq, skv, d, causal, n_dots):
    """CostEstimate so XLA's scheduler can overlap collectives with the
    kernel (the pallas body is opaque to XLA's own cost analysis)."""
    frac = 0.5 if causal else 1.0
    return pl.CostEstimate(
        flops=int(n_dots * 2 * bh * sq * skv * d * frac),
        bytes_accessed=int(2 * bh * (sq + skv) * d * 2 * n_dots),
        transcendentals=int(bh * sq * skv * frac),
    )


def _pick_block(s: int, target: int) -> int:
    """Largest power-of-two-ish divisor of s that is <= target."""
    b = min(target, s)
    while b > 1 and s % b:
        b //= 2
    return max(b, 1)


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_sc, m_sc, l_sc, *, scale, causal, bq, bk, n_kv, off):
    i = pl.program_id(1)  # q block
    j = pl.program_id(2)  # kv block

    @pl.when(j == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    # causal: skip blocks entirely above the (bottom-right aligned) diagonal
    run = True
    if causal:
        run = j * bk <= (i + 1) * bq - 1 + off

    @pl.when(run)
    def _body():
        # keep dots in the input dtype (bf16 runs the MXU at full rate; f32
        # matmul is ~8x slower) with f32 accumulation
        q = q_ref[0]                                 # (bq, d)
        k = k_ref[0]                                 # (bk, d)
        v = v_ref[0]                                 # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = off + i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_sc[:, :1]                         # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # rows fully masked so far have m_new == NEG_INF; exp(s - m_new)
        # would be exp(0) = 1 garbage — substitute 0 so exp(NEG_INF) == 0
        m_safe = jnp.where(m_new <= NEG_INF * 0.5, 0.0, m_new)
        p = jnp.exp(s - m_safe)                      # (bq, bk) f32
        corr = jnp.exp(m_prev - m_new)               # (bq, 1)
        l_new = l_sc[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_sc[:] = acc_sc[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[:] = jnp.broadcast_to(m_new, m_sc.shape)
        l_sc[:] = jnp.broadcast_to(l_new, l_sc.shape)

    @pl.when(j == n_kv - 1)
    def _finish():
        l = l_sc[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_sc[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_sc[:, :1] + jnp.log(l_safe)


def _flash_fwd(q, k, v, scale, causal, bq, bk):
    bh, sq, d = q.shape
    bhk, skv, _ = k.shape
    group = bh // bhk
    n_q, n_kv = pl.cdiv(sq, bq), pl.cdiv(skv, bk)

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, n_kv=n_kv, off=skv - sq)
    out_shape = [
        jax.ShapeDtypeStruct(q.shape, q.dtype),
        jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
    ]
    o, lse = pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j, g=group: (b // g, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j, g=group: (b // g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        out_shape=out_shape,
        cost_estimate=_cost(bh, sq, skv, d, causal, n_dots=2),
        name="flash_attention_fwd",
        interpret=_interpret(),
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_sc, *, scale, causal, bq, bk, n_kv, off):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    run = True
    if causal:
        run = j * bk <= (i + 1) * bq - 1 + off

    @pl.when(run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]                             # (bq, 1)
        delta = delta_ref[0]                         # (bq, 1)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = off + i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        # fully-masked rows carry lse == NEG_INF; exp(s - lse) would be 1
        lse_safe = jnp.where(lse <= NEG_INF * 0.5, 0.0, lse)
        p = jnp.exp(s - lse_safe)                    # (bq, bk) f32
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(k.dtype)
        dq_sc[:] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    @pl.when(j == n_kv - 1)
    def _finish():
        dq_ref[0] = dq_sc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_sc, dv_sc,
                    *, scale, causal, bq, bk, n_q, n_inner, off):
    j = pl.program_id(1)   # kv block (outer)
    e = pl.program_id(2)   # inner: q-heads of the GQA group x q blocks
    i = e % n_q            # q block within the head

    @pl.when(e == 0)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    run = True
    if causal:
        run = (i + 1) * bq - 1 + off >= j * bk

    @pl.when(run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]
        delta = delta_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = off + i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        lse_safe = jnp.where(lse <= NEG_INF * 0.5, 0.0, lse)
        p = jnp.exp(s - lse_safe)                    # (bq, bk) f32
        pc = p.astype(do.dtype)
        dv_sc[:] += jax.lax.dot_general(pc, do, (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(q.dtype)  # (bq, bk)
        dk_sc[:] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    @pl.when(e == n_inner - 1)
    def _finish():
        dk_ref[0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)


def _flash_bwd(res, g, scale, causal, bq, bk):
    q, k, v, o, lse = res
    do = g
    bh, sq, d = q.shape
    bhk, skv, _ = k.shape
    group = bh // bhk
    n_q, n_kv = pl.cdiv(sq, bq), pl.cdiv(skv, bk)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, n_kv=n_kv, off=skv - sq),
        grid=(bh, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j, g_=group: (b // g_, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j, g_=group: (b // g_, j, 0)),
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        cost_estimate=_cost(bh, sq, skv, d, causal, n_dots=3),
        name="flash_attention_bwd_dq",
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)

    # dk/dv: grid over kv heads; the inner axis walks every q block of every
    # q-head in the GQA group, accumulating in VMEM scratch — the group
    # reduction happens in-register instead of a second [bh, skv, d] HBM pass.
    n_inner = group * n_q
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, n_q=n_q, n_inner=n_inner,
                          off=skv - sq),
        grid=(bhk, n_kv, n_inner),
        in_specs=[
            pl.BlockSpec((1, bq, d),
                         lambda b, j, e, g_=group, nq=n_q:
                         (b * g_ + e // nq, e % nq, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, e: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, e: (b, j, 0)),
            pl.BlockSpec((1, bq, d),
                         lambda b, j, e, g_=group, nq=n_q:
                         (b * g_ + e // nq, e % nq, 0)),
            pl.BlockSpec((1, bq, 1),
                         lambda b, j, e, g_=group, nq=n_q:
                         (b * g_ + e // nq, e % nq, 0)),
            pl.BlockSpec((1, bq, 1),
                         lambda b, j, e, g_=group, nq=n_q:
                         (b * g_ + e // nq, e % nq, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, e: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, e: (b, j, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bhk, skv, d), k.dtype),
            jax.ShapeDtypeStruct((bhk, skv, d), v.dtype),
        ],
        cost_estimate=_cost(bh, sq, skv, d, causal, n_dots=5),
        name="flash_attention_bwd_dkv",
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_core(q, k, v, scale, causal, bq, bk):
    o, _ = _flash_fwd(q, k, v, scale, causal, bq, bk)
    return o


def _flash_core_fwd(q, k, v, scale, causal, bq, bk):
    o, lse = _flash_fwd(q, k, v, scale, causal, bq, bk)
    return o, (q, k, v, o, lse)


def _flash_core_bwd(scale, causal, bq, bk, res, g):
    return _flash_bwd(res, g, scale, causal, bq, bk)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention(q, k, v, causal: bool = True, scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_kv: Optional[int] = None):
    """Flash attention over [batch, num_heads, seq, head_dim] inputs.

    k/v may have fewer heads (GQA); num_heads % num_kv_heads == 0.
    block_q/block_kv None (or 0) = auto: 256/512 capped to the seq lens —
    large blocks amortize the online-softmax bookkeeping and keep the MXU
    fed; VMEM cost at d<=128 is well under budget.
    """
    b, h, sq, d = q.shape
    _, hk, skv, _ = k.shape
    assert h % hk == 0, f"GQA requires h({h}) % hk({hk}) == 0"
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    bq = _pick_block(sq, block_q or 256)
    bk = _pick_block(skv, block_kv or 512)
    assert sq % bq == 0 and skv % bk == 0, \
        f"seq lengths ({sq},{skv}) must be multiples of block sizes ({bq},{bk})"
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * hk, skv, d)
    vf = v.reshape(b * hk, skv, d)
    # fold batch into the head axis keeping kv-head grouping contiguous
    o = _flash_core(qf, kf, vf, scale, causal, bq, bk)
    return o.reshape(b, h, sq, d)


def mha_reference(q, k, v, causal: bool = True, scale: Optional[float] = None):
    """jnp reference implementation for parity tests (O(S^2) memory)."""
    b, h, sq, d = q.shape
    _, hk, skv, _ = k.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if h != hk:
        rep = h // hk
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((sq, skv), bool), k=skv - sq)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if causal:
        # fully-masked rows (sq > skv) produce zeros, matching the kernel
        any_valid = jnp.any(mask, axis=-1)[None, None, :, None]
        p = jnp.where(any_valid, p, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)
