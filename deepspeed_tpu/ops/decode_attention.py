"""Dense-cache decode attention — Pallas TPU kernel (inference v1 hot path).

TPU-native equivalent of the reference's v1 inference attention kernels
(csrc/transformer/inference/csrc/ softmax + attention over the contiguous
KV cache). One query token per sequence attends over its dense cache
[B, kvh, M, hd]; pages past the sequence length are skipped.

Why a kernel instead of the jnp einsum the cached path otherwise runs:
  * GQA without jnp.repeat — the q heads of a group read their kv head's
    cache block once from HBM; the einsum path materializes a repeated
    [B, nh, M, hd] cache every step (2-8x the HBM traffic of the cache
    itself, and decode is HBM-bound).
  * cache blocks stream HBM->VMEM in the native cache dtype; the f32
    upcast happens in VMEM.
  * blocks wholly past `length` are skipped (pl.when), so short sequences
    in a long max_len cache don't pay for the tail.

Structure mirrors inference/v2/kernels/paged_attention.py (same
online-softmax scratch carry); the only difference is direct [B, kvh, M]
indexing instead of a block table.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_sc, m_sc, l_sc,
            *, bs, n_blocks, scale, m_total):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    length = len_ref[b]

    @pl.when(j * bs < length)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)           # (group, hd)
        k = k_ref[0, 0].astype(jnp.float32)           # (bs, hd)
        v = v_ref[0, 0].astype(jnp.float32)           # (bs, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)
        # the tail block may extend past M (Pallas pads with garbage/NaN);
        # p is 0 there but 0 * NaN = NaN in the p @ v dot — zero v's pad
        lane = j * bs + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        v = jnp.where(lane < m_total, v, 0.0)
        m_prev = m_sc[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_sc[:] = jnp.broadcast_to(
            l_sc[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True),
            l_sc.shape)
        acc_sc[:] = acc_sc[:] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[:] = jnp.broadcast_to(m_new, m_sc.shape)

    @pl.when(j == n_blocks - 1)
    def _finish():
        l = l_sc[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_sc[:] / l_safe).astype(o_ref.dtype)


def dense_decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                           v_cache: jnp.ndarray, lengths: jnp.ndarray,
                           block_kv: int = 256) -> jnp.ndarray:
    """q [B, nh, hd] (one token per sequence); k/v_cache [B, kvh, M, hd];
    lengths [B] (valid cache tokens incl. the current one). Returns
    [B, nh, hd]."""
    B, nh, hd = q.shape
    _, kvh, M, _ = k_cache.shape
    group = nh // kvh
    # bs need not divide M: the grid covers ceil(M/bs) blocks and Pallas
    # pads the tail block, whose garbage lanes the `pos < length` mask
    # already excludes (length <= M always). Keeping bs large matters —
    # cache lengths are arbitrary user numbers (prompt + max_new_tokens),
    # and degrading to tiny blocks on non-power-of-two M would be a silent
    # perf cliff on the hot decode path.
    bs = min(block_kv, max(8, -(-M // 8) * 8))
    n_blocks = -(-M // bs)  # cdiv
    scale = 1.0 / (hd ** 0.5)
    q4 = q.reshape(B, kvh, group, hd)

    kernel = functools.partial(_kernel, bs=bs, n_blocks=n_blocks,
                               scale=scale, m_total=M)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, kvh, n_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, group, hd), lambda b, h, j, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, hd), lambda b, h, j, ln: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bs, hd), lambda b, h, j, ln: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, hd),
                               lambda b, h, j, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, hd), jnp.float32),
            pltpu.VMEM((group, 128), jnp.float32),
            pltpu.VMEM((group, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, kvh, group, hd), q.dtype),
        name="decode_attention",
        interpret=_interpret(),
    )(lengths.astype(jnp.int32), q4, k_cache, v_cache)
    return out.reshape(B, nh, hd)
