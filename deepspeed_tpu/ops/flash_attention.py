"""Flash attention — Pallas TPU kernels.

TPU-native replacement for the reference's fused attention kernels
(csrc/transformer/ds_transformer_cuda.cpp softmax path, and the inference
attention kernels in csrc/transformer/inference). Implements the
memory-efficient online-softmax algorithm (never materializes the [S, S]
score matrix) as Mosaic kernels that share one block schedule: a forward
and one backward, or, where a head's dQ does not fit VMEM, a forward and
the backward as a pair (dq; dk/dv):

  * forward and backward dq: grid (BH, Sq/bq, kv chunks). A chunk is the
    run of K/V rows one grid step keeps in VMEM — the whole sequence
    when it fits ``_RESIDENT_BYTES``, which is one chunk and one K/V fetch a
    head. Inside a step a ``lax.fori_loop`` walks the chunk in (bq, bk)
    blocks and stops at the causal bound, so blocks above the diagonal
    are neither scheduled nor fetched: with more than one chunk the
    index map clamps to the last live chunk, which names the block
    already resident and Pallas elides the copy.
  * backward (dk/dv, and dq with them): grid (BHkv, Skv/bk, GQA group x q
    chunks), the same walk transposed — q and dO are the resident
    operands, the loop starts at the first live q block, and the scores
    are computed as ``k·qT`` so that ``dv = pT·dO`` and ``dk = dsT·q`` are
    plain matmuls and the group reduction stays in VMEM scratch. The fused
    kernel (``flash_attention_bwd``) takes ``dq += ds·k`` from the same
    tile: the scores, the exp and the mask are made once a block and five
    matmuls do the work of the pair's seven. Its dQ is a float32
    accumulator for every q row under the kv head, carried across the
    kv-block axis and written out at the head's last kv block; it is the
    backward wherever that fits (``_fused_bwd_fits``, from the call's
    shapes: both benchmark cells, GQA 4 at width 128, 4,096 rows).
    Otherwise ``flash_attention_bwd_dkv`` is the same kernel without dQ
    and ``flash_attention_bwd_dq`` runs beside it.

The forward's walk is split in two loops over the same integers: blocks
the diagonal crosses run the masked body, blocks wholly below it run a body
without compare or select. The backward kernels mask every block they
visit (the split measured nothing there on the v5e). ``block_schedule``
counts the visited and the diagonal's blocks from the bounds the kernels
use (``_kv_bounds``/``_q_bounds``) and is published as the gauges
``flash_blocks_{grid,live,masked}``, whose ``kernel`` label (``fwd``;
``bwd``, or ``dq`` and ``dkv``) says which kernels a call took.

Row statistics are lane-dense: lse and delta are ``[BH, 1, Sq]`` float32
(the sequence is the minor dimension; no 128x lane padding in HBM). The
forward keeps its running max and sum lane-replicated ``(bq, 128)`` in
scratch, as the update needs them; dq turns its lse/delta rows into
columns once per q block; the kv-major backward broadcasts them as rows
against the transposed tile. The softmax scale multiplies the float32
scores; dq and dk take it on the float32 accumulator.

Supports causal masking (bottom-right aligned for sq != skv, matching the
usual decode convention; fully-masked rows give zeros) and grouped-query
attention (kv-head indexing in the BlockSpec index map). f32 accumulation
on the MXU (preferred_element_type) with bf16 inputs. Blocks come from
``_auto_blocks``, measured on the v5e per kernel; ``block_q``/``block_kv``
override it for all of them. A q block is the lane dimension of the lse and
delta blocks, so it is a multiple of 128 or the whole of sq (``_plan``).

On non-TPU backends (the CPU test mesh) kernels run in interpret mode;
parity is tested against the jnp reference in tests/unit/ops.
"""

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..telemetry import registry as _registry

NEG_INF = -1e30
_LANES = 128
# VMEM the resident operands of one grid step (K and V; kv-major q and dO)
# may take, their double buffers included. 4 MiB holds 4096 rows of bf16
# at head widths up to 128; the scoped default on a v5e is 16 MiB and the
# score tiles need the rest.
_RESIDENT_BYTES = 4 * 2 ** 20
# VMEM one fused backward call may plan on (``_fused_bwd_fits``). The v5e's
# scoped default is 16 MiB, and where the compiler draws the line moved by
# 1 MiB with the size of the program round the call: two are left.
_FUSED_BWD_BYTES = 14 * 2 ** 20

_NT = (((1,), (1,)), ((), ()))      # a · bT
_NN = (((1,), (0,)), ((), ()))      # a · b
_TN = (((0,), (0,)), ((), ()))      # aT · b


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pick_block(s: int, target: int) -> int:
    """Largest power-of-two-ish divisor of s that is <= target."""
    b = min(target, s)
    while b > 1 and s % b:
        b //= 2
    return max(b, 1)


def _chunk_rows(s: int, block: int, d: int, itemsize: int) -> int:
    """Rows of a [s, d] operand pair one grid step keeps resident: all of
    s when two such operands, double-buffered, fit ``_RESIDENT_BYTES``,
    else the largest divisor of s that does and is a multiple of block."""
    row_bytes = 2 * 2 * max(d, _LANES) * itemsize
    for n in range(1, s // block + 1):
        rows, rem = divmod(s, n)
        if (rem == 0 and rows % block == 0
                and rows * row_bytes <= _RESIDENT_BYTES):
            return rows
    return block


# ---------------------------------------------------------------------------
# Block schedule: which (q block, kv block) pairs a kernel visits
# ---------------------------------------------------------------------------

def _clip(x, lo, hi):
    if all(isinstance(a, int) for a in (x, lo, hi)):
        return min(max(x, lo), hi)
    return jnp.clip(x, lo, hi)


def _kv_bounds(i, c, *, bq, bk, ck, off, causal):
    """For q block i and kv chunk c: (n_full, n_live) in blocks of the
    chunk. Blocks [0, n_full) lie wholly on or below the diagonal, blocks
    [n_full, n_live) are crossed by it, the rest are dead. Works on
    Python ints (``block_schedule``) and on traced ones (the kernels)."""
    n = ck // bk
    if not causal:
        return n, n
    first_q = i * bq + off - c * ck          # relative to the chunk's start
    n_live = _clip((first_q + bq - 1) // bk + 1, 0, n)
    n_full = _clip((first_q + 1) // bk, 0, n_live)
    return n_full, n_live


def _q_bounds(j, c, *, bq, bk, cq, off, causal):
    """For kv block j and q chunk c: (i_lo, i_full) in blocks of the
    chunk. q blocks [i_lo, i_full) are crossed by the diagonal, blocks
    [i_full, cq // bq) lie wholly below it, blocks before i_lo are dead."""
    n = cq // bq
    if not causal:
        return 0, 0
    first_k = j * bk - off - c * cq          # first row that sees block j
    i_lo = _clip(first_k // bq, 0, n)
    i_full = _clip(-((-(first_k + bk - 1)) // bq), i_lo, n)
    return i_lo, i_full


def _last_live_chunk(i, *, bq, ck, n_chunks, off):
    """The kv chunk holding q block i's last visible column (clamped)."""
    return _clip((i * bq + bq - 1 + off) // ck, 0, n_chunks - 1)


def _first_live_chunk(j, *, bk, cq, n_chunks, off):
    """The q chunk holding the first row that sees kv block j (clamped)."""
    return _clip((j * bk - off) // cq, 0, n_chunks - 1)


class BlockSchedule(NamedTuple):
    grid: int       # grid steps a head
    live: int       # (bq, bk) block pairs a kernel body visits
    masked: int     # of those, the ones the diagonal crosses
    fetched: int    # chunk fetches a head (steps naming one chunk share one)


def block_schedule(sq: int, skv: int, bq: int, bk: int, causal: bool, *,
                   chunk: Optional[int] = None,
                   kv_major: bool = False) -> BlockSchedule:
    """What one head costs in grid steps, visited blocks and fetches.

    ``chunk`` is the rows of the inner operand a grid step keeps resident
    (default: one block, i.e. every block is its own grid step and fetch);
    ``kv_major`` counts the dk/dv walk (kv blocks outside, q inside)."""
    off = skv - sq
    n_outer, inner, block = ((skv // bk, sq, bq) if kv_major
                             else (sq // bq, skv, bk))
    chunk = chunk or block
    n_chunks = inner // chunk
    live = masked = fetched = 0
    resident = None                 # the chunk the previous grid step named
    for o in range(n_outer):
        for c in range(n_chunks):
            if kv_major:
                i_lo, i_full = _q_bounds(o, c, bq=bq, bk=bk, cq=chunk,
                                         off=off, causal=causal)
                live += chunk // bq - i_lo
                masked += i_full - i_lo
                named = max(c, _first_live_chunk(
                    o, bk=bk, cq=chunk, n_chunks=n_chunks, off=off))
            else:
                n_full, n_live = _kv_bounds(o, c, bq=bq, bk=bk, ck=chunk,
                                            off=off, causal=causal)
                live += n_live
                masked += n_live - n_full
                named = min(c, _last_live_chunk(
                    o, bq=bq, ck=chunk, n_chunks=n_chunks, off=off))
            named = named if causal else c
            fetched += named != resident
            resident = named
    return BlockSchedule(n_outer * n_chunks, live, masked, fetched)


def _publish(kernel: str, sq: int, skv: int, d: int, sched: BlockSchedule,
             masked: int):
    """Gauges of a kernel's schedule, set while its call is traced;
    ``masked`` is the visited blocks that run the kernel's masked body."""
    reg = _registry.get_registry()
    labels = ("kernel", "geometry")
    at = dict(kernel=kernel, geometry=f"{sq}x{skv}x{d}")
    reg.gauge("flash_blocks_grid", "grid steps a head of a flash kernel",
              labelnames=labels).labels(**at).set(sched.grid)
    reg.gauge("flash_blocks_live", "(bq, bk) blocks a head's walk visits",
              labelnames=labels).labels(**at).set(sched.live)
    reg.gauge("flash_blocks_masked", "visited blocks that build a mask",
              labelnames=labels).labels(**at).set(masked)


def _cost(bh, d, itemsize, sched: BlockSchedule, bq, bk, n_dots, rows_moved):
    """CostEstimate so XLA's scheduler can overlap collectives with the
    kernel (the pallas body is opaque to XLA's own cost analysis): the
    matmuls of the blocks the walk visits, and every operand row once."""
    area = sched.live * bq * bk
    return pl.CostEstimate(
        flops=int(n_dots * 2 * bh * area * d),
        bytes_accessed=int(bh * rows_moved * d * itemsize),
        transcendentals=int(bh * area),
    )


# ---------------------------------------------------------------------------
# In-kernel helpers
# ---------------------------------------------------------------------------

def _lanes(x, n):
    """A lane-replicated (rows, 128) statistic as (rows, n)."""
    if n <= _LANES:
        return x if n == _LANES else x[:, :n]
    reps = pl.cdiv(n, _LANES)
    x = jnp.tile(x, (1, reps))
    return x if reps * _LANES == n else x[:, :n]


def _walk(lo, hi, body):
    """body(j) for j in [lo, hi); state lives in scratch refs."""
    def step(j, carry):
        body(j)
        return carry
    jax.lax.fori_loop(lo, hi, step, 0)


def _kv_chunk_map(group, causal, bq, ck, n_chunks, off):
    """Index map of the resident K/V chunk for grid (head, q block, chunk):
    a dead step names the last live chunk, which is already in VMEM."""
    def kv_map(b, i, c):
        if causal and n_chunks > 1:
            c = jnp.minimum(c, _last_live_chunk(i, bq=bq, ck=ck,
                                                n_chunks=n_chunks, off=off))
        return b // group, c, 0
    return kv_map


def _rel(shape, q_axis):
    """Position of a tile's q index minus its kv index, before the block's
    own offset: an element is visible iff this is >= that offset."""
    return (jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
            - jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis))


def _dot(a, b, dims):
    # keep dots in the input dtype (bf16 runs the MXU at full rate; f32
    # matmul is ~8x slower) with f32 accumulation
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_sc, m_sc, l_sc,
                *, scale, causal, bq, bk, ck, n_chunks, off):
    i = pl.program_id(1)  # q block
    c = pl.program_id(2)  # kv chunk
    d = q_ref.shape[-1]

    @pl.when(c == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    q = q_ref[0]                                     # (bq, d)
    n_full, n_live = _kv_bounds(i, c, bq=bq, bk=bk, ck=ck, off=off,
                                causal=causal)
    if causal:
        rel = _rel((bq, bk), q_axis=0)

    def block(j, masked):
        rows = pl.ds(pl.multiple_of(j * bk, bk), bk)
        k = k_ref[0, rows, :]                        # (bk, d)
        v = v_ref[0, rows, :]
        s = _dot(q, k, _NT) * scale                  # (bq, bk) f32
        if masked:
            s = jnp.where(rel >= c * ck + j * bk - i * bq - off, s, NEG_INF)
        m_prev = m_sc[:]                             # (bq, 128)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # rows fully masked so far have m_new == NEG_INF; exp(s - m_new)
        # would be exp(0) = 1 garbage — substitute 0 so exp(NEG_INF) == 0
        m_safe = (jnp.where(m_new <= NEG_INF * 0.5, 0.0, m_new)
                  if masked else m_new)
        p = jnp.exp(s - _lanes(m_safe, bk))          # (bq, bk) f32
        corr = jnp.exp(m_prev - m_new)               # (bq, 128)
        l_sc[:] = l_sc[:] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_sc[:] = acc_sc[:] * _lanes(corr, d) + _dot(p.astype(v.dtype), v,
                                                       _NN)
        m_sc[:] = m_new

    _walk(0, n_full, lambda j: block(j, False))
    if causal:
        _walk(n_full, n_live, lambda j: block(j, True))

    @pl.when(c == n_chunks - 1)
    def _finish():
        l = l_sc[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_sc[:] / _lanes(l_safe, d)).astype(o_ref.dtype)
        lse_ref[0] = _col_to_row(m_sc[:] + jnp.log(l_safe))


def _col_to_row(x):
    """A lane-replicated (rows, 128) statistic as one lane-dense (1, rows)
    row: per group of 128 rows, the diagonal of the square the group
    spans, summed over sublanes."""
    pieces = []
    for lo in range(0, x.shape[0], _LANES):
        n = min(_LANES, x.shape[0] - lo)
        eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
        pieces.append(jnp.sum(jnp.where(eye, x[lo:lo + n, :n], 0.0), axis=0,
                              keepdims=True))
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces, axis=1)


def _row_to_col(ref):
    """A (1, 1, rows) block of a lane-dense statistic as a lane-replicated
    (rows, 128) column."""
    col = ref[0, 0][:, None]
    return jnp.broadcast_to(col, (col.shape[0], _LANES))


def _flash_fwd(q, k, v, scale, causal, bq, bk):
    bh, sq, d = q.shape
    bhk, skv, _ = k.shape
    group = bh // bhk
    off = skv - sq
    ck = _chunk_rows(skv, bk, d, k.dtype.itemsize)
    n_q, n_chunks = sq // bq, skv // ck
    sched = block_schedule(sq, skv, bq, bk, causal, chunk=ck)
    _publish("fwd", sq, skv, d, sched, sched.masked)

    kv_map = _kv_chunk_map(group, causal, bq, ck, n_chunks, off)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, bq=bq, bk=bk, ck=ck,
        n_chunks=n_chunks, off=off)
    o, lse = pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_chunks),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, c: (b, i, 0)),
            pl.BlockSpec((1, ck, d), kv_map),
            pl.BlockSpec((1, ck, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, c: (b, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, i, c: (b, 0, i)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
        ],
        cost_estimate=_cost(bh, d, q.dtype.itemsize, sched, bq, bk, n_dots=2,
                            rows_moved=2 * sq + 2 * skv // group),
        name="flash_attention_fwd",
        interpret=_interpret(),
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_sc, *, scale, causal, bq, bk, ck, n_chunks, off):
    i = pl.program_id(1)
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    q = q_ref[0]
    do = do_ref[0]
    lse = _row_to_col(lse_ref)                       # (bq, 128)
    # fully-masked rows carry lse == NEG_INF; exp(s - lse) would be 1
    lse = jnp.where(lse <= NEG_INF * 0.5, 0.0, lse)
    delta = _row_to_col(delta_ref)
    _, n_live = _kv_bounds(i, c, bq=bq, bk=bk, ck=ck, off=off, causal=causal)
    if causal:
        rel = _rel((bq, bk), q_axis=0)

    def block(j):
        rows = pl.ds(pl.multiple_of(j * bk, bk), bk)
        k = k_ref[0, rows, :]
        v = v_ref[0, rows, :]
        s = _dot(q, k, _NT) * scale
        if causal:
            s = jnp.where(rel >= c * ck + j * bk - i * bq - off, s, NEG_INF)
        p = jnp.exp(s - _lanes(lse, bk))             # (bq, bk) f32
        dp = _dot(do, v, _NT)
        ds = (p * (dp - _lanes(delta, bk))).astype(k.dtype)
        dq_sc[:] += _dot(ds, k, _NN)

    _walk(0, n_live, block)

    @pl.when(c == n_chunks - 1)
    def _finish():
        dq_ref[0] = (dq_sc[:] * scale).astype(dq_ref.dtype)


def _bwd_kv_major_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         *refs, scale, causal, bq, bk, cq, n_kv, n_chunks,
                         n_inner, off, fused):
    """dK and dV of one kv block: the walk over the q rows that see it.
    ``fused`` takes dQ on the same walk, from the tile it already holds:
    ``dq_sc`` is the float32 dQ of every q row under this kv head (GQA
    group x sq), carried across the kv-block axis."""
    if fused:
        dq_ref, dk_ref, dv_ref, dq_sc, dk_sc, dv_sc = refs
    else:
        dk_ref, dv_ref, dk_sc, dv_sc = refs
    j = pl.program_id(1)   # kv block (outer)
    e = pl.program_id(2)   # inner: q-heads of the GQA group x q chunks
    c = e % n_chunks       # q chunk within the head
    acc = pl.multiple_of(e * cq, cq)     # this step's first row of dq_sc

    @pl.when(e == 0)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    if fused:
        @pl.when(j == 0)
        def _init_dq():
            # every row, not those the first kv block's walk visits: rows
            # that see no key (sq > skv) are reached by no walk
            dq_sc[pl.ds(acc, cq), :] = jnp.zeros((cq, dq_sc.shape[1]),
                                                 dq_sc.dtype)

    k = k_ref[0]                                     # (bk, d)
    v = v_ref[0]
    i_lo, _ = _q_bounds(j, c, bq=bq, bk=bk, cq=cq, off=off, causal=causal)
    if causal:
        # transposed tile: rows are kv positions, lanes are q positions
        rel = _rel((bk, bq), q_axis=1)

    def block(i):
        start = pl.multiple_of(i * bq, bq)
        q = q_ref[0, pl.ds(start, bq), :]            # (bq, d)
        do = do_ref[0, pl.ds(start, bq), :]
        lse = lse_ref[0, :, pl.ds(start, bq)]        # (1, bq)
        delta = delta_ref[0, :, pl.ds(start, bq)]
        st = _dot(k, q, _NT) * scale                 # (bk, bq) f32
        if causal:
            st = jnp.where(rel >= j * bk - off - c * cq - i * bq, st,
                           NEG_INF)
        lse = jnp.where(lse <= NEG_INF * 0.5, 0.0, lse)
        pt = jnp.exp(st - lse)                       # (bk, bq) f32
        dv_sc[:] += _dot(pt.astype(do.dtype), do, _NN)
        dpt = _dot(v, do, _NT)
        dst = (pt * (dpt - delta)).astype(q.dtype)
        dk_sc[:] += _dot(dst, q, _NN)
        if fused:
            # Mosaic places the transpose; on the v5e it costs nothing
            # (PERF.md, PR 28: equal to an explicit dst.T and to none)
            rows = pl.ds(pl.multiple_of(acc + start, bq), bq)
            dq_sc[rows, :] += _dot(dst, k, _TN)

    _walk(i_lo, cq // bq, block)

    @pl.when(e == n_inner - 1)
    def _finish():
        dk_ref[0] = (dk_sc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)

    if fused:
        @pl.when(j == n_kv - 1)
        def _finish_dq():
            dq_ref[0] = (dq_sc[pl.ds(acc, cq), :] * scale).astype(
                dq_ref.dtype)


def _row_dots(do, o):
    """delta = rowsum(dO * O) in float32 over the last axis: what the
    backward reads of the forward's output."""
    return jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)


def _fused_bwd_fits(group, sq, cq, bq, bk, d, itemsize):
    """Whether taking dQ on the dK/dV walk fits VMEM: a float32 dQ row for
    every q row under a kv head (GQA group x sq) and the double-buffered
    output chunk, beside what the walk holds anyway (the q and dO chunks;
    the k, v, dk and dv blocks with their accumulators) and two score
    tiles. Rows are padded to the lanes. Compiled for the v5e at group 1
    to 14, sq 2048 to 8192, widths 64 and 128 and eight block shapes, the
    kernel was refused from ``held + 1.5 tiles`` = 15 to 16 MiB on."""
    held = (group * sq * 4 + (2 + 4) * cq * itemsize
            + bk * (8 * itemsize + 8)) * max(d, _LANES)
    return held + 2 * bq * bk * 4 <= _FUSED_BWD_BYTES


def _kv_major_plan(blocks, group, sq, d, itemsize):
    """(fused, bq, bk, rows of q and dO a step keeps) of the kv-major
    backward: the fused kernel at its own blocks where it fits, else dk/dv
    at theirs, with dq apart."""
    for fused, (bq, bk) in ((True, blocks[3]), (False, blocks[2])):
        cq = _chunk_rows(sq, bq, d, itemsize)
        if not fused or _fused_bwd_fits(group, sq, cq, bq, bk, d, itemsize):
            return fused, bq, bk, cq


def _flash_bwd(q, k, v, do, lse, delta, scale, causal, blocks):
    """dq, dk, dv of folded ``[bh, s, d]`` operands; ``lse`` and ``delta``
    are ``[bh, 1, sq]`` float32. One kernel where the head's dQ fits VMEM
    (``_fused_bwd_fits``), else dq and dk/dv apart."""
    bh, sq, d = q.shape
    bhk, skv, _ = k.shape
    group = bh // bhk
    off = skv - sq
    itemsize = q.dtype.itemsize

    # dk/dv: grid over kv heads; the inner axis walks every q chunk of every
    # q-head in the GQA group, accumulating in VMEM scratch — the group
    # reduction happens in-register instead of a second [bh, skv, d] HBM pass.
    fused, bq, bk, cq = _kv_major_plan(blocks, group, sq, d, itemsize)
    n_kv, n_chunks = skv // bk, sq // cq
    n_inner = group * n_chunks
    sched = block_schedule(sq, skv, bq, bk, causal, chunk=cq, kv_major=True)
    # the backward kernels mask every block they visit: splitting their
    # walks bought nothing on the chip (PERF.md, PR 26)
    _publish("bwd" if fused else "dkv", sq, skv, d, sched,
             sched.live if causal else 0)

    def q_chunk(b, j, e):
        c = e % n_chunks
        if causal and n_chunks > 1:
            c = jnp.maximum(c, _first_live_chunk(j, bk=bk, cq=cq,
                                                 n_chunks=n_chunks, off=off))
        return b * group + e // n_chunks, c

    def q_map(b, j, e):
        return (*q_chunk(b, j, e), 0)

    def row_map(b, j, e):
        head, c = q_chunk(b, j, e)
        return head, 0, c

    def dq_map(b, j, e):
        # written at a head's last kv block; until then every step names
        # the block that step will write first, and nothing is copied out
        last = j == n_kv - 1
        return (b * group + jnp.where(last, e // n_chunks, 0),
                jnp.where(last, e % n_chunks, 0), 0)

    kv_spec = pl.BlockSpec((1, bk, d), lambda b, j, e: (b, j, 0))
    out_specs = [kv_spec, kv_spec]
    out_shape = [jax.ShapeDtypeStruct((bhk, skv, d), k.dtype),
                 jax.ShapeDtypeStruct((bhk, skv, d), v.dtype)]
    scratch = [pltpu.VMEM((bk, d), jnp.float32)] * 2
    if fused:
        out_specs.insert(0, pl.BlockSpec((1, cq, d), dq_map))
        out_shape.insert(0, jax.ShapeDtypeStruct(q.shape, q.dtype))
        scratch.insert(0, pltpu.VMEM((group * sq, d), jnp.float32))
    outs = pl.pallas_call(
        functools.partial(_bwd_kv_major_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, cq=cq, n_kv=n_kv, n_chunks=n_chunks,
                          n_inner=n_inner, off=off, fused=fused),
        grid=(bhk, n_kv, n_inner),
        in_specs=[pl.BlockSpec((1, cq, d), q_map), kv_spec, kv_spec,
                  pl.BlockSpec((1, cq, d), q_map),
                  pl.BlockSpec((1, 1, cq), row_map),
                  pl.BlockSpec((1, 1, cq), row_map)],
        out_specs=out_specs,
        scratch_shapes=scratch,
        out_shape=out_shape,
        cost_estimate=_cost(bh, d, itemsize, sched, bq, bk,
                            n_dots=5 if fused else 4,
                            rows_moved=(3 if fused else 2) * sq
                            + 4 * skv // group),
        # dq, dk and dv take the place of q, k and v, each block written
        # after its last read: with what XLA pads a [bh, s, 64] array to,
        # three arrays fewer at the step's peak
        input_output_aliases={0: 0, 1: 1, 2: 2} if fused else {},
        name="flash_attention_bwd" if fused else "flash_attention_bwd_dkv",
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)
    if fused:
        return tuple(outs)
    dk, dv = outs

    bq, bk = blocks[1]
    ck = _chunk_rows(skv, bk, d, itemsize)
    n_q, n_chunks = sq // bq, skv // ck
    sched = block_schedule(sq, skv, bq, bk, causal, chunk=ck)
    _publish("dq", sq, skv, d, sched, sched.live if causal else 0)

    kv_map = _kv_chunk_map(group, causal, bq, ck, n_chunks, off)
    q_spec = pl.BlockSpec((1, bq, d), lambda b, i, c: (b, i, 0))
    row_spec = pl.BlockSpec((1, 1, bq), lambda b, i, c: (b, 0, i))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal, bq=bq,
                          bk=bk, ck=ck, n_chunks=n_chunks, off=off),
        grid=(bh, n_q, n_chunks),
        in_specs=[q_spec, pl.BlockSpec((1, ck, d), kv_map),
                  pl.BlockSpec((1, ck, d), kv_map), q_spec, row_spec,
                  row_spec],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        cost_estimate=_cost(bh, d, itemsize, sched, bq, bk, n_dots=3,
                            rows_moved=3 * sq + 2 * skv // group),
        name="flash_attention_bwd_dq",
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def _fold(x):
    """[b, h, s, d] -> [b*h, s, d]: batch folded into the head axis, the
    kernels' grid axis (kv-head grouping stays contiguous)."""
    b, h, s, d = x.shape
    return x.reshape(b * h, s, d)


def _heads_to_tokens(o, b):
    """[b*h, s, d] -> [b, s, h*d]: the layout the output projection reads,
    and the one HBM holds without padding (a minor dimension of 64 is
    padded to the 128 lanes: twice the bytes)."""
    bh, s, d = o.shape
    return o.reshape(b, bh // b, s, d).transpose(0, 2, 1, 3).reshape(
        b, s, bh // b * d)


def _tokens_to_heads(o, d):
    """[b, s, h*d] -> [b, h, s, d]."""
    b, s, hd = o.shape
    return o.reshape(b, s, hd // d, d).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_core(q, k, v, scale, causal, blocks):
    o, _ = _flash_fwd(_fold(q), _fold(k), _fold(v), scale, causal,
                      *blocks[0])
    return o.reshape(q.shape)


def _flash_core_fwd(q, k, v, scale, causal, blocks):
    b, _, _, d = q.shape
    o, lse = _flash_fwd(_fold(q), _fold(k), _fold(v), scale, causal,
                        *blocks[0])
    # The two residuals only the kernel can make are named, so that a
    # checkpoint policy can keep them (runtime/activation_checkpointing:
    # ATTN_NAMES, the save_attn policy): with both saved, nothing in the
    # recomputed layer reads the forward pallas_call and it is dropped as
    # dead code. o is kept as [b, s, h*d], which HBM does not pad (lse's
    # [bh, 1, sq] is tiled T(1,128): no padding either). The output is
    # derived from the named o, so that what follows it in a recomputed
    # layer reads the saved array too. q, k and v are cheap to make again
    # and stay unnamed.
    o = checkpoint_name(_heads_to_tokens(o, b), "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return _tokens_to_heads(o, d), (q, k, v, o, lse)


def _flash_core_bwd(scale, causal, blocks, res, g):
    q, k, v, o, lse = res
    b, h, sq, d = q.shape
    # the kernels read o only through delta: taken in the layout o is kept
    # in, against dO in the same one (under the model this undoes the
    # transpose that made g, and XLA drops the pair), so o never goes
    # back to the kernels' padded [bh, s, d]
    do_tok = _heads_to_tokens(_fold(g), b)
    delta = _row_dots(do_tok.reshape(b, sq, h, d), o.reshape(b, sq, h, d))
    dq, dk, dv = _flash_bwd(
        _fold(q), _fold(k), _fold(v), _fold(g), lse,
        delta.transpose(0, 2, 1).reshape(b * h, 1, sq), scale, causal, blocks)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def _auto_blocks(causal):
    """(block_q, block_kv) targets for the forward, dq, dk/dv and fused
    backward kernels, read off scripts/flash_kernel_table.py --sweep on a
    v5e (PERF.md, PR 26 and PR 28): block_q 128-1024 x block_kv 128-2048,
    each kernel alone. Causal calls took 512 x 512 in every kernel at
    every shape swept (head width 64 and 128, GQA group 1 and 4, sq ==
    skv at 2048 and 4096, sq 1024 under skv 2048), so the diagonal is the
    table's one key; with none to waste work on, non-causal calls took the
    whole 2048 rows of K/V a block (4 / 16 / 6 % over 512 x 512) in the
    first three, and 1024 in the fused backward, whose VMEM the 4 MiB score
    tiles of 512 x 2048 would take."""
    return (((512, 512),) * 4 if causal
            else ((512, 2048),) * 3 + ((512, 1024),))


def _plan(q_shape, k_shape, causal, scale, block_q, block_kv):
    """(scale, ((bq, bk) for forward, dq, dk/dv, fused backward)) of a
    call."""
    _, h, sq, d = q_shape
    _, hk, skv, _ = k_shape
    assert h % hk == 0, f"GQA requires h({h}) % hk({hk}) == 0"
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    blocks = tuple(
        (_pick_block(sq, block_q or tq), _pick_block(skv, block_kv or tk))
        for tq, tk in _auto_blocks(causal))
    for bq, _ in blocks:
        if bq % _LANES and bq != sq:
            raise ValueError(
                f"flash attention's q block is the lane dimension of its "
                f"lse and delta blocks: it has to be a multiple of {_LANES} "
                f"or all of sq, and sq={sq} with block_q={block_q} gives "
                f"{bq}")
    return scale, blocks


def flash_attention(q, k, v, causal: bool = True, scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_kv: Optional[int] = None):
    """Flash attention over [batch, num_heads, seq, head_dim] inputs.

    k/v may have fewer heads (GQA); num_heads % num_kv_heads == 0.
    block_q/block_kv None (or 0) = auto: ``_auto_blocks``, capped to the
    seq lens; a value overrides every kernel's.
    """
    scale, blocks = _plan(q.shape, k.shape, causal, scale, block_q, block_kv)
    return _flash_core(q, k, v, scale, causal, blocks)


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
