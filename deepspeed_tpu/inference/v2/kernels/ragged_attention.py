"""Ragged paged attention — one Pallas TPU kernel family for every batch.

TPU-native counterpart of the Ragged Paged Attention kernel (PAPERS.md,
arXiv:2604.15464) and of the reference's blocked-flash + atom-builder
pair (inference/v2/kernels/ragged_ops/): ONE kernel consumes a ragged
batch — variable-length prefill chunks, chunked continuations, and
single-token decode rows — as a flat token buffer with per-row paged
block tables, and computes causal attention for every token against the
paged KV pool in a single launch. Pure decode is the same kernel with an
identity token->row map (``paged_attention.py``), so the two can never
diverge numerically.

Descriptor layout (built by ``ragged.batch.RaggedBatch``):

* ``q`` ``[T, nh, hd]`` — the flat new-token buffer: every row's fed
  tokens concatenated, padded to the token bucket ``T``.
* ``row_ids`` ``[T]`` — which batch row each token belongs to (padding
  tokens point at row 0; their ``lengths`` entry is 0 so they attend
  over nothing).
* ``lengths`` ``[T]`` — per-TOKEN causal bound: how many cache positions
  (including the token itself) the token may attend to. For a prefill
  chunk token at absolute position p this is p+1, which is what makes
  causal masking inside a chunk fall out of the same page walk decode
  rows use. 0 marks padding.
* ``block_tables`` ``[R, MB]`` — each row's paged KV block table.

The KV append for the new tokens is the jnp scatter in the surrounding
jitted layer body (``paged_model.paged_ragged_step``) — the same
compiled launch; see the design note in paged_model.py for why the
scatter is XLA's job (it fuses with the qkv projections) while the
Pallas budget goes to the pool reads, which XLA would otherwise
materialize as an [T, max_ctx, ...] gather.

Two variants, chosen STATICALLY from the pool geometry by
:func:`kernel_variant` (the table ``tests/unit/ops/
test_kernels_lower_tpu.py`` pins against the Mosaic compiler):

* ``"dma"`` (grid ``(T,)``, manual DMA). The pools stay HBM-resident
  (``memory_space=ANY``); each token walks only the pages its causal
  bound covers (``ceil(length/bs)``, a dynamic ``fori_loop`` bound) with
  double-buffered ``make_async_copy``, so DMA traffic scales with real
  context length, not table width. Mosaic only accepts the page slice
  ``pool[page]`` when the page's trailing ``(kv_heads, head_dim)`` dims
  are aligned to the pool's HBM tiling, which limits this variant to
  lane-dense geometries (see :func:`kernel_variant`).
* ``"pipelined"`` (grid ``(T, MB)``, BlockSpec-indexed). Streams every
  one of the table's ``MB`` slots per token (compute is skipped past the
  causal bound, the copy is not), but the pipeline emitter pads
  unaligned pages itself, so it compiles at every geometry. It is also
  the variant interpret mode runs off-chip (the manual DMA/semaphore
  protocol wedges under interpret).

Each page step loads the block's K/V for ALL kv heads at once — the
(block_size, kv_heads, head_dim) tile equals the array's trailing dims,
which is what the Mosaic lowering requires. GQA is a static Python loop
over kv heads inside the kernel, each head updating its own rows of the
flat (nh, ...) softmax scratch; position masking handles the partial
last page.

int8 ``kv_quant`` pools: the per-(block, kv-head) scales are gathered by
the row's block table OUTSIDE the kernel (``scale[block_tables]``,
``R*MB*kvh`` floats) and delivered as one ``MB*kvh`` SMEM block per row
— a ``(1, kvh)`` slice of the ``[nb, kvh]`` scale array is not a legal
Mosaic block or DMA slice at any geometry, and a scalar read from SMEM
is the one operand Mosaic broadcasts over a whole (bs, hd) tile. The
kernel dequantizes each head's page slice in VMEM.

Design note — token-grid vs query-tiling: this kernel walks pages per
TOKEN, which makes decode rows optimal but re-streams a prefill chunk's
shared prefix once per chunk token (O(chunk * ctx / bs) page loads
instead of O(ctx / bs) per q-tile). The published RPA kernel tiles
queries per row to amortize that; doing the same here means (q-tile,
page) grid cells with per-row tile maps. The SplitFuse chunk budget
bounds the waste meanwhile.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

VARIANTS = ("dma", "pipelined")


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def kernel_variant(head_dim: int, kv_heads: int, kv_quant: bool) -> str:
    """Which variant serves a ``[nb, bs, kv_heads, head_dim]`` pool on
    the TPU: ``"dma"`` where Mosaic accepts the manual page slice,
    ``"pipelined"`` everywhere else. Decided from static config only —
    never by trying a compile — and pinned row by row against the real
    compiler in tests/unit/ops/test_kernels_lower_tpu.py.

    The DMA slice needs the page's trailing dims aligned to the pool's
    HBM tiling: ``head_dim`` a multiple of the 128 lanes, and
    ``kv_heads`` a whole number of sublane tiles — XLA tiles that dim by
    8 rows, or by 4 when it is exactly 4. bf16 and int8 pools follow the
    same rule (kv_heads 1 and 2 tile differently per dtype and are left
    to the pipelined variant)."""
    if head_dim % 128 == 0 and (kv_heads % 8 == 0 or kv_heads == 4):
        return "dma"
    return "pipelined"


def _page_update(q_ref, k_tile, v_tile, ks, vs, j, length, acc_sc, m_sc,
                 l_sc, *, bs, scale, kvh, group, io_dtype):
    """One page's online-softmax update, all kv heads (shared by both
    variants so their numerics cannot diverge). k_tile/v_tile are the
    page's (bs, kvh, hd) tiles as stored; ks/vs map a head index to the
    page's dequant scale for an int8 pool (None otherwise). The int8
    dequant routes through the pool's serving dtype so it is the SAME
    arithmetic as paged_model._kv_read's gather dequant (bit-identical
    at fp32 io; one rounding at bf16). GQA is a static Python loop (kvh
    is a compile-time constant), each head updating its own rows of the
    flat (kvh*group, ...) scratch."""
    pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (group, bs), 1)
    for h in range(kvh):                              # static unroll (GQA)
        rows = slice(h * group, (h + 1) * group)
        q = q_ref[0, h].astype(jnp.float32)           # (group, hd)
        k = k_tile[:, h, :].astype(jnp.float32)       # (bs, hd)
        v = v_tile[:, h, :].astype(jnp.float32)
        if ks is not None:
            k = (k * ks(h)).astype(io_dtype).astype(jnp.float32)
            v = (v * vs(h)).astype(io_dtype).astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev = m_sc[rows, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_sc[rows] = jnp.broadcast_to(
            l_sc[rows, :1] * corr + jnp.sum(p, axis=1, keepdims=True),
            (group, l_sc.shape[1]))
        acc_sc[rows] = acc_sc[rows] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[rows] = jnp.broadcast_to(m_new, (group, m_sc.shape[1]))


def _init_scratch(acc_sc, m_sc, l_sc):
    acc_sc[:] = jnp.zeros_like(acc_sc)
    m_sc[:] = jnp.full_like(m_sc, NEG_INF)
    l_sc[:] = jnp.zeros_like(l_sc)


def _finalize(o_ref, acc_sc, l_sc, *, kvh, group):
    """Write acc/l to the output block (shared by both variants)."""
    for h in range(kvh):                              # static unroll
        rows = slice(h * group, (h + 1) * group)
        l = l_sc[rows, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, h] = (acc_sc[rows] / l_safe).astype(o_ref.dtype)


def _scale_rows(ks_ref, vs_ref, j, kvh):
    """Page j's per-head scale readers over the row's (1, 1, MB*kvh) SMEM
    block: head -> scalar (a scalar splat is the one broadcast Mosaic
    does over sublanes and lanes at once)."""
    if ks_ref is None:
        return None, None
    return (lambda h: ks_ref[0, 0, j * kvh + h],
            lambda h: vs_ref[0, 0, j * kvh + h])


def _pipelined_kernel(row_ref, len_ref, bt_ref, q_ref, k_ref, v_ref, *rest,
                      quant, n_pages, **static):
    """Grid (T, MB): token t streams page j of ITS row's table (index
    map ``bt[row[t], j]``)."""
    (ks_ref, vs_ref), rest = (rest[:2], rest[2:]) if quant \
        else ((None, None), rest)
    o_ref, acc_sc, m_sc, l_sc = rest
    t = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _init_scratch(acc_sc, m_sc, l_sc)

    length = len_ref[t]

    @pl.when(j * static["bs"] < length)
    def _body():
        ks, vs = _scale_rows(ks_ref, vs_ref, j, static["kvh"])
        _page_update(q_ref, k_ref[0], v_ref[0], ks, vs, j, length,
                     acc_sc, m_sc, l_sc, **static)

    @pl.when(j == n_pages - 1)
    def _finish():
        _finalize(o_ref, acc_sc, l_sc, kvh=static["kvh"],
                  group=static["group"])


def _dma_kernel(row_ref, len_ref, bt_ref, q_ref, k_hbm, v_hbm, *rest,
                quant, **static):
    """Grid (T,): per token, double-buffered manual DMA over the pages
    its causal bound covers, out of its row's table. k_sc/v_sc are
    (2, bs, kvh, hd) VMEM slots; sem is a (2, 2) DMA semaphore array
    (slot x {k, v})."""
    (ks_ref, vs_ref), rest = (rest[:2], rest[2:]) if quant \
        else ((None, None), rest)
    o_ref, k_sc, v_sc, acc_sc, m_sc, l_sc, sem = rest
    bs = static["bs"]
    t = pl.program_id(0)
    row = row_ref[t]
    length = len_ref[t]
    n_pages = (length + bs - 1) // bs

    _init_scratch(acc_sc, m_sc, l_sc)

    def k_dma(slot, j):
        return pltpu.make_async_copy(
            k_hbm.at[bt_ref[row, j]], k_sc.at[slot], sem.at[slot, 0])

    def v_dma(slot, j):
        return pltpu.make_async_copy(
            v_hbm.at[bt_ref[row, j]], v_sc.at[slot], sem.at[slot, 1])

    @pl.when(n_pages > 0)
    def _start():
        k_dma(0, 0).start()
        v_dma(0, 0).start()

    def body(j, _):
        slot = jax.lax.rem(j, 2)
        nxt = jax.lax.rem(j + 1, 2)

        @pl.when(j + 1 < n_pages)
        def _prefetch():
            k_dma(nxt, j + 1).start()
            v_dma(nxt, j + 1).start()

        k_dma(slot, j).wait()
        v_dma(slot, j).wait()
        ks, vs = _scale_rows(ks_ref, vs_ref, j, static["kvh"])
        _page_update(q_ref, k_sc[slot], v_sc[slot], ks, vs, j, length,
                     acc_sc, m_sc, l_sc, **static)
        return 0

    jax.lax.fori_loop(0, n_pages, body, 0)

    _finalize(o_ref, acc_sc, l_sc, kvh=static["kvh"], group=static["group"])


def ragged_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, row_ids: jnp.ndarray,
                     lengths: jnp.ndarray,
                     block_tables: jnp.ndarray,
                     k_scale: jnp.ndarray = None,
                     v_scale: jnp.ndarray = None,
                     variant: Optional[str] = None) -> jnp.ndarray:
    """Ragged paged attention (serving hot path).

    q [T, nh, hd] flat token buffer; k/v_cache [nb, bs, kvh, hd];
    row_ids [T] token -> batch row; lengths [T] per-token causal bound
    (0 = padding); block_tables [R, MB] int32. For the int8 ``kv_quant``
    pool, ``k_scale``/``v_scale`` [nb, kvh] are the per-(block, head)
    dequant scales — the kernel dequantizes in VMEM, so quantized KV
    serves through the SAME one-program ragged family. ``variant``
    defaults to :func:`kernel_variant`'s static choice for the pool
    geometry; off-TPU the pipelined variant runs in interpret mode
    whatever was asked. Returns [T, nh, hd]."""
    T, nh, hd = q.shape
    nb, bs, kvh, _ = k_cache.shape
    MB = block_tables.shape[1]
    group = nh // kvh
    quant = k_scale is not None
    interpret = _interpret()
    if variant is None:
        variant = kernel_variant(hd, kvh, quant)
    if interpret:
        variant = "pipelined"
    if variant not in VARIANTS:
        raise ValueError(f"variant must be one of {VARIANTS}, got {variant!r}")
    dma = variant == "dma"
    block_tables = block_tables.astype(jnp.int32)
    q4 = q.reshape(T, kvh, group, hd)
    static = dict(bs=bs, scale=1.0 / (hd ** 0.5), kvh=kvh, group=group,
                  io_dtype=q.dtype)

    # index maps see (grid indices..., row_ids, lengths, block_tables)
    def tok(t, *_):
        return (t, 0, 0, 0)

    def row_scales(t, *a):
        return (a[-3][t], 0, 0)

    def page(t, j, row, ln, bt):
        return (bt[row[t], j], 0, 0, 0)

    operands = [q4, k_cache, v_cache]
    if dma:
        kernel = functools.partial(_dma_kernel, quant=quant, **static)
        grid = (T,)
        pool_spec = pl.BlockSpec(memory_space=pl.ANY)   # pool stays in HBM
        scratch = [pltpu.VMEM((2, bs, kvh, hd), k_cache.dtype),
                   pltpu.VMEM((2, bs, kvh, hd), v_cache.dtype)]
        sems = [pltpu.SemaphoreType.DMA((2, 2))]
    else:
        kernel = functools.partial(_pipelined_kernel, quant=quant,
                                   n_pages=MB, **static)
        grid = (T, MB)
        pool_spec = pl.BlockSpec((1, bs, kvh, hd), page)
        scratch, sems = [], []
    in_specs = [pl.BlockSpec((1, kvh, group, hd), tok), pool_spec, pool_spec]
    if quant:
        R = block_tables.shape[0]
        for sc in (k_scale, v_scale):
            in_specs.append(pl.BlockSpec((1, 1, MB * kvh), row_scales,
                                         memory_space=pltpu.SMEM))
            operands.append(sc.astype(jnp.float32)[block_tables]
                            .reshape(R, 1, MB * kvh))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, kvh, group, hd), tok),
            scratch_shapes=scratch + [
                pltpu.VMEM((kvh * group, hd), jnp.float32),
                pltpu.VMEM((kvh * group, 128), jnp.float32),
                pltpu.VMEM((kvh * group, 128), jnp.float32),
            ] + sems),
        out_shape=jax.ShapeDtypeStruct((T, kvh, group, hd), q.dtype),
        interpret=interpret,
        name=f"ragged_attention_{variant}",
    )(row_ids.astype(jnp.int32), lengths.astype(jnp.int32), block_tables,
      *operands)
    return out.reshape(T, nh, hd)
