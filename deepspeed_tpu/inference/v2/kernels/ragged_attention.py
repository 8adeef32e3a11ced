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
* ``k_cache`` / ``v_cache`` ``[L, nb, bs, kvh * hd]`` and ``layer`` —
  the pool's leaves as stored, WHOLE, and which layer to attend (a
  traced scalar, prefetched): a page is ``pool[layer, block]``, ``bs``
  lane-dense rows, and it is read where it lies.

The KV append for the new tokens is the jnp scatter in the surrounding
jitted layer body (``paged_model.paged_ragged_step``) — the same
compiled launch; see the design note in paged_model.py for why the
scatter is XLA's job (it fuses with the qkv projections) while the
Pallas budget goes to the pool reads, which XLA would otherwise
materialize as an [T, max_ctx, ...] gather.

Two variants, chosen STATICALLY from the pool geometry by
:func:`kernel_variant` (the table ``tests/unit/ops/
test_kernels_lower_tpu.py`` pins against the Mosaic compiler):

* ``"tiled"`` — the page walk goes per ROW. Grid ``(T / tq,)``: a step
  owns a tile of up to 128 flat tokens and walks the rows whose tokens
  lie in it (prefill: one row a tile; a mixed step: whatever rows the
  tile's tokens belong to; decode: see the one-token form below). THE
  TILE (:func:`_token_tile`) is a power of two of 16 to 128 tokens, at
  most 1,024 query rows a lane block (tokens x heads of the block x GQA
  group), read off the launch's bucket and the pool's geometry alone:
  every tile re-reads its row's context and pays for its own zeroed
  state, first chunk and normalise, and every chunk visit for its 64
  copy starts and the state's read and write, whatever the tile holds,
  so half the tiles are half of all that for the same products. A
  row's pages arrive in chunks of ``_CHUNK_POSITIONS`` positions by
  ``make_async_copy`` out of its block table, double-buffered across
  chunks AND rows, up to the causal bound of the row's last token in the
  tile (a dynamic bound: no page past it is copied or visited), so a
  prefill chunk's keys are read once a tile and not once a token. The
  pool stays in HBM (``memory_space=ANY``) and a page is copied by
  ``k_hbm.at[layer, page]``: ``bs`` rows of ``kvh * hd`` lanes, whole
  tiles that move their own bytes; a 128-lane block of it is two
  64-wide heads or one head a multiple of 128 wide.
  A block's query rows — tile tokens x GQA group x heads of the block,
  each zero outside its own head's lanes — go through the matrix unit
  together on the pool's bf16 with float32 accumulation
  (:func:`_tile_update`); tokens of other rows and positions past a
  token's own bound are masked, which is all in-tile causality is.
  THE UPDATE IS TRANSPOSED: a chunk's scores are ``k q^T``, a position a
  sublane and a query row a LANE, and the float32 state lies the same
  way (max and sum ``(1, rows)``, the accumulator ``v^T p``, turned back
  once a tile), so that a row's max and sum run down the vector
  registers elementwise; along the lanes each was a cross-lane
  reduction a register of eight rows, a chunk and a lane block, and the
  two were half of a prompt launch at the 8k-context cells (PERF.md
  section 6, PR 59, which also measured that an update with no mask for
  the chunks a tile sees whole gains nothing: every chunk is masked).
  Serves every geometry :func:`tiled_geometry` accepts.
  THE ONE-TOKEN FORM (``one_token=True``, static: the caller knows every
  row has exactly one token, as ``paged_attention`` and the pattern's
  decode step do): a grid step owns ``_ONE_TOKEN_ROWS`` rows and walks
  them the same way, but the queries lie token-major, so a chunk goes
  through the matrix unit against the WALKED ROW'S OWN query rows (a
  lane block's ``hpb * group``, padded to a sublane tile) and not
  against the tile's 16 tokens' of which 15 would be masked; the
  float32 state is one row's, started at its first chunk and written to
  its place in the output tile behind its last (:func:`_walk_rows`'
  ``begin`` / ``finish``); a position is masked by the row's bound and
  window alone (:func:`_row_visible`). The same chunks in the same order
  on the same products: the token tile's output to the order of the
  float32 sums. The launch keeps its name. The latent kernel takes the
  same form over all ``nh`` heads.
  A CHUNK'S COPIES (:func:`_walk_rows`) are started A RUN OF PAGES A
  DESCRIPTOR where the row's table says its places lie on consecutive
  blocks (:func:`table_runs`, made once a program by the caller and
  prefetched beside the table: one copy ``pool[layer, b : b + 32]`` for
  a chunk that lies together, the sizes in a shorter run's binary
  digits), and a page at a time, two pages a trip of the loop, where
  they lie alone or the launch is handed no runs: a page of 16
  positions is 16 KB a leaf at 4 kv heads of 128, and 64 starts a chunk
  in one instruction stream with the products were a quarter of a
  decode launch at 8k contexts (PERF.md section 6, PR 67). They are
  waited for by their BYTES: one wait
  a semaphore for a whole chunk, the powers of two in its pages for a
  partial one (:func:`_chunk_waits`). And in the one-token form a chunk
  meets ALL its lane blocks' query rows in one update
  (:func:`_blocks_update`): the blocks' scores stacked, one mask, max,
  exp and sum over them and the state read and written once, where a
  block at a time was ``nblk`` chains of product, softmax and product
  one behind the other, whose latency and not the matrix unit's intake
  was what a chunk cost beside its bytes (PERF.md section 6, PR 50).
* ``"pipelined"`` (grid ``(T, MB)``, BlockSpec-indexed): one step a
  token and table slot, the page a ``(1, 1, bs, kvh * hd)`` block of
  the same stored leaf by the index map ``(layer, bt[row, j])``, its
  heads cut by static lane slices in a static loop
  (:func:`_page_update`). Streams every slot (compute is skipped past
  the causal bound, the copy is not), but the pipeline emitter pads
  unaligned pages itself, so it compiles where a page row is not whole
  128-lane blocks of whole heads (a lone 64-wide head, 80- and 96-wide
  heads, toy widths).

Off the TPU each variant runs under its interpreter when asked for by
name (the tiled one under ``pltpu.InterpretParams``: DMAs, semaphores and
all), so tier-1 holds both to the gather reference chip-free; the
engine's programs default to the pipelined one there, whose interpreter
is several times the faster.

int8 ``kv_quant`` pools: the per-(block, kv-head) scales are gathered by
the row's block table OUTSIDE the kernel (``scale[block_tables]``,
``R*MB*kvh`` floats) and reach SMEM — pipelined: one ``MB*kvh`` block a
row; tiled: a chunk's slice by DMA beside its pages — because a scalar
read from SMEM is the one operand Mosaic broadcasts over a whole tile.
Both dequantize in VMEM through the pool's serving dtype, the arithmetic
of ``paged_model._kv_read``.

What the pool is, and why (``paged_model.init_paged_kv_cache``): a
layer is stored ``[nb, bs, kvh * hd]``. With ``(kvh, hd)`` as the minor
axes, bf16 tiles of ``(16, 128)`` padded a 64-wide head to 128 lanes, no
lane-dense view of a layer was a bitcast, and every launch paid for the
layer's slice, a reshape pass and four pool-sized relayout copies round
the kernel (3.0 s of a 4.8 s ``generate()`` call at OPT-1.3B; PERF.md
section 6, PR 34 and 37). Stored lane-dense the pool has one layout at
every head width, a page is contiguous, and both variants and the
latent kernel address it the same way: the whole leaf, the layer a
scalar. ``tests/unit/ops/test_kernels_lower_tpu.py`` pins, by AOT, that
the serving programs hold no slice, reshape or copy of a layer.
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ....utils.bucketing import pow2_bucket

NEG_INF = -1e30

VARIANTS = ("tiled", "pipelined")
# the latent pool's one variant, as ``engine.attention_impl`` names it
LATENT = "latent"

# the tiled kernel's VMEM: two slots of K and V chunks, the query and
# output tiles twice, the float32 state — 12 MB at OPT-1.3B's geometry,
# over the 16 MB the compiler grants by default once a group is wider
_TILED_VMEM_BYTES = 64 * 2 ** 20


# positions of a row fetched and computed at a time (tuned on the chip)
_CHUNK_POSITIONS = 512

# rows a grid step of the one-token form walks
_ONE_TOKEN_ROWS = 16

# pages whose copies one trip of the walk's start loop issues (a guarded
# tail behind the whole trips); tuned on the chip: 2 read 6-10 % under 1 at
# the latent and the 8k-context decode launches and the same where the
# copies' bytes bound the launch, 4 read 1-6 % over 2 (PERF.md section 6,
# PR 50)
_START_UNROLL = 2

# the pages a run must hold to be copied as one (:func:`table_runs`): a
# shorter run's pages are started one by one, because a segment of the walk
# costs a lookup and a trip of a loop, which a start or two saved do not pay
# for; read on the chip (PERF.md section 6, PR 67)
_RUN_PAGES = 8

# a page of a leaf must be UNDER this many bytes for the launches over its
# pool to be handed their tables' runs (:func:`runs_serve`); read on the chip
_RUN_PAGE_BYTES = 32 * 1024


def _chunk_pages(MB: int, nb: int, bs: int) -> int:
    """Pages a chunk of the walk: ``_CHUNK_POSITIONS`` positions, and no
    more than a row's table has places or the pool blocks (a chunk's
    waiting descriptor names that many blocks of the pool)."""
    return max(1, min(MB, nb, _CHUNK_POSITIONS // bs))


def table_runs(block_tables, cp: int):
    """How a row's table LIES, place by place: ``[R, MB]`` int32 beside
    ``block_tables`` ``[R, MB]``, read off the table and nothing else.
    Places ``p`` and ``p + 1`` are LINKED where the second's block is the
    first's plus one. At place ``p``:

    * ``+s`` (2 or more): a RUN starts here, ``s`` places whose blocks are
      ``b .. b + s - 1``, which one copy ``pool[layer, b : b + s]`` brings
      (:func:`_walk_rows` cuts it into a few descriptors of static sizes);
    * ``-t`` (1 or more): the next ``t`` places are each linked to nothing
      behind them, and are copied a page at a time.

    A run of under ``_RUN_PAGES`` pages in all is no run: its links are
    struck out first and its pages lie alone (what is left of a LONGER run
    behind a chunk's first place or ahead of its end is still a run,
    however short).

    Both count no further than the table's last place (a ring's run never
    wraps) and are capped a little over ``cp``: the walk takes ``min(., the
    chunk's pages left)``, which is how a run ends at a chunk's end. A
    place's entry holds for a walk that STARTS there (a chunk's first
    page, or the place behind the run or the stretch before), so a
    run's last page reads as a lone one: it is, for a chunk that starts
    on it. The null block links to nothing that matters: padding is
    zeros, and ``0, 0`` is no run.

    ``numpy`` in, ``numpy`` out (the host's counter,
    :func:`copy_counts`); traced, a few shifted selects (the count of
    equal neighbours by doubling: ``log2(cp)`` steps, no scan and no
    cumulative minimum, which lowers to half a megabyte of TPU code a
    table). The serving programs make it ONCE, ahead of the layers
    (``paged_model.paged_ragged_step`` / ``paged_decode_window``), and
    hand it to every attention launch as a prefetched scalar array."""
    xp = np if isinstance(block_tables, np.ndarray) else jnp
    R, MB = block_tables.shape

    def shifted(a, d):
        """``a`` ``d`` places further on (``d`` < 0: further back), zeros
        past the table's ends"""
        if abs(d) >= MB:
            return xp.zeros_like(a)
        pad = xp.zeros((R, abs(d)), a.dtype)
        return xp.concatenate([a[:, d:], pad] if d > 0
                              else [pad, a[:, :d]], axis=1)

    def counted(a, step, cap):
        """At each place, the ones of ``a`` from there on without a gap
        (``step`` -1: from there back), capped at ``cap`` or a little
        over: by doubling"""
        d = 1
        while d < cap:
            a = a + xp.where(a == d, shifted(a, step * d), 0)
            d *= 2
        return a

    bt = block_tables.astype(xp.int32)
    last = xp.arange(MB, dtype=xp.int32)[None, :] == MB - 1
    link = (shifted(bt, 1) == bt + 1) & ~last
    # a run of under ``_RUN_PAGES`` pages is no run: its pages lie alone
    links = link.astype(xp.int32)
    link = link & (counted(links, 1, _RUN_PAGES) + counted(
        links, -1, _RUN_PAGES) >= _RUN_PAGES)
    # places from p on whose link is p's own, less one
    same = counted(((shifted(link, 1) == link) & ~last).astype(xp.int32),
                   1, cp)
    return xp.where(link, same + 2, -(same + 1)).astype(xp.int32)


def _run_sizes(cp: int):
    """The static sizes (pages) of a run's descriptors, largest first:
    the powers of two up to ``cp``. A run of ``s`` pages (``s <= cp``)
    is started as the sizes in ``s``'s binary digits, each at the pages
    the larger ones leave (:func:`_run_cut`): one descriptor for a whole
    chunk of 32, at most five for any other length."""
    return tuple(1 << i for i in reversed(range(cp.bit_length())))


def _run_cut(s: int, cp: int):
    """``[(offset, size)]``: the descriptors :func:`_walk_rows` starts for
    a run of ``s`` pages, whole numbers in and out (the kernel makes the
    same cut on a traced ``s``; the host's counter and a test read it
    here). The sizes add up to ``s`` for every ``s`` in ``0..cp``."""
    return [(s & ~(2 * size - 1), size) for size in _run_sizes(cp)
            if s & size]


def runs_serve(page_bytes: int) -> bool:
    """Whether a launch over a pool whose page is ``page_bytes`` a leaf is
    handed its tables' runs (static: shapes alone). Where a page's copy is
    32 KB and more its bytes already take as long as its start, the launch
    stands on its bytes and a run's one start gains nothing, while the
    lookup a chunk is not free: read on the chip at the benchmark's
    decode launches (PERF.md section 6, PR 67: pages of 8 and 16 KB gain
    20 to 32 % over a pool that lies together, granite's 32 KB 0.7 %,
    OPT-1.3B's 64 KB lose 1 %). Such a launch is the program it was."""
    return page_bytes < _RUN_PAGE_BYTES


def launch_runs(block_tables, k_cache, head_dim: int):
    """:func:`table_runs` of ``block_tables`` for the launches that walk
    the pool ``k_cache`` ``[L, nb, bs, kvh * head_dim]`` by them, or None
    where such a launch does not read it (static): off the TPU and
    wherever the pipelined variant serves, which copy a page a grid
    step, and over a pool whose pages are too large to gain by it
    (:func:`runs_serve`)."""
    nb, bs, F = k_cache.shape[1:]
    if _interpret() or tiled_geometry(head_dim, F // head_dim) is None \
            or not runs_serve(bs * F * k_cache.dtype.itemsize):
        return None
    return table_runs(block_tables, _chunk_pages(block_tables.shape[1], nb,
                                                 bs))


def _sublane_tiles(rows: int) -> int:
    """``rows`` query rows padded to whole (8, 128) tiles and no further"""
    return -(-rows // 8) * 8


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def kernel_variant(head_dim: int, kv_heads: int, kv_quant: bool) -> str:
    """Which variant serves a pool of ``kv_heads`` heads of ``head_dim``
    (stored ``[L, nb, bs, kv_heads * head_dim]`` whichever it is):
    ``"tiled"`` wherever a page row splits into whole 128-lane blocks of
    whole heads (:func:`tiled_geometry`), ``"pipelined"`` everywhere
    else. Decided from static config only — never by trying a compile —
    and pinned row by row against the real compiler in
    tests/unit/ops/test_kernels_lower_tpu.py; bf16 and int8 pools follow
    the same rule."""
    return "tiled" if tiled_geometry(head_dim, kv_heads) else "pipelined"


def _page_update(q_ref, k_ref, v_ref, ks, vs, j, length, acc_sc, m_sc,
                 l_sc, *, bs, scale, kvh, group, io_dtype, window=0):
    """One page's online-softmax update, all kv heads (shared by both
    variants so their numerics cannot diverge). k_ref/v_ref are the
    page's (1, 1, bs, kvh * hd) block as stored, a head its ``hd`` lanes
    (a static slice); ks/vs map a head index to the page's dequant scale
    for an int8 pool (None otherwise). The int8
    dequant routes through the pool's serving dtype so it is the SAME
    arithmetic as paged_model._kv_read's gather dequant (bit-identical
    at fp32 io; one rounding at bf16). GQA is a static Python loop (kvh
    is a compile-time constant), each head updating its own rows of the
    flat (kvh*group, ...) scratch. ``j`` is the page's index among the
    row's POSITIONS (``window``: positions under ``length - window`` are
    masked too)."""
    pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (group, bs), 1)
    seen = pos < length
    if window:
        seen = seen & (pos >= length - window)
    hd = q_ref.shape[-1]
    for h in range(kvh):                              # static unroll (GQA)
        rows = slice(h * group, (h + 1) * group)
        lanes = slice(h * hd, (h + 1) * hd)
        q = q_ref[0, h].astype(jnp.float32)           # (group, hd)
        k = k_ref[0, 0, :, lanes].astype(jnp.float32)  # (bs, hd)
        v = v_ref[0, 0, :, lanes].astype(jnp.float32)
        if ks is not None:
            k = (k * ks(h)).astype(io_dtype).astype(jnp.float32)
            v = (v * vs(h)).astype(io_dtype).astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale
        s = jnp.where(seen, s, NEG_INF)
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


def _pipelined_kernel(layer_ref, row_ref, len_ref, bt_ref, q_ref, k_ref,
                      v_ref, *rest, quant, n_pages, **static):
    """Grid (T, MB): token t streams page j of ITS row's table out of
    the whole pool (index map ``(layer, bt[row[t], j])``). With a
    ``window`` the table is a ring (position p at place ``(p // bs) %
    MB``): place j holds the newest page of the token's positions that
    is congruent to j, and is computed where that page reaches into the
    window."""
    (ks_ref, vs_ref), rest = (rest[:2], rest[2:]) if quant \
        else ((None, None), rest)
    o_ref, acc_sc, m_sc, l_sc = rest
    t = pl.program_id(0)
    j = pl.program_id(1)
    bs, window = static["bs"], static.get("window", 0)

    @pl.when(j == 0)
    def _init():
        _init_scratch(acc_sc, m_sc, l_sc)

    length = len_ref[t]
    if window:
        newest = jnp.maximum(length - 1, 0) // bs
        page = newest - (newest - j) % n_pages
        live = (length > 0) & (page >= 0) \
            & ((page + 1) * bs > length - window)
    else:
        page, live = j, j * bs < length

    @pl.when(live)
    def _body():
        ks, vs = _scale_rows(ks_ref, vs_ref, j, static["kvh"])
        _page_update(q_ref, k_ref, v_ref, ks, vs, page, length,
                     acc_sc, m_sc, l_sc, **static)

    @pl.when(j == n_pages - 1)
    def _finish():
        _finalize(o_ref, acc_sc, l_sc, kvh=static["kvh"],
                  group=static["group"])


def tiled_geometry(head_dim: int, kv_heads: int):
    """``(lane-block width, kv heads a block)`` of the tiled variant, or
    None where a page's ``kv_heads * head_dim`` row does not split into
    whole 128-lane blocks of whole heads. A block is one head (width a
    multiple of 128) or the ``128 // head_dim`` heads that share 128
    lanes. A LATENT pool (attention='mla') is not asked here: its page
    row is one row that every head reads whole, whatever its width (576
    is four and a half lane blocks), and :func:`latent_attention` serves
    it."""
    if (kv_heads * head_dim) % 128:
        return None
    if head_dim % 128 == 0:
        return head_dim, 1
    if 128 % head_dim == 0:
        return 128, 128 // head_dim
    return None


def one_token_tile_serves(latent: bool, head_dim: int, kv_heads: int) -> bool:
    """Whether the attention launches of a model's DECODE programs take
    the ONE-TOKEN form here: on a TPU (off it those programs run the
    pipelined variant and the latent pool's gathering reference, which
    have no tile to speak of), over a latent pool or a pool whose
    geometry the tiled variant serves. That every row of such a launch
    has one token is static (``paged_attention``,
    ``_pattern_step(one_token=True)``) and the kernels take the form
    wherever they are told so: every geometry the benchmark holds gained
    by it on the chip (PERF.md section 6, PR 47: 32 heads of a latent
    row 2.4 x, GQA group 8 at width 128 1.14 x and 1.27 x with a window,
    two 64-wide heads a lane block 1.04 x), so none is left on the token
    tile. What the engine's ``inference_attention_one_token_steps_total``
    counts by."""
    return not _interpret() and (
        latent or tiled_geometry(head_dim, kv_heads) is not None)


def decode_positions(contexts, bs: int, table_pages: int, pool_blocks: int,
                     window: int = 0):
    """``(held, chunked)`` of the one-token form's launches whose rows'
    bounds are ``contexts`` (whole numbers, one a row and launch): the
    positions their rows hold, to whole pages (with a ``window``, from
    the window's first page), and the positions their chunks hold whole,
    as :func:`_walk_rows` cuts them from a table of ``table_pages``
    places over a pool of ``pool_blocks``. ``held / chunked`` is the
    share of a chunk that is there: the copies bring ``held``, the
    products run over ``chunked``. Host arithmetic on what the host
    knows (numpy in, ints out): the engine's
    ``inference_attention_decode_positions_total``."""
    cp = _chunk_pages(table_pages, pool_blocks, bs)
    _, pages = decode_walks(contexts, bs, window)
    return (int(pages.sum()) * bs, int((-(-pages // cp)).sum()) * cp * bs)


def _ones(x):
    """The ones in each whole number's binary digits (numpy)"""
    x = np.asarray(x, np.int64)
    return sum((x >> i) & 1 for i in range(max(int(x.max(initial=0)), 1)
                                           .bit_length()))


def copy_counts(tables, rows, first, pages, cp: int, ring: int = 0,
                runs: bool = True):
    """``(pages, descriptors)``: what ONE leaf's copies of these walks
    bring and in how many starts, by the cut :func:`_walk_rows` makes.
    Walk i is row ``rows[i]``'s of ``tables`` (the host's, ``[R, MB]``
    numpy): ``pages[i]`` pages from the row's page ``first[i]`` on, in
    chunks of ``cp`` from there, a page at place ``page % ring`` (``ring``
    0: at its own place). ``runs`` False: the launch was handed no runs,
    a start a page. A chunk's descriptors are, of each run of linked
    places it holds (clipped to the chunk and to the ring's last place),
    the ones in its length's binary digits (:func:`_run_cut`), and one a
    page that lies alone: read off prefix sums over the table, no loop
    over pages or chunks (a test holds it to the kernel's own cut, walked
    chunk by chunk). Host arithmetic on what the host holds (numpy in,
    ints out): the engine's ``inference_attention_copy_pages_total`` /
    ``..._descriptors_total``; ``pages / descriptors`` is 1.0 over a pool
    with no two neighbouring places on neighbouring blocks and ``cp``
    where every chunk lies together."""
    rows, first, pages = (np.asarray(a, np.int64) for a in
                          (rows, first, pages))
    keep = pages > 0
    rows, first, pages = rows[keep], first[keep], pages[keep]
    total = int(pages.sum())
    if not runs or not total:
        return total, total
    tables = np.asarray(tables)
    R, MB = tables.shape
    W = ring or MB
    link = np.zeros((R, W + 1), bool)           # place p to p + 1
    link[:, :W] = table_runs(
        np.ascontiguousarray(tables[:, :W]), cp) > 0
    before = np.zeros((R, W + 1), bool)         # place p - 1 to p
    before[:, 1:] = link[:, :-1]
    at = np.arange(W + 1)[None, :]
    # the run a place lies in: where it starts and ends (exclusive)
    start = np.maximum.accumulate(
        np.where(link & ~before, at, -1), axis=1)
    end = np.minimum.accumulate(
        np.where(~link, at, W)[:, ::-1], axis=1)[:, ::-1] + 1
    in_run = link | before
    # prefix sums: the lone pages, and every run's descriptors at its start
    lone = np.zeros((R, W + 1), np.int64)
    lone[:, 1:] = np.cumsum(~in_run[:, :W], axis=1)
    whole = np.zeros((R, W + 1), np.int64)
    whole[:, 1:] = np.cumsum(
        np.where(link & ~before, _ones(end - at), 0)[:, :W], axis=1)

    def pieces(r, a, b):
        """Descriptors of places [a, b) of row r: no wrap, b - a <= cp"""
        n = lone[r, b] - lone[r, a] + whole[r, b] - whole[r, a]
        # the run a lies in, where a is not its first page
        head = in_run[r, a] & (start[r, a] < a)
        n = n + np.where(head, _ones(np.minimum(end[r, a], b) - a), 0)
        # a run that starts in [a, b) and ends past b
        z = b - 1
        tail = in_run[r, z] & (end[r, z] > b) & (start[r, z] >= a)
        n = n + np.where(tail, _ones(b - start[r, z])
                         - _ones(end[r, z] - start[r, z]), 0)
        return int(n.sum())

    # a lane a (walk, chunk)
    chunks = -(-pages // cp)
    walk = np.repeat(np.arange(pages.size), chunks)
    c = np.arange(walk.size) - np.repeat(np.cumsum(chunks) - chunks, chunks)
    r = rows[walk]
    a = first[walk] + c * cp
    n = np.minimum(pages[walk] - c * cp, cp)
    if not ring:
        return total, pieces(r, a, a + n)
    a = a % ring
    b = a + n
    over = b > ring                     # a run ends at the ring's last place
    return total, pieces(r, a, np.minimum(b, ring)) + pieces(
        r[over], np.zeros_like(a[over]), b[over] - ring)


def launch_copies(tables, rows, first, pages, bs: int, pool_blocks: int,
                  page_bytes: int, ring: int = 0):
    """:func:`copy_counts` of the walks as a launch over ``tables`` (its
    pool ``pool_blocks`` pages of ``bs`` positions, ``page_bytes`` a
    leaf) makes them: the chunk it cuts from the table's width, and the
    table's runs where such a launch is handed them
    (:func:`runs_serve`)."""
    MB = ring or np.shape(tables)[1]
    return copy_counts(tables, rows, first, pages,
                       _chunk_pages(MB, pool_blocks, bs), ring,
                       runs=runs_serve(page_bytes))


def _token_tile(tokens: int, rpb: int) -> int:
    """Tokens a grid step of the token tile owns, from the launch's
    token bucket and a lane block's query rows a token (``rpb`` = heads a
    block x GQA group): a power of two of 16 to 128, at most 1,024 query
    rows a lane block where that leaves 16, and no more than the launch
    has. A tile pays its own fixed part (the state zeroed, a first chunk
    nothing hides, the normalise) and a chunk visit its starts and its
    state's read and write whatever the tile holds, and a row's keys are
    read once a TILE: 1,024 rows halve all of it where 512 left the
    matrix unit a quarter used (PERF.md section 6, PR 59)."""
    return max(16, min(pow2_bucket(tokens, 128),
                       1 << (max(1024 // rpb, 1).bit_length() - 1)))


def token_tile(tokens: int, heads: int, head_dim: int, kv_heads: int) -> int:
    """:func:`_token_tile` of a launch of ``tokens`` (its bucket) by a
    model's widths, for a host that counts what the launch walks
    (:func:`prompt_chunks`)."""
    return _token_tile(tokens, tiled_geometry(head_dim, kv_heads)[1]
                       * (heads // kv_heads))


def token_tile_serves(head_dim: int, kv_heads: int) -> bool:
    """Whether a ragged step's attention launches are the token tile's
    here: on a TPU (off it the step runs the pipelined variant, a token
    a grid step), over a pool whose geometry the tiled variant serves.
    What the engine's ``inference_attention_prompt_chunks_total`` counts
    by."""
    return not _interpret() and tiled_geometry(head_dim, kv_heads) is not None


def prompt_chunks(new, contexts, bs: int, table_pages: int, pool_blocks: int,
                  window: int = 0, tq: int = 128):
    """``(whole, masked)``: the chunk visits of ONE token-tile launch
    whose rows feed ``new`` tokens each (packed in order) that end at
    ``contexts`` (the last token's bound), in tiles of ``tq``, as
    :func:`_walk_rows` cuts the chunks from a table of ``table_pages``
    places over a pool of ``pool_blocks``: a visit is ``whole`` where
    the tile holds ``tq`` tokens of one row that all see every position
    of the chunk (its last under the lowest bound and, with a
    ``window``, its first not under the highest bound's window),
    ``masked`` otherwise (an edge of the bound or the window, a tile of
    several rows or of a row's tail). The visits are what a launch's
    time goes by (a chunk's copies started and its state read and
    written whatever the tile holds) and what a larger tile halves; the
    kernel masks every one: an update with no mask for the whole ones
    read 0.5-6 % SLOWER on the chip, not faster (PERF.md section 6,
    PR 59).
    Host arithmetic on what the host knows (numpy in, ints out): the
    engine's ``inference_attention_prompt_chunks_total``."""
    _, lo, hi, held = _tile_rows(new, contexts, tq)
    if not lo.size:
        return 0, 0
    P = _chunk_pages(table_pages, pool_blocks, bs) * bs
    base = np.maximum(lo - window, 0) // bs * bs if window else 0
    chunks = -(-(hi - base) // P)
    # chunk c is whole for c in [c0, c1]: its end under the lowest bound,
    # its start inside the highest bound's window
    c1 = np.minimum((lo - base) // P, chunks) - 1
    c0 = np.maximum(-(-(hi - window - base) // P), 0) if window else 0
    whole = np.where(held == tq, np.maximum(c1 - c0 + 1, 0), 0)
    return int(whole.sum()), int((chunks - whole).sum())


def _tile_rows(new, contexts, tq: int):
    """One entry a (row, tile) pair of a token-tile launch whose rows feed
    ``new`` tokens each (packed in order) that end at ``contexts``, in
    tiles of ``tq``: ``(row, lo, hi, held)``, the row (its index in
    ``new``), the bounds of the first and the last of its tokens in the
    tile, and how many of them the tile holds. What :func:`_walk_rows`'
    ``bounds`` reads, on the host (numpy)."""
    new = np.asarray(new, np.int64)
    fed = np.flatnonzero(new > 0)
    ctx, new = np.asarray(contexts, np.int64)[fed], new[fed]
    end = np.cumsum(new)                    # a row's tokens: [tok0, end)
    tok0 = end - new
    tiles = (end - 1) // tq - tok0 // tq + 1        # a row's tokens lie in
    row = np.repeat(np.arange(new.size), tiles)
    tile = np.arange(row.size) - np.repeat(np.cumsum(tiles) - tiles, tiles) \
        + (tok0 // tq)[row]
    first = np.maximum(tile * tq, tok0[row])
    last = np.minimum(tile * tq + tq - 1, end[row] - 1)
    bound0 = ctx[row] - end[row] + 1         # a token's bound less its index
    return fed[row], bound0 + first, bound0 + last, last - first + 1


def prompt_walks(new, contexts, bs: int, window: int = 0, tq: int = 128):
    """``(rows, first page, pages)`` of ONE token-tile launch's walks, one
    a (row, tile) pair (:func:`_tile_rows`): the tile walks the row's
    pages from the one that holds the first position its earliest token
    sees (page 0 without a ``window``) to the one that holds its last
    token's bound. What :func:`copy_counts` takes."""
    row, lo, hi, _ = _tile_rows(new, contexts, tq)
    first = np.maximum(lo - window, 0) // bs if window \
        else np.zeros_like(lo)
    return row, first, -(-hi // bs) - first


def decode_walks(contexts, bs: int, window: int = 0):
    """``(first page, pages)`` of the one-token form's walks of rows whose
    bounds are ``contexts`` (one a row and launch), as
    :func:`decode_positions` counts their positions."""
    ctx = np.asarray(contexts, np.int64)
    first = np.maximum(ctx - window, 0) // bs if window \
        else np.zeros_like(ctx)
    return first, -(-ctx // bs) - first


def _visible(tl_ref, t0, first, last, c, tq, reps, P, base=0, window=0):
    """``(reps * tq, P)``: which of chunk c's positions each query row
    of the tile may attend. Query rows are ``reps`` copies of the tile's
    tokens; a token outside [first, last] (another row's) sees nothing,
    one inside sees the positions under its own causal bound
    (``tl_ref``, (tq, 1)) and, with a ``window``, not under ``bound -
    window``. ``base``: the position the row's chunk 0 starts at (0
    without a window: the walk starts at the row's first page)."""
    tok = t0 + jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)
    eff = jnp.where((tok >= first) & (tok <= last), tl_ref[...], 0)
    eff = jnp.concatenate([eff] * reps, axis=0)
    pos = c * P + jax.lax.broadcasted_iota(jnp.int32, (reps * tq, P), 1)
    if not window:
        return pos < eff
    pos = pos + base
    return (pos < eff) & (pos >= eff - window)


def _row_visible(bound, c, rows, P, base=0, window=0):
    """:func:`_visible` in the one-token form, ``(rows, P)``: every
    query row is the walked row's own token, so what it may attend of
    chunk c is the positions under that token's ``bound`` (a scalar)
    and, with a ``window``, not under ``bound - window``."""
    pos = base + c * P + jax.lax.broadcasted_iota(jnp.int32, (rows, P), 1)
    if not window:
        return pos < bound
    return (pos < bound) & (pos >= bound - window)


def _tile_visible(tb_ref, t0, first, last, c, tq, P, base=0, window=0):
    """:func:`_visible` as the token tile reads it, ``(P, Mp)``: chunk
    c's positions down the sublanes, the tile's query rows along the
    lanes (row ``m`` is token ``m % tq`` of the tile; ``tb_ref``, ``(1,
    1, Mp)``, holds each row's causal bound, 0 for a row that pads the
    lanes). A token outside [first, last] sees nothing."""
    col = jax.lax.broadcasted_iota(jnp.int32, tb_ref.shape[1:], 1)
    tok = t0 + (col & (tq - 1))
    eff = jnp.where((tok >= first) & (tok <= last), tb_ref[0], 0)
    pos = base + c * P + jax.lax.broadcasted_iota(
        jnp.int32, (P, eff.shape[1]), 0)
    if not window:
        return pos < eff
    return (pos < eff) & (pos >= eff - window)


def _when(cond, fn):
    """``fn()`` where ``cond`` holds: at once for a Python bool (the
    tests' whole numbers), under ``pl.when`` for a traced scalar."""
    if isinstance(cond, bool):
        if cond:
            fn()
    else:
        pl.when(cond)(fn)


def _chunk_waits(n, cp, wait):
    """Wait for the ``n`` pages (1..cp) whose copies a chunk started.
    A DMA semaphore counts bytes, so ``wait(size)`` waits on ONE
    descriptor of ``size`` pages (never started: its bytes are all it is
    for): a whole chunk is one wait of ``cp`` pages, a partial one the
    powers of two in ``n``, at most ``log2(cp)`` waits where a page's
    copy used to be waited for by itself. The sizes add up to ``n``
    exactly (a test holds that for every n), or the semaphore would be
    left with bytes the next chunk of the slot reads as its own."""
    def powers():
        for i in range((cp - 1).bit_length()):
            _when((n >> i) & 1 == 1, lambda i=i: wait(1 << i))
    _when(n == cp, lambda: wait(cp))
    _when(n < cp, powers)


def _walk_rows(len_ref, bt_ref, first_ref, last_ref, lo, hi, *, t0, tq, bs,
               cp, copies, waits, compute, chunk_copies=None, window=0,
               ring=0, begin=None, finish=None, runs_ref=None,
               run_copies=None):
    """The walk the tiled and the latent kernel share: the tile of flat
    tokens [t0, t0 + tq) visits the rows ``lo..hi`` that own its tokens,
    a row's pages in chunks of ``cp`` up to the causal bound of the
    row's last token in the tile — no page past it is copied or
    visited. ``copies(page, slot, j)`` are the DMAs that bring pool page
    ``page`` to place j of VMEM slot ``slot``: a start a page, because a
    page is where the table says. They are WAITED FOR BY THEIR BYTES:
    ``waits(slot, size)`` are descriptors of ``size`` pages on the same
    semaphores, one a semaphore, only ever waited on, and a chunk waits
    on the few whose sizes add up to its pages (:func:`_chunk_waits`:
    one for a whole chunk). ``chunk_copies(r, c, slot)`` are the copies
    a whole chunk needs besides, started and waited for as they are.
    The next chunk, of this row or the next, is in flight while
    ``compute(first, last, c, slot, base)`` runs on this one ([first,
    last]: the row's tokens in the tile; ``base``: the position the
    row's chunk 0 starts at).
    ``begin(first)`` / ``finish(first)``, where given (the one-token
    form), run before a row's first chunk is computed and behind its
    last.

    ``window`` > 0 (static): a token sees only its last ``window``
    positions, so a row's walk STARTS at the page that holds the first
    position its earliest token in the tile sees (no page wholly below
    that is copied or visited), chunk c is the ``cp`` pages from there,
    and ``base`` is that page's first position (0 without a window).
    The row's table is then a ring of ``ring`` places: the page of
    positions ``[b * bs, (b + 1) * bs)`` is ``bt_ref[r, b % ring]``
    (a table that holds every position is a ring that never wraps).

    ``runs_ref`` (:func:`table_runs` of the table, beside it; None: every
    page is started by itself, the walk as it always was): a chunk's
    copies are started A RUN OF PAGES A DESCRIPTOR. A run of ``s`` pages
    on blocks ``b .. b + s - 1`` is started as the few ``run_copies(b +
    off, slot, j + off, size)`` of static sizes that add up to it
    (:func:`_run_cut`: one for a chunk that lies together), a stretch of
    pages that lie alone a page at a time as ever, two a trip. ONE lookup
    at the chunk's first place says which it is for the whole chunk,
    nearly always; a chunk that holds several segments (and every chunk
    of a ring, which may turn its last place) is walked segment by
    segment, a lookup each. A segment ends at the chunk's end and at the
    ring's last place. The bytes that land in a slot, their places and
    the waits are the same, so ``compute`` sees the same chunk to the
    last bit."""
    P, T = cp * bs, len_ref.shape[0]

    def bounds(r):
        """Row r's tokens in this tile [first, last], the causal bound
        of the last of them (0: the row has no token here) and the first
        page of its walk."""
        r = jnp.minimum(r, first_ref.shape[0] - 1)   # hi + 1 is asked too
        first = jnp.maximum(first_ref[r], t0)
        last = jnp.minimum(last_ref[r], t0 + tq - 1)
        kv = jnp.where(first <= last, len_ref[jnp.clip(last, 0, T - 1)], 0)
        if not window:
            return first, last, kv, 0
        low = len_ref[jnp.clip(first, 0, T - 1)] - window
        return first, last, kv, jnp.maximum(low, 0) // bs

    def n_chunks(r):
        _, _, kv, page0 = bounds(r)
        if not window:
            return (kv + P - 1) // P
        # a row with no token here has no bound and no window either
        return jnp.maximum(kv - page0 * bs + P - 1, 0) // P

    def next_row(r):
        return jax.lax.while_loop(
            lambda r: (r <= hi) & (n_chunks(r) == 0), lambda r: r + 1, r)

    def pages(r, c):
        _, _, kv, page0 = bounds(r)
        if not window:
            return jnp.minimum((kv + bs - 1) // bs - c * cp, cp)
        return jnp.minimum((kv + bs - 1) // bs - page0 - c * cp, cp)

    def start(r, c, slot):
        """Start every copy of chunk c of row r: ``_START_UNROLL`` pages
        a trip of the loop, the pages of a last, partial trip each under
        its own guard."""
        n = pages(r, c)
        at0 = c * cp
        if window:
            # one remainder a chunk: a place is under ``ring`` again by
            # one subtraction, since a chunk is no longer than the ring
            at0 = (bounds(r)[3] + at0) % ring

        def each(count, one):
            """``one(i)`` for i under ``count``: ``_START_UNROLL`` a trip of
            the loop, a last, partial trip's each under its own guard"""
            def trip(g, _):
                for u in range(_START_UNROLL):                # static
                    one(g * _START_UNROLL + u)
                return 0
            whole = count // _START_UNROLL
            jax.lax.fori_loop(0, whole, trip, 0)
            for u in range(_START_UNROLL - 1):                # the tail
                i = whole * _START_UNROLL + u
                pl.when(i < count)(functools.partial(one, i))

        def place(j):
            at = at0 + j
            if window:
                at = jnp.where(at >= ring, at - ring, at)
            return at

        def one(j):
            for dma in copies(bt_ref[r, place(j)], slot, j):
                dma.start()

        def lone(j0, at, count):
            """``count`` pages a copy each: places ``at ..`` of the table
            (no wrap among them) to the slot's ``j0 ..``"""
            def page(i):
                for dma in copies(bt_ref[r, at + i], slot, j0 + i):
                    dma.start()
            each(count, page)

        def run(j0, block, count):
            """``count`` pages that lie together from ``block``, as the
            sizes in ``count``'s binary digits (:func:`_run_cut`)"""
            for size in _run_sizes(cp):                       # static
                def some(size=size):
                    off = count & ~(2 * size - 1)
                    for dma in run_copies(block + off, slot, j0 + off, size):
                        dma.start()
                pl.when(count & size != 0)(some)

        def segment(j):
            at = place(j)
            lies = runs_ref[r, at]
            count = jnp.minimum(jnp.abs(lies), n - j)
            pl.when(lies > 0)(lambda: run(j, bt_ref[r, at], count))
            pl.when(lies < 0)(lambda: lone(j, at, count))
            return j + count

        def segments():
            jax.lax.while_loop(lambda j: j < n, segment, jnp.int32(0))

        if runs_ref is None:
            each(n, one)
        elif window:
            # a chunk may turn the ring's last place: segment by segment
            # (a fast path ahead of the loop read 3.6 % SLOWER over a pool
            # with no run in it, where this reads the parent's time)
            segments()
        else:
            # one lookup says how the whole chunk lies, nearly always:
            # alone, page by page exactly as ever (2 % over the parent's
            # launch on a pool with no run in it, where the loop alone
            # reads 6 %), or together, one run; else segment by segment
            lies = runs_ref[r, at0]
            whole_lone, whole_run = lies <= -n, lies >= n
            pl.when(whole_lone)(lambda: each(n, one))
            pl.when(whole_run)(lambda: run(0, bt_ref[r, at0], n))
            pl.when(~(whole_lone | whole_run))(segments)
        if chunk_copies is not None:
            for dma in chunk_copies(r, c, slot):
                dma.start()

    def wait(r, c, slot):
        def pages_of(size):
            for dma in waits(slot, size):
                dma.wait()
        _chunk_waits(pages(r, c), cp, pages_of)
        if chunk_copies is not None:
            for dma in chunk_copies(r, c, slot):
                dma.wait()

    r0 = next_row(lo)

    @pl.when(r0 <= hi)
    def _first():
        start(r0, 0, 0)

    def step(state):
        r, c, slot = state
        last = c + 1 >= n_chunks(r)
        nr = jax.lax.cond(last, lambda: next_row(r + 1), lambda: r)
        nc = jnp.where(last, 0, c + 1)

        @pl.when(nr <= hi)
        def _prefetch():
            start(nr, nc, 1 - slot)

        wait(r, c, slot)
        first, last_tok, _, page0 = bounds(r)
        if begin is not None:
            pl.when(c == 0)(lambda: begin(first))
        compute(first, last_tok, c, slot, page0 * bs)
        if finish is not None:
            pl.when(last)(lambda: finish(first))
        return nr, nc, 1 - slot

    jax.lax.while_loop(lambda state: state[0] <= hi, step,
                       (r0, jnp.int32(0), jnp.int32(0)))


def _row_hooks(one_token, finish, acc_sc, m_sc, l_sc):
    """:func:`_walk_rows`' hooks of the one-token form (none otherwise):
    the float32 state starts afresh at a row's first chunk, and
    ``finish`` writes the row's output behind its last."""
    if not one_token:
        return {}
    return dict(begin=lambda _: _init_scratch(acc_sc, m_sc, l_sc),
                finish=finish)


def _tile_update(q, k, v, visible, acc_sc, m_sc, l_sc, b, *, scale):
    """One lane block's online-softmax update over one KV chunk, the
    token tile's: q ``(Mp, bw)`` (the rows of the block's query heads,
    each zero outside its own kv head's lanes), k/v ``(P, bw)`` as
    stored (bf16 on the chip: the products are exact in the float32
    accumulator), visible ``(P, Mp)`` which positions each query row may
    attend (none for a token of another row).
    TRANSPOSED: the scores are ``k q^T``, ``(P, Mp)``, a query row a
    LANE and a position a sublane, so that a row's max and sum run DOWN
    the vector registers, elementwise, and its statistics ``(1, Mp)``
    spread over the scores by a sublane broadcast; along the lanes each
    was a cross-lane reduction a register of eight rows, and the two
    were half a prompt launch's time (PERF.md section 6, PR 59). The
    state is transposed with them: max and sum ``(1, Mp)``, the
    accumulator ``v^T p``, ``(bw, Mp)``.
    Max, sum and accumulator stay float32; p is rounded to the pool's
    dtype for the second product, as ops/flash_attention.py does. A
    masked score is 2 * NEG_INF under a running max that starts at
    NEG_INF, so a row with nothing to attend yet adds exp(NEG_INF) = 0
    and keeps its state."""
    s = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(visible, s, 2 * NEG_INF)
    m_prev = m_sc[b]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_sc[b] = l_sc[b] * corr + jnp.sum(p, axis=0, keepdims=True)
    acc_sc[b] = acc_sc[b] * corr + jax.lax.dot_general(
        v, p.astype(v.dtype), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_sc[b] = m_new


def _blocks_update(q, k, v, visible, acc_sc, m_sc, l_sc, *, scale):
    """:func:`_tile_update` of every lane block at once (the one-token
    form): q a list of ``(M, bw)``, k / v lists of ``(P, bw)``, one a
    lane block; the blocks' scores are stacked ``(nblk * M, P)`` so that
    the mask, max, exp and sums run ONCE over all of them and the state
    is read and written once, where a block at a time is ``nblk`` chains
    of product, softmax and product one behind the other. The same
    sums a row: a row's max and sum run over its own P scores and each
    product is the block's own."""
    nblk, M = len(q), q[0].shape[0]
    rows = nblk * M

    def flat(ref):
        return ref[...].reshape(rows, ref.shape[-1])
    s = jnp.concatenate([jax.lax.dot_general(
        q[b], k[b], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) for b in range(nblk)],
        axis=0) * scale
    s = jnp.where(visible, s, 2 * NEG_INF)
    m_prev = flat(m_sc)[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_sc[...] = jnp.broadcast_to(
        flat(l_sc)[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True),
        (rows, l_sc.shape[-1])).reshape(l_sc.shape)
    p = p.astype(v[0].dtype)
    pv = jnp.concatenate([jax.lax.dot_general(
        p[b * M:(b + 1) * M], v[b], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) for b in range(nblk)], axis=0)
    acc_sc[...] = (flat(acc_sc) * corr + pv).reshape(acc_sc.shape)
    m_sc[...] = jnp.broadcast_to(
        m_new, (rows, m_sc.shape[-1])).reshape(m_sc.shape)


def _tiled_kernel(layer_ref, len_ref, bt_ref, first_ref, last_ref, lo_ref,
                  hi_ref, q_ref, tl_ref, k_hbm, v_hbm, *rest, quant, bs,
                  scale, kvh, hd, hpb, group, tq, cp, io_dtype, window=0,
                  ring=0, one_token=False, runs_ref=None):
    """Grid (T / tq,): one step a tile of ``tq`` flat tokens. The tile
    walks the rows that own its tokens (``lo_ref``/``hi_ref``), a row's
    pages in chunks of ``cp`` up to the causal bound of the row's last
    token in the tile — no page past it is copied or visited. A chunk's
    pages go by ``make_async_copy`` from the HBM pool into one of two
    VMEM slots; the next chunk (of this row or the next) is in flight
    while this one is computed. The pools are the stored leaves whole,
    ``(L, nb, bs, F)``, the layer a prefetched scalar: a page is
    ``k_hbm.at[layer, page]``, ``bs`` rows of ``F = kvh * hd`` lanes,
    whole tiles where it lies; k_buf/v_buf are (2, cp, bs, F); sem is
    (2, 2) = slot x {k, v}, a start a page and a chunk's pages waited
    for by their bytes (one wait a semaphore where the chunk is whole).
    The token tile's float32 state is TRANSPOSED, a query row a lane
    (:func:`_tile_update`): acc_sc ``(nblk, bw, Mp)``, m_sc / l_sc
    ``(nblk, 1, Mp)``, ``Mp`` the tile's ``rpb * tq`` query rows a lane
    block to whole lane tiles, and tl_ref ``(1, 1, Mp)`` their bounds.

    ``one_token`` (static): every row has ONE token, and the tile is
    ``tq`` rows walked one after another. The queries lie token-major,
    ``(tq, nblk, rows, bw)`` (a lane block's ``hpb * group`` query rows
    padded to whole sublane tiles), so a chunk goes through the matrix
    unit against the walked row's own query rows and nothing else; the
    float32 state is ONE row's, started at the row's first chunk and
    written to the row's place in the output tile ``(tq, nblk, group,
    bw)`` behind its last; a position is masked by the row's bound (and
    window) alone, and a chunk's lane blocks are updated all at once
    (:func:`_blocks_update`).

    ``runs_ref`` (:func:`table_runs`, prefetched beside the table; None:
    a start a page): pages that lie on consecutive blocks arrive a run a
    descriptor, ``k_hbm.at[layer, b : b + size]`` to the slot's places
    ``j : j + size`` (:func:`_walk_rows`)."""
    if quant:
        ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf, acc_sc, m_sc, \
            l_sc, sem, ssem = rest
    else:
        o_ref, k_buf, v_buf, acc_sc, m_sc, l_sc, sem = rest
    nblk, rpb = q_ref.shape[1:3] if one_token else q_ref.shape[:2]
    bw = q_ref.shape[3]
    M, P = rpb * tq, cp * bs
    Mp = acc_sc.shape[-1]       # the token tile: M to whole lane tiles
    sw = ks_buf.shape[0] // 2 if quant else 0   # a slot of scales, words
    t0 = pl.program_id(0) * tq
    lo, hi = lo_ref[pl.program_id(0)], hi_ref[pl.program_id(0)]
    layer = layer_ref[0]

    if one_token:
        o_ref[...] = jnp.zeros_like(o_ref)     # a row that is never walked
    else:
        _init_scratch(acc_sc, m_sc, l_sc)

    @pl.when(pl.program_id(0) == 0)
    def _zero():
        # a stale value page is multiplied by p = 0: it must be finite
        v_buf[...] = jnp.zeros_like(v_buf)

    def copies(page, slot, j):
        return (pltpu.make_async_copy(k_hbm.at[layer, page],
                                      k_buf.at[slot, j], sem.at[slot, 0]),
                pltpu.make_async_copy(v_hbm.at[layer, page],
                                      v_buf.at[slot, j], sem.at[slot, 1]))

    def run_copies(block, slot, j, size):
        """``size`` (static) pages on consecutive blocks from ``block``"""
        src, dst = pl.ds(block, size), pl.ds(j, size)
        return (pltpu.make_async_copy(k_hbm.at[layer, src],
                                      k_buf.at[slot, dst], sem.at[slot, 0]),
                pltpu.make_async_copy(v_hbm.at[layer, src],
                                      v_buf.at[slot, dst], sem.at[slot, 1]))

    def waits(slot, size):
        """``size`` pages' bytes on the slot's two semaphores (which
        pages the descriptors name is of no account: they never start)"""
        return run_copies(0, slot, 0, size)

    def scale_copies(r, c, slot):
        src = pl.ds(pl.multiple_of(
            (r * (bt_ref.shape[1] // cp) + c) * sw, sw), sw)
        dst = pl.ds(pl.multiple_of(slot * sw, sw), sw)
        return (pltpu.make_async_copy(ks_hbm.at[src], ks_buf.at[dst],
                                      ssem.at[slot, 0]),
                pltpu.make_async_copy(vs_hbm.at[src], vs_buf.at[dst],
                                      ssem.at[slot, 1]))

    def dequant(x, s_buf, slot, b):
        """The block's (cp, bs, bw) slice of an int8 chunk through the
        pool's serving dtype: paged_model._kv_read's arithmetic, a scale
        a page and head splat from SMEM."""
        lane = jax.lax.broadcasted_iota(jnp.int32, (bs, bw), 1)
        rows = []
        for j in range(cp):
            at = slot * sw + j * kvh + b * hpb
            sc = jnp.full((bs, bw), s_buf[at], jnp.float32)
            for i in range(1, hpb):
                sc = jnp.where(lane >= i * hd, s_buf[at + i], sc)
            rows.append(sc)
        return (x.astype(jnp.float32).reshape(P, bw)
                * jnp.concatenate(rows, axis=0)).astype(io_dtype)

    def block(slot, b):
        """Lane block b of the slot's chunk: keys and values ``(P, bw)``"""
        lanes = slice(b * bw, (b + 1) * bw)
        k = k_buf[slot, :, :, lanes]
        v = v_buf[slot, :, :, lanes]
        if quant:
            k = dequant(k, ks_buf, slot, b)
            v = dequant(v, vs_buf, slot, b)
        return k.reshape(P, bw), v.reshape(P, bw)

    def compute(first, last, c, slot, base):
        if one_token:
            # every lane block's update at once: one softmax over the
            # stacked scores where there were nblk chains one behind the
            # other
            ks, vs = zip(*(block(slot, b) for b in range(nblk)))
            _blocks_update(
                [q_ref[first - t0, b] for b in range(nblk)], ks, vs,
                _row_visible(len_ref[first], c, nblk * rpb, P, base, window),
                acc_sc, m_sc, l_sc, scale=scale)
            return
        visible = _tile_visible(tl_ref, t0, first, last, c, tq, P, base,
                                window)
        for b in range(nblk):                                 # static
            k, v = block(slot, b)
            q = q_ref[b].reshape(M, bw)
            if Mp != M:        # a small launch: zero rows to whole lanes
                q = jnp.concatenate(
                    [q, jnp.zeros((Mp - M, bw), q.dtype)], axis=0)
            _tile_update(q, k, v, visible, acc_sc, m_sc, l_sc, b,
                         scale=scale)

    def finish(first):
        """The walked row's output, a head's lanes from its own rows."""
        lane = jax.lax.broadcasted_iota(jnp.int32, (group, bw), 1)
        for b in range(nblk):                                 # static
            l = l_sc[b, :, :1]
            a = acc_sc[b] / jnp.where(l == 0.0, 1.0, l)
            out = a[:group]
            for i in range(1, hpb):
                out = jnp.where(lane >= i * hd,
                                a[i * group:(i + 1) * group], out)
            o_ref[first - t0, b] = out.astype(o_ref.dtype)

    _walk_rows(len_ref, bt_ref, first_ref, last_ref, lo, hi, t0=t0, tq=tq,
               bs=bs, cp=cp, copies=copies, waits=waits, compute=compute,
               chunk_copies=scale_copies if quant else None, window=window,
               ring=ring, runs_ref=runs_ref, run_copies=run_copies,
               **_row_hooks(one_token, finish, acc_sc, m_sc, l_sc))
    if one_token:
        return

    lane = jax.lax.broadcasted_iota(jnp.int32, (group * tq, bw), 1)
    for b in range(nblk):                                     # static
        l = l_sc[b]
        # the state is transposed (:func:`_tile_update`): back, once a tile
        a = (acc_sc[b] / jnp.where(l == 0.0, 1.0, l)).T[:M].reshape(
            hpb, group * tq, bw)
        out = a[0]
        for i in range(1, hpb):        # head i's lanes from head i's rows
            out = jnp.where(lane >= i * hd, a[i], out)
        o_ref[b] = out.reshape(group, tq, bw).astype(o_ref.dtype)


def _tiled_kernel_runs(layer_ref, len_ref, bt_ref, first_ref, last_ref,
                       lo_ref, hi_ref, runs_ref, *rest, **static):
    """:func:`_tiled_kernel` with the table's runs prefetched behind the
    other scalars"""
    _tiled_kernel(layer_ref, len_ref, bt_ref, first_ref, last_ref, lo_ref,
                  hi_ref, *rest, runs_ref=runs_ref, **static)


def _row_descriptors(row_ids, lengths, R, tq):
    """What :func:`_walk_rows` reads: each row's first and last flat
    token (a row's tokens are contiguous in pack order; [R] each), and
    each tile's first and last row ([T / tq] each). T is whole tiles."""
    T = row_ids.shape[0]
    tok = jnp.arange(T, dtype=jnp.int32)
    valid = lengths > 0
    mine = (row_ids[None, :] == jnp.arange(R, dtype=jnp.int32)[:, None]) \
        & valid[None, :]                                        # [R, T]
    row_first = jnp.min(jnp.where(mine, tok, T), axis=1)
    row_last = jnp.max(jnp.where(mine, tok, -1), axis=1)
    tile_lo = jnp.min(jnp.where(valid, row_ids, R).reshape(-1, tq), axis=1)
    tile_hi = jnp.max(jnp.where(valid, row_ids, -1).reshape(-1, tq), axis=1)
    return row_first, row_last, tile_lo, tile_hi


def _tiled_call(q, k_cache, v_cache, layer, row_ids, lengths, block_tables,
                k_scale, v_scale, interpret, window=0, one_token=False,
                runs=None):
    """Lay the operands out for :func:`_tiled_kernel` and undo it: the
    per-row descriptor (first and last flat token, from ``row_ids`` and
    ``lengths``: a row's tokens are contiguous in pack order), a tile's
    first and last row, and queries as ``[block, row of the block, T,
    bw]`` (``one_token``: ``[T, block, row of the block, bw]``, a
    token's query rows one tile). The pools go in as they are stored,
    whole. ``runs`` (:func:`table_runs` of ``block_tables``, or None)
    rides beside the table, one more prefetched scalar array."""
    T0, nh, hd = q.shape
    bs, F = k_cache.shape[2:]
    kvh = F // hd
    R, MB = block_tables.shape
    group = nh // kvh
    bw, hpb = tiled_geometry(hd, kvh)
    nblk, rpb = kvh // hpb, hpb * group
    quant = k_scale is not None
    if window and quant:
        raise NotImplementedError(
            "the tiled kernel takes a window over a bf16 / float32 pool: a "
            "chunk of its walk no longer starts at a chunk of the table, "
            "where an int8 pool's scales are sliced (dequantise the layer "
            "first: paged_model._per_head_attention_sublayer)")
    ring = MB             # the table's places, before the padding below
    # a tile: :func:`_token_tile` (one_token: the rows a grid step walks)
    tq = _ONE_TOKEN_ROWS if one_token else _token_tile(T0, rpb)
    T = -(-T0 // tq) * tq
    cp = _chunk_pages(MB, k_cache.shape[1], bs)
    if MB % cp:
        block_tables = jnp.pad(block_tables, ((0, 0), (0, cp - MB % cp)))
        if runs is not None:
            # (a padding place lies alone: an entry of 0 would be a
            # segment of no pages, and a walk that never ends)
            runs = jnp.pad(runs, ((0, 0), (0, cp - MB % cp)),
                           constant_values=-1)
        MB = block_tables.shape[1]
    if T != T0:
        q = jnp.pad(q, ((0, T - T0), (0, 0), (0, 0)))
        row_ids = jnp.pad(row_ids, (0, T - T0))
        lengths = jnp.pad(lengths, (0, T - T0))

    row_first, row_last, tile_lo, tile_hi = _row_descriptors(
        row_ids, lengths, R, tq)

    qx = q.reshape(T, nblk, hpb, group, hd)
    if not one_token:
        qx = qx.transpose(1, 2, 3, 0, 4)
    if hpb > 1:       # a query row is zero outside its own head's lanes
        own = jnp.eye(hpb, dtype=bool)[:, None, :, None] if one_token \
            else jnp.eye(hpb, dtype=bool)[None, :, None, None, :, None]
        qx = jnp.where(own, qx[..., None, :], 0)
    if one_token:
        # a token's query rows of a lane block, whole sublane tiles
        M = _sublane_tiles(rpb)
        qx = jnp.pad(qx.reshape(T, nblk, rpb, bw),
                     ((0, 0), (0, 0), (0, M - rpb), (0, 0)))
        q_block, o_block = (tq, nblk, M, bw), (tq, nblk, group, bw)

        def tile(i, *_):
            return (i, 0, 0, 0)
        bounds = lengths.reshape(T, 1)
        bounds_spec = pl.BlockSpec((tq, 1), lambda i, *_: (i, 0))
        state = [pltpu.VMEM((nblk, M, bw), jnp.float32),
                 pltpu.VMEM((nblk, M, 128), jnp.float32),
                 pltpu.VMEM((nblk, M, 128), jnp.float32)]
    else:
        # a tile's query rows lie along the LANES of its scores and state
        # (:func:`_tile_update`): their bounds a row a tile, padded with
        # rows that see nothing to whole lane tiles
        M = rpb * tq
        Mp = -(-M // 128) * 128
        qx = qx.reshape(nblk, rpb, T, bw)
        q_block, o_block = (nblk, rpb, tq, bw), (nblk, group, tq, bw)

        def tile(i, *_):
            return (0, 0, i, 0)
        bounds = jnp.pad(jnp.broadcast_to(
            lengths.reshape(T // tq, 1, tq), (T // tq, rpb, tq)).reshape(
            T // tq, 1, M), ((0, 0), (0, 0), (0, Mp - M)))
        bounds_spec = pl.BlockSpec((1, 1, Mp), lambda i, *_: (i, 0, 0))
        state = [pltpu.VMEM((nblk, bw, Mp), jnp.float32),
                 pltpu.VMEM((nblk, 1, Mp), jnp.float32),
                 pltpu.VMEM((nblk, 1, Mp), jnp.float32)]

    pool_spec = pl.BlockSpec(memory_space=pl.ANY)       # pool stays in HBM
    in_specs = [pl.BlockSpec(q_block, tile), bounds_spec, pool_spec,
                pool_spec]
    operands = [qx, bounds, k_cache, v_cache]
    scratch = [pltpu.VMEM((2, cp, bs, F), k_cache.dtype),
               pltpu.VMEM((2, cp, bs, F), v_cache.dtype)]
    sems = [pltpu.SemaphoreType.DMA((2, 2))]
    if quant:
        # a chunk's scales, [cp, kvh] padded to a whole 1,024-word tile, go
        # to SMEM beside its pages: one flat array, a slice a chunk
        w = -(-cp * kvh // 1024) * 1024
        for sc in (k_scale, v_scale):
            sc = sc.astype(jnp.float32)[block_tables].reshape(
                R * MB // cp, cp * kvh)
            in_specs.append(pool_spec)
            operands.append(jnp.pad(sc, ((0, 0), (0, w - cp * kvh)))
                            .reshape(-1))
        scratch += [pltpu.SMEM((2 * w,), jnp.float32)] * 2
        sems.append(pltpu.SemaphoreType.DMA((2, 2)))
    scratch += state
    tables = (block_tables, row_first, row_last, tile_lo, tile_hi)
    if runs is not None:
        tables += (runs.astype(jnp.int32),)
    kernel = functools.partial(
        _tiled_kernel if runs is None else _tiled_kernel_runs,
        quant=quant, bs=bs, scale=1.0 / (hd ** 0.5), kvh=kvh,
        hd=hd, hpb=hpb, group=group, tq=tq, cp=cp, io_dtype=q.dtype,
        one_token=one_token,
        **(dict(window=window, ring=ring) if window else {}))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2 + len(tables),
            grid=(T // tq,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(o_block, tile),
            scratch_shapes=scratch + sems),
        out_shape=jax.ShapeDtypeStruct(
            (T, nblk, group, bw) if one_token else (nblk, group, T, bw),
            q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_TILED_VMEM_BYTES),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="ragged_attention_window" if window
        else "ragged_attention_tiled",
    )(layer, lengths, *tables, *operands)
    out = out.reshape(T, nblk, group, hpb, hd).transpose(0, 1, 3, 2, 4) \
        if one_token \
        else out.reshape(nblk, group, T, hpb, hd).transpose(2, 0, 3, 1, 4)
    return out.reshape(T, nh, hd)[:T0]


def ragged_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, layer, row_ids: jnp.ndarray,
                     lengths: jnp.ndarray,
                     block_tables: jnp.ndarray,
                     k_scale: jnp.ndarray = None,
                     v_scale: jnp.ndarray = None,
                     variant: Optional[str] = None,
                     window: int = 0,
                     one_token: bool = False,
                     runs: jnp.ndarray = None) -> jnp.ndarray:
    """Ragged paged attention (serving hot path).

    q [T, nh, hd] flat token buffer; k/v_cache the pool's leaves as
    stored, WHOLE, [L, nb, bs, kvh * hd] (``paged_model.
    init_paged_kv_cache``; the kv heads are the row's lanes over hd),
    ``layer`` a traced scalar: a launch reads its layer's pages where
    they lie and nothing cuts a layer out. row_ids [T] token -> batch
    row; lengths [T] per-token causal bound (0 = padding); block_tables
    [R, MB] int32. For the int8 ``kv_quant`` pool, ``k_scale``/
    ``v_scale`` [nb, kvh] are the LAYER's per-(block, head) dequant
    scales — the kernel dequantizes in VMEM, so quantized KV
    serves through the SAME one-program ragged family. ``variant``
    defaults to :func:`kernel_variant`'s static choice for the pool
    geometry; off-TPU the default is the pipelined variant in interpret
    mode, and ``variant="tiled"`` runs the tiled one under the TPU
    interpreter, DMAs and semaphores included. Returns [T, nh, hd].

    ``window`` (static; 0: none, today's kernels bit for bit): a token
    with bound n sees positions ``[n - window, n)`` only, and a row's
    table is read as a RING: the page of positions ``[b * bs, (b + 1) *
    bs)`` lies at place ``b % MB``, so a pool whose rows own ``window +
    largest chunk + one block`` positions serves any context (a table
    that holds every position never wraps). The tiled variant starts a
    row's walk at its window's first page; in a trace the launch is
    ``ragged_attention_window`` (tiled) / ``..._pipelined_window``.

    ``one_token`` (static): the caller's word that every row has
    exactly one token (a decode batch). The tiled variant then takes its
    one-token form (the same launch names, the same sums in the same
    order); the pipelined one is a token a grid step already.

    ``runs`` (:func:`table_runs` of ``block_tables``; None: the launch
    as it always was): how the rows' tables lie, made once a program by
    the caller. The tiled variant then starts a chunk's copies a run of
    pages a descriptor (:func:`_walk_rows`): the same bytes to the same
    places, the same output to the last bit. The pipelined variant
    copies a page a grid step and does not read it."""
    T, nh, hd = q.shape
    bs, F = k_cache.shape[2:]
    kvh = F // hd
    MB = block_tables.shape[1]
    group = nh // kvh
    quant = k_scale is not None
    interpret = _interpret()
    if variant is None:
        # off the TPU the engine's programs take the pipelined variant:
        # its interpreter is several times faster than the TPU
        # interpreter the tiled one needs, which only a test asks for
        variant = "pipelined" if interpret else kernel_variant(hd, kvh, quant)
    if variant not in VARIANTS:
        raise ValueError(f"variant must be one of {VARIANTS}, got {variant!r}")
    row_ids = row_ids.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)
    block_tables = block_tables.astype(jnp.int32)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    if variant == "tiled":
        if tiled_geometry(hd, kvh) is None:
            raise ValueError(f"no tiled variant for {kvh} kv heads of {hd}")
        return _tiled_call(q, k_cache, v_cache, layer, row_ids, lengths,
                           block_tables, k_scale, v_scale, interpret,
                           window, one_token, runs)
    q4 = q.reshape(T, kvh, group, hd)
    static = dict(bs=bs, scale=1.0 / (hd ** 0.5), kvh=kvh, group=group,
                  io_dtype=q.dtype, **({"window": window} if window else {}))

    # index maps see (grid indices..., layer, row_ids, lengths, block_tables)
    def tok(t, *_):
        return (t, 0, 0, 0)

    def row_scales(t, *a):
        return (a[-3][t], 0, 0)

    def page(t, j, layer, row, ln, bt):
        return (layer[0], bt[row[t], j], 0, 0)

    in_specs = [pl.BlockSpec((1, kvh, group, hd), tok),
                pl.BlockSpec((1, 1, bs, F), page),
                pl.BlockSpec((1, 1, bs, F), page)]
    operands = [q4, k_cache, v_cache]
    if quant:
        R = block_tables.shape[0]
        for sc in (k_scale, v_scale):
            in_specs.append(pl.BlockSpec((1, 1, MB * kvh), row_scales,
                                         memory_space=pltpu.SMEM))
            operands.append(sc.astype(jnp.float32)[block_tables]
                            .reshape(R, 1, MB * kvh))
    out = pl.pallas_call(
        functools.partial(_pipelined_kernel, quant=quant, n_pages=MB,
                          **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(T, MB),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, kvh, group, hd), tok),
            scratch_shapes=[
                pltpu.VMEM((kvh * group, hd), jnp.float32),
                pltpu.VMEM((kvh * group, 128), jnp.float32),
                pltpu.VMEM((kvh * group, 128), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((T, kvh, group, hd), q.dtype),
        interpret=interpret,
        name="ragged_attention_pipelined" + "_window" * bool(window),
    )(layer, row_ids, lengths, block_tables, *operands)
    return out.reshape(T, nh, hd)


def ragged_attention_reference(q, k_cache, v_cache, layer, row_ids, lengths,
                               block_tables, k_scale=None, v_scale=None,
                               window: int = 0):
    """:func:`ragged_attention` by gathering, window and ring included:
    each row's pages once, every token against the positions its row's
    table holds under its own bound (and inside its window), a dense
    masked softmax in float32. Place i of a table of MB pages holds the
    newest position p < bound with p = i mod (MB * bs), which for a
    table that holds every position is i itself. The ``jnp:gather``
    path of a layer pattern's per-head layers and the kernels' parity
    reference."""
    T, nh, hd = q.shape
    R, MB = block_tables.shape
    bs, F = k_cache.shape[2:]
    kvh = F // hd

    def rows(cache, scale):
        pages = cache[layer][block_tables].reshape(R, MB, bs, kvh, hd)
        if scale is not None:
            pages = (pages.astype(jnp.float32) * scale[block_tables][
                :, :, None, :, None]).astype(q.dtype)
        return jnp.repeat(pages.reshape(R, MB * bs, kvh, hd), nh // kvh,
                          axis=2)[row_ids]                # [T, ctx, nh, hd]

    k, v = rows(k_cache, k_scale), rows(v_cache, v_scale)
    ctx = MB * bs
    newest = lengths[:, None] - 1
    pos = newest - (newest - jnp.arange(ctx)[None, :]) % ctx   # [T, ctx]
    seen = (pos >= 0) & (lengths[:, None] > 0)
    if window:
        seen = seen & (pos >= lengths[:, None] - window)
    s = jnp.einsum("thd,tchd->thc", q, k).astype(jnp.float32) / hd ** 0.5
    p = jax.nn.softmax(jnp.where(seen[:, None, :], s, NEG_INF), axis=-1)
    p = jnp.where(lengths[:, None, None] > 0, p, 0.0)   # padding: zeros
    return jnp.einsum("thc,tchd->thd", p.astype(q.dtype), v)


# ---------------------------------------------------------------------------
# Latent (MLA) pool
# ---------------------------------------------------------------------------
def _latent_update(q, kv, visible, acc_sc, m_sc, l_sc, *, dc, scale):
    """:func:`_tile_update` over a latent chunk: q ``(M, W)`` (all heads
    of the tile's tokens, or the walked row's), kv ``(P, W)`` the keys
    whole and, their first ``dc`` lanes, the values; the same float32
    max, sum and accumulator and the same mask."""
    s = jax.lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(visible, s, 2 * NEG_INF)          # as _tile_update masks
    m_prev = m_sc[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_sc[...] = jnp.broadcast_to(
        l_sc[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True), l_sc.shape)
    acc_sc[...] = acc_sc[...] * corr + jax.lax.dot_general(
        p.astype(kv.dtype), kv[:, :dc], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_sc[...] = jnp.broadcast_to(m_new, m_sc.shape)


def _latent_kernel(layer_ref, len_ref, bt_ref, first_ref, last_ref, lo_ref,
                   hi_ref, q_ref, tl_ref, pool_hbm, *rest, bs, scale, dc,
                   tq, cp, one_token=False, window=0, ring=0, picked=False):
    """Grid (T / tq,), the tiled variant's walk (:func:`_walk_rows`)
    over a latent pool ``[L, nb, bs, W]`` left whole in HBM, the layer a
    prefetched scalar. All ``nh`` heads of the tile's tokens, ``(nh *
    tq, W)`` query rows in the absorbed form (a head's query against the
    latent's ``dc`` lanes, then its rotated part), go through the matrix
    unit together against ONE row a position: a chunk's ``(P, W)`` rows
    are the keys whole and, their first ``dc`` lanes, the values, so a
    page is read once. buf is (2, cp, bs, W); sem is (2,), a start a
    page and one wait a whole chunk (by its bytes).

    ``one_token`` (static): every row has ONE token and the tile is
    ``tq`` rows walked one after another (:func:`_tiled_kernel`): the
    queries lie token-major ``(tq, nh, W)``, a chunk meets the walked
    row's ``nh`` query rows alone, the float32 state is one row's and
    goes to the row's place in the output tile ``(tq, nh, dc)`` behind
    its last chunk.

    ``window`` / ``ring`` (static): a token sees its last ``window``
    positions and the row's table is a ring of ``ring`` places, as the
    tiled kernel's (:func:`_walk_rows`).

    ``picked`` (static; the token tile's form only): one more operand
    left in HBM, ``[T, positions]`` flags (> 0: the token PICKED the
    position; every head reads the same), a chunk's ``(tq, P)`` of
    which rides in beside its pages (``pbuf`` (2, tq, P), ``psem``
    (2,)) and is laid on the causal mask: a token attends the positions
    under its bound that it picked and no other."""
    if picked:
        picked_hbm, o_ref, buf, acc_sc, m_sc, l_sc, sem, pbuf, psem = rest
    else:
        o_ref, buf, acc_sc, m_sc, l_sc, sem = rest
    if one_token:
        nh, W = q_ref.shape[1:]
    else:
        nh, W = q_ref.shape[0], q_ref.shape[2]
    M, P = nh * tq, cp * bs
    t0 = pl.program_id(0) * tq
    lo, hi = lo_ref[pl.program_id(0)], hi_ref[pl.program_id(0)]
    layer = layer_ref[0]

    if one_token:
        o_ref[...] = jnp.zeros_like(o_ref)     # a row that is never walked
    else:
        _init_scratch(acc_sc, m_sc, l_sc)

    @pl.when(pl.program_id(0) == 0)
    def _zero():
        # a stale page is multiplied by p = 0: it must be finite
        buf[...] = jnp.zeros_like(buf)

    def copies(page, slot, j):
        return (pltpu.make_async_copy(pool_hbm.at[layer, page],
                                      buf.at[slot, j], sem.at[slot]),)

    def waits(slot, size):
        """``size`` pages' bytes on the slot's semaphore"""
        first = pl.ds(0, size)
        return (pltpu.make_async_copy(pool_hbm.at[layer, first],
                                      buf.at[slot, first], sem.at[slot]),)

    def compute(first, last, c, slot, base):
        visible = _row_visible(len_ref[first], c, nh, P, base, window) \
            if one_token \
            else _visible(tl_ref, t0, first, last, c, tq, nh, P, base, window)
        if picked:
            # the tile's query rows are ``nh`` copies of its tokens
            # (compared in float32: the chip compares no bf16)
            visible = visible & (jnp.concatenate(
                [pbuf[slot].astype(jnp.float32)] * nh, axis=0) > 0)
        q = q_ref[first - t0] if one_token else q_ref[...].reshape(M, W)
        _latent_update(q, buf[slot].reshape(P, W), visible, acc_sc, m_sc,
                       l_sc, dc=dc, scale=scale)

    def picked_copies(r, c, slot):
        """The tile's tokens' flags for chunk c's positions"""
        return (pltpu.make_async_copy(
            picked_hbm.at[pl.ds(pl.multiple_of(t0, tq), tq),
                          pl.ds(pl.multiple_of(c * P, P), P)],
            pbuf.at[slot], psem.at[slot]),)

    def finish(first):
        l = l_sc[:, :1]
        o_ref[first - t0] = (acc_sc[...] / jnp.where(l == 0.0, 1.0, l)
                             ).astype(o_ref.dtype)

    _walk_rows(len_ref, bt_ref, first_ref, last_ref, lo, hi, t0=t0, tq=tq,
               bs=bs, cp=cp, copies=copies, waits=waits, compute=compute,
               **(dict(window=window, ring=ring) if window else {}),
               **(dict(chunk_copies=picked_copies) if picked else {}),
               **_row_hooks(one_token, finish, acc_sc, m_sc, l_sc))
    if one_token:
        return

    l = l_sc[:, :1]
    o_ref[...] = (acc_sc[...] / jnp.where(l == 0.0, 1.0, l)).reshape(
        nh, tq, dc).astype(o_ref.dtype)


def latent_attention_reference(q, pool, layer, row_ids, lengths,
                               block_tables, *, dc: int, scale: float,
                               window: int = 0, picked=None):
    """:func:`latent_attention` by gathering: each row's pages once,
    every token against its row's positions under its own bound, the
    softmax in float32. The ``jnp:gather`` path of a latent pool and the
    kernel's parity reference. ``window``: a token sees its last
    ``window`` positions and the table is the row's ring, place i the
    newest position p under the bound with p = i mod the ring
    (:func:`ragged_attention_reference`). ``picked`` ``[T, MB * bs]``
    (> 0: the token picked the position): a token attends what it
    picked of what its bound shows."""
    R, MB = block_tables.shape
    bs, W = pool.shape[2:]
    rows = pool[layer][block_tables].reshape(R, MB * bs, W)[row_ids]
    s = jnp.einsum("htw,tcw->htc", q, rows).astype(jnp.float32) * scale
    if window:
        ctx = MB * bs
        newest = lengths[:, None] - 1
        pos = newest - (newest - jnp.arange(ctx)[None, :]) % ctx
        seen = (pos >= 0) & (pos >= lengths[:, None] - window)
    else:
        seen = jnp.arange(MB * bs)[None, :] < lengths[:, None]   # [T, ctx]
    if picked is not None:      # [T, ctx]: what each token picked
        seen = seen & (picked > 0)
    p = jax.nn.softmax(jnp.where(seen[None], s, NEG_INF), axis=-1)
    p = jnp.where(lengths[None, :, None] > 0, p, 0.0)   # padding: zeros
    return jnp.einsum("htc,tcd->htd", p.astype(q.dtype), rows[..., :dc])


def latent_attention(q, pool, layer, row_ids, lengths, block_tables, *,
                     dc: int, scale: float,
                     interpret: Optional[bool] = None,
                     one_token: bool = False, window: int = 0,
                     picked=None):
    """Ragged paged attention over a LATENT pool (attention='mla', the
    absorbed form): every head's query attends ONE cached row a
    position, whose first ``dc`` lanes are also the values.

    q ``[nh, T, W]`` flat token buffer, head-major (a head's ``dc``-wide
    query in the latent's space, then its rotated part; W = pool row);
    pool ``[L, nb, bs, W]`` whole, ``layer`` a traced scalar; row_ids,
    lengths [T] and block_tables [R, MB] as :func:`ragged_attention`
    takes them. Returns ``[nh, T, dc]``, a head's output in the latent's
    space. On a TPU the kernel (``ragged_attention_latent`` in a trace);
    off it the gathering reference, and the kernel under the TPU
    interpreter (DMAs, semaphores and all) only where ``interpret`` asks
    for it: that interpreter is several times slower than the gather.

    ``one_token`` (static): the caller's word that every row has
    exactly one token (a decode batch); the kernel then takes its
    one-token form, under the same name.

    ``window`` > 0 (static): a token sees its last ``window`` positions
    and ``block_tables`` is each row's RING in a pool of its own
    (``ragged_attention_latent_window`` in a trace), as
    :func:`ragged_attention` takes a window over per-head pools.

    ``picked`` ``[T, MB * bs]`` (any dtype; > 0: the token PICKED the
    position): a token attends only what it picked of the positions
    under its bound, every head the same (a full latent layer whose
    indexer selects; ``ragged_attention_latent_picked`` in a trace). The
    token tile's form only. Who still calls it: a selecting prompt
    launch of FEW tokens a row (``paged_model.index_prompt_form``:
    "absorbed", a ragged step's tail) and the parity tests; a launch of
    many tokens a row attends per-head keys and values instead
    (:func:`picked_heads_attention`)."""
    nh, T0, W = q.shape
    if interpret is None and _interpret():
        return latent_attention_reference(
            q, pool, layer, row_ids, lengths, block_tables, dc=dc,
            scale=scale, **({"window": window} if window else {}),
            **({} if picked is None else {"picked": picked}))
    assert picked is None or not (one_token or window), \
        "picked positions ride the token tile's form, without a window"
    row_ids = row_ids.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)
    block_tables = block_tables.astype(jnp.int32)
    bs = pool.shape[2]
    R, MB = block_tables.shape
    ring = MB             # the table's places, before the padding below
    # a tile: a power of two of 16 to 128 tokens, 512 query rows where
    # that leaves 16 (one_token: the rows a grid step walks)
    tq = _ONE_TOKEN_ROWS if one_token else max(16, min(
        pow2_bucket(T0, 128), 1 << (max(512 // nh, 1).bit_length() - 1)))
    T = -(-T0 // tq) * tq
    cp = _chunk_pages(MB, pool.shape[1], bs)
    if MB % cp:
        block_tables = jnp.pad(block_tables, ((0, 0), (0, cp - MB % cp)))
    if T != T0:
        q = jnp.pad(q, ((0, 0), (0, T - T0), (0, 0)))
        row_ids = jnp.pad(row_ids, (0, T - T0))
        lengths = jnp.pad(lengths, (0, T - T0))
    extra_in, extra_scratch, extra_args = [], [], []
    if picked is not None:
        # flags a token and position of the (padded) table, in the
        # pool's type: a chunk's (tq, P) of them is whole tiles
        P = cp * bs
        picked = (picked > 0).astype(pool.dtype)
        picked = jnp.pad(picked, ((0, T - T0), (
            0, block_tables.shape[1] * bs - picked.shape[1])))
        extra_in = [pl.BlockSpec(memory_space=pl.ANY)]
        extra_scratch = [pltpu.VMEM((2, tq, P), pool.dtype),
                         pltpu.SemaphoreType.DMA((2,))]
        extra_args = [picked]
    row_first, row_last, tile_lo, tile_hi = _row_descriptors(
        row_ids, lengths, R, tq)
    if one_token:
        # token-major, a token's heads whole sublane tiles
        M = _sublane_tiles(nh)
        q = jnp.pad(q.transpose(1, 0, 2), ((0, 0), (0, M - nh), (0, 0)))
        q_block, o_block, out_shape = (tq, M, W), (tq, M, dc), (T, M, dc)

        def tile(i, *_):
            return (i, 0, 0)
    else:
        M = nh * tq
        q_block, o_block, out_shape = (nh, tq, W), (nh, tq, dc), (nh, T, dc)

        def tile(i, *_):
            return (0, i, 0)
    out = pl.pallas_call(
        functools.partial(_latent_kernel, bs=bs, scale=scale, dc=dc, tq=tq,
                          cp=cp, one_token=one_token,
                          **(dict(window=window, ring=ring) if window
                             else {}),
                          **(dict(picked=True) if extra_args else {})),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(T // tq,),
            in_specs=[pl.BlockSpec(q_block, tile),
                      pl.BlockSpec((tq, 1), lambda i, *_: (i, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)] + extra_in,
            out_specs=pl.BlockSpec(o_block, tile),
            scratch_shapes=[pltpu.VMEM((2, cp, bs, W), pool.dtype),
                            pltpu.VMEM((M, dc), jnp.float32),
                            pltpu.VMEM((M, 128), jnp.float32),
                            pltpu.VMEM((M, 128), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))]
            + extra_scratch),
        out_shape=jax.ShapeDtypeStruct(out_shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_TILED_VMEM_BYTES),
        interpret=pltpu.InterpretParams() if interpret or _interpret()
        else False,
        name="ragged_attention_latent" + "_window" * bool(window)
        + "_picked" * bool(extra_args),
    )(jnp.asarray(layer, jnp.int32).reshape(1), lengths, block_tables,
      row_first, row_last, tile_lo, tile_hi, q, lengths.reshape(T, 1), pool,
      *extra_args)
    if one_token:
        return out[:T0, :nh].transpose(1, 0, 2)
    return out[:, :T0]


# ---------------------------------------------------------------------------
# Latent attention in the EXPANDED form, under a selection
# ---------------------------------------------------------------------------
def _expand_kernel(rows_ref, pieces_ref, lat_ref, w_ref, k_ref, v_ref, *,
                   dc, dn):
    """Grid (heads / hb, live pieces): one piece of ``E`` latent rows
    ``(E, W)`` times ``hb`` heads' up-projections ``(dc, hb x (dn +
    dv))``, summed in float32, a head's keys ``(E, dn)`` and values
    ``(E, dv)`` written to its own slab."""
    hb = k_ref.shape[2]
    dv = v_ref.shape[-1]
    y = jnp.dot(lat_ref[0, 0][:, :dc], w_ref[0],
                preferred_element_type=jnp.float32)
    for j in range(hb):                                       # static
        at = j * (dn + dv)
        k_ref[0, 0, j] = y[:, at:at + dn].astype(k_ref.dtype)
        v_ref[0, 0, j] = y[:, at + dn:at + dn + dv].astype(v_ref.dtype)


def expand_latent_rows_reference(lat, w, reach=None, *, dc: int, dn: int):
    """:func:`expand_latent_rows` as one einsum over every piece."""
    y = jnp.einsum("rpcd,dhn->rphcn", lat[..., :dc], w)
    return y[..., :dn], y[..., dn:]


def expand_latent_rows(lat, w, reach, *, dc: int, dn: int,
                       interpret: Optional[bool] = None,
                       heads_a_step: int = 4):
    """Rows' cached latent rows turned into per-head keys and values
    (the EXPANDED form's operands): lat ``[R, C, E, W]`` (a row's
    positions in ``C`` pieces of ``E``; the first ``dc`` lanes ``c^kv``),
    w ``[dc, nh, dn + dv]`` (``W^UK | W^UV`` a head), reach ``[R]`` the
    positions a row's tokens reach. Returns k ``[R, C, nh, E, dn]`` and
    v ``[R, C, nh, E, dv]`` in the latent's type, products summed in
    float32. On a TPU the kernel ``latent_rows_expand``: its grid runs
    over the pieces under ``reach`` alone (a dynamic bound), so a table
    far wider than its rows costs nothing and a piece past a row's reach
    is NEVER WRITTEN (nothing may read it: :func:`picked_heads_attention`
    stops at its tiles' bounds). Off it one einsum over everything, and
    the kernel under the TPU interpreter only where ``interpret`` asks
    for it."""
    R, C, E, W = lat.shape
    nh, dv = w.shape[1], w.shape[2] - dn
    if interpret is None and _interpret():
        return expand_latent_rows_reference(lat, w, dc=dc, dn=dn)
    hb = math.gcd(heads_a_step, nh)
    under = (jnp.arange(C, dtype=jnp.int32)[None, :] * E
             < reach[:, None]).reshape(R * C)
    live = jnp.argsort(~under, stable=True).astype(jnp.int32)

    def out_block(h, s, rows_ref, pieces_ref):
        return (rows_ref[s], pieces_ref[s], h, 0, 0)

    return pl.pallas_call(
        functools.partial(_expand_kernel, dc=dc, dn=dn),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nh // hb, jnp.maximum(jnp.sum(under, dtype=jnp.int32), 1)),
            in_specs=[
                pl.BlockSpec((1, 1, E, W), lambda h, s, rows_ref, pieces_ref:
                             (rows_ref[s], pieces_ref[s], 0, 0)),
                pl.BlockSpec((1, dc, hb * (dn + dv)),
                             lambda h, s, *_: (h, 0, 0))],
            out_specs=[pl.BlockSpec((1, 1, hb, E, dn), out_block),
                       pl.BlockSpec((1, 1, hb, E, dv), out_block)]),
        out_shape=[jax.ShapeDtypeStruct((R, C, nh, E, dn), lat.dtype),
                   jax.ShapeDtypeStruct((R, C, nh, E, dv), lat.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_TILED_VMEM_BYTES),
        interpret=pltpu.InterpretParams() if interpret or _interpret()
        else False,
        name="latent_rows_expand",
    )(live // C, live % C, lat,
      w.reshape(dc, nh // hb, hb * (dn + dv)).transpose(1, 0, 2))


def _picked_heads_kernel(rows_ref, chunks_ref, q_ref, k_ref, kr_ref, v_ref,
                         p_ref, len_ref, o_ref, acc_sc, m_sc, l_sc, *, scale,
                         P):
    """Grid (tiles, heads / hs, chunks): a tile of ``tq`` tokens of ONE
    row (``rows_ref[i]``) against that row's per-head keys (``k_ref``
    ``(P, dn)`` a head beside the row's shared rotated part ``kr_ref``
    ``(P, dr)``, laid side by side here: one product of ``dn + dr``) and
    values ``(P, dv)``, ``hs`` heads a step, a chunk of ``P`` positions
    at a time, BlockSpec-pipelined from contiguous temporaries. The
    update is the token tile's, TRANSPOSED (:func:`_tile_update`: a
    token a lane, scores ``k q^T`` ``(P, tq)``, max and sum ``(1,
    tq)``, the accumulator ``v^T p`` ``(dv, tq)``, all float32), one a
    head, under ONE mask a step: the causal bound (``len_ref`` ``(1,
    tq)``) and the tile's flags for the chunk (``p_ref`` ``(tq / tt, P,
    tt)``, > 0: picked; every head reads the same). ``chunks_ref[i]``: the
    chunks under the tile's largest bound; a step past them computes
    nothing and its blocks' indices stand still, so nothing is copied
    for it (the grid's chunk axis is dynamic: the chunks under the
    LAUNCH's largest bound)."""
    i, c = pl.program_id(0), pl.program_id(2)
    hs, tq = q_ref.shape[:2]
    dv = v_ref.shape[-1]

    @pl.when(c == 0)
    def _begin():
        _init_scratch(acc_sc, m_sc, l_sc)

    @pl.when(c < chunks_ref[i])
    def _chunk():
        pos = c * P + jax.lax.broadcasted_iota(jnp.int32, (P, tq), 0)
        # (compared in float32: the chip compares neither bf16 nor int8)
        picked = jnp.concatenate([p_ref[j] for j in range(p_ref.shape[0])],
                                 axis=1)
        visible = (pos < len_ref[...]) & (picked.astype(jnp.float32) > 0)
        for h in range(hs):                                   # static
            _tile_update(
                q_ref[h], jnp.concatenate([k_ref[0, 0, h], kr_ref[0, 0]],
                                          axis=1), v_ref[0, 0, h], visible,
                acc_sc, m_sc, l_sc, h, scale=scale)

    @pl.when(c == pl.num_programs(2) - 1)
    def _finish():
        for h in range(hs):                                   # static
            l = l_sc[h]
            o_ref[:, h * dv:(h + 1) * dv] = (
                acc_sc[h] / jnp.where(l == 0.0, 1.0, l)).T.astype(o_ref.dtype)


def picked_heads_attention_reference(q, k, k_rope, v, picked_t, tile_rows,
                                     lengths, *, scale: float, tq: int):
    """:func:`picked_heads_attention` in jnp: a tile's row's keys and
    values gathered whole, the softmax in float32 over the positions
    under a token's bound that it picked. Toy sizes and parity."""
    nh, N, dk = q.shape
    R, C, _, E, dn = k.shape
    ctx, n = C * E, N // tq
    k, v = (a.transpose(0, 2, 1, 3, 4).reshape(R, nh, ctx, -1)
            for a in (k, v))
    q = q.reshape(nh, n, tq, dk)
    s = (jnp.einsum("hitd,ihcd->hitc", q[..., :dn], k[tile_rows])
         + jnp.einsum("hitd,icd->hitc", q[..., dn:],
                      k_rope.reshape(R, ctx, -1)[tile_rows])
         ).astype(jnp.float32) * scale
    picked = picked_t.transpose(0, 2, 1).reshape(N, -1)[:, :ctx]
    picked = jnp.pad(picked, ((0, 0), (0, ctx - picked.shape[1])))
    seen = (jnp.arange(ctx)[None, :] < lengths[:, None]) & (picked > 0)
    p = jax.nn.softmax(jnp.where(seen.reshape(n, tq, ctx)[None], s, NEG_INF),
                       axis=-1)
    p = jnp.where(jnp.any(seen, axis=-1).reshape(n, tq)[None, ..., None],
                  p, 0.0)                       # nothing to attend: zeros
    o = jnp.einsum("hitc,ihcd->ithd", p.astype(q.dtype), v[tile_rows])
    return o.reshape(N, nh * v.shape[-1])


# positions a chunk of :func:`picked_heads_attention`: the caller's keys
# and values hold whole chunks of them: 6 % under 512 positions on the
# chip (PERF.md section 6, PR 69: 12.79 -> 12.01 ms at 1,024 tokens x
# 16,384 positions). Eight heads a step bring 11.70, and 18 MB of a
# step's blocks: inside the program's loop over groups of heads XLA
# fuses the loop's output into the call and holds the call to 16 MB
# whatever ``vmem_limit_bytes`` says, so a step keeps four
PICKED_CHUNK = 1024


def picked_heads_tile(tokens: int, rows: int) -> int:
    """Tokens a tile of :func:`picked_heads_attention` by the launch's
    static shapes: a power of two of 128 to 512, no more than a row's
    even share of the launch (a row's keys and values are read once a
    TILE and head)."""
    return max(128, min(512, pow2_bucket(max(tokens // max(rows, 1), 1),
                                         512)))


def picked_heads_attention(q, k, k_rope, v, picked_t, tile_rows, lengths, *,
                           scale: float, tq: int,
                           interpret: Optional[bool] = None,
                           heads_a_step: int = 4,
                           chunk: int = PICKED_CHUNK):
    """Latent attention in the EXPANDED form under a selection: tokens
    packed in tiles of ``tq``, every tile the tokens of ONE row, attend
    head by head that row's per-head keys and values, which the caller
    made of the row's latent rows once for the launch
    (``paged_model._expanded_index_attention``).

    q ``[nh, N, dn + dr]`` (N whole tiles; ``q_nope | q_rope``); a
    row's ``ctx = C x E`` positions lie in ``C`` pieces of ``E`` as the
    caller made them: k ``[R, C, nh, E, dn]`` (``c^kv W^UK``), k_rope
    ``[R, C, E, dr]`` (the rotated part every head shares), v ``[R, C,
    nh, E, dv]`` (``c^kv W^UV``); picked_t ``[N / tt, positions, tt]``
    (int8 or the queries' type; > 0: the token PICKED the position: the
    flags of ``tt`` tokens at a time, TRANSPOSED, a token a lane, as the
    caller's loop over tiles of tokens leaves them; ``tt`` divides
    ``tq``; positions past the array's are picked by none), tile_rows
    ``[N / tq]`` each tile's row, lengths ``[N]`` each token's causal
    bound (0: no token); a piece whole chunks of ``chunk`` positions (or
    one chunk). A token attends
    the positions under its bound that it picked; nothing past the
    launch's largest bound is read (the grid's chunk axis ends there).
    Returns ``[N, nh * dv]``, token-major. On a TPU the kernel
    (``ragged_attention_latent_picked_heads`` in a trace); off it the
    jnp reference, and the kernel under the TPU interpreter only where
    ``interpret`` asks for it."""
    if interpret is None and _interpret():
        return picked_heads_attention_reference(
            q, k, k_rope, v, picked_t, tile_rows, lengths, scale=scale,
            tq=tq)
    nh, N, dk = q.shape
    R, C, _, E, dn = k.shape
    dv = v.shape[-1]
    n = N // tq
    hs = math.gcd(heads_a_step, nh)
    P = min(chunk, E)
    tt = picked_t.shape[-1]
    assert N == n * tq and E % P == 0 and tq % 128 == 0 and tq % tt == 0, \
        (N, tq, tt, E, P)
    per = E // P                                    # chunks a piece
    lengths = lengths.astype(jnp.int32)
    chunks = -(-jnp.max(lengths.reshape(n, tq), axis=1) // P)

    def at(i, c, chunks_ref):
        """the chunk a step reads: the last one under the tile's bound
        where the step is past it (the same block: no copy)"""
        return jnp.maximum(jnp.minimum(c, chunks_ref[i] - 1), 0)

    def head_block(i, h, c, rows_ref, chunks_ref):
        c = at(i, c, chunks_ref)
        return (rows_ref[i], c // per, h, c % per, 0)

    def rope_block(i, h, c, rows_ref, chunks_ref):
        c = at(i, c, chunks_ref)
        return (rows_ref[i], c // per, c % per, 0)

    return pl.pallas_call(
        functools.partial(_picked_heads_kernel, scale=scale, P=P),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            # (the chunks under the launch's largest bound: a dynamic
            # bound, so a table far wider than its rows costs no step)
            grid=(n, nh // hs, jnp.maximum(jnp.max(chunks), 1)),
            in_specs=[
                pl.BlockSpec((hs, tq, dk), lambda i, h, c, *_: (h, i, 0)),
                pl.BlockSpec((1, 1, hs, P, dn), head_block),
                pl.BlockSpec((1, 1, P, dk - dn), rope_block),
                pl.BlockSpec((1, 1, hs, P, dv), head_block),
                pl.BlockSpec((tq // tt, P, tt),
                             lambda i, h, c, rows_ref, chunks_ref:
                             (i, at(i, c, chunks_ref), 0)),
                pl.BlockSpec((1, tq), lambda i, h, c, *_: (0, i))],
            out_specs=pl.BlockSpec((tq, hs * dv),
                                   lambda i, h, c, *_: (i, h)),
            scratch_shapes=[pltpu.VMEM((hs, dv, tq), jnp.float32),
                            pltpu.VMEM((hs, 1, tq), jnp.float32),
                            pltpu.VMEM((hs, 1, tq), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((N, nh * dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_TILED_VMEM_BYTES),
        interpret=pltpu.InterpretParams() if interpret or _interpret()
        else False,
        name="ragged_attention_latent_picked_heads",
    )(tile_rows.astype(jnp.int32), chunks, q, k, k_rope, v,
      picked_t.astype(jnp.int8) if picked_t.dtype == jnp.bool_
      else picked_t, lengths.reshape(1, N))
