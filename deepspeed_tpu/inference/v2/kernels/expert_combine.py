"""The routed rows back from expert order, weighted and summed, by DMA.

``moe.sharded_moe.dropless_topk_dispatch`` leaves the experts' output
``ys`` ``[k T, H]`` in EXPERT order; a token's result is the sum over its
k picks of (the pick's weight x the pick's row of ``ys``). XLA's form of
that is a row gather through the inverse permutation to ``[k, T, H]``
and a fusion over it, and the gather of bfloat16 rows out of HBM runs at
a sixth of the memory's rate (PERF.md section 6, PR 55: 1.25 ms for
granite's 168 MB): in the tiled layout a row of ``[N, H]`` is ``H / 128``
pieces of 256 B, each sharing its 32-bit words with the neighbour row.

A DMA moves a row at the memory's rate where the row is ONE piece, and
Mosaic (jax 0.9.0) starts a copy only of whole tiles of the two minor
axes: a one-row (or two-row) slice of a tiled ``[N, H]`` operand in HBM
is refused, float32 or bfloat16 (``Slice shape along dimension 0 must be
aligned to tiling``), so the row's index has to be a LEADING axis. Two
launches, :func:`rows_combine`:

* ``moe_rows_whole``: ``ys`` streams through fast memory in blocks of
  rows, read as PAIRS (rows ``2 p`` and ``2 p + 1`` of a bf16 array are
  one row of 32-bit words: ``pltpu.bitcast``, no shuffle of halves) and
  written as ``[N / 2, H / 128, 128]`` words: a pair is then one piece
  of ``4 H`` bytes. Blocks past the last held row (a share's launch:
  half to three quarters of the rows) are neither read nor written.
* ``moe_rows_combine``: a tile of 16 to 64 tokens a grid step
  (:func:`_tokens`). A tile's HELD picks are listed first (XLA's, a
  stable sort of a tile's mask: where each lies in expert order, its
  place in the tile, its weight; a block at a time in SMEM, beside the
  next tile's), so that the kernel's loops run over them alone and
  branch on nothing: a skipped trip of a loop costs what a copy's start
  does, about 20 ns. The pairs' copies out of HBM (``pl.ANY``) are
  started a tile ahead, all of a tile's in flight, and waited for by
  their bytes; a pick held elsewhere is in no list, so nothing is copied
  for it and nothing added: never ``0 x`` what the grouped matmul left
  past its last group. The half is picked in fast memory (a bf16 value
  IS the high half of its float32: ``word << 16`` or ``word``, ``&
  0xffff0000``, which also cuts off the pair's other row), weighted and
  added in float32, and the tile's ``[tokens, H]`` block is written once.

Neither name starts with ``gmm``: the experts' rooflines read the
grouped matmuls alone.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# the combine's two buffers of a tile's pairs (a tile's are in flight at
# once and the next tile's behind them): :func:`_tokens`
_BUFFER_BYTES = 11 * 2 ** 20
# copies started a trip of the start loop, and tokens summed a trip of
# the sums' (k picks each): independent chains side by side (eight
# starts a trip read 27 ns a start where every pick is held, four 17)
_STARTS = 4
_SUMS = 1
# rows a grid step of the relayout
WHOLE_ROWS = 256
# a dispatch of fewer rows keeps XLA's gather (scripts/bench_kernels.py
# --only dispatch-rows; PERF.md section 6, PR 61)
MIN_ROWS = 4096


def shape_serves(rows: int, width: int, dtype, share: bool) -> bool:
    """The rule alone: whether a dispatch whose experts' output is
    ``[rows, width]`` of ``dtype`` is one the kernels take. A SHARE's
    (the experts held here are some of the router's, so that half to
    three quarters of the rows are held elsewhere and are neither relaid
    nor copied), of whole lane blocks of bfloat16, an even count of at
    least ``MIN_ROWS``. Where every expert is held the relayout reads
    and writes every row and a copy's start is paid for every pick:
    while this PR's end-to-end runs were made the kernels read 5.44 ms
    against the gather's 5.23 at trinity-mini's 131,072 rows of 4 KB and
    4.72 against 4.75 at smallthinker's; with four starts a trip (the
    final form) 4.29 and 3.81 alone on the chip, which no end-to-end run
    has followed yet: those launches keep the gather until one has
    (PERF.md sections 6 and 7, PR 61)."""
    return (share and dtype == jnp.bfloat16 and width % 128 == 0
            and rows % 2 == 0 and rows >= MIN_ROWS)


def rows_combine_serves(rows: int, width: int, dtype, share: bool) -> bool:
    """:func:`shape_serves` on a TPU (elsewhere every launch keeps the
    XLA lines)."""
    return jax.default_backend() == "tpu" \
        and shape_serves(rows, width, dtype, share)


def _interpret(interpret):
    return pltpu.InterpretParams() if interpret else False


def _whole_kernel(last_ref, ys_ref, out_ref):
    @pl.when(pl.program_id(0) <= last_ref[0])
    def _():
        R, H = ys_ref.shape
        pairs = pltpu.bitcast(ys_ref[...], jnp.int32)       # [R / 2, H]
        out_ref[...] = pairs.reshape(R // 2, H // 128, 128)


def rows_whole(ys, rows_held=None, interpret=False):
    """``ys`` [N, H] bfloat16 as pairs of rows, each ONE piece:
    ``[N / 2, H / 128, 128]`` int32, word ``[p, b, l]`` = row ``2 p``'s
    value at column ``128 b + l`` in its low half, row ``2 p + 1``'s in
    its high half. ``rows_held`` (a traced count; None: all): rows from
    there on are left as they lie (unwritten)."""
    N, H = ys.shape
    R = WHOLE_ROWS
    pad = -N % R
    if pad:
        ys = jnp.pad(ys, ((0, pad), (0, 0)))
    steps = (N + pad) // R
    last = jnp.reshape(steps - 1 if rows_held is None else jnp.minimum(
        jnp.maximum(rows_held - 1, 0) // R, steps - 1), (1,)).astype(jnp.int32)
    return pl.pallas_call(
        _whole_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(steps,),
            # a block past the last held one is the last held one again:
            # the pipeline fetches and writes a block when its index moves
            in_specs=[pl.BlockSpec(
                (R, H), lambda g, last: (jnp.minimum(g, last[0]), 0))],
            out_specs=pl.BlockSpec(
                (R // 2, H // 128, 128),
                lambda g, last: (jnp.minimum(g, last[0]), 0, 0))),
        out_shape=jax.ShapeDtypeStruct(
            ((N + pad) // 2, H // 128, 128), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="moe_rows_whole",
        interpret=_interpret(interpret),
    )(last, ys)


def _tokens(k: int, width: int) -> int:
    """Tokens a grid step of the combine: the most, a power of two from
    16 to 64, whose two buffers of k pairs a token stay inside
    ``_BUFFER_BYTES`` (granite's 10 pairs of 16 KB: 32; nemotron's 6 of
    12 KB in whole tiles: 64)."""
    lanes = -(-width // 1024) * 1024          # whole (8, 128) tiles
    fit = _BUFFER_BYTES // (2 * k * lanes * 4)
    return max(16, min(64, 1 << max(fit.bit_length() - 1, 0)))


def _combine_kernel(count_ref, half_ref, w_ref, src_ref, dst_ref, src_ahead,
                    dst_ahead, pairs_ref, out_ref, buf, sums, sem, *, k,
                    arithmetic):
    TT, H = out_ref.shape
    g, steps = pl.program_id(0), pl.num_programs(0)
    slot = g % 2

    def starts(src, dst, tile, slot):
        """Start the copies of ``tile``'s held picks, whose pairs ``src``
        lists and whose places in the tile ``dst``, into buffer
        ``slot``: ``_STARTS`` a trip of the loop (a lone start a trip is
        one chain of address arithmetic behind the other, 52 ns a start;
        side by side they overlap, 22). The lists go on with the picks
        held elsewhere, so the last trip's surplus copies whatever lies
        there to places whose picks are masked."""
        def some(t, _):
            for u in range(_STARTS):
                c = t * _STARTS + u
                pltpu.make_async_copy(pairs_ref.at[src[0, c]],
                                      buf.at[slot, dst[0, c]],
                                      sem.at[slot]).start()
        jax.lax.fori_loop(0, count_ref[tile] // _STARTS, some, None)

    pl.when(g == 0)(lambda: starts(src_ref, dst_ref, 0, 0))
    # the next tile's copies are in flight while this tile is summed
    pl.when(g + 1 < steps)(
        lambda: starts(src_ahead, dst_ahead, g + 1, 1 - slot))

    # a DMA semaphore counts bytes: a descriptor of 2^b pairs, never
    # started, waits for as many copies; the powers of two in the count
    for b in range(buf.shape[1].bit_length()):
        @pl.when((count_ref[g] >> b) & 1 == 1)
        def _(b=b):
            held = buf.at[slot, pl.ds(0, 1 << b)]
            pltpu.make_async_copy(held, held, sem.at[slot]).wait()

    def token(i):
        acc = jnp.zeros(buf.shape[2:], jnp.float32)
        for j in range(k):
            n = j * TT + i
            # the pick's mask, and in its low bits the shift that brings
            # its half of the word up (:func:`rows_combine`)
            half = half_ref[0, n]
            word = (buf[slot, n] << (half & 31)) & half
            acc = acc + pltpu.bitcast(word, jnp.float32) * w_ref[0, n]
        sums[i] = acc

    def tokens(t, _):
        for u in range(_SUMS):
            token(t * _SUMS + u)

    if arithmetic:
        jax.lax.fori_loop(0, TT // _SUMS, tokens, None)
    else:
        sums[...] = jnp.zeros_like(sums)
    # a token's sum is [H / 128, 128] as its pairs are; the block goes
    # out as rows
    out_ref[...] = sums[...].reshape(TT, H).astype(out_ref.dtype)


def _rows_combine(ys, inv, held, topv, rows_held=None, *, arithmetic=True,
                  skip=True, interpret=False):
    """``sum_j topv[t, j] * ys[inv[j * T + t]]`` over the picks whose
    ``held[j * T + t]`` (None: all), as ``[T, H]`` in ``ys``'s type.
    ``ys`` [k T, H] bfloat16 in expert order, ``inv`` [k T] where each
    pick-major row lies in it, ``topv`` [T, k] weights, ``rows_held``
    the count of rows the experts computed (:func:`rows_whole`). The
    products and the sum are float32. ``arithmetic`` / ``skip`` False
    are the bench's variants, for their times alone (the copies with no
    sum; the picks held elsewhere relaid, copied and added too)."""
    H = ys.shape[1]
    T, k = topv.shape
    TT = _tokens(k, H)
    pad = -T % TT
    tiles = (T + pad) // TT
    pairs = rows_whole(ys, rows_held if skip else None, interpret)

    def by_tile(a, fill):
        """``a`` [k T] pick-major as a tile's row: [tiles, k TT]."""
        a = jnp.pad(a.reshape(k, T), ((0, 0), (0, pad)),
                    constant_values=fill)
        return a.reshape(k, tiles, TT).transpose(1, 0, 2).reshape(
            tiles, k * TT)

    here = jnp.ones((k * T,), bool) if held is None or not skip else held
    here = by_tile(here, False)
    pos = by_tile(inv.astype(jnp.int32), 0)
    w = by_tile(topv.astype(jnp.float32).T.reshape(-1), 0.0)
    # a tile's held picks first, in their order: the loop that starts
    # the copies runs over a list and branches on nothing. ONE sort
    # carries the picks' places in the tile and their pairs along (a
    # ``take_along_axis`` behind an argsort is a gather of single
    # words: 0.21 ms a granite dispatch, my traced chip run, PR 61)
    _, dst, src = jax.lax.sort(
        (~here, jnp.broadcast_to(jnp.arange(k * TT, dtype=jnp.int32),
                                 here.shape), pos // 2),
        dimension=1, is_stable=True, num_keys=1)
    count = -(-jnp.sum(here, axis=1, dtype=jnp.int32) // _STARTS) * _STARTS

    def scalars(ahead):
        return pl.BlockSpec(
            (None, 1, k * TT),
            lambda g, count: (jnp.minimum(g + ahead, tiles - 1), 0, 0),
            memory_space=pltpu.SMEM)

    # a bf16 value is the high half of its float32: row 2 q is its
    # pair's low half (shifted up by 16: the mask's low bits say so, and
    # cut nothing off a word whose low half is then zeros), row 2 q + 1
    # the high one; a pick held elsewhere is masked to an exact zero,
    # whatever its place in the buffer holds
    half = jnp.where(here, jnp.where(pos % 2 == 0, -65536 + 16, -65536), 0)
    half, w, src, dst = (a[:, None] for a in (half, w, src, dst))
    out = pl.pallas_call(
        functools.partial(_combine_kernel, k=k, arithmetic=arithmetic),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(tiles,),
            in_specs=[scalars(0)] * 4 + [scalars(1)] * 2 + [
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((TT, H), lambda g, count: (g, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, k * TT, H // 128, 128), jnp.int32),
                pltpu.VMEM((TT, H // 128, 128), jnp.float32),
                pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((T + pad, H), ys.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="moe_rows_combine",
        interpret=_interpret(interpret),
    )(count, half, w, src, dst, src, dst, pairs)
    return out[:T] if pad else out


# under its own jit, as the grouped matmul is: a program's expert layers
# (a run of layers a scan body) then lower the two kernels ONCE a program,
# not once a body (0.35 s a body of set-up at granite's widths)
rows_combine = jax.jit(_rows_combine,
                       static_argnames=("arithmetic", "skip", "interpret"))
