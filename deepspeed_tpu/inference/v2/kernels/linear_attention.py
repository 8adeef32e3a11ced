"""Linear attention with a recurrent state (Kimi Delta Attention, KDA:
Kimi Linear, arXiv:2510.26692), in the two forms a server needs.

A head keeps a float32 state ``S`` [d_k, d_v] in place of cached
positions. A token with query ``q``, key ``k`` (both l2-normalised),
value ``v``, log-decay ``g`` <= 0 a KEY CHANNEL and update strength
``beta`` in (0, 1) does

    S' = diag(exp(g)) S;   S = S' + beta k (v - S'^T k)^T;   o = S^T q

* :func:`kda_step`: that update, one token a row (decode). Elementwise
  float32: a row's state read once and written once.
* the CHUNKED form: the same recurrence over a row's prompt tokens,
  ``chunk`` (64) tokens at a time in matmuls, the state carried from
  chunk to chunk. Rows of any lengths lie one after another in the
  ragged step's flat token buffer.

Each form has a Pallas kernel that works on the state leaf where it
lies and an XLA implementation that is its reference in the tests, and
so has the one-token form of the short convolution in front of q, k
and v:

===========  ==========================  ===============================
form         kernel (a TPU, d_k and d_v  XLA (every other backend and
             multiples of 128, heads in  width: the CPU tests, toy
             whole grid steps)           widths)
===========  ==========================  ===============================
one token    :func:`kda_state_update`    gather, :func:`kda_step`, scatter
chunked      :func:`kda_chunk_fwd`       :func:`kda_chunked`
convolution  :func:`conv_update`         gather, :func:`causal_conv_step`,
at decode    (q, k and v each whole      scatter
             (16, 128) tiles; one part
             of any rows, with a bias:
             a state-space layer's)
===========  ==========================  ===============================

:func:`state_kernel_serves` / :func:`chunk_kernel_serves` /
:func:`conv_kernel_serves` say which runs, from the leaf's shape and
``jax.default_backend()`` alone: no option selects a form.
:func:`kda_chunked` is a ``while_loop`` whose
step j takes chunk j of a block of rows (gathered by ``starts`` /
``counts``) from their slots and back. :func:`kda_chunk_fwd` is one
launch a layer: a grid step takes one row and 8 of its heads, the
heads' states come from the row's slot once, stay in VMEM over the row's
chunks and go back once, and the tokens (the layer's bf16 projections,
:func:`kda_inputs` run on them in VMEM) are copied from where they lie
in the flat buffer and the outputs to where they go, a window of 64
tokens at a time.

The chunked form (the WY representation of the delta rule): with ``G``
the cumulative log-decay inside a chunk and ``A[i, j] = sum_c k_i[c]
k_j[c] exp(G_i[c] - G_j[c])`` for j < i, the rank-one updates of a chunk
are ``u = (I + diag(beta) A)^-1 beta (V - (K exp(G)) S_0)``, a unit
lower-triangular solve; then ``o = (Q exp(G)) S_0 + tril(QK) u`` and
``S_C = diag(exp(G_C)) S_0 + (K exp(G_C - G))^T u``. ``exp(-G_j)`` alone
overflows float32 (64 tokens at the decay's floor of -5 reach e^320), so
a pair (i, j) is factored round the cumulative decay at the start of i's
SUB-block of ``sub`` (16) tokens: ``exp(G_i - R) <= 1`` and ``exp(R -
G_j) <= e^(16 x 5)``, which float32 holds. :func:`kda_chunked` hands the
solve to ``solve_triangular``; the kernel inverts ``I + diag(beta) A``
on its 16 x 16 diagonal blocks by substitution on the VPU and takes the
blocks below them through the MXU (:func:`_chunk_in_vmem`). Every
product of either is float32 (``Precision.HIGHEST``).

:func:`causal_conv_rows` / :func:`causal_conv_step` are the short
depthwise convolution in front of q, k and v, over a row's own tokens,
its last ``taps - 1`` inputs carried as state beside ``S`` in a leaf of
its own, an input as rows of 128 lanes (:func:`conv_leaf_shape`).
:func:`conv_update` is the one-token form as one launch a layer: a
grid step takes ``SLOTS_A_STEP`` rows, a row's slot comes in and goes
back once by the kernel's own copies, several in flight, the taps
(and a bias, which the Mamba-2 layers of ``state_space.py`` bring with
their one input in place of three) are fetched once. The ragged step
(a row's prompt tokens) keeps :func:`causal_conv_rows`.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .slot_leaf import hbm_out, in_hbm

CHUNK = 64
SUB = 16
ROWS_A_STEP = 32
# rows whose slots a grid step of the convolution's one-token kernel has
# in flight each way; 4 and 16 read the same on the chip, 2 a fifth more
# and 1 (the blocked pipeline's) three quarters more (PERF.md, PR 57)
SLOTS_A_STEP = 8
_HI = jax.lax.Precision.HIGHEST


def kda_step(q, k, v, g, beta, state):
    """One token a row. q, k, g [N, nh, dk]; v [N, nh, dv]; beta
    [N, nh]; state [N, nh, dk, dv]; all float32. Returns (o [N, nh, dv],
    state)."""
    s = state * jnp.exp(g)[..., None]
    u = beta[..., None] * (v - jnp.sum(s * k[..., None], axis=-2))
    s = s + k[..., None] * u[..., None, :]
    return jnp.sum(s * q[..., None], axis=-2), s


# heads of one row a step of the decode kernel's grid takes: 16 states of
# [128, 128] float32 are 1 MB in and 1 MB out, double-buffered 4 MB
HEADS_A_STEP = 16


def state_kernel_serves(leaf) -> bool:
    """Whether :func:`kda_state_update` takes this state leaf
    ``[layers, slots, nh, dk, dv]``: on a TPU, a head's state whole
    (8, 128) tiles, the heads whole steps."""
    nh, dk, dv = leaf.shape[2:]
    return (jax.default_backend() == "tpu" and dk % 128 == 0
            and dv % 128 == 0 and nh % min(HEADS_A_STEP, nh) == 0
            and min(HEADS_A_STEP, nh) % 8 == 0)


def _state_kernel(layer_ref, slots_ref, fresh_ref, s_ref, eg_ref, k_ref,
                  bk_ref, q_ref, bv_ref, so_ref, o_ref, *, heads):
    """One row's ``heads`` states through one token. The key-side
    vectors (exp(g), k, beta k, q) come as COLUMNS ``[dk, heads]`` (a
    head a lane: its slice broadcasts along the state's lanes), the
    value side (beta v, the output) as rows ``[heads, dv]``; what
    contracts over dk is a sum over sublanes."""
    del layer_ref, slots_ref            # the index maps read them
    keep = fresh_ref[pl.program_id(0)] == 0
    for j in range(heads):
        s = jnp.where(keep, s_ref[j].astype(jnp.float32), 0.0)
        s = s * eg_ref[:, j:j + 1]
        u = bv_ref[j:j + 1, :] - jnp.sum(s * bk_ref[:, j:j + 1], axis=0,
                                         keepdims=True)
        s = s + k_ref[:, j:j + 1] * u
        so_ref[j] = s.astype(so_ref.dtype)
        o_ref[j:j + 1, :] = jnp.sum(s * q_ref[:, j:j + 1], axis=0,
                                    keepdims=True)


def kda_state_update(leaf, layer, slots, fresh, q, k, v, g, beta,
                     interpret=False):
    """:func:`kda_step` on the rows' slots of the state leaf where it
    lies: ``leaf`` ``[layers, slots, nh, dk, dv]`` stays whole in HBM
    (coloured so, as :func:`conv_update`'s), and a grid step copies in
    ``HEADS_A_STEP`` heads of row n's slot
    ``slots[n]`` at ``layer`` (both prefetched scalars), puts them
    through the token and copies them back to where they came from
    (aliased): a state is read once and written once, where a gather,
    the update and a scatter move it three times. ``fresh[n]``: the
    row's first token, its state starts from zeros. q, k, g
    [N, nh, dk], v [N, nh, dv], beta [N, nh], float32. Returns
    (o [N, nh, dv] float32, leaf). A trace shows it as
    ``kda_state_update``."""
    N, nh, dk = k.shape
    dv = v.shape[-1]
    hb = min(HEADS_A_STEP, nh)
    blocks = nh // hb

    def columns(x):                     # [N, nh, dk] -> [N, blocks, dk, hb]
        return x.reshape(N, blocks, hb, dk).transpose(0, 1, 3, 2)

    col = pl.BlockSpec((None, None, dk, hb), lambda n, h, *_: (n, h, 0, 0))
    row = pl.BlockSpec((None, hb, dv), lambda n, h, *_: (n, h, 0))
    state = pl.BlockSpec(
        (None, None, hb, dk, dv),
        lambda n, h, layer, slots, fresh: (layer[0], slots[n], h, 0, 0))
    leaf = in_hbm(leaf, interpret)
    so, o = pl.pallas_call(
        functools.partial(_state_kernel, heads=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(N, blocks),
            in_specs=[state, col, col, col, col, row],
            out_specs=[state, row]),
        out_shape=[hbm_out(leaf, interpret),
                   jax.ShapeDtypeStruct((N, nh, dv), jnp.float32)],
        input_output_aliases={3: 0},
        name="kda_state_update", interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), slots.astype(jnp.int32),
      fresh.astype(jnp.int32), leaf, columns(jnp.exp(g)), columns(k),
      columns(beta[..., None] * k), columns(q), beta[..., None] * v)
    return o, so


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def kda_inputs(q, k, v, f, b, rate, dt_bias, floor):
    """The recurrence's float32 (q, k, v, g, beta) of a linear layer's
    convolved q, k, v and its gate projections f, b, a head's width on
    the last axis (b: a value a head): q and k l2-normalised (q times
    d_k^-1/2), the decay ``floor * sigmoid(rate (f + dt_bias))`` a key
    channel, the update strength ``sigmoid(b)``. Elementwise but for the
    norms' sums over the last axis: the same lines run on ``[..., nh,
    d]`` in XLA and on one head's ``[chunk, d]`` inside the kernel."""
    q, k, v, f = (a.astype(jnp.float32) for a in (q, k, v, f))
    g = floor * jax.nn.sigmoid(rate * (f + dt_bias))
    return (l2norm(q) * q.shape[-1] ** -0.5, l2norm(k), v, g,
            jax.nn.sigmoid(b.astype(jnp.float32)))


# heads of one row a grid step of the chunk kernel takes, as the leading
# axis of every value in it: an operation is traced ONCE for all of them
# (a Python loop over the heads cost a ragged step 26-35 s of tracing),
# and Mosaic issues it a head after another, so that one head's product
# is in the MXU while the next is issued (a head at a time the kernel
# waited out each product's latency: 17.7 ms a layer of the cell's
# launch). Compile time grows faster than the heads: 2.9 s a kernel
# with 8, 7.9 with 16 (AOT), for 9.3 against 9.1 ms a layer (the chip).
CHUNK_HEADS = 8


def chunk_kernel_serves(leaf) -> bool:
    """Whether :func:`kda_chunk_fwd` takes this state leaf ``[layers,
    slots, nh, dk, dv]``: on a TPU, a head's q, k and v whole lane
    blocks of the flat token buffer, the heads whole grid steps whose
    outputs are whole (8, 128) tiles of ``[T, nh, dv]``."""
    nh, dk, dv = leaf.shape[2:]
    return (jax.default_backend() == "tpu" and dk % 128 == 0
            and dv % 128 == 0 and nh % CHUNK_HEADS == 0)


def _bdot(a, b, contract):
    """A float32 product a head: ``contract`` names the contracted axis
    of each operand, axis 0 the heads."""
    return jax.lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((0,), (0,))),
        precision=_HI, preferred_element_type=jnp.float32)


def _chunk_in_vmem(q, k, v, g, beta, st, sub):
    """The heads of a grid step through one chunk, every operand a value
    in VMEM, heads leading. q, k, g [H, C, dk]; v [H, C, dv]; beta
    [H, C, 1]; ``st`` the states TRANSPOSED [H, dv, dk] (their decay
    then scales lanes). :func:`_chunk`'s mathematics with the triangular
    solve as matmuls: the pairs come out of ONE product transposed
    (token j a sublane), the unit lower-triangular ``I + N`` is inverted
    on its ``sub`` x ``sub`` diagonal blocks row by row on the VPU
    (exact float32) and the blocks below them go by forward substitution
    through the MXU. Returns (o [H, C, dv], st)."""
    H, C, dk = k.shape
    nb = C // sub
    row = jax.lax.broadcasted_iota(jnp.int32, (1, C, 1), 1)
    # inclusive cumulative log-decay inside each sub-block: a scan of
    # log2(sub) shifted adds
    Gs, step = g, 1
    while step < sub:
        Gs = Gs + jnp.where(row % sub >= step, pltpu.roll(Gs, step, 1), 0.0)
        step *= 2
    # the cumulative decay at the start of each sub-block, and at the end
    ref = [jnp.zeros((H, 1, dk), jnp.float32)]
    for a in range(1, nb + 1):
        ref.append(ref[-1] + Gs[:, a * sub - 1:a * sub])
    last = ref.pop()
    G = Gs + jnp.concatenate(
        [jnp.broadcast_to(r, (H, sub, dk)) for r in ref], axis=1)
    fwd = jnp.exp(Gs)                                        # <= 1
    # row a of the pairs' blocks against every token j up to its last:
    # k_j exp(R_a - G_j), at most e^(-floor sub) inside block a itself
    kh = jnp.concatenate(
        [k[:, :(a + 1) * sub] * jnp.exp(ref[a] - G[:, :(a + 1) * sub])
         for a in range(nb)], axis=1)
    pt = _bdot(kh, jnp.concatenate([beta * k * fwd, q * fwd], axis=1),
               (2, 2))                                       # [H, .., 2C]
    eg = jnp.exp(G)
    y = _bdot(jnp.concatenate([k * eg, q * eg], axis=1), st, (2, 2))
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 2 * C), 2)
    wt, off = jnp.zeros((H, C, 2 * C), jnp.float32), 0
    for a in range(nb):
        n = (a + 1) * sub
        piece = pt[:, off:off + n]
        if n < C:
            piece = jnp.concatenate(
                [piece, jnp.zeros((H, C - n, 2 * C), jnp.float32)], axis=1)
        wt = jnp.where(lane % C // sub == a, piece, wt)
        off += n
    # lanes [0, C): N^T = (diag(beta) A)^T, strictly j < i; lanes
    # [C, 2C): the queries' pairs, j <= i
    wt = jnp.where(row < lane % C + lane // C, wt, 0.0)
    w = jnp.swapaxes(wt, 1, 2)                               # [H, 2C, C]
    rhs = beta * (v - y[:, :C])
    # (I + N_aa)^-1 of every diagonal block (block a of head h at a H +
    # h), a row at a time: row i is e_i - sum_j N[i, j] row j, N[i, :] a
    # sublane vector of N^T
    dt = jnp.concatenate(
        [wt[:, a * sub:(a + 1) * sub, a * sub:(a + 1) * sub]
         for a in range(nb)], axis=0)
    tri = jax.lax.broadcasted_iota(jnp.int32, (1, sub, 1), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, sub), 2)
    inv = jnp.broadcast_to((tri == col).astype(jnp.float32), dt.shape)
    for i in range(1, sub):
        inv = jnp.where(tri == i, (col == i).astype(jnp.float32) - jnp.sum(
            dt[:, :, i:i + 1] * inv, axis=1, keepdims=True), inv)
    us = []
    for a in range(nb):
        ra = rhs[:, a * sub:(a + 1) * sub]
        if a:
            ra = ra - _bdot(w[:, a * sub:(a + 1) * sub, :a * sub],
                            jnp.concatenate(us, axis=1), (2, 1))
        us.append(_bdot(inv[a * H:(a + 1) * H], ra, (2, 1)))
    u = jnp.concatenate(us, axis=1)
    o = y[:, C:] + _bdot(w[:, C:], u, (2, 1))
    st = st * jnp.exp(last) + _bdot(u, k * jnp.exp(last - G), (1, 1))
    return o, st


def _chunk_kernel(layer_ref, slots_ref, fresh_ref, starts_ref, counts_ref,
                  s_ref, rate_ref, bias_ref, q_hbm, k_hbm, v_hbm, f_hbm,
                  b_hbm, so_ref, o_hbm, qb, kb, vb, fb, bb, ob, st, sem_in,
                  sem_out, *, floor, sub):
    """One row's ``heads`` states through the row's tokens. The flat
    buffer is cut into WINDOWS of ``chunk`` tokens where it lies (window
    w: tokens w chunk .. (w + 1) chunk), and the row takes every window
    it has a token in, the tokens of other rows masked (k = 0, g = 0,
    beta = 0: they leave the state as it was): every copy is aligned,
    and a row whose first token is a window's first takes exactly its
    chunks. The states wait in VMEM (``st``, transposed) from window to
    window."""
    del layer_ref, slots_ref            # the index maps read them
    heads, dv, dk = st.shape
    chunk = qb.shape[1]
    r, hblk = pl.program_id(0), pl.program_id(1)
    start, count = starts_ref[r], counts_ref[r]
    # the row's windows [w0, w1), and where window w's copies land
    w0 = start // chunk
    w1 = jnp.where(count > 0, (start + count - 1) // chunk + 1, w0)
    st[...] = jnp.swapaxes(jnp.where(
        fresh_ref[r] == 0, s_ref[...].astype(jnp.float32), 0.0), 1, 2)

    def tokens_of(w):
        return pl.ds(pl.multiple_of(w * chunk, chunk), chunk)

    def lanes_of(d):
        return pl.ds(pl.multiple_of(hblk * heads * d, heads * d), heads * d)

    def copies_in(w):
        rows, slot = tokens_of(w), (w - w0) % 2
        return [pltpu.make_async_copy(src.at[rows, lanes_of(d)],
                                      dst.at[slot], sem_in.at[i, slot])
                for i, (src, dst, d) in enumerate((
                    (q_hbm, qb, dk), (k_hbm, kb, dk), (v_hbm, vb, dv),
                    (f_hbm, fb, dk)))] + [
            pltpu.make_async_copy(b_hbm.at[hblk, rows], bb.at[slot],
                                  sem_in.at[4, slot])]

    def outputs_of(w):
        return o_hbm.at[tokens_of(w), pl.ds(hblk * heads, heads)]

    def copy_out(w, slot):
        return pltpu.make_async_copy(ob.at[slot], outputs_of(w),
                                     sem_out.at[slot])

    # the first row's steps write zeros over their heads' outputs, every
    # window of the buffer: what a token of no row keeps
    @pl.when(r == 0)
    def _():
        ob[0] = jnp.zeros(ob.shape[1:], ob.dtype)
        windows = o_hbm.shape[0] // chunk
        jax.lax.fori_loop(
            0, windows, lambda w, c: copy_out(w, 0).start(), None)
        jax.lax.fori_loop(
            0, windows, lambda w, c: copy_out(w, 0).wait(), None)

    @pl.when(w1 > w0)
    def _():
        for c in copies_in(w0):
            c.start()

    def by_head(x, d):                  # [C, heads d] -> [heads, C, d]
        return jnp.stack([x[:, h * d:(h + 1) * d] for h in range(heads)])

    def window(w, carry):
        slot = (w - w0) % 2
        for c in copies_in(w):
            c.wait()

        @pl.when(w + 1 < w1)
        def _():
            for c in copies_in(w + 1):
                c.start()

        @pl.when(w - 2 >= w0)             # ob[slot]'s last write has left
        def _():
            copy_out(w - 2, slot).wait()

        t = w * chunk + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
        live = (t >= start) & (t < start + count)

        # a window the row shares with its neighbours: their outputs
        # (or the zeros of no row's tokens) come in and go back as they are
        @pl.when((w * chunk < start) | ((w + 1) * chunk > start + count))
        def _():
            old = pltpu.make_async_copy(outputs_of(w), ob.at[slot],
                                        sem_out.at[slot])
            old.start()
            old.wait()

        o, st[...] = _chunk_in_vmem(*(
            jnp.where(live, a, 0.0) for a in kda_inputs(
                by_head(qb[slot], dk), by_head(kb[slot], dk),
                by_head(vb[slot], dv), by_head(fb[slot], dk),
                by_head(bb[slot], 1), rate_ref[...], bias_ref[...],
                floor)), st[...], sub)
        for h in range(heads):
            ob[slot, :, h] = jnp.where(live, o[h], ob[slot, :, h])
        copy_out(w, slot).start()
        return carry

    jax.lax.fori_loop(w0, w1, window, 0)
    for back in (2, 1):
        @pl.when(w1 - back >= w0)
        def _():
            copy_out(w1 - back, (w1 - back - w0) % 2).wait()
    so_ref[...] = jnp.swapaxes(st[...], 1, 2).astype(so_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("floor", "chunk", "sub", "interpret"))
def kda_chunk_fwd(tokens, rate, dt_bias, leaf, layer, slots, fresh, starts,
                  counts, floor, chunk=CHUNK, sub=SUB, interpret=False):
    """:func:`kda_chunked` as ONE kernel a layer and launch, the rows'
    states in place. ``tokens``: the flat (q, k, v, f [T, nh d], b
    [T, nh]) as the layer made them, :func:`kda_inputs` run on a window
    of them inside the kernel with ``rate`` [nh] and ``dt_bias``
    [nh dk]. The grid is (row, block of ``CHUNK_HEADS`` heads); a step
    copies the block's states in from ``leaf[layer, slots[r]]`` once
    (zeros where ``fresh[r]``), walks the row's windows of ``chunk``
    tokens where they lie in the flat buffer, and copies the states back
    once (aliased): :func:`kda_state_update`'s addressing. Returns
    (o [T, nh, dv] float32, zeros at tokens of no row; leaf). A trace
    shows it as ``kda_chunk_fwd``. A jit of its own: the layers of a
    program, and the program's signatures, trace the kernel once."""
    assert chunk % sub == 0 and floor * sub > -88.0, (chunk, sub, floor)
    q, k, v, f, b = tokens
    T = q.shape[0]
    R = starts.shape[0]
    nh, dk, dv = leaf.shape[2:]
    hb = CHUNK_HEADS
    blocks = nh // hb
    pad = -T % chunk
    if pad:
        q, k, v, f, b = (jnp.pad(a, ((0, pad), (0, 0)))
                         for a in (q, k, v, f, b))
    # beta's projection a head block, its heads the first of 128 lanes
    b = jnp.pad(b.astype(jnp.float32).reshape(T + pad, blocks, hb)
                .transpose(1, 0, 2), ((0, 0), (0, 0), (0, 128 - hb)))
    rate = jnp.broadcast_to(
        rate.astype(jnp.float32).reshape(nh, 1, 1), (nh, 1, dk))
    dt_bias = dt_bias.astype(jnp.float32).reshape(nh, 1, dk)
    state = pl.BlockSpec(
        (None, None, hb, dk, dv),
        lambda r, h, layer, slots, *_: (layer[0], slots[r], h, 0, 0))
    gate = pl.BlockSpec((hb, 1, dk), lambda r, h, *_: (h, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    leaf, o = pl.pallas_call(
        functools.partial(_chunk_kernel, floor=floor, sub=sub),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(R, blocks),
            in_specs=[state, gate, gate] + [hbm] * 5,
            out_specs=[state, hbm],
            scratch_shapes=[
                pltpu.VMEM((2, chunk, hb * dk), q.dtype),
                pltpu.VMEM((2, chunk, hb * dk), k.dtype),
                pltpu.VMEM((2, chunk, hb * dv), v.dtype),
                pltpu.VMEM((2, chunk, hb * dk), f.dtype),
                pltpu.VMEM((2, chunk, 128), jnp.float32),
                pltpu.VMEM((2, chunk, hb, dv), jnp.float32),
                pltpu.VMEM((hb, dv, dk), jnp.float32),
                pltpu.SemaphoreType.DMA((5, 2)),
                pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct(leaf.shape, leaf.dtype),
                   jax.ShapeDtypeStruct((T + pad, nh, dv), jnp.float32)],
        input_output_aliases={5: 0},
        name="kda_chunk_fwd",
        interpret=pltpu.InterpretParams() if interpret else False,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), slots.astype(jnp.int32),
      fresh.astype(jnp.int32), starts.astype(jnp.int32),
      counts.astype(jnp.int32), leaf, rate, dt_bias, q, k, v, f, b)
    return o[:T], leaf


def _chunk(q, k, v, g, beta, state, sub, floor):
    """One chunk of every row. q, k, g [R, nh, C, dk]; v [R, nh, C, dv];
    beta [R, nh, C]; state [R, nh, dk, dv]. A masked token has k = 0,
    beta = 0 and g = 0: it leaves the state as it was. ``floor`` < 0 is
    the least a token's g can be."""
    R, nh, C, dk = k.shape
    nb = C // sub
    G = jnp.cumsum(g, axis=2)                                # inclusive
    # cumulative decay at the start of each sub-block: [R, nh, nb, dk]
    ref = jnp.concatenate(
        [jnp.zeros_like(G[:, :, :1]), G[:, :, sub - 1:C - 1:sub]], axis=2)
    own = jnp.repeat(ref, sub, axis=2)                       # [R, nh, C, dk]
    fwd = jnp.exp(G - own)                                   # <= 1
    # k_j exp(R_a - G_j) for every sub-block a and token j of the chunk;
    # a token behind a's last is never read (masks below) and is capped
    back = jnp.exp(jnp.minimum(
        ref[:, :, :, None] - G[:, :, None], -floor * sub))
    kh = k[:, :, None] * back                                # [R,nh,nb,C,dk]

    def pairs(x):
        xs = (x * fwd).reshape(R, nh, nb, sub, dk)
        return jnp.einsum("rhaid,rhajd->rhaij", xs, kh,
                          precision=_HI).reshape(R, nh, C, C)

    idx = jnp.arange(C)
    below = idx[:, None] > idx[None, :]
    A = jnp.where(below, pairs(k), 0.0)
    QK = jnp.where(below | (idx[:, None] == idx[None, :]), pairs(q), 0.0)
    kin = k * jnp.exp(G)
    rhs = beta[..., None] * jnp.concatenate([kin, v], axis=-1)
    M = jnp.eye(C, dtype=A.dtype) + beta[..., None] * A
    X = jax.scipy.linalg.solve_triangular(M, rhs, lower=True,
                                          unit_diagonal=True)
    W, U0 = X[..., :dk], X[..., dk:]
    u = U0 - jnp.einsum("rhck,rhkv->rhcv", W, state, precision=_HI)
    o = jnp.einsum("rhck,rhkv->rhcv", q * jnp.exp(G), state,
                   precision=_HI) \
        + jnp.einsum("rhij,rhjv->rhiv", QK, u, precision=_HI)
    last = G[:, :, -1:]                                      # [R, nh, 1, dk]
    state = jnp.swapaxes(jnp.exp(last), 2, 3) * state + jnp.einsum(
        "rhck,rhcv->rhkv", k * jnp.exp(last - G), u, precision=_HI)
    return o, state


def kda_chunked(tokens, prepare, leaf, layer, slots, fresh, starts, counts,
                floor, chunk=CHUNK, sub=SUB, rows_a_step=ROWS_A_STEP):
    """The recurrence over the rows of a flat token buffer, from and to
    the rows' slots of the state leaf.

    ``tokens`` is a tuple of flat arrays [T, ...] and ``prepare`` makes
    of their gathered rows ``[B, C, ...]`` the float32 (q, k, v, g,
    beta) of a chunk, heads on axis 2 (``[B, C, nh, d]``; beta
    ``[B, C, nh]``): the caller's norms and gates run on a chunk's
    tokens only. Row r owns the tokens ``starts[r] .. starts[r] +
    counts[r]`` (``counts`` 0: no token) and the slot ``slots[r]`` of
    ``leaf`` ``[layers, slots, nh, dk, dv]`` at ``layer``: its state
    before its first token here (zeros where ``fresh[r]``), and after
    its last. ``floor`` < 0 bounds g from below (``floor * sub`` has to
    stay above float32's -88). A step of the loop takes one chunk of
    ``rows_a_step`` rows from their slots and back (what is live at once
    is theirs, not the launch's: the pairs' operands of 128 rows of 32
    heads are 0.5 GB each, their states 0.27), the rows' blocks inside,
    the chunks outside. Returns (o [T, nh, dv] float32, zeros at tokens
    of no row; leaf)."""
    assert chunk % sub == 0 and floor * sub > -88.0, (chunk, sub, floor)
    T = tokens[0].shape[0]
    R = starts.shape[0]
    nh, dv = leaf.shape[2], leaf.shape[4]
    B = max(b for b in range(1, min(rows_a_step, R) + 1) if R % b == 0)
    blocks = R // B
    steps = jnp.max((counts + chunk - 1) // chunk) * blocks
    within = jnp.arange(chunk)

    def body(carry):
        step, leaf, out = carry
        j, r0 = step // blocks, (step % blocks) * B
        first, count, slot, new_row = (
            jax.lax.dynamic_slice_in_dim(a, r0, B)
            for a in (starts, counts, slots, fresh))
        off = j * chunk + within                             # [C]
        live = off[None, :] < count[:, None]                 # [B, C]
        idx = jnp.where(live, first[:, None] + off[None, :], 0)
        q, k, v, g, beta = prepare(*(a[idx] for a in tokens))
        m = live[..., None, None]
        q, k, v, g = (jnp.where(m, a, 0.0).transpose(0, 2, 1, 3)
                      for a in (q, k, v, g))
        beta = jnp.where(live[..., None], beta, 0.0).transpose(0, 2, 1)
        state = jnp.where((new_row & (j == 0))[:, None, None, None], 0.0,
                          leaf[layer, slot].astype(jnp.float32))
        o, state = _chunk(q, k, v, g, beta, state, sub, floor)
        leaf = leaf.at[layer, slot].set(state.astype(leaf.dtype))
        out = out.at[jnp.where(live, idx, T)].set(
            o.transpose(0, 2, 1, 3), mode="drop")
        return step + 1, leaf, out

    _, leaf, out = jax.lax.while_loop(
        lambda c: c[0] < steps, body,
        (jnp.int32(0), leaf, jnp.zeros((T, nh, dv), jnp.float32)))
    return out, leaf


def causal_conv_rows(x, taps, conv_state, row_ids, starts, counts,
                     act=None):
    """Depthwise causal convolution over each row's own tokens of the
    flat buffer, then ``act``. x [T, D]; taps [K, D] (tap K - 1 meets
    the token itself); conv_state [R, K - 1, D]: the row's last K - 1
    inputs before these tokens, oldest first (zeros: none). Returns
    (act(y) [T, D] in x's type, the rows' new conv_state in
    ``conv_state``'s type). The sum runs in float32; every token is
    first convolved against its own launch's tokens alone, and a row's
    first K - 1 tokens, which reach behind it into the state, are then
    made again from the few inputs they need and written over."""
    T, K = x.shape[0], taps.shape[0]
    act = act or (lambda y: y)
    taps = taps.astype(jnp.float32)
    off = jnp.arange(T) - starts[row_ids]                    # place in row
    y = x.astype(jnp.float32) * taps[K - 1]
    for s in range(1, K):
        back = jnp.roll(x, s, axis=0).astype(jnp.float32)
        y = y + jnp.where((off >= s)[:, None], back, 0.0) * taps[K - 1 - s]
    out = act(y).astype(x.dtype)
    # [old inputs, this launch's first K - 1]: [R, 2K - 2, D], float32
    head = x[jnp.clip(starts[:, None] + jnp.arange(K - 1)[None, :],
                      0, T - 1)]
    seq = jnp.concatenate([conv_state.astype(jnp.float32),
                           head.astype(jnp.float32)], axis=1)
    for p in range(K - 1):
        yp = sum(taps[j] * seq[:, p + j] for j in range(K))
        out = out.at[jnp.where(counts > p, starts + p, T)].set(
            act(yp).astype(x.dtype), mode="drop")
    # the last K - 1 of [old inputs, this launch's]: entry i is input
    # counts + i of that sequence
    ext = counts[:, None] + jnp.arange(K - 1)[None, :]       # [R, K - 1]
    from_x = x[jnp.clip(starts[:, None] + ext - (K - 1), 0, T - 1)]
    from_old = jnp.take_along_axis(
        conv_state, jnp.clip(ext, 0, K - 2)[..., None], axis=1)
    new = jnp.where((ext >= K - 1)[..., None],
                    from_x.astype(conv_state.dtype), from_old)
    return out, new


def causal_conv_step(x, taps, conv_state, act=None):
    """One token a row: x [N, D], conv_state [N, K - 1, D]. Returns
    (act(y) [N, D] in x's type, conv_state)."""
    seq = jnp.concatenate(
        [conv_state.astype(jnp.float32), x.astype(jnp.float32)[:, None]],
        axis=1)                                              # [N, K, D]
    y = jnp.sum(seq * taps.astype(jnp.float32)[None], axis=1)
    y = y if act is None else act(y)
    return y.astype(x.dtype), seq[:, 1:].astype(conv_state.dtype)


def conv_leaf_shape(layers, slots, taps, width):
    """The shape of the convolution's state leaf: a slot's last
    ``taps - 1`` inputs of the ``width`` channels (q, k and v side by
    side), an input ``width / 128`` rows of 128 lanes where the width is
    whole lane blocks (float32 tiles of (8, 128) then hold a slot with
    no padding, where ``[taps - 1 = 3, width]`` pads 3 sublanes to 4
    and leaves a vector register an eighth full), else one row."""
    lanes = 128 if width % 128 == 0 else width
    return (layers, slots, taps - 1, width // lanes, lanes)


def conv_kernel_serves(leaf, parts=3) -> bool:
    """Whether :func:`conv_update` takes this convolution leaf
    ``[layers, slots, taps - 1, rows, lanes]`` (:func:`conv_leaf_shape`)
    for an input that comes as ``parts`` arrays side by side (a linear
    layer's q, k and v; one for a state-space layer's x, B and C): on a
    TPU, the channels whole lane blocks, and each of several parts whole
    (16, 128) tiles (what projections in bfloat16 ask; float32 ones
    half of it; ONE part is the leaf's whole rows, whatever their
    count)."""
    rows, lanes = leaf.shape[3:]
    return (jax.default_backend() == "tpu" and lanes == 128
            and (parts == 1 or rows % (parts * 16) == 0))


def _conv_token(held, x, w_ref, b_ref, act="silu"):
    """One row's token: ``held`` the slot's last inputs [rows, 128]
    float32, oldest first, ``x`` the token's own; the taps ``w_ref``
    [K, rows, 128], the bias ``b_ref`` [rows, 128] or None. The sum runs
    oldest tap first, then the bias, then ``act`` ("silu" or "none")."""
    seq = held + [x]
    y = seq[0] * w_ref[0]
    for t in range(1, len(seq)):
        y = y + seq[t] * w_ref[t]
    if b_ref is not None:
        y = y + b_ref[...]
    return jax.nn.silu(y) if act == "silu" else y


def _conv_kernel(layer_ref, slots_ref, fresh_ref, leaf_in, w_ref, *refs,
                 parts, bias, steps, act="silu"):
    """``R`` rows' tokens through the convolution a grid step. A row's
    slot [K - 1, rows, 128] (its last inputs, oldest first, the parts
    one after another along the rows) is copied out of ``leaf_in`` at
    ``[layer, slots[row]]`` and, shifted by the token, back to the same
    place of ``leaf_out`` (the same buffer) by the kernel's own copies:
    a step starts the NEXT step's ``R`` copies in before it waits for
    its own, and waits for a buffer's copies back only two steps later,
    so up to ``3 R`` copies of a slot are in flight, where the blocked
    pipeline had one each way and took 0.35 us of its own a row.
    ``refs``: the tokens' projections [R, rows / parts, 128] each, the
    bias [rows, 128] where the convolution has one, ``leaf_out``, the
    parts going out, two buffers of ``R`` slots each way and their
    semaphores. Two rows on ONE slot (rows that are not alive share the
    null slot) race on it, which nothing reads."""
    ins = refs[:parts]
    b_ref = refs[parts] if bias else None
    leaf_out = refs[parts + bias]
    outs = refs[parts + bias + 1:2 * parts + bias + 1]
    held_in, held_out, sem_in, sem_out = refs[2 * parts + bias + 1:]
    R, K1 = held_in.shape[1:3]
    g = pl.program_id(0)
    buf = g % 2

    def load(step, b, i):
        return pltpu.make_async_copy(
            leaf_in.at[layer_ref[0], slots_ref[step * R + i]],
            held_in.at[b, i], sem_in.at[b, i])

    def store(step, b, i):
        return pltpu.make_async_copy(
            held_out.at[b, i],
            leaf_out.at[layer_ref[0], slots_ref[step * R + i]],
            sem_out.at[b, i])

    def rows(body):
        # a loop, not R copies of the body: a program lowers the kernel
        # once a call site, and eight rows unrolled took eight times as
        # long to lower as one (set-up, not speed: PERF.md, PR 57)
        jax.lax.fori_loop(0, R, lambda i, _: body(i), None)

    @pl.when(g == 0)
    def _():
        rows(lambda i: load(0, 0, i).start())

    if steps > 1:
        @pl.when(g + 1 < steps)
        def _():
            rows(lambda i: load(g + 1, 1 - buf, i).start())

        @pl.when(g >= 2)
        def _():
            rows(lambda i: store(g - 2, buf, i).wait())

    part = ins[0].shape[1]

    def token(i):
        load(g, buf, i).wait()
        keep = fresh_ref[g * R + i] == 0
        x = jnp.concatenate([r[i].astype(jnp.float32) for r in ins], axis=0)
        held = [jnp.where(keep, held_in[buf, i, t].astype(jnp.float32), 0.0)
                for t in range(K1)]
        y = _conv_token(held, x, w_ref, b_ref, act)
        for t, kept in enumerate(held[1:] + [x]):
            held_out[buf, i, t] = kept.astype(held_out.dtype)
        store(g, buf, i).start()
        for j, o_ref in enumerate(outs):
            o_ref[i] = y[j * part:(j + 1) * part].astype(o_ref.dtype)

    rows(token)

    @pl.when(g == steps - 1)
    def _():
        rows(lambda i: store(g, buf, i).wait())
        if steps > 1:
            rows(lambda i: store(g - 1, 1 - buf, i).wait())


def conv_update(leaf, layer, slots, fresh, parts, taps, bias=None,
                name="kda_conv_update", interpret=False, act="silu"):
    """:func:`causal_conv_step` with ``act`` ("silu", or "none": a
    convolution that is a mixer's whole core and carries no activation;
    static) on the rows' slots of the
    convolution leaf where it lies: ``leaf`` ``[layers, slots, K - 1,
    rows, 128]`` (:func:`conv_leaf_shape`) stays whole in HBM (COLOURED
    so, operand and aliased output, where the kernel is compiled for a
    TPU: ``slot_leaf``; left to the compiler, a leaf that fits the
    chip's fast memory was carried there whole and back round every
    launch, and ``pl.ANY`` or ``pltpu.HBM`` on the ``BlockSpec`` did not
    stop it: they say where the kernel finds its operand, not where the
    program keeps it; on a TPU the caller donates the leaf or carries
    it in a loop, ``slot_leaf``'s last paragraph), and a grid step
    copies in the slots ``slots[n]`` at ``layer`` (both prefetched
    scalars) of ``SLOTS_A_STEP`` rows (of its largest divisor that
    divides the rows' count) side by side, a step ahead
    (:func:`_conv_kernel`), takes the rows' new projections ``parts``
    (arrays [N, D] side by side along the channels, in their own type),
    and writes each slot's inputs shifted by one with the token's own
    appended back to where they came from (aliased) and the convolved,
    activated parts in the projections' type: a slot is read once and
    written once. ``fresh[n]``: the row's first token, its inputs start
    from zeros. ``taps`` [K, channels] (tap K - 1 meets the token
    itself) and ``bias`` [channels] (None: none), fetched once. The sum
    runs in float32, oldest tap first, then the bias. Returns (the
    parts [N, D], leaf). A trace shows it as ``name``."""
    N, D = parts[0].shape
    K1, rows, lanes = leaf.shape[2:]
    n = len(parts)
    R = math.gcd(N, SLOTS_A_STEP)
    part = pl.BlockSpec((R, rows // n, lanes), lambda g, *_: (g, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    weights = pl.BlockSpec((K1 + 1, rows, lanes), lambda g, *_: (0, 0, 0))
    offset = [] if bias is None else [
        bias.astype(jnp.float32).reshape(rows, lanes)]
    leaf = in_hbm(leaf, interpret)
    leaf, *mixed = pl.pallas_call(
        functools.partial(_conv_kernel, parts=n, bias=len(offset),
                          steps=N // R, act=act),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(N // R,),
            in_specs=[hbm, weights] + [part] * n + [
                pl.BlockSpec((rows, lanes), lambda g, *_: (0, 0))
                for _ in offset],
            out_specs=[hbm] + [part] * n,
            scratch_shapes=[
                pltpu.VMEM((2, R, K1, rows, lanes), leaf.dtype)] * 2 + [
                pltpu.SemaphoreType.DMA((2, R))] * 2),
        out_shape=[hbm_out(leaf, interpret)] + [
            jax.ShapeDtypeStruct((N, rows // n, lanes), a.dtype)
            for a in parts],
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name=name,
        interpret=pltpu.InterpretParams() if interpret else False,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), slots.astype(jnp.int32),
      fresh.astype(jnp.int32), leaf,
      taps.astype(jnp.float32).reshape(K1 + 1, rows, lanes),
      *(a.reshape(N, rows // n, lanes) for a in parts), *offset)
    return tuple(a.reshape(N, D) for a in mixed), leaf


def kda_conv_update(leaf, layer, slots, fresh, q, k, v, taps,
                    interpret=False):
    """:func:`conv_update` of a linear layer's q, k and v [N, D] under
    ``taps`` [K, 3 D]: ``kda_conv_update`` in a trace."""
    return conv_update(leaf, layer, slots, fresh, (q, k, v), taps,
                       interpret=interpret)
