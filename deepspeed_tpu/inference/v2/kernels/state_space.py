"""The Mamba-2 state-space recurrence (SSD: "Transformers are SSMs",
arXiv:2405.21060), in the two forms a server needs.

A head keeps a float32 state ``S`` [d_head, d_state] in place of cached
positions. A token with input ``x`` [d_head], step ``dt`` > 0, and the
vectors ``B``, ``C`` [d_state] of the head's GROUP (``groups`` of them a
token, consecutive heads a group: heads ``h`` with ``h // (heads /
groups)`` equal read the same pair; one group: all heads share it)
does, with the head's ``A`` < 0,

    S = exp(dt A) S + (dt x) B^T;   y = S C      (+ D x, the caller's)

Every function here takes B and C as ``[.., groups * d_state]``, the
groups side by side as the convolution leaves them, and reads the group
count off that width: one code path, and at one group the operations it
always made.

The decay is a SCALAR a head and token, so a head's ``d_head`` channels
are independent of each other and of which head they belong to but for
that scalar: the recurrence is written a CHANNEL (``nh * d_head`` of
them), and the state leaf keeps a channel a lane,

    ``[layers, slots, channels / 128, d_state, 128]``

(:func:`state_leaf_shape`; channel ``c = h * d_head + p`` lies at
``[c // 128, :, c % 128]``): whole (8, 128) tiles at every head width
(``[.., d_head = 64, d_state]`` would leave lanes or sublanes half full
on one side or the other), a token's ``y`` comes out as the lane-dense
row it is projected from, and what contracts over ``d_state`` is a sum
over sublanes.

* one token a row (decode): :func:`ssm_step` in XLA (gather, update,
  scatter), and the kernel :func:`ssm_state_update`, which takes a row's
  state out of its slot once and puts it back once, aliased
  (``kda_state_update``'s manner), and a token's B and C at their own
  size, a group a row of ``d_state`` values: it spreads a pair over the
  lanes in VMEM, once for all the lane blocks that read it, and no copy
  of a pair a lane ever lies in HBM.
* a row's prompt tokens, CHUNKED: inside a chunk of ``Q`` tokens with
  ``La`` the inclusive cumulative ``dt A``,
  ``y_t = exp(La_t) C_t S_0 + sum_{s <= t} (C_t . B_s) exp(La_t - La_s)
  dt_s x_s`` and ``S_Q = exp(La_Q) S_0 + sum_s exp(La_Q - La_s) (dt_s
  x_s) B_s^T``: matmuls, the pairs' decay ``exp(La_t - La_s)`` <= 1 made
  pair by pair (no factor of it overflows, whatever ``dt A`` is:
  Mamba-2 has no floor on it). ``C B^T`` is one matrix a group.
  :func:`ssm_chunked` in XLA (a ``while_loop`` over chunks of a block of
  rows), and the kernel :func:`ssm_chunk_fwd`: the flat token buffer is
  cut into windows of ``Q`` tokens where it lies, a grid step takes one
  window and a block of lane blocks of channels (inside ONE group of
  B and C), walks the rows that have tokens
  in it (their neighbours' masked), and a row's state waits in VMEM
  from window to window, out of its slot at the row's first window and
  back at its last.

:func:`state_kernel_serves` / :func:`chunk_kernel_serves` say which
runs, from the leaf's shape, the head width, the group count and
``jax.default_backend()`` alone: no option selects a form. A kernel
takes a lane block of 128 channels with ONE B and C, so where a lane
block would straddle two groups the XLA forms serve. Every product
is float32 (``Precision.HIGHEST``).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .slot_leaf import hbm_out, in_hbm

CHUNK = 256
_HI = jax.lax.Precision.HIGHEST
# channel groups (of 128 lanes) of one row a decode grid step takes at
# most: 16 states of [128, 128] float32 are 1 MB in and 1 MB out,
# double-buffered 4 (``_blocks_a_step``: fewer where a state is larger)
GROUPS_A_STEP = 16
# channel groups a grid step of the chunk kernel takes
CHUNK_GROUPS = 4
# the pairs' decay [rows, heads, Q, Q] float32 the XLA form may hold
_PAIR_BYTES = 1 << 28


def state_leaf_shape(layers, slots, channels, d_state):
    """The shape of the state leaf: a slot's ``channels`` states of
    ``d_state`` values, 128 channels a lane block where the channels are
    whole lane blocks, else all of them one block."""
    lanes = 128 if channels % 128 == 0 else channels
    return (layers, slots, channels // lanes, d_state, lanes)


def heads_of(state, heads):
    """A slot's (or slots') state as the heads' ``[..., heads, d_head,
    d_state]`` of the leaf's ``[..., groups, d_state, lanes]``."""
    *lead, g, n, w = state.shape
    return jnp.swapaxes(state, -1, -2).reshape(*lead, heads,
                                               g * w // heads, n)


def _channels(per_head, d_head):
    """A head's scalar at each of its channels: [..., nh] -> [..., nh *
    d_head]."""
    return jnp.repeat(per_head, d_head, axis=-1)


def _spread(v, leaf):
    """B or C [N, groups * d_state] at every channel of the leaf's
    layout: broadcastable against a row's state [N, G, d_state, W]. One
    group broadcasts as it is; more repeat a group's vector over its
    channels, wherever the lane blocks' edges fall."""
    G, n, W = leaf.shape[2:]
    N, groups = v.shape[0], v.shape[1] // n
    if groups == 1:
        return v[:, None, :, None]
    each = jnp.repeat(v.reshape(N, groups, n), G * W // groups, axis=1)
    return jnp.swapaxes(each.reshape(N, G, W, n), -1, -2)


def ssm_step(leaf, layer, slots, fresh, x, dt, a, b, c):
    """One token a row: x [N, C] (C = nh d_head channels), dt [N, nh]
    (softplus taken), a [nh] < 0, b and c [N, groups * d_state],
    float32; the rows' states from ``leaf[layer, slots]`` (zeros where
    ``fresh``) and back. Returns (y [N, C] float32 without the skip
    term, leaf)."""
    N, C = x.shape
    G, _, W = leaf.shape[2:]
    d_head = C // dt.shape[-1]
    decay = _channels(jnp.exp(dt * a), d_head).reshape(N, G, 1, W)
    dtx = (_channels(dt, d_head) * x).reshape(N, G, 1, W)
    s = jnp.where(fresh[:, None, None, None], 0.0,
                  leaf[layer, slots].astype(jnp.float32))
    s = s * decay + _spread(b, leaf) * dtx
    y = jnp.sum(s * _spread(c, leaf), axis=2).reshape(N, C)
    return y, leaf.at[layer, slots].set(s.astype(leaf.dtype))


def _blocks_a_step(blocks, d_state):
    """Lane blocks of one row a decode grid step takes, by the leaf's
    shape: ``GROUPS_A_STEP`` of them at a state of 128 a channel and
    under, and as many as keep a step's states at that size (1 MB in
    float32) above it: 8 at a state of 256, whose 16 would be 8 MB
    double-buffered in and out against a scoped default of 16."""
    return min(GROUPS_A_STEP, blocks,
               max(GROUPS_A_STEP * 128 // d_state, 1))


def _blocks_a_group(blocks, step, groups):
    """Lane blocks that read one B and C, where ``blocks`` lane blocks
    in grid steps of ``step`` are served under ``groups`` groups: a
    group whole lane blocks, and a grid step whole groups or a group
    whole grid steps; else None."""
    per = blocks // groups
    if blocks % groups or (per % step and step % per):
        return None
    return per


def state_kernel_serves(leaf, groups=1) -> bool:
    """Whether :func:`ssm_state_update` takes this state leaf
    ``[layers, slots, lane blocks, d_state, lanes]`` under ``groups``
    groups of B and C: on a TPU, the channels whole lane blocks, a lane
    block's state whole (8, 128) tiles, the lane blocks whole grid
    steps, no lane block astride two groups."""
    g, n, w = leaf.shape[2:]
    gb = _blocks_a_step(g, n)
    return (jax.default_backend() == "tpu" and w == 128 and n % 8 == 0
            and g % gb == 0 and gb % 8 == 0
            and _blocks_a_group(g, gb, groups) is not None)


def _state_kernel(layer_ref, slots_ref, fresh_ref, s_ref, decay_ref, dtx_ref,
                  b_ref, c_ref, so_ref, y_ref, b_sc, c_sc, *, per):
    """One row's lane blocks of channels through one token: a block's
    state [d_state, 128], its channels' decay and ``dt x`` rows of 128
    lanes. ``b_ref`` and ``c_ref`` [pairs, d_state] are the pairs of the
    groups this step's blocks lie in, a group a row as the convolution
    leaves it; a group's pair is spread over the lanes ONCE, into
    ``b_sc`` / ``c_sc`` [d_state, 128] (the row over the sublanes, then
    one transpose), and serves its ``per`` blocks from there."""
    del layer_ref, slots_ref            # the index maps read them
    keep = fresh_ref[pl.program_id(0)] == 0
    blocks, n, W = s_ref.shape
    for k in range(b_ref.shape[0]):
        for ref, sc in ((b_ref, b_sc), (c_ref, c_sc)):
            sc[...] = jnp.broadcast_to(ref[k:k + 1, :], (W, n)).T
        for j in range(k * per, min((k + 1) * per, blocks)):
            s = jnp.where(keep, s_ref[j].astype(jnp.float32), 0.0)
            s = s * decay_ref[j:j + 1, :] + b_sc[...] * dtx_ref[j:j + 1, :]
            so_ref[j] = s.astype(so_ref.dtype)
            y_ref[j:j + 1, :] = jnp.sum(s * c_sc[...], axis=0, keepdims=True)


def ssm_state_update(leaf, layer, slots, fresh, x, dt, a, b, c,
                     interpret=False):
    """:func:`ssm_step` on the rows' slots of the state leaf where it
    lies: ``leaf`` stays whole in HBM (coloured so, ``slot_leaf``:
    the one rule of every slot leaf a kernel updates in place, whether
    or not it could fit anywhere else), and a grid step copies in
    ``GROUPS_A_STEP`` channel groups of row n's slot ``slots[n]`` at
    ``layer`` (both prefetched scalars; ``_blocks_a_step`` of them: 16
    at a state of 128, 8 at 256), puts them through the token and
    copies them back to where they came from (aliased): a state is read
    once and written once, where a gather, the update and a scatter
    move it three times. B and C [N, groups * d_state] go in at their
    own size, a group a row ``[N, .., pairs, d_state]``, and a grid step
    takes the ``pairs`` rows of the groups its lane blocks lie in (a few
    KB): the kernel spreads a pair over the lanes in VMEM. A trace shows
    it as ``ssm_state_update``."""
    N, C = x.shape
    G, n, W = leaf.shape[2:]
    d_head = C // dt.shape[-1]
    gb = _blocks_a_step(G, n)
    per = _blocks_a_group(G, gb, b.shape[1] // n)
    pairs = max(gb // per, 1)           # groups of B and C a grid step
    span = max(gb, per)                 # lane blocks a block of pairs serves
    decay = _channels(jnp.exp(dt * a), d_head).reshape(N, G, W)
    dtx = (_channels(dt, d_head) * x).reshape(N, G, W)
    row = pl.BlockSpec((None, gb, W), lambda r, g, *_: (r, g, 0))
    shared = pl.BlockSpec((None, None, pairs, n),
                          lambda r, g, *_: (r, g * gb // span, 0, 0))
    state = pl.BlockSpec(
        (None, None, gb, n, W),
        lambda r, g, layer, slots, fresh: (layer[0], slots[r], g, 0, 0))
    leaf = in_hbm(leaf, interpret)
    so, y = pl.pallas_call(
        functools.partial(_state_kernel, per=per),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(N, G // gb),
            in_specs=[state, row, row, shared, shared],
            out_specs=[state, row],
            scratch_shapes=[pltpu.VMEM((n, W), jnp.float32)] * 2),
        out_shape=[hbm_out(leaf, interpret),
                   jax.ShapeDtypeStruct((N, G, W), jnp.float32)],
        input_output_aliases={3: 0},
        name="ssm_state_update", interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), slots.astype(jnp.int32),
      fresh.astype(jnp.int32), leaf, decay, dtx,
      *(v.reshape(N, -1, pairs, n) for v in (b, c)))
    return y.reshape(N, C), so


def _chunk(x, dt, a, b, c, state):
    """One chunk of every row, a head at a time in einsums. x [R, Q, nh,
    p]; dt [R, Q, nh] (0 at a masked token: it leaves the state as it
    was and adds nothing); a [nh]; b, c [R, Q, groups * n]; state [R,
    nh, p, n]. Returns (y [R, Q, nh, p], state). More groups than one:
    the same chunk a group, over the group's heads."""
    R, Q, nh, p = x.shape
    groups = b.shape[-1] // state.shape[-1]
    if groups > 1:
        hg = nh // groups
        y, state = jax.vmap(_chunk, in_axes=(2, 2, 0, 2, 2, 1),
                            out_axes=(2, 1))(
            x.reshape(R, Q, groups, hg, p), dt.reshape(R, Q, groups, hg),
            a.reshape(groups, hg), b.reshape(R, Q, groups, -1),
            c.reshape(R, Q, groups, -1),
            state.reshape(R, groups, hg, p, -1))
        return y.reshape(R, Q, nh, p), state.reshape(R, nh, p, -1)
    la = jnp.cumsum(dt * a, axis=1)                         # [R, Q, nh]
    idx = jnp.arange(Q)
    seen = (idx[:, None] >= idx[None, :])[None, :, :, None]
    pair = jnp.where(seen, jnp.exp(jnp.minimum(
        la[:, :, None] - la[:, None], 0.0)), 0.0)           # [R, t, s, nh]
    cb = jnp.einsum("rtn,rsn->rts", c, b, precision=_HI)
    dtx = dt[..., None] * x
    y = jnp.einsum("rts,rtsh,rshp->rthp", cb, pair, dtx, precision=_HI) \
        + jnp.exp(la)[..., None] * jnp.einsum(
            "rtn,rhpn->rthp", c, state, precision=_HI)
    last = la[:, -1]                                        # [R, nh]
    state = jnp.exp(last)[..., None, None] * state + jnp.einsum(
        "rsh,rshp,rsn->rhpn", jnp.exp(last[:, None] - la), dtx, b,
        precision=_HI)
    return y, state


def ssm_chunked(leaf, layer, slots, fresh, starts, counts, xbc, dt, a,
                chunk=CHUNK):
    """The recurrence over the rows of a flat token buffer, from and to
    the rows' slots of the state leaf. ``xbc`` [T, C + 2 groups
    d_state]: a token's x, B and C (each all its groups) side by side as
    the convolution leaves them, any float type (C, the channels, is the
    leaf's); dt [T, nh] (softplus taken); a [nh]. Row r owns the
    tokens ``starts[r] .. starts[r] + counts[r]`` (``counts`` 0: none)
    and the slot ``slots[r]``: its state before its first token here
    (zeros where ``fresh[r]``), and after its last. A step of the loop
    takes one chunk of a block of rows from their slots and back.
    Returns (y [T, C] in ``xbc``'s type without the skip term, zeros at
    tokens of no row; leaf)."""
    T, C = xbc.shape[0], leaf.shape[2] * leaf.shape[4]
    n = (xbc.shape[1] - C) // 2         # groups x d_state
    x, b, c = xbc[:, :C], xbc[:, C:C + n], xbc[:, C + n:]
    R, nh = starts.shape[0], dt.shape[-1]
    p, f32 = C // nh, jnp.float32
    B = max(k for k in range(1, R + 1) if R % k == 0 and (
        k == 1 or k * nh * chunk * chunk * 4 <= _PAIR_BYTES))
    blocks = R // B
    steps = jnp.max((counts + chunk - 1) // chunk) * blocks
    within = jnp.arange(chunk)

    def body(carry):
        step, leaf, out = carry
        j, r0 = step // blocks, (step % blocks) * B
        first, count, slot, new_row = (
            jax.lax.dynamic_slice_in_dim(v, r0, B)
            for v in (starts, counts, slots, fresh))
        off = j * chunk + within
        live = off[None, :] < count[:, None]                # [B, Q]
        idx = jnp.where(live, first[:, None] + off[None, :], 0)
        xs = x[idx].astype(f32).reshape(B, chunk, nh, p)
        dts = jnp.where(live[..., None], dt[idx].astype(f32), 0.0)
        state = jnp.where((new_row & (j == 0))[:, None, None, None], 0.0,
                          heads_of(leaf[layer, slot].astype(f32), nh))
        y, state = _chunk(xs, dts, a.astype(f32), b[idx].astype(f32),
                          c[idx].astype(f32), state)
        back = jnp.swapaxes(
            state.reshape(B, -1, leaf.shape[4], leaf.shape[3]), -1, -2)
        leaf = leaf.at[layer, slot].set(back.astype(leaf.dtype))
        out = out.at[jnp.where(live, idx, T)].set(
            y.reshape(B, chunk, C), mode="drop")
        return step + 1, leaf, out

    _, leaf, out = jax.lax.while_loop(
        lambda v: v[0] < steps, body,
        (jnp.int32(0), leaf, jnp.zeros((T, C), f32)))
    return out.astype(xbc.dtype), leaf


def chunk_kernel_serves(leaf, d_head, groups=1) -> bool:
    """Whether :func:`ssm_chunk_fwd` takes this state leaf ``[layers,
    slots, lane blocks, d_state, lanes]`` of heads ``d_head`` wide under
    ``groups`` groups of B and C: on a TPU, the channels whole lane
    blocks, a lane block whole heads or a head whole lane blocks,
    ``d_state`` whole lane blocks (B and C are blocks of the token
    buffer behind the channels', and the contracted width of the
    window's products), the lane blocks whole grid steps, and a grid
    step inside ONE group (its ``C B^T`` is one matrix)."""
    g, n, w = leaf.shape[2:]
    return (jax.default_backend() == "tpu" and w == 128 and n % 128 == 0
            and (128 % d_head == 0 or d_head % 128 == 0)
            and g % CHUNK_GROUPS == 0 and g % groups == 0
            and g // groups % CHUNK_GROUPS == 0)


def _dot(x, y, contract):
    return jax.lax.dot_general(x, y, ((contract[:1], contract[1:]), ((), ())),
                               precision=_HI,
                               preferred_element_type=jnp.float32)


def _chunk_kernel(layer_ref, slots_ref, fresh_ref, starts_ref, counts_ref,
                  lo_ref, hi_ref, a_ref, x_ref, dt_ref, b_ref, c_ref,
                  leaf_in, leaf_out, o_ref, st, sb, sem, *, d_head):
    """One window of ``Q`` flat tokens, ``groups`` channel groups of it,
    row by row of the rows that have tokens in it (``lo_ref[w] ..
    hi_ref[w]``). ``st`` [groups, d_state, 128] is the walked row's
    state, which waits there from window to window; ``sb`` stages it in
    the leaf's type on its way from and to the slot."""
    groups, n, W = st.shape
    Q = x_ref.shape[0]
    hblk, w = pl.program_id(0), pl.program_id(1)
    per = max(W // d_head, 1)           # heads a lane block holds
    first_head = hblk * (groups * W // d_head)
    f32 = jnp.float32
    t_col = w * Q + jax.lax.broadcasted_iota(jnp.int32, (Q, 1), 0)
    i_col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    i_row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    lower = (i_col >= i_row).astype(f32)            # [t, s]: s <= t
    upper = (i_col <= i_row).astype(f32)            # [s, t]: s <= t
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)
    bm, cm = b_ref[...].astype(f32), c_ref[...].astype(f32)
    cb = _dot(cm, bm, (1, 1)) * lower                        # [Q, Q]
    # this step's heads out of all of them: a one-hot product, exact
    nhp = dt_ref.shape[1]
    pick = (jax.lax.broadcasted_iota(jnp.int32, (nhp, W), 0)
            == first_head + jax.lax.broadcasted_iota(
                jnp.int32, (nhp, W), 1)).astype(f32)
    dts = _dot(dt_ref[...], pick, (1, 0))                    # [Q, W]
    gs = _dot(dt_ref[...] * a_ref[...], pick, (1, 0))        # dt A
    xs = x_ref[...].astype(f32)
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    def slot_of(ref, r):
        return ref.at[layer_ref[0], slots_ref[r],
                      pl.ds(hblk * groups, groups)]

    def row(r, carry):
        start, count = starts_ref[r], counts_ref[r]

        @pl.when(count > 0)
        def _():
            begins = start >= w * Q
            ends = start + count <= (w + 1) * Q

            @pl.when(begins & (fresh_ref[r] != 0))
            def _():
                st[...] = jnp.zeros(st.shape, st.dtype)

            @pl.when(begins & (fresh_ref[r] == 0))
            def _():
                load = pltpu.make_async_copy(slot_of(leaf_in, r), sb,
                                             sem.at[0])
                load.start()
                load.wait()
                st[...] = sb[...].astype(f32)

            # a token of another row: dt 0, so that it leaves the
            # state as it was and adds nothing to a token behind it
            live = (t_col >= start) & (t_col < start + count)
            dtl = jnp.where(live, dts, 0.0)                  # [Q, W]
            g = jnp.where(live, gs, 0.0)
            la = _dot(lower, g, (1, 0))                      # inclusive
            la_t = _dot(g, upper, (0, 0))                    # [W, Q]
            for j in range(groups):
                xg = xs[:, j * W:(j + 1) * W]
                y = jnp.zeros((Q, W), f32)
                grow = jnp.zeros((Q, W), f32)    # exp(la) a channel
                keep = jnp.zeros((1, W), f32)    # exp(la_Q) a channel
                left = jnp.zeros((Q, W), f32)    # dt x exp(la_Q - la)
                for i in range(per):
                    h = j * W // d_head + i     # among this step's heads
                    mine = (lane // d_head == i) if per > 1 else None
                    col, dtc = la[:, h:h + 1], dtl[:, h:h + 1]
                    pair = jnp.exp(jnp.minimum(col - la_t[h:h + 1, :], 0.0))
                    dtx = xg * dtc
                    if mine is not None:
                        dtx = jnp.where(mine, dtx, 0.0)
                    y = y + _dot(cb * pair, dtx, (1, 0))
                    last = col[Q - 1:Q, :]
                    parts = (jnp.exp(col), jnp.exp(last),
                             dtx * jnp.exp(last - col))
                    if mine is None:
                        # a head a lane block: its scalars over the lanes
                        # here, one axis at a time (Mosaic spreads no
                        # [1, 1] over sublanes and lanes at once)
                        grow, _, left = parts
                        keep = jnp.exp(jnp.broadcast_to(last, (1, W)))
                    else:
                        grow, keep, left = (
                            jnp.where(mine, v, acc) for v, acc in
                            zip(parts, (grow, keep, left)))
                s = st[j]
                y = y + grow * _dot(cm, s, (1, 0))
                st[j] = s * keep + _dot(bm, left, (0, 0))
                at = pl.ds(j * W, W)
                o_ref[:, at] = jnp.where(live, y.astype(o_ref.dtype),
                                         o_ref[:, at])

            @pl.when(ends)
            def _():
                sb[...] = st[...].astype(sb.dtype)
                store = pltpu.make_async_copy(sb, slot_of(leaf_out, r),
                                              sem.at[0])
                store.start()
                store.wait()

        return carry

    jax.lax.fori_loop(lo_ref[w], hi_ref[w] + 1, row, 0)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssm_chunk_fwd(leaf, layer, slots, fresh, starts, counts, xbc, dt, a,
                  chunk=CHUNK, interpret=False):
    """:func:`ssm_chunked` as ONE kernel a layer and launch, the rows'
    states in place (its arguments and results). The grid is (block of
    ``CHUNK_GROUPS`` lane blocks of channels, window of ``chunk`` flat
    tokens); x, B and C are blocks of the ONE ``xbc`` where it lies (the
    channels' lane blocks, then every group's B and every group's C, of
    which a grid step takes its own group's: no slice of it is made),
    and they, dt and the output (``xbc``'s type) move by the pipeline's
    own copies, a window at a time; the leaf stays whole in HBM and a row's state is copied out of ``leaf[layer, slots[r]]`` at
    the row's first window (zeros where ``fresh[r]``) and back at its
    last (aliased). A trace shows it as ``ssm_chunk_fwd``. A jit of its
    own: the layers of a program, and the program's signatures, trace
    the kernel once."""
    nh = dt.shape[-1]
    G, n, W = leaf.shape[2:]
    T, C = xbc.shape[0], G * W
    groups = (xbc.shape[1] - C) // (2 * n)
    gb = CHUNK_GROUPS
    per = G // groups // gb             # grid steps a group of B and C
    pad = -T % chunk
    if pad:
        xbc, dt = (jnp.pad(v, ((0, pad), (0, 0))) for v in (xbc, dt))
    windows = (T + pad) // chunk
    ends = starts + counts
    edges = jnp.arange(windows, dtype=jnp.int32) * chunk
    lo = jnp.searchsorted(ends, edges, side="right").astype(jnp.int32)
    hi = jnp.searchsorted(starts, edges + chunk,
                          side="left").astype(jnp.int32) - 1
    lanes = -(-nh // 128) * 128
    dt = jnp.pad(dt.astype(jnp.float32), ((0, 0), (0, lanes - nh)))
    a = jnp.pad(a.astype(jnp.float32), (0, lanes - nh)).reshape(1, lanes)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    leaf, y = pl.pallas_call(
        functools.partial(_chunk_kernel, d_head=C // nh),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7, grid=(G // gb, windows),
            in_specs=[
                pl.BlockSpec((1, lanes), lambda g, w, *_: (0, 0)),
                pl.BlockSpec((chunk, gb * W), lambda g, w, *_: (w, g)),
                pl.BlockSpec((chunk, lanes), lambda g, w, *_: (w, 0)),
                pl.BlockSpec((chunk, n), lambda g, w, *_: (
                    w, C // n + (g // per if groups > 1 else 0))),
                pl.BlockSpec((chunk, n), lambda g, w, *_: (
                    w, C // n + groups + (g // per if groups > 1 else 0))),
                hbm],
            out_specs=[hbm, pl.BlockSpec((chunk, gb * W),
                                         lambda g, w, *_: (w, g))],
            scratch_shapes=[pltpu.VMEM((gb, n, W), jnp.float32),
                            pltpu.VMEM((gb, n, W), leaf.dtype),
                            pltpu.SemaphoreType.DMA((1,))]),
        out_shape=[jax.ShapeDtypeStruct(leaf.shape, leaf.dtype),
                   jax.ShapeDtypeStruct((T + pad, C), xbc.dtype)],
        input_output_aliases={12: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 2 ** 20),
        name="ssm_chunk_fwd",
        interpret=pltpu.InterpretParams() if interpret else False,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), slots.astype(jnp.int32),
      fresh.astype(jnp.int32), starts.astype(jnp.int32),
      counts.astype(jnp.int32), lo, hi, a, xbc, dt, xbc, xbc, leaf)
    return y[:T], leaf
