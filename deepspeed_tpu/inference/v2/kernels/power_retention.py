"""Power retention (Manifest AI: "Scaling Context Requires Rethinking
Attention", arXiv:2507.04239), degree 2, in the two forms a server needs.

The softmax's ``exp(q.k)`` becomes ``(q.k / sqrt(hd))^2`` times a
learned cumulative decay, and because ``(q.k)^2 = phi(q).phi(k)`` for
the symmetric second power ``phi``, a KEY/VALUE head keeps a float32
state of fixed size in place of cached positions, which the ``G`` query
heads of its group all read. With ``g`` <= 0 a token's log-decay (one
scalar a key/value head):

    S = e^g S + phi(k) v^T      z = e^g z + phi(k)
    o_h = phi(q_h)^T S / (phi(q_h)^T z + eps)          h in the group

**The layout of phi.** The hd (hd + 1) / 2 distinct products ``u_a u_b``
are kept by CIRCULAR DISTANCE: row ``d`` of ``phi(u)`` [hd / 2 + 1, hd]
is ``u * roll(u, d) * c_d`` (lane a: ``u_a u_{a - d}``), ``c_0 = 1``,
``c_d = sqrt 2`` for 0 < d < hd / 2 and ``c_{hd/2} = 1`` (at distance
hd / 2 lanes a and a + hd / 2 hold the same pair: both stay, each
weighed once, which is the pair's two), all over sqrt(hd). A row is ONE
lane rotation of the 128-lane vector and a product, so ``phi`` is made
in fast memory from 128 lanes and never written out; the price is
hd / 2 values kept twice: 8,320 rows a head at hd = 128 where the
mechanism has 8,256. :func:`canonical_state` / :func:`canonical_norm`
give the 8,256 in the order ``a <= b`` (what ``sequence_state`` hands
out and a reference compares).

The state leaf is ``[layers, slots, kv heads, hd / 2 + 1, hd, hd]``
(``[.., d, i, a]``: row d of phi, value channel i, lane a) and the
normaliser's ``[layers, slots, kv heads, rows, hd]``, phi's rows
rounded up to whole sublane tiles of 8 (72 for 65; the rest stay
zeros: XLA's TPU compiler aborts on an update of a leaf whose rows are
not): a lane of phi a lane, so that phi's rows broadcast over sublanes,
and a head's state 65 whole [128, 128] tiles at the published width.

* one token a row (decode): :func:`retention_step` in XLA (gather,
  update, scatter) and the kernel :func:`retention_state_update`, a grid
  step a (row, key/value head): the head's 4.26 MB come out of the slot
  once and go back once (aliased), on the VPU.
* a row's prompt tokens, CHUNKED (``CHUNK`` tokens): inside a chunk the
  attention form ``(Q K^T)^2 / hd`` times the decay mask, between chunks
  ``phi(Q) S`` and ``S += phi(K)^T V``. The decay is a scalar a token,
  so a pair's ``exp(G_t - G_r)`` (r <= t) is made pair by pair and is
  <= 1, as are ``exp(G_t)`` and ``exp(G_C - G_r)``: no factor overflows
  whatever the gate (``exp(-G_r)`` alone would after a few tens of
  tokens of a hard one). :func:`retention_chunked` in XLA (a
  ``while_loop`` over the rows' chunks, a row's chunk a step) and the kernel
  :func:`retention_chunk_fwd`: a grid step a (row, key/value head), the
  head's state in VMEM over the row's chunks, phi's rows made one
  distance at a time and fed to the MXU from registers.

:func:`state_kernel_serves` / :func:`chunk_kernel_serves` say which
runs, from the leaf's shape and ``jax.default_backend()`` alone: no
option selects a form. Every product is float32
(``Precision.HIGHEST``).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .slot_leaf import hbm_out, in_hbm

CHUNK = 128
# value channels of a head's state a pass of the decode kernel's inner
# loop takes: 32 rows x 5 query heads are 20 accumulator registers
STRIPE = 32
_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


def phi_rows(hd):
    """Rows of ``phi``: the circular distances 0 .. hd / 2."""
    return hd // 2 + 1


def leaf_shapes(layers, slots, kv_heads, hd):
    """The shapes of the state leaf and of the normaliser's."""
    D = phi_rows(hd)
    return (layers, slots, kv_heads, D, hd, hd), (layers, slots, kv_heads,
                                                  -(-D // 8) * 8, hd)


def _coef(hd):
    c = np.full((phi_rows(hd),), np.sqrt(2.0), np.float32)
    c[0] = c[-1] = 1.0
    return c / np.sqrt(np.float32(hd))


def phi(u):
    """``[..., hd] -> [..., hd / 2 + 1, hd]``: the module docstring's
    layout, so that ``sum(phi(q) * phi(k)) = (q . k)^2 / hd``."""
    hd = u.shape[-1]
    assert hd % 2 == 0, hd
    # row d, lane a: u_{a - d}. One gather by a constant table: XLA's
    # TPU compiler aborts on a lone ``jnp.roll`` along the lanes
    back = (np.arange(hd)[None, :] - np.arange(phi_rows(hd))[:, None]) % hd
    return u[..., None, :] * jnp.take(u, back, axis=-1) \
        * _coef(hd)[:, None]


def _canonical_index(hd):
    """Where the pair (a, b), a <= b in row-major order, lies in phi's
    layout: (row d, lane) and what the kept value is multiplied by."""
    a, b = np.triu_indices(hd)
    near = b - a <= hd // 2
    d = np.where(near, b - a, hd - (b - a))
    lane = np.where(near, b, a)
    scale = np.where(d == hd // 2, np.sqrt(2.0), 1.0).astype(np.float32)
    return d, lane, scale


def canonical_state(state):
    """``[..., hd / 2 + 1, hd, hd] -> [..., hd (hd + 1) / 2, hd]``: the
    state against ``phi`` in the order a <= b, ``phi_ab = u_a u_b
    (sqrt 2 if a < b else 1) / sqrt(hd)``, a value channel last."""
    d, lane, scale = _canonical_index(state.shape[-1])
    xp = np if isinstance(state, np.ndarray) else jnp
    # two index arrays with a slice between them: their axis leads
    return xp.moveaxis(state[..., d, :, lane], 0, -2) * scale[:, None]


def canonical_norm(norm):
    """``[..., rows >= hd / 2 + 1, hd] -> [..., hd (hd + 1) / 2]``."""
    d, lane, scale = _canonical_index(norm.shape[-1])
    return norm[..., d, lane] * scale


def retention_step(state, norm, layer, slots, fresh, q, k, v, g, eps):
    """One token a row. q [N, nh, hd]; k, v [N, nkv, hd]; g [N, nkv] the
    log-decay; float32. The rows' states from ``state[layer, slots]`` /
    ``norm[layer, slots]`` (zeros where ``fresh``) and back. Returns
    (o [N, nh, hd] float32, state, norm)."""
    N, nh, hd = q.shape
    nkv = k.shape[1]
    s = jnp.where(fresh[:, None, None, None, None], 0.0,
                  state[layer, slots].astype(_F32))     # [N, nkv, D, i, a]
    decay, pk = jnp.exp(g), phi(k)                      # [N, nkv, D, a]
    D = pk.shape[-2]
    z = jnp.where(fresh[:, None, None, None], 0.0,
                  norm[layer, slots, :, :D].astype(_F32))
    s = s * decay[..., None, None, None] \
        + v[:, :, None, :, None] * pk[:, :, :, None, :]
    z = z * decay[..., None, None] + pk
    pq = phi(q).reshape(N, nkv, nh // nkv, *pk.shape[2:])
    num = jnp.einsum("njgda,njdia->njgi", pq, s, precision=_HI)
    den = jnp.einsum("njgda,njda->njg", pq, z, precision=_HI)
    o = num / (den[..., None] + eps)
    return (o.reshape(N, nh, hd),
            state.at[layer, slots].set(s.astype(state.dtype)),
            norm.at[layer, slots].set(_padded(z, norm)))


def _padded(z, norm):
    """A normaliser [.., D, hd] as the leaf keeps it: in its type, zeros
    behind phi's rows."""
    pad = norm.shape[-2] - z.shape[-2]
    return jnp.pad(z.astype(norm.dtype),
                   [(0, 0)] * (z.ndim - 2) + [(0, pad), (0, 0)])


def state_kernel_serves(state) -> bool:
    """Whether :func:`retention_state_update` takes this state leaf
    ``[layers, slots, kv heads, hd / 2 + 1, hd, hd]``: on a TPU, a head
    128 wide (phi's row a whole lane rotation, a distance's state one
    [128, 128] tile block)."""
    return jax.default_backend() == "tpu" and state.shape[-1] == 128


chunk_kernel_serves = state_kernel_serves


def _dot(x, y, contract):
    """A float32 product: ``contract`` names the contracted axis of each
    operand."""
    return jax.lax.dot_general(
        x, y, (((contract[0],), (contract[1],)), ((), ())),
        precision=_HI, preferred_element_type=_F32)


def _coef_of(d, D, hd):
    """phi's factor at the (traced) distance ``d``."""
    return jnp.where((d == 0) | (d == D - 1), 1.0, np.sqrt(2.0)
                     ).astype(_F32) * np.float32(hd ** -0.5)


def _state_kernel(layer_ref, slots_ref, fresh_ref, s_ref, z_ref, q_ref,
                  k_ref, v_ref, dec_ref, so_ref, zo_ref, o_ref, pk, pq, acc,
                  *, eps, stripe):
    """One row's key/value head through one token: the state ``[D, i,
    a]``, the group's queries ``[G, hd]``, the head's key, value and
    decay (its scalar on every lane) rows ``[1, hd]``. phi's rows are
    made once into ``pk`` / ``pq`` (a distance a sublane); the state
    then passes ``stripe`` value channels at a time, a distance after
    another, with the group's ``G`` partial outputs in registers."""
    del layer_ref, slots_ref            # the index maps read them
    keep = fresh_ref[pl.program_id(0)] == 0
    D, hd, _ = s_ref.shape
    G = q_ref.shape[0]

    # phi's rows, eight distances at a time: sublane r of the block at
    # distance d0 is rotated by d0 + r
    at = jax.lax.broadcasted_iota(jnp.int32, (8, 1), 0)

    def rows_of(u, d0):                 # [1, hd] -> phi [d0 .. d0 + 8, hd]
        ub = jnp.broadcast_to(u, (8, hd))
        cd = jnp.where(d0 + at >= D, 0.0, _coef_of(d0 + at, D, hd))
        return ub * pltpu.roll(ub, d0, 1, stride=1, stride_axis=0) * cd

    for d0 in range(0, pk.shape[0], 8):
        pk[d0:d0 + 8, :] = rows_of(k_ref[...], d0)
        for h in range(G):
            pq[h, d0:d0 + 8, :] = rows_of(q_ref[h:h + 1, :], d0)
    dec = dec_ref[...]                                       # [1, hd]
    # every row of the leaf's: phi is zeros behind its D
    z = jnp.where(keep, z_ref[...].astype(_F32), 0.0) * dec + pk[...]
    zo_ref[...] = z.astype(zo_ref.dtype)
    # v[i] on every lane of sublane i: diag(v) x ones, exact
    eye = jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 0) \
        == jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 1)
    ones = jnp.ones((hd, hd), _F32)
    vb = _dot(jnp.where(eye, v_ref[...], 0.0), ones, (1, 0))
    for st in range(hd // stripe):
        rows = pl.ds(st * stripe, stripe)
        vbs = vb[st * stripe:(st + 1) * stripe]

        def distance(d, accs):
            s = jnp.where(keep, s_ref[d, rows, :].astype(_F32), 0.0) * dec \
                + vbs * pk[pl.ds(d, 1), :]
            so_ref[d, rows, :] = s.astype(so_ref.dtype)
            return tuple(a + s * pq[h, pl.ds(d, 1), :]
                         for h, a in enumerate(accs))

        accs = jax.lax.fori_loop(
            0, D, distance,
            tuple(jnp.zeros((stripe, hd), _F32) for _ in range(G)))
        for h in range(G):
            acc[h, rows, :] = accs[h]
    for h in range(G):
        den = jnp.sum(z * pq[h], keepdims=True)              # [1, 1]
        num = _dot(ones[:8], acc[h], (1, 1))[:1]             # [1, i]
        o_ref[h:h + 1, :] = num / (den + eps)


def retention_state_update(state, norm, layer, slots, fresh, q, k, v, g,
                           eps, interpret=False):
    """:func:`retention_step` on the rows' slots of the leaves where
    they lie: both stay whole in HBM (coloured so, ``slot_leaf``:
    left to the compiler, the normaliser's leaf, 40 MB at the cell's
    shape, was carried into the chip's fast memory in four slices ahead
    of every launch and copied back behind it), and a grid step copies
    in ONE
    key/value head of row n's slot ``slots[n]`` at ``layer`` (prefetched
    scalars), puts it through the token for the head's whole group of
    queries and copies it back to where it came from (aliased): a state
    is read once and written once. A trace shows it as
    ``retention_state_update``."""
    N, nh, hd = q.shape
    nkv = k.shape[1]
    G, D = nh // nkv, phi_rows(hd)
    Dp = norm.shape[-2]
    row = pl.BlockSpec((None, None, 1, hd), lambda n, j, *_: (n, j, 0, 0))
    group = pl.BlockSpec((None, None, G, hd), lambda n, j, *_: (n, j, 0, 0))
    s_spec = pl.BlockSpec(
        (None, None, None, D, hd, hd),
        lambda n, j, layer, slots, fresh: (layer[0], slots[n], j, 0, 0, 0))
    z_spec = pl.BlockSpec(
        (None, None, None, Dp, hd),
        lambda n, j, layer, slots, fresh: (layer[0], slots[n], j, 0, 0))
    lanes = (N, nkv, 1, hd)
    state, norm = in_hbm(state, interpret), in_hbm(norm, interpret)
    state, norm, o = pl.pallas_call(
        functools.partial(_state_kernel, eps=eps, stripe=min(STRIPE, hd)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(N, nkv),
            in_specs=[s_spec, z_spec, group, row, row, row],
            out_specs=[s_spec, z_spec, group],
            scratch_shapes=[pltpu.VMEM((Dp, hd), _F32),
                            pltpu.VMEM((G, Dp, hd), _F32),
                            pltpu.VMEM((G, hd, hd), _F32)]),
        out_shape=[hbm_out(state, interpret), hbm_out(norm, interpret),
                   jax.ShapeDtypeStruct((N, nkv, G, hd), _F32)],
        input_output_aliases={3: 0, 4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=48 * 2 ** 20),
        name="retention_state_update", interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), slots.astype(jnp.int32),
      fresh.astype(jnp.int32), state, norm, q.reshape(N, nkv, G, hd),
      k.reshape(lanes), v.reshape(lanes),
      jnp.broadcast_to(jnp.exp(g)[..., None, None], lanes))
    return o.reshape(N, nh, hd), state, norm


def _chunk(q, k, v, g, s, z, eps):
    """One chunk of every row. q [B, C, nkv, G, hd]; k, v [B, C, nkv,
    hd]; g [B, C, nkv]; s [B, nkv, D, hd, hd]; z [B, nkv, D, hd]. A
    masked token has k = 0 and g = 0: it leaves the state as it was.
    Returns (o [B, C, nkv, G, hd], s, z)."""
    C, hd = q.shape[1], q.shape[-1]
    Gc = jnp.cumsum(g, axis=1)                               # inclusive
    idx = jnp.arange(C)
    seen = (idx[:, None] >= idx[None, :])[None, :, :, None]
    pair = jnp.where(seen, jnp.exp(jnp.minimum(
        Gc[:, :, None] - Gc[:, None], 0.0)), 0.0)            # [B, t, r, j]
    scores = jnp.einsum("btjgd,brjd->btrjg", q, k, precision=_HI)
    A = jnp.square(scores) / hd * pair[..., None]
    grown = jnp.exp(Gc)[..., None]                           # [B, t, j, 1]
    pq, pk = phi(q), phi(k)
    num = jnp.einsum("btrjg,brji->btjgi", A, v, precision=_HI) \
        + grown[..., None] * jnp.einsum("btjgda,bjdia->btjgi", pq, s,
                                        precision=_HI)
    den = jnp.sum(A, axis=2) + grown * jnp.einsum(
        "btjgda,bjda->btjg", pq, z, precision=_HI)
    last = Gc[:, -1]                                         # [B, j]
    w = jnp.exp(last[:, None] - Gc)                          # <= 1
    s = jnp.exp(last)[..., None, None, None] * s + jnp.einsum(
        "brj,brji,brjda->bjdia", w, v, pk, precision=_HI)
    z = jnp.exp(last)[..., None, None] * z + jnp.einsum(
        "brj,brjda->bjda", w, pk, precision=_HI)
    return num / (den[..., None] + eps), s, z


def retention_chunked(state, norm, layer, slots, fresh, starts, counts, q,
                      k, v, g, eps, chunk=CHUNK):
    """The recurrence over the rows of a flat token buffer, from and to
    the rows' slots. q [T, nh, hd]; k, v [T, nkv, hd]; g [T, nkv];
    float32. Row r owns the tokens ``starts[r] .. starts[r] +
    counts[r]`` (``counts`` 0: none) and the slot ``slots[r]``: its
    state before its first token here (zeros where ``fresh[r]``), and
    after its last. A step of the loop takes one chunk of ONE row from
    its slot and back (phi of a chunk's queries is 0.17 GB a row at the
    published widths). Returns (o [T, nh, hd] float32, zeros at tokens
    of no row; state; norm)."""
    q, k, v, g = (jnp.asarray(a, _F32) for a in (q, k, v, g))
    T, nh, hd = q.shape
    nkv = k.shape[1]
    R = starts.shape[0]
    steps = jnp.max((counts + chunk - 1) // chunk) * R
    within = jnp.arange(chunk)

    def body(carry):
        step, state, norm, out = carry
        j, r = step // R, step % R
        first, count, slot, new_row = (
            jax.lax.dynamic_slice_in_dim(a, r, 1)
            for a in (starts, counts, slots, fresh))
        off = j * chunk + within
        live = off[None, :] < count[:, None]                 # [1, C]
        idx = jnp.where(live, first[:, None] + off[None, :], 0)
        m = live[..., None, None]
        start_zero = (new_row & (j == 0))
        s = jnp.where(start_zero[:, None, None, None, None], 0.0,
                      state[layer, slot].astype(_F32))
        z = jnp.where(start_zero[:, None, None, None], 0.0,
                      norm[layer, slot, :, :phi_rows(hd)].astype(_F32))
        o, s, z = _chunk(
            q[idx].reshape(1, chunk, nkv, nh // nkv, hd),
            jnp.where(m, k[idx], 0.0), v[idx],
            jnp.where(live[..., None], g[idx], 0.0), s, z, eps)
        state = state.at[layer, slot].set(s.astype(state.dtype))
        norm = norm.at[layer, slot].set(_padded(z, norm))
        out = out.at[jnp.where(live, idx, T)].set(
            o.reshape(1, chunk, nh, hd), mode="drop")
        return step + 1, state, norm, out

    _, state, norm, out = jax.lax.while_loop(
        lambda c: c[0] < steps, body,
        (jnp.int32(0), state, norm, jnp.zeros((T, nh, hd), _F32)))
    return out, state, norm


# the loop over phi's distances in the chunk kernel, under a name of its
# own so that ``scripts/bench_kernels.py`` can time the kernel without it
_distances_loop = jax.lax.fori_loop


def _chunk_kernel(layer_ref, slots_ref, fresh_ref, starts_ref, counts_ref,
                  s_ref, z_ref, q_hbm, k_hbm, v_hbm, g_hbm, so_ref, zo_ref,
                  o_hbm, qb, kb, vb, gb, ob, acc, dacc, zs, sem_in, sem_out,
                  *, eps):
    """One row's key/value head through the row's tokens, with the
    head's whole group of queries. The flat buffer is cut into WINDOWS
    of ``chunk`` tokens where it lies, and the row takes every window it
    has a token in, the tokens of other rows masked (k = 0, g = 0: they
    leave the state as it was): every copy is aligned
    (``linear_attention._chunk_kernel``'s manner). The state waits in
    the output's block ``so_ref`` from window to window, the normaliser
    in ``zs`` (float32: a distance's row of a leaf kept in bfloat16 is
    half a packed sublane, which Mosaic does not address).
    ``g_hbm`` [nkv, T, hd] holds a token's log-decay on every lane."""
    del layer_ref, slots_ref            # the index maps read them
    D, hd, _ = so_ref.shape
    G, chunk = acc.shape[0], qb.shape[1]
    r, j = pl.program_id(0), pl.program_id(1)
    start, count = starts_ref[r], counts_ref[r]
    w0 = start // chunk
    w1 = jnp.where(count > 0, (start + count - 1) // chunk + 1, w0)
    keep = fresh_ref[r] == 0
    so_ref[...] = jnp.where(keep, s_ref[...], 0).astype(so_ref.dtype)
    zs[...] = jnp.where(keep, z_ref[...].astype(_F32), 0.0)

    def tokens_of(w):
        return pl.ds(pl.multiple_of(w * chunk, chunk), chunk)

    def lanes_of(n):
        return pl.ds(pl.multiple_of(j * n * hd, n * hd), n * hd)

    def copies_in(w):
        rows, slot = tokens_of(w), (w - w0) % 2
        return [pltpu.make_async_copy(src.at[rows, lanes_of(n)],
                                      dst.at[slot], sem_in.at[i, slot])
                for i, (src, dst, n) in enumerate((
                    (q_hbm, qb, G), (k_hbm, kb, 1), (v_hbm, vb, 1)))] + [
            pltpu.make_async_copy(g_hbm.at[j, rows], gb.at[slot],
                                  sem_in.at[3, slot])]

    def outputs_of(w):
        return o_hbm.at[tokens_of(w), lanes_of(G)]

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

    t_row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    t_col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = (t_row >= t_col).astype(_F32)           # [t, r]: r <= t
    eye = (t_row == t_col).astype(_F32)

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

        k = jnp.where(live, kb[slot], 0.0)
        v = vb[slot]
        # the inclusive cumulative log-decay, a token a sublane (its
        # value on every lane) and, transposed, a token a lane
        Gc = _dot(lower, jnp.where(live, gb[slot], 0.0), (1, 0))
        Gr = _dot(eye, Gc, (1, 1))                           # [t, r]: G_r
        pair = lower * jnp.exp(jnp.minimum(Gc - Gr, 0.0))
        grown = jnp.exp(Gc)                                  # exp(G_t)
        last = Gc[chunk - 1:chunk, :]                        # [1, hd]
        kept = jnp.exp(last)
        back = jnp.exp(last - Gc)                            # <= 1
        vw = v * back
        for h in range(G):
            acc[h] = jnp.zeros((chunk, hd), _F32)
            dacc[h] = jnp.zeros((chunk, hd), _F32)

        def distance(d, c):
            cd = _coef_of(d, D, hd)
            s = so_ref[d].astype(_F32)                       # [i, a]
            zd = zs[pl.ds(d, 1), :]                          # [1, a]
            for h in range(G):
                qh = qb[slot, :, h * hd:(h + 1) * hd]
                qd = qh * pltpu.roll(qh, d, 1) * cd
                acc[h] = acc[h] + _dot(qd, s, (1, 1))        # [t, i]
                dacc[h] = dacc[h] + qd * zd
            kd = k * pltpu.roll(k, d, 1) * cd
            so_ref[d] = (s * kept + _dot(vw, kd, (0, 0))).astype(
                so_ref.dtype)
            zs[pl.ds(d, 1), :] = zd * kept + jnp.sum(
                kd * back, axis=0, keepdims=True)
            return c

        _distances_loop(0, D, distance, 0)
        for h in range(G):
            qh = qb[slot, :, h * hd:(h + 1) * hd]
            A = jnp.square(_dot(qh, k, (1, 1))) * np.float32(1.0 / hd) * pair
            num = _dot(A, v, (1, 0)) + grown * acc[h]
            den = jnp.sum(A, axis=1, keepdims=True) + grown * jnp.sum(
                dacc[h], axis=1, keepdims=True)
            at = slice(h * hd, (h + 1) * hd)
            ob[slot, :, at] = jnp.where(live, num / (den + eps),
                                        ob[slot, :, at])
        copy_out(w, slot).start()
        return carry

    jax.lax.fori_loop(w0, w1, window, 0)
    for back in (2, 1):
        @pl.when(w1 - back >= w0)
        def _():
            copy_out(w1 - back, (w1 - back - w0) % 2).wait()
    zo_ref[...] = zs[...].astype(zo_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "chunk", "interpret"))
def retention_chunk_fwd(state, norm, layer, slots, fresh, starts, counts, q,
                        k, v, g, eps, chunk=CHUNK, interpret=False):
    """:func:`retention_chunked` as ONE kernel a layer and launch, the
    rows' states in place (its arguments and results). The grid is (row,
    key/value head); a step copies the head's state in from
    ``state[layer, slots[r]]`` once (zeros where ``fresh[r]``), walks
    the row's windows of ``chunk`` tokens where they lie in the flat
    buffer, and copies it back once (aliased). A trace shows it as
    ``retention_chunk_fwd``. A jit of its own: the layers of a program,
    and the program's signatures, trace the kernel once."""
    T, nh, hd = q.shape
    nkv = k.shape[1]
    G, D = nh // nkv, phi_rows(hd)
    assert chunk == hd, (chunk, hd)     # the decay's transpose is [hd, hd]
    R = starts.shape[0]
    pad = -T % chunk
    q, k, v = (jnp.pad(a.reshape(T, -1), ((0, pad), (0, 0)))
               for a in (q, k, v))
    g = jnp.broadcast_to(jnp.pad(g, ((0, pad), (0, 0))).T[..., None],
                         (nkv, T + pad, hd))
    s_spec = pl.BlockSpec(
        (None, None, None, D, hd, hd),
        lambda r, j, layer, slots, *_: (layer[0], slots[r], j, 0, 0, 0))
    z_spec = pl.BlockSpec(
        (None, None, None, norm.shape[-2], hd),
        lambda r, j, layer, slots, *_: (layer[0], slots[r], j, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    state, norm, o = pl.pallas_call(
        functools.partial(_chunk_kernel, eps=eps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(R, nkv),
            in_specs=[s_spec, z_spec] + [hbm] * 4,
            out_specs=[s_spec, z_spec, hbm],
            scratch_shapes=[
                pltpu.VMEM((2, chunk, G * hd), _F32),
                pltpu.VMEM((2, chunk, hd), _F32),
                pltpu.VMEM((2, chunk, hd), _F32),
                pltpu.VMEM((2, chunk, hd), _F32),
                pltpu.VMEM((2, chunk, G * hd), _F32),
                pltpu.VMEM((G, chunk, hd), _F32),
                pltpu.VMEM((G, chunk, hd), _F32),
                pltpu.VMEM(norm.shape[-2:], _F32),
                pltpu.SemaphoreType.DMA((4, 2)),
                pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(norm.shape, norm.dtype),
                   jax.ShapeDtypeStruct((T + pad, nh * hd), _F32)],
        input_output_aliases={5: 0, 6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 2 ** 20),
        name="retention_chunk_fwd",
        interpret=pltpu.InterpretParams() if interpret else False,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), slots.astype(jnp.int32),
      fresh.astype(jnp.int32), starts.astype(jnp.int32),
      counts.astype(jnp.int32), state, norm, q.astype(_F32),
      k.astype(_F32), v.astype(_F32), g.astype(_F32))
    return o[:T].reshape(T, nh, hd), state, norm
