"""Linear attention with a recurrent state (Kimi Delta Attention, KDA:
Kimi Linear, arXiv:2510.26692), in the two forms a server needs.

A head keeps a float32 state ``S`` [d_k, d_v] in place of cached
positions. A token with query ``q``, key ``k`` (both l2-normalised),
value ``v``, log-decay ``g`` <= 0 a KEY CHANNEL and update strength
``beta`` in (0, 1) does

    S' = diag(exp(g)) S;   S = S' + beta k (v - S'^T k)^T;   o = S^T q

* :func:`kda_step`: that update, one token a row (decode). Elementwise
  float32: a row's state read once and written once.
* :func:`kda_chunked`: the same recurrence over a row's prompt tokens,
  ``chunk`` (64) tokens at a time in matmuls, the state carried from
  chunk to chunk. Rows of any lengths lie in the ragged step's flat
  token buffer; step j of a ``while_loop`` takes chunk j of EVERY row
  (gathered by ``starts`` / ``counts``), so the loop runs as many steps
  as the longest row has chunks.

The chunked form (the WY representation of the delta rule): with ``G``
the cumulative log-decay inside a chunk and ``A[i, j] = sum_c k_i[c]
k_j[c] exp(G_i[c] - G_j[c])`` for j < i, the rank-one updates of a chunk
are ``u = (I + diag(beta) A)^-1 beta (V - (K exp(G)) S_0)``, a unit
lower-triangular solve; then ``o = (Q exp(G)) S_0 + tril(QK) u`` and
``S_C = diag(exp(G_C)) S_0 + (K exp(G_C - G))^T u``. ``exp(-G_j)`` alone
overflows float32 (64 tokens at the decay's floor of -5 reach e^320), so
a pair (i, j) is factored round the cumulative decay at the start of i's
SUB-block of ``sub`` (16) tokens: ``exp(G_i - R) <= 1`` and ``exp(R -
G_j) <= e^(16 x 5)``, which float32 holds.

:func:`causal_conv_rows` / :func:`causal_conv_step` are the short
depthwise convolution in front of q, k and v, over a row's own tokens,
its last ``taps - 1`` inputs carried as state beside ``S``.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64
SUB = 16
ROWS_A_STEP = 32
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
    lies: ``leaf`` ``[layers, slots, nh, dk, dv]`` stays whole in HBM,
    and a grid step copies in ``HEADS_A_STEP`` heads of row n's slot
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
    so, o = pl.pallas_call(
        functools.partial(_state_kernel, heads=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(N, blocks),
            in_specs=[state, col, col, col, col, row],
            out_specs=[state, row]),
        out_shape=[jax.ShapeDtypeStruct(leaf.shape, leaf.dtype),
                   jax.ShapeDtypeStruct((N, nh, dv), jnp.float32)],
        input_output_aliases={3: 0},
        name="kda_state_update", interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), slots.astype(jnp.int32),
      fresh.astype(jnp.int32), leaf, columns(jnp.exp(g)), columns(k),
      columns(beta[..., None] * k), columns(q), beta[..., None] * v)
    return o, so


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
