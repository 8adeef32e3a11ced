"""Mixture-of-Experts gating + dispatch.

TPU-native analogue of the reference's expert parallelism
(deepspeed/moe/sharded_moe.py: top1gating :184, top2gating :282, MOELayer
:425, _AllToAll :95; deepspeed/moe/layer.py:16 MoE). The reference dispatches
tokens with an explicit all-to-all over the expert process group; here the
dispatch is the GShard-style einsum against a static-capacity one-hot tensor,
with expert-stacked parameters sharded over the "expert" mesh axis — XLA
lowers the resharding of the dispatched [E, C, H] activations onto the same
ICI all-to-all the reference issues by hand.

Static shapes (capacity = ceil(tokens/E * capacity_factor)) are exactly the
reference's drop_tokens=True mode — which is also the only mode that maps
well onto XLA; dropless variants need ragged kernels (future ragged_dot path).
"""

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def _capacity(num_tokens: int, num_experts: int, capacity_factor: float,
              min_capacity: int) -> int:
    cap = int(math.ceil(num_tokens / num_experts * capacity_factor))
    return max(cap, min_capacity)


def _one_hot(x, n):
    return jax.nn.one_hot(x, n, dtype=jnp.float32)


def top1gating(logits, capacity_factor: float = 1.0, min_capacity: int = 4,
               noisy_gate_policy: Optional[str] = None, rng=None,
               drop_tokens: bool = True):
    if not drop_tokens:
        raise NotImplementedError(
            "use moe_layer_dropless (jax.lax.ragged_dot grouped GEMM) for "
            "drop_tokens=False; the einsum dispatch path is capacity-based")
    """Switch-style top-1 gating (reference sharded_moe.py:184).

    logits: [T, E]. Returns (aux_loss, combine [T,E,C], dispatch mask [T,E,C]).
    """
    T, E = logits.shape
    C = _capacity(T, E, capacity_factor, min_capacity)
    if noisy_gate_policy == "RSample" and rng is not None:
        logits_w_noise = logits + jax.random.gumbel(rng, logits.shape)
    else:
        logits_w_noise = logits
    gates = jax.nn.softmax(logits, axis=-1)                     # [T, E]
    idx = jnp.argmax(logits_w_noise, axis=-1)                   # [T]
    mask1 = _one_hot(idx, E)                                    # [T, E]

    # load-balancing aux loss (Switch eq. 4; reference l_aux at :253)
    me = jnp.mean(gates, axis=0)                                # [E]
    ce = jnp.mean(mask1, axis=0)                                # [E]
    aux_loss = jnp.sum(me * ce) * E

    # position of each token within its expert's capacity
    pos = jnp.cumsum(mask1, axis=0) - mask1                     # [T, E]
    pos_in_expert = jnp.sum(pos * mask1, axis=-1)               # [T]
    keep = (pos_in_expert < C).astype(jnp.float32)              # drop overflow
    mask1 = mask1 * keep[:, None]

    gate1 = jnp.sum(gates * mask1, axis=-1)                     # [T]
    pos_oh = _one_hot(pos_in_expert.astype(jnp.int32), C)       # [T, C]
    dispatch = mask1[:, :, None] * pos_oh[:, None, :]           # [T, E, C]
    combine = dispatch * gate1[:, None, None]
    return aux_loss, combine, dispatch


def top2gating(logits, capacity_factor: float = 1.0, min_capacity: int = 4,
               rng=None, drop_tokens: bool = True):
    """GShard top-2 gating (reference sharded_moe.py:282); deterministic
    second expert (argmax after masking expert 1)."""
    if not drop_tokens:
        raise NotImplementedError(
            "dropless MoE is not supported; see top1gating")
    T, E = logits.shape
    C = _capacity(T, E, capacity_factor * 2.0, min_capacity)
    gates = jax.nn.softmax(logits, axis=-1)

    idx1 = jnp.argmax(gates, axis=-1)
    mask1 = _one_hot(idx1, E)
    gates_wo1 = gates * (1.0 - mask1)
    idx2 = jnp.argmax(gates_wo1, axis=-1)
    mask2 = _one_hot(idx2, E)

    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1, axis=0)
    aux_loss = jnp.sum(me * ce) * E

    pos1 = jnp.cumsum(mask1, axis=0) - mask1
    pos_in1 = jnp.sum(pos1 * mask1, axis=-1)
    # expert-2 positions come after all expert-1 claims (reference locations2
    # += sum of mask1)
    pos2 = jnp.cumsum(mask2, axis=0) - mask2 + jnp.sum(mask1, axis=0, keepdims=True)
    pos_in2 = jnp.sum(pos2 * mask2, axis=-1)

    mask1 = mask1 * (pos_in1 < C).astype(jnp.float32)[:, None]
    mask2 = mask2 * (pos_in2 < C).astype(jnp.float32)[:, None]

    g1 = jnp.sum(gates * mask1, axis=-1)
    g2 = jnp.sum(gates * mask2, axis=-1)
    denom = jnp.maximum(g1 + g2, 1e-9)
    g1, g2 = g1 / denom, g2 / denom

    disp1 = mask1[:, :, None] * _one_hot(pos_in1.astype(jnp.int32), C)[:, None, :]
    disp2 = mask2[:, :, None] * _one_hot(pos_in2.astype(jnp.int32), C)[:, None, :]
    dispatch = disp1 + disp2
    combine = disp1 * g1[:, None, None] + disp2 * g2[:, None, None]
    return aux_loss, combine, dispatch


def _gate_and_dispatch(xt, gate_w, top_k, capacity_factor, min_capacity,
                       noisy_gate_policy, rng):
    """Shared gating prologue of every capacity-routed MoE variant: fp32
    router logits + top-1/top-2 gating. Returns (aux, combine, dispatch)."""
    logits = (xt.astype(jnp.float32) @ gate_w.astype(jnp.float32))
    if top_k == 1:
        return top1gating(logits, capacity_factor, min_capacity,
                          noisy_gate_policy, rng)
    return top2gating(logits, capacity_factor, min_capacity, rng)


def moe_layer(x, gate_w, expert_params, expert_fn, topo=None,
              top_k: int = 1, capacity_factor: float = 1.0,
              min_capacity: int = 4, rng=None,
              noisy_gate_policy: Optional[str] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Apply an expert-parallel MoE layer.

    x: [B, S, H]; gate_w: [H, E]; expert_params: pytree with leading expert
    dim [E, ...] (sharded over the "expert" axis by the caller's specs);
    expert_fn(params_e, x_e) applies one expert to [C', H].

    Returns (output [B,S,H], aux_loss scalar).
    """
    B, S, H = x.shape
    xt = x.reshape(B * S, H)
    aux, combine, dispatch = _gate_and_dispatch(
        xt, gate_w, top_k, capacity_factor, min_capacity, noisy_gate_policy,
        rng)

    # dispatch: [T,E,C] x [T,H] -> [E,C,H]   (the all-to-all happens here when
    # E is sharded over the expert axis and T over the data axes)
    xe = jnp.einsum("tec,th->ech", dispatch.astype(x.dtype), xt)
    if topo is not None and topo.axis_size("expert") > 1:
        from jax.sharding import PartitionSpec as P
        from jax.sharding import NamedSharding

        xe = jax.lax.with_sharding_constraint(
            xe, NamedSharding(topo.mesh, P("expert", None, None)))

    ye = jax.vmap(expert_fn)(expert_params, xe)                 # [E, C, H]
    out = jnp.einsum("tec,ech->th", combine.astype(x.dtype), ye)
    return out.reshape(B, S, H), aux.astype(jnp.float32)


def moe_layer_manual(x, gate_w, expert_params_local, expert_fn,
                     ep_axis: str = "expert",
                     top_k: int = 1, capacity_factor: float = 1.0,
                     min_capacity: int = 4, rng=None,
                     noisy_gate_policy: Optional[str] = None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE with an EXPLICIT all-to-all dispatch, for use
    inside a manual shard_map program — the compiled 1F1B pipeline, where
    GSPMD cannot insert the expert collective (the reference's _AllToAll
    autograd op, sharded_moe.py:95, done by hand the same way).

    x: the device-LOCAL [B, S, H] token block (the expert axis is a batch
    axis, so every expert peer holds different tokens);
    gate_w: [H, E_global] (replicated over the expert axis);
    expert_params_local: pytree with leading LOCAL expert dim [E/ep, ...].

    Dispatch: capacity-pad locally to [E, C, H], all_to_all the per-owner
    blocks over `ep_axis`, run the local experts on [E/ep, ep*C, H], and
    all_to_all back before the combine. All shapes are static (capacity
    routing), which is what makes this legal inside the compiled pipeline.
    """
    B, S, H = x.shape
    from ..comm.quantized import _axis_size
    ep = _axis_size(ep_axis)
    xt = x.reshape(B * S, H)
    E = gate_w.shape[-1]
    assert E % ep == 0, f"num_experts {E} not divisible by ep {ep}"
    aux, combine, dispatch = _gate_and_dispatch(
        xt, gate_w, top_k, capacity_factor, min_capacity, noisy_gate_policy,
        rng)

    xe = jnp.einsum("tec,th->ech", dispatch.astype(x.dtype), xt)  # [E, C, H]
    C = xe.shape[1]
    e_loc = E // ep
    # block o = my tokens for peer o's experts -> peer o; received block p =
    # peer p's tokens for MY experts
    xr = jax.lax.all_to_all(xe, ep_axis, split_axis=0, concat_axis=0,
                            tiled=True)                    # [ep*e_loc, C, H]
    xr = xr.reshape(ep, e_loc, C, H).transpose(1, 0, 2, 3) \
           .reshape(e_loc, ep * C, H)
    ye = jax.vmap(expert_fn)(expert_params_local, xr)      # [e_loc, ep*C, H]
    ye = ye.reshape(e_loc, ep, C, H).transpose(1, 0, 2, 3).reshape(E, C, H)
    ye = jax.lax.all_to_all(ye, ep_axis, split_axis=0, concat_axis=0,
                            tiled=True)                    # back to senders
    out = jnp.einsum("tec,ech->th", combine.astype(x.dtype), ye)
    return out.reshape(B, S, H), aux.astype(jnp.float32)


def ragged_swiglu_experts(expert_params, xs, group_sizes, gate=jax.nn.silu):
    """SwiGLU expert stack as grouped GEMMs over token groups.

    The TPU-native equivalent of the reference's CUTLASS MoE grouped GEMM
    (inference/v2/kernels/cutlass_ops/moe_gemm): `jax.lax.ragged_dot` tiles
    the per-expert segments onto the MXU without materializing the [E, C, H]
    capacity tensor. xs: [T, H] tokens SORTED by expert; group_sizes: [E].
    ``gate``: the gate's function, ``down(gate(gate_w x) * (up x))``: SiLU,
    or ReLU for the "reglu" form (:func:`expert_forms`); the three-matrix
    expert is one body whatever gates it.
    """
    wg, wu, wd = expert_params                                 # [E, H, F] ...
    g = jax.lax.ragged_dot(xs, wg, group_sizes)
    u = jax.lax.ragged_dot(xs, wu, group_sizes)
    return jax.lax.ragged_dot(gate(g) * u, wd, group_sizes)


def ragged_relu2_experts(expert_params, xs, group_sizes):
    """:func:`ragged_swiglu_experts` for the two-matrix expert with a
    squared ReLU and no gate, ``down(relu(up x)^2)`` (the nemotron_h
    block). ``expert_params`` are (up [E, F, H], down [E, F, H]): BOTH
    keep the model's width H as their last axis, ``up`` as a linear
    layer's checkpoint stores it (out x in). An expert's own width F
    need be no whole number of 128-lane blocks (1,856 is 14.5), and a
    TPU lays an array whose last axis is not out with another axis last
    where that saves padding: a [E, H, F] leaf would reach the grouped
    matmul's kernel through a copy of every expert, each launch."""
    wu, wd = expert_params
    u = jax.nn.relu(jax.lax.ragged_dot(xs, jnp.swapaxes(wu, -1, -2),
                                       group_sizes))
    return jax.lax.ragged_dot(u * u, wd, group_sizes)


# the most of an expert's [K, N] weight the grouped-matmul kernel takes
# as ONE tile (twice that is resident: the next arrives while this one
# is used): the whole expert where it fits, else its K rows by the
# largest whole fraction of its N columns that does (:func:`_gmm_columns`)
_GMM_WHOLE_EXPERT_BYTES = 4 * 2 ** 20
_GMM_ROWS = 128


def _gmm_columns(w):
    """Columns of an expert's [.., K, N] weight a tile of the grouped
    matmul holds beside all K rows: N where the expert fits
    ``_GMM_WHOLE_EXPERT_BYTES`` (every accepted width does), else N
    over the least divisor that makes whole 128-lane blocks fit (4,096 x
    768 in bf16 is 6.3 MB: two tiles of 384 columns; the kernel's grid
    has the column tiles outermost, so an expert's tile still waits
    over its consecutive row tiles and its weights stream once); None
    where no such split exists."""
    K, N = w.shape[-2:]
    for d in range(1, N // 128 + 1):
        if N % (128 * d) == 0 \
                and K * (N // d) * w.dtype.itemsize <= _GMM_WHOLE_EXPERT_BYTES:
            return N // d
    return None


def _whole_row_tiles(xs):
    """``xs`` padded with rows of zeros to whole row tiles of the grouped
    matmul (rows past the last group: not computed), and its own rows."""
    m = xs.shape[0]
    pad = (-m) % _GMM_ROWS
    return (jnp.pad(xs, ((0, pad), (0, 0))) if pad else xs), m


def gmm_swiglu_experts(expert_params, xs, group_sizes, gate=jax.nn.silu):
    """``ragged_swiglu_experts`` (its ``gate`` too) through the Pallas
    grouped matmul JAX ships
    (``jax.experimental.pallas.ops.tpu.megablox``), a row tile of 128
    against an expert's WHOLE [K, N] weight: a launch then streams
    each touched expert's weights once and nothing else, where XLA's own
    ``ragged_dot`` kernel took 2.5 times as long at 256 experts of
    2048 x 768 and two rows an expert (9.03 ms against 3.66 a layer; at
    65,536 rows 17.0 against 9.15; PERF.md section 6, PR 36). Serving
    only (no caller differentiates it), on a TPU, where
    :func:`gmm_serves` says the weights fit. A trace shows the kernels as
    ``gmm.N`` (the name of JAX's jitted function around them)."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    wg, wu, wd = expert_params
    xs, m = _whole_row_tiles(xs)

    def mm(x, w):
        return gmm(x, w, group_sizes, preferred_element_type=x.dtype,
                   tiling=(_GMM_ROWS, w.shape[1], _gmm_columns(w)))

    return mm(gate(mm(xs, wg)) * mm(xs, wu), wd)[:m]


def gmm_relu2_experts(expert_params, xs, group_sizes):
    """:func:`ragged_relu2_experts` through the same Pallas grouped
    matmul as :func:`gmm_swiglu_experts` (a trace shows its two kernels
    as ``gmm.N`` too), both matrices [E, F, H] tiled alike: all F rows
    by :func:`_gmm_columns` of the H columns (1,856 x 2,688 in bf16 is
    10 MB: three tiles of 896 columns). ``down`` is the plain product
    over the column tiles. ``up`` is the product with the matrix
    transposed, and its column tiles are tiles of the CONTRACTED width:
    a row tile sums over them (the kernel's innermost grid axis), so an
    expert's ``up`` streams once a row tile of its rows: once at a
    decode step's few rows an expert."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    wu, wd = expert_params
    xs, m = _whole_row_tiles(xs)
    u = jax.nn.relu(gmm(
        xs, wu, group_sizes, preferred_element_type=xs.dtype,
        tiling=(_GMM_ROWS, _gmm_columns(wu), wu.shape[1]),
        transpose_rhs=True))
    return gmm(u * u, wd, group_sizes, preferred_element_type=xs.dtype,
               tiling=(_GMM_ROWS, wd.shape[1], _gmm_columns(wd)))[:m]


def _swiglu_expert(x, wg, wu, wd, gate=jax.nn.silu):
    return (gate(x @ wg) * (x @ wu)) @ wd


def _relu2_expert(x, wu, wd):
    """One plain relu^2 expert, ``wu`` [H, F] and ``wd`` [F, H] (the
    shared expert's leaves: its width is whole lane blocks)."""
    u = jax.nn.relu(x @ wu)
    return (u * u) @ wd


def expert_forms(form):
    """An expert's form (``TransformerConfig.moe_expert_form``) as (the
    routed experts over sorted rows by ``ragged_dot``, the same by the
    Pallas grouped matmul, ONE always-on expert on plain rows). "reglu"
    is the three-matrix form's one body with a ReLU for its gate."""
    gated = (ragged_swiglu_experts, gmm_swiglu_experts, _swiglu_expert)
    if form == "reglu":
        return tuple(functools.partial(fn, gate=jax.nn.relu)
                     for fn in gated)
    return {"swiglu": gated,
            "relu2": (ragged_relu2_experts, gmm_relu2_experts,
                      _relu2_expert)}[form]


def gmm_serves(expert_params) -> bool:
    """Whether the grouped-matmul kernel takes these experts: on a TPU,
    every weight's last axis whole 128-lane blocks and the one before it
    whole (16, 128) tiles (every accepted width: whole lane blocks too),
    one expert's weight (or its rows by a whole fraction of its columns)
    small enough to be a tile."""
    if jax.default_backend() != "tpu":
        return False
    return all(w.shape[-1] % 128 == 0 and w.shape[-2] % 16 == 0
               and _gmm_columns(w) is not None for w in expert_params)


def topk_routing(logits, k: int, scoring: str = "softmax", bias=None,
                 normalize: bool = True, scale: float = 1.0,
                 n_group: int = 1, topk_group: int = 1,
                 norm_eps: float = 1e-20):
    """Router logits [T, E] (float32) -> (chosen experts [T, k], their
    weights [T, k]), as served. ``scoring`` makes a logit a score
    (softmax over the experts, or an independent sigmoid); ``bias`` [E]
    is added to the scores to CHOOSE the top k and is no part of a
    weight (DeepSeek-V3's ``noaux_tc``: the bias balances load without a
    loss term). Its group limit: the experts form ``n_group`` groups of
    consecutive indices, a group scores the sum of its best two (bias
    included), and the top k are chosen inside the best ``topk_group``
    groups (``n_group`` 1: one group, every expert stands). The k > 1
    chosen weights are normalised over the chosen set where
    ``normalize`` (top-1 keeps its raw score: top1gating's g1), a
    sigmoid's sum guarded by ``norm_eps`` (the published blocks' own:
    DeepSeek-V3's 1e-20, lfm2_moe's 1e-6), then scaled."""
    scores = (jax.nn.softmax(logits, axis=-1) if scoring == "softmax"
              else jax.nn.sigmoid(logits))
    plain = bias is None and n_group == 1    # the scores choose as they are
    choose = scores if bias is None else scores + bias.astype(scores.dtype)
    if n_group > 1:
        T, E = choose.shape
        grouped = choose.reshape(T, n_group, E // n_group)
        best2, _ = jax.lax.top_k(grouped, 2)
        _, kept = jax.lax.top_k(jnp.sum(best2, axis=-1), topk_group)
        stands = jnp.zeros((T, n_group), bool).at[
            jnp.arange(T)[:, None], kept].set(True)
        choose = jnp.where(stands[:, :, None], grouped,
                           -jnp.inf).reshape(T, E)
    if plain:
        topv, topi = jax.lax.top_k(scores, k)
    else:
        _, topi = jax.lax.top_k(choose, k)
        topv = jnp.take_along_axis(scores, topi, axis=-1)
    if k > 1 and normalize:
        total = jnp.sum(topv, axis=-1, keepdims=True)
        # the published code's guard; a softmax's chosen never sum to 0
        topv = topv / (total + norm_eps if scoring == "sigmoid" else total)
    if scale != 1.0:
        topv = topv * scale
    return topi, topv


def dropless_topk_dispatch(xt, topi, topv, expert_params, num_experts: int,
                           ragged_expert_fn=None, stack_layer=None,
                           held_from=None, rows_combine=None):
    """Sorted-token grouped-GEMM core shared by the training dropless MoE
    and the v2 serving path (_moe_mlp): route every (token, choice) row to
    its expert with one argsort + `jax.lax.ragged_dot`, bring the rows
    back, and weight by the gate value. xt: [T, H]; topi/topv: [T, k].
    Returns [T, H].

    The rows are keyed PICK-major: row ``j * T + t`` is (token t, pick
    j), so that the experts' output comes back from expert order as k
    dense ``[T, H]`` slabs, ``[k, T, H]``, by ONE row gather through the
    inverse permutation, and the mask, the weights and the sum over the
    picks are one fusion over them. The token-major form this replaced
    scattered the rows into a zero-filled ``[T * k, H]`` and summed over
    ``[T, k, H]``: a TPU scatters rows one after another where it
    gathers them abreast, and the tiled layout pads a second-minor axis
    of k = 10 to 16, so that the "reshape" was a copy of 1.6x the bytes
    (granite's prompt, ms a call, PR 54's trace: the unsort's scatters
    613.7 where the gather INTO expert order of the same 168 MB a run is
    73.9; the reshapes 219.2).

    The rows come back in one of TWO FORMS. The gather above is XLA's
    and is what every caller that hands nothing gets: the training side,
    which differentiates the dispatch, and a decode step, whose few
    hundred rows XLA gathers out of fast memory in microseconds.
    ``rows_combine`` (``(ys, inv, held, topv, rows_held) -> [T, H]``:
    ``inference/v2/kernels/expert_combine.rows_combine``) takes the
    experts' output where it lies in HBM and does the gather, the mask,
    the weights and the sum itself, products in float32; the serving
    path hands it for the launches whose SHAPE it serves
    (``expert_combine.rows_combine_serves``: on a TPU, a share
    (``held_from``) of bfloat16 rows of whole lane blocks, an even count
    of at least ``MIN_ROWS``: a share's prompt launch, whose rows held
    elsewhere are then neither relaid nor copied), the way it hands the
    grouped matmul in place of ``ragged_dot``. No option chooses.

    ``stack_layer`` (a traced scalar): ``expert_params`` are a whole
    scanned stack's, [L, E, ...], and the layer is chosen by WHERE its
    groups lie among L * E (every other group is empty), so that the
    grouped matmul reads the stack in place: a layer sliced out of the
    stack for a custom call is a copy of all its experts.

    ``held_from`` (an int; None: ``expert_params`` hold every expert the
    router scores): they hold ``num_experts`` of them, the router's
    ``held_from`` .. (expert parallelism's share on one chip). A pick
    outside them sorts behind every group, is computed by no expert and
    adds nothing."""
    T, H = xt.shape
    k = topi.shape[-1]
    idx = topi.T.reshape(-1)                     # [k*T], pick-major
    held = None
    if held_from is not None:
        idx = idx - held_from
        held = (idx >= 0) & (idx < num_experts)
        idx = jnp.where(held, idx, num_experts)
    order = jnp.argsort(idx)                     # stable
    xs = xt[order % T]                           # row j*T+t <-> (token t, j)
    # (a pick held elsewhere has index num_experts: counted in no group)
    group_sizes = jnp.sum(idx[:, None] == jnp.arange(num_experts),
                          axis=0, dtype=jnp.int32)
    if stack_layer is not None:
        L = expert_params[0].shape[0]
        group_sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((L * num_experts,), jnp.int32), group_sizes,
            (stack_layer * num_experts,))
        expert_params = tuple(w.reshape(-1, *w.shape[2:])
                              for w in expert_params)
    fn = ragged_expert_fn or ragged_swiglu_experts
    ys = fn(expert_params, xs, group_sizes)      # [k*T, H], expert order
    # where each pick-major row lies in expert order: the inverse of a
    # permutation is its argsort (a sort of k*T int32 is a quarter of the
    # time of their scatter on a TPU). The rows are a permutation's, so
    # the gather's transpose is a plain row scatter
    inv = jnp.argsort(order)
    if rows_combine is not None:
        return rows_combine(ys, inv, held, topv, jnp.sum(group_sizes))
    return gather_rows_combine(ys, inv, held, topv)


def gather_rows_combine(ys, inv, held, topv):
    """The experts' output ``ys`` [k T, H] (expert order) back as [T, H]
    by XLA: one row gather through ``inv`` [k T] (where each pick-major
    row lies) to ``[k, T, H]``, the picks ``held`` [k T] elsewhere (None:
    none) masked, weighted by ``topv`` [T, k] and summed."""
    T, k = topv.shape
    rows = ys.at[inv].get(
        unique_indices=True, mode="promise_in_bounds").reshape(k, T, -1)
    if held is not None:
        # rows past the last group hold whatever the grouped matmul
        # left, finite or not: a zero weight would not do
        rows = jnp.where(held.reshape(k, T, 1), rows, 0)
    # products in the experts' type; jnp.sum adds them up in float32
    return jnp.sum(rows * topv.T[..., None].astype(ys.dtype), axis=0)


def moe_layer_dropless(x, gate_w, expert_params, ragged_expert_fn=None,
                       topo=None, rng=None,
                       noisy_gate_policy: Optional[str] = None):
    """Dropless top-1 MoE (the reference's drop_tokens=False mode,
    sharded_moe.py top1gating dynamic-capacity branch) via sorted tokens +
    `jax.lax.ragged_dot` grouped GEMM — no token is ever dropped and no
    [T, E, C] dispatch tensor is built.

    Expert parameters must be device-local (ep=1) on THIS path: ragged
    groups have data-dependent sizes, which cannot cross a static SPMD
    all-to-all. The reference composes dropless with EP by all-reducing a
    dynamic capacity at runtime (reference sharded_moe.py:214-218) —
    torch can reshape to a step-dependent capacity, XLA cannot. The
    static-shape equivalent is ``moe_layer_dropless_ep`` below: worst-case
    capacity C=T compiled in, memory traded for droplessness.
    """
    if topo is not None and topo.axis_size("expert") > 1:
        raise NotImplementedError(
            "ragged dropless MoE needs device-local experts (expert axis "
            "must be 1): ragged group sizes are data-dependent and cannot "
            "ride a static expert all-to-all. For ep>1 use "
            "moe_layer_dropless_ep (worst-case static capacity).")
    B, S, H = x.shape
    T = B * S
    E = gate_w.shape[-1]
    xt = x.reshape(T, H)
    logits = xt.astype(jnp.float32) @ gate_w.astype(jnp.float32)
    if noisy_gate_policy == "RSample" and rng is not None:
        logits_w_noise = logits + jax.random.gumbel(rng, logits.shape)
    else:
        logits_w_noise = logits
    gates = jax.nn.softmax(logits, axis=-1)
    idx = jnp.argmax(logits_w_noise, axis=-1)                   # [T]

    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(_one_hot(idx, E), axis=0)
    aux = jnp.sum(me * ce) * E

    gate_p = jnp.take_along_axis(gates, idx[:, None], axis=-1)  # [T, 1]
    out = dropless_topk_dispatch(xt, idx[:, None], gate_p, expert_params, E,
                                 ragged_expert_fn)
    return out.reshape(B, S, H), aux.astype(jnp.float32)


def moe_layer_dropless_ep(x, gate_w, expert_params, expert_fn, topo,
                          top_k: int = 1, rng=None,
                          noisy_gate_policy: Optional[str] = None,
                          max_dispatch_elems: int = 1 << 28
                          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dropless top-1/top-2 MoE UNDER expert parallelism (reference
    drop_tokens=False with ep>1). The reference sizes its dispatch buffers
    with a runtime all-reduced max capacity (sharded_moe.py:214-218);
    XLA's static shapes can't — so the worst case (C = T for top-1, 2T for
    top-2: ``capacity_factor=E`` through ``_capacity``, whose top-2 branch
    doubles it) is compiled in and the standard einsum dispatch + GSPMD
    expert all-to-all runs over it. Semantically dropless: per-expert load
    can never exceed that capacity, so it never binds.

    MEMORY TRADE (read before using): the dispatch/combine tensors are
    [T, E, k*T] — quadratic in local tokens. Fine for modest T (decode
    batches, short prefill chunks, the routed block after dp/sp sharding),
    ruinous for long sequences — ``max_dispatch_elems`` rejects that
    regime loudly instead of OOMing; prefer capacity routing or ep=1
    ragged dropless there.
    """
    B, S, _ = x.shape
    T = B * S
    E = gate_w.shape[-1]
    if T * E * (top_k * T) > max_dispatch_elems:
        raise NotImplementedError(
            f"dropless-under-ep worst-case dispatch is [T,E,k*T] = "
            f"[{T},{E},{top_k * T}] (> {max_dispatch_elems} elements): "
            f"quadratic in tokens. Chunk the sequence (smaller prefill "
            f"bucket), use capacity routing, or serve with ep=1.")
    # capacity_factor = E makes _capacity == ceil(T/E * E) == T
    return moe_layer(x, gate_w, expert_params, expert_fn, topo,
                     top_k=top_k, capacity_factor=float(E), min_capacity=1,
                     rng=rng, noisy_gate_policy=noisy_gate_policy)


def residual_moe_combine(x, moe_out, mlp_out, coef_w, coef_b=None):
    """Residual-MoE mixture (reference moe/layer.py:118-123, the PR-MoE
    building block, arXiv:2201.05596): a 2-way softmax over a learned
    coefficient head weights the routed-expert output against a dense MLP
    applied to the same input."""
    coef = x @ coef_w.astype(x.dtype)
    if coef_b is not None:
        coef = coef + coef_b.astype(x.dtype)
    coef = jax.nn.softmax(coef.astype(jnp.float32), axis=-1).astype(x.dtype)
    return moe_out * coef[..., 0:1] + mlp_out * coef[..., 1:2]
