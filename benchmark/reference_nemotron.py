"""Plain reference: the ``nemotron_h`` block as
``NVIDIA-Nemotron-3-Nano-30B-A3B-BF16`` publishes it
(https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16,
``config.json``; the equations are those of the family's public
``modeling_nemotron_h.py`` and of the Mamba-2 paper, arXiv:2405.21060),
in straightforward ``jax.numpy`` float32: no kernel, no cache, no
batching, no chunking, a token at a time through the state-space layers'
recurrence, and nothing imported from the program.

Stream: ``h = embed[id]`` (no multiplier). Every layer i is ONE
sub-layer behind ONE norm, ``h = h + f_i(RMSNorm_i(h))`` (eps 1e-5, a
learned weight, no bias anywhere but the convolution's), and
``layer_types`` (the first characters of ``hybrid_override_pattern``
spelt out: ``M`` "mamba", ``E`` "moe", ``*`` "attention") says which:

1. ``mamba`` (Mamba-2, ``mamba_n_groups`` groups): ``[z | xBC | dt] = x
   W_in`` (d_inner | d_inner + 2 groups d_state | heads, d_inner = heads
   x ``mamba_d_head`` whatever ``expand`` says); ``xBC = silu(conv(xBC) +
   b)``, a causal depthwise convolution of ``mamba_d_conv`` taps over
   the sequence's own tokens; ``x`` [heads, d_head], ``B``, ``C``
   [groups, d_state]; ``dt = softplus(dt + dt_bias)``, ``A =
   -exp(A_log)`` a head. From S = 0 ``[d_head, d_state]`` a head, token
   by token, head h of group g = h // (heads / groups): ``S_h = exp(dt_h
   A_h) S_h + (dt_h x_h) B_g^T``; ``y_h = S_h C_g + D_h x_h``. Output:
   ``W_out (groupnorm_rms(y * silu(z)) w)``, the RMS over each group's
   d_inner / groups channels, the weight over all of d_inner.
2. ``moe``: ``s = sigmoid(x W_r)`` over ALL ``moe_num_experts``; the
   ``moe_top_k`` largest of ``s + b`` (the selection bias chooses and
   does not weigh; one group, no group limit); weights ``s_e / (sum of
   the chosen + 1e-20) * moe_routed_scale``; expert e is TWO matrices
   and a squared ReLU, ``W2_e relu(W1_e x)^2``; plus the shared expert
   in the same form, ``moe_shared_experts`` experts wide. THE SHARE:
   ``params`` hold ``moe_experts_held`` experts, the router's
   ``moe_experts_first`` .., and a chosen expert that is not among them
   adds nothing (it is another chip's: expert parallelism's cut, the
   configuration's ``reduced``).
3. ``attention``: q, k, v by three bias-free projections to
   ``num_heads`` / ``num_kv_heads`` heads of ``head_dim``, NO rotation,
   scores ``q k^T / sqrt(head_dim)``, causal softmax, ``wo``.
4. ``logits = RMSNorm(h) lm_head`` over the held rows of the UNTIED
   head (a vocabulary slice is a smaller vocabulary: ``embed`` and
   ``lm_head`` hold the same rows).

DEPARTURES from the checkpoint's own forward: none in the equations.
``residual_in_fp32`` false describes the checkpoint's type; here (and in
the program) the stream is float32. ``rescale_prenorm_residual``,
``time_step_min / max / floor`` are the initialiser's.

ASSUMED (the configuration's file lists the same under ``assumed``): no
rotation on the attention layers (``NemotronHAttention`` applies no
position signal; ``rope_theta`` and ``partial_rotary_factor`` describe
no tensor that is served); ``head_dim`` 128 is a key of its own (32 x
128 = 4,096 beside hidden 2,688); the gated norm multiplies by silu(z)
BEFORE the norm and norms a GROUP (``n_groups`` 8: 512 channels); no
clamp on dt (``time_step_limit`` (0, inf)); ``expand`` is read and
unused; A_log, dt_bias, D and the taps seeded in the ranges Mamba-2
initialises them in (``weights_nemotron.py``).

Every call runs under ``jax.default_matmul_precision("highest")``.
Parameters are read in the program's layout (``embed``, ``lm_head``,
``final_norm``; ``ssm_layers`` / ``full_layers`` the mixers of a kind in
layer order; ``layers`` the norm, router and experts of the EXPERT
layers alone, in layer order) and cast up a layer at a time, an expert
stack a held expert at a time. A routed expert's ``e_up`` is stored out
x in, [experts, width, hidden], as a linear layer's checkpoint stores
it; every other matrix in x out.
"""

import jax
import jax.numpy as jnp

SUPPORTED = dict(attention="mha", norm="rmsnorm", positional="none",
                 tie_embeddings=False, moe_scoring="sigmoid",
                 moe_selection_bias=True, moe_norm_topk=True,
                 moe_expert_form="relu2")
KINDS = {"mamba": "ssm", "attention": "full", "moe": "moe"}


def check_supported(fields):
    """This reference is the nemotron_h block as Nemotron-3-Nano sets
    it; refuse a configuration it does not describe."""
    for key, want in SUPPORTED.items():
        if fields.get(key) != want:
            raise ValueError(
                f"benchmark/reference_nemotron.py implements the "
                f"nemotron_h block ({SUPPORTED}); configuration has "
                f"{key}={fields.get(key)!r}. Add a reference for it.")
    types = fields.get("layer_types") or ["?"]
    if set(types) - set(KINDS) or "moe" not in types \
            or not fields.get("moe_num_experts") \
            or not fields.get("moe_shared_experts") \
            or fields.get("moe_first_dense_layers") \
            or fields.get("moe_n_group", 1) != 1 \
            or fields["mamba_n_heads"] % fields.get("mamba_n_groups", 1):
        raise ValueError(
            "benchmark/reference_nemotron.py: a layer_types pattern of "
            "mamba, moe and attention layers (one sub-layer each), routed "
            "experts with a shared expert, one router group and mamba "
            "heads a whole multiple of the groups are part of the block")


def layer_kinds(f):
    return [KINDS[t] for t in f["layer_types"]]


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * w


def _causal_conv(x, taps):
    """x [S, D], taps [K, D]: y_t = sum_j taps[j] x_{t - (K - 1) + j},
    zeros before the sequence."""
    K = taps.shape[0]
    xp = jnp.pad(x, ((K - 1, 0), (0, 0)))
    return sum(taps[j] * xp[j:j + x.shape[0]] for j in range(K))


def _mamba_mixer(x, lp, f):
    """(what the mixer adds to the stream, the state S after the last
    token [heads, d_head, d_state])."""
    S = x.shape[0]
    nh, p, n = f["mamba_n_heads"], f["mamba_d_head"], f["mamba_d_state"]
    g = f.get("mamba_n_groups", 1)
    di, hg = nh * p, nh // g
    h = _rms_norm(x, lp["attn_norm"], f["norm_eps"])
    zxd = h @ lp["w_in"]
    z, xbc, dt = zxd[:, :di], zxd[:, di:2 * di + 2 * g * n], \
        zxd[:, 2 * di + 2 * g * n:]
    xbc = _causal_conv(xbc, lp["conv"])
    if "conv_b" in lp:
        xbc = xbc + lp["conv_b"]
    xbc = jax.nn.silu(xbc)
    xs = xbc[:, :di].reshape(S, g, hg, p)
    b = xbc[:, di:di + g * n].reshape(S, g, n)
    c = xbc[:, di + g * n:].reshape(S, g, n)
    dt = jax.nn.softplus(dt + lp["dt_bias"]).reshape(S, g, hg)
    a = -jnp.exp(lp["a_log"]).reshape(g, hg)

    def token(state, t):
        xt, bt, ct, dtt = t                 # [g, hg, p] [g, n] [g, n] [g, hg]
        state = jnp.exp(dtt * a)[:, :, None, None] * state \
            + (dtt[:, :, None] * xt)[..., None] * bt[:, None, None, :]
        return state, jnp.einsum("ghpn,gn->ghp", state, ct)

    state, y = jax.lax.scan(token, jnp.zeros((g, hg, p, n), jnp.float32),
                            (xs, b, c, dt))
    y = y + lp["d_skip"].reshape(g, hg)[:, :, None] * xs
    gated = y.reshape(S, g, di // g) * jax.nn.silu(z).reshape(S, g, di // g)
    y = _rms_norm(gated, lp["gate_norm"].reshape(g, di // g), f["norm_eps"])
    return y.reshape(S, di) @ lp["w_out"], state.reshape(nh, p, n)


def _attention_mixer(x, lp, f):
    S = x.shape[0]
    nh, nkv = f["num_heads"], f.get("num_kv_heads") or f["num_heads"]
    hd = f.get("head_dim_override") or f["hidden_size"] // nh
    h = _rms_norm(x, lp["attn_norm"], f["norm_eps"])
    q = (h @ lp["wq"]).reshape(S, nh, hd)
    k = jnp.repeat((h @ lp["wk"]).reshape(S, nkv, hd), nh // nkv, axis=1)
    v = jnp.repeat((h @ lp["wv"]).reshape(S, nkv, hd), nh // nkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf),
                           axis=-1)
    o = jnp.einsum("hqk,khd->qhd", probs, v).reshape(S, nh * hd)
    return o @ lp["wo"], None


def _mixer(x, stack, i, kind, f):
    lp = jax.tree.map(lambda a: _f32(a[i]), stack)
    return (_mamba_mixer if kind == "ssm" else _attention_mixer)(x, lp, f)


_mixer_jit = jax.jit(_mixer, static_argnums=(3, 4))


def _relu2(h, w1, w2):
    return jnp.square(jax.nn.relu(h @ w1)) @ w2


def route(logits, bias, f):
    """Router logits [S, E] -> (chosen [S, k], weights [S, k]): sigmoid
    scores, the k largest of score + bias, the chosen SCORES over their
    sum (+ 1e-20) times the routed scale."""
    scores = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(scores + bias, f["moe_top_k"])
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if f.get("moe_norm_topk", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * f.get("moe_routed_scale", 1.0)


def _router_and_shared(x, small, f):
    lp = jax.tree.map(_f32, small)
    h = _rms_norm(x, lp["mlp_norm"], f["norm_eps"])
    chosen, w = route(h @ lp["moe_gate_w"], lp["moe_gate_bias"], f)
    return _relu2(h, lp["shared_up"], lp["shared_down"]), h, chosen, w


_router_jit = jax.jit(_router_and_shared, static_argnums=(2,))


@jax.jit
def _held_experts(h, local, w, e_up, e_down):
    """sum_j w_j E_j(h) over the chosen experts that are held: ``local``
    [S, k] is a chosen expert's index among the held ones, -1 where it
    is held elsewhere. A held expert at a time over every position, its
    output weighed by what the positions that chose it gave it (0: not
    chosen)."""
    def add(e, acc):
        weight = jnp.sum(jnp.where(local == e, w, 0.0), axis=-1)
        return acc + weight[:, None] * _relu2(h, _f32(e_up[e]).T,
                                              _f32(e_down[e]))

    return jax.lax.fori_loop(0, e_up.shape[0], add, jnp.zeros_like(h))


def expert_layer(x, stack, i, f):
    """What expert layer ``i`` (its place among the expert layers) adds
    to the stream x [S, H]: (the held routed experts' part, the shared
    expert's part)."""
    experts = ("e_up", "e_down")
    small = {k: v[i] for k, v in stack.items() if k not in experts}
    shared, h, chosen, w = _router_jit(x, small, _Frozen(f))
    held = stack["e_up"].shape[1]
    local = chosen - f.get("moe_experts_first", 0)
    local = jnp.where((local >= 0) & (local < held), local, -1)
    return _held_experts(h, local, w, *(stack[k][i] for k in experts)), \
        shared


class _Frozen(dict):
    """``fields`` as a static argument of a jitted function."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


@jax.jit
def _head(x, final_w, head, eps):
    return _rms_norm(x, _f32(final_w), eps) @ _f32(head)


def _layers(params, fields, ids, layers=None):
    """The residual stream after the first ``layers`` layers (None: all
    of them), and the mamba mixers' final states in layer order."""
    f = _Frozen(fields)
    x = _f32(params["embed"][jnp.asarray(ids, jnp.int32)])
    seen = {"ssm": 0, "full": 0, "moe": 0}
    states = []
    for kind in layer_kinds(fields)[:layers]:
        if kind == "moe":
            routed, shared = expert_layer(x, params["layers"], seen[kind],
                                          fields)
            x = x + routed + shared
        else:
            add, state = _mixer_jit(x, params[kind + "_layers"], seen[kind],
                                    kind, f)
            x = x + add
            if kind == "ssm":
                states.append(state)
        seen[kind] += 1
    return x, states


def hidden(params, fields, ids):
    """The residual stream after the last layer."""
    return _layers(params, fields, ids)[0]


def leading_states(params, fields, ids, layers=1):
    """[layers, heads, d_head, d_state] float32: the recurrent state S
    after the last token of ``ids`` in the first ``layers`` mamba
    layers. Layer 0 is a mamba mixer AHEAD OF EVERY ROUTED EXPERT: what
    its state holds depends on no expert choice, so a comparison of it
    is free of the swap a hard top-k makes against a float32 reference.
    ``layers`` > 1 reads the next ones too, for a run's detail."""
    check_supported(fields)
    kinds = layer_kinds(fields)
    at = [i for i, k in enumerate(kinds) if k == "ssm"][:layers]
    with jax.default_matmul_precision("highest"):
        return jnp.stack(_layers(params, fields, ids,
                                 layers=at[-1] + 1)[1])


def logits(params, fields, ids):
    """[S, vocab] float32 logits of one sequence ``ids`` [S]."""
    check_supported(fields)
    with jax.default_matmul_precision("highest"):
        return _head(hidden(params, fields, ids), params["final_norm"],
                     params["lm_head"], fields["norm_eps"])


def next_token_loss(params, fields, ids):
    """Mean next-token cross-entropy of one sequence, float32."""
    lg = logits(params, fields, ids)[:-1]
    tgt = jnp.asarray(ids, jnp.int32)[1:]
    logz = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, tgt[:, None], axis=-1)[:, 0]
    return float(jnp.mean(logz - picked))
