"""Plain reference: the ``granitemoehybrid`` block as
``granite-4.0-h-small`` publishes it
(https://huggingface.co/ibm-granite/granite-4.0-h-small, ``config.json``;
the equations are those of ``modeling_granitemoehybrid.py`` and of the
Mamba-2 paper, arXiv:2405.21060), in straightforward ``jax.numpy``
float32: no kernel, no cache, no batching, no chunking, a token at a
time through the state-space layers' recurrence, and nothing imported
from the program.

Stream: ``h = embedding_multiplier * embed[id]``. Layer i (x =
RMSNorm(h), eps 1e-5, no projection bias) adds ``residual_multiplier``
times its mixer's output to the stream, then ``residual_multiplier``
times (routed + shared experts) of ONE norm of the stream. ``layer_types``
says which mixer.

1. ``mamba`` (Mamba-2): ``[z | xBC | dt] = x W_in`` (d_inner | d_inner +
   2 d_state | heads); ``xBC = silu(conv(xBC) + b)``, a causal depthwise
   convolution of ``mamba_d_conv`` taps over the sequence's own tokens;
   ``[x | B | C] = xBC`` (heads of ``mamba_d_head`` | d_state | d_state:
   one group, B and C serve every head); ``dt = softplus(dt + dt_bias)``,
   ``A = -exp(A_log)`` a head. From S = 0 ``[d_head, d_state]`` a head,
   token by token: ``S = exp(dt A) S + (dt x) B^T``; ``y = S C + D x``.
   Output: ``W_out (RMSNorm(y * silu(z)) w)``, the norm over all
   ``d_inner`` channels (one group).
2. ``attention``: q, k, v by three bias-free projections to
   ``num_heads`` / ``num_kv_heads`` heads of ``head_dim``, NO rotation
   (``position_embedding_type`` nope), scores ``q k^T *
   attention_multiplier``, causal softmax, ``wo``.
3. Expert layer: ``l = x W_r`` over ALL ``moe_num_experts``; the
   ``moe_top_k`` largest; weights a softmax over THOSE logits; expert e
   is ``W_down,e (silu(g) * u)``, ``[g | u] = W_in,e x``; plus the shared
   SwiGLU, ``moe_shared_experts`` experts wide. THE SHARE: ``params``
   hold ``moe_experts_held`` experts, the router's
   ``moe_experts_first`` .., and a chosen expert that is not among them
   adds nothing (it is another chip's: expert parallelism's cut, the
   configuration's ``reduced``).
4. ``logits = RMSNorm(h) embed^T / logits_scaling`` over the held rows
   of the tied table.

ASSUMED (the configuration's file lists the same under ``assumed``):
``intermediate_size`` 768 is an expert's width (the catalog's note);
``head_dim`` = hidden / heads = 128; the gated norm multiplies by
silu(z) BEFORE the norm and norms over the whole 8,192 (n_groups 1);
no clamp on dt (``time_step_limit`` (0, inf)); A_log, dt_bias, D and
the taps seeded in the ranges Mamba-2 initialises them in
(``weights_granite.py``).

Every call runs under ``jax.default_matmul_precision("highest")``.
Parameters are read in the program's layout (``embed``, ``final_norm``;
``ssm_layers`` / ``full_layers`` the mixers of a kind in layer order;
``layers`` the norm, router and experts of every layer) and cast up a
layer at a time, an expert stack a held expert at a time.
"""

import jax
import jax.numpy as jnp

SUPPORTED = dict(attention="mha", norm="rmsnorm", activation="swiglu",
                 positional="none", tie_embeddings=True,
                 moe_scoring="softmax", moe_norm_topk=True,
                 mamba_n_groups=1)
KINDS = {"mamba": "ssm", "attention": "full"}


def check_supported(fields):
    """This reference is the granitemoehybrid block as
    granite-4.0-h-small sets it; refuse a configuration it does not
    describe."""
    for key, want in SUPPORTED.items():
        if fields.get(key, 1 if key == "mamba_n_groups" else None) != want:
            raise ValueError(
                f"benchmark/reference_granite.py implements the "
                f"granitemoehybrid block ({SUPPORTED}); configuration has "
                f"{key}={fields.get(key)!r}. Add a reference for it.")
    if set(fields.get("layer_types") or ["?"]) - set(KINDS) \
            or not fields.get("moe_num_experts") \
            or not fields.get("moe_shared_experts") \
            or fields.get("moe_first_dense_layers"):
        raise ValueError("benchmark/reference_granite.py: a layer_types "
                         "pattern of mamba and attention, routed experts "
                         "and a shared expert on every layer are part of "
                         "the block")


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
    S = x.shape[0]
    nh, p, n = f["mamba_n_heads"], f["mamba_d_head"], f["mamba_d_state"]
    di = nh * p
    h = _rms_norm(x, lp["attn_norm"], f["norm_eps"])
    zxd = h @ lp["w_in"]
    z, xbc, dt = zxd[:, :di], zxd[:, di:2 * di + 2 * n], zxd[:, 2 * di + 2 * n:]
    xbc = _causal_conv(xbc, lp["conv"])
    if "conv_b" in lp:
        xbc = xbc + lp["conv_b"]
    xbc = jax.nn.silu(xbc)
    xs = xbc[:, :di].reshape(S, nh, p)
    b, c = xbc[:, di:di + n], xbc[:, di + n:]
    dt = jax.nn.softplus(dt + lp["dt_bias"])                 # [S, nh]
    a = -jnp.exp(lp["a_log"])

    def token(state, t):
        xt, bt, ct, dtt = t
        state = jnp.exp(dtt * a)[:, None, None] * state \
            + (dtt[:, None] * xt)[:, :, None] * bt[None, None, :]
        return state, jnp.einsum("hpn,n->hp", state, ct)

    state, y = jax.lax.scan(token, jnp.zeros((nh, p, n), jnp.float32),
                            (xs, b, c, dt))
    y = (y + lp["d_skip"][:, None] * xs).reshape(S, di)
    y = _rms_norm(y * jax.nn.silu(z), lp["gate_norm"], f["norm_eps"])
    return x + f.get("residual_scale", 1.0) * (y @ lp["w_out"]), state


def _attention_mixer(x, lp, f):
    S = x.shape[0]
    nh, nkv = f["num_heads"], f.get("num_kv_heads") or f["num_heads"]
    hd = f.get("head_dim_override") or f["hidden_size"] // nh
    h = _rms_norm(x, lp["attn_norm"], f["norm_eps"])
    q = (h @ lp["wq"]).reshape(S, nh, hd)
    k = jnp.repeat((h @ lp["wk"]).reshape(S, nkv, hd), nh // nkv, axis=1)
    v = jnp.repeat((h @ lp["wv"]).reshape(S, nkv, hd), nh // nkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) \
        * (f.get("attn_scale") or hd ** -0.5)
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf),
                           axis=-1)
    o = jnp.einsum("hqk,khd->qhd", probs, v).reshape(S, nh * hd)
    return x + f.get("residual_scale", 1.0) * (o @ lp["wo"]), None


def _mixer(x, stack, i, kind, f):
    """(the stream after the mixer, a mamba mixer's state S after the
    last token [heads, d_head, d_state]; None for an attention one)."""
    lp = jax.tree.map(lambda a: _f32(a[i]), stack)
    return (_mamba_mixer if kind == "ssm" else _attention_mixer)(x, lp, f)


_mixer_jit = jax.jit(_mixer, static_argnums=(3, 4))


def _swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def route(logits, f):
    """Router logits [S, E] -> (chosen [S, k], weights [S, k]): the k
    largest, a softmax over those k."""
    top, chosen = jax.lax.top_k(logits, f["moe_top_k"])
    return chosen, jax.nn.softmax(top, axis=-1)


def _router_and_shared(x, small, f):
    lp = jax.tree.map(_f32, small)
    h = _rms_norm(x, lp["mlp_norm"], f["norm_eps"])
    chosen, w = route(h @ lp["moe_gate_w"], f)
    shared = _swiglu(h, lp["shared_gate"], lp["shared_up"],
                     lp["shared_down"])
    return shared, h, chosen, w


_router_jit = jax.jit(_router_and_shared, static_argnums=(2,))


@jax.jit
def _held_experts(h, local, w, e_gate, e_up, e_down):
    """sum_j w_j E_j(h) over the chosen experts that are held: ``local``
    [S, k] is a chosen expert's index among the held ones, -1 where it
    is held elsewhere. A held expert at a time over every position, its
    output weighed by what the positions that chose it gave it (0: not
    chosen)."""
    def add(e, acc):
        weight = jnp.sum(jnp.where(local == e, w, 0.0), axis=-1)
        return acc + weight[:, None] * _swiglu(
            h, _f32(e_gate[e]), _f32(e_up[e]), _f32(e_down[e]))

    return jax.lax.fori_loop(0, e_gate.shape[0], add, jnp.zeros_like(h))


def expert_layer(x, stack, i, f):
    """What layer ``i``'s expert layer adds to the stream x [S, H]
    BEFORE the residual multiplier: (the held routed experts' part, the
    shared expert's part)."""
    experts = ("e_gate", "e_up", "e_down")
    small = {k: v[i] for k, v in stack.items() if k not in experts}
    shared, h, chosen, w = _router_jit(x, small, _Frozen(f))
    held = stack["e_gate"].shape[1]
    local = chosen - f.get("moe_experts_first", 0)
    local = jnp.where((local >= 0) & (local < held), local, -1)
    return _held_experts(h, local, w, *(stack[k][i] for k in experts)), \
        shared


class _Frozen(dict):
    """``fields`` as a static argument of a jitted function."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


@jax.jit
def _head(x, final_w, embed, eps, scale):
    return _rms_norm(x, _f32(final_w), eps) @ _f32(embed).T / scale


def _layers(params, fields, ids, mixers=None):
    """The residual stream after the last layer, and the mamba mixers'
    final states in layer order; ``mixers``: stop after that many
    mixers (the expert layer behind the last of them is not run)."""
    f = _Frozen(fields)
    kinds = layer_kinds(fields)[:mixers]
    x = fields.get("embed_scale", 1.0) \
        * _f32(params["embed"][jnp.asarray(ids, jnp.int32)])
    seen = {"ssm": 0, "full": 0}
    states = []
    for i, kind in enumerate(kinds):
        x, state = _mixer_jit(x, params[kind + "_layers"], seen[kind],
                              kind, f)
        seen[kind] += 1
        if kind == "ssm":
            states.append(state)
        if i + 1 == mixers:
            break
        routed, shared = expert_layer(x, params["layers"], i, fields)
        x = x + fields.get("residual_scale", 1.0) * (routed + shared)
    return x, states


def hidden(params, fields, ids):
    """The residual stream after the last layer."""
    return _layers(params, fields, ids)[0]


def leading_states(params, fields, ids, layers=1):
    """[layers, heads, d_head, d_state] float32: the recurrent state S
    after the last token of ``ids`` in the first ``layers`` mamba
    layers. Layer 0's mixer runs AHEAD OF EVERY ROUTED EXPERT (every
    layer of this block has experts, so only layer 0's does): what its
    state holds depends on no expert choice, so a comparison of it is
    free of the swap a hard top-k makes against a float32 reference.
    ``layers`` > 1 reads the next ones too, for a run's detail."""
    check_supported(fields)
    kinds = layer_kinds(fields)
    at = [i for i, k in enumerate(kinds) if k == "ssm"][:layers]
    with jax.default_matmul_precision("highest"):
        return jnp.stack(_layers(params, fields, ids,
                                 mixers=at[-1] + 1)[1])


def logits(params, fields, ids):
    """[S, vocab] float32 logits of one sequence ``ids`` [S]."""
    check_supported(fields)
    with jax.default_matmul_precision("highest"):
        return _head(hidden(params, fields, ids), params["final_norm"],
                     params["embed"], fields["norm_eps"],
                     fields.get("logit_scale", 1.0))


def next_token_loss(params, fields, ids):
    """Mean next-token cross-entropy of one sequence, float32."""
    lg = logits(params, fields, ids)[:-1]
    tgt = jnp.asarray(ids, jnp.int32)[1:]
    logz = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, tgt[:, None], axis=-1)[:, 0]
    return float(jnp.mean(logz - picked))
