"""Plain reference: the ``bailing_hybrid`` block as ``Ling-3.0-flash``
publishes it (https://huggingface.co/inclusionAI/Ling-3.0-flash,
``config.json``), in straightforward ``jax.numpy`` float32: no kernel,
no cache, no batching, no chunking, a token at a time through the
linear layers' recurrence, and nothing imported from the program.

Layer i of the block (x = RMSNorm(h), eps 1e-6, no biases, residual
adds) has a LATENT mixer where (i + 1) % ``layer_group_size`` == 0 and
a LINEAR one otherwise; the first ``first_k_dense_replace`` layers have
a dense SwiGLU, the rest the expert layer.

1. Linear mixer (Kimi Delta Attention: Kimi Linear, arXiv:2510.26692,
   and the ``kda`` layer of flash-linear-attention, whose ``safe_gate``
   / ``lower_bound`` the source's ``kda_safe_gate`` /
   ``kda_lower_bound`` name), ``num_heads`` heads of d = ``head_dim``
   keys and values: q~, k~, v~ = Wq x, Wk x, Wv x, each through a causal
   depthwise convolution of ``short_conv_kernel_size`` taps over the
   sequence's own tokens and SiLU; a head's q = l2norm(q~) d^-1/2, k =
   l2norm(k~), v = v~; the decay a head and key channel g = lb *
   sigmoid(exp(A_log) (Wf x + dt_bias)), lb = ``kda_lower_bound``; beta
   = sigmoid(Wb x) a head. From S = 0 [d, d], token by token:
   S' = diag(exp(g)) S;  S = S' + beta k (v - S'^T k)^T;  o = S^T q.
   Output: Wo (RMSNorm_head(o) * sigmoid(Wg x)), one gate a head.
2. Latent mixer: DeepSeek-V3's in the NON-absorbed form, the query ONE
   projection (``q_lora_rank`` null); keys and values expanded per head
   from the normed latent; rope at ``rope_theta`` on the query's rope
   part and the one key part all heads share, pairs of lanes (2i,
   2i + 1) in place; scores over sqrt(qk width), causal softmax; the
   same head-wise sigmoid gate on the heads' outputs before Wo.
3. Expert layer: s = sigmoid(Wr x) over ALL ``moe_num_experts``; to
   choose, s + bias: ``n_group`` groups of consecutive experts, a
   group's score the sum of its best two, the best ``topk_group`` groups
   kept, the best k experts among them; weights are s of the chosen (no
   bias) over their sum (+ 1e-20) times ``routed_scaling_factor``; plus
   the shared expert. THE SHARE: ``params`` hold
   ``moe_experts_held`` experts, the router's ``moe_experts_first`` ..,
   and a chosen expert that is not among them adds nothing (it is
   another chip's: expert parallelism's cut, the configuration's
   ``reduced``).
4. The final RMSNorm and the untied head over the held slice of the
   vocabulary.

ASSUMED (the configuration's file lists the same under ``assumed``):

* the pattern rule above (the source gives ``layer_group_size`` 6 and
  no list);
* ``use_qk_norm`` means the l2norm of q and k on a linear layer and the
  latent's own norm (``kv_norm``) on a latent one; l2norm(x) = x /
  sqrt(sum x^2 + 1e-6), flash-linear-attention's;
* the head-wise gate (``gated_attention_proj_granularity_type``
  head_wise) stands on BOTH mixers;
* ``kda_lower_bound`` enters as above (the ``kda`` layer's safe gate);
* A_log, dt_bias and the taps are seeded in the ranges the ``kda``
  layer initialises them (``weights_ling.py``);
* 0 in ``expert_swiglu_limit_list`` / ``share_expert_swiglu_limit_list``
  (every kept layer) is read as no clamp;
* ``value_norm``, ``up_proj_norm``, ``use_nGPT``, ``use_kda_lora``,
  ``num_kv_heads_for_linear_attn`` 0, ``max_window_layers`` and
  ``scale_router_input`` false switch nothing on; the prediction module
  is left out (``reduced``).

Every call runs under ``jax.default_matmul_precision("highest")``.
Parameters are read in the program's layout (``embed``, ``lm_head``,
``final_norm``; ``kda_layers`` / ``mla_layers`` the mixers of a kind in
layer order; ``lead_layers`` / ``layers`` the norm and MLP of the
leading dense and of the expert layers) and cast up a layer at a time,
an expert stack only at the experts a block of positions chose.
"""

import jax
import jax.numpy as jnp

SUPPORTED = dict(attention="mla", norm="rmsnorm", activation="swiglu",
                 positional="rope", tie_embeddings=False,
                 moe_scoring="sigmoid", moe_selection_bias=True,
                 moe_norm_topk=True, rope_interleave=True,
                 attn_gate="head", q_lora_rank=0)
EXPERT_BLOCK = 16
L2_EPS = 1e-6


def check_supported(fields):
    """This reference is the bailing_hybrid block as Ling-3.0-flash sets
    it; refuse a configuration it does not describe."""
    for key, want in SUPPORTED.items():
        if fields.get(key, 0 if key == "q_lora_rank" else None) != want:
            raise ValueError(
                f"benchmark/reference_ling.py implements the "
                f"bailing_hybrid block ({SUPPORTED}); configuration has "
                f"{key}={fields.get(key)!r}. Add a reference for it.")
    if not fields.get("linear_attn_period") \
            or not fields.get("moe_num_experts") \
            or not fields.get("moe_shared_experts"):
        raise ValueError("benchmark/reference_ling.py: a layer pattern, "
                         "routed experts and a shared expert are part of "
                         "the block")


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * w


def _l2norm(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                        + L2_EPS)


def _rope_pairs(x, theta):
    S, D = x.shape[0], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    ang = ang.reshape(S, *([1] * (x.ndim - 2)), D // 2)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                     x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], axis=-1)
    return out.reshape(x.shape)


def layer_kinds(f):
    p = f["linear_attn_period"]
    return ["mla" if (i + 1) % p == 0 else "kda"
            for i in range(f["num_layers"])]


def _causal_conv(x, taps):
    """x [S, D], taps [K, D]: y_t = sum_j taps[j] x_{t - (K - 1) + j},
    zeros before the sequence."""
    K = taps.shape[0]
    xp = jnp.pad(x, ((K - 1, 0), (0, 0)))
    return sum(taps[j] * xp[j:j + x.shape[0]] for j in range(K))


def _linear_mixer(x, lp, f):
    S = x.shape[0]
    nh, d = f["num_heads"], f["linear_head_dim"]
    D = nh * d
    h = _rms_norm(x, lp["attn_norm"], f["norm_eps"])
    taps = lp["conv"]
    q, k, v = (jax.nn.silu(_causal_conv(h @ lp[w], taps[:, i * D:(i + 1) * D]))
               .reshape(S, nh, d) for i, w in enumerate(("wq", "wk", "wv")))
    q, k = _l2norm(q) * d ** -0.5, _l2norm(k)
    g = f["linear_decay_floor"] * jax.nn.sigmoid(
        jnp.exp(lp["a_log"])[:, None]
        * ((h @ lp["wf"]) + lp["dt_bias"]).reshape(S, nh, d))
    beta = jax.nn.sigmoid(h @ lp["wb"])                      # [S, nh]

    def token(state, t):
        qt, kt, vt, gt, bt = t
        s = jnp.exp(gt)[:, :, None] * state                  # [nh, dk, dv]
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", s, kt))
        s = s + jnp.einsum("hk,hv->hkv", kt, u)
        return s, jnp.einsum("hkv,hk->hv", s, qt)

    state, o = jax.lax.scan(token, jnp.zeros((nh, d, d), jnp.float32),
                            (q, k, v, g, beta))
    o = _rms_norm(o, lp["o_norm"], f["norm_eps"]) \
        * jax.nn.sigmoid(h @ lp["wg"])[..., None]
    return x + o.reshape(S, D) @ lp["wo"], state


def _latent_mixer(x, lp, f):
    S = x.shape[0]
    nh, dc = f["num_heads"], f["kv_lora_rank"]
    dn, dr, dv = f["qk_nope_head_dim"], f["qk_rope_head_dim"], \
        f["v_head_dim"]
    eps = f["norm_eps"]
    h = _rms_norm(x, lp["attn_norm"], eps)
    q = (h @ lp["wq"]).reshape(S, nh, dn + dr)
    kv = h @ lp["wkv_a"]
    ckv = _rms_norm(kv[:, :dc], lp["kv_norm"], eps)
    k_rope = _rope_pairs(kv[:, dc:], f["rope_theta"])
    q_rope = _rope_pairs(q[..., dn:], f["rope_theta"])
    kvb = (ckv @ lp["wkv_b"]).reshape(S, nh, dn + dv)
    scores = (jnp.einsum("qhd,khd->hqk", q[..., :dn], kvb[..., :dn])
              + jnp.einsum("qhd,kd->hqk", q_rope, k_rope)) \
        / jnp.sqrt(jnp.float32(dn + dr))
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf),
                           axis=-1)
    o = jnp.einsum("hqk,khd->qhd", probs, kvb[..., dn:])
    o = o * jax.nn.sigmoid(h @ lp["wg"])[..., None]
    return x + o.reshape(S, nh * dv) @ lp["wo"], None


def _mixer(x, stack, i, kind, f):
    """(the stream after the mixer, a linear mixer's state S after the
    last token [heads, d, d]; None for a latent one)."""
    lp = jax.tree.map(lambda a: _f32(a[i]), stack)
    return (_linear_mixer if kind == "kda" else _latent_mixer)(x, lp, f)


_mixer_jit = jax.jit(_mixer, static_argnums=(3, 4))


def _swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def _dense_mlp(x, stack, i, f):
    lp = jax.tree.map(lambda a: _f32(a[i]), stack)
    h = _rms_norm(x, lp["mlp_norm"], f["norm_eps"])
    return x + _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])


_dense_mlp_jit = jax.jit(_dense_mlp, static_argnums=(3,))


def route(scores, bias, f):
    """Sigmoid scores [S, E] -> (chosen [S, k], weights [S, k]): the
    group-limited choice by score + bias, the weights without it."""
    S, E = scores.shape
    G, keep = f.get("moe_n_group", 1), f.get("moe_topk_group", 1)
    choose = scores + bias
    if G > 1:
        grouped = choose.reshape(S, G, E // G)
        best2, _ = jax.lax.top_k(grouped, 2)
        _, kept = jax.lax.top_k(jnp.sum(best2, axis=-1), keep)
        stands = jnp.any(kept[:, :, None] == jnp.arange(G)[None, None],
                         axis=1)                             # [S, G]
        choose = jnp.where(stands[:, :, None], grouped,
                           -jnp.inf).reshape(S, E)
    _, chosen = jax.lax.top_k(choose, f["moe_top_k"])
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) \
        * f["moe_routed_scale"]


def _router_and_shared(x, small, f):
    lp = jax.tree.map(_f32, small)
    h = _rms_norm(x, lp["mlp_norm"], f["norm_eps"])
    chosen, w = route(jax.nn.sigmoid(h @ lp["moe_gate_w"]),
                      lp["moe_gate_bias"], f)
    shared = _swiglu(h, lp["shared_gate"], lp["shared_up"],
                     lp["shared_down"])
    return x + shared, h, chosen, w


_router_jit = jax.jit(_router_and_shared, static_argnums=(2,))


@jax.jit
def _held_experts(h, local, w, e_gate, e_up, e_down):
    """sum_j w_j E_j(h) over the chosen experts that are held, for a
    block of positions: ``local`` [B, k] is a chosen expert's index
    among the held ones, -1 where it is held elsewhere."""
    at = jnp.maximum(local, 0)
    wg, wu, wd = (_f32(a[at]) for a in (e_gate, e_up, e_down))
    g = jnp.einsum("bh,bkhf->bkf", h, wg)
    u = jnp.einsum("bh,bkhf->bkf", h, wu)
    y = jnp.einsum("bkf,bkfh->bkh", jax.nn.silu(g) * u, wd)
    return jnp.sum(y * jnp.where(local >= 0, w, 0.0)[..., None], axis=1)


def _expert_mlp(x, stack, i, f):
    experts = ("e_gate", "e_up", "e_down")
    small = {k: v[i] for k, v in stack.items() if k not in experts}
    x, h, chosen, w = _router_jit(x, small, _Frozen(f))
    held = stack["e_gate"].shape[1]
    local = chosen - f.get("moe_experts_first", 0)
    local = jnp.where((local >= 0) & (local < held), local, -1)
    S = x.shape[0]
    pad = (-S) % EXPERT_BLOCK
    hp, wp = (jnp.pad(a, ((0, pad), (0, 0))) for a in (h, w))
    lp_ = jnp.pad(local, ((0, pad), (0, 0)), constant_values=-1)
    stored = [stack[k][i] for k in experts]
    out = [_held_experts(hp[b:b + EXPERT_BLOCK], lp_[b:b + EXPERT_BLOCK],
                         wp[b:b + EXPERT_BLOCK], *stored)
           for b in range(0, S + pad, EXPERT_BLOCK)]
    return x + jnp.concatenate(out)[:S]


class _Frozen(dict):
    """``fields`` as a static argument of a jitted function."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


@jax.jit
def _head(x, final_w, lm_head, eps):
    return _rms_norm(x, _f32(final_w), eps) @ _f32(lm_head)


def _layers(params, fields, ids, mixers=None):
    """The residual stream after the last layer, and the linear mixers'
    final states in layer order; ``mixers``: stop after that many
    mixers (the MLP behind the last of them is not run)."""
    f = _Frozen(fields)
    lead = fields.get("moe_first_dense_layers", 0)
    kinds = layer_kinds(fields)[:mixers]
    x = _f32(params["embed"][jnp.asarray(ids, jnp.int32)])
    seen = {"kda": 0, "mla": 0}
    states = []
    for i, kind in enumerate(kinds):
        x, state = _mixer_jit(x, params[kind + "_layers"], seen[kind],
                              kind, f)
        seen[kind] += 1
        if kind == "kda":
            states.append(state)
        if i + 1 == mixers:
            break
        x = _dense_mlp_jit(x, params["lead_layers"], i, f) if i < lead \
            else _expert_mlp(x, params["layers"], i - lead, f)
    return x, states


def hidden(params, fields, ids):
    """The residual stream after the last layer."""
    return _layers(params, fields, ids)[0]


def leading_states(params, fields, ids):
    """[n, heads, d, d] float32: the recurrent state S after the last
    token of ``ids`` in the linear layers AHEAD OF EVERY ROUTED EXPERT:
    the leading dense layers' mixers and the first expert layer's (its
    mixer runs before its experts). What these states hold depends on
    no expert choice, so a comparison of them is free of the swap a
    hard top-k makes against a float32 reference; they are the
    recurrence's own output, read before any other layer dilutes it."""
    check_supported(fields)
    kinds = layer_kinds(fields)
    n = min(fields.get("moe_first_dense_layers", 0) + 1, len(kinds))
    with jax.default_matmul_precision("highest"):
        return jnp.stack(_layers(params, fields, ids, mixers=n)[1])


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
