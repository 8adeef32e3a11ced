"""Plain reference: the DeepSeek-V3 block as ``JoyAI-LLM-Flash``
publishes it (https://huggingface.co/jdopensource/JoyAI-LLM-Flash,
``config.json``; the equations are DeepSeek-V3's, arXiv:2412.19437
section 2.1), in straightforward ``jax.numpy`` float32: no kernel, no
cache, no batching, no scan, and nothing imported from the program.

One layer on ``h`` [S, 2048], RMSNorm eps 1e-6, no biases:

1. latent attention, in the NON-absorbed form: queries through the
   ``q_lora_rank`` bottleneck with its norm, 32 heads of 128 + 64; keys
   and values expanded PER HEAD from the normed 512-wide latent
   (``wkv_b``: 128 + 128 a head); rope at ``rope_theta`` on the query's
   64 and on the ONE 64-wide key part all heads share, rotating the
   pairs of lanes (2i, 2i + 1) in place (``rope_interleave``); scores
   over sqrt(192), causal softmax, values, ``wo``;
2. layer 0 (``first_k_dense_replace`` 1): a dense SwiGLU of width 7168;
3. layers 1..: sigmoid scores, the top 8 of 256 by score PLUS the
   selection bias, weights the scores WITHOUT it, over their sum
   (+ 1e-20) and times 2.5; the chosen experts' SwiGLUs (width 768) so
   weighted, plus the shared expert's;
4. the final RMSNorm and the untied head.

Departures from the published description, each because the served cut
has no such part or the mathematics is the same:

* ``n_group`` = ``topk_group`` = 1: the group-limited choice keeps
  every group, so the top 8 are taken over all 256 experts at once;
* ``rope_scaling`` is null: no YaRN factor, no mscale on the softmax
  scale;
* the multi-token-prediction module is left out (the configuration's
  ``reduced`` says so): it adds no term to these logits;
* the experts a token did not choose are not computed: here by
  gathering the chosen experts' weights a block of positions at a time,
  which is the sum over the chosen experts written as a batched matmul.

Every call runs under ``jax.default_matmul_precision("highest")``.
Parameters are read in the program's layout (``embed``, ``lm_head``,
``final_norm``; ``lead_layers`` the leading dense stack and ``layers``
the expert stack, leaves with a leading layer axis) and cast up a layer
at a time, an expert stack only at the experts a block of positions
chose (a whole layer's 256 experts are 4.96 GB in float32).
"""

import jax
import jax.numpy as jnp

SUPPORTED = dict(attention="mla", norm="rmsnorm", activation="swiglu",
                 positional="rope", tie_embeddings=False,
                 moe_scoring="sigmoid", moe_selection_bias=True,
                 moe_norm_topk=True, rope_interleave=True)
# positions whose chosen experts are gathered and cast up together: 128
# positions x 8 experts x 4.7M weights x 4 B = 19 GB would not fit; 16
# positions are 2.4 GB
EXPERT_BLOCK = 16


def check_supported(fields):
    """This reference is the DeepSeek-V3 block as JoyAI-LLM-Flash sets
    it; refuse a configuration it does not describe rather than compare
    against the wrong mathematics."""
    for key, want in SUPPORTED.items():
        if fields.get(key) != want:
            raise ValueError(
                f"benchmark/reference_joyai.py implements the DeepSeek-V3 "
                f"block ({SUPPORTED}); configuration has {key}="
                f"{fields.get(key)!r}. Add a reference for it.")
    if not fields.get("moe_num_experts") \
            or not fields.get("moe_shared_experts"):
        raise ValueError("benchmark/reference_joyai.py: routed experts and "
                         "a shared expert are part of the block")


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * w


def _rope_pairs(x, theta):
    """x [S, ..., D]: lanes (2i, 2i + 1) rotated in place by position x
    theta ** (-2i / D)."""
    S, D = x.shape[0], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs    # [S, D/2]
    ang = ang.reshape(S, *([1] * (x.ndim - 2)), D // 2)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                     x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], axis=-1)
    return out.reshape(x.shape)


def _attention(x, lp, f):
    S = x.shape[0]
    nh, dc = f["num_heads"], f["kv_lora_rank"]
    dn, dr, dv = f["qk_nope_head_dim"], f["qk_rope_head_dim"], \
        f["v_head_dim"]
    eps = f["norm_eps"]
    h = _rms_norm(x, lp["attn_norm"], eps)
    q = (_rms_norm(h @ lp["wq_a"], lp["q_norm"], eps)
         @ lp["wq_b"]).reshape(S, nh, dn + dr)
    kv = h @ lp["wkv_a"]
    ckv = _rms_norm(kv[:, :dc], lp["kv_norm"], eps)
    k_rope = _rope_pairs(kv[:, dc:], f["rope_theta"])          # [S, dr]
    q_rope = _rope_pairs(q[..., dn:], f["rope_theta"])         # [S, nh, dr]
    kvb = (ckv @ lp["wkv_b"]).reshape(S, nh, dn + dv)          # per head
    scores = (jnp.einsum("qhd,khd->hqk", q[..., :dn], kvb[..., :dn])
              + jnp.einsum("qhd,kd->hqk", q_rope, k_rope)) \
        / jnp.sqrt(jnp.float32(dn + dr))
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf),
                           axis=-1)
    o = jnp.einsum("hqk,khd->qhd", probs, kvb[..., dn:]).reshape(S, nh * dv)
    return x + o @ lp["wo"]


def _swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def _dense_layer(x, layers, i, f):
    lp = jax.tree.map(lambda a: _f32(a[i]), layers)
    x = _attention(x, lp, f)
    h = _rms_norm(x, lp["mlp_norm"], f["norm_eps"])
    return x + _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])


_dense_layer_jit = jax.jit(_dense_layer, static_argnums=(3,))


def _expert_attention_and_router(x, small, f):
    """The layer up to the expert choice: (x after attention, the normed
    input of the MLP, the chosen experts [S, k], their weights)."""
    lp = jax.tree.map(_f32, small)
    x = _attention(x, lp, f)
    h = _rms_norm(x, lp["mlp_norm"], f["norm_eps"])
    s = jax.nn.sigmoid(h @ lp["moe_gate_w"])                   # [S, E]
    _, chosen = jax.lax.top_k(s + lp["moe_gate_bias"], f["moe_top_k"])
    w = jnp.take_along_axis(s, chosen, axis=-1)                # no bias
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) \
        * f["moe_routed_scale"]
    shared = _swiglu(h, lp["shared_gate"], lp["shared_up"],
                     lp["shared_down"])
    return x + shared, h, chosen, w


_router_jit = jax.jit(_expert_attention_and_router, static_argnums=(2,))


@jax.jit
def _chosen_experts(h, chosen, w, e_gate, e_up, e_down):
    """sum_j w_j E_chosen_j(h) for a block of positions: h [B, H],
    chosen / w [B, k]; the layer's expert weights [E, ...] as stored,
    cast up at the chosen experts only."""
    wg, wu, wd = (_f32(a[chosen]) for a in (e_gate, e_up, e_down))
    g = jnp.einsum("bh,bkhf->bkf", h, wg)
    u = jnp.einsum("bh,bkhf->bkf", h, wu)
    y = jnp.einsum("bkf,bkfh->bkh", jax.nn.silu(g) * u, wd)
    return jnp.sum(y * w[..., None], axis=1)


def _expert_layer(x, layers, i, f):
    experts = ("e_gate", "e_up", "e_down")
    small = {k: v[i] for k, v in layers.items() if k not in experts}
    x, h, chosen, w = _router_jit(x, small, _Frozen(f))
    S = x.shape[0]
    pad = (-S) % EXPERT_BLOCK
    hp, cp, wp = (jnp.pad(a, ((0, pad), (0, 0))) for a in (h, chosen, w))
    stored = [layers[k][i] for k in experts]
    out = [_chosen_experts(hp[b:b + EXPERT_BLOCK], cp[b:b + EXPERT_BLOCK],
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


def logits(params, fields, ids):
    """[S, vocab] float32 logits of one sequence ``ids`` [S]."""
    check_supported(fields)
    f = _Frozen(fields)
    lead = fields.get("moe_first_dense_layers", 0)
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][jnp.asarray(ids, jnp.int32)])
        for i in range(lead):
            x = _dense_layer_jit(x, params["lead_layers"], i, f)
        for i in range(fields["num_layers"] - lead):
            x = _expert_layer(x, params["layers"], i, f)
        return _head(x, params["final_norm"], params["lm_head"],
                     fields["norm_eps"])


def next_token_loss(params, fields, ids):
    """Mean next-token cross-entropy of one sequence, float32."""
    lg = logits(params, fields, ids)[:-1]
    tgt = jnp.asarray(ids, jnp.int32)[1:]
    logz = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, tgt[:, None], axis=-1)[:, 0]
    return float(jnp.mean(logz - picked))
