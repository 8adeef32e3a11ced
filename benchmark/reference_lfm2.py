"""Plain reference: the ``lfm2_moe`` block as ``LFM2-8B-A1B`` publishes it
(https://huggingface.co/LiquidAI/LFM2-8B-A1B, ``config.json``; what the
config's keys do not state is from the model's public modelling code,
``transformers/models/lfm2/modeling_lfm2.py`` and, for the sparse block,
``transformers/models/lfm2_moe/modeling_lfm2_moe.py``, and is listed under
``assumed`` in ``configs/lfm2-8b-a1b.json``), in straightforward
``jax.numpy`` float32: no kernel, no cache, no chunking, no batching, no
scan over layers, and nothing imported from the program.

``x = embed[ids]`` (no multiplier, no norm at the input). Layer i of kind
``layer_types[i]``, RMSNorm eps 1e-5 with a learned weight, no bias
anywhere:

1. ``h = RMSNorm_operator(x)``;
   * ``conv``: ``p = h W_in`` [S, 3 H]; ``B, C, u = p[:, :H], p[:, H:2H],
     p[:, 2H:]`` (``BCx.chunk(3)``); ``g = B * u``; ``c_t = w[0] g_{t-2}
     + w[1] g_{t-1} + w[2] g_t`` a channel (depthwise, causal, zeros
     before the sequence's start; the source's ``Conv1d`` with padding
     ``taps - 1`` cut to the sequence, its weight ``[H, 1, taps]`` here
     ``[taps, H]``); ``m = (C * c) W_out``. No activation in the mixer.
     What a cache holds of it is the last ``taps - 1`` values of ``g``
     (the source caches ``taps`` columns of them; the oldest only feeds
     the step that drops it);
   * ``full_attention``: ``q = RMSNorm_head(h Wq)`` [32, 64], ``k =
     RMSNorm_head(h Wk)`` [8, 64], ``v = h Wv``; q and k rotated
     (``rotate_half`` over all 64, theta 1e6) AFTER the head norms;
     ``m = softmax(q k^T / 8, causal) v Wo``, four query heads a
     key/value head;
2. ``x = x + m``; ``f = RMSNorm_ffn(x)``;
3. a layer below ``num_dense_layers``: ``x = x + (silu(f W1) * (f W3))
   W2`` at 7,168; else ``s = sigmoid(f Wr)`` over 32 experts in float32,
   the 4 largest of ``s + expert_bias`` chosen (the bias chooses and does
   NOT weigh), ``w = s[pick] / (sum(s[pick]) + 1e-6) *
   routed_scaling_factor`` (1), ``x = x + sum_j w_j E_{pick_j}(f)``,
   experts SwiGLU of 1,792, no shared expert;
4. ``logits = RMSNorm_embedding(x) embed^T`` (the final norm is the
   source's ``embedding_norm``; the head is tied).

Departures from the published code, each because the mathematics is the
same:

* the taps are a shifted sum (``_short_conv``), not a ``Conv1d``;
* EVERY expert is computed on every token and weighted by the picks'
  mask (a token's unchosen experts weigh 0), an expert at a time, each
  cast up once for the whole sequence: no program of this reference
  depends on what the router chose, so none compiles anew a seed;
* the layers run one at a time, each leaf cast up where it is read
  (float32 weights of the served cut are 18.7 GB);
* a sequence is padded with zeros to a whole multiple of ``PAD`` tokens
  and what is returned cut back to its length: every layer is causal,
  so no position sees the padding behind it, and the cell's two lengths
  (512 and 1,023) are ONE program a layer, not two (a float32 matmul
  under ``highest`` is 5-6 s of compile each).

Every call runs under ``jax.default_matmul_precision("highest")``.
Parameters are read in the program's layout (``embed``, ``final_norm``;
``conv_layers`` / ``full_layers`` the mixers of a kind in layer order;
``lead_layers`` / ``layers`` the norm and MLP of the leading dense and of
the expert layers, leaves with a leading layer axis).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

SUPPORTED = dict(attention="mha", norm="rmsnorm", activation="swiglu",
                 positional="rope", tie_embeddings=True,
                 moe_scoring="sigmoid", moe_selection_bias=True,
                 moe_norm_topk=True, moe_norm_topk_eps=1e-6, qk_norm=True,
                 conv_bias=False)
KINDS = {"conv": "conv", "full_attention": "full"}
EXPERTS = ("e_gate", "e_up", "e_down")
PAD = 1024


def check_supported(fields):
    """This reference is the lfm2_moe block as LFM2-8B-A1B sets it;
    refuse a configuration it does not describe rather than compare
    against the wrong mathematics."""
    for key, want in SUPPORTED.items():
        if fields.get(key) != want:
            raise ValueError(
                f"benchmark/reference_lfm2.py implements the lfm2_moe "
                f"block ({SUPPORTED}); configuration has {key}="
                f"{fields.get(key)!r}. Add a reference for it.")
    types = fields.get("layer_types")
    if not types or len(types) != fields["num_layers"] \
            or set(types) - set(KINDS):
        raise ValueError("benchmark/reference_lfm2.py: layer_types names "
                         f"a kind of {sorted(KINDS)} a layer")
    if fields.get("conv_taps", 0) < 2 or fields.get("moe_shared_experts") \
            or fields.get("moe_n_group", 1) != 1 \
            or fields.get("moe_experts_held") \
            or fields.get("moe_routed_scale", 1.0) != 1.0:
        raise ValueError("benchmark/reference_lfm2.py: conv_taps >= 2, "
                         "every routed expert held, no shared expert, no "
                         "group limit, routed_scaling_factor 1")


def layer_kinds(fields):
    return [KINDS[t] for t in fields["layer_types"]]


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * w


def _rope_halves(x, theta):
    """x [S, heads, D]: lanes (i, i + D/2) rotated by position x
    theta ** (-2i / D) (``rotate_half``)."""
    S, D = x.shape[0], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], axis=-1)


def _gated_inputs(x, lp, f):
    """(``g = B * u``, ``C``) of a conv layer, each [S, H]."""
    H = f["hidden_size"]
    p = _rms_norm(x, lp["attn_norm"], f["norm_eps"]) @ lp["w_in"]
    return p[:, :H] * p[:, 2 * H:], p[:, H:2 * H]


def _short_conv(x, lp, f):
    """What the doubly gated short convolution adds to x."""
    g, c = _gated_inputs(x, lp, f)
    taps = lp["conv"]                              # [K, H]; K - 1: itself
    K, S = taps.shape[0], g.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, g.shape[1])), g])
    y = sum(taps[j] * padded[j:j + S] for j in range(K))
    return (c * y) @ lp["w_out"]


def _qkv(x, lp, f):
    """(q [S, nh, hd], k, v [S, nkv, hd]) of an attention layer: the
    keys and values as a cache would hold them."""
    S = x.shape[0]
    nh, nkv = f["num_heads"], f["num_kv_heads"]
    hd, eps = f["hidden_size"] // nh, f["norm_eps"]
    a = _rms_norm(x, lp["attn_norm"], eps)
    q = _rms_norm((a @ lp["wq"]).reshape(S, nh, hd), lp["q_norm"], eps)
    k = _rms_norm((a @ lp["wk"]).reshape(S, nkv, hd), lp["k_norm"], eps)
    v = (a @ lp["wv"]).reshape(S, nkv, hd)
    q, k = (_rope_halves(t, f["rope_theta"]) for t in (q, k))
    return q, k, v


def _attention(x, lp, f):
    """What full causal grouped-query attention adds to x."""
    S = x.shape[0]
    nh, nkv = f["num_heads"], f["num_kv_heads"]
    hd = f["hidden_size"] // nh
    q, k, v = _qkv(x, lp, f)
    q = q.reshape(S, nkv, nh // nkv, hd)          # a kv head's query heads
    s = jnp.einsum("qkgd,ckd->kgqc", q, k) / jnp.sqrt(jnp.float32(hd))
    seen = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    o = jnp.einsum("kgqc,ckd->qkgd", p, v).reshape(S, nh * hd)
    return o @ lp["wo"]


def _swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def _mixer(x, mixer, f, kind):
    mixer = jax.tree.map(_f32, mixer)
    return x + (_short_conv if kind == "conv" else _attention)(x, mixer, f)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _dense_layer(x, mixer, mlp, f, kind):
    x = _mixer(x, mixer, f, kind)
    mlp = jax.tree.map(_f32, mlp)
    m = _rms_norm(x, mlp["mlp_norm"], f["norm_eps"])
    return x + _swiglu(m, mlp["w_gate"], mlp["w_up"], mlp["w_down"])


def route(m, gate_w, bias, f):
    """The router on the experts' normed input m [S, H]: (the chosen
    experts [S, k], their weights [S, k])."""
    s = jax.nn.sigmoid(m @ gate_w)                             # [S, E]
    _, chosen = jax.lax.top_k(s + bias, f["moe_top_k"])
    w = jnp.take_along_axis(s, chosen, axis=-1)                # no bias
    return chosen, w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _expert_layer(x, mixer, small, experts, f, kind):
    """The layer with EVERY expert computed on every token, an expert at
    a time, and weighted by the picks' mask. ``small``: the layer's
    leaves but the routed experts'; ``experts``: (e_gate, e_up, e_down)
    [E, ...] as stored, each cast up an expert at a time."""
    x = _mixer(x, mixer, f, kind)
    small = jax.tree.map(_f32, small)
    m = _rms_norm(x, small["mlp_norm"], f["norm_eps"])
    chosen, w = route(m, small["moe_gate_w"], small["moe_gate_bias"], f)
    E = experts[0].shape[0]
    # [S, E]: a token's weight on each expert, 0 on the ones not chosen
    weight = jnp.sum(jax.nn.one_hot(chosen, E) * w[..., None], axis=1)

    def add(e, y):
        wg, wu, wd = (_f32(a[e]) for a in experts)
        return y + _swiglu(m, wg, wu, wd) * weight[:, e][:, None]

    return x + jax.lax.fori_loop(0, E, add, jnp.zeros_like(m))


class _Frozen(dict):
    """``fields`` as a static argument of a jitted function."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def _layers(params, fields):
    """Every layer in order: (kind, its mixer's leaves, its MLP's
    leaves, whether the MLP is the expert layer)."""
    lead = fields.get("moe_first_dense_layers", 0)
    seen = {"conv": 0, "full": 0}
    for i, kind in enumerate(layer_kinds(fields)):
        mixer = jax.tree.map(lambda a, at=seen[kind]: a[at],
                             params[kind + "_layers"])
        seen[kind] += 1
        stack, at = (params["lead_layers"], i) if i < lead \
            else (params["layers"], i - lead)
        yield kind, mixer, jax.tree.map(lambda a, at=at: a[at], stack), \
            i >= lead


def _embed(params, ids):
    """The embedded sequence, padded with zero ids to a whole multiple
    of ``PAD`` tokens."""
    ids = np.asarray(ids, np.int64)
    padded = np.zeros(-(-len(ids) // PAD) * PAD, np.int64)
    padded[:len(ids)] = ids
    return _f32(params["embed"][jnp.asarray(padded, jnp.int32)])


def _layer(x, kind, mixer, mlp, routed, f):
    if not routed:
        return _dense_layer(x, mixer, mlp, f, kind)
    small = {k: v for k, v in mlp.items() if k not in EXPERTS}
    return _expert_layer(x, mixer, small, tuple(mlp[k] for k in EXPERTS),
                         f, kind)


def _stream(params, fields, ids):
    """The stream behind the last layer at every PADDED position."""
    check_supported(fields)
    f = _Frozen(fields)
    with jax.default_matmul_precision("highest"):
        x = _embed(params, ids)
        for kind, mixer, mlp, routed in _layers(params, fields):
            x = _layer(x, kind, mixer, mlp, routed, f)
        return x


def hidden(params, fields, ids):
    """[S, hidden] float32: the stream behind the last layer, before the
    final norm."""
    return _stream(params, fields, ids)[:len(ids)]


@jax.jit
def _head(x, final_w, embed, eps):
    return _rms_norm(x, _f32(final_w), eps) @ _f32(embed).T


def logits(params, fields, ids):
    """[S, vocab] float32 logits of one sequence ``ids`` [S]."""
    x = _stream(params, fields, ids)
    with jax.default_matmul_precision("highest"):
        return _head(x, params["final_norm"], params["embed"],
                     fields["norm_eps"])[:len(ids)]


def next_token_loss(params, fields, ids):
    """Mean next-token cross-entropy of one sequence, float32."""
    lg = logits(params, fields, ids)[:-1]
    tgt = jnp.asarray(np.asarray(ids)[1:], jnp.int32)
    picked = jnp.take_along_axis(lg, tgt[:, None], axis=-1)[:, 0]
    return float(jnp.mean(jax.nn.logsumexp(lg, axis=-1) - picked))


def _leading(params, fields, ids, layers, kind, read):
    """``read(x, mixer, f)`` of the first ``layers`` layers of ``kind``
    (None: every one), the stream carried to each."""
    check_supported(fields)
    f = _Frozen(fields)
    kinds = layer_kinds(fields)
    want = kinds.count(kind) if layers is None else layers
    out = []
    with jax.default_matmul_precision("highest"):
        x = _embed(params, ids)
        for k, mixer, mlp, routed in _layers(params, fields):
            if k == kind:
                out.append(read(x, jax.tree.map(_f32, mixer), f))
                if len(out) == want:
                    break
            x = _layer(x, k, mixer, mlp, routed, f)
    return out


def leading_states(params, fields, ids, layers=None):
    """What a cache holds of the first ``layers`` conv layers (None:
    every one) after ``ids``: ``[layers, taps - 1, H]`` float32, a
    layer's last ``taps - 1`` gated inputs ``g = B * u``, oldest first
    (zeros ahead of a sequence shorter than that). Layers 0 and 1 of the
    published model stand ahead of every routed expert."""
    keep = fields["conv_taps"] - 1

    def last_inputs(x, mixer, f):
        g, _ = _gated_inputs(x, mixer, f)
        g = g[:len(ids)]
        return jnp.concatenate([jnp.zeros((keep, g.shape[1])), g])[-keep:]

    return jnp.stack(_leading(params, fields, ids, layers, "conv",
                              last_inputs))


def leading_kv(params, fields, ids, layers=None):
    """The keys and values of the first ``layers`` attention layers
    (None: every one) as a cache would hold them (k normed a head, then
    rotated): ``(k, v)`` each [layers, S, kv_heads * head_dim] float32."""
    def kv(x, mixer, f):
        _, k, v = _qkv(x, mixer, f)
        return (k.reshape(k.shape[0], -1)[:len(ids)],
                v.reshape(v.shape[0], -1)[:len(ids)])

    pairs = _leading(params, fields, ids, layers, "full", kv)
    return (jnp.stack([k for k, _ in pairs]),
            jnp.stack([v for _, v in pairs]))
