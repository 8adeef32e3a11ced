"""Plain reference: the ``afmoe`` block as ``Trinity-Mini`` publishes it
(https://huggingface.co/arcee-ai/Trinity-Mini, ``config.json``; what the
config's keys do not state is from the model's public modelling code,
``transformers/models/afmoe/modeling_afmoe.py``, and is listed under
``assumed`` in ``configs/trinity-mini.json``), in straightforward
``jax.numpy`` float32: no kernel, no cache, no ring, no chunking, no
batching, no scan over layers, and nothing imported from the program.

``h0 = embed(ids) * sqrt(hidden_size)`` (``mup_enabled``). Layer l of
kind ``layer_types[l]``, RMSNorm eps 1e-5 throughout, no biases:

1. ``a = RMSNorm_in(h)``; ``q = a Wq`` as [32, 128], ``k = a Wk`` and
   ``v = a Wv`` as [4, 128], ``g = a Wg`` (4,096 wide); q and k each
   RMS-normed over the 128 with a learned weight; ON A
   ``sliding_attention`` LAYER ONLY q and k rotated (theta 10,000, the
   whole head, halves (i, i + 64)); a ``full_attention`` layer has no
   position signal;
2. scores ``q k^T / sqrt(128)``, each kv head serving 8 query heads; the
   token at position p sees positions j <= p and, on a sliding layer,
   j > p - 2048; softmax in float32; ``o = (softmax v) * sigmoid(g)``
   element-wise; ``h = h + RMSNorm_post_attn(o Wo)``;
3. ``m = RMSNorm_pre_mlp(h)``; a layer below ``num_dense_layers``: ``y =
   (silu(m Wgate) * (m Wup)) Wdown`` at 6,144; else ``s = sigmoid(m
   Wr)`` over 128 experts, the 8 largest of ``s + bias`` chosen, their
   ``s`` (without the bias) over their sum (+ 1e-20, ``route_norm``) and
   times ``route_scale`` 2.826, ``y = sum_k w_k E_k(m) + E_shared(m)``,
   experts SwiGLU of 1,024; ``h = h + RMSNorm_post_mlp(y)``;
4. ``logits = RMSNorm_final(h) W_head`` (untied).

Departures from the published description, each because the mathematics
is the same or the served cut has no such part:

* ``n_group`` = ``topk_group`` = 1: no group limit, the top 8 are taken
  over all 128 experts at once;
* ``rope_scaling`` is null: plain rope;
* the sum over a token's chosen experts is made an EXPERT at a time:
  the rows that chose expert e are gathered (at most ``capacity`` of
  them, the fullest expert's count, found on the host), its SwiGLU
  computed on them and added to their tokens with their weights: an
  expert's weights are cast up once for the whole sequence (8,703
  positions x 8 gathered experts would be 1.7 TB in float32), and no
  token's unchosen expert is computed;
* attention runs a block of queries at a time against every key, and the
  head a block of rows at a time, so that 8,703 positions fit: ``logits``
  returns an array-like that makes only the rows it is sliced for (a
  whole [8,703, 200,192] float32 array is 7 GB).

Every call runs under ``jax.default_matmul_precision("highest")``.
Parameters are read in the program's layout (``embed``, ``lm_head``,
``final_norm``; ``window_layers`` / ``full_layers`` the mixers of a kind
in layer order; ``lead_layers`` / ``layers`` the norms and MLP of the
leading dense and of the expert layers, leaves with a leading layer
axis) and cast up a layer, and an expert, at a time.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

SUPPORTED = dict(attention="mha", norm="rmsnorm", activation="swiglu",
                 positional="rope", tie_embeddings=False,
                 moe_scoring="sigmoid", moe_selection_bias=True,
                 moe_norm_topk=True, qk_norm=True, rope_sliding_only=True,
                 attn_gate="elementwise", norm_scheme="sandwich")
KINDS = {"sliding_attention": "window", "full_attention": "full"}
# queries scored together against every key: [32, 512, 8703] float32 is
# 0.57 GB; rows of the head made together: [512, 200192] is 0.41 GB
QUERY_BLOCK = 512
HEAD_BLOCK = 512


def check_supported(fields):
    """This reference is the afmoe block as Trinity-Mini sets it; refuse
    a configuration it does not describe rather than compare against the
    wrong mathematics."""
    for key, want in SUPPORTED.items():
        if fields.get(key) != want:
            raise ValueError(
                f"benchmark/reference_trinity.py implements the afmoe "
                f"block ({SUPPORTED}); configuration has {key}="
                f"{fields.get(key)!r}. Add a reference for it.")
    types = fields.get("layer_types")
    if not types or len(types) != fields["num_layers"] \
            or set(types) - set(KINDS):
        raise ValueError("benchmark/reference_trinity.py: layer_types "
                         f"names a kind of {sorted(KINDS)} a layer")
    if not fields.get("moe_num_experts") \
            or not fields.get("moe_shared_experts") \
            or fields.get("moe_n_group", 1) != 1 \
            or fields.get("moe_experts_held"):
        raise ValueError("benchmark/reference_trinity.py: every routed "
                         "expert held, one shared expert, no group limit")


def layer_kinds(fields):
    return [KINDS[t] for t in fields["layer_types"]]


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * w


def _rope_halves(x, theta):
    """x [S, heads, D]: lanes (i, i + D/2) rotated by position x
    theta ** (-2i / D)."""
    S, D = x.shape[0], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], axis=-1)


def _head_dim(f):
    return f.get("head_dim_override") or f["hidden_size"] // f["num_heads"]


def _qkv(x, lp, f, kind):
    """(normed input, q [S, nh, hd], k, v [S, nkv, hd]) of a layer: the
    keys and values as a cache would hold them."""
    S = x.shape[0]
    nh, nkv, hd = f["num_heads"], f["num_kv_heads"], _head_dim(f)
    eps = f["norm_eps"]
    a = _rms_norm(x, lp["attn_norm"], eps)
    q = _rms_norm((a @ lp["wq"]).reshape(S, nh, hd), lp["q_norm"], eps)
    k = _rms_norm((a @ lp["wk"]).reshape(S, nkv, hd), lp["k_norm"], eps)
    v = (a @ lp["wv"]).reshape(S, nkv, hd)
    if kind == "window":
        q, k = (_rope_halves(t, f["rope_theta"]) for t in (q, k))
    return a, q, k, v


def _attention(x, lp, f, kind):
    """h + RMSNorm_post_attn(((softmax(q k^T / sqrt(d)) v) * sigmoid(g))
    Wo), a block of queries at a time against every key."""
    S = x.shape[0]
    nh, nkv, hd = f["num_heads"], f["num_kv_heads"], _head_dim(f)
    a, q, k, v = _qkv(x, lp, f, kind)
    q = q.reshape(S, nkv, nh // nkv, hd)      # a kv head's query heads
    keys = jnp.arange(S)
    out = []
    for start in range(0, S, QUERY_BLOCK):
        at = jnp.arange(start, min(start + QUERY_BLOCK, S))
        s = jnp.einsum("qkgd,ckd->kgqc", q[at], k) / jnp.sqrt(
            jnp.float32(hd))
        seen = keys[None, :] <= at[:, None]
        if kind == "window":
            seen = seen & (keys[None, :] > at[:, None] - f["attn_window"])
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("kgqc,ckd->qkgd", p, v))
    o = jnp.concatenate(out).reshape(S, nh * hd)
    o = o * jax.nn.sigmoid(a @ lp["wg"])
    return x + _rms_norm(o @ lp["wo"], lp["attn_post_norm"], f["norm_eps"])


def _swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


@functools.partial(jax.jit, static_argnums=(3, 4))
def _dense_layer(x, mixer, mlp, f, kind):
    mixer, mlp = jax.tree.map(_f32, (mixer, mlp))
    x = _attention(x, mixer, f, kind)
    m = _rms_norm(x, mlp["mlp_norm"], f["norm_eps"])
    y = _swiglu(m, mlp["w_gate"], mlp["w_up"], mlp["w_down"])
    return x + _rms_norm(y, mlp["mlp_post_norm"], f["norm_eps"])


@functools.partial(jax.jit, static_argnums=(3, 4))
def _attention_and_router(x, mixer, small, f, kind):
    """The layer up to the expert choice: (x after attention, the normed
    input of the MLP, the chosen experts [S, k], their weights).
    ``small``: the layer's leaves but the routed experts'."""
    mixer, small = jax.tree.map(_f32, (mixer, small))
    x = _attention(x, mixer, f, kind)
    m = _rms_norm(x, small["mlp_norm"], f["norm_eps"])
    s = jax.nn.sigmoid(m @ small["moe_gate_w"])                # [S, E]
    _, chosen = jax.lax.top_k(s + small["moe_gate_bias"], f["moe_top_k"])
    w = jnp.take_along_axis(s, chosen, axis=-1)                # no bias
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) \
        * f["moe_routed_scale"]
    return x, m, chosen, w


@functools.partial(jax.jit, static_argnums=(6, 7))
def _experts_and_post_norm(x, m, chosen, w, small, experts, f, capacity):
    """``x + RMSNorm_post_mlp(sum_k w_k E_k(m) + E_shared(m))``, an
    expert at a time over the (at most ``capacity``) rows that chose
    it; ``experts``: (e_gate, e_up, e_down) [E, ...] as stored, each
    cast up an expert at a time."""
    small = jax.tree.map(_f32, small)
    S, k = chosen.shape
    order = jnp.argsort(chosen.reshape(-1), stable=True)
    by_expert = chosen.reshape(-1)[order]
    token = (jnp.arange(S * k) // k)[order]
    weight = w.reshape(-1)[order]
    first = jnp.searchsorted(by_expert, jnp.arange(experts[0].shape[0]))

    def add(e, y):
        at = jnp.clip(first[e] + jnp.arange(capacity), 0, S * k - 1)
        mine = (first[e] + jnp.arange(capacity) < S * k) \
            & (by_expert[at] == e)
        rows = jnp.where(mine, token[at], 0)
        wg, wu, wd = (_f32(a[e]) for a in experts)
        out = _swiglu(m[rows], wg, wu, wd) \
            * jnp.where(mine, weight[at], 0.0)[:, None]
        return y.at[rows].add(out)

    y = jax.lax.fori_loop(0, experts[0].shape[0], add, jnp.zeros_like(m))
    y = y + _swiglu(m, small["shared_gate"], small["shared_up"],
                    small["shared_down"])
    return x + _rms_norm(y, small["mlp_post_norm"], f["norm_eps"])


def _expert_layer(x, mixer, small, experts, f, kind):
    x, m, chosen, w = _attention_and_router(x, mixer, small, f, kind)
    fullest = int(np.bincount(np.asarray(chosen).reshape(-1)).max())
    return _experts_and_post_norm(x, m, chosen, w, small, experts, f,
                                  -(-fullest // 128) * 128)


class _Frozen(dict):
    """``fields`` as a static argument of a jitted function."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def _layers(params, fields):
    """Every layer in order: (kind, its mixer's leaves, its MLP's
    leaves, whether the MLP is the expert layer)."""
    lead = fields.get("moe_first_dense_layers", 0)
    seen = {"window": 0, "full": 0}
    for i, kind in enumerate(layer_kinds(fields)):
        mixer = jax.tree.map(lambda a, at=seen[kind]: a[at],
                             params[kind + "_layers"])
        seen[kind] += 1
        stack, at = (params["lead_layers"], i) if i < lead \
            else (params["layers"], i - lead)
        yield kind, mixer, jax.tree.map(lambda a, at=at: a[at], stack), \
            i >= lead


def _embed(params, fields, ids):
    return _f32(params["embed"][jnp.asarray(ids, jnp.int32)]) \
        * jnp.float32(fields["hidden_size"]) ** 0.5


def hidden(params, fields, ids):
    """[S, hidden] float32: the stream behind the last layer, before the
    final norm."""
    check_supported(fields)
    f = _Frozen(fields)
    experts = ("e_gate", "e_up", "e_down")
    with jax.default_matmul_precision("highest"):
        x = _embed(params, fields, ids)
        for kind, mixer, mlp, routed in _layers(params, fields):
            if routed:
                small = {k: v for k, v in mlp.items() if k not in experts}
                x = _expert_layer(x, mixer, small,
                                  tuple(mlp[k] for k in experts), f, kind)
            else:
                x = _dense_layer(x, mixer, mlp, f, kind)
        return x


@jax.jit
def _head(x, final_w, lm_head, eps):
    return _rms_norm(x, _f32(final_w), eps) @ _f32(lm_head)


class Logits:
    """[S, vocab] float32 logits that exist a slice at a time: indexing
    by a row, a slice or an array of rows makes those rows (in blocks of
    ``HEAD_BLOCK``) and no others; ``np.asarray`` makes them all."""

    def __init__(self, x, params, eps):
        self.x, self.params, self.eps = x, params, eps
        self.shape = (x.shape[0], params["lm_head"].shape[1])
        self.dtype = jnp.float32

    def __len__(self):
        return self.shape[0]

    def __getitem__(self, rows):
        x = self.x[rows]
        one = x.ndim == 1
        x = x[None] if one else x
        with jax.default_matmul_precision("highest"):
            out = jnp.concatenate([
                _head(x[at:at + HEAD_BLOCK], self.params["final_norm"],
                      self.params["lm_head"], self.eps)
                for at in range(0, x.shape[0], HEAD_BLOCK)])
        return out[0] if one else out

    def __array__(self, dtype=None, copy=None):
        out = np.asarray(self[:])
        return out if dtype is None else out.astype(dtype)


def logits(params, fields, ids):
    """[S, vocab] float32 logits of one sequence ``ids`` [S], made where
    they are sliced (``Logits``)."""
    return Logits(hidden(params, fields, ids), params, fields["norm_eps"])


def next_token_loss(params, fields, ids):
    """Mean next-token cross-entropy of one sequence, float32, the head
    a block of rows at a time."""
    lg = logits(params, fields, ids)
    tgt = np.asarray(ids)[1:]
    total = 0.0
    for at in range(0, len(tgt), HEAD_BLOCK):
        rows = lg[at:at + HEAD_BLOCK][:len(tgt) - at]
        t = jnp.asarray(tgt[at:at + HEAD_BLOCK], jnp.int32)
        picked = jnp.take_along_axis(rows, t[:, None], axis=-1)[:, 0]
        total += float(jnp.sum(jax.nn.logsumexp(rows, axis=-1) - picked))
    return total / len(tgt)


def leading_kv(params, fields, ids):
    """The keys and values of the layers AHEAD OF EVERY ROUTED EXPERT,
    as a cache would hold them (k normed and, on a sliding layer,
    rotated): ``(k, v)`` each [layers, S, kv_heads * head_dim] float32,
    for the leading dense layers and the first expert layer, whose input
    no expert choice has touched."""
    check_supported(fields)
    f = _Frozen(fields)
    lead = fields.get("moe_first_dense_layers", 0)
    ks, vs = [], []
    with jax.default_matmul_precision("highest"):
        x = _embed(params, fields, ids)
        for i, (kind, mixer, mlp, _) in enumerate(_layers(params, fields)):
            _, _, k, v = _qkv(x, jax.tree.map(_f32, mixer), f, kind)
            ks.append(k.reshape(k.shape[0], -1))
            vs.append(v.reshape(v.shape[0], -1))
            if i == lead:
                break
            x = _dense_layer(x, mixer, mlp, f, kind)
    return jnp.stack(ks), jnp.stack(vs)
