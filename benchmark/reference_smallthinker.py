"""Plain reference: the block ``SmallThinker-21BA3B-Instruct`` publishes
(https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct,
``config.json``; the family's report is arXiv:2507.20984; what the
config's keys do not state is listed under ``assumed`` in
``configs/smallthinker-21ba3b-instruct.json``), in straightforward
``jax.numpy`` float32: no kernel, no cache, no ring, no chunking, no
batching, no scan over layers, and nothing imported from the program.

``x0 = embed(ids)``. Layer l of kind ``layer_types[l]``, RMSNorm eps 1e-6
throughout, no bias anywhere, the head untied:

1. ``h = RMSNorm_in(x)``;
2. THE ROUTER READS ``h``, the MIXER's normed input, ahead of attention:
   ``z = h W_r`` over 64 experts in float32; the 6 largest logits chosen,
   ``p = softmax`` over those six
   (``moe_primary_router_apply_softmax`` true, ``norm_topk_prob`` true);
3. ``q = h Wq`` as [28, 128], ``k = h Wk`` and ``v = h Wv`` as [4, 128];
   query head n reads key/value head ``n // 7``; no norm on q or k, no
   gate on the heads. ON A ``sliding_attention`` LAYER q and k are
   rotated (theta 1.5e6, the whole head, halves (i, i + 64)) and the
   token at position p sees positions j with ``p - 4096 < j <= p``; a
   ``full_attention`` layer has NO rotation at all and sees every j <= p;
   scores ``q k^T / sqrt(128)``, softmax in float32;
   ``x1 = x + concat(heads) Wo``;
4. ``g = RMSNorm_post(x1)``; ``x2 = x1 + sum_j p_j W_down[i_j] (
   relu(W_gate[i_j] g) * (W_up[i_j] g))``: 64 ReGLU experts of 768, the
   six of step 2, which were chosen on ``h`` and run on ``g``;
5. ``logits = RMSNorm_final(x) W_head``.

Departures from the published description, each because the mathematics
is the same or the published model has no such part:

* the published form is written here: the softmax over the six chosen
  LOGITS. The program scores with a softmax over all 64 and normalises
  the six over their sum (``moe_scoring`` softmax with
  ``moe_norm_topk``), which is the same six numbers
  (``exp(z_i) / sum_chosen exp(z_j)`` either way; a test holds the two
  together to float32 rounding);
* "secondary experts / sparse ReGLU" in the family's description names
  skipping the gate's zeros INSIDE a chosen expert (a predictor says
  which of its 768 rows the ReLU will zero, and they are not read): an
  implementation of the same sum, and no part of the mathematics here;
* the other value of ``moe_primary_router_apply_softmax`` (false: a
  sigmoid of the six over their sum) is a branch the published model
  does not take, and is refused;
* ``rope_scaling`` is null: plain rope;
* the sum over a token's chosen experts is made an EXPERT at a time,
  its rows ``ROW_BLOCK`` at a time: the host sorts the (token, pick)
  pairs by expert and pads each expert's to whole blocks (``_groups``;
  a padded row weighs 0), and the device computes a block's ReGLU on
  its rows and adds it to their tokens with their weights: no token's
  unchosen expert is computed, and no shape depends on the weights or
  the ids, so a sequence length compiles once whatever the seed (an
  earlier form took the fullest expert's count as a static size: a
  program a count, 17-25 s each to compile for the chip, most of a
  cold run's ten minutes);
* attention runs a block of queries at a time against every key
  (``lax.map`` over the blocks: one block's program, whatever their
  number), and the head a block of rows at a time, so that 8,703
  positions fit: ``logits`` returns an array-like that makes only the
  rows it is sliced for;
* a sequence is filled up to whole query blocks with token 0 and the
  rows behind its end are dropped (``hidden``): no position sees a later
  one and an expert reads one token at a time, so no row of the
  sequence changes, and the cell's two lengths (8,192 and 8,703) share
  one set of programs: a float32 matmul under ``highest`` takes the
  chip's compiler 5-6 s, a layer kind's program 10.

Every call runs under ``jax.default_matmul_precision("highest")``.
Parameters are read in the program's layout (``embed``, ``lm_head``,
``final_norm``; ``window_layers`` / ``full_layers`` the mixers of a kind
in layer order, ``attn_norm`` with them; ``layers`` the post-attention
norm ``mlp_norm``, the router and the experts of every layer, leaves
with a leading layer axis) and cast up a layer, and an expert's block
of rows, at a time.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

SUPPORTED = dict(attention="mha", norm="rmsnorm", activation="swiglu",
                 positional="rope", tie_embeddings=False,
                 moe_scoring="softmax", moe_norm_topk=True,
                 moe_expert_form="reglu", moe_router_ahead=True,
                 rope_sliding_only=True)
# what the block has none of: a configuration that sets one is another's
ABSENT = dict(qk_norm=False, attn_gate="none", norm_scheme="pre",
              moe_selection_bias=False, moe_shared_experts=0,
              moe_first_dense_layers=0, moe_routed_scale=1.0,
              moe_n_group=1, moe_experts_held=0, embed_scale=1.0,
              attn_bias=False, mlp_bias=False)
KINDS = {"sliding_attention": "window", "full_attention": "full"}
EXPERTS = ("e_gate", "e_up", "e_down")
# queries scored together against every key: [28, 512, 8703] float32 is
# 0.5 GB; rows of the head made together: [512, 151936] is 0.31 GB
QUERY_BLOCK = 512
HEAD_BLOCK = 512
# rows of one expert computed together
ROW_BLOCK = 128


def check_supported(fields):
    """This reference is the block as SmallThinker-21BA3B-Instruct sets
    it; refuse a configuration it does not describe rather than compare
    against the wrong mathematics."""
    for key, want in SUPPORTED.items():
        if fields.get(key) != want:
            raise ValueError(
                f"benchmark/reference_smallthinker.py implements the "
                f"SmallThinker block ({SUPPORTED}); configuration has "
                f"{key}={fields.get(key)!r}. Add a reference for it.")
    for key, want in ABSENT.items():
        if fields.get(key, want) != want:
            raise ValueError(
                f"benchmark/reference_smallthinker.py: the block has no "
                f"{key} (got {fields[key]!r})")
    types = fields.get("layer_types")
    if not types or len(types) != fields["num_layers"] \
            or set(types) - set(KINDS):
        raise ValueError("benchmark/reference_smallthinker.py: layer_types "
                         f"names a kind of {sorted(KINDS)} a layer")
    if not fields.get("moe_num_experts"):
        raise ValueError("benchmark/reference_smallthinker.py: every layer "
                         "is an expert layer")


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
    """(the layer's normed input h, q [S, nh, hd], k, v [S, nkv, hd]):
    the keys and values as a cache would hold them."""
    S = x.shape[0]
    nh, nkv, hd = f["num_heads"], f["num_kv_heads"], _head_dim(f)
    h = _rms_norm(x, lp["attn_norm"], f["norm_eps"])
    q = (h @ lp["wq"]).reshape(S, nh, hd)
    k = (h @ lp["wk"]).reshape(S, nkv, hd)
    v = (h @ lp["wv"]).reshape(S, nkv, hd)
    if kind == "window":        # a full layer has no position signal
        q, k = (_rope_halves(t, f["rope_theta"]) for t in (q, k))
    return h, q, k, v


def route(h, gate_w, k):
    """The published router on the layer's normed input ``h``: (the
    chosen experts [S, k], their weights): the k largest logits, and the
    softmax over those k."""
    z, chosen = jax.lax.top_k(h @ gate_w, k)
    return chosen, jax.nn.softmax(z, axis=-1)


def _attend(q, k, v, window):
    """``softmax(q k^T / sqrt(d)) v``: q [S, nkv, group, hd] (a kv
    head's query heads together), k, v [S, nkv, hd]; position p sees
    j <= p, and j > p - window where there is one. ``QUERY_BLOCK``
    queries at a time against every key (S is whole blocks:
    ``hidden``)."""
    S, hd = q.shape[0], q.shape[-1]
    keys = jnp.arange(S)

    def block(at):
        s = jnp.einsum("qkgd,ckd->kgqc", q[at], k) / jnp.sqrt(
            jnp.float32(hd))
        seen = keys[None, :] <= at[:, None]
        if window is not None:
            seen = seen & (keys[None, :] > at[:, None] - window)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("kgqc,ckd->qkgd", p, v)

    return jax.lax.map(block, keys.reshape(-1, QUERY_BLOCK)).reshape(q.shape)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _router_and_attention(x, mixer, small, f, kind):
    """The layer up to its experts: (x1 = x + attention, g the experts'
    normed input, the chosen experts [S, k], their weights). The router
    reads h, the MIXER's normed input; ``small``: the layer's leaves but
    the routed experts'."""
    mixer, small = jax.tree.map(_f32, (mixer, small))
    S = x.shape[0]
    nh, nkv, hd = f["num_heads"], f["num_kv_heads"], _head_dim(f)
    h, q, k, v = _qkv(x, mixer, f, kind)
    chosen, w = route(h, small["moe_gate_w"], f["moe_top_k"])
    o = _attend(q.reshape(S, nkv, nh // nkv, hd), k, v,
                f["attn_window"] if kind == "window" else None)
    x = x + o.reshape(S, nh * hd) @ mixer["wo"]
    return x, _rms_norm(x, small["mlp_norm"], f["norm_eps"]), chosen, w


def _reglu(g, wg, wu, wd):
    return (jax.nn.relu(g @ wg) * (g @ wu)) @ wd


def _groups(chosen, w, num_experts):
    """The (token, pick) pairs sorted by expert, each expert's padded to
    whole blocks of ``ROW_BLOCK``, on the host: (expert [B], token
    [B, ROW_BLOCK], weight [B, ROW_BLOCK]); a padded place is token 0 at
    weight 0. ``B = S k // ROW_BLOCK + E`` blocks hold any choice, so
    the shapes follow from S alone; the blocks past the last expert's
    are expert 0's, all padding."""
    chosen, w = np.asarray(chosen), np.asarray(w)
    S, k = chosen.shape
    order = np.argsort(chosen.reshape(-1), kind="stable")
    count = np.bincount(chosen.reshape(-1), minlength=num_experts)
    blocks = -(-count // ROW_BLOCK)
    B = S * k // ROW_BLOCK + num_experts
    expert = np.zeros(B, np.int32)
    expert[:blocks.sum()] = np.repeat(np.arange(num_experts), blocks)
    # where a sorted pair lands: its expert's first block, then its place
    # among that expert's pairs
    start = (np.cumsum(blocks) - blocks) * ROW_BLOCK - (
        np.cumsum(count) - count)
    place = np.repeat(start, count) + np.arange(S * k)
    token = np.zeros(B * ROW_BLOCK, np.int32)
    weight = np.zeros(B * ROW_BLOCK, np.float32)
    token[place] = order // k
    weight[place] = w.reshape(-1)[order]
    return expert, token.reshape(B, ROW_BLOCK), weight.reshape(B, ROW_BLOCK)


@jax.jit
def _experts(x, g, expert, token, weight, experts):
    """``x + sum_j w_j E_j(g)``, a block of one expert's rows at a time
    (``_groups``); ``experts``: (e_gate, e_up, e_down) [E, ...] as
    stored, an expert's cast up for each of its blocks."""

    def add(b, y):
        wg, wu, wd = (_f32(a[expert[b]]) for a in experts)
        out = _reglu(g[token[b]], wg, wu, wd) * weight[b][:, None]
        return y.at[token[b]].add(out)

    return x + jax.lax.fori_loop(0, expert.shape[0], add,
                                 jnp.zeros_like(g))


def _layer(x, mixer, mlp, f, kind):
    small = {k: v for k, v in mlp.items() if k not in EXPERTS}
    x, g, chosen, w = _router_and_attention(x, mixer, small, f, kind)
    return _experts(x, g, *_groups(chosen, w, f["moe_num_experts"]),
                    tuple(mlp[k] for k in EXPERTS))


class _Frozen(dict):
    """``fields`` as a static argument of a jitted function."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def _layers(params, fields):
    """Every layer in order: (kind, its mixer's leaves, its norm's,
    router's and experts' leaves)."""
    seen = {"window": 0, "full": 0}
    for i, kind in enumerate(layer_kinds(fields)):
        mixer = jax.tree.map(lambda a, at=seen[kind]: a[at],
                             params[kind + "_layers"])
        seen[kind] += 1
        yield kind, mixer, jax.tree.map(lambda a, at=i: a[at],
                                        params["layers"])


def _embed(params, ids):
    return _f32(params["embed"][jnp.asarray(ids, jnp.int32)])


def hidden(params, fields, ids):
    """[S, hidden] float32: the stream behind the last layer, before the
    final norm."""
    check_supported(fields)
    f = _Frozen(fields)
    S = len(ids)
    # whole query blocks: no position sees a later one and an expert
    # reads one token, so what is appended changes no row ahead of it
    ids = np.pad(np.asarray(ids), (0, -S % QUERY_BLOCK))
    with jax.default_matmul_precision("highest"):
        x = _embed(params, ids)
        for kind, mixer, mlp in _layers(params, fields):
            x = _layer(x, mixer, mlp, f, kind)
        return x[:S]


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


@functools.partial(jax.jit, static_argnums=(2, 3))
def _first_kv(x, mixer, f, kind):
    return _qkv(x, jax.tree.map(_f32, mixer), f, kind)[2:]


def leading_kv(params, fields, ids):
    """The keys and values of the layer AHEAD OF EVERY ROUTED EXPERT, as
    a cache would hold them (k rotated on a sliding layer): ``(k, v)``
    each [1, S, kv_heads * head_dim] float32, for the first layer, whose
    input no expert has touched (the block has no leading dense
    layer)."""
    check_supported(fields)
    f = _Frozen(fields)
    with jax.default_matmul_precision("highest"):
        kind, mixer, _ = next(_layers(params, fields))
        k, v = _first_kv(_embed(params, ids), mixer, f, kind)
    return k.reshape(1, k.shape[0], -1), v.reshape(1, v.shape[0], -1)
