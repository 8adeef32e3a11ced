"""Plain reference: the ``dots3_note`` block as ``dots3-note-prev``
publishes it (https://huggingface.co/dots-studio/dots3-note-prev,
``config.json``; latent attention is DeepSeek-V3's, arXiv:2412.19437
section 2.1, the indexer DeepSeek-V3.2-Exp's "DeepSeek Sparse
Attention"), in straightforward ``jax.numpy`` float32: no kernel, no
cache, no batching, no scan, and nothing imported from the program.

``x_t`` a layer's input, ``a_t = RMSNorm(x_t)`` (eps 1e-5), no biases
but the indexer's LayerNorm. ``layer_types[i]`` says which mixer:

1. FULL latent attention (``full_attention``), in the EXPANDED form:
   ``c^q = r_q RMSNorm(a W^qa)``, heads of ``[q^n | q^r] = c^q W^qb``;
   ``[c~ | k^r] = a W^kva``, ``c^kv = r_kv RMSNorm(c~)``; per head
   ``[k^n | v] = c^kv W^kvb``; ``q^r`` and the ONE ``k^r`` all heads
   share rotated at ``rope_theta``, the pairs of lanes (2i, 2i + 1) in
   place (``rope_interleave``); scores over ``sqrt(nope + rope)``.
   ``r = sqrt(hidden / rank)`` (``mla_lora_rescale``). A query token
   attends ONLY the positions its indexer picked: ``q^I_j = c^q W^Iq_j``
   (``index_n_heads`` heads of ``index_head_dim``), ``k^I =
   LayerNorm(a W^Ik)`` with weight AND bias, the first ``qk_rope_head_dim``
   lanes of both rotated with the HALVES paired (lane i with i + half)
   at ``rope_theta``; ``I[t, s] = sum_j w[t, j] relu(q^I[t, j] . k^I[s])``
   with ``w = (a W^Iw) index_n_heads^-1/2 index_head_dim^-1/2``, as a
   whole causally masked matrix a block of queries; ``S_t`` the
   ``index_topk`` positions ``s <= t`` of largest ``I`` by
   ``jax.lax.top_k`` (every ``s <= t`` while ``t + 1 <= index_topk``).
   Each head's output times ``sigmoid((a W^g)_h)`` (``attn_gate``
   "head"), then ``W^o``.
2. SLIDING latent attention (``sliding_attention``): the same equations
   at the ``swa_*`` sizes (its own heads, ranks, head widths and theta),
   no indexer, ``S_t = {s : t - attn_window < s <= t}``.
3. layer 0 (``moe_first_dense_layers`` 1): a dense SwiGLU.
4. layers 1..: sigmoid scores over ``moe_num_experts``, the top 8 by
   score PLUS the selection bias, weights the scores WITHOUT it, over
   their sum (+ 1e-20), times ``moe_routed_scale``; the chosen experts'
   SwiGLUs so weighted, plus the shared expert's.
5. the final RMSNorm and the untied head.

Departures from the published description, each noted at its line:

* (A) the experts this chip does not hold are LEFT OUT: the tree holds
  ``moe_experts_held`` of the router's experts from
  ``moe_experts_first``; a pick outside them adds nothing, in program
  and reference alike (the other seven chips' part of the sum);
* (B) V3.2's Hadamard rotation of ``q^I`` and ``k^I`` is orthogonal and
  cancels in the dot product: not applied;
* (C) V3.2 runs its indexer in FP8: a deployment's precision; float32
  here;
* (D) no group-limited choice (``n_group`` 1), no rope scaling;
* (E) the vision and audio towers and the MTP module: side modules with
  no key in the language model's configuration, left out;
* (F) a sequence is padded behind its end to whole blocks of ``BLOCK``
  positions (always at least one), so that the prompt and the prompt
  with its served tokens are ONE compiled shape; causal, so no real
  position sees the padding.

Every call runs under ``jax.default_matmul_precision("highest")``.
Parameters are read in the program's layout (``embed``, ``lm_head``,
``final_norm``; ``mla_layers`` / ``mla_window_layers`` the mixers of a
kind in layer order; ``lead_layers`` / ``layers`` the norm and MLP of
the leading dense and of the expert layers) and cast up a layer, and an
expert, at a time.
"""

import jax
import jax.numpy as jnp
import numpy as np

SUPPORTED = dict(attention="mla", norm="rmsnorm", activation="swiglu",
                 positional="rope", tie_embeddings=False,
                 moe_scoring="sigmoid", moe_selection_bias=True,
                 moe_norm_topk=True, rope_interleave=True,
                 attn_gate="head", mla_lora_rescale=True)
KINDS = {"full_attention": "mla", "sliding_attention": "mla_window"}
# query positions a block: the indexer's matrix of a block is [BLOCK,
# S] float32 (1,024 x 33,792: 138 MB), a head group's scores [heads,
# BLOCK, S]
BLOCK = 1024
# heads whose keys, values and scores are expanded together (8 x 1,024
# x 17,408 float32 scores: 0.57 GB, and as much again for the softmax);
# a group is one call of ONE compiled program a latent kind: every head
# of a block unrolled into one program took 3.5 minutes to compile where
# this takes 1.5 (my chip runs, PR 68)
HEAD_GROUP = 8
# positions whose dense MLP is made together (8,192 x 13,824 float32:
# 0.45 GB a matrix of three)
MLP_BLOCK = 8192


def check_supported(fields):
    """This reference is the dots3_note block; refuse a configuration it
    does not describe rather than compare against the wrong
    mathematics."""
    for key, want in SUPPORTED.items():
        if fields.get(key) != want:
            raise ValueError(
                f"benchmark/reference_dots3.py implements the dots3_note "
                f"block ({SUPPORTED}); configuration has {key}="
                f"{fields.get(key)!r}. Add a reference for it.")
    types = fields.get("layer_types") or ()
    if len(types) != fields["num_layers"] or set(types) - set(KINDS) \
            or not fields.get("index_topk") \
            or not fields.get("moe_num_experts") \
            or not fields.get("moe_shared_experts"):
        raise ValueError(
            "benchmark/reference_dots3.py: layer_types of full_attention "
            "/ sliding_attention a layer, an indexer, routed experts and "
            "a shared expert are part of the block")


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * w


def _layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w + b


def _angles(S, D, theta, ndim, t0=0):
    freqs = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = (t0 + jnp.arange(S, dtype=jnp.float32))[:, None] * freqs
    return ang.reshape(S, *([1] * (ndim - 2)), D // 2)         # [S, D/2]


def _rope_pairs(x, theta, t0=0):
    """x [S, ..., D], the positions ``t0 ..``: lanes (2i, 2i + 1)
    rotated in place by position x theta ** (-2i / D)."""
    ang = _angles(x.shape[0], x.shape[-1], theta, x.ndim, t0)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                     x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], axis=-1)
    return out.reshape(x.shape)


def _rope_halves(x, rot, theta, t0=0):
    """x [S, ..., D], the positions ``t0 ..``: of the first ``rot``
    lanes, lane i rotated with lane i + rot / 2; the rest pass."""
    ang = _angles(x.shape[0], rot, theta, x.ndim, t0)
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang),
                            x[..., rot:]], axis=-1)


def sizes(f, kind):
    """(heads, q rank, kv rank, nope, rope, v, theta) of a latent kind"""
    if kind == "mla_window":
        return (f["swa_num_heads"], f["swa_q_lora_rank"],
                f["swa_kv_lora_rank"], f["swa_qk_nope_head_dim"],
                f["swa_qk_rope_head_dim"], f["swa_v_head_dim"],
                f["swa_rope_theta"])
    return (f["num_heads"], f["q_lora_rank"], f["kv_lora_rank"],
            f["qk_nope_head_dim"], f["qk_rope_head_dim"], f["v_head_dim"],
            f["rope_theta"])


def _latents(x, lp, f, kind):
    """What a latent layer makes of its input before any position meets
    another: (a, c^q, the cached row [c^kv | k^r rotated]). The heads'
    queries are made a block of positions and a group of heads at a
    time (``_group_attend``): all of them at once are 3.3 GB at 33k
    positions."""
    _, rq, dc, _, _, _, theta = sizes(f, kind)
    H, eps = f["hidden_size"], f["norm_eps"]
    a = _rms_norm(x, lp["attn_norm"], eps)
    cq = (H / rq) ** 0.5 * _rms_norm(a @ lp["wq_a"], lp["q_norm"], eps)
    kv = a @ lp["wkv_a"]
    ckv = (H / dc) ** 0.5 * _rms_norm(kv[:, :dc], lp["kv_norm"], eps)
    return a, cq, jnp.concatenate(
        [ckv, _rope_pairs(kv[:, dc:], theta)], axis=-1)


_latents_jit = jax.jit(_latents, static_argnums=(2, 3))


def _index_keys(a, lp, f):
    """k^I [S, index_head_dim]: one key a position"""
    k = _layer_norm(a @ lp["index_wk"], lp["index_k_norm"],
                    lp["index_k_bias"], f["norm_eps"])
    return _rope_halves(k, f["qk_rope_head_dim"], f["rope_theta"])


def _index_scores(a, cq, index_wq, index_ww, ki, t0, f):
    """What a GROUP of the indexer's heads adds to ``I`` [B, S] for a
    block of queries at positions ``t0 ..``: ``index_wq`` [rq, g, d] and
    ``index_ww`` [H, g] are the group's slices; the queries' first
    lanes rotated with the halves paired, ``w`` with both scales."""
    ih, d = f["index_n_heads"], f["index_head_dim"]
    qi = _rope_halves(jnp.einsum("tr,rjd->tjd", cq, index_wq),
                      f["qk_rope_head_dim"], f["rope_theta"], t0)
    wi = (a @ index_ww) * (ih ** -0.5 * d ** -0.5)
    s = jnp.einsum("tjd,sd->tjs", qi, ki)
    return jnp.einsum("tjs,tj->ts", jax.nn.relu(s), wi)


_index_scores_jit = jax.jit(_index_scores, static_argnums=(6,))


def _pick(scores, t0, topk):
    """``S_t`` of a block of queries at positions ``t0 ..``, as a mask
    [B, S]: the whole causally masked matrix I, then ``jax.lax.top_k``."""
    B, S = scores.shape
    causal = jnp.arange(S)[None, :] <= t0 + jnp.arange(B)[:, None]
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf),
                           min(topk, S))
    picked = jnp.zeros((B, S), bool).at[jnp.arange(B)[:, None], idx].set(True)
    return picked & causal


_pick_jit = jax.jit(_pick, static_argnums=(2,))


def _index_groups(lp, f):
    """The indexer's weights a group of ``HEAD_GROUP`` heads: [(index_wq
    [rq, g, d], index_ww [H, g])], sliced once a layer."""
    ih, d = f["index_n_heads"], f["index_head_dim"]
    wq = lp["index_wq"].reshape(-1, ih, d)
    return [(wq[:, j:j + HEAD_GROUP], lp["index_ww"][:, j:j + HEAD_GROUP])
            for j in range(0, ih, HEAD_GROUP)]


def _block_select(a, cq, groups, ki, t0, f):
    """``S_t`` of a block of queries: the indexer's heads a group at a
    time (one compiled program, many calls), summed, then picked."""
    scores = 0.0
    for wq, ww in groups:
        scores = scores + _index_scores_jit(a, cq, wq, ww, ki, t0, f)
    return _pick_jit(scores, t0, f["index_topk"])


def _group_attend(cq, wq_b, t0, rows, wkv_b, seen, sz):
    """A GROUP of heads of a block of queries at positions ``t0 ..``
    (their latents ``cq`` [B, q rank]; ``wq_b`` [rq, g, nope + rope] and
    ``wkv_b`` [kv rank, g, nope + v] the group's slices) over cached rows
    [K, kv rank + rope] that each query may see where ``seen`` [B, K]:
    keys and values EXPANDED a head from the latent. Returns [B, g, v]."""
    _, _, dc, dn, dr, dv, theta = sz
    q = jnp.einsum("tr,rhd->thd", cq, wq_b)
    q_rope = _rope_pairs(q[..., dn:], theta, t0)
    kvb = jnp.einsum("kc,chd->khd", rows[:, :dc], wkv_b)
    s = (jnp.einsum("qhd,khd->hqk", q[..., :dn], kvb[..., :dn])
         + jnp.einsum("qhd,kd->hqk", q_rope, rows[:, dc:])) \
        / jnp.sqrt(jnp.float32(dn + dr))
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, kvb[..., dn:])


_group_attend_jit = jax.jit(_group_attend, static_argnums=(6,))


def _head_groups(lp, sz):
    """A latent layer's head weights a group of ``HEAD_GROUP`` heads:
    [(wq_b [rq, g, nope + rope], wkv_b [kv rank, g, nope + v])], sliced
    once a layer."""
    nh, rq, dc, dn, dr, dv, _ = sz
    wq_b = lp["wq_b"].reshape(rq, nh, dn + dr)
    wkv_b = lp["wkv_b"].reshape(dc, nh, dn + dv)
    return [(wq_b[:, h:h + HEAD_GROUP], wkv_b[:, h:h + HEAD_GROUP])
            for h in range(0, nh, HEAD_GROUP)]


def _block_attend(cq, groups, t0, rows, seen, sz):
    """Every head of a block of queries, a group of ``HEAD_GROUP`` heads
    at a time (one compiled program a kind, many calls). Returns [B, nh
    * v]."""
    return jnp.concatenate([
        _group_attend_jit(cq, wq_b, t0, rows, wkv_b, seen, sz)
        for wq_b, wkv_b in groups], axis=1).reshape(cq.shape[0], -1)


@jax.jit
def _gate_and_out(a, o, wg, wo):
    """Of a block of positions: each head's output times sigmoid of its
    gate, then ``W^o`` (the heads' outputs of 33k positions at once are
    2.2 GB)."""
    nh = wg.shape[1]
    g = jax.nn.sigmoid(a @ wg)[..., None]
    return (o.reshape(o.shape[0], nh, -1) * g).reshape(o.shape) @ wo


def _mixer(x, lp, f, kind, keep=None):
    """A latent layer's mixer on x [S, H]; ``keep`` (a dict) is handed
    the layer's cached rows, index keys and selection masks."""
    S = x.shape[0]
    ff = _Frozen(f)
    a, cq, rows = _latents_jit(x, lp, ff, kind)
    sz = sizes(f, kind)
    heads = _head_groups(lp, sz)
    out = []
    if kind == "mla":
        ki = _index_keys(a, lp, f)
        index = _index_groups(lp, f)
        picks = []
        for t0 in range(0, S, BLOCK):
            at = slice(t0, t0 + BLOCK)
            seen = _block_select(a[at], cq[at], index, ki, t0, ff)
            out.append(_gate_and_out(a[at], _block_attend(
                cq[at], heads, t0, rows, seen, sz), lp["wg"], lp["wo"]))
            if keep is not None:
                picks += [seen[p - t0] for p in keep["probe"]
                          if t0 <= p < t0 + BLOCK]
        if keep is not None:
            keep.update(rows=rows, index_k=ki, picked=jnp.stack(picks)
                        if picks else jnp.zeros((0, S), bool))
    else:
        # positions t - window < s <= t: a block of queries sees the
        # window - 1 positions ahead of it and its own
        w = f["attn_window"]
        back = -(-(w - 1) // BLOCK) * BLOCK
        padded = jnp.pad(rows, ((back, 0), (0, 0)))
        for t0 in range(0, S, BLOCK):
            t = t0 + jnp.arange(min(BLOCK, S - t0))[:, None]
            s = t0 - back + jnp.arange(back + t.shape[0])[None, :]
            seen = (s <= t) & (s > t - w) & (s >= 0)
            out.append(_gate_and_out(a[t0:t0 + BLOCK], _block_attend(
                cq[t0:t0 + BLOCK], heads, t0,
                padded[t0:t0 + back + t.shape[0]], seen, sz),
                lp["wg"], lp["wo"]))
        if keep is not None:
            keep.update(rows=rows)
    return x + jnp.concatenate(out)


@jax.jit
def _swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ _f32(wg)) * (h @ _f32(wu))) @ _f32(wd)


def _dense_mlp(x, lp, f):
    h = _rms_norm(x, _f32(lp["mlp_norm"]), f["norm_eps"])
    return x + jnp.concatenate([
        _swiglu(h[t0:t0 + MLP_BLOCK], lp["w_gate"], lp["w_up"],
                lp["w_down"]) for t0 in range(0, x.shape[0], MLP_BLOCK)])


def _route(x, small, f):
    """(the MLP's normed input, the chosen experts [S, k], their
    weights [S, k])."""
    lp = jax.tree.map(_f32, small)
    h = _rms_norm(x, lp["mlp_norm"], f["norm_eps"])
    s = jax.nn.sigmoid(h @ lp["moe_gate_w"])                   # [S, E]
    _, chosen = jax.lax.top_k(s + lp["moe_gate_bias"], f["moe_top_k"])
    w = jnp.take_along_axis(s, chosen, axis=-1)                # no bias
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) \
        * f["moe_routed_scale"]
    return h, chosen, w


_route_jit = jax.jit(_route, static_argnums=(2,))


@jax.jit
def _weight_of(chosen, w, e):
    """What each position gives expert ``e``: its weight where it chose
    it, 0 where it did not."""
    return jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)


def _expert_mlp(x, layers, i, f, held=None):
    """The expert layer ``i`` of the stack. (A) only the experts the
    tree HOLDS are computed, ``held`` = (first, count) of them (None:
    the tree's own share): every held expert over every position,
    weighted 0 where the position did not choose it (no program depends
    on what the router chose)."""
    first, count = held or (f.get("moe_experts_first", 0),
                            layers["e_gate"].shape[1])
    experts = ("e_gate", "e_up", "e_down")
    small = {k: v[i] for k, v in layers.items()
             if k not in experts and not k.startswith("shared_")}
    h, chosen, w = _route_jit(x, small, _Frozen(f))
    out = _swiglu(h, *(layers["shared_" + k][i]
                       for k in ("gate", "up", "down")))
    at = first - f.get("moe_experts_first", 0)   # the share's place here
    for e in range(count):
        out = out + _weight_of(chosen, w, first + e)[:, None] * _swiglu(
            h, *(layers[k][i, at + e] for k in experts))
    return x + out


class _Frozen(dict):
    """``fields`` as a static argument of a jitted function."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


@jax.jit
def _head(x, final_w, lm_head, eps):
    return _rms_norm(x, _f32(final_w), eps) @ _f32(lm_head)


def _padded(ids):
    """(F) ``ids`` with at least one padding position behind them, to
    whole blocks of ``BLOCK``."""
    ids = np.asarray(ids, np.int64)
    return np.pad(ids, (0, (len(ids) // BLOCK + 1) * BLOCK - len(ids)))


def _walk(params, fields, ids, layers=None, keep=None, held=None,
          probe=()):
    """The stream behind the first ``layers`` layers (None: all) of one
    sequence, padding and all; ``keep`` (a list) is handed a dict a
    layer (``_mixer``) with the layer's input ``x`` beside, a full
    layer's ``S_t`` at the positions ``probe``."""
    f = dict(fields)
    lead = f.get("moe_first_dense_layers", 0)
    types = f["layer_types"]
    x = _f32(params["embed"][jnp.asarray(_padded(ids), jnp.int32)])
    at = {kind: 0 for kind in KINDS.values()}
    for i in range(f["num_layers"] if layers is None else layers):
        kind = KINDS[types[i]]
        lp = jax.tree.map(lambda a: _f32(a[at[kind]]),
                          params[kind + "_layers"])
        at[kind] += 1
        kept = None if keep is None else {"x": x, "probe": sorted(probe)}
        x = _mixer(x, lp, f, kind, kept)
        if keep is not None:
            keep.append(kept)
            if i + 1 == layers:
                break               # nothing reads this layer's MLP
        if i < lead:
            x = _dense_mlp(x, jax.tree.map(lambda a: a[i],
                                           params["lead_layers"]), f)
        else:
            x = _expert_mlp(x, params["layers"], i - lead, f, held)
    return x


def logits(params, fields, ids, held=None):
    """[S, vocab] float32 logits of one sequence ``ids`` [S]. ``held``
    = (first, count): another share of the experts than the tree's own
    (a test adds the eight shares up)."""
    check_supported(fields)
    with jax.default_matmul_precision("highest"):
        x = _walk(params, fields, ids, held=held)
        return _head(x, params["final_norm"], params["lm_head"],
                     fields["norm_eps"])[:len(ids)]


def leading_layers(params, fields, ids, layers, probe=()):
    """What the first ``layers`` layers hold of one sequence: a dict a
    layer with ``x`` [S, H] (the layer's input), ``rows`` [S, row] (the
    cached latent rows) and, of a full layer, ``index_k`` [S, d] and
    ``picked`` [len(probe), S] bool: ``S_t`` of each position t of
    ``probe``, in ascending order."""
    check_supported(fields)
    keep = []
    with jax.default_matmul_precision("highest"):
        _walk(params, fields, ids, layers=layers, keep=keep, probe=probe)
    S = len(ids)
    return [{k: v[:S] if k != "picked" else v[:, :S]
             for k, v in kept.items() if k != "probe"} for kept in keep]


def next_token_loss(params, fields, ids):
    """Mean next-token cross-entropy of one sequence, float32."""
    lg = logits(params, fields, ids)[:-1]
    tgt = jnp.asarray(ids, jnp.int32)[1:]
    logz = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, tgt[:, None], axis=-1)[:, 0]
    return float(jnp.mean(logz - picked))
