"""Plain reference: the ``brumby`` block as ``Brumby-14B-Base`` publishes
it (https://huggingface.co/manifestai/Brumby-14B-Base/blob/main/config.json;
the Qwen3-14B block with every attention layer replaced by POWER
RETENTION: Manifest AI, "Scaling Context Requires Rethinking Attention",
arXiv:2507.04239, and the Brumby-14B-Base release note), in
straightforward ``jax.numpy`` float32: the ATTENTION form, no state, no
chunks, no kernel, no batching, and nothing imported from the program.

Layer input ``x_t``, ``a = RMSNorm(x_t)`` (eps ``norm_eps``, no biases
but the gate's):

    q_h = rope_t(rmsnorm_hd(W_q a)_h)   h = 1 .. num_heads
    k_j = rope_t(rmsnorm_hd(W_k a)_j)   v_j = (W_v a)_j   j = 1 .. num_kv_heads
    gamma_{t,j} = log sigmoid(w_decay,j . a + b_decay,j) <= 0
    G_{t,j} = sum_{r <= t} gamma_{r,j}
    s_{t,r} = (q_{t,h} . k_{r,j} / sqrt(hd))^2 exp(G_{t,j} - G_{r,j})
                                        r <= t, h in j's group
    o_{t,h} = sum_r s_{t,r} v_{r,j} / (sum_r s_{t,r} + eps)
    x' = x + W_o [o_{t,1} .. o_{t,nh}]
    x'' = x' + W_down(silu(W_gate n) * (W_up n)),  n = RMSNorm(x')

``(Q K^T)^2`` and the decay mask are made a block of ``QUERY_BLOCK``
query rows at a time against every key, so that 2,304 positions fit.
Then the final RMSNorm and the untied head.

The SAME thing as a recurrence a key/value head is the reference's
second, independent route (:func:`leading_states`), a token at a time:

    S_t = e^{gamma_t} S_{t-1} + phi(k_t) v_t^T      [hd (hd + 1) / 2, hd]
    z_t = e^{gamma_t} z_{t-1} + phi(k_t)            [hd (hd + 1) / 2]
    phi(u) = (u_a u_b (sqrt 2 if a < b else 1))_{a <= b} / sqrt(hd)

(the pairs a <= b in row-major order), so that ``phi(q) . phi(k) = (q .
k / sqrt(hd))^2`` and ``o_{t,h} = phi(q)^T S_t / (phi(q)^T z_t + eps)``;
a test ties the two routes (``tests/unit/inference/
test_retention_serving.py``).

ASSUMED (``config.json`` states none of these; the configuration's file
lists the same under ``assumed``): the degree, 2; the gate, ONE scalar a
key/value head and token from the normed input through ``log sigmoid``,
with a bias; the normaliser, the weights' own sum, and its ``eps``
(``retention_eps``, 1e-6); the Qwen3 head norms (a learned weight a head
lane, eps ``norm_eps``) and rotation (lanes (i, i + hd / 2), theta
``rope_theta``, the whole head) stay in front of the kernel;
``max_window_layers``, ``sliding_window`` and ``use_sliding_window`` are
Qwen3 leftovers that shape nothing.

DEPARTURES from a checkpoint: seeded weights (``weights_brumby.py``),
the first ``num_layers`` layers only with the final norm and head behind
them (the configuration's cut).

Every call runs under ``jax.default_matmul_precision("highest")``.
Parameters are read in the program's layout (``embed``, ``lm_head``,
``final_norm``; ``retention_layers`` the mixers, ``layers`` the norm and
MLP of every layer) and cast up a layer at a time.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

SUPPORTED = dict(attention="mha", norm="rmsnorm", activation="swiglu",
                 positional="rope", tie_embeddings=False, qk_norm=True)
QUERY_BLOCK = 256


def check_supported(fields):
    """This reference is the brumby block; refuse a configuration it
    does not describe rather than compare against the wrong
    mathematics."""
    for key, want in SUPPORTED.items():
        if fields.get(key) != want:
            raise ValueError(
                f"benchmark/reference_brumby.py implements the brumby "
                f"block ({SUPPORTED}); configuration has {key}="
                f"{fields.get(key)!r}. Add a reference for it.")
    types = fields.get("layer_types")
    if not types or len(types) != fields["num_layers"] \
            or set(types) != {"power_retention"} \
            or fields.get("moe_num_experts"):
        raise ValueError("benchmark/reference_brumby.py: every layer a "
                         "power_retention layer with a dense MLP")


class _Frozen(dict):
    """``fields`` as a static argument of a jitted function."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


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


def _eps(f):
    return f.get("retention_eps", 1e-6)


def _qkvg(x, lp, f):
    """(q [S, nh, hd], k, v [S, nkv, hd], gamma [S, nkv]) of a layer."""
    S = x.shape[0]
    nh, nkv, hd = f["num_heads"], f["num_kv_heads"], _head_dim(f)
    eps = f["norm_eps"]
    a = _rms_norm(x, lp["attn_norm"], eps)
    q = _rms_norm((a @ lp["wq"]).reshape(S, nh, hd), lp["q_norm"], eps)
    k = _rms_norm((a @ lp["wk"]).reshape(S, nkv, hd), lp["k_norm"], eps)
    v = (a @ lp["wv"]).reshape(S, nkv, hd)
    q, k = (_rope_halves(t, f["rope_theta"]) for t in (q, k))
    return q, k, v, jax.nn.log_sigmoid(a @ lp["w_decay"] + lp["b_decay"])


def retention(q, k, v, gamma, eps):
    """The attention form: q [S, nh, hd]; k, v [S, nkv, hd]; gamma
    [S, nkv]. Returns o [S, nh, hd]; a block of query rows at a time
    against every key."""
    S, nh, hd = q.shape
    nkv = k.shape[1]
    q = q.reshape(S, nkv, nh // nkv, hd)      # a kv head's query heads
    G = jnp.cumsum(gamma, axis=0)                            # [S, nkv]
    keys = jnp.arange(S)
    out = []
    for start in range(0, S, QUERY_BLOCK):
        at = jnp.arange(start, min(start + QUERY_BLOCK, S))
        qk = jnp.einsum("tjgd,rjd->jgtr", q[at], k) / jnp.sqrt(
            jnp.float32(hd))
        seen = keys[None, :] <= at[:, None]                  # [t, r]
        decay = jnp.where(seen, jnp.exp(jnp.where(
            seen, G[at].T[:, :, None] - G.T[:, None, :], 0.0)), 0.0)
        s = jnp.square(qk) * decay[:, None]                  # [j, g, t, r]
        out.append(jnp.einsum("jgtr,rjd->tjgd", s, v)
                   / (jnp.sum(s, axis=-1).transpose(2, 0, 1)[..., None]
                      + eps))
    return jnp.concatenate(out).reshape(S, nh, hd)


def phi(u):
    """[..., hd] -> [..., hd (hd + 1) / 2]: the symmetric second power,
    pairs a <= b in row-major order."""
    hd = u.shape[-1]
    a, b = np.triu_indices(hd)
    scale = np.where(a < b, np.sqrt(2.0), 1.0) / np.sqrt(hd)
    return u[..., a] * u[..., b] * scale.astype(np.float32)


def recurrence(q, k, v, gamma, eps):
    """The same mixer as a recurrence, a token at a time from S = 0, z
    = 0. Returns (o [S, nh, hd], S [nkv, P, hd], z [nkv, P])."""
    S_, nh, hd = q.shape
    nkv = k.shape[1]
    P = hd * (hd + 1) // 2

    def token(carry, t):
        s, z = carry
        qt, kt, vt, gt = t
        pk = phi(kt)                                         # [nkv, P]
        decay = jnp.exp(gt)
        s = decay[:, None, None] * s + pk[:, :, None] * vt[:, None, :]
        z = decay[:, None] * z + pk
        pq = phi(qt).reshape(nkv, nh // nkv, P)
        o = jnp.einsum("jgp,jpd->jgd", pq, s) / (
            jnp.einsum("jgp,jp->jg", pq, z)[..., None] + eps)
        return (s, z), o.reshape(nh, hd)

    (s, z), o = jax.lax.scan(
        token, (jnp.zeros((nkv, P, hd), jnp.float32),
                jnp.zeros((nkv, P), jnp.float32)), (q, k, v, gamma))
    return o, s, z


@functools.partial(jax.jit, static_argnums=(3,))
def _layer(x, mixer, mlp, f):
    mixer, mlp = jax.tree.map(_f32, (mixer, mlp))
    S = x.shape[0]
    o = retention(*_qkvg(x, mixer, f), _eps(f))
    x = x + o.reshape(S, -1) @ mixer["wo"]
    n = _rms_norm(x, mlp["mlp_norm"], f["norm_eps"])
    return x + (jax.nn.silu(n @ mlp["w_gate"]) * (n @ mlp["w_up"])) \
        @ mlp["w_down"]


@functools.partial(jax.jit, static_argnums=(2,))
def _state_after(x, mixer, f):
    """(S, z) a layer's recurrence leaves after the tokens whose stream
    into the layer is ``x``."""
    _, s, z = recurrence(*_qkvg(x, jax.tree.map(_f32, mixer), f), _eps(f))
    return s, z


@jax.jit
def _head(x, final_w, lm_head, eps):
    return _rms_norm(x, _f32(final_w), eps) @ _f32(lm_head)


def _streams(params, fields, ids):
    """The stream INTO every layer and out of the last: num_layers + 1
    arrays [S, H]."""
    f = _Frozen(fields)
    x = _f32(params["embed"][jnp.asarray(ids, jnp.int32)])
    xs = [x]
    for i in range(fields["num_layers"]):
        x = _layer(x, jax.tree.map(lambda a: a[i],
                                   params["retention_layers"]),
                   jax.tree.map(lambda a: a[i], params["layers"]), f)
        xs.append(x)
    return xs


def hidden(params, fields, ids):
    """The residual stream after the last layer."""
    return _streams(params, fields, ids)[-1]


def leading_states(params, fields, ids, layers=1):
    """(S [layers, nkv, hd (hd + 1) / 2, hd], z [layers, nkv, hd (hd +
    1) / 2]) float32: the state and normaliser of the first ``layers``
    layers after the last token of ``ids``, by the RECURRENCE on the
    layer's own q, k, v and gate, whose input is the attention form's
    stream. The model is dense, so no layer's state hangs on an expert
    choice; but a bf16 engine's stream drifts from the float32 one layer
    by layer (layer 0's state reads 2.9e-3 from this on the chip, layer
    7's 2.2e-2: PERF.md section 4), and that drift is the activations',
    not the state's. Layer 0's input is the embedding itself, the same
    values on both sides: its state is the recurrence's own output, read
    before any other layer's rounding reaches it, and the default.
    ``layers`` > 1 reads the next ones too, for a run's detail and the
    toy's tests."""
    check_supported(fields)
    f = _Frozen(fields)
    with jax.default_matmul_precision("highest"):
        xs = _streams(params, {**fields, "num_layers": layers - 1}, ids)
        pairs = [_state_after(x, jax.tree.map(
            lambda a: a[i], params["retention_layers"]), f)
            for i, x in enumerate(xs)]
    return (jnp.stack([s for s, _ in pairs]),
            jnp.stack([z for _, z in pairs]))


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
