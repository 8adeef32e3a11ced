"""Plain reference: the ``falcon_h1`` block as ``Falcon-H1-34B-Instruct``
publishes it
(https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json;
the equations are those of ``transformers/models/falcon_h1/
modeling_falcon_h1.py``: ``FalconH1DecoderLayer.forward``,
``FalconH1Mixer.torch_forward``, ``compute_mup_vector``,
``FalconH1RMSNormGated``, ``FalconH1Attention``, ``FalconH1MLP``,
``FalconH1Model.forward``, ``FalconH1ForCausalLM.forward``; and of the
Mamba-2 paper, arXiv:2405.21060), in straightforward ``jax.numpy``
float32: no kernel, no cache, no batching, no chunking, a token at a time
through the recurrence, full causal attention, and nothing imported from
the program. ``tests/unit/inference/test_falcon_h1_serving.py`` holds it
to the published code at toy widths.

Every layer is the SAME layer, and its mixer is TWO mixers on ONE norm
(RMSNorm, eps ``norm_eps``, no bias but the convolution's)::

    x0 = embed[ids] * embed_scale
    h  = rmsnorm(x, attn_norm)                               # input_layernorm
    # state-space half (d_ssm = mamba_n_heads x mamba_d_head channels)
    p  = ((h * ssm_in_scale) @ W_in) * mup            # [z | x | B | C | dt]
         mup = ssm_{z,x,b,c,dt}_scale over the five segments
    xBC = silu(causal_conv(xBC, taps) + conv_b)
    dt = softplus(dt + dt_bias);  A = -exp(a_log)            # no clamp on dt
    S_t = exp(dt_t A) S_{t-1} + (dt_t x_t) B_t^T;  y_t = S_t C_t + D x_t
         head h reads group h // (heads / groups) of B and C
    y  = rmsnorm_grouped(y * silu(z), gate_norm, groups)   # gate BEFORE norm
    m  = (y @ W_out) * ssm_out_scale
    # attention half: num_heads on num_kv_heads x head_dim, rotate_half
    q  = (h * attn_in_scale) @ Wq;  v = (h * attn_in_scale) @ Wv
    k  = ((h * attn_in_scale) @ Wk) * key_scale
    a  = softmax(rope(q) rope(k)^T / sqrt(head_dim), causal) v
    a  = (a @ Wo) * attn_out_scale
    x  = x + m + a
    g  = rmsnorm(x, mlp_norm)                                # pre_ff_layernorm
    x  = x + (((g @ W_up) * silu((g @ W_gate) * mlp_gate_scale)) @ W_down)
             * mlp_down_scale
    logits = (rmsnorm(x, final_norm) @ lm_head) / logit_scale

DEPARTURES, each noted in the configuration's ``assumed`` too: the
source MULTIPLIES the logits by ``lm_head_multiplier`` 1/128 where
``fields.logit_scale`` 128 divides them (a power of two: the same
float); the multipliers are the program's field names, one scalar each,
where the source has two lists (``ssm_multipliers``,
``mlp_multipliers``); ``mamba_chunk_size`` is a schedule of the source's
chunked form and no equation. A sequence is padded to a whole
``PAD_TO`` tokens so that the cell's two lengths compile two programs
and not one a length: a padded token is behind every real one (causal:
nothing real reads it) and leaves the state as it was.

Every call runs under ``jax.default_matmul_precision("highest")``.
Parameters are read in the program's layout (``embed``, ``lm_head``,
``final_norm``; ``hybrid_layers`` both halves' leaves and the one
``attn_norm``, in layer order; ``layers`` the MLP's norm and matrices)
and cast up a layer at a time; the head a block of columns at a time
(261,120 x 5,120 in float32 is 5.3 GB, which does not fit beside the
weights).
"""

import jax
import jax.numpy as jnp
import numpy as np

SUPPORTED = dict(attention="mha", norm="rmsnorm", activation="swiglu",
                 positional="rope", tie_embeddings=False)
LAYER = "mamba_attention"
PAD_TO = 512
HEAD_BLOCK = 32640      # columns of the head cast up at a time (261,120 / 8)


def check_supported(fields):
    """This reference is the falcon_h1 block; refuse a configuration it
    does not describe."""
    for key, want in SUPPORTED.items():
        if fields.get(key) != want:
            raise ValueError(
                f"benchmark/reference_falcon_h1.py implements the "
                f"falcon_h1 block ({SUPPORTED}); configuration has "
                f"{key}={fields.get(key)!r}. Add a reference for it.")
    if set(fields.get("layer_types") or ["?"]) != {LAYER} \
            or fields.get("moe_num_experts") \
            or fields.get("mamba_norm_before_gate") \
            or fields["mamba_n_heads"] % fields.get("mamba_n_groups", 1):
        raise ValueError(
            "benchmark/reference_falcon_h1.py: every layer a "
            f"{LAYER!r} layer with a dense MLP behind it, heads a whole "
            "multiple of groups, the gate ahead of the grouped norm, "
            "are part of the block")


class _Frozen(dict):
    """``fields`` as a static argument of a jitted function."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


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


def mup_vector(f):
    """``compute_mup_vector``: the five segments' multipliers over the
    projection's columns [z | x | B | C | dt]."""
    di = f["mamba_n_heads"] * f["mamba_d_head"]
    gn = f.get("mamba_n_groups", 1) * f["mamba_d_state"]
    return jnp.concatenate([
        jnp.full((w,), f.get(k, 1.0), jnp.float32) for w, k in (
            (di, "ssm_z_scale"), (di, "ssm_x_scale"), (gn, "ssm_b_scale"),
            (gn, "ssm_c_scale"), (f["mamba_n_heads"], "ssm_dt_scale"))])


def _state_space_half(h, lp, f, live):
    """(what the state-space half returns BEFORE ``ssm_out_scale``, the
    state S after the last live token [heads, d_head, d_state]).
    ``live`` [S]: False at a padded token, which leaves S as it was."""
    S = h.shape[0]
    nh, p, n = f["mamba_n_heads"], f["mamba_d_head"], f["mamba_d_state"]
    groups = f.get("mamba_n_groups", 1)
    di, gn = nh * p, groups * n
    proj = ((h * f.get("ssm_in_scale", 1.0)) @ lp["w_in"]) * mup_vector(f)
    z, xbc, dt = proj[:, :di], proj[:, di:2 * di + 2 * gn], \
        proj[:, 2 * di + 2 * gn:]
    xbc = _causal_conv(xbc, lp["conv"])
    if "conv_b" in lp:
        xbc = xbc + lp["conv_b"]
    xbc = jax.nn.silu(xbc)
    xs = xbc[:, :di].reshape(S, nh, p)
    # head h reads group h // (heads / groups)
    b = jnp.repeat(xbc[:, di:di + gn].reshape(S, groups, n),
                   nh // groups, axis=1)                     # [S, nh, n]
    c = jnp.repeat(xbc[:, di + gn:].reshape(S, groups, n),
                   nh // groups, axis=1)
    dt = jax.nn.softplus(dt + lp["dt_bias"])                 # [S, nh]
    a = -jnp.exp(lp["a_log"])

    def token(state, t):
        xt, bt, ct, dtt, on = t
        new = jnp.exp(dtt * a)[:, None, None] * state \
            + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
        return jnp.where(on, new, state), \
            jnp.einsum("hpn,hn->hp", new, ct)

    state, y = jax.lax.scan(token, jnp.zeros((nh, p, n), jnp.float32),
                            (xs, b, c, dt, live))
    y = (y + lp["d_skip"][:, None] * xs).reshape(S, di)
    # the gate BEFORE the norm (mamba_norm_before_gate false), the norm
    # a group of heads at a time under one weight
    y = (y * jax.nn.silu(z)).reshape(S, groups, di // groups)
    y = _rms_norm(y, lp["gate_norm"].reshape(groups, di // groups),
                  f["norm_eps"]).reshape(S, di)
    return y @ lp["w_out"], state


def _rope(x, theta):
    """x [S, heads, d]: rotate_half over the whole head at positions
    0 .. S - 1."""
    S, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + rot * sin


def _attention_half(h, lp, f):
    """(what the attention half returns BEFORE ``attn_out_scale``, the
    rotated keys [S, kv_heads * head_dim] and the values, as a cache
    would hold them)."""
    S = h.shape[0]
    nh, nkv = f["num_heads"], f.get("num_kv_heads") or f["num_heads"]
    hd = f.get("head_dim_override") or f["hidden_size"] // nh
    hin = h * f.get("attn_in_scale", 1.0)
    q = (hin @ lp["wq"]).reshape(S, nh, hd)
    k = ((hin @ lp["wk"]) * f.get("key_scale", 1.0)).reshape(S, nkv, hd)
    v = (hin @ lp["wv"]).reshape(S, nkv, hd)
    q, k = _rope(q, f["rope_theta"]), _rope(k, f["rope_theta"])
    kk, vv = (jnp.repeat(t, nh // nkv, axis=1) for t in (k, v))
    scores = jnp.einsum("qhd,khd->hqk", q, kk) * hd ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf),
                           axis=-1)
    o = jnp.einsum("hqk,khd->qhd", probs, vv).reshape(S, nh * hd)
    return o @ lp["wo"], k.reshape(S, nkv * hd), v.reshape(S, nkv * hd)


def _mixers(x, stack, i, live, f):
    """The stream after layer ``i``'s two mixers, the state-space
    half's final state, the attention half's keys and values."""
    lp = jax.tree.map(lambda a: _f32(a[i]), stack)
    h = _rms_norm(x, lp["attn_norm"], f["norm_eps"])
    m, state = _state_space_half(h, lp, f, live)
    a, k, v = _attention_half(h, lp, f)
    x = x + m * f.get("ssm_out_scale", 1.0) \
        + a * f.get("attn_out_scale", 1.0)
    return x, state, k, v


_mixers_jit = jax.jit(_mixers, static_argnums=(4,))


def _mlp(x, stack, i, f):
    lp = jax.tree.map(lambda a: _f32(a[i]), stack)
    g = _rms_norm(x, lp["mlp_norm"], f["norm_eps"])
    y = (g @ lp["w_up"]) * jax.nn.silu(
        (g @ lp["w_gate"]) * f.get("mlp_gate_scale", 1.0))
    return x + (y @ lp["w_down"]) * f.get("mlp_down_scale", 1.0)


_mlp_jit = jax.jit(_mlp, static_argnums=(3,))


@jax.jit
def _normed(x, w, eps):
    return _rms_norm(x, _f32(w), eps)


@jax.jit
def _head_block(x, block, scale):
    return (x @ _f32(block)) / scale


def _padded(ids):
    ids = np.asarray(ids)
    S = len(ids)
    live = np.arange(S + (-S % PAD_TO)) < S
    return S, jnp.asarray(np.pad(ids, (0, -S % PAD_TO)), jnp.int32), \
        jnp.asarray(live)


def _layers(params, fields, ids, layers=None, mixers_only=False):
    """The padded residual stream after ``layers`` layers (None: all),
    with every layer's (state, keys, values); ``mixers_only``: the last
    of them stops behind its mixers."""
    f = _Frozen(fields)
    n = len(fields["layer_types"]) if layers is None else layers
    S, ids, live = _padded(ids)
    x = fields.get("embed_scale", 1.0) * _f32(params["embed"][ids])
    kept = []
    for i in range(n):
        x, state, k, v = _mixers_jit(x, params["hybrid_layers"], i, live, f)
        kept.append((state, k[:S], v[:S]))
        if mixers_only and i + 1 == n:
            break
        x = _mlp_jit(x, params["layers"], i, f)
    return x, S, kept


def leading_states(params, fields, ids, layers=1):
    """[layers, heads, d_head, d_state] float32: the recurrent state S
    after the last token of ``ids`` in the first ``layers`` layers'
    state-space halves. The block has no router, so every layer's state
    may be judged; the runner judges layer 0's."""
    check_supported(fields)
    with jax.default_matmul_precision("highest"):
        kept = _layers(params, fields, ids, layers, mixers_only=True)[2]
    return jnp.stack([state for state, _, _ in kept])


def leading_kv(params, fields, ids, layers=1):
    """(keys, values), each [layers, S, kv_heads * head_dim] float32:
    what a cache holds of ``ids`` in the first ``layers`` layers'
    attention halves: the keys times ``key_scale`` and rotated, the
    values as projected."""
    check_supported(fields)
    with jax.default_matmul_precision("highest"):
        kept = _layers(params, fields, ids, layers, mixers_only=True)[2]
    return (jnp.stack([k for _, k, _ in kept]),
            jnp.stack([v for _, _, v in kept]))


def logits(params, fields, ids):
    """[S, vocab] float32 logits of one sequence ``ids`` [S]."""
    check_supported(fields)
    with jax.default_matmul_precision("highest"):
        x, S, _ = _layers(params, fields, ids)
        x = _normed(x[:S], params["final_norm"], fields["norm_eps"])
        head = params["lm_head"]
        scale = fields.get("logit_scale", 1.0)
        return jnp.concatenate(
            [_head_block(x, head[:, at:at + HEAD_BLOCK], scale)
             for at in range(0, head.shape[1], HEAD_BLOCK)], axis=-1)


def next_token_loss(params, fields, ids):
    """Mean next-token cross-entropy of one sequence, float32."""
    lg = logits(params, fields, ids)[:-1]
    tgt = jnp.asarray(ids, jnp.int32)[1:]
    logz = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, tgt[:, None], axis=-1)[:, 0]
    return float(jnp.mean(logz - picked))
