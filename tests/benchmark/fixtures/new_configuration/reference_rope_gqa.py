"""Plain reference of the fixture block that
``test_a_new_configuration_is_files_only`` adds to a copy of the
benchmark: a pre-norm decoder with RMSNorm, rotary positions (the
rotate-half convention: a head's first half pairs with its second),
grouped-query attention, a SwiGLU MLP, no biases and an untied head, in
straightforward ``jax.numpy`` float32 under ``highest``: no kernels, no
cache, no scan. It imports nothing of the program and nothing of the OPT
block's ``reference.py``; it keeps ``evidence.CONTRACT``.

Parameters are read in the program's layout (``embed``, ``final_norm``,
``lm_head`` [hidden, vocab] and ``layers`` whose leaves carry a leading
layer axis) and cast up a layer at a time.
"""

import jax
import jax.numpy as jnp

SUPPORTED = dict(norm="rmsnorm", activation="swiglu", positional="rope",
                 attn_bias=False, tie_embeddings=False)


def check_supported(fields):
    for key, want in SUPPORTED.items():
        if fields.get(key) != want:
            raise ValueError(
                f"benchmark/reference_rope_gqa.py implements {SUPPORTED}; "
                f"configuration has {key}={fields.get(key)!r}")


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """[S, heads, hd] rotated by position, a head's first half paired
    with its second."""
    S, _, hd = x.shape
    half = hd // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(x, lp, nh, nkv, theta, eps):
    """One decoder layer on [S, H] float32; ``lp`` is that layer's slice."""
    S = x.shape[0]
    h = _rms_norm(x, lp["attn_norm"], eps)
    q = _rope((h @ lp["wq"]).reshape(S, nh, -1), theta)
    k = _rope((h @ lp["wk"]).reshape(S, nkv, -1), theta)
    v = (h @ lp["wv"]).reshape(S, nkv, -1)
    # a group of nh / nkv query heads shares one key and value head
    k, v = (jnp.repeat(a, nh // nkv, axis=1) for a in (k, v))
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    x = x + jnp.einsum("hqk,khd->qhd", probs, v).reshape(S, -1) @ lp["wo"]
    h = _rms_norm(x, lp["mlp_norm"], eps)
    return x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) \
        @ lp["w_down"]


@jax.jit
def _embed(embed, ids):
    return _f32(embed[ids])


@jax.jit
def _head(x, final_norm, lm_head, eps):
    return _rms_norm(x, _f32(final_norm), eps) @ _f32(lm_head)


def _one_layer(x, layers, i, nh, nkv, theta, eps):
    lp = jax.tree.map(lambda a: _f32(a[i]), layers)
    return _layer(x, lp, nh, nkv, theta, eps)


_one_layer_jit = jax.jit(_one_layer, static_argnums=(3, 4, 5, 6))


def logits(params, fields, ids):
    """[S, vocab] float32 logits of one sequence ``ids`` [S]."""
    check_supported(fields)
    eps = float(fields.get("norm_eps", 1e-5))
    theta = float(fields.get("rope_theta", 10000.0))
    nh = fields["num_heads"]
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], jnp.asarray(ids, jnp.int32))
        for i in range(fields["num_layers"]):
            x = _one_layer_jit(x, params["layers"], i, nh,
                               fields.get("num_kv_heads") or nh, theta, eps)
        return _head(x, params["final_norm"], params["lm_head"], eps)


def next_token_loss(params, fields, ids):
    """Mean next-token cross-entropy of one sequence, float32."""
    lg = logits(params, fields, ids)[:-1]
    tgt = jnp.asarray(ids, jnp.int32)[1:]
    logz = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, tgt[:, None], axis=-1)[:, 0]
    return float(jnp.mean(logz - picked))
