"""Plain reference: the pre-LN decoder block in straightforward
``jax.numpy`` float32 — no kernels, no KV cache, no batching tricks, no
scan. One copy serves every configuration whose file describes this block
(OPT: learned positions, LayerNorm, ReLU MLP, biases, tied head).

Follows the OPT paper (Zhang et al. 2022, arXiv:2205.01068) and the
``facebook/opt-*`` reference code. Departure: the published checkpoints
store positions with an offset of 2 (a padding artefact of fairseq); with
seeded random weights that is a relabelling of rows, so position p reads
row p here, as the program does.

Every call runs under ``jax.default_matmul_precision("highest")``: on a
TPU a float32 matmul otherwise runs in bf16 passes, which is the very
thing this is here to be compared against.

Parameters are read in the program's layout (a dict with ``embed``,
``pos_embed``, ``final_norm[_b]`` and ``layers`` whose leaves carry a
leading layer axis) and cast up one layer at a time, so a float32 copy of
the whole model never exists.
"""

import jax
import jax.numpy as jnp

SUPPORTED = dict(norm="layernorm", activation="relu", positional="learned",
                 attn_bias=True, tie_embeddings=True)


def check_supported(fields):
    """This reference is the OPT block; refuse a configuration it does
    not describe rather than compare against the wrong mathematics."""
    for key, want in SUPPORTED.items():
        if fields.get(key) != want:
            raise ValueError(
                f"benchmark/reference.py implements the OPT block "
                f"({SUPPORTED}); configuration has {key}="
                f"{fields.get(key)!r}. Add a reference for it.")


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _layer(x, lp, n_heads, eps):
    """One decoder layer on [S, H] float32; ``lp`` is that layer's slice."""
    S, H = x.shape
    hd = H // n_heads
    h = _layer_norm(x, lp["attn_norm"], lp["attn_norm_b"], eps)
    q = (h @ lp["wq"] + lp["b_q"]).reshape(S, n_heads, hd)
    k = (h @ lp["wk"] + lp["b_k"]).reshape(S, n_heads, hd)
    v = (h @ lp["wv"] + lp["b_v"]).reshape(S, n_heads, hd)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf),
                           axis=-1)
    o = jnp.einsum("hqk,khd->qhd", probs, v).reshape(S, H)
    x = x + o @ lp["wo"] + lp["b_o"]
    h = _layer_norm(x, lp["mlp_norm"], lp["mlp_norm_b"], eps)
    h = jax.nn.relu(h @ lp["w_up"] + lp["b_up"])
    return x + h @ lp["w_down"] + lp["b_down"]


@jax.jit
def _embed(embed, pos_embed, ids):
    return _f32(embed[ids]) + _f32(pos_embed[:ids.shape[0]])


def _one_layer(x, layers, i, n_heads, eps):
    lp = jax.tree.map(lambda a: _f32(a[i]), layers)
    return _layer(x, lp, n_heads, eps)


_one_layer_jit = jax.jit(_one_layer, static_argnums=(3, 4))


@jax.jit
def _head(x, final_w, final_b, embed, eps):
    x = _layer_norm(x, _f32(final_w), _f32(final_b), eps)
    return x @ _f32(embed).T


def logits(params, fields, ids):
    """[S, vocab] float32 logits of one sequence ``ids`` [S]."""
    check_supported(fields)
    eps = float(fields.get("norm_eps", 1e-5))
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        x = _embed(params["embed"], params["pos_embed"], ids)
        for i in range(fields["num_layers"]):
            x = _one_layer_jit(x, params["layers"], i, fields["num_heads"],
                               eps)
        return _head(x, params["final_norm"], params["final_norm_b"],
                     params["embed"], eps)


def next_token_loss(params, fields, ids):
    """Mean next-token cross-entropy of one sequence, float32."""
    lg = logits(params, fields, ids)[:-1]
    tgt = jnp.asarray(ids, jnp.int32)[1:]
    logz = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, tgt[:, None], axis=-1)[:, 0]
    return float(jnp.mean(logz - picked))
