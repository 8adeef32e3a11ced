"""Seeded weights of the OPT block, made by the benchmark: on the device,
in one jitted call from ``--seed``, in the type they are served in.

The plain reference may take nothing the program has made, so a served
cell's weights are the benchmark's own: the engine is handed them
(``InferenceEngineV2(..., params=...)``) and the reference, once the
engine is gone, makes the same tree again from the same seed. The layout
is the one ``reference.py`` reads (``embed``, ``pos_embed``,
``final_norm[_b]`` and ``layers`` with a leading layer axis).

The scales are chosen so that every term of the block moves the logits,
which is what lets a comparison of logits see a fault in any of them
(the program's own initialiser, 0.02 everywhere with zero biases and a
tied head, makes the current token its own best successor, by ~20 logits
as reckoned from the scales:
greedy decoding then repeats one token whatever the cache holds):

* a matrix's spread is a gain over the root of its fan-in, so that a
  toy width behaves as the published one does (at hidden 2048 the gains
  give 0.03 for ``wq`` and ``wk``, 0.02 for ``wv`` and ``w_up``, 0.04
  for ``wo``, 0.005 for ``w_down``);
* attention and the MLP each add about as much to the residual stream as
  the layers before them, so neither hides the other, and the
  embedding's own direction is a small part of the last hidden state:
  the best next token is not the current one;
* queries and keys have a spread of 1.36, which gives scores a spread of
  about 2: a row of attention rests on a few keys, and a key at the
  wrong position shows;
* biases 0.02 (larger ones add one fixed direction to every position's
  residual, and every position then prefers the same few tokens) and
  norms 1 +- 0.1: none is a no-op;
* the key bias carries OUTLIER channels, two a head of 64 (every 32nd
  channel) at +-40, some thirty times a key's spread, as OPT's own
  checkpoints carry a few channels far above the rest (Dettmers et al.
  2022, LLM.int8(), arXiv:2208.07339). A bias on the keys moves every
  score of a query alike, so the softmax, and the reference, do not see
  it; a cache that stores keys in fewer bits against their block's
  largest value does, which is what makes the comparison tell int8 keys
  from bf16 ones.
"""

import jax
import jax.numpy as jnp

from .reference import check_supported

MATRIX_GAIN = dict(wq=1.36, wk=1.36, wv=0.9, wo=1.8, w_up=0.9,
                   w_down=0.45)
EMBED_STD = 0.02
BIAS_STD = 0.02
NORM_STD = 0.1
KEY_OUTLIER, KEY_OUTLIER_EVERY = 40.0, 32


def shapes(fields):
    """``{path: (shape, kind)}`` of the OPT block's parameters; ``kind``
    picks the distribution."""
    h, ffn = fields["hidden_size"], fields["intermediate_size"]
    L, v = fields["num_layers"], fields["vocab_size"]
    top = {"embed": ((v, h), "embed"),
           "pos_embed": ((fields["max_seq_len"], h), "embed"),
           "final_norm": ((h,), "norm"), "final_norm_b": ((h,), "bias")}
    layers = {"attn_norm": ((L, h), "norm"), "attn_norm_b": ((L, h), "bias"),
              "mlp_norm": ((L, h), "norm"), "mlp_norm_b": ((L, h), "bias"),
              "wq": ((L, h, h), "wq"), "wk": ((L, h, h), "wk"),
              "wv": ((L, h, h), "wv"), "wo": ((L, h, h), "wo"),
              "b_q": ((L, h), "bias"), "b_k": ((L, h), "key_bias"),
              "b_v": ((L, h), "bias"), "b_o": ((L, h), "bias"),
              "w_up": ((L, h, ffn), "w_up"), "b_up": ((L, ffn), "bias"),
              "w_down": ((L, ffn, h), "w_down"), "b_down": ((L, h), "bias")}
    return top, layers


def _draw(key, shape, kind, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    if kind == "norm":
        x = 1.0 + NORM_STD * x
    elif kind == "bias":
        x = BIAS_STD * x
    elif kind == "key_bias":
        channel = jnp.arange(shape[-1]) % KEY_OUTLIER_EVERY == 0
        x = jnp.where(channel, KEY_OUTLIER * jnp.sign(x), BIAS_STD * x)
    elif kind == "embed":
        x = EMBED_STD * x
    else:
        x = MATRIX_GAIN[kind] / shape[-2] ** 0.5 * x
    return x.astype(dtype)


def make(fields, seed, dtype=jnp.bfloat16):
    """The whole tree in ``dtype``, one jitted call. ``seed`` is any
    whole number the driver gives (over 2**31 too): it is folded into
    the key 31 bits at a time."""
    check_supported(fields)
    top, layers = shapes(fields)
    seed = int(seed)
    names = sorted(top) + sorted(layers)

    # the seed is an ARGUMENT of the jitted call, not a constant in it:
    # one compiled program, and one entry of the persistent cache, serves
    # every seed
    @jax.jit
    def build(lo, hi):
        key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
        keys = dict(zip(names, jax.random.split(key, len(names))))
        out = {n: _draw(keys[n], s, k, dtype) for n, (s, k) in top.items()}
        out["layers"] = {n: _draw(keys[n], s, k, dtype)
                         for n, (s, k) in layers.items()}
        return out

    return build(jnp.uint32(seed & 0x7FFFFFFF),
                 jnp.uint32((seed >> 31) & 0x7FFFFFFF))
