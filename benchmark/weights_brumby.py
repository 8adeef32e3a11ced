"""Seeded weights of the ``brumby`` block (``reference_brumby.py``), made
by the benchmark: on the device, in one jitted call from ``--seed``, in
the type they are served in, in the program's layout (``embed``,
``lm_head``, ``final_norm``; ``retention_layers`` the power-retention
mixers and ``layers`` the norm and MLP of every layer, both in layer
order).

Sized so that every term moves the logits and none hides the others:

* the embedding has spread 1, the stream's; every other norm is 1 +- 10 %;
* ``wq`` / ``wk`` have gain 1 over the root of their fan-in (q and k are
  normed a head behind them); the q and k norms' learned weights are
  1.36 +- 10 %, ``weights_trinity.py``'s, so that ``q . k / sqrt(hd)``
  has spread ~1.85 and its square, the pair's weight, a mean of ~3.4
  with a long tail: a row of retention rests on some of its positions
  and not on all alike;
* THE DECAY: a layer's ``b_decay`` are the key/value heads' shares of
  [-3, 6.9], evenly spaced and handed to the heads in an order drawn
  from the seed (+- 0.1), so that in EVERY layer ``gamma = log
  sigmoid(.)`` spans -3.05 (a hard gate: a token's weight falls by 21
  times a position, and ``exp(-G)`` alone would overflow float32 after
  29 tokens) to -0.001 (a soft one: a memory of a thousand positions,
  in which a state rounded at every update adds its error up, which is
  what the cell's control shows), and ``w_decay`` has gain 1: a head's
  gate moves by a few times from token to token round its bias. Drawn
  independently a head, one layer in thirty would have no soft head
  (0.66^8) and its state would forget faster than a rounding adds up;
  drawn in a narrow range the decay would never be exercised;
* ``wv`` has gain 1 and ``wo`` 0.6: a head's output is a weighted mean
  of values, 0.1 (soft gate) to 1 (hard gate) wide, and the mixer's
  update 0.1 to 0.3 of the stream;
* the MLP's ``w_gate`` / ``w_up`` have gain 1 and ``w_down`` 0.5: an
  update of ~0.2 of the stream;
* the head's gain is 2.5.
"""

import jax
import jax.numpy as jnp

from .reference_brumby import check_supported

GAIN = dict(wq=1.0, wk=1.0, wv=1.0, wo=0.6, w_decay=1.0, w_gate=1.0,
            w_up=1.0, w_down=0.5, lm_head=2.5)
QK_NORM = 1.36
NORM_STD = 0.1
DECAY_BIAS = (-3.0, 6.9)
DECAY_JITTER = 0.1
SERVED_AS = jnp.bfloat16


def shapes(fields):
    """``{stack: {leaf: (shape, kind)}}`` of the block's parameters."""
    f = fields
    h, v, nh, nkv = f["hidden_size"], f["vocab_size"], f["num_heads"], \
        f["num_kv_heads"]
    hd = f.get("head_dim_override") or h // nh
    n, ffn = f["num_layers"], f["intermediate_size"]
    return {
        "top": {"embed": ((v, h), "embed"), "final_norm": ((h,), "norm"),
                "lm_head": ((h, v), "lm_head")},
        "retention_layers": {
            "attn_norm": ((n, h), "norm"),
            "wq": ((n, h, nh * hd), "wq"), "wk": ((n, h, nkv * hd), "wk"),
            "wv": ((n, h, nkv * hd), "wv"), "wo": ((n, nh * hd, h), "wo"),
            "q_norm": ((n, hd), "qk_norm"), "k_norm": ((n, hd), "qk_norm"),
            "w_decay": ((n, h, nkv), "w_decay"),
            "b_decay": ((n, nkv), "b_decay")},
        "layers": {"mlp_norm": ((n, h), "norm"),
                   "w_gate": ((n, h, ffn), "w_gate"),
                   "w_up": ((n, h, ffn), "w_up"),
                   "w_down": ((n, ffn, h), "w_down")}}


def parameters(fields):
    """How many parameters the tree holds."""
    total = 0
    for leaves in shapes(fields).values():
        for shape, _ in leaves.values():
            size = 1
            for s in shape:
                size *= s
            total += size
    return total


def _draw(key, shape, kind, dtype):
    if kind == "b_decay":
        # [layers, kv heads]: the range's even shares, a layer's in an
        # order of its own
        order, jitter = jax.random.split(key)
        spread = jnp.linspace(*DECAY_BIAS, shape[-1])
        x = jax.vmap(lambda k: jax.random.permutation(k, spread))(
            jax.random.split(order, shape[0])) \
            + DECAY_JITTER * jax.random.normal(jitter, shape, jnp.float32)
    else:
        x = jax.random.normal(key, shape, jnp.float32)
        if kind == "norm":
            x = 1.0 + NORM_STD * x
        elif kind == "qk_norm":
            x = QK_NORM * (1.0 + NORM_STD * x)
        elif kind != "embed":
            x = GAIN[kind] / shape[-2] ** 0.5 * x
    # the checkpoint is bf16 (SERVED_AS): an engine asked to serve it in
    # float32 (the rehearsal's) holds the same values, and so does the
    # reference, which makes the tree again in the default type
    return x.astype(SERVED_AS).astype(dtype)


def make(fields, seed, dtype=SERVED_AS):
    """The whole tree in ``dtype``, one jitted call. ``seed`` is any
    whole number the driver gives (over 2**31 too): it is folded into
    the key 31 bits at a time, and is an ARGUMENT of the jitted call, so
    one compiled program serves every seed."""
    check_supported(fields)
    tree = shapes(fields)
    names = [(stack, leaf) for stack in sorted(tree)
             for leaf in sorted(tree[stack])]
    seed = int(seed)

    @jax.jit
    def build(lo, hi):
        key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
        keys = dict(zip(names, jax.random.split(key, len(names))))
        out = {stack: {leaf: _draw(keys[stack, leaf], s, k, dtype)
                       for leaf, (s, k) in leaves.items()}
               for stack, leaves in tree.items()}
        return {**out.pop("top"), **out}

    return build(jnp.uint32(seed & 0x7FFFFFFF),
                 jnp.uint32((seed >> 31) & 0x7FFFFFFF))
