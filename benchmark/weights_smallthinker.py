"""Seeded weights of the SmallThinker block
(``reference_smallthinker.py``), made by the benchmark: on the device, in
one jitted call from ``--seed``, in the type they are served in, in the
program's layout (``embed``, ``lm_head``, ``final_norm``;
``window_layers`` / ``full_layers`` the per-head mixers of a kind in
layer order, each with its ``attn_norm``; ``layers`` the post-attention
norm ``mlp_norm``, the router and the experts of every layer).

On ``weights_trinity.py``'s design, so that every term moves the logits
and none hides the others. What is this block's own: it is PRE-norm with
no norm on a sub-layer's output, on q or on k, so a sub-layer's size is
its matrices', reckoned at the cell's contexts (a numpy reading at
published widths, 8,192 positions, PR 58, on the sandbox's CPU: counts,
not speeds):

* the embedding has spread 1; norms 1 +- 0.1; the head's gain 2.5;
* ``wq`` / ``wk`` have gain 1.36 each (nothing norms a head here), which
  gives scores a spread of 1.85, ``weights_trinity.py``'s: a row of
  attention rests on some of its positions and not on all alike (413 of
  8,192 on a full layer, 224 of 4,096 on a sliding one, by 1 / sum p^2);
* ``wv`` has gain 1 and carries OUTLIER channels, lane 0 of every head
  30 times the rest, with the matching rows of ``wo`` 30 times smaller
  (Dettmers et al. 2022, arXiv:2208.07339; ``weights_trinity.py``): the
  reference and a bf16 row do not see it; a page stored in 8 bits
  against its own largest value keeps 30 times fewer steps for every
  other lane, which is what makes the comparison tell an int8 pool from
  a bf16 one;
* a mean of values over that many positions has spread 0.087 (sliding)
  and 0.070 (full), so ``wo`` has gain 2.3: a sliding layer's mixer adds
  0.2 of the stream and a full layer's 0.16 at the compared positions
  (trinity's post-norm sets 0.2 whatever the heads return; here the
  first few hundred positions of a prompt, whose rows rest on few keys,
  take more);
* router logits of spread 1.5 (``moe_gate_w`` gain 1.5 on a unit normed
  input): the softmax over the six chosen gives weights from 0.08 to
  0.35, root of their squares' sum 0.48;
* ``e_gate`` / ``e_up`` gain 1, ``e_down`` 0.35: the routed experts add
  0.12 of the stream. RESCALED for the gate: an expert's output at gain
  1 has spread 0.706 under ReLU where SiLU gives 0.596 (E relu(z)^2 =
  0.5, E silu(z)^2 = 0.355: a ReLU gate passes 1.19 times what SiLU
  does, not half), and six weighted as above sum to 0.343, so trinity's
  0.224 (eight of ~0.35 behind a post-norm) became 0.12 / 0.343.
"""

import jax
import jax.numpy as jnp

from .reference_smallthinker import check_supported, layer_kinds

GAIN = dict(wq=1.36, wk=1.36, wv=1.0, wo=2.3, moe_gate_w=1.5,
            e_gate=1.0, e_up=1.0, e_down=0.35, lm_head=2.5)
NORM_STD = 0.1
VALUE_OUTLIER = 30.0
SERVED_AS = jnp.bfloat16


def shapes(fields):
    """``{stack: {leaf: (shape, kind)}}`` of the block's parameters."""
    f = fields
    h, v, nh, nkv = f["hidden_size"], f["vocab_size"], f["num_heads"], \
        f["num_kv_heads"]
    hd = f.get("head_dim_override") or h // nh
    E, fe, n = f["moe_num_experts"], f["moe_intermediate_size"], \
        f["num_layers"]
    kinds = layer_kinds(f)

    def mixer(m):
        return {"attn_norm": ((m, h), "norm"),
                "wq": ((m, h, nh * hd), "wq"),
                "wk": ((m, h, nkv * hd), "wk"),
                "wv": ((m, h, nkv * hd), "wv"),
                "wo": ((m, nh * hd, h), "wo")}

    return {
        "top": {"embed": ((v, h), "embed"), "lm_head": ((h, v), "lm_head"),
                "final_norm": ((h,), "norm")},
        "window_layers": mixer(kinds.count("window")),
        "full_layers": mixer(kinds.count("full")),
        "layers": {"mlp_norm": ((n, h), "norm"),
                   "moe_gate_w": ((n, h, E), "moe_gate_w"),
                   "e_gate": ((n, E, h, fe), "e_gate"),
                   "e_up": ((n, E, h, fe), "e_up"),
                   "e_down": ((n, E, fe, h), "e_down")}}


def _draw(key, shape, kind, dtype, hd):
    x = jax.random.normal(key, shape, jnp.float32)
    if kind == "norm":
        x = 1.0 + NORM_STD * x
    elif kind != "embed":       # the embedding has spread 1
        x = GAIN[kind] / shape[-2] ** 0.5 * x
        if kind == "wv":        # lane 0 of every head, far above the rest
            x = x * jnp.where(jnp.arange(shape[-1]) % hd == 0,
                              VALUE_OUTLIER, 1.0)
        elif kind == "wo":      # and what reads it as much smaller
            x = x / jnp.where(jnp.arange(shape[-2]) % hd == 0,
                              VALUE_OUTLIER, 1.0)[:, None]
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
    hd = fields.get("head_dim_override") \
        or fields["hidden_size"] // fields["num_heads"]
    tree = {stack: leaves for stack, leaves in shapes(fields).items()
            if all(s[0] for s, _ in leaves.values())}
    names = [(stack, leaf) for stack in sorted(tree)
             for leaf in sorted(tree[stack])]
    seed = int(seed)

    @jax.jit
    def build(lo, hi):
        key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
        keys = dict(zip(names, jax.random.split(key, len(names))))
        out = {stack: {leaf: _draw(keys[stack, leaf], s, k, dtype, hd)
                       for leaf, (s, k) in leaves.items()}
               for stack, leaves in tree.items()}
        return {**out.pop("top"), **out}

    return build(jnp.uint32(seed & 0x7FFFFFFF),
                 jnp.uint32((seed >> 31) & 0x7FFFFFFF))
