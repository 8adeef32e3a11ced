"""Seeded weights of the ``afmoe`` block (``reference_trinity.py``), made
by the benchmark: on the device, in one jitted call from ``--seed``, in
the type they are served in, in the program's layout (``embed``,
``lm_head``, ``final_norm``; ``window_layers`` / ``full_layers`` the
per-head mixers of a kind in layer order; ``lead_layers`` / ``layers``
the norms and MLP of the leading dense and of the expert layers).

Sized as ``weights_joyai.py`` sizes its block, so that every term moves
the logits and none hides the others. What is this block's own: under
the sandwich scheme a sub-layer's output passes an RMSNorm before it
joins the stream, so ITS size is that norm's weight and no matrix's:

* the embedding has spread ``hidden_size ** -1/2``, so that the stream
  behind the block's own scale (``* sqrt(hidden_size)``) has spread 1;
  the post-norm of a mixer is 0.2 +- 10 % and of an MLP 0.15 +- 10 %: a
  sub-layer's update is 0.15 to 0.2 of the stream AFTER its post-norm;
* ``wq`` / ``wk`` have gain 1 (q and k are normed a head behind them);
  the q and k norms' learned weights are 1.36 +- 10 %, which gives
  scores a spread of about 1.85, ``weights_joyai.py``'s: a row of
  attention rests on some of its positions and not on all alike;
* ``wv`` has gain 1 and carries OUTLIER channels, lane 0 of every head
  (one lane in ``head_dim``) 30 times the rest, with the matching rows
  of ``wo`` 30 times smaller, so the channel adds to the output what any
  other does, as trained transformers carry a few channels far above the
  rest (Dettmers et al. 2022, arXiv:2208.07339; ``weights_joyai.py``
  puts them in the latent). The reference and a bf16 row do not see it;
  a page stored in 8 bits against its own largest value keeps 30 times
  fewer steps for every other lane, which is what makes the comparison
  tell an int8 pool from a bf16 one;
* the gate's ``wg`` has gain 1.5 (sigmoids from 0.1 to 0.9); ``wo`` 1;
* the dense MLP and the shared expert 0.26 on their down matrices, the
  routed experts 0.224 (eight of them weighted ~0.35 each): of like
  size inside the MLP's post-normed 0.15;
* router logits of spread 1.5, a selection bias of spread 0.02;
* the other norms 1 +- 0.1; the head's gain 2.5.
"""

import jax
import jax.numpy as jnp

from .reference_trinity import check_supported, layer_kinds

GAIN = dict(wq=1.0, wk=1.0, wv=1.0, wg=1.5, wo=1.0,
            w_gate=1.0, w_up=1.0, w_down=0.26, moe_gate_w=1.5,
            e_gate=1.0, e_up=1.0, e_down=0.224, shared_gate=1.0,
            shared_up=1.0, shared_down=0.26, lm_head=2.5)
NORM = dict(norm=1.0, qk_norm=1.36, post_attn=0.2, post_mlp=0.15)
NORM_STD = 0.1
BIAS_STD = 0.02
VALUE_OUTLIER = 30.0
SERVED_AS = jnp.bfloat16


def shapes(fields):
    """``{stack: {leaf: (shape, kind)}}`` of the block's parameters."""
    f = fields
    h, v, nh, nkv = f["hidden_size"], f["vocab_size"], f["num_heads"], \
        f["num_kv_heads"]
    hd = f.get("head_dim_override") or h // nh
    lead = f.get("moe_first_dense_layers", 0)
    E, fe = f["moe_num_experts"], f["moe_intermediate_size"]
    fs = f["moe_shared_experts"] * fe
    kinds = layer_kinds(f)
    n, ffn = f["num_layers"] - lead, f["intermediate_size"]

    def mixer(m):
        return {"attn_norm": ((m, h), "norm"),
                "wq": ((m, h, nh * hd), "wq"),
                "wk": ((m, h, nkv * hd), "wk"),
                "wv": ((m, h, nkv * hd), "wv"),
                "wg": ((m, h, nh * hd), "wg"),
                "q_norm": ((m, hd), "qk_norm"),
                "k_norm": ((m, hd), "qk_norm"),
                "wo": ((m, nh * hd, h), "wo"),
                "attn_post_norm": ((m, h), "post_attn")}

    return {
        "top": {"embed": ((v, h), "embed"), "lm_head": ((h, v), "lm_head"),
                "final_norm": ((h,), "norm")},
        "window_layers": mixer(kinds.count("window")),
        "full_layers": mixer(kinds.count("full")),
        "lead_layers": {"mlp_norm": ((lead, h), "norm"),
                        "mlp_post_norm": ((lead, h), "post_mlp"),
                        "w_gate": ((lead, h, ffn), "w_gate"),
                        "w_up": ((lead, h, ffn), "w_up"),
                        "w_down": ((lead, ffn, h), "w_down")},
        "layers": {"mlp_norm": ((n, h), "norm"),
                   "mlp_post_norm": ((n, h), "post_mlp"),
                   "moe_gate_w": ((n, h, E), "moe_gate_w"),
                   "moe_gate_bias": ((n, E), "bias"),
                   "e_gate": ((n, E, h, fe), "e_gate"),
                   "e_up": ((n, E, h, fe), "e_up"),
                   "e_down": ((n, E, fe, h), "e_down"),
                   "shared_gate": ((n, h, fs), "shared_gate"),
                   "shared_up": ((n, h, fs), "shared_up"),
                   "shared_down": ((n, fs, h), "shared_down")}}


def _draw(key, shape, kind, dtype, hd):
    x = jax.random.normal(key, shape, jnp.float32)
    if kind in NORM:
        x = NORM[kind] * (1.0 + NORM_STD * x)
    elif kind == "bias":
        x = BIAS_STD * x
    elif kind == "embed":
        x = shape[-1] ** -0.5 * x
    else:
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
