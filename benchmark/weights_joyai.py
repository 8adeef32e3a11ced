"""Seeded weights of the DeepSeek-V3 block (``reference_joyai.py``),
made by the benchmark: on the device, in one jitted call from
``--seed``, in the type they are served in, in the program's layout
(``embed``, ``lm_head``, ``final_norm``, ``lead_layers`` [1, ...] the
leading dense stack, ``layers`` [n, ...] the expert stack).

The scales are chosen so that every term of the block moves the logits
(the program's own initialiser, 0.02 everywhere, a zero selection bias,
lets attention and the experts vanish beside the embedding):

* a matrix's spread is a gain over the root of its fan-in, so a toy
  width behaves as the published one does;
* the embedding has spread 1 and every sub-layer adds 0.12 to 0.2 to
  the residual stream (as reckoned from the gains): attention 0.2
  (values 0.9, ``wo`` 0.32), the dense MLP and the SHARED expert 0.15
  (0.26 on their down matrices), the ROUTED experts 0.12 (eight of them
  weighted ~0.31 each: 0.224 on ``e_down``): of like size, so none
  hides the others, and each a small part of the stream, as a
  sub-layer's update is in a trained model past its first layers. At
  0.7 each (the first weights tried) one expert swapped for its
  runner-up, which bf16 rounding did to one routing decision in twenty,
  moved a row's logits by 0.15 to 0.55 of their largest (my chip runs,
  PR 36: 11 rows of 16), and no limit could tell a sound run from a
  lower precision. At 0.15 each a swap still happens in one sound run
  in eight and reads 0.06-0.10, where the 8-bit control (whose fault is
  attention's) read 0.18-0.25: attention is given the larger share and
  the routed experts the smaller, so that the two stand further apart;
* queries, per-head keys and the shared rotated key part have a spread
  of 1.36 each, which gives scores a spread of about 1.85: a row of
  attention rests on a few positions, and a position read wrong shows;
* router logits have a spread of 1.5, so sigmoid scores run from 0.05
  to 0.95 and are not all near a half; the selection bias has a spread
  of 0.02, a few times the gaps among a token's best scores (the eight
  best of 256 lie 0.005 apart), so it changes which experts are chosen
  and does not choose them alone (at 0.1 a few experts with a large
  bias took most of the rows), and is no part of a weight;
* norms 1 +- 0.1: none is a no-op; the head's gain 2.5 spreads the
  logits by about that;
* the latent carries OUTLIER channels, one in 128 (every 128th lane of
  ``kv_norm`` is +-200 in place of 1 +- 0.1, and that lane's row of
  ``wkv_b`` is 200 times smaller, so the channel adds to keys and values
  what any other does), as trained transformers carry a few channels
  far above the rest (Dettmers et al. 2022, LLM.int8(),
  arXiv:2208.07339; the OPT block's weights put them in the key bias).
  The reference and a bf16 row do not see it; a row stored in 8 bits
  against its own largest value rounds every other lane to nothing,
  which is what makes the comparison tell an int8 latent pool from a
  bf16 one. At 40 the 8-bit row kept a third of each lane and read
  0.10-0.15 where a sound run whose rounding swapped one expert read
  0.068 (my chip runs, PR 36): too near to set a limit between.
"""

import jax
import jax.numpy as jnp

from .reference_joyai import check_supported

GAIN = dict(wq_a=1.0, wq_b=1.36, wkv_a=1.36, wkv_b=1.1, wo=0.32,
            w_gate=1.0, w_up=1.0, w_down=0.26, moe_gate_w=1.5,
            e_gate=1.0, e_up=1.0, e_down=0.224, shared_gate=1.0,
            shared_up=1.0, shared_down=0.26, lm_head=2.5)
EMBED_STD = 1.0
NORM_STD = 0.1
BIAS_STD = 0.02
LATENT_OUTLIER, LATENT_OUTLIER_EVERY = 200.0, 128
SERVED_AS = jnp.bfloat16


def shapes(fields):
    """``{stack: {leaf: (shape, kind)}}`` of the block's parameters."""
    f = fields
    h, v, nh = f["hidden_size"], f["vocab_size"], f["num_heads"]
    lead = f.get("moe_first_dense_layers", 0)
    E, fe = f["moe_num_experts"], f["moe_intermediate_size"]
    fs = f["moe_shared_experts"] * fe
    qk = f["qk_nope_head_dim"] + f["qk_rope_head_dim"]

    def attention(n):
        return {"attn_norm": ((n, h), "norm"),
                "wq_a": ((n, h, f["q_lora_rank"]), "wq_a"),
                "q_norm": ((n, f["q_lora_rank"]), "norm"),
                "wq_b": ((n, f["q_lora_rank"], nh * qk), "wq_b"),
                "wkv_a": ((n, h, f["kv_lora_rank"]
                           + f["qk_rope_head_dim"]), "wkv_a"),
                "kv_norm": ((n, f["kv_lora_rank"]), "latent_norm"),
                "wkv_b": ((n, f["kv_lora_rank"], nh * (
                    f["qk_nope_head_dim"] + f["v_head_dim"])), "wkv_b"),
                "wo": ((n, nh * f["v_head_dim"], h), "wo"),
                "mlp_norm": ((n, h), "norm")}

    n, ffn = f["num_layers"] - lead, f["intermediate_size"]
    return {
        "top": {"embed": ((v, h), "embed"), "lm_head": ((h, v), "lm_head"),
                "final_norm": ((h,), "norm")},
        "lead_layers": {**attention(lead),
                        "w_gate": ((lead, h, ffn), "w_gate"),
                        "w_up": ((lead, h, ffn), "w_up"),
                        "w_down": ((lead, ffn, h), "w_down")},
        "layers": {**attention(n),
                   "moe_gate_w": ((n, h, E), "moe_gate_w"),
                   "moe_gate_bias": ((n, E), "bias"),
                   "e_gate": ((n, E, h, fe), "e_gate"),
                   "e_up": ((n, E, h, fe), "e_up"),
                   "e_down": ((n, E, fe, h), "e_down"),
                   "shared_gate": ((n, h, fs), "shared_gate"),
                   "shared_up": ((n, h, fs), "shared_up"),
                   "shared_down": ((n, fs, h), "shared_down")}}


def _draw(key, shape, kind, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    if kind == "norm":
        x = 1.0 + NORM_STD * x
    elif kind == "latent_norm":
        outlier = jnp.arange(shape[-1]) % LATENT_OUTLIER_EVERY == 0
        x = jnp.where(outlier, LATENT_OUTLIER * jnp.sign(x),
                      1.0 + NORM_STD * x)
    elif kind == "wkv_b":
        outlier = jnp.arange(shape[-2]) % LATENT_OUTLIER_EVERY == 0
        x = GAIN[kind] / shape[-2] ** 0.5 * x / jnp.where(
            outlier, LATENT_OUTLIER, 1.0)[:, None]
    elif kind == "bias":
        x = BIAS_STD * x
    elif kind == "embed":
        x = EMBED_STD * x
    else:
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
    if not fields.get("moe_first_dense_layers", 0):
        del tree["lead_layers"]
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
