"""Seeded weights of the ``dots3_note`` block (``reference_dots3.py``),
made by the benchmark: on the device, in one jitted call from
``--seed``, in the type they are served in, in the program's layout
(``embed``, ``lm_head``, ``final_norm``; ``mla_layers`` /
``mla_window_layers`` the mixers of a latent kind in layer order;
``lead_layers`` / ``layers`` the norm and MLP of the leading dense and
of the expert layers, the expert stack holding ``moe_experts_held``
experts under a router of ``moe_num_experts``).

Sized as ``weights_joyai.py`` sizes its block, so that every term moves
the logits and none hides the others:

* a matrix's spread is a gain over the root of its fan-in; the
  embedding has spread 1 and (i) a sub-layer adds 0.1 to 0.2 of that to
  the residual stream: each latent mixer 0.2 (values 0.9, the head gate
  halves them: ``wo`` 0.64, as ``weights_ling.py``'s gated latent
  mixer), the dense MLP and the shared expert 0.15 (0.26 on their down
  matrices), the held routed experts less (``e_down`` 0.224, of which
  about ONE of a token's eight picks lands on this chip's 32 of 256);
* the latents are RESCALED (``mla_lora_rescale``: a normed latent times
  sqrt(hidden / rank), 2.24 at rank 1,024 and 3.16 at 512), so the
  matrices that read them carry the gain ``weights_joyai.py`` gives
  them over that factor: queries, per-head keys and the shared rotated
  key part keep a spread of 1.36 each and values 0.9 (scores of spread
  about 1.85: a row of attention rests on a few positions);
* (ii) WHICH positions a full layer selects shows in what it adds:
  with scores of spread 1.85 over 2,048 positions the softmax puts
  about half its weight on the best 100 of them, and the indexer's
  score of a position is independent of the attention's (its matrices
  are seeded apart), so two sets of 2,048 that differ share those 100
  only by chance: dropping the lower-scored 1,024 of a token's 2,048
  (the cell's control) moves a full layer's output by 0.6-0.8 of its
  size, a tenth of the stream after two such layers, and a set picked
  from wrong keys moves it by all of it;
* the indexer: ``index_wq`` gain 1 over the rescaled latent's root
  (queries of spread 1), keys LayerNormed (weight 1 +- 0.1, bias of
  spread 0.1: neither a no-op), ``index_ww`` gain 1: a head's weight
  is of either sign, as a trained one may be, and a score is the sum of
  64 such terms, of spread about 1 / 3;
* router logits of spread 1.5, a selection bias of spread 0.02;
* norms 1 +- 0.1; the head's gain 2.5.
"""

import jax
import jax.numpy as jnp

from .reference_dots3 import KINDS, check_supported, sizes

GAIN = dict(wq_a=1.0, wq_b=1.36, wkv_a=1.36, wkv_b=1.1, wo=0.64, wg=1.5,
            index_wq=1.0, index_wk=1.0, index_ww=1.0,
            w_gate=1.0, w_up=1.0, w_down=0.26, moe_gate_w=1.5,
            e_gate=1.0, e_up=1.0, e_down=0.224, shared_gate=1.0,
            shared_up=1.0, shared_down=0.26, lm_head=2.5)
EMBED_STD = 1.0
NORM_STD = 0.1
BIAS_STD = 0.02
INDEX_BIAS_STD = 0.1
SERVED_AS = jnp.bfloat16


def shapes(fields):
    """``{stack: {leaf: (shape, kind, what its input was scaled by)}}``
    of the block's parameters."""
    f = fields
    h, v = f["hidden_size"], f["vocab_size"]
    lead = f.get("moe_first_dense_layers", 0)
    E, fe = f["moe_num_experts"], f["moe_intermediate_size"]
    held = f.get("moe_experts_held") or E
    fs = f["moe_shared_experts"] * fe
    kinds = [KINDS[t] for t in f["layer_types"]]

    def mixer(kind):
        n = kinds.count(kind)
        nh, rq, dc, dn, dr, dv, _ = sizes(f, kind)
        rs_q, rs_kv = (h / rq) ** 0.5, (h / dc) ** 0.5
        out = {"attn_norm": ((n, h), "norm", 1),
               "wq_a": ((n, h, rq), "wq_a", 1),
               "q_norm": ((n, rq), "norm", 1),
               "wq_b": ((n, rq, nh * (dn + dr)), "wq_b", rs_q),
               "wkv_a": ((n, h, dc + dr), "wkv_a", 1),
               "kv_norm": ((n, dc), "norm", 1),
               "wkv_b": ((n, dc, nh * (dn + dv)), "wkv_b", rs_kv),
               "wo": ((n, nh * dv, h), "wo", 1),
               "wg": ((n, h, nh), "wg", 1)}
        if kind == "mla":
            ih, d = f["index_n_heads"], f["index_head_dim"]
            out.update(
                index_wq=((n, rq, ih * d), "index_wq", rs_q),
                index_wk=((n, h, d), "index_wk", 1),
                index_k_norm=((n, d), "norm", 1),
                index_k_bias=((n, d), "index_bias", 1),
                index_ww=((n, h, ih), "index_ww", 1))
        return out

    n, ffn = f["num_layers"] - lead, f["intermediate_size"]
    return {
        "top": {"embed": ((v, h), "embed", 1),
                "lm_head": ((h, v), "lm_head", 1),
                "final_norm": ((h,), "norm", 1)},
        **{kind + "_layers": mixer(kind) for kind in dict.fromkeys(kinds)},
        "lead_layers": {"mlp_norm": ((lead, h), "norm", 1),
                        "w_gate": ((lead, h, ffn), "w_gate", 1),
                        "w_up": ((lead, h, ffn), "w_up", 1),
                        "w_down": ((lead, ffn, h), "w_down", 1)},
        "layers": {"mlp_norm": ((n, h), "norm", 1),
                   "moe_gate_w": ((n, h, E), "moe_gate_w", 1),
                   "moe_gate_bias": ((n, E), "bias", 1),
                   "e_gate": ((n, held, h, fe), "e_gate", 1),
                   "e_up": ((n, held, h, fe), "e_up", 1),
                   "e_down": ((n, held, fe, h), "e_down", 1),
                   "shared_gate": ((n, h, fs), "shared_gate", 1),
                   "shared_up": ((n, h, fs), "shared_up", 1),
                   "shared_down": ((n, fs, h), "shared_down", 1)}}


def _draw(key, shape, kind, scaled, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    if kind == "norm":
        x = 1.0 + NORM_STD * x
    elif kind == "bias":
        x = BIAS_STD * x
    elif kind == "index_bias":
        x = INDEX_BIAS_STD * x
    elif kind == "embed":
        x = EMBED_STD * x
    else:
        x = GAIN[kind] / (scaled * shape[-2] ** 0.5) * x
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
        out = {stack: {leaf: _draw(keys[stack, leaf], *spec, dtype)
                       for leaf, spec in leaves.items()}
               for stack, leaves in tree.items()}
        return {**out.pop("top"), **out}

    return build(jnp.uint32(seed & 0x7FFFFFFF),
                 jnp.uint32((seed >> 31) & 0x7FFFFFFF))
