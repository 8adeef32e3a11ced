"""Seeded weights of the ``bailing_hybrid`` block (``reference_ling.py``),
made by the benchmark: on the device, in one jitted call from
``--seed``, in the type they are served in, in the program's layout
(``embed``, ``lm_head``, ``final_norm``; ``kda_layers`` / ``mla_layers``
the mixers of a kind in layer order; ``lead_layers`` / ``layers`` the
norm and MLP of the leading dense and of the expert layers, the expert
stack holding ``moe_experts_held`` experts under a router of
``moe_num_experts``).

Sized as ``weights_joyai.py`` sizes its block, so that every term moves
the logits and none hides the others:

* a matrix's spread is a gain over the root of its fan-in; the
  embedding has spread 1 and a sub-layer adds 0.1 to 0.2 of that to the
  residual stream: a linear mixer 0.2 (its normed, gated heads are
  about 0.55 wide: ``wo`` 0.36), the latent mixer 0.2 (values 0.9, the
  gate halves them: ``wo`` 0.64), the dense MLP and the shared expert
  0.15 (0.26 on their down matrices), the held routed experts less:
  ``e_down`` 0.224 as in ``weights_joyai.py``, of which about two of a
  token's eight picks land on this chip's share. The mixers get the
  larger share: the state is what this block adds, and a state kept in
  a lower precision (the cell's control) has to show;
* a linear mixer's q, k and v projections have gain 1 (q and k are
  l2-normalised behind the convolution); the taps are uniform in
  +-taps^-1/2, the depthwise convolution's default initialisation;
  ``a_log`` = log U(1, 16) a head and ``dt_bias`` the inverse softplus
  of exp U(log 0.001, log 0.1) a channel, the ranges the ``kda`` layer
  of flash-linear-attention initialises them in; the decay's projection
  ``wf`` has gain 1, so that most channels decay slowly (a long memory,
  in which a rounded state's error adds up) and the few whose ``wf x``
  is large forget within a few tokens; ``wb`` and ``wg`` have gain 1.5
  (sigmoids from 0.1 to 0.9);
* the latent mixer as ``weights_joyai.py``'s, without the bottleneck:
  ``wq`` 1.36, ``wkv_a`` 1.36, ``wkv_b`` 1.1 (no outlier channels: this
  cell's control is not an 8-bit pool);
* router logits of spread 1.5, a selection bias of spread 0.02;
* norms 1 +- 0.1; the head's gain 2.5.
"""

import math

import jax
import jax.numpy as jnp

from .reference_ling import check_supported, layer_kinds

GAIN = dict(wq=1.0, wk=1.0, wv=1.0, wf=1.0, wb=1.5, wg=1.5, kda_wo=0.36,
            mla_wq=1.36, wkv_a=1.36, wkv_b=1.1, mla_wo=0.64,
            w_gate=1.0, w_up=1.0, w_down=0.26, moe_gate_w=1.5,
            e_gate=1.0, e_up=1.0, e_down=0.224, shared_gate=1.0,
            shared_up=1.0, shared_down=0.26, lm_head=2.5)
EMBED_STD = 1.0
NORM_STD = 0.1
BIAS_STD = 0.02
A_RANGE = (1.0, 16.0)
DT_RANGE = (0.001, 0.1)
SERVED_AS = jnp.bfloat16


def shapes(fields):
    """``{stack: {leaf: (shape, kind)}}`` of the block's parameters."""
    f = fields
    h, v, nh = f["hidden_size"], f["vocab_size"], f["num_heads"]
    lead = f.get("moe_first_dense_layers", 0)
    E, fe = f["moe_num_experts"], f["moe_intermediate_size"]
    held = f.get("moe_experts_held") or E
    fs = f["moe_shared_experts"] * fe
    qk = f["qk_nope_head_dim"] + f["qk_rope_head_dim"]
    kinds = layer_kinds(f)
    nl, na = kinds.count("kda"), kinds.count("mla")
    d = f["linear_head_dim"]
    D, K = nh * d, f.get("linear_conv_size", 4)
    n, ffn = f["num_layers"] - lead, f["intermediate_size"]
    return {
        "top": {"embed": ((v, h), "embed"), "lm_head": ((h, v), "lm_head"),
                "final_norm": ((h,), "norm")},
        "kda_layers": {
            "attn_norm": ((nl, h), "norm"),
            "wq": ((nl, h, D), "wq"), "wk": ((nl, h, D), "wk"),
            "wv": ((nl, h, D), "wv"), "wf": ((nl, h, D), "wf"),
            "wb": ((nl, h, nh), "wb"), "wg": ((nl, h, nh), "wg"),
            "conv": ((nl, K, 3 * D), "taps"),
            "a_log": ((nl, nh), "a_log"), "dt_bias": ((nl, D), "dt_bias"),
            "o_norm": ((nl, d), "norm"), "wo": ((nl, D, h), "kda_wo")},
        "mla_layers": {
            "attn_norm": ((na, h), "norm"),
            "wq": ((na, h, nh * qk), "mla_wq"),
            "wkv_a": ((na, h, f["kv_lora_rank"] + f["qk_rope_head_dim"]),
                      "wkv_a"),
            "kv_norm": ((na, f["kv_lora_rank"]), "norm"),
            "wkv_b": ((na, f["kv_lora_rank"], nh * (
                f["qk_nope_head_dim"] + f["v_head_dim"])), "wkv_b"),
            "wg": ((na, h, nh), "wg"),
            "wo": ((na, nh * f["v_head_dim"], h), "mla_wo")},
        "lead_layers": {"mlp_norm": ((lead, h), "norm"),
                        "w_gate": ((lead, h, ffn), "w_gate"),
                        "w_up": ((lead, h, ffn), "w_up"),
                        "w_down": ((lead, ffn, h), "w_down")},
        "layers": {"mlp_norm": ((n, h), "norm"),
                   "moe_gate_w": ((n, h, E), "moe_gate_w"),
                   "moe_gate_bias": ((n, E), "bias"),
                   "e_gate": ((n, held, h, fe), "e_gate"),
                   "e_up": ((n, held, h, fe), "e_up"),
                   "e_down": ((n, held, fe, h), "e_down"),
                   "shared_gate": ((n, h, fs), "shared_gate"),
                   "shared_up": ((n, h, fs), "shared_up"),
                   "shared_down": ((n, fs, h), "shared_down")}}


def _draw(key, shape, kind, dtype):
    if kind == "taps":
        bound = shape[-2] ** -0.5
        x = jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    elif kind == "a_log":
        x = jnp.log(jax.random.uniform(key, shape, jnp.float32, *A_RANGE))
    elif kind == "dt_bias":
        lo, hi = (math.log(v) for v in DT_RANGE)
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        x = dt + jnp.log(-jnp.expm1(-dt))
    else:
        x = jax.random.normal(key, shape, jnp.float32)
        if kind == "norm":
            x = 1.0 + NORM_STD * x
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
    tree = {stack: leaves for stack, leaves in shapes(fields).items()
            if all(s[0] for s, _ in leaves.values())}
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
