"""Seeded weights of the ``lfm2_moe`` block (``reference_lfm2.py``), made
by the benchmark: on the device, in one jitted call from ``--seed``, in
the type they are served in, in the program's layout (``embed`` (tied:
the head too), ``final_norm``; ``conv_layers`` / ``full_layers`` the
mixers of a kind in layer order; ``lead_layers`` / ``layers`` the norm
and MLP of the leading dense and of the expert layers).

Sized as ``weights_granite.py`` sizes its block, the other one with a
TIED table and no shared expert to lean on, so that every term moves the
logits and none hides the others:

* a matrix's spread is a gain over the root of its fan-in, so a toy
  width behaves as the published one does. The tied table has spread
  ``2.5 / sqrt(hidden)``: the logits (a unit-RMS normed stream against
  the table) have spread 2.5, the accepted cells' head gain, and the
  stream starts ``S = 2.5 / sqrt(hidden)`` wide;
* what a sub-layer adds is sized IN UNITS OF S, whatever the stream
  holds (every sub-layer reads a norm): a mixer 3, LAYER 0's MIXER 9
  (``FIRST_MIXER``), a dense MLP 3, the routed experts 0.75, so that the
  stream ends ~15 S wide. With a tied table the normed stream points
  at its own token's row by 1 / that width, and the token's own logit
  stands sqrt(hidden) / width spreads over the rest: 45 spreads with
  updates of a fifth of the stream (every greedy token then repeats the
  prompt's last, ``token_gap`` compares nothing: PERF.md section 6, PR
  49), 3.0 with these. The first mixer's 9 is the stream's stable part:
  it runs ahead of every routed expert, so no swapped expert moves it,
  and the routed experts are the smallest term, so a swap (one pick of
  four) moves the least. At 1.5 (the first weights tried) one pick
  swapped for its runner-up moved the stream into the next layer by 7 to
  9 % (``state_err`` of layers 2 and 3: 8.6e-2 where a row without a
  swap reads 7e-3), which is about the gap between a token's fourth and
  fifth score: every later layer then swaps with even odds, and the
  logits of a sound run read 0.15 to 0.20 of the largest (my chip runs,
  PR 65, seeds 6500000011-12), too near anything a fault would read;
* the conv mixer: ``w_in`` gain 1 on its three blocks (B, C and u are
  ~1 wide, the input gate ``B * u`` ~1), the taps normal of spread
  ``taps ** -1/2`` (the convolution of ~1-wide inputs is ~1 wide), the
  output gate ``C *`` keeps it ~1, so ``w_out``'s gain is the update;
* attention: ``wq`` / ``wk`` gain 1 (q and k are normed a head behind
  them), the q and k norms' weights 1.36 +- 10 % (scores of spread
  ~1.85, ``weights_joyai.py``'s: a row rests on some positions and not
  on all alike), ``wv`` gain 1; what the softmax returns is ~0.3 of the
  values' spread at contexts of 512 to 1,023 (read on the CPU), so
  ``wo``'s gain is 10 for an update of 3;
* a SwiGLU's product is 0.58 wide: the dense ``w_down`` 5.2; a token's
  four picks weigh ~0.25 each, their sum is 0.5 x 0.58 wide:
  ``e_down`` 2.6;
* router logits of spread 1.5; ``expert_bias`` of spread 0.03, under
  the gap between a token's fourth and fifth best score of 32, so that
  it changes picks (tests/unit/inference/test_lfm2_serving.py counts
  that it does) and does not choose them alone, and is no part of a
  weight;
* the other norms 1 +- 0.1.
"""

import jax
import jax.numpy as jnp

from .reference_lfm2 import check_supported, layer_kinds

HEAD_GAIN = 2.5
# a sub-layer's update in units of the embedding's spread: its output
# matrix's gain is this over what reaches that matrix (REACHES)
UPDATE = dict(w_out=3.0, wo=3.0, w_down=3.0, e_down=0.75)
REACHES = dict(w_out=1.0, wo=0.3, w_down=0.58, e_down=0.29)
FIRST_MIXER = 3.0
GAIN = dict(w_in=1.0, conv=1.0, wq=1.0, wk=1.0, wv=1.0, w_gate=1.0,
            w_up=1.0, e_gate=1.0, e_up=1.0, moe_gate_w=1.5)
NORM = dict(norm=1.0, qk_norm=1.36)
NORM_STD = 0.1
BIAS_STD = 0.03
SERVED_AS = jnp.bfloat16


def shapes(fields):
    """``{stack: {leaf: (shape, kind)}}`` of the block's parameters."""
    f = fields
    h, v, nh, nkv = f["hidden_size"], f["vocab_size"], f["num_heads"], \
        f["num_kv_heads"]
    hd = h // nh
    lead = f.get("moe_first_dense_layers", 0)
    E, fe = f["moe_num_experts"], f["moe_intermediate_size"]
    kinds = layer_kinds(f)
    n, ffn = f["num_layers"] - lead, f["intermediate_size"]
    nc, nf = kinds.count("conv"), kinds.count("full")
    return {
        "top": {"embed": ((v, h), "embed"), "final_norm": ((h,), "norm")},
        "conv_layers": {"attn_norm": ((nc, h), "norm"),
                        "w_in": ((nc, h, 3 * h), "w_in"),
                        "conv": ((nc, f["conv_taps"], h), "conv"),
                        "w_out": ((nc, h, h), "w_out")},
        "full_layers": {"attn_norm": ((nf, h), "norm"),
                        "wq": ((nf, h, nh * hd), "wq"),
                        "wk": ((nf, h, nkv * hd), "wk"),
                        "wv": ((nf, h, nkv * hd), "wv"),
                        "q_norm": ((nf, hd), "qk_norm"),
                        "k_norm": ((nf, hd), "qk_norm"),
                        "wo": ((nf, nh * hd, h), "wo")},
        "lead_layers": {"mlp_norm": ((lead, h), "norm"),
                        "w_gate": ((lead, h, ffn), "w_gate"),
                        "w_up": ((lead, h, ffn), "w_up"),
                        "w_down": ((lead, ffn, h), "w_down")},
        "layers": {"mlp_norm": ((n, h), "norm"),
                   "moe_gate_w": ((n, h, E), "moe_gate_w"),
                   "moe_gate_bias": ((n, E), "bias"),
                   "e_gate": ((n, E, h, fe), "e_gate"),
                   "e_up": ((n, E, h, fe), "e_up"),
                   "e_down": ((n, E, fe, h), "e_down")}}


def _draw(key, shape, kind, dtype, hidden, first_is_layer_0):
    x = jax.random.normal(key, shape, jnp.float32)
    spread = HEAD_GAIN / hidden ** 0.5          # the embedding's: S
    if kind in NORM:
        x = NORM[kind] * (1.0 + NORM_STD * x)
    elif kind == "bias":
        x = BIAS_STD * x
    elif kind == "embed":
        x = spread * x
    elif kind in UPDATE:
        x = UPDATE[kind] * spread / REACHES[kind] / shape[-2] ** 0.5 * x
        if kind == "w_out" and first_is_layer_0:
            x = x.at[0].multiply(FIRST_MIXER)
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
    hidden = fields["hidden_size"]
    conv_first = layer_kinds(fields)[0] == "conv"
    tree = {stack: leaves for stack, leaves in shapes(fields).items()
            if all(s[0] for s, _ in leaves.values())}
    names = [(stack, leaf) for stack in sorted(tree)
             for leaf in sorted(tree[stack])]
    seed = int(seed)

    @jax.jit
    def build(lo, hi):
        key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
        keys = dict(zip(names, jax.random.split(key, len(names))))
        out = {stack: {leaf: _draw(keys[stack, leaf], s, k, dtype, hidden,
                                   conv_first)
                       for leaf, (s, k) in leaves.items()}
               for stack, leaves in tree.items()}
        return {**out.pop("top"), **out}

    return build(jnp.uint32(seed & 0x7FFFFFFF),
                 jnp.uint32((seed >> 31) & 0x7FFFFFFF))
