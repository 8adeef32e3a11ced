"""Seeded weights of the ``nemotron_h`` block (``reference_nemotron.py``),
made by the benchmark: on the device, in one jitted call from
``--seed``, in the type they are served in, in the program's layout
(``embed``, ``lm_head`` (UNTIED), ``final_norm``; ``ssm_layers`` /
``full_layers`` the mixers of a kind in layer order; ``layers`` the
norm, router, held experts and shared expert of the EXPERT layers alone,
the expert stack holding ``moe_experts_held`` experts under a router of
``moe_num_experts``).

Sized as ``weights_ling.py`` sizes its block (PERF.md section 4's rule:
the table is untied, so ``granite``'s departure does not carry over), so
that every term moves the logits and none hides the others:

* a matrix's spread is a gain over the root of its fan-in; the
  embedding has spread 1 and a sub-layer adds 0.1 to 0.2 of that to the
  stream. A mamba mixer 0.2: its group-normed, gated output is 1 wide,
  ``w_out`` 0.2 (the mixers get the larger share: the state is what the
  cell's control rounds, and it has to show). The attention mixer 0.2:
  no position signal and scores ``q k^T / sqrt(128)``, so ``wq`` and
  ``wk`` 1.4 give the scores a spread of ~2 (a softmax that chooses
  among a few hundred positions and is not their mean), ``wv`` 1; the
  values' weighted mean is then ~0.3 wide at contexts of 256 to 640
  (read on the CPU at published widths), ``wo`` 0.7;
* a relu^2 expert's output grows with the SQUARE of its input's
  spread: with ``e_up`` 1 the hidden ``u = W1 x`` is 1 wide,
  ``relu(u)^2`` has mean 1/2 and root mean square sqrt(3/2) = 1.22, and
  ``W2`` at gain g gives 1.22 g. The shared expert (3,712 wide, the same
  form) adds 0.15: ``shared_up`` 1, ``shared_down`` 0.12. A token's six
  picks weigh ``2.5 s_e / sum`` ~ 0.42 each and about three of them land
  on this chip's half: 0.42 x sqrt(3) x 1.22 g = 0.89 g. The held routed
  experts add LESS than the rule's 0.1, as ``weights_ling.py``'s do:
  ``e_down`` 0.075, 0.067 of the stream. At 0.13 (0.12 of the stream:
  the first build) a swapped expert, which is the pick that left AND
  the pick that came, each 0.42 x 1.22 x 0.13 = 0.067 wide and
  heavy-tailed (a fourth moment of the input), moved the stream by a
  tenth a layer, and four seeds on the chip read ``token_gap`` to 0.122
  and ``logit_err`` to 0.109 where the accepted sparse cells' largest
  are 0.0996 and 0.0844 (my chip runs, PR 52: PERF.md section 6): a hard
  top-6 of 128 in bf16 swaps an expert against a float32 reference
  whatever the weights, so the weights make a swap cost what it costs
  the accepted cells (0.054 here, ling's 0.05);
* a mamba mixer's ``w_in`` by its four column blocks, as
  ``weights_granite.py``: z and x gain 1, B and C (all groups) gain 3
  (behind the taps and SiLU they are ~1 wide, so that what the state
  returns, ``S C``, stands beside the skip term ``D x`` and a state kept
  in a lower precision shows), dt gain 1; the taps uniform in
  +-taps^-1/2 with a bias of spread 0.02; ``a_log`` = log U(1, 16),
  ``dt_bias`` the inverse softplus of exp U(log 0.001, log 0.1), ``D`` =
  1: the ranges Mamba-2 initialises them in (``time_step_min`` /
  ``time_step_max`` of the source's config are these);
* router logits of spread 1.5, a selection bias of spread 0.02;
  norms 1 +- 0.1; the head's gain 2.5.
"""

import math

import jax
import jax.numpy as jnp

from .reference_nemotron import check_supported, layer_kinds

GAIN = dict(w_out=0.2, wq=1.4, wk=1.4, wv=1.0, wo=0.7, moe_gate_w=1.5,
            e_up=1.0, e_down=0.075, shared_up=1.0, shared_down=0.12,
            lm_head=2.5)
W_IN_GAIN = dict(z=1.0, x=1.0, bc=3.0, dt=1.0)
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
    nkv = f.get("num_kv_heads") or nh
    hd = f.get("head_dim_override") or h // nh
    E, fe = f["moe_num_experts"], f["moe_intermediate_size"]
    held = f.get("moe_experts_held") or E
    fs = f["moe_shared_experts"] * fe
    kinds = layer_kinds(f)
    ns, na, ne = (kinds.count(k) for k in ("ssm", "full", "moe"))
    mh, ds = f["mamba_n_heads"], f["mamba_d_state"]
    di, K = mh * f["mamba_d_head"], f.get("mamba_d_conv", 4)
    dc = di + 2 * f.get("mamba_n_groups", 1) * ds
    return {
        "top": {"embed": ((v, h), "embed"), "lm_head": ((h, v), "lm_head"),
                "final_norm": ((h,), "norm")},
        "ssm_layers": {
            "attn_norm": ((ns, h), "norm"),
            "w_in": ((ns, h, di + dc + mh), "w_in"),
            "conv": ((ns, K, dc), "taps"), "conv_b": ((ns, dc), "bias"),
            "dt_bias": ((ns, mh), "dt_bias"), "a_log": ((ns, mh), "a_log"),
            "d_skip": ((ns, mh), "one"), "gate_norm": ((ns, di), "norm"),
            "w_out": ((ns, di, h), "w_out")},
        "full_layers": {
            "attn_norm": ((na, h), "norm"),
            "wq": ((na, h, nh * hd), "wq"), "wk": ((na, h, nkv * hd), "wk"),
            "wv": ((na, h, nkv * hd), "wv"), "wo": ((na, nh * hd, h), "wo")},
        "layers": {"mlp_norm": ((ne, h), "norm"),
                   "moe_gate_w": ((ne, h, E), "moe_gate_w"),
                   "moe_gate_bias": ((ne, E), "bias"),
                   "e_up": ((ne, held, fe, h), "e_up"),
                   "e_down": ((ne, held, fe, h), "e_down"),
                   "shared_up": ((ne, h, fs), "shared_up"),
                   "shared_down": ((ne, fs, h), "shared_down")}}


def _draw(key, shape, kind, dtype, fields):
    if kind == "taps":
        bound = shape[-2] ** -0.5
        x = jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    elif kind == "a_log":
        x = jnp.log(jax.random.uniform(key, shape, jnp.float32, *A_RANGE))
    elif kind == "dt_bias":
        lo, hi = (math.log(v) for v in DT_RANGE)
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        x = dt + jnp.log(-jnp.expm1(-dt))
    elif kind == "one":
        x = jnp.ones(shape, jnp.float32)
    else:
        x = jax.random.normal(key, shape, jnp.float32)
        if kind == "norm":
            x = 1.0 + NORM_STD * x
        elif kind == "bias":
            x = BIAS_STD * x
        elif kind == "embed":
            x = EMBED_STD * x
        elif kind == "w_in":
            di = fields["mamba_n_heads"] * fields["mamba_d_head"]
            bc = 2 * fields.get("mamba_n_groups", 1) * fields["mamba_d_state"]
            g = W_IN_GAIN
            gain = jnp.concatenate([
                jnp.full((di,), g["z"]), jnp.full((di,), g["x"]),
                jnp.full((bc,), g["bc"]),
                jnp.full((shape[-1] - 2 * di - bc,), g["dt"])])
            x = gain / shape[-2] ** 0.5 * x
        else:
            # ``e_up`` is stored out x in: its fan-in is its last axis
            x = GAIN[kind] / shape[-1 if kind == "e_up" else -2] ** 0.5 * x
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
    if not fields.get("mamba_conv_bias", True):
        del tree["ssm_layers"]["conv_b"]
    names = [(stack, leaf) for stack in sorted(tree)
             for leaf in sorted(tree[stack])]
    seed = int(seed)

    @jax.jit
    def build(lo, hi):
        key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
        keys = dict(zip(names, jax.random.split(key, len(names))))
        out = {stack: {leaf: _draw(keys[stack, leaf], s, k, dtype, fields)
                       for leaf, (s, k) in leaves.items()}
               for stack, leaves in tree.items()}
        return {**out.pop("top"), **out}

    return build(jnp.uint32(seed & 0x7FFFFFFF),
                 jnp.uint32((seed >> 31) & 0x7FFFFFFF))
