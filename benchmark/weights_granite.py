"""Seeded weights of the ``granitemoehybrid`` block
(``reference_granite.py``), made by the benchmark: on the device, in one
jitted call from ``--seed``, in the type they are served in, in the
program's layout (``embed`` (tied: the head too), ``final_norm``;
``ssm_layers`` / ``full_layers`` the mixers of a kind in layer order;
``layers`` the norm, router, held experts and shared expert of every
layer, the expert stack holding ``moe_experts_held`` experts under a
router of ``moe_num_experts``).

Sized as ``weights_ling.py`` sizes its block, so that every term moves
the logits and none hides the others, with the block's multipliers
taken into account:

* a matrix's spread is a gain over the root of its fan-in. The TIED
  table has spread 0.02, a muP model's: the stream starts at 12 x 0.02
  = 0.24 and the logits (a unit-RMS normed stream against the table,
  over 16) have spread 0.02 sqrt(hidden) / 16, 0.08 at hidden 4,096;
* every sub-layer adds about 1 to the stream AFTER the residual
  multiplier 0.22, whatever the stream holds, and LAYER 0's MIXER 3
  (``FIRST_MIXER``): a mixer 1.0, the shared expert 0.75, the held
  routed experts 0.4. The stream ends ~5 wide. ISSUE 49 asked for 0.1
  to 0.2 OF THE STREAM; with a tied table that leaves 12 x embed[id]
  the stream's main part to the end, the normed stream then points at
  its own token's row, and that token's logit stands 50 spreads over
  the rest: every greedy token is the prompt's last, ``token_gap``
  compares nothing and ``logit_err`` reads 6e-4 whatever the program
  does (my chip run, PR 49: PERF.md section 6). With updates as wide
  as the stream the token's own logit is ~3 spreads, inside the largest
  of 50,176, and about half of the served tokens repeat their
  predecessor. The first mixer's 3 is the stream's stable part: it
  runs ahead of every routed expert, so no swapped expert moves it,
  and what a swap in layers 0 and 1 moves is a tenth of the stream
  where it was a fifth (all mixers 1.0 and the routed experts 0.67
  read ``logit_err`` 0.06 to 0.15 and ``token_gap`` to 0.23 on two
  seeds; this reads 0.013 to 0.033 and to 0.035 on four). So: a mamba
  mixer's gated norm's output is 1 wide, ``w_out`` 4.5; the attention
  mixer's scores are q k^T / 128 where a softmax wants spread ~2 over a
  thousand positions, ``wq`` and ``wk`` 4.5, and the values' mean over
  the positions that then carry weight is a tenth of their spread,
  ``wv`` 4 and ``wo`` 6 (read at a context of 1,024 on the CPU);
  ``shared_down`` 6.1; a token's ten picks weigh ~0.1 each and about
  five land on this chip's share, ``e_down`` 11.7;
* a mamba mixer's ``w_in`` by its four column blocks: z and x gain 1,
  B and C gain 3 (behind the taps and SiLU they are ~1 wide, so that
  what the state returns, ``S C``, stands beside the skip term ``D x``
  and a state kept in a lower precision, the cell's control, shows), dt
  gain 1 (``dt = softplus(. + dt_bias)`` then varies a few times round
  the bias's 0.001 to 0.1: most heads decay slowly, a long memory in
  which a rounded state's error adds up); the taps uniform in
  +-taps^-1/2 with a bias of spread 0.02; ``a_log`` = log U(1, 16),
  ``dt_bias`` the inverse softplus of exp U(log 0.001, log 0.1), ``D`` =
  1: the ranges Mamba-2 initialises them in;
* router logits of spread 1.5; norms 1 +- 0.1.
"""

import math

import jax
import jax.numpy as jnp

from .reference_granite import check_supported, layer_kinds

GAIN = dict(w_out=4.5, wq=4.5, wk=4.5, wv=4.0, wo=6.0, moe_gate_w=1.5,
            e_gate=1.0, e_up=1.0, e_down=11.7, shared_gate=1.0,
            shared_up=1.0, shared_down=6.1)
W_IN_GAIN = dict(z=1.0, x=1.0, bc=3.0, dt=1.0)
FIRST_MIXER = 3.0
EMBED_STD = 0.02
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
    ns, na, n = kinds.count("ssm"), kinds.count("full"), len(kinds)
    mh, ds = f["mamba_n_heads"], f["mamba_d_state"]
    di, K = mh * f["mamba_d_head"], f.get("mamba_d_conv", 4)
    dc = di + 2 * ds
    return {
        "top": {"embed": ((v, h), "embed"), "final_norm": ((h,), "norm")},
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
        "layers": {"mlp_norm": ((n, h), "norm"),
                   "moe_gate_w": ((n, h, E), "moe_gate_w"),
                   "e_gate": ((n, held, h, fe), "e_gate"),
                   "e_up": ((n, held, h, fe), "e_up"),
                   "e_down": ((n, held, fe, h), "e_down"),
                   "shared_gate": ((n, h, fs), "shared_gate"),
                   "shared_up": ((n, h, fs), "shared_up"),
                   "shared_down": ((n, fs, h), "shared_down")}}


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
        elif kind == "w_out":
            first = jnp.where(jnp.arange(shape[0]) == 0, FIRST_MIXER, 1.0)
            x = GAIN[kind] * first[:, None, None] / shape[-2] ** 0.5 * x
        elif kind == "w_in":
            di = fields["mamba_n_heads"] * fields["mamba_d_head"]
            ds, g = fields["mamba_d_state"], W_IN_GAIN
            gain = jnp.concatenate([
                jnp.full((di,), g["z"]), jnp.full((di,), g["x"]),
                jnp.full((2 * ds,), g["bc"]),
                jnp.full((shape[-1] - 2 * di - 2 * ds,), g["dt"])])
            x = gain / shape[-2] ** 0.5 * x
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
