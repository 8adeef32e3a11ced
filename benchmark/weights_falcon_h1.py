"""Seeded weights of the ``falcon_h1`` block (``reference_falcon_h1.py``),
made by the benchmark: on the device, in one jitted call from ``--seed``,
in the type they are served in, in the program's layout (``embed``,
``lm_head``, ``final_norm``; ``hybrid_layers`` both halves' leaves of
every layer and their ONE ``attn_norm``; ``layers`` the MLP's norm and
three matrices).

Sized by the rule of the other untied blocks (``weights_smallthinker.py``:
every sub-layer adds 0.1 to 0.2 of the stream, so that every term moves
the logits and none hides the others), WITH THE BLOCK'S MULTIPLIERS
TAKEN INTO ACCOUNT: a matrix's spread is a gain over the root of its
fan-in, and where the block multiplies what the matrix makes by ``m``
the gain is the wanted spread over ``m``. So a multiplier that the
program dropped, or took twice, moves that term by 1 / m (4 to 90 times
at the published values), which ``logit_err`` reads:

* the embedding has spread 1 / ``embed_scale``: the stream starts 1
  wide; norms 1 +- 0.1; the head's gain is 2.5 x ``logit_scale``:
  logits of spread 2.5 (smallthinker's);
* the state-space half's ``w_in`` by its five column blocks, each over
  ``ssm_in_scale`` x its segment's multiplier: z and x 1 wide, B and C 3
  (behind the taps and SiLU ~1, so that what the state returns, ``S C``,
  stands beside the skip term ``D x``), dt 0.5; ``dt_bias`` the inverse
  softplus of exp U(log 0.0005, log 0.004) and ``A`` = -U(0.5, 2): a
  token's decay ``exp(dt A)`` lies between 0.98 and 0.9998, neither 0
  nor 1, and a head remembers from ~100 to a few thousand tokens, the
  cell's contexts (1,024 to 1,535) in the middle: what the heads of a
  model published for 262,144 positions are for, and the regime in
  which a state kept in a lower precision, the cell's control, SHOWS: a
  rounding of the whole state at every one-token update adds up over a
  head's memory, sqrt(memory) x 2^-9, where the inputs' own rounding
  does not grow with it. (Mamba-2's initial ranges, dt 0.001 to 0.1
  under ``A`` to 16, give a memory of ~12 tokens with these inputs: the
  control then read ``state_err`` 5.3e-3 to 6.9e-3 against the sound
  runs' 2.9e-3 to 3.1e-3 on three seeds, my chip runs, PR 62: no room
  for a limit between them; nemotron's cell met the same, PERF.md
  section 7.) The taps uniform in +- taps^-1/2 with a bias of spread
  0.02; ``D`` 1 +- 0.1; the gated norm's output is 1 wide, so ``w_out``
  has gain 0.15 /
  ``ssm_out_scale``;
* the attention half: ``wq`` 1.36 / ``attn_in_scale`` and ``wk`` 1.36 /
  (``attn_in_scale`` x ``key_scale``): scores of spread 1.85 over
  sqrt(head_dim), so a row rests on ~1 / 30 of its positions (33 of
  1,024); ``wv`` 1 / ``attn_in_scale``; a mean of values over that many
  positions has spread ~0.16, so ``wo`` has gain 1 / ``attn_out_scale``:
  the half adds ~0.15 of the stream at the compared positions;
* the MLP: ``w_gate`` 1 / ``mlp_gate_scale`` (a unit gate ahead of
  SiLU), ``w_up`` 1, ``w_down`` 0.34 / ``mlp_down_scale``: silu(g) u has
  spread 0.6, the MLP adds 0.2.
"""

import math

import jax
import jax.numpy as jnp

from .reference_falcon_h1 import check_supported

W_IN_SPREAD = dict(z=1.0, x=1.0, b=3.0, c=3.0, dt=0.5)
QK_SPREAD = 1.36
MIXER_ADDS = 0.15
MLP_DOWN = 0.34
HEAD_SPREAD = 2.5
NORM_STD = 0.1
BIAS_STD = 0.02
A_RANGE = (0.5, 2.0)
DT_RANGE = (0.0005, 0.004)
SERVED_AS = jnp.bfloat16


def shapes(fields):
    """``{stack: {leaf: (shape, kind)}}`` of the block's parameters."""
    f = fields
    h, v, nh = f["hidden_size"], f["vocab_size"], f["num_heads"]
    nkv = f.get("num_kv_heads") or nh
    hd = f.get("head_dim_override") or h // nh
    n, ffn = len(f["layer_types"]), f["intermediate_size"]
    mh, K = f["mamba_n_heads"], f.get("mamba_d_conv", 4)
    di = mh * f["mamba_d_head"]
    dc = di + 2 * f.get("mamba_n_groups", 1) * f["mamba_d_state"]
    return {
        "top": {"embed": ((v, h), "embed"), "lm_head": ((h, v), "lm_head"),
                "final_norm": ((h,), "norm")},
        "hybrid_layers": {
            "attn_norm": ((n, h), "norm"),
            "w_in": ((n, h, di + dc + mh), "w_in"),
            "conv": ((n, K, dc), "taps"), "conv_b": ((n, dc), "bias"),
            "dt_bias": ((n, mh), "dt_bias"), "a_log": ((n, mh), "a_log"),
            "d_skip": ((n, mh), "norm"), "gate_norm": ((n, di), "norm"),
            "w_out": ((n, di, h), "w_out"),
            "wq": ((n, h, nh * hd), "wq"), "wk": ((n, h, nkv * hd), "wk"),
            "wv": ((n, h, nkv * hd), "wv"), "wo": ((n, nh * hd, h), "wo")},
        "layers": {"mlp_norm": ((n, h), "norm"),
                   "w_gate": ((n, h, ffn), "w_gate"),
                   "w_up": ((n, h, ffn), "w_up"),
                   "w_down": ((n, ffn, h), "w_down")}}


def spreads(fields):
    """What each matrix's output is wide BEFORE the block's multiplier:
    the wanted spread over the multiplier the block lays on it."""
    f = lambda k: fields.get(k, 1.0)                      # noqa: E731
    a_in = f("attn_in_scale")
    return dict(
        wq=QK_SPREAD / a_in, wk=QK_SPREAD / (a_in * f("key_scale")),
        wv=1.0 / a_in, wo=1.0 / f("attn_out_scale"),
        w_out=MIXER_ADDS / f("ssm_out_scale"),
        w_gate=1.0 / f("mlp_gate_scale"), w_up=1.0,
        w_down=MLP_DOWN / f("mlp_down_scale"),
        lm_head=HEAD_SPREAD * f("logit_scale"))


def _w_in_spread(fields, width):
    di = fields["mamba_n_heads"] * fields["mamba_d_head"]
    gn = fields.get("mamba_n_groups", 1) * fields["mamba_d_state"]
    s_in = fields.get("ssm_in_scale", 1.0)
    return jnp.concatenate([
        jnp.full((w,), W_IN_SPREAD[k] / (s_in * fields.get(
            f"ssm_{k}_scale", 1.0)), jnp.float32)
        for w, k in ((di, "z"), (di, "x"), (gn, "b"), (gn, "c"),
                     (width - 2 * di - 2 * gn, "dt"))])


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
    else:
        x = jax.random.normal(key, shape, jnp.float32)
        if kind == "norm":
            x = 1.0 + NORM_STD * x
        elif kind == "bias":
            x = BIAS_STD * x
        elif kind == "embed":
            x = x / fields.get("embed_scale", 1.0)
        elif kind == "w_in":
            x = _w_in_spread(fields, shape[-1]) / shape[-2] ** 0.5 * x
        else:
            x = spreads(fields)[kind] / shape[-2] ** 0.5 * x
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
    if not fields.get("mamba_conv_bias", True):
        del tree["hybrid_layers"]["conv_b"]
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
