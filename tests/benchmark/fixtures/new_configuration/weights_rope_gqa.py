"""Seeded weights of the fixture block (``reference_rope_gqa.py``), in
the program's layout, on the device, in one jitted call of which the
seed is an argument. A matrix's spread is a gain over the root of its
fan-in, so that a toy width behaves as a wide one does; queries and keys
have a spread of 1.36 (scores of about 2: a row of attention rests on a
few keys, and a key at the wrong position shows); norms are 1 +- 0.1.
"""

import jax
import jax.numpy as jnp

from .reference_rope_gqa import check_supported

GAIN = dict(wq=1.36, wk=1.36, wv=0.9, wo=1.8, w_gate=0.9, w_up=0.9,
            w_down=0.9, lm_head=1.0)
EMBED_STD = 0.02
NORM_STD = 0.1


def shapes(fields):
    """``{name: shape}`` at the top and in ``layers``."""
    h, ffn = fields["hidden_size"], fields["intermediate_size"]
    L, v, nh = fields["num_layers"], fields["vocab_size"], \
        fields["num_heads"]
    hd = h // nh
    kv = (fields.get("num_kv_heads") or nh) * hd
    top = {"embed": (v, h), "final_norm": (h,), "lm_head": (h, v)}
    layers = {"attn_norm": (L, h), "mlp_norm": (L, h),
              "wq": (L, h, nh * hd), "wk": (L, h, kv), "wv": (L, h, kv),
              "wo": (L, nh * hd, h), "w_gate": (L, h, ffn),
              "w_up": (L, h, ffn), "w_down": (L, ffn, h)}
    return top, layers


def _draw(key, name, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("norm"):
        x = 1.0 + NORM_STD * x
    elif name == "embed":
        x = EMBED_STD * x
    else:
        x = GAIN[name] / shape[-2] ** 0.5 * x
    return x.astype(dtype)


def make(fields, seed, dtype=jnp.bfloat16):
    """The whole tree in ``dtype`` (left out: bfloat16, the type the
    fixture is served in). ``seed`` is any whole number, folded into the
    key 31 bits at a time."""
    check_supported(fields)
    top, layers = shapes(fields)
    seed = int(seed)
    names = sorted(top) + sorted(layers)

    @jax.jit
    def build(lo, hi):
        key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
        keys = dict(zip(names, jax.random.split(key, len(names))))
        out = {n: _draw(keys[n], n, s, dtype) for n, s in top.items()}
        out["layers"] = {n: _draw(keys[n], n, s, dtype)
                         for n, s in layers.items()}
        return out

    return build(jnp.uint32(seed & 0x7FFFFFFF),
                 jnp.uint32((seed >> 31) & 0x7FFFFFFF))
