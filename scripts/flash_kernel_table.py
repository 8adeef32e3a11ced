#!/usr/bin/env python
"""Per-kernel times of ops/flash_attention.py on the current backend.

Prints, for the benchmark cells' two geometries and for the other shapes
that run the same kernels (head width 128, GQA, non-causal, sq != skv),
the milliseconds a call of the forward and of the backward takes alone
(``attention_autotune.time_kernels``: ``bwd`` is the whole backward, the
fused kernel where the shapes take it; where they do, ``pair_ms`` is the
dq + dk/dv pair forced on the same operands), beside the forward and
forward+backward times of the two flash kernels the installed jax ships
(``pallas.ops.tpu.flash_attention``, and ``splash_attention`` with its
two-kernel and with its fused backward, which sums dQ partials through
HBM) as yardsticks. ``--sweep`` adds one row per (block_q, block_kv)
candidate; ``flash_attention._auto_blocks`` is read off that. It needs the
chip: without one it exits naming the platform it found (``time_kernels``
itself runs on any backend, for its unit test).

    python scripts/flash_kernel_table.py [--sweep] [--steps 10] [--only NAME]
"""

import argparse
import json
import math
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops import flash_attention as fa
from deepspeed_tpu.ops.attention_autotune import (_inputs, _time_step,
                                                  time_kernels)

# name: (batch, heads, kv_heads, seq, head_dim, kv_seq, causal)
GEOMETRIES = {
    "opt-125m.train-dense": (32, 12, 12, 2048, 64, 2048, True),
    "opt-1.3b.zero3-dp4": (4, 32, 32, 2048, 64, 2048, True),
    "head128": (4, 16, 16, 2048, 128, 2048, True),
    "gqa4-head128": (4, 32, 8, 2048, 128, 2048, True),
    "non-causal": (4, 12, 12, 2048, 64, 2048, False),
    "sq1024-skv2048": (4, 12, 12, 1024, 64, 2048, True),
    "seq4096": (2, 12, 12, 4096, 64, 4096, True),
    # over 4,096 rows: more than one resident chunk, the gridded walk
    "seq8192-gqa2": (1, 12, 6, 8192, 64, 8192, True),
}
LIBRARY_BLOCK = 512
SWEEP = [(bq, bk) for bq in (128, 256, 512, 1024)
         for bk in (128, 256, 512, 1024, 2048)]


def _library(name, batch, heads, kv_heads, seq, head_dim, kv_seq, causal,
             steps):
    """Forward and forward+backward seconds of a jax library kernel, or
    the reason it does not take this geometry."""
    if kv_heads != heads or kv_seq != seq:
        return {"skipped": "MHA with sq == skv only"}
    q, k, v = _inputs(batch, heads, kv_heads, seq, head_dim, jnp.bfloat16)
    scale = 1.0 / math.sqrt(head_dim)
    blk = min(LIBRARY_BLOCK, seq)     # their default, 128, is 5x slower
    if name == "jax_flash":
        from jax.experimental.pallas.ops.tpu import flash_attention as lib
        sizes = lib.BlockSizes(
            block_q=blk, block_k_major=blk, block_k=blk, block_b=1,
            block_q_major_dkv=blk, block_k_major_dkv=blk, block_k_dkv=blk,
            block_q_dkv=blk, block_k_major_dq=blk, block_k_dq=blk,
            block_q_dq=blk)

        def attn(q, k, v):
            return lib.flash_attention(q, k, v, causal=causal,
                                       sm_scale=scale, block_sizes=sizes)
    else:
        from jax.experimental.pallas.ops.tpu.splash_attention import (
            splash_attention_kernel as sk, splash_attention_mask as sm)
        one = (sm.CausalMask((seq, seq)) if causal
               else sm.FullMask((seq, seq)))
        dq = ({"use_fused_bwd_kernel": True} if name == "jax_splash_fused"
              else {"block_q_dq": blk, "block_kv_dq": blk})
        kernel = sk.make_splash_mha(
            sm.MultiHeadMask([one] * heads), head_shards=1, q_seq_shards=1,
            block_sizes=sk.BlockSizes(
                block_q=blk, block_kv=blk, block_kv_compute=blk,
                block_q_dkv=blk, block_kv_dkv=blk, block_kv_dkv_compute=blk,
                **dq))

        def attn(q, k, v):
            return jax.vmap(kernel)(q * scale, k, v)

    def loss(q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2)

    try:
        return {
            "fwd": _time_step(jax.jit(attn), (q, k, v), steps),
            "fwd_bwd": _time_step(jax.jit(jax.grad(loss, argnums=(0, 1, 2))),
                                  (q, k, v), steps),
        }
    except Exception as e:  # noqa: BLE001 — a yardstick may refuse a shape
        return {"skipped": f"{type(e).__name__}: {str(e)[:120]}"}


def _ms(x):
    return {k: round(v * 1e3, 3) if isinstance(v, float) else v
            for k, v in x.items()}


def _pair(**kw):
    """``time_kernels`` with the fused backward refused: the pair."""
    budget, fa._FUSED_BWD_BYTES = fa._FUSED_BWD_BYTES, 0
    try:
        return time_kernels(**kw)
    finally:
        fa._FUSED_BWD_BYTES = budget


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--sweep", action="store_true")
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--only", action="append",
                   help="geometry name (repeatable); default all")
    p.add_argument("--no-library", action="store_true")
    args = p.parse_args(argv)

    from deepspeed_tpu.accelerator.tpu_accelerator import require_tpu
    dev = require_tpu()[0]
    print(json.dumps({"backend": jax.default_backend(),
                      "device_kind": dev.device_kind, "jax": jax.__version__}))
    for name in args.only or GEOMETRIES:
        b, h, hk, s, d, skv, causal = GEOMETRIES[name]
        row = {"geometry": name, "shape": [b, h, hk, s, skv, d],
               "causal": causal}
        kw = dict(batch=b, heads=h, kv_heads=hk, seq=s, head_dim=d,
                  kv_seq=skv, causal=causal, steps=args.steps)
        row["ours_ms"] = _ms(time_kernels(**kw))
        if "dq" not in row["ours_ms"]:
            row["pair_ms"] = _ms(_pair(**kw))
        if not args.no_library:
            for lib in ("jax_flash", "jax_splash", "jax_splash_fused"):
                row[f"{lib}_ms"] = _ms(_library(lib, b, h, hk, s, d, skv,
                                                causal, args.steps))
        print(json.dumps(row), flush=True)
        if args.sweep:
            for bq, bk in SWEEP:
                if bq > s or bk > skv:
                    continue
                try:
                    t = _ms(time_kernels(**kw, block_q=bq, block_kv=bk))
                except Exception as e:  # noqa: BLE001 — e.g. out of VMEM
                    t = {"refused": f"{type(e).__name__}: {str(e)[:120]}"}
                print(json.dumps({"geometry": name, "block_q": bq,
                                  "block_kv": bk, **t}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
