"""Break down where the v2 paged decode step spends its time on-chip.

r05 chip evidence showed paged serving at 56 tok/s vs 5232 dense — 93x.
This script times each layer of the stack separately so the fix targets
the real cost, not a guess:

  1. paged_attention Pallas kernel alone (one layer's shapes)
  2. the jnp gather fallback on the same shapes
  3. the full jitted paged_decode step (kernel on/off)
  4. one engine put() cycle (adds host scheduling + transfers)

Usage: python scripts/serving_profile.py [--batch 8]
"""

import argparse
import json
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def timeit(fn, *args, reps=20, warmup=3):
    import jax
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--out", default="artifacts/r05/serving_profile.json")
    args = ap.parse_args()

    from deepspeed_tpu.accelerator.tpu_accelerator import require_tpu
    require_tpu()
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.benchmarks.serving_bench import build_model
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.inference.v2.kernels.paged_attention import \
        paged_attention

    rec = {"backend": jax.default_backend(), "batch": args.batch}
    model = build_model(4, 256)
    cfg = model.cfg
    params = model.init_params(jax.random.PRNGKey(0))
    N = args.batch

    # --- 1/2: one layer's attention, kernel vs gather fallback ---------
    nb, bs, kvh, hd = 4096, 64, cfg.kv_heads, cfg.head_dim
    MB = 16
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((N, cfg.num_heads, hd)),
                    jnp.bfloat16)
    kc = jnp.asarray(rng.standard_normal((nb, bs, kvh, hd)), jnp.bfloat16)
    vc = jnp.asarray(rng.standard_normal((nb, bs, kvh, hd)), jnp.bfloat16)
    tables = jnp.asarray(
        rng.integers(1, nb, (N, MB)).astype(np.int32))
    lengths = jnp.full((N,), 192, jnp.int32)

    kern = jax.jit(paged_attention)
    rec["kernel_attn_ms"] = round(
        timeit(kern, q, kc, vc, tables, lengths) * 1e3, 3)

    def gather_attn(q, kc, vc, tables, lengths):
        ctx = MB * bs
        kp = kc[tables].reshape(N, ctx, kvh, hd)
        vp = vc[tables].reshape(N, ctx, kvh, hd)
        if kvh != cfg.num_heads:
            kp = jnp.repeat(kp, cfg.num_heads // kvh, axis=2)
            vp = jnp.repeat(vp, cfg.num_heads // kvh, axis=2)
        s = jnp.einsum("nhd,nchd->nhc", q, kp).astype(jnp.float32)
        s = s / np.sqrt(hd)
        mask = jnp.arange(ctx)[None, :] < lengths[:, None]
        s = jnp.where(mask[:, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("nhc,nchd->nhd", p, vp)

    rec["gather_attn_ms"] = round(
        timeit(jax.jit(gather_attn), q, kc, vc, tables, lengths) * 1e3, 3)

    # --- 3: full decode step, kernel on vs off -------------------------
    for use_kernel, key in ((True, "decode_step_kernel_ms"),
                            (False, "decode_step_gather_ms")):
        eng = InferenceEngineV2(model, {
            "dtype": "bfloat16", "use_paged_kernel": use_kernel,
            "state_manager": {"max_tracked_sequences": max(N, 8),
                              "max_ragged_batch_size": 2048,
                              "num_blocks": 4096},
        }, params=params)
        prompts = [list(map(int, p)) for p in
                   rng.integers(0, 2047, (N, 128))]
        uids = list(range(N))
        eng.put(uids, prompts)
        tok = [[5]] * N

        def step():
            return eng.put(uids, tok)

        for _ in range(3):
            step()
        t0 = time.perf_counter()
        reps = 20
        for _ in range(reps):
            step()
        rec[key] = round((time.perf_counter() - t0) / reps * 1e3, 3)
        for u in uids:
            eng.flush(u)
        del eng
        jax.clear_caches()

    print(json.dumps(rec, indent=1))
    outp = pathlib.Path(args.out)
    outp.parent.mkdir(parents=True, exist_ok=True)
    outp.write_text(json.dumps(rec, indent=1))


if __name__ == "__main__":
    main()
