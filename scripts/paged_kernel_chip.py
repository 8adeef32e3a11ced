"""On-chip parity + timing for the two paged-attention variants.

The manual-DMA variant only runs on real TPU (interpret mode can't
simulate its semaphore protocol), so its correctness evidence is a chip
run: parity vs the BlockSpec-pipelined variant, plus timing at
serving-like shapes. Geometries are ones Mosaic accepts the DMA page
slice for (head_dim 128, kv_heads 4 or a multiple of 8 — see
kernels/ragged_attention.kernel_variant); chip_smoke.py phase K is the
always-run version of the parity half.

Writes artifacts/r05/paged_kernel_chip.json.
"""

import json
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def main():
    from deepspeed_tpu.accelerator.tpu_accelerator import require_tpu
    require_tpu()
    import functools

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.v2.kernels.paged_attention import \
        paged_attention

    rec = {"device": str(jax.devices()[0].device_kind)}
    rng = np.random.default_rng(0)

    def run_case(label, N, nh, kvh, hd, nb, bs, MB, length):
        q = jnp.asarray(rng.standard_normal((N, nh, hd)), jnp.bfloat16)
        kc = jnp.asarray(rng.standard_normal((nb, bs, kvh, hd)),
                         jnp.bfloat16)
        vc = jnp.asarray(rng.standard_normal((nb, bs, kvh, hd)),
                         jnp.bfloat16)
        tables = jnp.asarray(rng.integers(1, nb, (N, MB)).astype(np.int32))
        lengths = jnp.full((N,), length, jnp.int32)
        f_dma = jax.jit(functools.partial(paged_attention, variant="dma"))
        f_pipe = jax.jit(functools.partial(paged_attention,
                                           variant="pipelined"))
        a = jax.block_until_ready(f_dma(q, kc, vc, tables, lengths))
        b = jax.block_until_ready(f_pipe(q, kc, vc, tables, lengths))
        err = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                    - b.astype(jnp.float32))))

        def bench(f, reps=30):
            for _ in range(3):
                f(q, kc, vc, tables, lengths)
            jax.block_until_ready(f(q, kc, vc, tables, lengths))
            t0 = time.perf_counter()
            for _ in range(reps):
                out = f(q, kc, vc, tables, lengths)
            jax.block_until_ready(out)
            return (time.perf_counter() - t0) / reps * 1e3

        case = {"dma_vs_pipelined_max_err": err,
                "dma_ms": round(bench(f_dma), 3),
                "pipelined_ms": round(bench(f_pipe), 3),
                "N": N, "MB": MB, "length": length, "bs": bs}
        rec[label] = case
        print(label, json.dumps(case), flush=True)

    # serving-bench shape: short context in a wide table (the case the
    # DMA kernel exists for)
    run_case("short_ctx_wide_table", 8, 4, 4, 128, 4096, 64, 16, 192)
    # long context, table fully used
    run_case("full_table", 8, 4, 4, 128, 4096, 64, 16, 1024)
    # GQA decode shape (group=4): exercises the q head-grouping and the
    # per-head rows slicing the MHA cases cannot
    run_case("gqa_llama", 16, 32, 8, 128, 2048, 64, 32, 512)

    outp = pathlib.Path("artifacts/r05/paged_kernel_chip.json")
    outp.parent.mkdir(parents=True, exist_ok=True)
    outp.write_text(json.dumps(rec, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
