"""Extended on-chip MFU hunt beyond bench.py's ladder.

bench.py's trial ladder is budget-truncated and stops at micro_batch=16;
this script explores the configs the ladder never reaches — larger micro
batches (24/32), unchunked cross-entropy at full batch, bigger flash
blocks, and the 4k-sequence x mid-batch corner — and prints a ranked
table plus the single best (cfg, micro, policy) so the flagship defaults
(and bench.py's trial order) can be updated from measurement rather than
guesswork. Run only when the chip is healthy:

    python scripts/mfu_hunt.py [--steps 8] [--budget 1200]

Results append to artifacts/r05/mfu_hunt.json.
"""

import argparse
import dataclasses
import json
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--budget", type=float, default=1200.0)
    ap.add_argument("--out", default="artifacts/r05/mfu_hunt.json")
    args = ap.parse_args()

    lt = os.environ.get("LIBTPU_INIT_ARGS", "")
    if "latency_hiding_scheduler" not in lt:
        os.environ["LIBTPU_INIT_ARGS"] = (
            lt + " --xla_tpu_enable_latency_hiding_scheduler=true").strip()

    from __graft_entry__ import _flagship_cfg
    from deepspeed_tpu.accelerator.tpu_accelerator import require_tpu
    require_tpu()
    import jax

    from bench import _measure

    base = _flagship_cfg()
    P = "save_dots_and_attn"
    trials = [
        # (label, cfg, micro, policy)
        ("mb24", dataclasses.replace(base, use_flash=True,
                                     flash_min_seq=2048), 24, P),
        ("mb32", dataclasses.replace(base, use_flash=True,
                                     flash_min_seq=2048), 32, P),
        ("mb16_nochunk", dataclasses.replace(
            base, use_flash=True, flash_min_seq=2048, loss_chunk=0), 16, P),
        ("mb16_chunk1k", dataclasses.replace(
            base, use_flash=True, flash_min_seq=2048, loss_chunk=1024), 16, P),
        ("mb32_dots_only", dataclasses.replace(
            base, use_flash=True, flash_min_seq=2048), 32,
         "dots_with_no_batch_dims_saveable"),
        ("s4096_mb8", dataclasses.replace(
            base, max_seq_len=4096, use_flash=True, flash_min_seq=2048),
         8, P),
        ("mb16_bq1k_bk1k", dataclasses.replace(
            base, use_flash=True, flash_min_seq=2048,
            attn_block_q=1024, attn_block_kv=1024), 16, P),
        ("mb24_nochunk", dataclasses.replace(
            base, use_flash=True, flash_min_seq=2048, loss_chunk=0), 24, P),
    ]

    outp = pathlib.Path(args.out)
    outp.parent.mkdir(parents=True, exist_ok=True)
    prior_runs = []
    if outp.exists():  # chip windows are scarce: accumulate, don't clobber
        try:
            prior = json.loads(outp.read_text())
            prior_runs = (prior.get("prior_runs", [])
                          + [{k: prior[k] for k in ("ranked", "device")
                              if k in prior}])
        except Exception:
            pass

    results = []

    def flush():
        # written after EVERY trial: an outer `timeout`
        # killing a long trial must not lose the completed measurements
        ranked = sorted((r for r in results if "mfu_pct" in r),
                        key=lambda r: -r["mfu_pct"])
        out = {"ranked": ranked, "all": results,
               "device": str(jax.devices()[0].device_kind)}
        if prior_runs:
            out["prior_runs"] = prior_runs
        outp.write_text(json.dumps(out, indent=1))
        return ranked

    t0 = time.perf_counter()
    for label, cfg, micro, policy in trials:
        if time.perf_counter() - t0 > args.budget:
            results.append({"label": label, "skipped": "budget"})
            flush()
            continue
        try:
            mfu, detail = _measure(cfg, micro, 1, args.steps, 2,
                                   jax.device_count(),
                                   remat_policy=policy)
            row = {"label": label, "mfu_pct": round(mfu * 100, 2),
                   "tok_s": detail["tokens_per_sec_per_chip"],
                   "micro": micro, "seq": detail["seq_len"],
                   "policy": policy, "loss_chunk": detail["loss_chunk"]}
        except Exception as exc:
            row = {"label": label, "error": repr(exc)[:200]}
        results.append(row)
        flush()
        print(json.dumps(row), flush=True)

    ranked = flush()
    print(json.dumps({"best": ranked[0] if ranked else None,
                      "out": str(outp)}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
