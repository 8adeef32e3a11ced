"""On-chip speculative-decoding timing: plain greedy vs prompt-lookup.

Two workloads through the same engine: periodic text (drafts accept —
the win case) and random text (drafts reject — the cold-streak cutoff
must keep the cost near plain greedy). Writes
artifacts/r05/spec_bench.json. Run only on a healthy chip.
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
    import jax

    from deepspeed_tpu.benchmarks.serving_bench import build_model
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2

    model = build_model(4, 256)
    params = model.init_params(jax.random.PRNGKey(0))

    def engine():
        return InferenceEngineV2(model, {
            "dtype": "bfloat16",
            "state_manager": {"max_tracked_sequences": 8,
                              "max_ragged_batch_size": 2048,
                              "num_blocks": 4096}}, params=params)

    rng = np.random.default_rng(0)
    unit = list(map(int, rng.integers(1, 2047, 8)))
    workloads = {
        "periodic": [unit * 16] * 4,                       # 128-token
        "random": [list(map(int, rng.integers(1, 2047, 128)))
                   for _ in range(4)],
    }
    rec = {"device": str(jax.devices()[0].device_kind), "new_tokens": 64}
    eng = engine()   # one engine: identical shapes, state flushed per call
    for spec in (False, True):                       # compile warmup
        eng.generate(workloads["periodic"], max_new_tokens=64,
                     speculative=spec)
    reps = 3
    uid = 100
    for name, prompts in workloads.items():
        times = {}
        outs = {}
        for spec in (False, True):
            t0 = time.perf_counter()
            for _ in range(reps):
                uid += len(prompts)
                outs[spec] = eng.generate(
                    prompts, max_new_tokens=64, speculative=spec,
                    uids=list(range(uid, uid + len(prompts))))
            times[spec] = (time.perf_counter() - t0) / reps
        assert all((a == b).all()
                   for a, b in zip(outs[False], outs[True])), \
            "speculative output diverged from greedy"
        rec[name] = {
            "plain_s": round(times[False], 3),
            "speculative_s": round(times[True], 3),
            "speedup": round(times[False] / times[True], 3),
        }
        print(name, json.dumps(rec[name]), flush=True)
    outp = pathlib.Path("artifacts/r05/spec_bench.json")
    outp.parent.mkdir(parents=True, exist_ok=True)
    outp.write_text(json.dumps(rec, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
