"""Serve a real HF checkpoint end-to-end: ``init_inference`` -> v2 ragged.

The one-call user path the reference documents for FastGen
(reference inference/v2/engine_factory.py build_hf_engine /
deepspeed/__init__.py:269 init_inference): hand an HF torch model to
``deepspeed_tpu.init_inference(..., use_ragged=True)`` and serve tokens off
the paged KV engine. Greedy decode is asserted TOKEN-FOR-TOKEN against HF's
own ``generate`` — cross-implementation correctness, not just smoke.

Zero-egress environments build the model as a seeded-weights fixture
(a real ``transformers.GPT2LMHeadModel``, 125M-class geometry by default);
where a download cache exists, ``--pretrained gpt2`` loads actual weights.

Prints ONE JSON line: greedy-match + decode tokens/sec.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--cpu", action="store_true",
                    help="pin the CPU backend (no TPU needed)")
    ap.add_argument("--new-tokens", type=int, default=20)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--hidden", type=int, default=768)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--pretrained", default=None,
                    help="HF model name to load real weights (needs network/cache)")
    ap.add_argument("--out", default=None, help="also write the JSON here")
    args = ap.parse_args(argv)

    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    import numpy as np
    import torch
    import transformers

    import deepspeed_tpu

    if args.pretrained:
        hf = transformers.AutoModelForCausalLM.from_pretrained(
            args.pretrained).eval()
    else:
        # seeded fixture: real HF module, deterministic random weights,
        # 125M-class GPT-2 geometry by default
        cfg = transformers.GPT2Config(
            vocab_size=50257, n_positions=256, n_embd=args.hidden,
            n_layer=args.layers, n_head=args.heads)
        torch.manual_seed(0)
        hf = transformers.GPT2LMHeadModel(cfg).eval()

    engine = deepspeed_tpu.init_inference(
        hf, dtype="float32", use_ragged=True,
        ragged={"state_manager": {"max_tracked_sequences": 2,
                                  "max_seq_len": 256, "num_blocks": 33,
                                  "block_size": 16},
                "prefill_bucket": 32})

    prompt = np.array([464, 3290, 318, 257, 845, 922, 3290, 11], np.int64)
    # greedy decode through the paged engine
    logits = engine.put([1], [prompt])
    toks = [int(np.argmax(logits[0]))]
    t0 = None
    for i in range(args.new_tokens - 1):
        if i == 1:
            t0 = time.perf_counter()  # skip the decode-compile step
        logits = engine.put([1], [[toks[-1]]])
        toks.append(int(np.argmax(logits[0])))
    if t0 is not None:
        dt = time.perf_counter() - t0
        tps = (args.new_tokens - 2) / dt if dt > 0 else float("nan")
    else:  # too few tokens to time past the compile step
        tps = float("nan")

    with torch.no_grad():
        ref = hf.generate(torch.from_numpy(prompt[None]),
                          max_new_tokens=args.new_tokens, do_sample=False,
                          pad_token_id=0)
    ref_toks = ref[0, len(prompt):].tolist()
    match = toks == ref_toks
    rec = {"metric": "hf_serve_greedy", "model": args.pretrained or
           f"gpt2-fixture-{args.layers}L{args.hidden}H",
           "backend": jax.default_backend(),
           "greedy_matches_hf": match, "new_tokens": args.new_tokens,
           "decode_tokens_per_sec": round(tps, 2)}
    print(json.dumps(rec))
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(rec, fh, indent=1)
    if not match:
        print(f"MISMATCH ours={toks} hf={ref_toks}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
