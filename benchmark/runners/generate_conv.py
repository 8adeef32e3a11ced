"""Generation by a model whose mixers are SHORT CONVOLUTIONS that keep a
row's last inputs a sequence: the accepted ``generate`` runner, whole and
as it stands (its window, its ``gen_tok_s``, its ``logit_err`` and
``token_gap``), and behind it ``state_err``, the number of ``correct``
that reads what the decode steps leave in a sequence's slot, as
``generate_ssm.py`` reads a state-space layer's (that file reads the key
``ssm_state`` and is an accepted file; this one reads ``conv_state``).

* ``state_err``: once the window has closed and its engine is gone, an
  engine is built again from the same seed and serves the probe batch
  (the batch ``logit_err`` probes: same rows, same lengths, the window's
  programs) in ONE ``generate()`` call of the window's own
  ``new_tokens`` that keeps its sequences; of ``check_rows`` rows drawn
  from the seed, what each holds in its slot after the call
  (``sequence_state``: every token but the last fed, so the slot's
  ``taps - 1`` gated inputs are those of the last one-token steps)
  against the reference's float32 gated inputs of the same tokens
  (``reference.leading_states``) in the mixers of the first
  ``JUDGED_LAYERS`` layers, the LEADING DENSE ones, which stand ahead of
  every routed expert (no swapped pick reaches them): |served -
  reference| over |reference| (Frobenius, a row and layer), the
  largest. Layer 0's slot rests on the last two token ids, the table,
  the first norm and ``w_in`` alone; layer 1's on layer 0's whole mixer
  (its taps, both gates, ``w_out``, the slot it carried through the
  prompt's chunk steps and the decode steps) and on the first dense MLP.

A cell's ``program_fields`` (absent in every cell as it stands; a
``control`` overlay lays it on) are fields laid on the PROGRAM's
configuration alone: the weights and the reference keep the
configuration's. That is how this block's control departs from one line
of the published equations where ``state_dtype`` cannot tell (the state
is a copy of products of the projections, not a sum over tokens).
"""

import gc
import time

import numpy as np

from . import generate as base
from .generate_state import check_rows

# the leading dense layers, whose mixers no routed expert stands ahead of
JUDGED_LAYERS = 2


def lay_program_fields(ctx):
    """The program's configuration with the cell's ``program_fields``
    laid on; ``ctx.fields`` (the weights', the reference's) stay."""
    over = ctx.cell.get("program_fields")
    if over:
        from deepspeed_tpu.models.transformer import TransformerConfig
        ctx.model_config = lambda: TransformerConfig(
            **{**ctx.fields, **over})
        ctx.log(f"program_fields laid on the program alone: {over}")


def served_states(ctx):
    """One call of the probe batch on an engine of its own; returns
    ``{row: (served tokens, conv_state [conv layers, taps - 1,
    hidden])}`` for the check rows. The engine is gone when this
    returns."""
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.models import TransformerLM

    cell, tr = ctx.cell, ctx.traffic
    cfg = ctx.model_config()
    engine = InferenceEngineV2(
        TransformerLM(cfg), cell["engine"],
        params=ctx.weights.make(ctx.fields, ctx.seed,
                                cell["engine"]["dtype"]))
    _, probe = base.make_batches(tr, cfg.vocab_size, ctx.seed)
    # generate() names its rows 0 .. rows - 1: a row's uid is its index
    outs = engine.generate(
        list(probe),
        max_new_tokens=tr["new_tokens"],
        temperature=tr["temperature"], eos_token_id=None,
        speculative=False, keep_sequences=True)
    kept = {r: (np.asarray(outs[r]), np.asarray(
        engine.sequence_state(r)["conv_state"], np.float32))
        for r in check_rows(ctx, len(probe))}
    del engine
    gc.collect()
    return kept


def state_error(ctx):
    """``state_err`` (the largest over the check rows and the first
    ``JUDGED_LAYERS`` layers) and every part ``{(row, layer): error}``."""
    t = time.perf_counter()
    kept = served_states(ctx)
    ctx.log(f"  state probe: its engine and call {time.perf_counter() - t:.1f}"
            " s (behind the window: the process's clock, not set-up's)")
    params = ctx.weights.make(ctx.fields, ctx.seed)
    parts = {}
    for row, (tokens, served) in kept.items():
        # the last served token was never fed: the state is the one
        # after tokens[:-1], where token_gap's reference pass ends too
        want = np.asarray(ctx.reference.leading_states(
            params, ctx.fields, tokens[:-1],
            layers=min(JUDGED_LAYERS, len(served))))
        for layer, (got, ref) in enumerate(zip(served, want)):
            parts[row, layer] = float(np.linalg.norm(got - ref)
                                      / np.linalg.norm(ref))
    return max(parts.values()), parts


def run(ctx):
    lay_program_fields(ctx)
    result = base.run(ctx)
    gc.collect()
    value, parts = state_error(ctx)
    limit = ctx.cell["limits"]["state_err"]["limit"]
    ctx.log(f"  compared: state_err {value:.4e} (limit {limit:.4e}); "
            "by (row, layer): "
            + ", ".join(f"{k} {v:.3e}" for k, v in sorted(parts.items())))
    result.correct_detail["compared"]["state_err"] = {
        "value": value, "limit": limit}
    result.correct_detail["state_err_by_row_and_layer"] = {
        f"{r}.{layer}": v for (r, layer), v in sorted(parts.items())}
    result.correct = bool(result.correct and value <= limit)
    return result
