"""Generation by a model whose STATE-SPACE layers keep recurrent state
a sequence: the accepted ``generate`` runner, whole and as it stands
(its window, its ``gen_tok_s``, its ``logit_err`` and ``token_gap``),
and behind it ``state_err``, the number of ``correct`` that reads the
state the decode steps leave in a sequence's slot, as
``generate_state.py`` reads a linear-attention layer's (that file reads
the key ``kda_state`` and is an accepted file; this one reads
``ssm_state``).

* ``state_err``: once the window has closed and its engine is gone, an
  engine is built again from the same seed and serves the probe batch
  (the batch ``logit_err`` probes: same rows, same lengths, the window's
  programs) in ONE ``generate()`` call that keeps its sequences; of
  ``check_rows`` rows drawn from the seed, the state each holds in its
  slot after the call (``sequence_state``: every token but the last fed,
  the prompt through the chunked form, carried from one ``put()`` chunk
  to the next, and ``new_tokens - 1`` one-token updates behind it)
  against the reference's float32 state after the same tokens
  (``reference.leading_states``) in LAYER 0's mixer, which is ahead of
  every routed expert (every layer of this block has experts, so only
  layer 0's is): |served - reference| over |reference| (Frobenius, a
  row), the largest. The next ``DETAIL_LAYERS - 1`` state-space layers
  are read the same way into the run's detail and judged by nothing: a
  swapped expert reaches them.
"""

import gc

import numpy as np

from . import generate as base
from .generate_state import check_rows

DETAIL_LAYERS = 5


def served_states(ctx):
    """One call of the probe batch on an engine of its own; returns
    ``{row: (served tokens, ssm_state [state-space layers, heads,
    d_head, d_state])}`` for the check rows. The engine is gone when
    this returns."""
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
        list(probe), max_new_tokens=tr["new_tokens"],
        temperature=tr["temperature"], eos_token_id=None,
        speculative=False, keep_sequences=True)
    kept = {r: (np.asarray(outs[r]), np.asarray(
        engine.sequence_state(r)["ssm_state"], np.float32))
        for r in check_rows(ctx, len(probe))}
    del engine
    gc.collect()
    return kept


def state_error(ctx):
    """``state_err`` (layer 0, the largest over the rows) and every
    part ``{(row, layer): error}`` of the first ``DETAIL_LAYERS``."""
    kept = served_states(ctx)
    params = ctx.weights.make(ctx.fields, ctx.seed)
    parts = {}
    for row, (tokens, served) in kept.items():
        # the last served token was never fed: the state is the one
        # after tokens[:-1], where token_gap's reference pass ends too
        want = np.asarray(ctx.reference.leading_states(
            params, ctx.fields, tokens[:-1],
            layers=min(DETAIL_LAYERS, len(served))))
        for layer, (got, ref) in enumerate(zip(served, want)):
            parts[row, layer] = float(np.linalg.norm(got - ref)
                                      / np.linalg.norm(ref))
    return max(v for (_, layer), v in parts.items() if layer == 0), parts


def run(ctx):
    result = base.run(ctx)
    gc.collect()
    value, parts = state_error(ctx)
    limit = ctx.cell["limits"]["state_err"]["limit"]
    ctx.log(f"  compared: state_err {value:.4e} (limit {limit:.4e}); "
            "by (row, layer), layer 0 judged: "
            + ", ".join(f"{k} {v:.3e}" for k, v in sorted(parts.items())))
    result.correct_detail["compared"]["state_err"] = {
        "value": value, "limit": limit}
    result.correct_detail["state_err_by_row_and_layer"] = {
        f"{r}.{layer}": v for (r, layer), v in sorted(parts.items())}
    result.correct = bool(result.correct and value <= limit)
    return result
