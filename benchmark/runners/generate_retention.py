"""Generation by a model of POWER-RETENTION layers (a fixed-size
power-kernel state a key/value head and sequence, no cached position):
the accepted ``generate`` runner, whole and as it stands (its window, its
``gen_tok_s``, its ``logit_err`` and ``token_gap``), and behind it
``state_err``, as ``generate_state`` reads it for a linear-attention
model: the state the decode steps leave in a sequence's slot.

* ``state_err``: once the window has closed and its engine is gone, an
  engine is built again from the same seed and serves the probe batch in
  ONE ``generate()`` call that keeps its sequences; of ``check_rows``
  rows drawn from the seed, what each holds in its slot after the call
  (``sequence_state``: every token but the last fed, the prompt through
  the chunked form in as many launches as the step's budget makes, and
  ``new_tokens - 1`` one-token updates behind it): a key/value head's
  state ``[8,256, 128]`` with its normaliser ``[8,256]`` as a 129th
  column, against the reference's float32 recurrence after the same
  tokens (``reference.leading_states``), in the layers that gives: the
  FIRST, whose input is the embedding itself, so that the number reads
  the state's precision and not the drift of a bf16 stream, which grows
  layer by layer whatever the state is kept in: |served - reference|
  over |reference| (Frobenius, a row and layer), the largest.

The second engine costs the run about half a minute behind its window;
neither the window nor ``setup_s`` sees it.
"""

import gc

import numpy as np

from . import generate as base
from .generate_state import check_rows


def served_states(ctx):
    """One call of the probe batch on an engine of its own; returns
    ``{row: (served tokens, state [layers, kv heads, P, hd + 1])}`` for
    the check rows, the normaliser the last column. The engine is gone
    when this returns."""
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
    kept = {}
    for r in check_rows(ctx, len(probe)):
        held = engine.sequence_state(r)
        kept[r] = (np.asarray(outs[r]), np.concatenate(
            [np.asarray(held["retention_state"], np.float32),
             np.asarray(held["retention_norm"], np.float32)[..., None]],
            axis=-1))
    del engine
    gc.collect()
    return kept


def state_error(ctx):
    """``state_err`` and its parts ``{(row, layer): error}``."""
    reference = ctx.reference
    if not callable(getattr(reference, "leading_states", None)):
        raise SystemExit(
            f"benchmark: runner generate_retention needs configuration "
            f"{ctx.cell['config']}'s reference to offer "
            f"leading_states(params, fields, ids)")
    kept = served_states(ctx)
    params = ctx.weights.make(ctx.fields, ctx.seed)
    parts = {}
    for row, (tokens, served) in kept.items():
        # the last served token was never fed: the state is the one
        # after tokens[:-1], where token_gap's reference pass ends too
        s, z = reference.leading_states(params, ctx.fields, tokens[:-1])
        want = np.concatenate([np.asarray(s), np.asarray(z)[..., None]],
                              axis=-1)
        for layer, (got, ref) in enumerate(zip(served, want)):
            parts[row, layer] = float(np.linalg.norm(got - ref)
                                      / np.linalg.norm(ref))
    return max(parts.values()), parts


def run(ctx):
    result = base.run(ctx)
    gc.collect()
    value, parts = state_error(ctx)
    limit = ctx.cell["limits"]["state_err"]["limit"]
    ctx.log(f"  compared: state_err {value:.4e} (limit {limit:.4e}); "
            "by (row, layer): "
            + ", ".join(f"{k} {v:.3e}" for k, v in sorted(parts.items())))
    result.correct_detail["compared"]["state_err"] = {
        "value": value, "limit": limit}
    result.correct = bool(result.correct and value <= limit)
    return result
