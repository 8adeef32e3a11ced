"""Generation by a model that keeps RECURRENT STATE a sequence: the
accepted ``generate`` runner, whole and as it stands (its window, its
``gen_tok_s``, its ``logit_err`` and ``token_gap``), and behind it one
more number of ``correct``, which reads what neither of those can: the
state the decode steps leave in a sequence's slot.

``logit_err`` is a prefill's, and a prefill carries its state inside
one launch; ``token_gap`` asks only that a served token be near the
reference's best, which a state of eight bits still gives, and both
carry the swap a hard top-k makes against a float32 reference. So a
state kept in a lower precision passed both (PERF.md section 4).

* ``state_err``: once the window has closed and its engine is gone, an
  engine is built again from the same seed and serves the probe batch
  (the batch ``logit_err`` probes: same rows, same lengths, the window's
  programs) in ONE ``generate()`` call that keeps its sequences; of
  ``check_rows`` rows drawn from the seed, the state each holds in its
  slot after the call (``sequence_state``: every token but the last fed,
  the prompt through the chunked form and ``new_tokens - 1`` one-token
  updates behind it) against the reference's float32 state after the
  same tokens (``reference.leading_states``), in the linear layers AHEAD
  OF EVERY ROUTED EXPERT, whose input no expert choice has touched:
  |served - reference| over |reference| (Frobenius, a row and layer),
  the largest.

The second engine costs the run about half a minute behind its window
(the weights again, the window's programs from the cache, one call);
neither the window nor ``setup_s`` sees it. A ``benchmark`` PR that may
edit ``generate.py`` can take the state from the window's own engine.
"""

import gc

import numpy as np

from . import generate as base


def check_rows(ctx, n_rows):
    """The rows ``logit_err`` compares (``generate.compare``'s draw)."""
    rng = np.random.default_rng([ctx.seed, 2])
    n = min(ctx.traffic["check_rows"], n_rows)
    return sorted(rng.choice(n_rows, replace=False, size=n).tolist())


def served_states(ctx):
    """One call of the probe batch on an engine of its own; returns
    ``{row: (served tokens, kda_state [linear layers, heads, d, d])}``
    for the check rows. The engine is gone when this returns."""
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
        engine.sequence_state(r)["kda_state"], np.float32))
        for r in check_rows(ctx, len(probe))}
    del engine
    gc.collect()
    return kept


def state_error(ctx):
    """``state_err`` and its parts ``{(row, layer): error}``."""
    reference = ctx.reference
    if not callable(getattr(reference, "leading_states", None)):
        raise SystemExit(
            f"benchmark: runner generate_state needs configuration "
            f"{ctx.cell['config']}'s reference to offer "
            f"leading_states(params, fields, ids)")
    kept = served_states(ctx)
    params = ctx.weights.make(ctx.fields, ctx.seed)
    parts = {}
    for row, (tokens, served) in kept.items():
        # the last served token was never fed: the state is the one
        # after tokens[:-1], where token_gap's reference pass ends too
        want = np.asarray(reference.leading_states(
            params, ctx.fields, tokens[:-1]))
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
