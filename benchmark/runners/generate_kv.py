"""Generation by a model whose cache has TWO GEOMETRIES (a layer pattern
of window and full per-head attention): the accepted ``generate`` runner,
whole and as it stands (its window, its ``gen_tok_s``, its ``logit_err``
and ``token_gap``), and behind it one more number of ``correct``, which
reads what neither of those is held to on a sparse cell: the keys and
values a sequence's RING holds once the prompt went in in chunks and the
decode steps turned it over.

``logit_err`` and ``token_gap`` carry the swap a hard top-k makes against
a float32 reference (3-8e-2 on the accepted sparse cells), so a cache
kept in a lower precision need not fail them (PERF.md section 4).

* ``kv_err``: once the window has closed and its engine is gone, an
  engine is built again from the same seed and serves the probe batch
  (the batch ``logit_err`` probes: same rows, same lengths, the window's
  programs) in ONE ``generate()`` call that keeps its sequences; of
  ``check_rows`` rows drawn from the seed, the keys and values each holds
  in its ring (``engine.sequence_kv``: every token but the last fed) for
  the window layers AHEAD OF EVERY ROUTED EXPERT (the leading dense
  layers and the first expert layer, whose input no expert choice has
  touched), at the last ``attn_window`` positions (what the next token
  would see), against the reference's float32 keys and values at the
  same positions (``reference.leading_kv``): |served - reference| over
  |reference| (Frobenius, a row, layer and leaf), the largest. It also
  says that a ring holds the right positions.

The second engine costs the run about a minute behind its window (the
weights again, the window's programs from the cache, one call); neither
the window nor ``setup_s`` sees it. A ``benchmark`` PR that may edit
``generate.py`` can take the ring from the window's own engine.
"""

import gc

import numpy as np

from . import generate as base
from .generate_state import check_rows


def served_kv(ctx):
    """One call of the probe batch on an engine of its own; returns
    ``{row: (served tokens, engine.sequence_kv(row))}`` for the check
    rows. The engine is gone when this returns."""
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
    kept = {r: (np.asarray(outs[r]), engine.sequence_kv(r, "window"))
            for r in check_rows(ctx, len(probe))}
    del engine
    gc.collect()
    return kept


def kv_error(ctx):
    """``kv_err`` and its parts ``{(row, layer, leaf): error}``."""
    reference = ctx.reference
    if not callable(getattr(reference, "leading_kv", None)):
        raise SystemExit(
            f"benchmark: runner generate_kv needs configuration "
            f"{ctx.cell['config']}'s reference to offer "
            f"leading_kv(params, fields, ids)")
    ahead = ctx.fields["layer_types"][
        :ctx.fields.get("moe_first_dense_layers", 0) + 1]
    if set(ahead) != {"sliding_attention"}:
        raise SystemExit(
            f"benchmark: runner generate_kv reads the RING of the layers "
            f"ahead of every routed expert, and here they are {ahead}")
    kept = served_kv(ctx)
    params = ctx.weights.make(ctx.fields, ctx.seed)
    window = ctx.fields["attn_window"]
    parts = {}
    for row, (tokens, held) in kept.items():
        # the last served token was never fed: the ring holds
        # tokens[:-1], where token_gap's reference pass ends too
        want = dict(zip("kv", reference.leading_kv(
            params, ctx.fields, tokens[:-1])))
        last = held["positions"][-window:]
        assert last[-1] == len(tokens) - 2 and (np.diff(last) == 1).all(), \
            f"row {row}: the ring gave positions {last[0]}..{last[-1]}"
        for leaf in "kv":
            ref = np.asarray(want[leaf], np.float32)[:, last]
            got = held[leaf][:len(ref), -len(last):]
            for layer in range(len(ref)):
                parts[row, layer, leaf] = float(
                    np.linalg.norm(got[layer] - ref[layer])
                    / np.linalg.norm(ref[layer]))
    return max(parts.values()), parts


def run(ctx):
    result = base.run(ctx)
    gc.collect()
    value, parts = kv_error(ctx)
    limit = ctx.cell["limits"]["kv_err"]["limit"]
    ctx.log(f"  compared: kv_err {value:.4e} (limit {limit:.4e}); "
            "by (row, layer, leaf): "
            + ", ".join(f"{k} {v:.3e}" for k, v in sorted(parts.items())))
    result.correct_detail["compared"]["kv_err"] = {
        "value": value, "limit": limit}
    result.correct = bool(result.correct and value <= limit)
    return result
