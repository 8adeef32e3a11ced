"""Generation by a model whose full latent layers read only the positions
a learned INDEXER picks, beside latent layers over a ring: the accepted
``generate`` runner, whole and as it stands (its window, its
``gen_tok_s``, its ``logit_err`` and ``token_gap``), and behind it the
numbers of ``correct`` that are held to the SELECTION and the pools,
which neither of those is held to on a sparse cell (a hard top-8 of 256
moves them by 3-8e-2 on the accepted sparse cells whatever attention
read: PERF.md section 4).

Once the window has closed and its engine is gone, an engine is built
again from the same seed and serves the probe batch (the batch
``logit_err`` probes: same rows, same lengths, the window's programs) in
ONE ``generate()`` call that keeps its sequences. Of ``check_rows`` rows
drawn from the seed, for the layers AHEAD OF EVERY ROUTED EXPERT (the
leading dense layers and the first expert layer, whose mixers' inputs no
expert choice has touched; they have to be full latent layers):

* ``pool_err``: the latent rows and the indexer's keys the sequence
  holds (``engine.sequence_kv``: every token but the last fed) against
  the reference's float32 rows and keys at every position
  (``reference.leading_layers``): |served - reference| over |reference|
  (Frobenius, a row, layer and leaf), the largest. The program stores a
  row's rotated lanes half-split (evens' results, then odds':
  ``paged_model._rotate_pairs``); the reference's are put in that order
  here.
* ``select_miss``: at ``probe_positions`` positions past
  ``index_topk`` (drawn from the seed over the prompt's second half and
  the served tokens: chunk steps and decode steps), the share of the
  reference's ``S_t`` that the program did NOT read: the program's own
  query path and selection (``paged_model.index_picks``, under the
  program's configuration) on the reference's layer input at the
  position, against the index keys the sequence CACHED; 1 - |S_ref & S_program| / |S_ref|, the largest over rows,
  layers and positions. A program that selects fewer, other or stale
  positions reads it; rounding swaps a few positions at the set's edge.

A cell's ``program_fields`` (absent in the cell as it stands; a
``control`` overlay lays it on) are fields laid on the PROGRAM's
configuration alone (``generate_conv.lay_program_fields``): the weights
and the reference keep the configuration's.

The second engine costs the run its weights again, the window's
programs from the cache and one call, behind the window; neither the
window nor ``setup_s`` sees it.
"""

import gc
import time

import numpy as np

from . import generate as base
from .generate_conv import lay_program_fields
from .generate_state import check_rows

PROBE_POSITIONS = 8


def judged_layers(fields):
    """The layers ahead of every routed expert: the leading dense ones
    and the first expert layer."""
    n = fields.get("moe_first_dense_layers", 0) + 1
    ahead = fields["layer_types"][:n]
    if set(ahead) != {"full_attention"}:
        raise SystemExit(
            f"benchmark: runner generate_sparse reads the rows, index "
            f"keys and selections of the full latent layers ahead of "
            f"every routed expert, and here they are {ahead}")
    return n


def probe_positions(ctx, row, fed):
    """``PROBE_POSITIONS`` positions of a sequence of ``fed`` tokens
    past ``index_topk``, from the seed and the row: half over the
    prompt's tail, half over the served tokens, the last fed among
    them."""
    topk, plen = ctx.fields["index_topk"], ctx.traffic["prompt_len"]
    rng = np.random.default_rng([ctx.seed, 3, row])
    lo = min(max(topk, plen // 2), fed - 1)
    early = rng.integers(lo, max(min(plen, fed), lo + 1),
                         PROBE_POSITIONS // 2)
    late = rng.integers(min(plen, fed - 1), fed,
                        PROBE_POSITIONS - len(early) - 1)
    return sorted({*early.tolist(), *late.tolist(), fed - 1})


def served_pools(ctx, layers):
    """One call of the probe batch on an engine of its own; returns
    ``{row: (served tokens, {"latent", "index_k": [layers, positions,
    lanes]})}`` for the check rows, on the host. The engine is gone
    when this returns: its weights and the reference's do not fit a
    chip together."""
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
        held = engine.sequence_kv(r, "full")
        kept[r] = (np.asarray(outs[r]), {
            "latent": held["latent"][:layers],
            "index_k": held["index_k"][:layers]})
    del engine
    gc.collect()
    return kept


def program_picks(ctx, params, layer, keys, x, positions):
    """What the PROGRAM's indexer of full layer ``layer`` picks at
    ``positions`` of a sequence whose cached index keys are ``keys``
    [positions held, d] (the served ones, as the pool held them), given
    the layer's input ``x`` there, as flags ``[positions, positions
    held]``: ``paged_model.index_picks``, the serving programs' own
    query path and selection, under the program's configuration and in
    the type it serves in, over the keys laid out as a pool of one
    sequence; a position of the prompt in the form a prompt's launch
    takes, a served token's in a decode step's."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.paged_model import index_picks

    dtype = jnp.dtype(ctx.cell["engine"]["dtype"])
    bs = ctx.cell["engine"]["state_manager"]["block_size"]
    blocks = -(-len(keys) // bs)
    pool = np.zeros((1, blocks * bs, keys.shape[1]), np.float32)
    pool[0, :len(keys)] = keys
    pool = jnp.asarray(pool, dtype).reshape(1, blocks, bs, -1)
    lp = jax.tree.map(lambda a: a[layer].astype(dtype),
                      params["mla_layers"])
    positions = np.asarray(positions)
    decoded = positions >= ctx.traffic["prompt_len"]
    flags = np.zeros((len(positions), blocks * bs), bool)
    for form in (False, True):
        at = np.flatnonzero(decoded == form)
        if len(at):
            flags[at] = np.asarray(jax.jit(
                index_picks, static_argnums=(0, 7))(
                ctx.model_config(), lp, jnp.asarray(x[at], jnp.float32),
                jnp.asarray(positions[at], jnp.int32), pool, jnp.int32(0),
                jnp.arange(blocks, dtype=jnp.int32), form))
    return flags[:, :len(keys)]


def half_split(rows, dc):
    """The reference's cached rows with their rotated lanes in the order
    the program stores them: evens' results, then odds'."""
    rope = rows[:, dc:]
    return np.concatenate([rows[:, :dc], rope[:, 0::2], rope[:, 1::2]], -1)


def sparse_errors(ctx):
    """``(pool_err, select_miss, parts)``: each the largest of its
    ``parts`` ``{(what, row, layer): value}``."""
    reference = ctx.reference
    if not callable(getattr(reference, "leading_layers", None)):
        raise SystemExit(
            f"benchmark: runner generate_sparse needs configuration "
            f"{ctx.cell['config']}'s reference to offer "
            f"leading_layers(params, fields, ids, layers, probe)")
    layers = judged_layers(ctx.fields)
    dc = ctx.fields["kv_lora_rank"]
    t = time.perf_counter()
    kept = served_pools(ctx, layers)
    ctx.log(f"  pool probe: its engine and call "
            f"{time.perf_counter() - t:.1f} s (behind the window: the "
            f"process's clock, not set-up's)")
    params = ctx.weights.make(ctx.fields, ctx.seed)
    parts = {}
    for row, (tokens, held) in kept.items():
        # the last served token was never fed: the pools hold
        # tokens[:-1], where token_gap's reference pass ends too
        fed = len(tokens) - 1
        probe = probe_positions(ctx, row, fed)
        want = reference.leading_layers(params, ctx.fields, tokens[:-1],
                                        layers, probe=probe)
        for layer, ref in enumerate(want):
            for leaf, got in (("latent", held["latent"][layer]),
                              ("index_k", held["index_k"][layer])):
                r = np.asarray(ref["rows" if leaf == "latent" else leaf],
                               np.float32)
                if leaf == "latent":
                    r = half_split(r, dc)
                parts[leaf, row, layer] = float(
                    np.linalg.norm(got[:fed, :r.shape[1]] - r)
                    / np.linalg.norm(r))
            mine = program_picks(
                ctx, params, layer, held["index_k"][layer][:fed],
                np.asarray(ref["x"])[probe], probe)
            picked = np.asarray(ref["picked"])
            parts["miss", row, layer] = float(max(
                1.0 - (mine[j] & picked[j]).sum() / max(picked[j].sum(), 1)
                for j in range(len(probe))))
    pool = max(v for k, v in parts.items() if k[0] != "miss")
    return pool, max(v for k, v in parts.items() if k[0] == "miss"), parts


def run(ctx):
    lay_program_fields(ctx)
    result = base.run(ctx)
    gc.collect()
    pool, miss, parts = sparse_errors(ctx)
    limits = ctx.cell["limits"]
    compared = result.correct_detail["compared"]
    for name, value in (("pool_err", pool), ("select_miss", miss)):
        limit = limits[name]["limit"]
        compared[name] = {"value": value, "limit": limit}
        result.correct = bool(result.correct and value <= limit)
        ctx.log(f"  compared: {name} {value:.4e} (limit {limit:.4e})")
    ctx.log("  by (what, row, layer): " + ", ".join(
        f"{k} {v:.3e}" for k, v in sorted(parts.items())))
    result.correct_detail["sparse_by_what_row_layer"] = {
        ".".join(map(str, k)): v for k, v in sorted(parts.items())}
    return result
