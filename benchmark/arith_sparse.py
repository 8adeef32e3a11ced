"""The benchmark's arithmetic for latent attention that reads what an
INDEXER picks, beside latent attention over a window: what the model's
equations ask of a launch, whatever implements them.

Beside ``arith_latent.py`` and not in it: no file an accepted cell reads
is edited for a new cell. Nothing here imports the program. A launch is
a list of ``(new tokens, context)`` a row, as ``arith_gen.
generate_call_rows`` gives them; query i of a row's ``new`` has the
causal bound ``context - new + i + 1`` (its own position included).

Every floor is the larger of a FLOP floor over the chip's bf16 peak and
a byte floor over its HBM bandwidth, launch by launch; operations are
counted in the cheaper, EXPANDED form (a score over ``nope + rope`` and
a value over ``v`` a head and position read), which no form undercuts,
so a share of a floor over a kernel's time cannot pass 100 %.
"""

import functools

from .arith_latent import launches


def capped_positions(new, context, cap):
    """sum over a row's ``new`` queries of min(bound, cap): the
    positions they read where a query reads ``cap`` at most."""
    first = context - new + 1                   # the first query's bound
    under = max(0, min(context, cap) - first + 1)      # bounds <= cap
    return under * (first + first + under - 1) // 2 + (new - under) * cap


def scored_positions(new, context, topk):
    """sum of the bounds of a row's queries whose bound is OVER
    ``topk``: the positions the indexer has to score (a query whose
    bound is no larger reads every position, and scores none)."""
    first = max(context - new + 1, topk + 1)
    n = context - first + 1
    return 0 if n <= 0 else n * (first + context) // 2


def sizes(fields, kind):
    """(layers, heads, kv rank, nope, rope, v) of a latent kind: "full"
    (``full_attention``) or "window" (``sliding_attention``)."""
    types = fields["layer_types"]
    if kind == "window":
        return (types.count("sliding_attention"), fields["swa_num_heads"],
                fields["swa_kv_lora_rank"], fields["swa_qk_nope_head_dim"],
                fields["swa_qk_rope_head_dim"], fields["swa_v_head_dim"])
    return (types.count("full_attention"), fields["num_heads"],
            fields["kv_lora_rank"], fields["qk_nope_head_dim"],
            fields["qk_rope_head_dim"], fields["v_head_dim"])


def read_flops(fields, launch, kind):
    """Operations ONE layer of ``kind`` must make for one launch: a
    score and a value a head and position READ, two operations a
    multiply-add; a full layer's query reads min(bound, index_topk)
    positions, a window layer's min(bound, attn_window)."""
    _, nh, _, dn, dr, dv = sizes(fields, kind)
    cap = fields["attn_window"] if kind == "window" else fields["index_topk"]
    return nh * (dn + dr + dv) * 2 * sum(
        capped_positions(new, ctx, cap) for new, ctx in launch)


def read_bytes(fields, launch, kind, itemsize=2):
    """Bytes ONE layer of ``kind`` must move for one launch: of each
    row, the cached rows its last query reads at least (min(context,
    cap): a window's earlier queries and another query's selection may
    need more, which is the implementation's to fetch), once, and each
    new token's query read and output written in the expanded form."""
    _, nh, dc, dn, dr, dv = sizes(fields, kind)
    cap = fields["attn_window"] if kind == "window" else fields["index_topk"]
    return sum(min(ctx, cap) * (dc + dr) * itemsize
               + new * nh * (dn + dr + dv) * itemsize for new, ctx in launch)


def indexer_flops(fields, launch):
    """Operations ONE full layer's indexer must make for one launch: a
    dot product of ``index_head_dim`` a head, query and position
    scored."""
    return fields["index_n_heads"] * fields["index_head_dim"] * 2 * sum(
        scored_positions(new, ctx, fields["index_topk"])
        for new, ctx in launch)


def indexer_bytes(fields, launch, itemsize=2):
    """Bytes ONE full layer's indexer must move for one launch: of each
    row that scores at all, its cached keys once and its new tokens'
    queries and weights."""
    d, nh, topk = fields["index_head_dim"], fields["index_n_heads"], \
        fields["index_topk"]
    return sum(ctx * d * itemsize + new * nh * (d + 1) * itemsize
               for new, ctx in launch if ctx > topk)


def least_seconds(fields, launch_rows, rows, peaks, work):
    """The least seconds every layer's ``work`` can take over the
    launches of ``launch_rows``: "selected_read" (the full layers'
    attention over what was picked), "ring_read" (the window layers'
    attention) or "indexer" (the full layers' scores)."""
    kind = "window" if work == "ring_read" else "full"
    flops, moved = (indexer_flops, indexer_bytes) if work == "indexer" \
        else (functools.partial(read_flops, kind=kind),
              functools.partial(read_bytes, kind=kind))
    one = sum(max(moved(fields, ln) / peaks["hbm_bytes_per_s"],
                  flops(fields, ln) / peaks["bf16_flops_per_s"])
              for ln in launches(launch_rows, rows))
    return sizes(fields, kind)[0] * one
