"""The benchmark's arithmetic for per-head attention under a layer
PATTERN of window and full layers (``fields["layer_types"]``): what a
launch must at least move and compute, layer kind by layer kind,
whatever form the kernel and the cache take.

Beside ``arith_gen.py`` and not in it: no file an accepted cell reads is
edited for a new cell (``arith_gen.ragged_attention_bytes`` counts every
layer whole). Nothing here imports the program.
"""

from .arith_gen import kv_bytes_per_token
from .arith_latent import launches

KINDS = {"sliding_attention": "window", "full_attention": "full"}


def layers_of(fields):
    """``{"window": n, "full": m}``: the layers of each kind."""
    kinds = [KINDS[t] for t in fields["layer_types"]]
    return {k: kinds.count(k) for k in ("window", "full")}


def row_positions(new, context, window):
    """Cached positions ONE layer's attention must read for a row whose
    ``new`` tokens end at ``context`` (the last of them sees that many,
    itself included): all of them on a full layer (``window`` 0), and on
    a window layer the union of its tokens' windows, ``window + new -
    1`` where the context holds that many."""
    return min(context, window + new - 1) if window else context


def visible_positions(new, context, window):
    """Positions the ``new`` tokens of one row attend, summed: token i
    sees ``context - new + i + 1``, and on a window layer no more than
    ``window`` (the causal half inside a chunk is counted as a half)."""
    first = context - new + 1               # what the first token sees
    if not window or context <= window:
        return new * (first + context) // 2
    full = max(0, min(new, context - window + 1))   # tokens at the window
    ramp = new - full                               # tokens still under it
    return full * window + ramp * (first + (first + ramp - 1)) // 2


def launch_bytes(fields, launch, window, itemsize=2):
    """Bytes ONE layer's attention must move through HBM for one launch
    (a list of ``(new, context)`` a row): each row's visible positions'
    keys and values ONCE (2,048 B a position at 4 kv heads of 128 in
    bf16), however many of its tokens or heads read them, and each new
    token's query read and output written once."""
    nh = fields["num_heads"]
    hd = fields.get("head_dim_override") or fields["hidden_size"] // nh
    kv = kv_bytes_per_token(fields, itemsize)
    qo = 2 * nh * hd * itemsize
    return sum(row_positions(new, ctx, window) * kv + new * qo
               for new, ctx in launch)


def launch_flops(fields, launch, window):
    """Floating-point operations ONE layer's attention must make for one
    launch: a score and a value over ``head_dim`` a query head and
    visible position, two operations a multiply-add: 4 x visible x
    (heads x head_dim) a token."""
    nh = fields["num_heads"]
    hd = fields.get("head_dim_override") or fields["hidden_size"] // nh
    return 4 * nh * hd * sum(visible_positions(new, ctx, window)
                             for new, ctx in launch)


def least_seconds(fields, launch_rows, rows, peaks):
    """The least seconds the pattern's attention can take over the
    launches of ``launch_rows`` (``arith_gen.generate_call_rows``: a
    call's prompt as ONE launch, which no way of feeding it in chunks
    beats, then each decode step): launch by launch and layer kind by
    layer kind the larger of the byte floor over the chip's HBM
    bandwidth and the FLOP floor over its bf16 peak, times the layers of
    the kind, summed. No implementation beats it, so a share of it over
    the kernels' time cannot pass 100 %."""
    total = 0.0
    for kind, n in layers_of(fields).items():
        window = fields["attn_window"] if kind == "window" else 0
        total += n * sum(
            max(launch_bytes(fields, ln, window) / peaks["hbm_bytes_per_s"],
                launch_flops(fields, ln, window) / peaks["bf16_flops_per_s"])
            for ln in launches(launch_rows, rows))
    return total
