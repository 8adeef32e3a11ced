"""The benchmark's arithmetic for a linear-attention layer's recurrent
state (Kimi Delta Attention: a float32 state ``[d_k, d_v]`` a head and
row in place of cached positions): what the one-token update of a
decode step, and the recurrence over a row's prompt tokens, must at
least move and compute, whatever implements them.

Beside ``arith_gen.py`` and not in it. Nothing here imports the program.
"""


def linear_layers(fields):
    """Layers of the pattern that are linear: all but every
    ``linear_attn_period``-th."""
    p = fields["linear_attn_period"]
    return sum(1 for i in range(fields["num_layers"]) if (i + 1) % p)


def state_values(fields):
    """Values of one row's state in ONE linear layer: heads x d_k x d_v
    of the recurrence, and the convolution's last taps - 1 inputs of q,
    k and v (32 x 128 x 128 + 3 x 3 x 4096 = 561,152)."""
    nh, d = fields["num_heads"], fields["linear_head_dim"]
    return nh * d * d + (fields.get("linear_conv_size", 4) - 1) * 3 * nh * d


def row_bytes(fields, itemsize=4):
    """Bytes ONE linear layer's update moves for one row and token: the
    row's state read once and written once (float32: 4,489,216 B). The
    token's own q, k, v, decay and output are small beside it and are
    left out, which only lowers the floor."""
    return 2 * state_values(fields) * itemsize


def row_flops(fields):
    """Floating-point operations of one row's one-token update in ONE
    layer: a head decays its state (d_k d_v), reads it against the key
    (2 d_k d_v), adds the rank-one update (2 d_k d_v) and reads it
    against the query (2 d_k d_v)."""
    nh, d = fields["num_heads"], fields["linear_head_dim"]
    return 7 * nh * d * d


def least_seconds(fields, rows, steps, peaks, itemsize=4):
    """The least seconds the one-token updates of ``steps`` decode steps
    of ``rows`` rows can take over every linear layer: the larger of the
    states' bytes over the chip's HBM bandwidth and the operations over
    its peak (the bf16 matmul peak: elementwise float32 work is slower
    still, which only lowers the floor). No implementation beats it."""
    return linear_layers(fields) * steps * rows * max(
        row_bytes(fields, itemsize) / peaks["hbm_bytes_per_s"],
        row_flops(fields) / peaks["bf16_flops_per_s"])


def prompt_row_bytes(fields, tokens, itemsize=4, act_itemsize=2):
    """Bytes ONE linear layer moves for one FRESH row's ``tokens``
    prompt tokens: each token's q, k, v and decay in (heads x d_k values
    each), its beta in and its output out, at the activations' width,
    and the row's state written once at the end (a fresh row starts from
    zeros: nothing is read). The chunked form's own passes are its own."""
    nh, d = fields["num_heads"], fields["linear_head_dim"]
    return tokens * (5 * nh * d + nh) * act_itemsize \
        + state_values(fields) * itemsize


def prompt_least_seconds(fields, rows, tokens, peaks, itemsize=4):
    """The least seconds the recurrence over ``rows`` fresh rows'
    ``tokens`` prompt tokens each can take over every linear layer: the
    larger of ``prompt_row_bytes`` over the chip's HBM bandwidth and the
    recurrence's own operations (``row_flops`` a token: a chunked form
    makes more, which are its own) over its peak."""
    return linear_layers(fields) * rows * max(
        prompt_row_bytes(fields, tokens, itemsize)
        / peaks["hbm_bytes_per_s"],
        tokens * row_flops(fields) / peaks["bf16_flops_per_s"])
