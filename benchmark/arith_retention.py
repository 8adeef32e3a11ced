"""The benchmark's arithmetic for a POWER-RETENTION layer (degree 2): a
key/value head keeps a float32 state ``[hd (hd + 1) / 2, hd]`` with a
normaliser ``[hd (hd + 1) / 2]`` in place of cached positions, read by
its group of query heads. What the one-token update of a decode step,
and the recurrence over a row's prompt tokens, must at least move and
compute, whatever implements them (the mechanism's 8,256 rows a head at
hd 128: what a layout keeps twice is its own).

Beside ``arith_state.py`` and ``arith_ssm.py`` and not in them. Nothing
here imports the program.
"""


def retention_layers(fields):
    """Layers of the pattern that are power-retention layers."""
    return sum(1 for t in fields.get("layer_types") or ()
               if t == "power_retention")


def head_dim(fields):
    return fields.get("head_dim_override") \
        or fields["hidden_size"] // fields["num_heads"]


def phi_values(fields):
    """Products of the symmetric second power of a head's key: hd (hd +
    1) / 2 (8,256 at hd 128)."""
    hd = head_dim(fields)
    return hd * (hd + 1) // 2


def state_values(fields):
    """Values of one row's state in ONE layer: kv heads x phi x (hd
    values and the normaliser): 8 x 8,256 x 129 = 8,520,192."""
    return fields["num_kv_heads"] * phi_values(fields) \
        * (head_dim(fields) + 1)


def token_values(fields):
    """Values of one token that enter or leave the mixer's core in ONE
    layer: q and the output a query head, k and v and the gate's scalar
    a key/value head: 2 x 40 x 128 + 2 x 8 x 128 + 8 = 12,296."""
    hd, nh, nkv = head_dim(fields), fields["num_heads"], \
        fields["num_kv_heads"]
    return 2 * nh * hd + 2 * nkv * hd + nkv


def row_bytes(fields, itemsize=4):
    """Bytes ONE layer's one-token update moves for one row: the row's
    state read once and written once in the type it is kept in, and the
    token's q, k, v, gate and output in float32 (float32 state:
    68,161,536 + 49,184 = 68,210,720 B)."""
    return 2 * state_values(fields) * itemsize + 4 * token_values(fields)


def row_flops(fields):
    """Floating-point operations of one row's one-token update in ONE
    layer: a state's value decays (1) and takes ``phi(k) v`` (2), and
    each of the group's query heads reads it (2 a head): kv heads x phi
    x (hd + 1) x (3 + 2 group) = 8,520,192 x 13."""
    group = fields["num_heads"] // fields["num_kv_heads"]
    return state_values(fields) * (3 + 2 * group)


def least_seconds(fields, rows, steps, peaks, itemsize=4):
    """The least seconds the one-token updates of ``steps`` decode steps
    of ``rows`` rows can take over every retention layer: the larger of
    the bytes over the chip's HBM bandwidth and the operations over its
    peak (the bf16 matmul peak: elementwise float32 work is slower
    still, which only lowers the floor)."""
    return retention_layers(fields) * steps * rows * max(
        row_bytes(fields, itemsize) / peaks["hbm_bytes_per_s"],
        row_flops(fields) / peaks["bf16_flops_per_s"])


def prompt_token_flops(fields):
    """Matmul operations of ONE prompt token in ONE layer between
    chunks: ``phi(q) S`` a query head and ``phi(k) v^T`` a key/value
    head, 2 x phi x hd each: (40 + 8) x 2 x 8,256 x 128 = 101,449,728.
    The attention form inside a chunk, the normaliser and phi itself are
    the implementation's own."""
    return (fields["num_heads"] + fields["num_kv_heads"]) * 2 \
        * phi_values(fields) * head_dim(fields)


def prompt_row_bytes(fields, tokens, chunks=1, itemsize=4, act_itemsize=2):
    """Bytes ONE layer moves for one FRESH row's ``tokens`` prompt
    tokens fed in ``chunks`` launches: each token's q, k, v, gate and
    output at the activations' width, and the row's state written once
    a launch and read once by every launch but the first."""
    return tokens * token_values(fields) * act_itemsize \
        + (2 * chunks - 1) * state_values(fields) * itemsize


def prompt_least_seconds(fields, rows, tokens, peaks, chunks=1, itemsize=4,
                         mxu_passes=1):
    """The least seconds the recurrence over ``rows`` fresh rows'
    ``tokens`` prompt tokens each can take over every retention layer:
    the larger of ``prompt_row_bytes`` over the chip's HBM bandwidth and
    ``prompt_token_flops`` a token over the MXU's rate in the precision
    the products run in: the bf16 peak over ``mxu_passes`` (1: bf16
    operands; 3: bf16_3x; 6: float32, which the MXU makes of six bf16
    passes)."""
    return retention_layers(fields) * rows * max(
        prompt_row_bytes(fields, tokens, chunks, itemsize)
        / peaks["hbm_bytes_per_s"],
        tokens * prompt_token_flops(fields) * mxu_passes
        / peaks["bf16_flops_per_s"])
