"""The benchmark's arithmetic for a state-space (Mamba-2) layer's
recurrent state (a float32 state ``[d_head, d_state]`` a head and row in
place of cached positions, and the convolution's last inputs): what the
one-token update of a decode step, and the recurrence over a row's
prompt tokens, must at least move and compute, whatever implements them.

Beside ``arith_state.py`` (the linear-attention layers') and not in it.
Nothing here imports the program.
"""


def ssm_layers(fields):
    """Layers of the pattern that are state-space layers."""
    return sum(1 for t in fields.get("layer_types") or () if t == "mamba")


def inner(fields):
    """Channels of the mixer's x and y: heads x d_head."""
    return fields["mamba_n_heads"] * fields["mamba_d_head"]


def state_values(fields):
    """Values of one row's state in ONE state-space layer: heads x
    d_head x d_state of the recurrence, and the convolution's last taps
    - 1 inputs of x, B and C (128 x 64 x 128 + 3 x 8,448 =
    1,073,920)."""
    n = fields["mamba_d_state"]
    return inner(fields) * n + (fields.get("mamba_d_conv", 4) - 1) \
        * (inner(fields) + 2 * n)


def row_bytes(fields, itemsize=4):
    """Bytes ONE state-space layer's update moves for one row and
    token: the row's state read once and written once (float32:
    8,591,360 B). The token's own x, B, C, dt and output are small
    beside it and are left out, which only lowers the floor."""
    return 2 * state_values(fields) * itemsize


def row_flops(fields):
    """Floating-point operations of one row's one-token update in ONE
    layer: a state's value decays (1), takes ``(dt x) B`` (2) and is
    read against ``C`` (2)."""
    return 5 * inner(fields) * fields["mamba_d_state"]


def least_seconds(fields, rows, steps, peaks, itemsize=4):
    """The least seconds the one-token updates of ``steps`` decode steps
    of ``rows`` rows can take over every state-space layer: the larger
    of the states' bytes over the chip's HBM bandwidth and the
    operations over its peak (the bf16 matmul peak: elementwise float32
    work is slower still, which only lowers the floor). No
    implementation beats it."""
    return ssm_layers(fields) * steps * rows * max(
        row_bytes(fields, itemsize) / peaks["hbm_bytes_per_s"],
        row_flops(fields) / peaks["bf16_flops_per_s"])


def prompt_row_bytes(fields, tokens, chunks=1, itemsize=4, act_itemsize=2):
    """Bytes ONE state-space layer moves for one FRESH row's ``tokens``
    prompt tokens fed in ``chunks`` launches: each token's x, B, C and
    dt in and its y out at the activations' width, and the row's state
    written once a launch and read once by every launch but the first
    (a fresh row starts from zeros; between launches the state has
    nowhere to wait but its slot). The chunked form's own passes are its
    own."""
    per_token = 2 * inner(fields) + 2 * fields["mamba_d_state"] \
        + fields["mamba_n_heads"]
    return tokens * per_token * act_itemsize \
        + (2 * chunks - 1) * state_values(fields) * itemsize


def prompt_least_seconds(fields, rows, tokens, peaks, chunks=1, itemsize=4):
    """The least seconds the recurrence over ``rows`` fresh rows'
    ``tokens`` prompt tokens each can take over every state-space
    layer: the larger of ``prompt_row_bytes`` over the chip's HBM
    bandwidth and the recurrence's own operations (``row_flops`` a
    token: a chunked form makes more, which are its own) over its
    peak."""
    return ssm_layers(fields) * rows * max(
        prompt_row_bytes(fields, tokens, chunks, itemsize)
        / peaks["hbm_bytes_per_s"],
        tokens * row_flops(fields) / peaks["bf16_flops_per_s"])
