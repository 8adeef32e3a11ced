"""The benchmark's arithmetic for the ``falcon_h1`` block's two-mixer
layers: what the state-space half's recurrence must at least move and
compute, whatever implements it, at a state of ``mamba_d_state`` a
channel with B and C in ``mamba_n_groups`` groups.

Beside ``arith_ssm.py`` and ``arith_nemotron.py`` and not in them: both
count the ``mamba`` entries of ``fields.layer_types`` (another block's
layers), and both add the convolution's taps to a row's state, which the
program moves under another scope (``ssm_conv``) than the one these
floors are read against (``ssm_state`` / ``ssm_scan``). Here the layers
are the ``mamba_attention`` entries and the bytes are those of the
state leaf the program really has, ``[layers, slots, channels / 128,
d_state, 128]`` float32. Nothing here imports the program.
"""

LAYER = "mamba_attention"


def hybrid_layers(fields):
    """Layers of the pattern that are two-mixer layers."""
    return sum(1 for t in fields.get("layer_types") or () if t == LAYER)


def inner(fields):
    """Channels of the state-space half's x and y: heads x d_head
    (``mamba_d_ssm`` 4,096; NOT expand x hidden)."""
    return fields["mamba_n_heads"] * fields["mamba_d_head"]


def bc_values(fields):
    """Values of one token's B and C together: two vectors of d_state a
    group (2 x 2 x 256 = 1,024)."""
    return 2 * fields.get("mamba_n_groups", 1) * fields["mamba_d_state"]


def state_values(fields):
    """Values of one row's recurrent state in ONE layer: channels x
    d_state (4,096 x 256 = 1,048,576: the slot's ``[32, 256, 128]``)."""
    return inner(fields) * fields["mamba_d_state"]


def state_row_bytes(fields, itemsize=4):
    """Bytes ONE layer's one-token update moves for one row: the row's
    state read once and written once (float32: 8,388,608 B). The token's
    own x, B, C, dt and output are small beside it and are left out,
    which only lowers the floor."""
    return 2 * state_values(fields) * itemsize


def state_row_flops(fields):
    """Floating-point operations of one row's one-token update in ONE
    layer: a state's value decays (1), takes ``(dt x) B`` (2) and is
    read against ``C`` (2)."""
    return 5 * state_values(fields)


def state_least_seconds(fields, rows, steps, peaks, itemsize=4):
    """The least seconds the one-token updates of ``steps`` decode steps
    of ``rows`` rows can take over every two-mixer layer: the larger of
    the states' bytes over the chip's HBM bandwidth and the operations
    over its peak (the bf16 matmul peak: elementwise float32 work is
    slower still, which only lowers the floor)."""
    return hybrid_layers(fields) * steps * rows * max(
        state_row_bytes(fields, itemsize) / peaks["hbm_bytes_per_s"],
        state_row_flops(fields) / peaks["bf16_flops_per_s"])


def scan_row_bytes(fields, tokens, chunks=1, itemsize=4, act_itemsize=2):
    """Bytes ONE layer's recurrence moves for one FRESH row's ``tokens``
    prompt tokens fed in ``chunks`` launches: each token's x, both
    groups' B and C in and its y out at the activations' width, its dt
    (a head a value, float32), and the row's state written once a launch
    and read once by every launch but the first (a fresh row starts from
    zeros; between launches the state has nowhere to wait but its
    slot)."""
    per_token = (2 * inner(fields) + bc_values(fields)) * act_itemsize \
        + fields["mamba_n_heads"] * 4
    return tokens * per_token \
        + (2 * chunks - 1) * state_values(fields) * itemsize


def scan_least_seconds(fields, rows, tokens, peaks, chunks=1, itemsize=4):
    """The least seconds the recurrence over ``rows`` fresh rows'
    ``tokens`` prompt tokens each can take over every two-mixer layer:
    the larger of ``scan_row_bytes`` over the chip's HBM bandwidth and
    the recurrence's own operations (``state_row_flops`` a token: a
    chunked form makes more, which are its own) over its peak."""
    return hybrid_layers(fields) * rows * max(
        scan_row_bytes(fields, tokens, chunks, itemsize)
        / peaks["hbm_bytes_per_s"],
        tokens * state_row_flops(fields) / peaks["bf16_flops_per_s"])
