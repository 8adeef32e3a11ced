"""The benchmark's arithmetic for the ``nemotron_h`` block's two new
mechanisms: what the routed experts' grouped matmuls and the state-space
layers' recurrence must at least move and compute, whatever implements
them.

Beside ``arith_experts.py`` and ``arith_ssm.py`` and not in them (both
are another block's: an expert of THREE matrices on every layer behind
the leading dense ones; B and C of ONE group). Here an expert is TWO
matrices (``down(relu(up x)^2)``: no gate), the expert layers are the
``moe`` entries of ``fields.layer_types`` (a layer is one sub-layer), and
a token's B and C are ``mamba_n_groups`` vectors each. Nothing here
imports the program.
"""


def expert_layers(fields):
    """Layers of the pattern that are expert layers."""
    return sum(1 for t in fields.get("layer_types") or () if t == "moe")


def ssm_layers(fields):
    """Layers of the pattern that are state-space layers."""
    return sum(1 for t in fields.get("layer_types") or () if t == "mamba")


def expert_bytes(fields, itemsize=2):
    """Bytes of one routed expert's TWO matrices (2,688 x 1,856 each:
    19,955,712 B in bf16)."""
    return 2 * fields["hidden_size"] * fields["moe_intermediate_size"] \
        * itemsize


def expert_row_flops(fields):
    """Floating-point operations one routed row makes in its expert:
    two matmuls of hidden x expert width, two operations a
    multiply-add (the squared ReLU is small beside them and is left out,
    which only lowers the floor)."""
    return 2 * 2 * fields["hidden_size"] * fields["moe_intermediate_size"]


def expert_pass_least_seconds(fields, touched, rows, peaks):
    """The least seconds ONE expert layer's routed matmuls can take in
    one launch that routed ``rows`` rows to ``touched`` distinct held
    experts: the larger of streaming each touched expert's two matrices
    once over the chip's HBM bandwidth (rows and results are small
    beside them and are left out, which only lowers the floor) and the
    rows' operations over its bf16 peak."""
    return max(touched * expert_bytes(fields) / peaks["hbm_bytes_per_s"],
               rows * expert_row_flops(fields) / peaks["bf16_flops_per_s"])


def experts_least_seconds(fields, kinds, peaks):
    """Over program kinds: ``kinds`` is ``[(passes in the slice, mean
    experts touched a pass, mean rows a pass)]``, a pass one expert
    layer in one launch."""
    return sum(passes * expert_pass_least_seconds(fields, touched, rows,
                                                  peaks)
               for passes, touched, rows in kinds)


def inner(fields):
    """Channels of the mixer's x and y: heads x d_head (NOT expand x
    hidden)."""
    return fields["mamba_n_heads"] * fields["mamba_d_head"]


def bc_values(fields):
    """Values of one token's B and C together: two vectors of d_state a
    group (2 x 8 x 128 = 2,048)."""
    return 2 * fields.get("mamba_n_groups", 1) * fields["mamba_d_state"]


def state_values(fields):
    """Values of one row's state in ONE state-space layer: heads x
    d_head x d_state of the recurrence, and the convolution's last taps
    - 1 inputs of x and of every group's B and C (64 x 64 x 128 + 3 x
    6,144 = 542,720)."""
    return inner(fields) * fields["mamba_d_state"] \
        + (fields.get("mamba_d_conv", 4) - 1) \
        * (inner(fields) + bc_values(fields))


def state_row_bytes(fields, itemsize=4):
    """Bytes ONE state-space layer's update moves for one row and
    token: the row's state read once and written once (float32:
    4,341,760 B). The token's own x, B, C, dt and output are small
    beside it and are left out, which only lowers the floor."""
    return 2 * state_values(fields) * itemsize


def state_row_flops(fields):
    """Floating-point operations of one row's one-token update in ONE
    layer: a state's value decays (1), takes ``(dt x) B`` (2) and is
    read against ``C`` (2)."""
    return 5 * inner(fields) * fields["mamba_d_state"]


def state_least_seconds(fields, rows, steps, peaks, itemsize=4):
    """The least seconds the one-token updates of ``steps`` decode steps
    of ``rows`` rows can take over every state-space layer: the larger
    of the states' bytes over the chip's HBM bandwidth and the
    operations over its peak (the bf16 matmul peak: elementwise float32
    work is slower still, which only lowers the floor)."""
    return ssm_layers(fields) * steps * rows * max(
        state_row_bytes(fields, itemsize) / peaks["hbm_bytes_per_s"],
        state_row_flops(fields) / peaks["bf16_flops_per_s"])


def scan_row_bytes(fields, tokens, chunks=1, itemsize=4, act_itemsize=2):
    """Bytes ONE state-space layer moves for one FRESH row's ``tokens``
    prompt tokens fed in ``chunks`` launches: each token's x, B and C
    of ALL groups and dt in and its y out at the activations' width, and
    the row's state written once a launch and read once by every launch
    but the first (a fresh row starts from zeros; between launches the
    state has nowhere to wait but its slot)."""
    per_token = 2 * inner(fields) + bc_values(fields) \
        + fields["mamba_n_heads"]
    return tokens * per_token * act_itemsize \
        + (2 * chunks - 1) * state_values(fields) * itemsize


def scan_least_seconds(fields, rows, tokens, peaks, chunks=1, itemsize=4):
    """The least seconds the recurrence over ``rows`` fresh rows'
    ``tokens`` prompt tokens each can take over every state-space
    layer: the larger of ``scan_row_bytes`` over the chip's HBM
    bandwidth and the recurrence's own operations (``state_row_flops``
    a token: a chunked form makes more, which are its own) over its
    peak."""
    return ssm_layers(fields) * rows * max(
        scan_row_bytes(fields, tokens, chunks, itemsize)
        / peaks["hbm_bytes_per_s"],
        tokens * state_row_flops(fields) / peaks["bf16_flops_per_s"])
