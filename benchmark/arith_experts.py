"""The benchmark's arithmetic for a layer of routed experts: what the
grouped matmuls must at least move and compute for the rows a launch
routed, whatever computes them.

Beside ``arith_gen.py`` and not in it. Nothing here imports the program.
"""


def expert_bytes(fields, itemsize=2):
    """Bytes of one routed expert's three SwiGLU matrices (2048 x 768
    each: 9,437,184 B in bf16)."""
    return 3 * fields["hidden_size"] * fields["moe_intermediate_size"] \
        * itemsize


def row_flops(fields):
    """Floating-point operations one routed row makes in its expert:
    three matmuls of hidden x expert width, two operations a
    multiply-add."""
    return 3 * 2 * fields["hidden_size"] * fields["moe_intermediate_size"]


def expert_layers(fields):
    return fields["num_layers"] - fields.get("moe_first_dense_layers", 0)


def pass_least_seconds(fields, touched, rows, peaks):
    """The least seconds ONE expert layer's routed matmuls can take in
    one launch that routed ``rows`` rows to ``touched`` distinct
    experts: the larger of streaming each touched expert's weights once
    over the chip's HBM bandwidth (rows and results are small beside
    them and are left out, which only lowers the floor) and the rows'
    operations over its bf16 peak."""
    return max(touched * expert_bytes(fields) / peaks["hbm_bytes_per_s"],
               rows * row_flops(fields) / peaks["bf16_flops_per_s"])


def least_seconds(fields, kinds, peaks):
    """Over program kinds: ``kinds`` is ``[(passes in the slice, mean
    experts touched a pass, mean rows a pass)]``, a pass one expert
    layer in one launch."""
    return sum(passes * pass_least_seconds(fields, touched, rows, peaks)
               for passes, touched, rows in kinds)
