"""Counts of the ``lfm2_moe`` block (``reference_lfm2.py``): parameters
by part, what a decode step has to stream, what a prompt has to compute,
and what the gated taps of one short-convolution layer move. Plain
arithmetic on a configuration's ``fields``; PERF.md's floors and the
tests' hand counts are these.
"""


def parts(fields):
    """Parameters of one of each part."""
    f = fields
    h, nh, nkv = f["hidden_size"], f["num_heads"], f["num_kv_heads"]
    hd = h // nh
    expert = 3 * h * f["moe_intermediate_size"]
    return {
        "expert": expert,
        "expert_layer": f["moe_num_experts"] * (expert + h + 1),
        "conv_mixer": h * 3 * h + f["conv_taps"] * h + h * h,
        "attention": 2 * h * nh * hd + 2 * h * nkv * hd + 2 * hd,
        "dense_mlp": 3 * h * f["intermediate_size"],
        "norms": 2 * h,                 # a layer's two
        "table": f["vocab_size"] * h}   # tied: the head too


def parameters(fields):
    """Every parameter of the served layers, the table and the final
    norm."""
    f, p = fields, parts(fields)
    lead = f["moe_first_dense_layers"]
    kinds = f["layer_types"]
    return (lead * p["dense_mlp"]
            + (f["num_layers"] - lead) * p["expert_layer"]
            + kinds.count("conv") * p["conv_mixer"]
            + kinds.count("full_attention") * p["attention"]
            + f["num_layers"] * p["norms"] + p["table"]
            + f["hidden_size"])


def state_bytes(fields, slots, itemsize=4):
    """The conv leaf: ``slots`` rows (the null slot counted by the
    caller) of ``taps - 1`` gated inputs a conv layer."""
    f = fields
    return (slots * f["layer_types"].count("conv") * (f["conv_taps"] - 1)
            * f["hidden_size"] * itemsize)


def pool_bytes(fields, num_blocks, block_size, itemsize=2):
    """Keys and values of the attention layers' pool."""
    f = fields
    row = f["num_kv_heads"] * (f["hidden_size"] // f["num_heads"])
    return (2 * f["layer_types"].count("full_attention") * num_blocks
            * block_size * row * itemsize)


def decode_step_bytes(fields, rows, context, itemsize=2, state_itemsize=4):
    """What one decode step of ``rows`` rows at ``context`` positions
    reads and writes at least: every weight once (every expert is
    touched where rows x top_k picks land on few experts), the rows'
    keys and values, the rows' conv state out and back."""
    f = fields
    row = f["num_kv_heads"] * (f["hidden_size"] // f["num_heads"])
    kv = 2 * f["layer_types"].count("full_attention") * rows * context \
        * row * itemsize
    return parameters(fields) * itemsize + kv \
        + 2 * state_bytes(fields, rows, state_itemsize)


def prompt_flops(fields, tokens, context):
    """Matmul operations of ``tokens`` prompt tokens whose rows end at
    ``context`` positions (2 a multiply-add): every matrix a token
    passes (its top_k experts of the expert layers), the causal scores
    and their values at half the context on average, the head left out
    (a chunk step hands on one token a row)."""
    f, p = fields, parts(fields)
    lead = f["moe_first_dense_layers"]
    kinds = f["layer_types"]
    h = f["hidden_size"]
    a_token = (lead * p["dense_mlp"]
               + (f["num_layers"] - lead) * (
                   f["moe_top_k"] * p["expert"]
                   + h * f["moe_num_experts"])
               + kinds.count("conv") * (h * 3 * h + h * h)
               + kinds.count("full_attention")
               * (p["attention"] - 2 * (h // f["num_heads"])))
    scores = kinds.count("full_attention") * 2 * h * (context / 2)
    return 2 * tokens * (a_token + scores)


def conv_gate_bytes(fields, rows, itemsize=4, state_itemsize=4):
    """What the gated taps of ONE conv layer move for ``rows`` one-token
    rows: ``W_in``'s output read (3 H a row), an H-wide row written, the
    row's slot read and written."""
    f = fields
    h = f["hidden_size"]
    return rows * (4 * h * itemsize
                   + 2 * (f["conv_taps"] - 1) * h * state_itemsize)
