"""The benchmark's arithmetic for generation: the tokens a window
generated, and the bytes paged attention must move for them.

Beside ``arith.py`` and not in it: no file an accepted cell reads is
edited for a new cell. Nothing here imports the program.
"""


def generated_tokens(outputs, prompt_len):
    """Tokens ``generate()`` added to its prompts: each returned row less
    its prompt. Prompt tokens are not counted."""
    return sum(len(row) - prompt_len for row in outputs)


def kv_bytes_per_token(fields, itemsize=2):
    """Bytes one cached position holds in ONE layer: a key and a value
    for every kv head."""
    nh = fields["num_heads"]
    kvh = fields.get("num_kv_heads") or nh
    hd = fields.get("head_dim_override") or fields["hidden_size"] // nh
    return 2 * kvh * hd * itemsize


def generate_call_rows(rows, prompt_len, new_tokens):
    """``(new tokens, context)`` of every (launch, row) of one greedy
    ``generate()`` call without an early stop: the prefill feeds
    ``prompt_len`` tokens into an empty cache and gives the first new
    token; each of the other ``new_tokens - 1`` is one decode step that
    feeds the token before it, so the contexts run from ``prompt_len +
    1`` to ``prompt_len + new_tokens - 1`` (the last token is never
    fed)."""
    out = [(prompt_len, prompt_len)] * rows
    for step in range(1, new_tokens):
        out += [(1, prompt_len + step)] * rows
    return out


def ragged_attention_bytes(fields, launch_rows, itemsize=2):
    """Bytes paged attention must move through HBM for ``launch_rows``,
    a list of ``(new tokens, context)`` per row and launch, over every
    layer: the ``context`` cached keys and values the row's newest token
    attends, read ONCE for the row however many of its tokens share
    them, and each new token's query read and output written once. No
    kernel that keeps its keys and values in a paged pool moves less, so
    a share of this over a kernel's time cannot pass 100 %. (A kernel
    that walks the pages per token re-reads a prefill's keys for every
    token of the chunk; those bytes are its own and are not counted.)"""
    nh = fields["num_heads"]
    hd = fields.get("head_dim_override") or fields["hidden_size"] // nh
    kv = kv_bytes_per_token(fields, itemsize)
    qo = 2 * nh * hd * itemsize
    per_layer = sum(ctx * kv + new * qo for new, ctx in launch_rows)
    return fields["num_layers"] * per_layer
