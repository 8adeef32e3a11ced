"""The benchmark's arithmetic for attention over a LATENT pool
(multi-head latent attention): what a launch must at least move and
compute, whatever form the kernel takes.

Beside ``arith_gen.py`` and not in it: no file an accepted cell reads is
edited for a new cell. Nothing here imports the program.
"""


def latent_row_bytes(fields, itemsize=2):
    """Bytes one cached position holds in ONE layer: the normed latent
    and the rotated key part all heads share (512 + 64 values: 1,152 B
    in bf16). Lanes a pool pads a row with are the pool's own and are
    not counted."""
    return (fields["kv_lora_rank"] + fields["qk_rope_head_dim"]) * itemsize


def launches(launch_rows, rows):
    """``arith_gen.generate_call_rows``' flat list of ``(new tokens,
    context)`` a row and launch, as a list of launches of ``rows`` rows
    each (a call's prefill, then each decode step)."""
    if len(launch_rows) % rows:
        raise ValueError(f"{len(launch_rows)} row-launches are not whole "
                         f"launches of {rows} rows")
    return [launch_rows[i:i + rows] for i in range(0, len(launch_rows),
                                                   rows)]


def visible_positions(new, context):
    """Positions the ``new`` tokens of one row attend, together, when
    the last of them sees ``context`` (itself included): token i of the
    chunk sees ``context - new + i + 1``."""
    return new * (context - new) + new * (new + 1) // 2


def launch_bytes(fields, launch, itemsize=2):
    """Bytes ONE layer's attention must move through HBM for one launch
    (a list of ``(new, context)`` a row): each row's ``context`` cached
    rows ONCE, however many of its tokens or heads read them, and each
    new token's query read and output written at their smallest form,
    the expanded one (heads x (128 + 64) in, heads x 128 out; the
    absorbed form's 576- and 512-wide ones are the kernel's own)."""
    nh = fields["num_heads"]
    q = nh * (fields["qk_nope_head_dim"] + fields["qk_rope_head_dim"])
    o = nh * fields["v_head_dim"]
    row = latent_row_bytes(fields, itemsize)
    return sum(ctx * row + new * (q + o) * itemsize for new, ctx in launch)


def launch_flops(fields, launch):
    """Floating-point operations ONE layer's attention must make for one
    launch, counted in the CHEAPER, expanded form: a score over 128 + 64
    and a value over 128 a head and visible position, two operations a
    multiply-add. (The absorbed form makes 576 + 512: those are the
    kernel's own.)"""
    per = fields["num_heads"] * (fields["qk_nope_head_dim"]
                                 + fields["qk_rope_head_dim"]
                                 + fields["v_head_dim"]) * 2
    return sum(visible_positions(new, ctx) for new, ctx in launch) * per


def least_seconds(fields, launch_rows, rows, peaks):
    """The least seconds every layer's attention can take over the
    launches of ``launch_rows``: launch by launch the larger of the byte
    floor over the chip's HBM bandwidth and the FLOP floor over its
    bf16 peak, summed, times the layers. No implementation beats it, so
    a share of it over a kernel's time cannot pass 100 %."""
    one = sum(max(launch_bytes(fields, ln) / peaks["hbm_bytes_per_s"],
                  launch_flops(fields, ln) / peaks["bf16_flops_per_s"])
              for ln in launches(launch_rows, rows))
    return fields["num_layers"] * one
