"""The latent attention kernel's share of its roofline over the traced
slice, %, for a configuration whose layer PATTERN makes only some of its
layers latent (``linear_attn_period`` p: layer i where (i + 1) % p ==
0): ``latent_roofline``'s number with the pattern's latent layers in
place of ``num_layers`` (``arith_latent.least_seconds`` multiplies by
``num_layers``: 8 here, where one layer is latent). A configuration
without a pattern reads nothing here (``latent_roofline.gen`` is its
metric); otherwise as ``latent_roofline``. params: ``pattern``."""

from .. import arith, arith_latent, tracing


def latent_layers(fields):
    p = fields["linear_attn_period"]
    return sum(1 for i in range(fields["num_layers"]) if (i + 1) % p == 0)


def read(ev, params):
    fields = ev.ctx.fields
    rows = getattr(ev, "launch_rows", None)
    if not ev.events or not rows or "kv_lora_rank" not in fields \
            or not fields.get("linear_attn_period"):
        return None
    seconds = tracing.op_seconds(ev.events, params["pattern"])
    if seconds <= 0:
        return None
    peaks = arith.peaks(ev.ctx.devices[0].device_kind)
    least = arith_latent.least_seconds(
        dict(fields, num_layers=latent_layers(fields)), rows,
        ev.ctx.traffic["rows"], peaks) \
        * ev.slice_steps / len(ev.ctx.devices)
    return arith.roofline_percent(least, seconds)
