"""The benchmark's own arithmetic: rates, model FLOPs, peaks and
roofline shares.

Nothing here imports the program. A later PR may edit the program's
``flops_per_token()`` or its peak table; it cannot edit these, so the
yardstick stays put while the program moves.
"""

import json
import statistics
from pathlib import Path

ROOT = Path(__file__).resolve().parent


def rate(amount, seconds):
    """``amount`` per second over a window of ``seconds``."""
    if seconds <= 0:
        raise ValueError(f"a rate needs a window longer than 0 s, got "
                         f"{seconds}")
    return amount / seconds


def steady_rate(step_seconds, block, amount_per_step):
    """The rate of the MEDIAN block of a window of whole steps that took
    ``step_seconds`` each, ``amount_per_step`` of work a step: a steadier
    statistic to stand BESIDE the rate over all the work and all the
    time, never in its place.

    The steps are grouped into consecutive blocks of ``block`` (one pass
    over the runner's distinct batches) and the ragged last block is
    dropped. A stall of the host spoils the block it falls in and the
    median does not see it, which is why this is no end-to-end reading:
    a user's tokens a second pay for every stall. What the program pays
    every few steps (a recompile, a periodic sync, a path that
    alternates) is inside every block and is counted, which the median
    step would hide. With fewer than two whole blocks there is no median
    to take: None."""
    whole = len(step_seconds) // block
    if whole < 2:
        return None
    blocks = [sum(step_seconds[i * block:(i + 1) * block])
              for i in range(whole)]
    return rate(block * amount_per_step, statistics.median(blocks))


def peaks(device_kind):
    """Published peaks of one chip of ``device_kind``. An unknown kind
    raises: a roofline share against a guessed peak is worse than none."""
    table = json.loads((ROOT / "peaks.json").read_text())
    if device_kind.startswith("_") or device_kind not in table:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r} in "
            f"benchmark/peaks.json (has: "
            f"{sorted(k for k in table if not k.startswith('_'))})")
    return table[device_kind]


# ---------------------------------------------------------------------------
# model operations
# ---------------------------------------------------------------------------
def matmul_params(fields):
    """Weights that sit in a matrix multiplication on every token of a
    dense pre-LN decoder: q, k, v, o and the two (or three, gated) MLP
    matrices per layer. Embedding lookups, biases and norms multiply no
    matrix and are left out; the LM head is counted apart."""
    h = fields["hidden_size"]
    nh = fields["num_heads"]
    kvh = fields.get("num_kv_heads") or nh
    hd = fields.get("head_dim_override") or h // nh
    ffn = fields["intermediate_size"]
    gated = fields.get("activation", "swiglu") in ("swiglu", "geglu",
                                                   "geglu_exact")
    attn = h * nh * hd + 2 * h * kvh * hd + nh * hd * h
    mlp = (3 if gated else 2) * h * ffn
    return fields["num_layers"] * (attn + mlp)


def train_flops_per_token(fields, seq_len):
    """Floating-point operations one token of a training step requires:
    forward plus backward, nothing recomputed (activation checkpointing
    re-runs the forward; those operations are NOT counted, as the MFU
    convention has it).

    * 6 per matrix weight (2 forward, 4 backward), LM head included
      (tied or not, the head is a hidden x vocab matmul).
    * attention: CAUSAL HALF ONLY. A token at position p attends p keys,
      on average seq/2. Forward is two matmuls (QK^T, PV) of 2*h flops
      per attended key, backward four (dV, dP, dQ, dK):
      6 * 2 * h * seq/2 = 6 * h * seq per layer. The program's
      ``flops_per_token()`` counts the masked half too (12 * h * seq).
    """
    h, L, v = (fields["hidden_size"], fields["num_layers"],
               fields["vocab_size"])
    return (6.0 * matmul_params(fields) + 6.0 * h * v
            + 6.0 * L * h * seq_len)


def mfu_percent(tokens_per_s_per_chip, flops_per_token, peak_flops):
    return 100.0 * tokens_per_s_per_chip * flops_per_token / peak_flops


def flash_flops(fields, seq_len, sequences):
    """Operations causal flash attention must do for ``sequences``
    sequences of ``seq_len`` through every layer, forward and backward:
    six S x S x head_dim matmuls per head (QK^T, PV, dV, dP, dQ, dK), the
    causal half of each, 2 flops a multiply-add. The backward's
    recomputation of QK^T is the kernel's choice and is not counted."""
    h = fields["num_heads"] * (fields.get("head_dim_override")
                               or fields["hidden_size"]
                               // fields["num_heads"])
    return 6.0 * seq_len * seq_len * h * fields["num_layers"] * sequences


def roofline_percent(least_seconds, kernel_seconds):
    if kernel_seconds <= 0:
        return None
    return 100.0 * least_seconds / kernel_seconds
