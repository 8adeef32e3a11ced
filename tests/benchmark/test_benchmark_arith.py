"""The benchmark's arithmetic against hand-worked answers."""

import json
import math
import statistics

import pytest

from benchmark import arith

OPT_125M = json.load(open("benchmark/configs/opt-125m.json"))["fields"]
OPT_1_3B = json.load(open("benchmark/configs/opt-1.3b.json"))["fields"]


def test_rate():
    assert arith.rate(65536 * 10, 20.0) == 32768.0
    with pytest.raises(ValueError):
        arith.rate(1, 0.0)


# a window of the dense cell: 64 steps of 0.6 s, 65,536 tokens a step,
# eight seeded batches a pass
STEPS, TOKENS, BLOCK = [0.6] * 64, 65536, 8
QUIET = TOKENS / 0.6


def mean_rate(step_s):
    return arith.rate(len(step_s) * TOKENS, sum(step_s))


def test_on_a_quiet_window_the_block_median_is_the_mean():
    assert arith.steady_rate(STEPS, BLOCK, TOKENS) == \
        pytest.approx(QUIET) == pytest.approx(mean_rate(STEPS))
    # the ragged last block is dropped: 5 steps of another speed after
    # 64 are not seen
    assert arith.steady_rate(STEPS + [0.9] * 5, BLOCK, TOKENS) == \
        pytest.approx(QUIET)


@pytest.mark.parametrize("at", [0, 7, 8, 37, 63])
def test_one_stall_of_the_host_moves_the_mean_and_not_the_median(at):
    # which is why the median stands beside train_tok_s and not for it
    stalled = list(STEPS)
    stalled[at] += 2.0
    assert mean_rate(stalled) < 0.97 * QUIET        # over 3 % down
    assert arith.steady_rate(stalled, BLOCK, TOKENS) == pytest.approx(QUIET)
    # and three blocks spoiled of eight are still not seen
    for other in (20, 50):
        stalled[other] += 1.0
    assert arith.steady_rate(stalled, BLOCK, TOKENS) == pytest.approx(QUIET)


@pytest.mark.parametrize("every,extra", [(4, 0.3), (2, 0.05), (8, 1.0)])
def test_what_the_program_pays_every_few_steps_moves_both_alike(every, extra):
    # a recompile, a periodic sync, an alternating path: inside every
    # block, so the median block carries it (the median STEP would not)
    paying = [0.6 + (extra if i % every == every - 1 else 0.0)
              for i in range(64)]
    got = arith.steady_rate(paying, BLOCK, TOKENS)
    assert got == pytest.approx(mean_rate(paying))
    assert got == pytest.approx(TOKENS / (0.6 + extra / every))
    if every > 2:
        assert TOKENS / statistics.median(paying) == pytest.approx(QUIET)


@pytest.mark.parametrize("steps", [0, 1, 7, 8, 15])
def test_fewer_than_two_whole_blocks_have_no_median(steps):
    assert arith.steady_rate([0.6] * steps, BLOCK, TOKENS) is None
    assert arith.steady_rate([0.6] * 16, BLOCK, TOKENS) == \
        pytest.approx(QUIET)


def test_matmul_params_of_the_two_opts():
    # per layer 4*h*h + 2*h*ffn
    assert arith.matmul_params(OPT_125M) == 12 * (4 * 768 ** 2
                                                  + 2 * 768 * 3072)
    assert arith.matmul_params(OPT_1_3B) == 24 * (4 * 2048 ** 2
                                                  + 2 * 2048 * 8192)


@pytest.mark.parametrize("fields,want_gflop", [
    (OPT_125M, 0.8545), (OPT_1_3B, 8.4698)])
def test_train_flops_per_token_counts_the_causal_half(fields, want_gflop):
    got = arith.train_flops_per_token(fields, 2048)
    assert got / 1e9 == pytest.approx(want_gflop, rel=1e-3)
    h, L, v = fields["hidden_size"], fields["num_layers"], 50272
    # the attention term alone: half of the program's 12*L*h*S
    assert got - 6 * arith.matmul_params(fields) - 6 * h * v == \
        pytest.approx(6.0 * L * h * 2048)


def test_mfu_is_rate_times_a_constant_over_the_peak():
    flops = arith.train_flops_per_token(OPT_125M, 2048)
    assert arith.mfu_percent(58000, flops, 197e12) == pytest.approx(
        100 * 58000 * flops / 197e12)
    assert 24 < arith.mfu_percent(58000, flops, 197e12) < 26


def test_flash_flops():
    # 6 matmuls of S*S*h, causal half, 2 flops a multiply-add
    assert arith.flash_flops(OPT_125M, 2048, 32) == \
        6.0 * 2048 * 2048 * 768 * 12 * 32


def test_roofline_share():
    assert arith.roofline_percent(1.0, 4.0) == 25.0
    assert arith.roofline_percent(1.0, 0.0) is None


def test_peaks_table_has_the_v5e_and_refuses_the_rest():
    p = arith.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    for kind in ("cpu", "TPU v4", "_source"):
        with pytest.raises(KeyError):
            arith.peaks(kind)
    assert math.isfinite(p["hbm_bytes"])
