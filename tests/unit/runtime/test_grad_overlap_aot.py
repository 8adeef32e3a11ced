"""Chip-free regression pin for the overlapped bucketed gradient reduction.

AOT-compiles the engine's real train step against a v5e:2x4 topology (the
libtpu compiler is a host library — no chip needed, same pipeline as
tests/model/test_flagship_scale.py) and asserts the PR's acceptance bar:
the gradient ``exposed_collective_fraction`` on the dp8 proxy drops from
1.0 (monolithic post-backward collective) to <= 0.5 under the bucketed
ring program. A change that silently reverts the reduction to one fused
synchronous collective fails HERE, not on the pod.
"""

import pytest

from deepspeed_tpu.benchmarks import aot_scale
from deepspeed_tpu.models import TransformerConfig


# slow, and run by nothing (the driver runs -m 'not slow'; ROADMAP D3). With
# the libtpu of this container the report finds no gradient collective at
# all in the bucketed step (total 0, so "exposed 0.0" is 0 of 0) and the two
# tests that count async ops fail, on PR 28's tree as on this one (PR 30).
pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def dp8_record():
    # the topology is described (and libtpu loaded) here, never while
    # the file is imported: every xdist worker imports every test file
    try:
        from jax.experimental import topologies
        topologies.get_topology_desc("v5e:2x4", platform="tpu")
    except Exception as e:  # noqa: BLE001 — whatever libtpu raises
        pytest.skip(f"no v5e:2x4 topology can be described here: {e}")
    # compact proxy: 2 unrolled layers keep the compile short while
    # still exercising layer-sliced buckets
    cfg = TransformerConfig(vocab_size=1024, hidden_size=256,
                            intermediate_size=512, num_layers=2,
                            num_heads=4, max_seq_len=128, use_flash=False,
                            scan_unroll=2)
    return aot_scale.grad_overlap_dp8(model_cfg=cfg, out_dir=None,
                                      reduce_bucket_size=1 << 18)


def test_grad_exposed_fraction_under_half(dp8_record):
    """The acceptance bar: bucketed gradient exchange <= 0.5 exposed (the
    seed's monolithic reduction measures 1.0)."""
    mono = dp8_record["exposed_collective_fraction_monolithic"]
    bucketed = dp8_record["exposed_collective_fraction"]
    assert mono > 0.9, dp8_record["monolithic"]
    assert bucketed <= 0.5, dp8_record["bucketed"]
    assert bucketed < mono


def test_bucketed_reduction_is_async_with_real_window(dp8_record):
    """The ring hops compile to async start/done pairs with compute
    actually scheduled inside the window (median > 1 instruction), and
    the bucket plan covers multiple buckets."""
    b = dp8_record["bucketed"]
    assert sum(b["async_ops"].values()) >= 7  # >= world-1 hops
    assert b["median_overlap_window"] > 1
    assert b["bucket_plan"]["num_buckets"] >= 2
    # layer slicing engaged: some bucket carries a per-layer slice
    names = [n for bk in b["bucket_plan"]["buckets"] for n in bk["leaves"]]
    assert any(n.endswith("[0]") or n.endswith("[1]") for n in names), names


def test_monolithic_baseline_is_sync(dp8_record):
    """The 'off' variant keeps the seed behavior: synchronous reduce-kind
    collectives only (this is what the bucketed program replaces)."""
    m = dp8_record["monolithic"]
    assert sum(m["sync_ops"].values()) >= 1
    assert not m["async_ops"]


def test_quantized_ring_keeps_overlap_and_shrinks_wire(dp8_record):
    """quantized_reduce=int8 on the same proxy: the int8 hops are still
    async ppermute pairs the scheduler overlaps (exposed fraction holds
    the PR-4 bar), and the plan's quantized wire bytes sit >= 3.5x below
    the fp32 ring's (the EQuARX compression bar)."""
    q = dp8_record["bucketed_int8"]
    assert dp8_record["exposed_collective_fraction_int8"] <= 0.5, q
    assert sum(q["async_ops"].values()) >= 7
    assert q["ring_wire_bytes_quant"] > 0
    assert dp8_record["quant_wire_ratio"] >= 3.5, dp8_record[
        "quant_wire_ratio"]
