"""The trace reduction on a hand-written event list with known answers,
and on one step of a real trace from the chip."""

import json
import types

import pytest

from benchmark import tracing
from benchmark.evidence import PROGRAM_SPANS
from benchmark.tracing import Event

DEV, HOST = "/device:TPU:0", "/host:CPU"
OPS, MODS = tracing.OPS_LINE, tracing.MODULES_LINE
KERNEL = "checkpoint.20:tpu_custom_call"      # an unnamed Pallas kernel

# three programs: A [0, 1], B [1.5, 2.5], C [2.6, 3.0]; the window is 3 s
# of which 2.4 s are busy, so 20 % idle. A's ops sit inside a `while`.
HAND = [
    Event(DEV, MODS, "jit_train_step(1)", 0.0, 1.0),
    Event(DEV, MODS, "jit_train_step(2)", 1.5, 1.0),
    Event(DEV, MODS, "jit_train_step(1)", 2.6, 0.4),
    Event(DEV, OPS, "while", 0.0, 1.0),
    Event(DEV, OPS, "fusion.1", 0.0, 0.4),
    Event(DEV, OPS, KERNEL, 0.4, 0.3),
    Event(DEV, OPS, "fusion.2", 0.7, 0.3),
    Event(DEV, OPS, "all-gather.1", 1.5, 0.4),      # 0.2 s alone,
    Event(DEV, OPS, "fusion.3", 1.7, 0.8),          # 0.2 s under compute
    # on the async line: a copy (not a collective) and the async half of
    # a reduce-scatter, 0.2 s under fusion.3 and 0.1 s past its end
    Event(DEV, tracing.ASYNC_LINE, "copy-start.4", 0.0, 1.0),
    Event(DEV, tracing.ASYNC_LINE, "reduce-scatter-start.2", 2.3, 0.3),
    Event(DEV, OPS, KERNEL, 2.6, 0.4),
    Event(HOST, "python", "train_host_sync", 0.95, 0.5),  # covers gap 1
    Event(HOST, "python", "train_data", 2.58, 0.45),      # too little of
]                                                         # gap 2


def test_idle_share_is_one_minus_the_union_of_op_intervals():
    busy, window = tracing.busy_and_window(HAND)
    assert (busy, window) == (pytest.approx(2.4), pytest.approx(3.0))
    assert tracing.idle_percent(HAND) == pytest.approx(20.0)


def test_leaf_ops_leave_the_while_wrapper_out():
    names = [e.name for e in tracing.leaf_ops(HAND, DEV)]
    assert "while" not in names and len(names) == 6
    assert tracing.op_seconds(HAND, "^checkpoint") == \
        pytest.approx(0.7)
    assert tracing.op_share_percent(HAND, "^checkpoint") == \
        pytest.approx(100 * 0.7 / 2.4)
    assert tracing.op_seconds(HAND, ":tpu_custom_call$") == \
        pytest.approx(0.7)
    assert tracing.op_seconds(HAND, "no_such_kernel") == 0.0


def test_gaps_are_charged_to_the_covering_span():
    gaps = tracing.program_gaps(HAND, DEV)
    assert gaps == [pytest.approx((1.0, 1.5)), pytest.approx((2.5, 2.6))]
    hosts = [e for e in HAND if e.plane == HOST]
    got = tracing.attribute_gaps(gaps, hosts)
    assert got[0] == ("train_host_sync", pytest.approx(0.5))
    # train_data covers only 0.02 of the 0.1 s gap: under half
    assert got[1] == ("(no span)", pytest.approx(0.1))
    assert tracing.attribute_gaps(gaps, hosts, top=1) == got[:1]


def test_collective_time_and_its_exposed_part():
    # all-gather [1.5, 1.9] and reduce-scatter [2.3, 2.6]; fusion.3 covers
    # [1.7, 2.5] and program C's kernel starts at 2.6
    total, alone = tracing.collective_seconds(HAND, DEV)
    assert (total, alone) == (pytest.approx(0.7), pytest.approx(0.3))
    sync_only = [e for e in HAND if e.line != tracing.ASYNC_LINE]
    assert tracing.collective_seconds(sync_only, DEV) == \
        (pytest.approx(0.4), pytest.approx(0.2))


def test_top_ops_and_interval_helpers():
    top = dict(tracing.top_ops(HAND, top=3))
    assert top["fusion.3"] == pytest.approx(0.8)
    assert top[KERNEL] == pytest.approx(0.7)
    assert tracing.union([(0, 1), (0.5, 2), (3, 4), (4, 4)]) == \
        [(0, 2), (3, 4)]
    assert tracing.overlap([(0, 2), (3, 4)], [(1, 3.5)]) == \
        pytest.approx(1.5)
    assert tracing.clip([(0, 2), (3, 4)], 1, 3.2) == [(1, 2), (3, 3.2)]
    with pytest.raises(ValueError):
        tracing.window([e for e in HAND if e.plane == HOST])


@pytest.mark.parametrize("full,short", [
    ("%fusion.4 = bf16[8,2048]{1,0} fusion(bf16[8] %p), kind=kLoop",
     "fusion.4"),
    ('%checkpoint.20 = (bf16[384,2048,64]) custom-call(bf16[1] %x), '
     'custom_call_target="tpu_custom_call", operand_layout_constraints={}',
     "checkpoint.20:tpu_custom_call"),
    ('%custom-call.7 = bf16[12] custom-call(), '
     'custom_call_target="AllocateBuffer"', "custom-call.7"),
    ("jit_train_step(5587994471015668583)",
     "jit_train_step(5587994471015668583)"),
    ("train_host_sync", "train_host_sync"),
])
def test_short_name(full, short):
    assert tracing.short_name(full) == short


def test_readers_on_the_hand_written_events():
    from benchmark.readers import collective_time, idle_share, op_time_share
    ev = types.SimpleNamespace(events=HAND, slice_steps=2)
    assert idle_share.read(ev, {}) == pytest.approx(20.0)
    assert op_time_share.read(ev, {"pattern": "nothing"}) is None
    assert op_time_share.read(ev, {"pattern": "all-gather"}) == \
        pytest.approx(100 * 0.4 / 2.4)
    assert collective_time.read(ev, {}) == pytest.approx(350.0)
    assert collective_time.read(ev, {"exposed": True}) == \
        pytest.approx(100 * 0.3 / 0.7)
    empty = types.SimpleNamespace(events=[], slice_steps=0)
    for reader in (idle_share, collective_time):
        assert reader.read(empty, {}) is None
    assert op_time_share.read(empty, {"pattern": "x"}) is None


def test_one_step_of_a_real_chip_trace():
    """One step of opt-125m.train-dense on the v5e and the small program
    after it (PR 24's probe run), in the neutral form: the reduction finds
    the unnamed flash kernels by their mark, leaves the wrappers out, and
    charges the gap between the two programs."""
    doc = json.load(open("tests/benchmark/fixtures/real_train_step.json"))
    events = tracing.from_jsonable(
        [doc["planes"][p], doc["lines"][ln], n, s * 1e-9, d * 1e-9]
        for p, ln, n, s, d in doc["rows"])
    assert tracing.device_planes(events) == [DEV]
    busy, window = tracing.busy_and_window(events)
    assert 0 < busy <= window
    assert tracing.idle_percent(events) == pytest.approx(0.318, abs=1e-3)
    leaves = tracing.leaf_ops(events, DEV)
    assert len(leaves) == 4020 < len([e for e in events if e.line == OPS])
    # four flash kernels a layer, twelve layers: forward, the forward
    # again under activation checkpointing, dK/dV and dQ
    flash = [e for e in leaves if e.name.endswith(":tpu_custom_call")]
    assert len(flash) == 48
    assert tracing.op_share_percent(events, ":tpu_custom_call$") == \
        pytest.approx(54.5, abs=0.05)
    assert tracing.collective_seconds(events, DEV) == (0, 0.0)
    mods = tracing.modules(events, DEV)
    assert [m.name.split("(")[0] for m in mods] == ["jit_train_step",
                                                    "jit__lambda"]
    gaps = tracing.program_gaps(events, DEV)
    hosts = [e for e in events if e.plane == HOST]
    assert {e.name for e in hosts} <= set(PROGRAM_SPANS)
    assert tracing.attribute_gaps(gaps, hosts) == [
        ("(no span)", pytest.approx(3.42e-3, abs=1e-5))]
    assert tracing.top_ops(events, top=1)[0][0] == \
        "checkpoint.20:tpu_custom_call"
