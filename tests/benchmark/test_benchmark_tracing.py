"""The trace reduction on a hand-written event list with known answers,
and on one step of a real trace from the chip."""

import json
import types

import pytest

from benchmark import tracing
from benchmark.evidence import PROGRAM_SPANS, Evidence
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


def test_a_while_keeps_only_the_time_between_its_body_ops():
    selfs = {(e.name, e.start_s): s for e, s in tracing.self_times(HAND, DEV)}
    assert selfs[("while", 0.0)] == pytest.approx(0.0)
    assert len(selfs) == 7
    assert tracing.op_seconds(HAND, "^checkpoint") == \
        pytest.approx(0.7)
    assert tracing.op_share_percent(HAND, "^checkpoint") == \
        pytest.approx(100 * 0.7 / 2.4)
    assert tracing.op_seconds(HAND, ":tpu_custom_call$") == \
        pytest.approx(0.7)
    assert tracing.op_seconds(HAND, "no_such_kernel") == 0.0
    # a loop that runs 0.1 s longer than its body keeps that 0.1 s
    longer = [e._replace(dur_s=1.1) if e.name == "while" else e
              for e in HAND]
    assert tracing.op_seconds(longer, "^while$") == pytest.approx(0.1)


def small_op_inside(events, kernel, at=0.25, dur_s=1e-4):
    """``events`` with the issue of an async copy put inside the first
    ``kernel`` call's interval, as the v5e's traces show it."""
    host = next(e for e in events if e.name == kernel and e.line == OPS)
    inner = Event(host.plane, OPS, "slice-start.12",
                  host.start_s + at * host.dur_s, dur_s)
    return events + [inner], host


def test_a_kernel_with_a_small_op_inside_it_is_counted():
    nested, host = small_op_inside(HAND, KERNEL)
    # the kernel keeps all of its time but the small op's, which is its
    # own; dropping the call whole (0.3 of 0.7 s) is what moved a
    # kernel's time by a sixth between traced runs of one tree
    assert tracing.op_seconds(nested, "^checkpoint") == \
        pytest.approx(0.7 - 1e-4)
    assert tracing.op_seconds(nested, "^slice-start") == pytest.approx(1e-4)
    pieces = dict((e.name, p) for e, p in
                  tracing.self_intervals(nested, DEV) if e is host)[KERNEL]
    assert pieces == [pytest.approx((0.4, 0.475)),
                      pytest.approx((0.4751, 0.7))]
    assert dict(tracing.top_ops(nested))[KERNEL] == pytest.approx(0.7 - 1e-4)
    # what the chip showed (my runs, PR 32): an AllocateBuffer of 1.25 ns
    # whose start, rounded to the trace's nanoseconds, is the kernel's
    # own and whose length is 0. It sorts after the kernel, and a rule
    # that asks "does the next operation end inside this one" dropped the
    # call whole: 3 calls of 36 in one run, 7 in another
    rounded, _ = small_op_inside(HAND, KERNEL, at=0.0, dur_s=0.0)
    assert tracing.op_seconds(rounded, "^checkpoint") == pytest.approx(0.7)
    assert tracing.op_seconds(rounded, "^slice-start") == 0.0


@pytest.mark.parametrize("events", [
    [e for e in HAND if e.name != "all-gather.1"],
    small_op_inside([e for e in HAND if e.name != "all-gather.1"], KERNEL)[0],
    # nested three deep, and a child that ends with its parent
    [Event(DEV, OPS, "while.1", 0.0, 1.0), Event(DEV, OPS, "call.2", 0.1, 0.8),
     Event(DEV, OPS, "fusion.3", 0.2, 0.3), Event(DEV, OPS, "fusion.4", 0.6, 0.3),
     Event(DEV, OPS, "fusion.5", 1.2, 0.5)],
], ids=["hand", "kernel-with-a-small-op", "three-deep"])
def test_self_times_add_up_to_the_busy_time(events):
    busy, _ = tracing.busy_and_window(events)
    selfs = tracing.self_times(events, DEV)
    assert sum(s for _, s in selfs) == pytest.approx(busy)
    assert all(s >= 0 for _, s in selfs)
    # no instant belongs to two operations
    own = [iv for _, pieces in tracing.self_intervals(events, DEV)
           for iv in pieces]
    assert tracing.total(tracing.union(own)) == pytest.approx(
        tracing.total(own))


def test_a_slice_that_will_not_change_is_worked_out_once():
    plain = tracing.self_intervals(HAND, DEV)
    assert tracing.self_intervals(HAND, DEV) is not plain
    whole = tracing.Events(HAND)
    assert not hasattr(whole, "append") and list(whole) == HAND
    once = tracing.self_intervals(whole, DEV)
    assert tracing.self_intervals(whole, DEV) is once
    assert [(e, p) for e, p in once] == [(e, p) for e, p in plain]
    assert tracing.op_seconds(whole, "^checkpoint") == pytest.approx(0.7)
    assert tracing.collective_seconds(whole, DEV) == \
        tracing.collective_seconds(HAND, DEV)


def test_side_by_side_is_not_nested():
    # fusion.3 starts inside all-gather.1 and outlasts it: each keeps its
    # whole time, the 0.2 s they share is run by both
    pairs = tracing.self_times(HAND, DEV)
    selfs = {e.name: s for e, s in pairs}
    assert selfs["all-gather.1"] == pytest.approx(0.4)
    assert selfs["fusion.3"] == pytest.approx(0.8)
    assert sum(s for _, s in pairs) == pytest.approx(
        tracing.busy_and_window(HAND)[0] + 0.2)


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
    selfs = tracing.self_times(events, DEV)
    assert len(selfs) == 4039 == len([e for e in events if e.line == OPS])
    # the loops are not counted twice: self times add up to the busy time
    assert sum(s for _, s in selfs) == pytest.approx(busy, rel=1e-9)
    loops = [(e, s) for e, s in selfs if s < e.dur_s - 1e-12]
    assert len(loops) == 4 and {e.name.split(".")[0] for e, _ in loops} \
        == {"while"}
    assert sum(s for _, s in loops) < 1e-3 < sum(e.dur_s for e, _ in loops)
    # four flash kernels a layer, twelve layers: forward, the forward
    # again under activation checkpointing, dK/dV and dQ
    flash = [e for e, _ in selfs if e.name.endswith(":tpu_custom_call")]
    assert len(flash) == 48
    share = tracing.op_share_percent(events, ":tpu_custom_call$")
    assert share == pytest.approx(54.5, abs=0.05)
    # the issue of an async copy inside one kernel call takes that call's
    # share down by its own 1 us and no more (the call is 1/48 of 54.5 %)
    nested, host = small_op_inside(events, "checkpoint.20:tpu_custom_call",
                                   dur_s=1e-6)
    assert host.dur_s > 5e-3
    assert tracing.op_share_percent(nested, ":tpu_custom_call$") == \
        pytest.approx(share - 100 * 1e-6 / busy, rel=1e-9)
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


def test_the_breakdown_names_the_bookkeeping_span(monkeypatch):
    from deepspeed_tpu.telemetry import memory
    monkeypatch.setattr(memory, "scopes", lambda program: None)
    assert "train_bookkeeping" in PROGRAM_SPANS
    events = HAND + [Event(HOST, "python", "train_bookkeeping", 2.5, 0.1),
                     Event(HOST, "python", "some_runtime_traceme", 0.9, 0.7)]
    got = Evidence(ctx=None, events=events).breakdown()
    assert got["idle_gaps"] == [["train_host_sync", pytest.approx(0.5)],
                                ["train_bookkeeping", pytest.approx(0.1)]]
    assert got["device_ops"][0] == ["fusion.3", pytest.approx(0.8)]
