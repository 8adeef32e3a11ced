"""The collector's pauses (telemetry/collector.py): every collection
counted by generation, the long ones as ``gc_pause`` spans, one hook a
process, and no lock waited for inside a collection."""

import gc

import pytest

from deepspeed_tpu.telemetry import (MetricsRegistry, collector,
                                     get_registry, set_registry, trace)

COUNT = "process_gc_collections_total"
PAUSE = "process_gc_pause_seconds"


@pytest.fixture
def registry():
    prev = set_registry(MetricsRegistry())
    trace.clear()
    try:
        yield get_registry()
    finally:
        set_registry(prev)
        trace.clear()


def _count(reg, generation):
    return reg.get(COUNT).labels(generation=str(generation)).value


def _pauses():
    return [s for s in trace.export() if s["name"] == "gc_pause"]


def test_a_forced_collection_is_counted_once(registry, monkeypatch):
    monkeypatch.setattr(collector, "SPAN_FROM_S", float("inf"))
    collector.install_gc_hook()
    before = _count(registry, 2)
    seen = registry.get(PAUSE).labels(generation="2").count
    gc.collect()
    assert _count(registry, 2) == before + 1
    assert registry.get(PAUSE).labels(generation="2").count == seen + 1
    assert registry.get(PAUSE).labels(generation="2").sum > 0
    gc.collect(0)
    assert _count(registry, 0) >= 1 and _count(registry, 2) == before + 1


@pytest.mark.parametrize("threshold,spans", [(0.0, 1), (float("inf"), 0)])
def test_a_span_only_for_a_collection_of_the_threshold_or_more(
        registry, monkeypatch, threshold, spans):
    """The threshold is driven, not slept for: at 0 every collection is
    long enough, at infinity none is."""
    collector.install_gc_hook()
    gc.collect()                    # what is there to collect goes first
    trace.clear()
    monkeypatch.setattr(collector, "SPAN_FROM_S", threshold)
    gc.collect(2)
    monkeypatch.setattr(collector, "SPAN_FROM_S", float("inf"))
    got = _pauses()
    assert len(got) == spans
    for s in got:
        assert s["attrs"]["generation"] == 2
        assert s["attrs"]["collected"] >= 0
        assert s["parent"] is None and s["duration_s"] > 0
        assert "annotated" not in s


def test_the_shipped_threshold_is_a_millisecond():
    """(tests/conftest.py raises it for every test: read the source)"""
    import inspect
    assert "\nSPAN_FROM_S = 1e-3\n" in inspect.getsource(collector)


def test_installed_twice_it_records_once(registry, monkeypatch):
    monkeypatch.setattr(collector, "SPAN_FROM_S", 0.0)
    collector.install_gc_hook()
    collector.install_gc_hook()
    assert gc.callbacks.count(collector._on_gc) == 1
    gc.collect()
    trace.clear()
    before = _count(registry, 2)
    gc.collect()
    monkeypatch.setattr(collector, "SPAN_FROM_S", float("inf"))
    assert _count(registry, 2) == before + 1
    assert len(_pauses()) == 1


def test_a_registry_it_was_never_installed_under_counts_nothing(
        registry, monkeypatch):
    """The hook makes no series inside a collection (that takes the
    family's lock): a default registry swapped in later has none until
    an engine is built under it."""
    monkeypatch.setattr(collector, "SPAN_FROM_S", float("inf"))
    collector.install_gc_hook()
    fresh = MetricsRegistry()
    prev = set_registry(fresh)
    try:
        gc.collect()
        assert fresh.get(COUNT) is None
        collector.install_gc_hook()
        gc.collect()
        assert _count(fresh, 2) == 1
    finally:
        set_registry(prev)


def test_the_span_waits_for_no_lock(registry):
    """A collection can fall inside the ring's own locked copy: the span
    is dropped there, never waited for."""
    assert trace.record_nowait("gc_pause", 1.0, 0.5, generation=2,
                               collected=0) is True
    with trace._lock:
        assert trace.record_nowait("gc_pause", 2.0, 0.5, generation=2,
                                   collected=0) is False
    got = _pauses()
    assert [s["start"] for s in got] == [1.0]
    assert got[0]["attrs"] == {"generation": 2, "collected": 0}


def test_both_engines_install_the_hook():
    """By name in the two constructors' telemetry set-up: a process that
    builds either has its pauses on the ring."""
    import inspect
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    from deepspeed_tpu.runtime.engine import DeepSpeedTpuEngine
    for cls in (InferenceEngineV2, DeepSpeedTpuEngine):
        assert "collector.install_gc_hook()" in inspect.getsource(
            cls._init_telemetry)


# -- the host thread: samples, segments, and the stalled leaf ---------------
# the serving engine's: what tells one kind of leaf from another
KIND = ("program", "ahead", "chunk")


def test_a_sample_of_the_calling_thread():
    """Six numbers that only grow; a busy loop raises the CPU seconds."""
    a = collector.thread_usage()
    n = 0
    while collector.thread_usage()[1] <= a[1]:
        n += sum(range(1000))
    b = collector.thread_usage()
    assert len(a) == len(b) == 6
    assert all(y >= x for x, y in zip(a, b))
    used = collector.usage_between(a, b)
    assert set(used) == set(collector._USAGE)
    assert used["cpu_s"] > 0 and used["runq_s"] >= 0
    assert all(isinstance(used[k], int)
               for k in ("nvcsw", "nivcsw", "majflt"))


def test_without_schedstat_the_wait_reads_none_throughout(
        registry, monkeypatch):
    """A kernel without schedstat: None in the sample, in a launch span's
    attrs and in a stall's evidence, and the series is not counted:
    never 0."""
    import threading
    monkeypatch.setattr(collector, "_SCHEDSTAT", "/proc/no/such/file")
    monkeypatch.setattr(collector, "_tls", threading.local())
    assert collector.thread_usage()[2] is None
    host = collector.HostThread("generate", kind_attrs=KIND)
    with host.launch("ragged_step") as sp:
        pass
    assert sp["attrs"]["runq_s"] is None and sp["attrs"]["cpu_s"] >= 0
    script = _Script(monkeypatch, schedstat=False)
    host = collector.HostThread("train")
    for _ in range(8):
        assert script.call(host, "leaf", 0.1) == []
    got, = script.call(host, "leaf", 1.0)
    assert got["runq_s"] is None and got["cause"] == "blocked"


class _Script:
    """``thread_usage`` as a script: hand-made calls, each ONE leaf span
    put on the ring at the times the fake samples say, so that a
    judgement is decided by numbers the test wrote."""

    def __init__(self, monkeypatch, schedstat=True):
        self.now = 100.0
        self.cpu = self.waited = 0.0
        self.schedstat = schedstat
        self.samples = []
        monkeypatch.setattr(collector, "thread_usage",
                            lambda: self.samples.pop(0))
        monkeypatch.setattr(collector, "STALL_MIN_S", 0.05)

    def _sample(self):
        self.samples.append((self.now, self.cpu,
                             self.waited if self.schedstat else None,
                             0, 0, 0))

    def call(self, host, leaf, seconds, cpu_s=0.001, runq_s=0.0,
             gc_s=0.0, **attrs):
        """A call without a root that is one leaf ``seconds`` long, the
        thread's CPU and run-queue wait moving by ``cpu_s`` and
        ``runq_s`` over it; its verdicts."""
        self._sample()
        with host.call():
            trace.record(leaf, self.now, seconds, **attrs)
            if gc_s:
                trace.record("gc_pause", self.now + 0.01, gc_s,
                             generation=2, collected=0)
            self.now += seconds
            self.cpu += cpu_s
            self.waited += runq_s
            self._sample()
        self.now += 1.0
        return host.judge()


@pytest.mark.parametrize("cause,moved", [
    ("blocked", dict()),
    ("cpu", dict(cpu_s=0.9)),
    ("runqueue", dict(runq_s=0.6, cpu_s=0.3)),
    ("gc", dict(gc_s=0.7, cpu_s=0.9)),
])
def test_a_leaf_over_its_median_is_a_stall_with_one_cause(
        registry, monkeypatch, cause, moved):
    """Eight sound calls, then one whose leaf takes ten times as long:
    ONE record, which names the leaf and its program, and the cause the
    numbers give: the first of gc, run queue and CPU that accounts for
    half of the excess, else blocked."""
    from deepspeed_tpu.telemetry import anomaly
    anomaly.reset()
    script = _Script(monkeypatch)
    host = collector.HostThread("generate", kind_attrs=KIND)
    for _ in range(8):
        assert script.call(host, "window_fetch", 0.1,
                           program="decode_window_greedy") == []
    at = script.now
    got = script.call(host, "window_fetch", 1.0,
                      program="decode_window_greedy", **moved)
    assert len(got) == 1 and got[0]["kind"] == "host_stall"
    v = got[0]
    assert (v["leaf"], v["program"], v["cause"]) == (
        "window_fetch", "decode_window_greedy", cause)
    assert v["seconds"] == pytest.approx(1.0)
    assert v["expected_s"] == pytest.approx(0.1)
    assert v["cpu_s"] == pytest.approx(moved.get("cpu_s", 0.001))
    assert v["runq_s"] == pytest.approx(moved.get("runq_s", 0.0))
    assert v["gc_s"] == pytest.approx(moved.get("gc_s", 0.0))
    assert v["compiles"] == 0 and v["where"] == "between"
    assert v["at_s"] == pytest.approx(0.0)      # its call began with it
    # the line a run keeps: leaf, program, seconds, expected, the cause
    for word in ("window_fetch", "decode_window_greedy", "1.000 s",
                 "0.100 expected", cause, "cpu_s=", "runq_s=", "nvcsw=",
                 "nivcsw=", "majflt="):
        assert word in v["summary"], (word, v["summary"])
    assert "\n" not in v["summary"]
    assert anomaly.recent()[-1] is v
    # on the ring over the excess, beside gc_pause and like it
    span, = [s for s in trace.export() if s["name"] == "host_stall"]
    assert span["start"] == pytest.approx(at + 0.1)
    assert span["duration_s"] == pytest.approx(0.9)
    assert span["parent"] is None and span["attrs"]["cause"] == cause
    assert span["attrs"]["leaf"] == "window_fetch"
    assert registry.get("host_stalls_total").labels(
        path="generate", cause=cause).value == 1
    assert registry.get("host_stall_seconds_total").labels(
        path="generate", cause=cause).value == pytest.approx(0.9)
    # the next sound call is sound: the median did not follow the stall
    assert script.call(host, "window_fetch", 0.1,
                       program="decode_window_greedy") == []


def test_fewer_than_eight_of_a_kind_judge_nothing(registry, monkeypatch):
    """A cold first call is judged by nothing, and a leaf is held to its
    OWN kind: another program's history is not its."""
    script = _Script(monkeypatch)
    host = collector.HostThread("generate", kind_attrs=KIND)
    for _ in range(7):
        assert script.call(host, "window_call", 0.1, program="a") == []
    assert script.call(host, "window_call", 5.0, program="a") == []
    assert script.call(host, "window_call", 5.0, program="b") == []
    assert [s for s in trace.export() if s["name"] == "host_stall"] == []


@pytest.mark.parametrize("seconds,stalled", [
    (0.149, False),         # 49 ms over: under the 50 ms
    (0.16, True),
    (2.4, False),           # of a leaf of 2 s, 20 %: under the quarter
    (2.6, True),
])
def test_over_by_fifty_milliseconds_and_a_quarter(
        registry, monkeypatch, seconds, stalled):
    script = _Script(monkeypatch)
    host = collector.HostThread("train")
    usual = 0.1 if seconds < 1 else 2.0
    for _ in range(8):
        script.call(host, "train_host_sync", usual)
    assert bool(script.call(host, "train_host_sync", seconds)) == stalled


def test_the_two_constants_as_shipped():
    """(tests/conftest.py raises the first for every test: read the
    source)"""
    import inspect
    source = inspect.getsource(collector)
    assert "\nSTALL_MIN_S = 0.05\n" in source
    assert "\nSTALL_OVER = 0.25\n" in source


def test_a_leaf_whose_cpu_is_usual_is_not_blamed_on_the_cpu(
        registry, monkeypatch):
    """``cpu`` is the CPU beyond what the leaf's segment usually takes:
    a leaf that computes 0.5 s every time and once sleeps 0.2 s more is
    blocked."""
    script = _Script(monkeypatch)
    host = collector.HostThread("train")
    for _ in range(8):
        script.call(host, "train_data", 0.6, cpu_s=0.5)
    got, = script.call(host, "train_data", 0.8, cpu_s=0.5)
    assert got["cause"] == "blocked"
    got, = script.call(host, "train_data", 0.8, cpu_s=0.7)
    assert got["cause"] == "cpu"


def test_a_call_that_compiled_reads_compile(registry, monkeypatch):
    """The real sampler and a real compile: a watched jit meets a second
    shape inside the judged leaf, and the watchdog's count decides."""
    import jax.numpy as jnp
    from deepspeed_tpu.telemetry import watchdog
    monkeypatch.setattr(collector, "STALL_MIN_S", 0.0)
    fn = watchdog.watch_jit("toy_program", lambda x: x * 2 + 1)
    host = collector.HostThread("generate", kind_attrs=KIND)

    def call(x):
        with trace.span("generate") as root, host.call(root):
            with trace.span("toy_call", program="toy_program"):
                fn(x).block_until_ready()
        return host.judge()

    assert call(jnp.ones(3)) == []          # the first shape: history 0
    for _ in range(8):
        call(jnp.ones(3))
    got = call(jnp.ones(5))
    assert [v["cause"] for v in got] == ["compile"]
    assert got[0]["compiles"] == 1 and got[0]["compile_s"] > 0
    assert got[0]["leaf"] == "toy_call"
    assert got[0]["program"] == "toy_program"


def test_segments_of_a_call_and_where_its_totals_go(registry, monkeypatch):
    """A call of two launches: each launch span carries its own deltas,
    the root the whole call's, the counters the totals by ``where``; a
    launch outside a call counts under ``launch`` alone."""
    usage = iter([(t, t / 10, t / 100, int(t), 0, 0)
                  for t in (0.0, 1.0, 3.0, 4.0, 8.0, 9.0, 20.0, 21.0)])
    monkeypatch.setattr(collector, "thread_usage", lambda: next(usage))
    host = collector.HostThread("generate", kind_attrs=KIND)
    with trace.span("generate") as root, host.call(root):     # t 0
        with host.launch("ragged_step", rows=3) as a:         # 1 .. 3
            assert "cpu_s" not in a.get("attrs", {})    # at its close
        with host.launch("decode_window") as b:               # 4 .. 8
            pass
    # closed at 9
    assert a["attrs"] == pytest.approx(
        {"rows": 3, "cpu_s": 0.2, "runq_s": 0.02, "nvcsw": 2, "nivcsw": 0,
         "majflt": 0})
    # the record the ring holds is the one the block was handed
    assert [s for s in trace.export() if s["name"] == "ragged_step"][-1] \
        is a
    assert b["attrs"]["cpu_s"] == pytest.approx(0.4)
    assert root["attrs"]["cpu_s"] == pytest.approx(0.9)
    assert root["attrs"]["nvcsw"] == 9
    cpu = registry.get("host_thread_cpu_seconds_total")
    assert cpu.labels(path="generate", where="launch").value \
        == pytest.approx(0.6)
    assert cpu.labels(path="generate", where="between").value \
        == pytest.approx(0.3)
    with host.launch("ragged_step") as c:                     # 20 .. 21
        pass
    assert c["attrs"]["cpu_s"] == pytest.approx(0.1)
    assert cpu.labels(path="generate", where="launch").value \
        == pytest.approx(0.7)
    assert cpu.labels(path="generate", where="between").value \
        == pytest.approx(0.3)
    assert host.judge() == []       # nine spans and no history


def test_attrs_set_inside_a_span_join_what_it_opened_with():
    trace.clear()
    with trace.span("launch", rows=3) as sp:
        sp["attrs"] = {"cpu_s": 0.5}
    with trace.span("plain"):
        pass
    a, b = trace.export()[-2:]
    assert a["attrs"] == {"rows": 3, "cpu_s": 0.5} and "attrs" not in b


def test_the_rings_tail_since_a_mark():
    trace.clear()
    trace.record("before", 0.0, 1.0)
    mark = trace.mark()
    assert trace.since(mark) == []
    trace.record("one", 1.0, 1.0)
    with trace.span("two"):
        assert trace.current_span_id() is not None
    assert [s["name"] for s in trace.since(mark)] == ["one", "two"]
    trace.clear()                   # the mark outlives what was dropped
    trace.record("three", 2.0, 1.0)
    assert [s["name"] for s in trace.since(mark)] == ["three"]
    assert trace.current_span_id() is None


def _chunked_call(script, host, fetches):
    """A hand-made call of chunk steps: a ``ragged_step`` launch span a
    chunk (attrs ``chunk``) over ONE ``ragged_fetch`` leaf of the
    seconds given, on the ring in the order spans close."""
    import threading
    track = threading.current_thread().name
    script._sample()
    with host.call():
        for chunk, seconds in enumerate(fetches):
            parent, leaf = next(trace._ids), next(trace._ids)
            trace._append({"name": "ragged_fetch", "start": script.now,
                           "duration_s": seconds, "depth": 1, "id": leaf,
                           "parent": parent, "track": track})
            trace._append({"name": "ragged_step", "start": script.now,
                           "duration_s": seconds, "depth": 0, "id": parent,
                           "parent": None, "track": track,
                           "attrs": {"chunk": chunk, "chunks": len(fetches)}})
            script.now += seconds
        script._sample()
    script.now += 1.0
    return host.judge()


def test_a_young_kind_is_held_to_the_longest_leaf_of_its_name(
        registry, monkeypatch):
    """A chunk step's leaves come once a call: before a kind (name,
    program, ``ahead``, ``chunk``) has its eight, a leaf is held to the
    LONGEST of its name under any launch. Later chunk steps that attend
    more are no stall; a chunk step that stands seconds is one, in a
    run's second call."""
    script = _Script(monkeypatch)
    host = collector.HostThread("generate", kind_attrs=KIND)
    grows = [0.2, 0.3, 0.4, 0.5]            # the last 2.5 x the first
    for _ in range(2):
        assert _chunked_call(script, host, grows) == []
    got = _chunked_call(script, host, [0.2, 0.3, 2.0, 0.5])
    assert len(got) == 1
    v = got[0]
    assert (v["leaf"], v["chunk"], v["cause"]) == ("ragged_fetch", 2,
                                                   "blocked")
    assert v["expected_s"] == pytest.approx(0.5)    # the longest so far
    assert v["at_s"] == pytest.approx(0.5)
    assert "chunk 2" in v["summary"]
    # and once a kind has its own eight, its own median judges it: the
    # third chunk at 0.66 s is over ITS usual 0.4, under the name's 2.0
    for _ in range(6):
        assert _chunked_call(script, host, grows) == []
    got = _chunked_call(script, host, [0.2, 0.3, 0.66, 0.5])
    assert [(v["chunk"], v["expected_s"]) for v in got] == [
        (2, pytest.approx(0.4))]


def test_the_call_that_first_fills_a_history_is_judged_by_nothing(
        registry, monkeypatch):
    """A leaf is held to the calls BEFORE its own: a fresh engine's one
    call of forty windows holds no stall whatever its leaves took, and
    the next call is held to it."""
    script = _Script(monkeypatch)
    host = collector.HostThread("generate", kind_attrs=KIND)

    def call(fetches):
        script._sample()
        with host.call():
            for seconds in fetches:
                trace.record("window_fetch", script.now, seconds,
                             program="decode_window_greedy")
                script.now += seconds
            script._sample()
        script.now += 1.0
        return host.judge()

    assert call([0.1] * 20 + [0.9] + [0.1] * 19) == []
    got = call([0.1] * 20 + [0.9] + [0.1] * 19)
    assert [(v["leaf"], v["expected_s"]) for v in got] == [
        ("window_fetch", pytest.approx(0.1))]
    assert got[0]["at_s"] == pytest.approx(2.0)


def test_a_stall_does_not_become_the_longest_leaf_of_its_name(
        registry, monkeypatch):
    """What a young kind is held to is the longest SOUND leaf: a chunk
    step that stood two seconds does not hide the next one that does."""
    script = _Script(monkeypatch)
    host = collector.HostThread("generate", kind_attrs=KIND)
    grows = [0.2, 0.3, 0.4, 0.5]
    for _ in range(2):
        assert _chunked_call(script, host, grows) == []
    for _ in range(2):
        got = _chunked_call(script, host, [0.2, 2.0, 0.4, 0.5])
        assert [(v["chunk"], v["expected_s"]) for v in got] == [
            (1, pytest.approx(0.5))]


def test_a_kind_is_what_the_engine_says_tells_leaves_apart(
        registry, monkeypatch):
    """``kind_attrs`` is the engine's vocabulary, not this module's:
    without it a leaf's kind is its name alone and the record names no
    attr; with it, two programs under one name are two kinds."""
    script = _Script(monkeypatch)
    plain = collector.HostThread("train")
    for _ in range(8):
        script.call(plain, "leaf", 0.1, program="a")
    got, = script.call(plain, "leaf", 1.0, program="b")
    assert "program" not in got and got["expected_s"] == pytest.approx(0.1)
    keyed = collector.HostThread("train", kind_attrs=("program",))
    for _ in range(8):
        script.call(keyed, "leaf", 0.1, program="a")
    assert script.call(keyed, "leaf", 1.0, program="b") == []
    got, = script.call(keyed, "leaf", 1.0, program="a")
    assert got["program"] == "a" and "(program a)" in got["summary"]
