"""Ring-buffer wraparound + concurrent-writer coverage for
telemetry/trace.py and timeline.py (PR 6 satellite): the Chrome-trace
export must stay well-formed JSON and per-request lifelines unbroken
when the serving-loop thread and the asyncio frontend thread write
through eviction."""

import json
import threading

import pytest

from deepspeed_tpu.telemetry import timeline, trace


@pytest.fixture(autouse=True)
def _fresh_trace():
    trace.set_capacity(4096)
    trace.clear()
    yield
    trace.set_capacity(4096)
    trace.clear()


def _emit_lifeline(uid, t0):
    """One request's full lifeline the way scheduler.py records it."""
    trace.record("request_queue", t0, 0.01, uid=uid)
    trace.record("request_prefill", t0 + 0.01, 0.02, uid=uid,
                 prompt_tokens=8)
    trace.record("request_decode", t0 + 0.03, 0.05, uid=uid, tokens=4)
    trace.record("request", t0, 0.08, uid=uid, tokens=4,
                 status="completed")


def test_wraparound_keeps_export_well_formed():
    trace.set_capacity(64)
    for i in range(1000):
        with trace.span("decode_step", batch=2, uids=[i]):
            pass
    spans = trace.export()
    assert len(spans) == 64
    # the retained window is the newest spans, ids strictly increasing
    ids = [s["id"] for s in spans]
    assert ids == sorted(ids)
    obj = timeline.to_chrome_trace()
    text = json.dumps(obj)                    # serializes cleanly
    parsed = json.loads(text)
    xs = [e for e in parsed["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == 64
    assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in xs)


def test_lifeline_survives_eviction_of_older_requests():
    """Old requests roll off; the most recent uid's lifeline must stay
    complete (all four phases present, consistent uid args)."""
    trace.set_capacity(32)
    for uid in range(200):
        _emit_lifeline(uid, float(uid))
    last = 199
    life = timeline.request_lifeline(last)
    for phase in timeline.REQUEST_PHASES:
        assert phase in life, (phase, life)
    assert life["request"]["attrs"]["status"] == "completed"
    # chrome export of the filtered lifeline is well-formed
    obj = timeline.to_chrome_trace(timeline.request_spans(last))
    names = [e["name"] for e in obj["traceEvents"] if e["ph"] == "X"]
    assert set(timeline.REQUEST_PHASES) <= set(names)


def test_concurrent_writers_with_wraparound():
    """Serving-loop-style writer (spans + retroactive lifelines) and an
    asyncio-frontend-style writer race through a small ring; export and
    Chrome JSON stay consistent throughout and afterwards."""
    trace.set_capacity(256)
    stop = threading.Event()
    errors = []

    def loop_writer():
        uid = 0
        try:
            while not stop.is_set():
                with trace.span("decode_window", batch=4,
                                uids=[uid, uid + 1]):
                    pass
                _emit_lifeline(uid, float(uid))
                uid += 1
        except Exception as e:   # pragma: no cover
            errors.append(e)

    def frontend_writer():
        try:
            trace.set_track("asyncio-frontend")
            i = 0
            while not stop.is_set():
                with trace.span("submit", uid=i):
                    pass
                i += 1
        except Exception as e:   # pragma: no cover
            errors.append(e)

    def reader():
        try:
            for _ in range(200):
                obj = timeline.to_chrome_trace()
                json.loads(json.dumps(obj))
                for e in obj["traceEvents"]:
                    assert "name" in e and "ph" in e
        except Exception as e:   # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=f)
               for f in (loop_writer, frontend_writer, reader)]
    for t in threads:
        t.start()
    threads[2].join()            # reader finishes its 200 exports
    stop.set()
    for t in threads[:2]:
        t.join()
    assert not errors, errors

    # one lifeline more, with the writers gone: the frontend writer can end
    # the race with a run of over 256 spans of its own, which leaves the
    # window without a whole lifeline to check (it did, in one whole run of
    # the suite in four, PR 27); the ring has wrapped under both writers by
    # now all the same
    trace.set_track("asyncio-frontend")
    with trace.span("submit", uid=-1):
        pass
    trace.set_track(None)
    _emit_lifeline(10 ** 9, 0.0)
    spans = trace.export()
    assert len(spans) == 256
    # both tracks present in the final window and mapped to distinct
    # tids in the export
    obj = timeline.to_chrome_trace()
    meta = {e["args"]["name"]: e["tid"]
            for e in obj["traceEvents"] if e["ph"] == "M"}
    assert "asyncio-frontend" in meta
    assert len(set(meta.values())) == len(meta)
    # the newest fully-recorded lifeline in the window is unbroken. The
    # ring drops oldest first, so a lifeline is whole in it when its first
    # record and its last are: the other writer can push a lifeline's
    # first record out before its last is written (a thread switch between
    # two of its four records lets thousands of spans in), and did, once
    # in three whole runs of the suite
    first = {s["attrs"]["uid"] for s in spans
             if s["name"] == "request_queue"}
    uids = [s["attrs"]["uid"] for s in spans
            if s["name"] == "request" and s["attrs"]["uid"] in first]
    assert uids, "no complete request span retained"
    life = timeline.request_lifeline(max(uids))
    for phase in timeline.REQUEST_PHASES:
        assert phase in life


def test_set_capacity_during_writes_does_not_corrupt():
    stop = threading.Event()
    errors = []

    def writer():
        try:
            i = 0
            while not stop.is_set():
                trace.record("w", float(i), 0.001, uid=i)
                i += 1
        except Exception as e:   # pragma: no cover
            errors.append(e)

    t = threading.Thread(target=writer)
    t.start()
    try:
        for cap in (16, 128, 8, 64) * 5:
            trace.set_capacity(cap)
            spans = trace.export()
            assert len(spans) <= cap
    finally:
        stop.set()
        t.join()
    assert not errors, errors
