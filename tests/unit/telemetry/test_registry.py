"""Metrics registry unit tests: counter/gauge/histogram semantics, label
handling, Prometheus exposition format, and JSON snapshot round-trip."""

import json

import pytest

from deepspeed_tpu.telemetry import MetricsRegistry
from deepspeed_tpu.telemetry.registry import DEFAULT_BUCKETS


@pytest.fixture()
def reg():
    return MetricsRegistry()


# -- counter ----------------------------------------------------------------
def test_counter_semantics(reg):
    c = reg.counter("requests_total", "help text")
    assert c.value == 0.0
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError, match="only increase"):
        c.inc(-1)


def test_gauge_semantics(reg):
    g = reg.gauge("queue_depth")
    g.set(4)
    g.inc()
    g.dec(2)
    assert g.value == 3.0


def test_histogram_semantics(reg):
    h = reg.histogram("latency_seconds", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    assert h.count == 4
    assert h.sum == pytest.approx(55.55)
    assert h.mean == pytest.approx(55.55 / 4)
    # raw per-bucket slots: one observation each (+Inf slot holds 50.0)
    assert h._default.bucket_counts == [1, 1, 1, 1]


def test_histogram_bucket_edges_are_inclusive(reg):
    # prometheus: le is <=, so an observation equal to a bound lands in it
    h = reg.histogram("edge_seconds", buckets=(1.0, 2.0))
    h.observe(1.0)
    assert h._default.bucket_counts == [1, 0, 0]


# -- labels -----------------------------------------------------------------
def test_labels_resolve_distinct_series(reg):
    c = reg.counter("ops_total", labelnames=("op",))
    c.labels(op="all_reduce").inc(2)
    c.labels(op="all_gather").inc()
    assert c.labels(op="all_reduce").value == 2.0
    assert c.labels(op="all_gather").value == 1.0
    # same label values -> the SAME cached series object
    assert c.labels(op="all_reduce") is c.labels(op="all_reduce")


def test_label_name_mismatch_raises(reg):
    c = reg.counter("ops_total", labelnames=("op",))
    with pytest.raises(ValueError, match="declared"):
        c.labels(kind="x")


def test_registration_idempotent_and_kind_checked(reg):
    a = reg.counter("x_total")
    assert reg.counter("x_total") is a
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x_total")
    with pytest.raises(ValueError, match="already registered"):
        reg.counter("x_total", labelnames=("op",))


def test_histogram_bucket_mismatch_raises(reg):
    h = reg.histogram("h_seconds", buckets=(0.1, 1.0))
    # same bounds (any order) resolve to the same family
    assert reg.histogram("h_seconds", buckets=(1.0, 0.1)) is h
    with pytest.raises(ValueError, match="buckets"):
        reg.histogram("h_seconds", buckets=(1.0, 10.0))


# -- prometheus exposition ---------------------------------------------------
def test_render_prometheus_scalars(reg):
    c = reg.counter("requests_total", "served requests")
    c.inc(3)
    g = reg.gauge("depth", labelnames=("queue",))
    g.labels(queue="prefill").set(7)
    text = reg.render_prometheus()
    assert "# HELP requests_total served requests" in text
    assert "# TYPE requests_total counter" in text
    assert "requests_total 3" in text
    assert "# TYPE depth gauge" in text
    assert 'depth{queue="prefill"} 7' in text


def test_render_prometheus_survives_a_nan_gauge(reg):
    """A poisoned step leaves ``training_loss`` NaN; the scrape that
    follows must still render (it raised ``cannot convert float NaN to
    integer``, for every later scrape of the process)."""
    reg.gauge("training_loss").set(float("nan"))
    reg.gauge("depth").set(2)
    text = reg.render_prometheus()
    assert "training_loss NaN" in text and "depth 2" in text


def test_render_prometheus_histogram_cumulative(reg):
    h = reg.histogram("lat_seconds", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    text = reg.render_prometheus()
    # exposition buckets are CUMULATIVE and end at +Inf == count
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="1"} 2' in text
    assert 'lat_seconds_bucket{le="+Inf"} 3' in text
    assert "lat_seconds_sum 5.55" in text
    assert "lat_seconds_count 3" in text


def test_render_prometheus_label_escaping(reg):
    g = reg.gauge("g", labelnames=("path",))
    g.labels(path='a"b\\c\nd').set(1)
    text = reg.render_prometheus()
    assert 'path="a\\"b\\\\c\\nd"' in text


# -- snapshot ---------------------------------------------------------------
def test_snapshot_json_round_trip(reg):
    reg.counter("c_total", "help", labelnames=("op",)).labels(op="x").inc(2)
    reg.gauge("g").set(1.5)
    h = reg.histogram("h_seconds", unit="s", buckets=(0.5, 1.0))
    h.observe(0.25)
    h.observe(2.0)
    snap = reg.snapshot()
    assert json.loads(json.dumps(snap)) == snap
    m = snap["metrics"]
    assert m["c_total"]["type"] == "counter"
    assert m["c_total"]["series"][0] == {"labels": {"op": "x"}, "value": 2.0}
    assert m["g"]["series"][0]["value"] == 1.5
    hs = m["h_seconds"]["series"][0]
    assert hs["count"] == 2 and hs["sum"] == pytest.approx(2.25)
    assert hs["buckets"] == {"0.5": 1, "1": 0, "+Inf": 1}
    assert m["h_seconds"]["unit"] == "s"


def test_scalar_items_flatten(reg):
    reg.counter("c_total").inc(2)
    reg.gauge("g", labelnames=("k",)).labels(k="v").set(3)
    h = reg.histogram("h_seconds")
    h.observe(0.5)
    items = dict(reg.scalar_items())
    assert items["c_total"] == 2.0
    assert items["g/k.v"] == 3.0
    assert items["h_seconds_count"] == 1.0
    assert items["h_seconds_sum"] == 0.5
    assert items["h_seconds_mean"] == 0.5
    # empty histograms emit nothing (no 0/0 means)
    reg.histogram("empty_seconds")
    assert "empty_seconds_count" not in dict(reg.scalar_items())


def test_reset_drops_families(reg):
    reg.counter("c_total").inc()
    reg.reset()
    assert reg.get("c_total") is None
    assert reg.snapshot() == {"metrics": {}}


def test_default_buckets_sorted():
    assert list(DEFAULT_BUCKETS) == sorted(DEFAULT_BUCKETS)


# -- quantile estimation (PR 6 satellite) -----------------------------------
def test_histogram_quantile_interpolates_within_buckets(reg):
    h = reg.histogram("q_seconds", buckets=(0.1, 0.2, 0.4))
    for v in [0.05] * 50 + [0.15] * 30 + [0.3] * 20:
        h.observe(v)
    # p50 lands exactly at the first bucket's upper edge (50/100 obs)
    assert h.quantile(0.5) == pytest.approx(0.1)
    # p60: 10 of the 30 obs in (0.1, 0.2] -> 1/3 into the bucket
    assert h.quantile(0.6) == pytest.approx(0.1 + (0.2 - 0.1) / 3)
    # p95: 15 of the 20 obs in (0.2, 0.4] -> 3/4 into the bucket
    assert h.quantile(0.95) == pytest.approx(0.2 + (0.4 - 0.2) * 0.75)
    # monotone in q
    qs = [h.quantile(q / 20) for q in range(21)]
    assert qs == sorted(qs)


def test_histogram_quantile_overflow_and_empty(reg):
    h = reg.histogram("q2_seconds", buckets=(0.1, 0.2))
    assert h.quantile(0.5) != h.quantile(0.5)   # NaN when empty
    for v in (5.0, 6.0, 7.0):
        h.observe(v)
    # everything in the +Inf bucket: report the largest finite bound
    # (documented: no upper edge to interpolate toward)
    assert h.quantile(0.5) == pytest.approx(0.2)
    with pytest.raises(ValueError, match="in \\[0, 1\\]"):
        h.quantile(1.5)


# -- exposition round-trip (PR 6 satellite) ---------------------------------
def _parse_exposition(text):
    """Minimal 0.0.4 parser: returns ({name: kind}, {name: [help lines]},
    [(metric, labels_dict, value)])."""
    import re
    types, helps, samples = {}, {}, []
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            types.setdefault(name, []).append(kind)
            continue
        if line.startswith("# HELP "):
            _, _, name, help_text = line.split(" ", 3)
            helps.setdefault(name, []).append(help_text)
            continue
        m = re.match(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{.*\})?\s+(\S+)$",
                     line)
        assert m, f"unparseable sample line: {line!r}"
        name, labels_raw, value = m.groups()
        labels = {}
        if labels_raw:
            for lm in re.finditer(r'(\w+)="((?:[^"\\]|\\.)*)"',
                                  labels_raw[1:-1]):
                k, v = lm.groups()
                labels[k] = (v.replace("\\n", "\n").replace('\\"', '"')
                             .replace("\\\\", "\\"))
        samples.append((name, labels, value))
    return types, helps, samples


def test_render_prometheus_round_trip_with_hostile_values(reg):
    """Escaping + exactly-once TYPE/HELP, verified by parsing the
    exposition back: hostile label values (backslash, quote, newline)
    and newline-bearing help text survive a round trip."""
    hostile = 'a\\b"c\nd'
    c = reg.counter("rt_total", 'help with "quotes", \\ and\nnewline',
                    labelnames=("tenant",))
    c.labels(tenant=hostile).inc(3)
    c.labels(tenant="plain").inc(1)
    h = reg.histogram("rt_seconds", "hist help", buckets=(0.1, 1.0),
                      labelnames=("op",))
    h.labels(op=hostile).observe(0.5)
    text = reg.render_prometheus()
    # every line is a comment or a sample; the parser asserts that
    types, helps, samples = _parse_exposition(text)
    # TYPE and HELP exactly once per family
    assert types["rt_total"] == ["counter"]
    assert types["rt_seconds"] == ["histogram"]
    assert len(helps["rt_total"]) == 1
    # help newline/backslash escaped on the wire, recoverable
    assert "\n" not in helps["rt_total"][0]
    assert helps["rt_total"][0].replace("\\n", "\n").replace(
        "\\\\", "\\") == 'help with "quotes", \\ and\nnewline'
    # hostile label value round-trips exactly
    got = {(n, l.get("tenant")): v for n, l, v in samples
           if n == "rt_total"}
    assert got[("rt_total", hostile)] == "3"
    assert got[("rt_total", "plain")] == "1"
    # histogram series parse with the le label intact alongside op
    le_vals = [l["le"] for n, l, _ in samples
               if n == "rt_seconds_bucket" and l.get("op") == hostile]
    assert le_vals == ["0.1", "1", "+Inf"]

# -- federated exposition (PR 10: routed /metrics) --------------------------
def test_render_federated_labels_each_source(reg):
    from deepspeed_tpu.telemetry.registry import render_federated
    r0, r1 = MetricsRegistry(), MetricsRegistry()
    reg.gauge("router_replicas", "fleet size").set(2)
    for i, r in enumerate((r0, r1)):
        r.counter("serving_requests_total", "per-replica").inc(i + 1)
        r.histogram("serving_ttft_seconds", "ttft",
                    buckets=(0.1, 1.0)).observe(0.5)
    text = render_federated([("router", reg), ("replica0", r0),
                             ("replica1", r1)])
    types, helps, samples = _parse_exposition(text)
    # TYPE/HELP exactly once even though two sources register the family
    assert types["serving_requests_total"] == ["counter"]
    assert len(helps["serving_requests_total"]) == 1
    got = {l["replica"]: v for n, l, v in samples
           if n == "serving_requests_total"}
    assert got == {"replica0": "1", "replica1": "2"}
    # histogram series carry the replica label on bucket/sum/count lines
    counts = {l["replica"]: v for n, l, v in samples
              if n == "serving_ttft_seconds_count"}
    assert counts == {"replica0": "1", "replica1": "1"}
    assert {l["replica"] for n, l, v in samples
            if n == "router_replicas"} == {"router"}


def test_render_federated_dedups_shared_registries_and_conflicts(reg):
    from deepspeed_tpu.telemetry.registry import render_federated
    other = MetricsRegistry()
    reg.counter("shared_total", "x").inc(5)
    # a replica listing the SAME registry object must not double-count
    other.gauge("shared_total", "conflicting kind").set(9)
    text = render_federated([("router", reg), ("replica0", reg),
                             ("replica1", other)])
    types, _, samples = _parse_exposition(text)
    assert types["shared_total"] == ["counter"]   # first definition wins
    rows = [(l["replica"], v) for n, l, v in samples
            if n == "shared_total"]
    assert rows == [("router", "5")]


def test_scoped_registry_restores_previous_default():
    from deepspeed_tpu.telemetry import get_registry
    from deepspeed_tpu.telemetry.registry import scoped_registry
    prev = get_registry()
    mine = MetricsRegistry()
    with scoped_registry(mine) as r:
        assert r is mine and get_registry() is mine
        mine.counter("scoped_total").inc()
    assert get_registry() is prev
    assert mine.family_total("scoped_total") == 1.0
