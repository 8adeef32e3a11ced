"""Fleet-wide distributed tracing e2e (`telemetry/context.py`,
`serve/router.py`, `serve/api.py` — ISSUE 10 acceptance): a
disaggregated routed request's hops — router dispatch, prefill, KV
handoff, decode — land in the stitched fleet timeline under ONE
trace id in causal order (greedy AND seeded sampling); the HTTP layer
continues W3C traceparent headers; routed `/metrics` federates
per-replica registries; `/statusz?format=json` is an explicit contract;
and the heartbeat gauge is the one per-replica liveness source."""

import asyncio
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.config_v2 import DSStateManagerConfig
from deepspeed_tpu.inference.v2.serve import (PrefillReplica,
                                              ReplicaRouter, RouterConfig,
                                              ServingAPI, ServingConfig,
                                              ServingEngine,
                                              build_replicas)
from deepspeed_tpu.models import TransformerConfig, TransformerLM
from deepspeed_tpu.telemetry import context as trace_context
from deepspeed_tpu.telemetry import get_registry, timeline, trace

_ENGINE_SPANS = {"decode_step", "decode_window", "ragged_step"}


@pytest.fixture(scope="module")
def model_and_params(tiny_model_256):
    # session-shared tiny model (tests/unit/conftest.py): one
    # init_params for the whole tier instead of one per module
    return tiny_model_256


def _engine(model, params, **sm_kw):
    sm = dict(max_tracked_sequences=8, max_seq_len=256, num_blocks=65,
              block_size=16, max_ragged_batch_size=512)
    sm.update(sm_kw)
    return InferenceEngineV2(
        model, RaggedInferenceEngineConfig(
            state_manager=DSStateManagerConfig(**sm), dtype="float32",
            prefill_bucket=16), params=params)


def _serving_config(**kw):
    kw.setdefault("token_budget", 64)
    kw.setdefault("chunk", 16)
    return ServingConfig(**kw)


def _prompts(ns, seed=0):
    rng = np.random.default_rng(seed)
    return [list(map(int, rng.integers(1, 127, n))) for n in ns]


def _first(spans, pred, what):
    xs = [s for s in spans if pred(s)]
    assert xs, (what, [(s["name"], s.get("lane")) for s in spans])
    return min(xs, key=lambda s: s["start"])


# -- THE acceptance e2e: one trace id across the disaggregated fleet -------
def test_disaggregated_request_one_trace_id_causal_order(
        model_and_params):
    """Greedy and seeded-sampling requests through the router's
    prefill->handoff->decode path: the stitched fleet timeline holds
    router dispatch, prefill, handoff transfer and decode spans under
    ONE trace_id each, in causal start order, on per-lane process
    rows."""
    model, params = model_and_params
    trace.clear()
    prompts = _prompts((20, 33), seed=21)
    req_kw = [dict(temperature=0.0),
              dict(temperature=0.8, top_p=0.9, seed=11)]

    async def run():
        replicas = build_replicas(
            [_engine(model, params), _engine(model, params)],
            _serving_config())
        pw = PrefillReplica("prefill0", _engine(model, params))
        router = ReplicaRouter(replicas,
                               RouterConfig(disaggregated=True),
                               prefill_replicas=[pw])
        await router.start()
        tids, outs = [], []
        for p, kw in zip(prompts, req_kw):
            ctx = trace_context.new_context()
            with trace_context.use(ctx):
                stream = await router.submit(p, 12, **kw)
            outs.append(await stream.drain())
            tids.append(ctx.trace_id)
        await router.stop()
        return tids, outs

    tids, outs = asyncio.run(run())
    assert all(len(o) == 12 for o in outs)
    assert tids[0] != tids[1]

    for tid, mode in zip(tids, ("greedy", "seeded-sampled")):
        spans = timeline.trace_spans(tid)
        dispatch = _first(spans, lambda s: s["name"] == "router_dispatch",
                          (mode, "dispatch"))
        assert dispatch.get("lane") == "router"
        assert dispatch["attrs"]["prefill_replica"] == "prefill0"
        prefill = _first(
            spans, lambda s: (s.get("lane") == "prefill0"
                              and s["name"] in _ENGINE_SPANS),
            (mode, "prefill"))
        handoff = _first(spans, lambda s: s["name"] == "router_handoff",
                         (mode, "handoff"))
        assert handoff.get("lane") == "router"
        assert handoff["attrs"]["src"] == "prefill0"
        decode = _first(
            spans, lambda s: (str(s.get("lane", "")).startswith("replica")
                              and s["name"] in _ENGINE_SPANS),
            (mode, "decode"))
        # causal order across the fleet on the shared clock
        assert (dispatch["start"] <= prefill["start"]
                <= handoff["start"] <= decode["start"]), mode
        # the request lifeline on the decode replica carries the id too
        req = _first(spans, lambda s: s["name"] == "request",
                     (mode, "request"))
        assert req["attrs"]["status"] == "completed"
        # stitched per-trace view: one process row per lane involved
        obj = timeline.stitch_fleet(trace_id=tid)
        rows = {e["args"]["name"] for e in obj["traceEvents"]
                if e["ph"] == "M" and e["name"] == "process_name"}
        assert "router" in rows and "prefill0" in rows
        assert any(r.startswith("replica") for r in rows), rows
        json.loads(json.dumps(obj))

    # the two requests' hop sets are disjoint by trace id
    assert not ({s["id"] for s in timeline.trace_spans(tids[0])}
                & {s["id"] for s in timeline.trace_spans(tids[1])})


# -- HTTP: traceparent in, traceparent echoed, ?trace= filtered view -------
async def _http(host, port, method, path, body=b"", headers=()):
    reader, writer = await asyncio.open_connection(host, port)
    head = [f"{method} {path} HTTP/1.1",
            f"Content-Length: {len(body)}"]
    head += [f"{k}: {v}" for k, v in headers]
    writer.write(("\r\n".join(head) + "\r\n\r\n").encode() + body)
    await writer.drain()
    raw = await reader.read()
    writer.close()
    head, _, payload = raw.partition(b"\r\n\r\n")
    return head.decode(), payload


def test_routed_http_traceparent_continues_and_timeline_filters(
        model_and_params):
    model, params = model_and_params
    trace.clear()
    upstream = trace_context.new_context()

    async def run():
        replicas = build_replicas(
            [_engine(model, params), _engine(model, params)],
            _serving_config(), own_registries=True)
        router = ReplicaRouter(replicas, RouterConfig())
        await router.start()
        api = ServingAPI(router)
        host, port = await api.start()

        reg = get_registry()
        hdr0 = reg.family_total("trace_contexts_total")
        head, payload = await _http(
            host, port, "POST", "/generate",
            json.dumps({"prompt": _prompts((10,), seed=1)[0],
                        "max_new_tokens": 4}).encode(),
            headers=[("traceparent", upstream.to_traceparent()),
                     ("baggage", "tenant=acme")])
        assert "200 OK" in head
        # the response echoes the CONTINUED trace id with the SERVER's
        # span id (never the caller's own span handed back)
        tp = [l for l in head.splitlines()
              if l.lower().startswith("traceparent:")]
        assert tp and upstream.trace_id in tp[0]
        assert upstream.span_id not in tp[0]
        lines = [json.loads(x) for x in payload.decode().splitlines()]
        assert lines[-1]["done"] and lines[-1]["n"] == 4
        assert lines[-1]["trace_id"] == upstream.trace_id
        assert reg.family_total("trace_contexts_total") > hdr0

        # the fleet timeline filtered to that trace holds the hops
        head, payload = await _http(
            host, port, "GET",
            f"/debug/timeline?trace={upstream.trace_id}")
        assert "200 OK" in head
        obj = json.loads(payload)
        names = {e["name"] for e in obj["traceEvents"]
                 if e["ph"] == "X"}
        assert "router_dispatch" in names
        assert names & _ENGINE_SPANS, names
        # routed mode rejects per-replica uid filters
        head, _ = await _http(host, port, "GET", "/debug/timeline?uid=1")
        assert "400 Bad Request" in head

        # routed /metrics federates the per-replica registries
        head, payload = await _http(host, port, "GET", "/metrics")
        assert 'replica="replica0"' in payload.decode()
        text = router.federated_metrics()
        assert 'replica="router"' in text
        type_lines = [l for l in text.splitlines()
                      if l.startswith("# TYPE")]
        assert len(type_lines) == len(set(type_lines))

        await api.stop()
        await router.stop()

    asyncio.run(run())


# -- /statusz?format=json explicit contract (satellite) ---------------------
def test_statusz_format_json_router_and_single_engine(model_and_params):
    model, params = model_and_params

    async def routed():
        replicas = build_replicas([_engine(model, params)],
                                  _serving_config())
        router = ReplicaRouter(replicas, RouterConfig())
        await router.start()
        api = ServingAPI(router)
        host, port = await api.start()
        head, payload = await _http(host, port, "GET",
                                    "/statusz?format=json")
        assert "200 OK" in head
        doc = json.loads(payload)
        assert doc["router"]["placement"] == "affinity"
        assert "replica0" in doc["replicas"]
        head, _ = await _http(host, port, "GET", "/statusz?format=xml")
        assert "400 Bad Request" in head
        await api.stop()
        await router.stop()

    async def single():
        serving = ServingEngine(_engine(model, params), _serving_config())
        await serving.start()
        api = ServingAPI(serving)
        host, port = await api.start()
        for path in ("/statusz", "/statusz?format=json"):
            head, payload = await _http(host, port, "GET", path)
            assert "200 OK" in head
            doc = json.loads(payload)
            assert "health" in doc and "anomalies" in doc
        head, _ = await _http(host, port, "GET", "/statusz?format=text")
        assert "400 Bad Request" in head
        await api.stop()
        await serving.stop()

    asyncio.run(routed())
    asyncio.run(single())


# -- heartbeat gauge: one source for /statusz + check_replicas (satellite) --
def test_heartbeat_age_gauge_is_fed_by_both_probes(model_and_params):
    model, params = model_and_params

    async def run():
        replicas = build_replicas([_engine(model, params)],
                                  _serving_config())
        router = ReplicaRouter(replicas,
                               RouterConfig(monitor_interval_s=0.0))
        await router.start()
        reg = get_registry()

        def gauge_value():
            fam = reg.get("router_replica_heartbeat_age_seconds")
            assert fam is not None
            return {v[0]: s.value for v, s in fam.series()}

        # check_replicas() feeds the gauge through the single probe
        await router.check_replicas()
        assert "replica0" in gauge_value()
        # so does the /statusz rollup (same replica_heartbeat_age())
        statusz = router.replica_statusz()
        vals = gauge_value()
        assert "replica0" in vals
        age = statusz["replica0"]["heartbeat_age_s"]
        assert (age is None and vals["replica0"] == 0.0) \
            or vals["replica0"] == age
        await router.stop()

    asyncio.run(run())


# -- fleet post-mortem trigger: replica anomaly -> one fleet bundle --------
def test_replica_anomaly_triggers_fleet_bundle(model_and_params,
                                               tmp_path):
    from deepspeed_tpu.telemetry import anomaly as ds_anomaly
    from deepspeed_tpu.telemetry import postmortem
    from deepspeed_tpu.telemetry.anomaly import DiagnosticsConfig

    model, params = model_and_params
    postmortem._reset_for_tests()
    ds_anomaly.reset()

    async def run():
        diag = DiagnosticsConfig(postmortem_on_anomaly=True,
                                 postmortem_dir=str(tmp_path))
        replicas = build_replicas([_engine(model, params)],
                                  _serving_config())
        router = ReplicaRouter(
            replicas, RouterConfig(monitor_interval_s=0.0,
                                   diagnostics=diag))
        await router.start()
        reg = get_registry()
        b0 = reg.family_total("router_fleet_postmortems_total")
        # no verdicts yet: the monitor pass writes nothing
        await router._maybe_fleet_postmortem()
        assert not list(tmp_path.glob("fleet-*"))
        # a replica detector raises a verdict into the shared ledger
        ds_anomaly.report("stall", "replica0 wedged mid-step")
        await router._maybe_fleet_postmortem()
        bundles = list(tmp_path.glob("fleet-*"))
        assert len(bundles) == 1 and "stall" in bundles[0].name
        manifest = json.loads(
            (bundles[0] / "manifest.json").read_text())
        assert manifest["kind"] == "fleet"
        assert "replica0" in manifest["replicas"]
        assert reg.family_total(
            "router_fleet_postmortems_total") - b0 == 1
        assert router.router_statusz()["last_fleet_bundle"] == \
            str(bundles[0])
        # the SAME verdict is not answered twice
        await router._maybe_fleet_postmortem()
        assert len(list(tmp_path.glob("fleet-*"))) == 1
        # two DIFFERENT fresh kinds in one tick: the chatty stall is
        # inside its rate window (defers to its previous bundle) but
        # must NOT consume the nan_loss trigger — that kind still
        # writes its own bundle
        ds_anomaly.report("stall", "wedged again")
        ds_anomaly.report("nan_loss", "poisoned layer")
        await router._maybe_fleet_postmortem()
        names = sorted(p.name for p in tmp_path.glob("fleet-*"))
        assert len(names) == 2 and any("nan_loss" in n for n in names)
        assert reg.family_total(
            "router_fleet_postmortems_total") - b0 == 2
        await router.stop()

    try:
        asyncio.run(run())
    finally:
        postmortem._reset_for_tests()
        ds_anomaly.reset()
