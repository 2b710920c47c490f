"""Span-timeline layer tests: stage recording + dominance, explicit
span-context capture across threads (the engine's collector handoff — the
seam contextvars do not survive), the bounded flight recorder and its
slow/error reservoirs, OpenMetrics exemplar render/parse round-trips, and
the server's ``/debug/requests`` endpoints end to end (timeline with >=5
named stages, Chrome trace-event export, deadline-expiry events)."""

import json
import logging
import threading
import time

import numpy as np
import pytest
from werkzeug.test import Client as WsgiClient

from gordo_components_tpu.builder import provide_saved_model
from gordo_components_tpu.observability import flightrec, spans, tracing
from gordo_components_tpu.observability.exposition import (
    parse_prometheus_text,
    render_prometheus,
)
from gordo_components_tpu.observability.registry import Registry
from gordo_components_tpu.serializer import pipeline_from_definition
from gordo_components_tpu.server import build_app
from gordo_components_tpu.server.engine import ServingEngine

# -- timeline unit tests -----------------------------------------------------


def test_timeline_stage_sums_and_dominance():
    timeline, token = spans.begin("aaaa000011112222", endpoint="anomaly")
    try:
        with spans.stage("score"):
            with spans.stage("dispatch"):
                time.sleep(0.02)
            with spans.stage("dispatch"):  # repeats sum
                time.sleep(0.01)
            with spans.stage("fetch"):
                pass
    finally:
        spans.end(token)
    timeline.finish(status="200")
    stages = timeline.stage_seconds()
    assert stages["dispatch"] >= 0.03
    assert set(stages) == {"score", "dispatch", "fetch"}
    # score CONTAINS the others: dominance looks at leaf stages only
    assert timeline.dominant_stage() == "dispatch"
    summary = timeline.summary()
    assert summary["trace_id"] == "aaaa000011112222"
    assert summary["endpoint"] == "anomaly"
    assert summary["stages_ms"]["dispatch"] >= 30.0


def test_timeline_dominance_falls_back_to_parent_when_alone():
    timeline = spans.Timeline("t")
    timeline.add_span("score", time.perf_counter(), 0.5)
    assert timeline.dominant_stage() == "score"


def test_chrome_trace_export_is_perfetto_shaped():
    timeline, token = spans.begin("bbbb000011112222")
    try:
        with spans.stage("dispatch", machine="m1"):
            pass
        spans.event("deadline_expired", where="engine.dispatch")
    finally:
        spans.end(token)
    timeline.finish(status="504", error="HTTP 504")
    chrome = timeline.to_chrome_trace()
    json.dumps(chrome)  # loadable = serializable, first of all
    events = chrome["traceEvents"]
    complete = [e for e in events if e["ph"] == "X"]
    instants = [e for e in events if e["ph"] == "i"]
    assert len(complete) == 1
    assert complete[0]["name"] == "dispatch"
    assert complete[0]["args"]["machine"] == "m1"
    assert {"ts", "dur", "pid", "tid"} <= set(complete[0])
    assert instants and instants[0]["name"] == "deadline_expired"
    # metadata events name the process and threads
    assert any(e["ph"] == "M" and e["name"] == "process_name" for e in events)


def test_bind_restores_trace_and_timeline_on_another_thread():
    tracing.install_log_record_factory()
    logger = logging.getLogger("test_spans.bind")
    records = []
    handler = logging.Handler()
    handler.emit = records.append
    logger.addHandler(handler)
    logger.setLevel(logging.INFO)
    try:
        with tracing.trace("cccc000011112222"):
            timeline, token = spans.begin("cccc000011112222")
            ctx = spans.capture()
            spans.end(token)

        def worker():
            # a bare thread: no inherited contextvars
            logger.info("unbound")
            with spans.bind(ctx):
                logger.info("bound")
                with spans.stage("fetch"):
                    pass

        thread = threading.Thread(target=worker)
        thread.start()
        thread.join()
    finally:
        logger.removeHandler(handler)
    by_message = {r.getMessage(): r for r in records}
    assert by_message["unbound"].trace_id == ""
    assert by_message["bound"].trace_id == "cccc000011112222"
    assert [s.name for s in timeline.spans] == ["fetch"]


def test_record_into_routes_to_captured_timeline():
    timeline, token = spans.begin("dddd000011112222")
    ctx = spans.capture()
    spans.end(token)
    started = time.perf_counter()
    spans.record_into(ctx, "device_execute", started, 0.25, path="cold")
    spans.event_into(ctx, "fetch_error", error="RuntimeError")
    assert timeline.stage_seconds() == {"device_execute": 0.25}
    assert timeline.events[0]["name"] == "fetch_error"
    # EMPTY_CONTEXT swallows silently (recorder disabled / CLI jobs)
    spans.record_into(spans.EMPTY_CONTEXT, "fetch", started, 0.1)


# -- flight recorder ---------------------------------------------------------


def _finished_timeline(trace_id, duration=0.0, error=""):
    timeline = spans.Timeline(trace_id)
    timeline.started -= duration  # backdate so .duration == duration
    timeline.finish(status="500" if error else "200", error=error)
    return timeline


def test_flight_recorder_ring_is_bounded_but_reservoirs_persist():
    recorder = flightrec.FlightRecorder(
        keep=4, slow_keep=2, error_keep=2, enabled=True
    )
    recorder.record(_finished_timeline("slow-one", duration=9.0))
    recorder.record(_finished_timeline("bad-one", error="HTTP 503"))
    for i in range(10):
        # each a step slower than the one before: which of them the slow
        # reservoir's second place ends up holding is then the last, not
        # whichever the clock's jitter made longest
        recorder.record(
            _finished_timeline(f"fast-{i}", duration=0.01 * (i + 1))
        )
    body = recorder.summaries(limit=50)
    assert body["recorded"] == 12
    assert body["kept"] == 4  # ring holds only the newest 4
    # ...but the slow reservoir still holds the slowest-ever request
    assert body["slowest"]["trace_id"] == "slow-one"
    assert recorder.get("slow-one") is not None
    # ...and the error ring still holds the errored one
    assert [e["trace_id"] for e in body["errors"]] == ["bad-one"]
    assert recorder.get("bad-one") is not None
    # rotated-out healthy traces are genuinely gone
    assert recorder.get("fast-0") is None
    assert recorder.get("fast-9") is not None


def test_flight_recorder_disabled_records_nothing():
    recorder = flightrec.FlightRecorder(keep=4, enabled=False)
    recorder.record(_finished_timeline("t1"))
    assert recorder.summaries()["recorded"] == 0
    assert recorder.get("t1") is None
    recorder.set_enabled(True)
    recorder.record(_finished_timeline("t2"))
    assert recorder.get("t2") is not None


# -- exemplars ---------------------------------------------------------------


def test_histogram_exemplar_render_parse_round_trip():
    registry = Registry()
    hist = registry.histogram("ex_seconds", buckets=(0.1, 1.0))
    with tracing.trace("feedface00000000"):
        hist.observe(0.05)
    hist.observe(0.5)  # untraced: no exemplar for this bucket
    text = render_prometheus(registry, exemplars=True)
    assert ' # {trace_id="feedface00000000"} 0.05 ' in text
    samples, exemplars = parse_prometheus_text(text, return_exemplars=True)
    assert samples["ex_seconds_count"] == [({}, 2.0)]
    rows = exemplars["ex_seconds_bucket"]
    assert len(rows) == 1
    labels, exemplar = rows[0]
    assert labels["le"] == "0.1"
    assert exemplar["labels"] == {"trace_id": "feedface00000000"}
    assert exemplar["value"] == 0.05
    assert exemplar["timestamp"] is not None
    # the DEFAULT render is strict v0.0.4 — no exemplars — because the
    # classic Prometheus text parser rejects the suffix outright
    assert "trace_id" not in render_prometheus(registry)


def test_label_value_containing_hash_is_not_an_exemplar():
    # a quoted label value with " # " (an error string, say) is a legal
    # plain sample; only a well-formed exemplar suffix behind a valid
    # sample counts as one
    samples, exemplars = parse_prometheus_text(
        "# TYPE errs_total counter\n"
        'errs_total{err="bad # thing"} 1\n'
        'errs_total{err="fake # {trace_id=\\"x\\"} 1"} 2\n',
        return_exemplars=True,
    )
    assert len(samples["errs_total"]) == 2
    assert exemplars == {}


def test_parse_rejects_malformed_and_misplaced_exemplars():
    with pytest.raises(ValueError, match="malformed"):
        parse_prometheus_text(
            "# TYPE h histogram\n"
            'h_bucket{le="+Inf"} 1 # not an exemplar\n'
            "h_sum 1.0\nh_count 1\n"
        )
    with pytest.raises(ValueError, match="neither a histogram bucket"):
        parse_prometheus_text(
            '# TYPE g gauge\ng 1 # {trace_id="abc"} 1\n'
        )
    long_value = "x" * 200
    with pytest.raises(ValueError, match="128"):
        parse_prometheus_text(
            "# TYPE h histogram\n"
            f'h_bucket{{le="+Inf"}} 1 # {{trace_id="{long_value}"}} 1\n'
            "h_sum 1.0\nh_count 1\n"
        )
    # counters may carry exemplars (OpenMetrics placement rule)
    parse_prometheus_text(
        "# TYPE c_total counter\n"
        'c_total 3 # {trace_id="abc"} 1 1700000000.0\n'
    )


# -- engine: span context across the collector handoff -----------------------

ENGINE_CONFIG = {
    "DiffBasedAnomalyDetector": {
        "base_estimator": {
            "TransformedTargetRegressor": {
                "regressor": {
                    "Pipeline": {
                        "steps": [
                            "MinMaxScaler",
                            {
                                "DenseAutoEncoder": {
                                    "kind": "feedforward_symmetric",
                                    "dims": [4],
                                    "epochs": 1,
                                    "batch_size": 32,
                                }
                            },
                        ]
                    }
                },
                "transformer": "MinMaxScaler",
            }
        }
    }
}


@pytest.fixture(scope="module")
def engine_models():
    rng = np.random.default_rng(31)
    X = rng.normal(size=(160, 4)).astype(np.float32) * 3 + 5
    model = pipeline_from_definition(ENGINE_CONFIG)
    model.fit(X)
    return {"span-m": model}


def test_collector_rebinds_trace_context_and_records_fetch_span(
    monkeypatch, caplog, engine_models
):
    """Satellite: the PR 4 collector handoff lost the trace id — log
    records emitted during device_get carried none, and nothing could
    attribute the fetch stage to a request. The item's captured
    SpanContext must restore both on the collector thread."""
    tracing.install_log_record_factory()
    monkeypatch.setenv("GORDO_DISPATCH_DEPTH", "2")
    engine = ServingEngine(engine_models)
    try:
        name = engine.machines()[0]
        bucket, _ = engine._by_name[name]
        # force the fetch through the collector (an idle engine would
        # fetch inline on the leader thread and prove nothing)
        monkeypatch.setattr(bucket, "_should_pipeline", lambda: True)
        engine_logger = logging.getLogger(
            "gordo_components_tpu.server.engine"
        )
        original_fetch = bucket._fetch

        def logging_fetch(job):
            engine_logger.info("collector device_get for spans test")
            return original_fetch(job)

        monkeypatch.setattr(bucket, "_fetch", logging_fetch)
        X = np.random.default_rng(5).normal(size=(70, 4)).astype(np.float32)
        with caplog.at_level(logging.INFO, logger=engine_logger.name):
            with tracing.trace("eeee000011112222"):
                timeline, token = spans.begin("eeee000011112222")
                try:
                    engine.anomaly(name, X)
                finally:
                    spans.end(token)
        engine.quiesce()
    finally:
        engine.close()
    fetch_logs = [
        r for r in caplog.records if "collector device_get" in r.getMessage()
    ]
    assert fetch_logs, "the instrumented fetch never logged"
    # the collector thread's log record carries the REQUEST's trace id
    assert all(
        r.trace_id == "eeee000011112222" for r in fetch_logs
    ), [r.trace_id for r in fetch_logs]
    stages = timeline.stage_seconds()
    assert {"queue_wait", "dispatch", "device_execute", "fetch"} <= set(stages)
    # and the fetch span really was recorded from the collector thread
    fetch_spans = [s for s in timeline.spans if s.name == "fetch"]
    assert fetch_spans
    assert any(
        s.thread == "gordo-bucket-collector" for s in fetch_spans
    ), [s.thread for s in fetch_spans]


# -- server e2e: /debug/requests + events ------------------------------------

DATA_CONFIG = {
    "type": "RandomDataset",
    "train_start_date": "2023-01-01T00:00:00+00:00",
    "train_end_date": "2023-01-04T00:00:00+00:00",
    "tag_list": ["s-a", "s-b", "s-c"],
}

SERVER_MODEL = {
    "DiffBasedAnomalyDetector": {
        "base_estimator": {
            "Pipeline": {
                "steps": [
                    "MinMaxScaler",
                    {"DenseAutoEncoder": {"kind": "feedforward_symmetric",
                                          "dims": [4], "epochs": 1,
                                          "batch_size": 32}},
                ]
            }
        }
    }
}


@pytest.fixture(scope="module")
def served_client(tmp_path_factory):
    root = tmp_path_factory.mktemp("spans_served")
    model_dir = provide_saved_model(
        "machine-s", SERVER_MODEL, DATA_CONFIG, str(root),
        evaluation_config={"cv_mode": "build_only"},
    )
    return WsgiClient(build_app({"machine-s": model_dir}, project="proj"))


def test_debug_requests_timeline_end_to_end(served_client):
    payload = json.dumps({"X": [[0.1, 0.2, 0.3]] * 70})
    response = served_client.post(
        "/gordo/v0/proj/machine-s/anomaly/prediction",
        data=payload, content_type="application/json",
        headers={tracing.TRACE_HEADER: "abcd1234abcd1234"},
    )
    assert response.status_code == 200
    listing = served_client.get("/debug/requests").get_json()
    rows = {r["trace_id"]: r for r in listing["requests"]}
    assert "abcd1234abcd1234" in rows
    row = rows["abcd1234abcd1234"]
    assert row["endpoint"] == "anomaly"
    # the acceptance contract: at least 5 named stages on a scoring request
    assert len(row["stages_ms"]) >= 5
    assert {"dispatch", "fetch", "score", "encode"} <= set(row["stages_ms"])
    full = served_client.get(
        "/debug/requests/abcd1234abcd1234"
    ).get_json()
    assert full["trace_id"] == "abcd1234abcd1234"
    assert len(full["spans"]) >= 5
    chrome = served_client.get(
        "/debug/requests/abcd1234abcd1234?format=chrome"
    ).get_json()
    complete = [e for e in chrome["traceEvents"] if e["ph"] == "X"]
    assert complete and all("ts" in e and "dur" in e for e in complete)
    # unknown trace → 404, not an empty 200
    assert served_client.get("/debug/requests/doesnotexist").status_code == 404


def test_expired_deadline_request_records_event_and_errors(served_client):
    payload = json.dumps({"X": [[0.1, 0.2, 0.3]] * 70})
    response = served_client.post(
        "/gordo/v0/proj/machine-s/anomaly/prediction",
        data=payload, content_type="application/json",
        headers={
            tracing.TRACE_HEADER: "dead123400000000",
            "X-Gordo-Deadline": "0",
        },
    )
    assert response.status_code == 504
    full = served_client.get(
        "/debug/requests/dead123400000000"
    ).get_json()
    assert full["status"] == "504"
    assert full["error"].startswith("HTTP 504")
    assert any(
        e["name"] == "deadline_expired" for e in full["events"]
    ), full["events"]
    # 5xx traces land in the error reservoir too
    listing = served_client.get("/debug/requests").get_json()
    assert "dead123400000000" in {
        e["trace_id"] for e in listing["errors"]
    }


def test_debug_requests_excludes_probe_endpoints(served_client):
    before = served_client.get("/debug/requests").get_json()["recorded"]
    served_client.get("/healthz")
    served_client.get("/metrics")
    served_client.get("/debug/requests")
    after = served_client.get("/debug/requests").get_json()["recorded"]
    assert after == before  # probe/scrape noise never enters the ring
