"""Observability layer tests: registry semantics (labels, histogram
buckets, get-or-create, thread-safety smoke), Prometheus text exposition
(render → parse round trip, malformed-input rejection), trace-id
propagation through the WSGI app into response headers and log records,
and the registry-backed engine/server series a warm request must emit."""

import json
import logging
import threading

import pytest
from werkzeug.test import Client

from gordo_components_tpu.builder import provide_saved_model
from gordo_components_tpu.observability import (
    REGISTRY,
    TRACE_HEADER,
    tracing,
)
from gordo_components_tpu.observability.exposition import (
    CONTENT_TYPE,
    parse_prometheus_text,
    render_prometheus,
)
from gordo_components_tpu.observability.logsetup import JsonFormatter
from gordo_components_tpu.observability.registry import INF, Registry
from gordo_components_tpu.server import build_app

# -- registry semantics ------------------------------------------------------


def test_counter_labels_and_accumulation():
    reg = Registry()
    c = reg.counter("req_total", "requests", labels=("endpoint", "status"))
    c.labels("healthz", "200").inc()
    c.labels("healthz", "200").inc(2)
    c.labels("predict", "500").inc()
    assert c.collect() == {
        ("healthz", "200"): 3.0,
        ("predict", "500"): 1.0,
    }


def test_counter_rejects_decrease_and_bad_arity():
    reg = Registry()
    c = reg.counter("c_total", labels=("a",))
    with pytest.raises(ValueError):
        c.labels("x").inc(-1)
    with pytest.raises(ValueError):
        c.labels("x", "y")


def test_gauge_set_inc_dec():
    reg = Registry()
    g = reg.gauge("g", labels=("k",))
    g.labels("a").set(5)
    g.labels("a").inc(2)
    g.labels("a").dec()
    assert g.collect() == {("a",): 6.0}


def test_get_or_create_returns_same_metric():
    reg = Registry()
    a = reg.counter("shared_total", "h", labels=("x",))
    b = reg.counter("shared_total", "other help ignored", labels=("x",))
    assert a is b
    a.labels("v").inc()
    assert b.collect() == {("v",): 1.0}


def test_get_or_create_rejects_kind_and_label_mismatch():
    reg = Registry()
    reg.counter("m", labels=("x",))
    with pytest.raises(ValueError):
        reg.gauge("m", labels=("x",))
    with pytest.raises(ValueError):
        reg.counter("m", labels=("x", "y"))


def test_get_or_create_rejects_histogram_bucket_and_keep_mismatch():
    reg = Registry()
    h = reg.histogram("h_seconds", buckets=(1.0, 10.0), keep=100)
    # identical re-registration is the normal get path
    assert reg.histogram("h_seconds", buckets=(1.0, 10.0), keep=100) is h
    with pytest.raises(ValueError, match="buckets"):
        reg.histogram("h_seconds", buckets=(0.5, 5.0), keep=100)
    with pytest.raises(ValueError, match="keep"):
        reg.histogram("h_seconds", buckets=(1.0, 10.0), keep=50)


def test_histogram_buckets_cumulative_and_inf():
    reg = Registry()
    h = reg.histogram("lat", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 0.5, 2.0):
        h.observe(v)
    data = h.collect()[()]
    # bucket bounds get +Inf appended; counts are cumulative
    assert data["buckets"] == [(0.1, 1), (1.0, 3), (INF, 4)]
    assert data["count"] == 4
    assert data["sum"] == pytest.approx(3.05)


def test_histogram_boundary_value_lands_in_le_bucket():
    reg = Registry()
    h = reg.histogram("b", buckets=(1.0,))
    h.observe(1.0)  # le="1.0" means <= 1.0
    assert h.collect()[()]["buckets"][0] == (1.0, 1)


def test_histogram_sample_window_bounded_but_count_exact():
    reg = Registry()
    h = reg.histogram("w", keep=10)
    for i in range(100):
        h.observe(float(i))
    data = h.collect()[()]
    assert data["count"] == 100
    assert len(data["samples"]) == 10
    assert data["samples"] == [float(i) for i in range(90, 100)]


def test_histogram_stats_percentiles():
    reg = Registry()
    h = reg.histogram("p", labels=("e",))
    for i in range(1, 101):
        h.labels("a").observe(float(i))
    stats = h.stats()[("a",)]
    assert stats["count"] == 100
    assert stats["p50"] == pytest.approx(50.0, abs=2)
    assert stats["p99"] == pytest.approx(99.0, abs=2)
    assert stats["mean"] == pytest.approx(50.5)


def test_registry_snapshot_shape():
    reg = Registry()
    reg.counter("c_total", "help here", labels=("k",)).labels("v").inc(3)
    reg.histogram("h_seconds").observe(0.25)
    snap = reg.snapshot()
    assert snap["c_total"]["kind"] == "counter"
    assert snap["c_total"]["series"] == {'k="v"': 3.0}
    h = snap["h_seconds"]["series"][""]
    assert h["count"] == 1 and h["sum"] == pytest.approx(0.25)
    json.dumps(snap)  # must be JSON-able as-is


def test_thread_safety_smoke():
    reg = Registry()
    c = reg.counter("n_total")
    h = reg.histogram("n_seconds", keep=50)

    def hammer():
        for _ in range(1000):
            c.inc()
            h.observe(0.001)

    threads = [threading.Thread(target=hammer) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.collect()[()] == 8000.0
    data = h.collect()[()]
    assert data["count"] == 8000
    assert data["buckets"][-1][1] == 8000  # +Inf bucket == count


# -- Prometheus exposition ---------------------------------------------------


def test_render_parse_round_trip():
    reg = Registry()
    reg.counter("rt_total", "a counter", labels=("k",)).labels("v1").inc(2)
    reg.gauge("rt_gauge", "a gauge").set(1.5)
    reg.histogram("rt_seconds", "a histogram", buckets=(0.1, 1.0)).observe(0.5)
    text = render_prometheus(reg)
    assert "# TYPE rt_total counter" in text
    assert 'rt_total{k="v1"} 2' in text
    assert "# TYPE rt_seconds histogram" in text
    assert 'rt_seconds_bucket{le="+Inf"} 1' in text
    samples = parse_prometheus_text(text)
    assert samples["rt_total"] == [({"k": "v1"}, 2.0)]
    assert samples["rt_gauge"] == [({}, 1.5)]
    assert ({"le": "+Inf"}, 1.0) in samples["rt_seconds_bucket"]
    assert samples["rt_seconds_count"] == [({}, 1.0)]


def test_exposition_escapes_label_values():
    reg = Registry()
    nasty = 'a"b\\c\nd'
    reg.counter("esc_total", labels=("k",)).labels(nasty).inc()
    text = render_prometheus(reg)
    samples = parse_prometheus_text(text)
    assert samples["esc_total"] == [({"k": nasty}, 1.0)]


def test_exposition_round_trips_backslash_n_literal():
    # a literal backslash followed by 'n' (e.g. a Windows-path-like value)
    # must NOT decode to a newline: sequential str.replace unescaping got
    # this wrong; the parser must scan left-to-right
    reg = Registry()
    for value in ("foo\\nbar", "c:\\new\\names", "\\\\n", "end\\"):
        reg.counter("bsl_total", labels=("k",)).labels(value).inc()
    samples = parse_prometheus_text(render_prometheus(reg))
    assert sorted(lbl["k"] for lbl, _ in samples["bsl_total"]) == sorted(
        ("foo\\nbar", "c:\\new\\names", "\\\\n", "end\\")
    )


def test_parse_rejects_malformed_sample():
    with pytest.raises(ValueError, match="line 1"):
        parse_prometheus_text("this is not exposition format\n")


def test_parse_rejects_unknown_type():
    with pytest.raises(ValueError, match="unknown metric type"):
        parse_prometheus_text("# TYPE x flumph\nx 1\n")


def test_parse_rejects_inconsistent_histogram():
    text = (
        "# TYPE h histogram\n"
        'h_bucket{le="+Inf"} 3\n'
        "h_sum 1.0\n"
        "h_count 4\n"
    )
    with pytest.raises(ValueError, match=r"\+Inf bucket"):
        parse_prometheus_text(text)


def test_parse_rejects_histogram_missing_inf_bucket():
    text = (
        "# TYPE h histogram\n"
        'h_bucket{le="1.0"} 3\n'
        "h_sum 1.0\n"
        "h_count 3\n"
    )
    with pytest.raises(ValueError, match="no \\+Inf bucket"):
        parse_prometheus_text(text)


# -- tracing -----------------------------------------------------------------


def test_trace_context_binds_and_restores():
    assert tracing.get_trace_id() == ""
    with tracing.trace("abc123") as tid:
        assert tid == "abc123"
        assert tracing.get_trace_id() == "abc123"
        assert tracing.current_or_new() == "abc123"
    assert tracing.get_trace_id() == ""
    assert tracing.current_or_new() != ""  # fresh id when none bound


def test_log_record_factory_stamps_trace_id(caplog):
    tracing.install_log_record_factory()
    test_logger = logging.getLogger("test_observability.stamp")
    with caplog.at_level(logging.INFO, logger=test_logger.name):
        with tracing.trace("deadbeef00000000"):
            test_logger.info("inside")
        test_logger.info("outside")
    inside, outside = caplog.records[-2:]
    assert inside.trace_id == "deadbeef00000000"
    assert outside.trace_id == ""


def test_json_formatter_includes_trace_fields():
    tracing.install_log_record_factory()
    with tracing.trace("feedface00000000"):
        record = logging.getLogger("jf").makeRecord(
            "jf", logging.INFO, __file__, 1, "hello %s", ("world",), None
        )
    payload = json.loads(JsonFormatter().format(record))
    assert payload["message"] == "hello world"
    assert payload["level"] == "INFO"
    assert payload["trace_id"] == "feedface00000000"


# -- client backoff jitter ---------------------------------------------------


def test_client_backoff_jitter_bounds():
    from gordo_components_tpu.client.client import Client

    client = Client("http://x", project="p", retry_backoff=1.0)
    delays = [client._backoff_delay(3) for _ in range(200)]
    # base for attempt 3 is 4.0 s; jitter spans ±50%
    assert all(2.0 <= d <= 6.0 for d in delays)
    assert max(delays) - min(delays) > 0.5  # actually jittered


# -- watchman: probe detail + fleet aggregation ------------------------------


class _FakeResponse:
    def __init__(self, status_code=200, body=None):
        self.status_code = status_code
        self._body = body

    def raise_for_status(self):
        if self.status_code >= 400:
            import requests

            raise requests.HTTPError(f"HTTP {self.status_code}")

    def json(self):
        if self._body is None:
            raise ValueError("no JSON")
        return self._body


def test_watchman_status_surfaces_probe_duration_and_last_error(monkeypatch):
    import requests

    from gordo_components_tpu.watchman.server import WatchmanServer

    watchman = WatchmanServer("proj", {"m-ok": "http://a", "m-dead": "http://b"})
    calls = {"n": 0}

    def fake_get(url, timeout=None):
        # status() also scrapes each base URL's /debug/requests for the
        # slowest-request summary; only the healthz probes count here
        if "/healthz" in url:
            calls["n"] += 1
        if "m-dead" in url:
            raise requests.ConnectionError("refused")
        return _FakeResponse(200)

    monkeypatch.setattr(requests, "get", fake_get)
    body = watchman.status()
    assert calls["n"] == 2 and not body["ok"]
    assert body["slow-requests"] == {}  # fake targets expose no recorder
    by_target = {e["target"]: e for e in body["endpoints"]}
    ok, dead = by_target["m-ok"], by_target["m-dead"]
    assert ok["healthy"] and ok["error"] == "" and ok["last_error"] == ""
    assert not dead["healthy"]
    assert "refused" in dead["error"]
    assert "refused" in dead["last_error"]  # timestamped copy
    assert dead["latency_ms"] >= 0

    # the machine recovers: current error clears, last_error persists
    monkeypatch.setattr(requests, "get", lambda url, timeout=None: _FakeResponse(200))
    recovered = {e["target"]: e for e in watchman.status()["endpoints"]}["m-dead"]
    assert recovered["healthy"] and recovered["error"] == ""
    assert "refused" in recovered["last_error"]


def test_watchman_metrics_aggregates_fleet_wide(monkeypatch):
    import requests

    from gordo_components_tpu.watchman.server import WatchmanServer

    watchman = WatchmanServer(
        "proj", {"m1": "http://a", "m2": "http://a", "m3": "http://b"}
    )
    bodies = {
        "http://a/metrics": {
            "engine": {"machines": 2, "dispatches": 10,
                       "host_path_machines": {"m2": "no scaler"}},
            "latency": {},
        },
        "http://b/metrics": {
            "engine": {"machines": 1, "dispatches": 5,
                       "host_path_machines": {}},
            "latency": {},
        },
    }
    monkeypatch.setattr(
        requests, "get",
        lambda url, timeout=None: _FakeResponse(200, bodies[url]),
    )
    out = watchman.metrics()
    # two distinct base URLs scraped once each, summed into the fleet block
    assert out["targets-total"] == 2 and out["targets-up"] == 2
    assert out["fleet"]["machines"] == 3
    assert out["fleet"]["dispatches"] == 15
    # host-path machines keep WHICH machine, target-prefixed (>1 server)
    assert out["fleet"]["host_path_machines"] == {"http://a/m2": "no scaler"}


def test_watchman_metrics_scrape_failure_counts_target_down(monkeypatch):
    import requests

    from gordo_components_tpu.watchman.server import WatchmanServer

    watchman = WatchmanServer("proj", {"m1": "http://a"})

    def fake_get(url, timeout=None):
        raise requests.ConnectionError("down")

    monkeypatch.setattr(requests, "get", fake_get)
    out = watchman.metrics()
    assert out["targets-up"] == 0 and out["targets-total"] == 1
    assert "error" in out["targets"]["http://a"]
    assert out["fleet"]["dispatches"] == 0


def test_watchman_wsgi_metrics_prometheus(monkeypatch):
    import requests

    from gordo_components_tpu.watchman.server import WatchmanServer

    watchman = WatchmanServer("proj", {"m1": "http://a"})
    monkeypatch.setattr(
        requests, "get", lambda url, timeout=None: _FakeResponse(200)
    )
    watchman.status()  # record at least one probe into the registry
    wsgi = Client(watchman)
    response = wsgi.get("/metrics?format=prometheus")
    assert response.status_code == 200
    assert response.headers["Content-Type"].startswith("text/plain")
    samples = parse_prometheus_text(response.get_data(as_text=True))
    assert "gordo_watchman_probes_total" in samples
    assert "gordo_watchman_probe_seconds_count" in samples


# -- e2e: WSGI app ----------------------------------------------------------

DATA_CONFIG = {
    "type": "RandomDataset",
    "train_start_date": "2023-01-01T00:00:00+00:00",
    "train_end_date": "2023-01-04T00:00:00+00:00",
    "tag_list": ["tag-a", "tag-b", "tag-c"],
}

PLAIN_MODEL = {
    "Pipeline": {
        "steps": [
            "MinMaxScaler",
            {"DenseAutoEncoder": {"kind": "feedforward_symmetric", "dims": [6],
                                  "epochs": 1, "batch_size": 32}},
        ]
    }
}


@pytest.fixture(scope="module")
def client(tmp_path_factory):
    root = tmp_path_factory.mktemp("obs_served")
    model_dir = provide_saved_model(
        "machine-o", PLAIN_MODEL, DATA_CONFIG, str(root),
        evaluation_config={"cv_mode": "build_only"},
    )
    return Client(build_app({"machine-o": model_dir}, project="proj"))


def test_trace_id_round_trips_and_reaches_logs(client, caplog):
    # probe endpoints log at DEBUG (watchman-poll noise control); the
    # access line still carries the trace id
    with caplog.at_level(logging.DEBUG,
                         logger="gordo_components_tpu.server.server"):
        response = client.get(
            "/gordo/v0/proj/machine-o/healthz",
            headers={TRACE_HEADER: "cafebabe12345678"},
        )
    assert response.status_code == 200
    assert response.headers[TRACE_HEADER] == "cafebabe12345678"
    stamped = [r for r in caplog.records
               if getattr(r, "trace_id", "") == "cafebabe12345678"]
    assert stamped, "no log record carried the injected trace id"


def test_server_mints_trace_id_when_absent(client):
    response = client.get("/gordo/v0/proj/machine-o/healthz")
    assert response.status_code == 200
    assert len(response.headers[TRACE_HEADER]) == 16


def test_prometheus_exposition_after_warm_prediction(client):
    payload = json.dumps({"X": [[0.1, 0.2, 0.3], [0.4, 0.5, 0.6]]})
    response = client.post(
        "/gordo/v0/proj/machine-o/prediction",
        data=payload, content_type="application/json",
    )
    assert response.status_code == 200
    response = client.get("/metrics?format=prometheus")
    assert response.status_code == 200
    assert response.headers["Content-Type"].startswith("text/plain")
    assert CONTENT_TYPE.startswith("text/plain")
    text = response.get_data(as_text=True)
    samples = parse_prometheus_text(text)  # must be valid exposition
    # acceptance: engine compile, cache, and dispatch-latency series exist
    assert "gordo_engine_program_cache_total" in samples
    assert any(
        name.startswith("gordo_engine_compile_seconds")
        or name.startswith("gordo_engine_dispatch_seconds")
        for name in samples
    )
    assert "gordo_server_request_duration_seconds_bucket" in samples
    assert "gordo_server_requests_total" in samples


def test_metrics_json_includes_registry_and_latency(client):
    client.get("/gordo/v0/proj/machine-o/healthz")
    body = client.get("/metrics").get_json()
    assert "healthz" in body["latency"]
    assert body["latency"]["healthz"]["count"] >= 1
    assert "registry" in body
    assert "gordo_server_requests_total" in body["registry"]
