"""Unified metrics plane: registry, exposition, histograms, /metrics.

Covers the ISSUE-2 test checklist: exposition format validity,
histogram bucket math, ``/metrics`` presence on JsonHttpServer-based
services (plus the worker runner's standalone metrics server), and the
metric-naming convention check as a tier-1 test.
"""

import math
import os
import subprocess
import sys

import pytest
import requests

from rafiki_tpu.observe.metrics import (Counter, Gauge, Histogram,
                                        MetricsRegistry,
                                        bucket_percentile,
                                        label_context, bound_labels,
                                        metrics_enabled,
                                        parse_exposition, registry,
                                        serve_metrics)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --- Registry / exposition format ---

def test_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("rafiki_tpu_node_widgets_total", "widgets")
    c.inc()
    c.inc(2, kind="a")
    assert c.value() == 1
    assert c.value(kind="a") == 2
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("rafiki_tpu_node_depth_queries")
    g.set(5, q="x")
    g.dec(2, q="x")
    assert g.value(q="x") == 3
    # get-or-create is idempotent, type-checked
    assert reg.counter("rafiki_tpu_node_widgets_total") is c
    with pytest.raises(TypeError):
        reg.gauge("rafiki_tpu_node_widgets_total")


def test_exposition_format_is_valid_prometheus_text():
    reg = MetricsRegistry()
    reg.counter("rafiki_tpu_node_a_total", "help text").inc(3, svc="s1")
    reg.gauge("rafiki_tpu_node_b_ratio").set(0.5)
    reg.histogram("rafiki_tpu_node_c_seconds",
                  buckets=(0.1, 1.0)).observe(0.05)
    text = reg.expose()
    lines = text.strip().splitlines()
    assert "# HELP rafiki_tpu_node_a_total help text" in lines
    assert "# TYPE rafiki_tpu_node_a_total counter" in lines
    assert 'rafiki_tpu_node_a_total{svc="s1"} 3' in lines
    assert "# TYPE rafiki_tpu_node_b_ratio gauge" in lines
    assert "rafiki_tpu_node_b_ratio 0.5" in lines
    assert "# TYPE rafiki_tpu_node_c_seconds histogram" in lines
    assert 'rafiki_tpu_node_c_seconds_bucket{le="0.1"} 1' in lines
    assert 'rafiki_tpu_node_c_seconds_bucket{le="1"} 1' in lines
    assert 'rafiki_tpu_node_c_seconds_bucket{le="+Inf"} 1' in lines
    assert "rafiki_tpu_node_c_seconds_count 1" in lines
    # every non-comment line is "name[{labels}] value"
    for line in lines:
        if line.startswith("#"):
            continue
        name_part, _, value = line.rpartition(" ")
        assert name_part and (value == "+Inf" or float(value) is not None)


def test_label_values_escaped():
    reg = MetricsRegistry()
    reg.counter("rafiki_tpu_node_esc_total").inc(
        1, path='ha"h\\a\nb')
    text = reg.expose()
    # json-style escapes: quote, backslash, newline never break the line
    assert len(text.strip().splitlines()) == 2
    parsed = parse_exposition(text)
    labels, value = parsed["rafiki_tpu_node_esc_total"][0]
    assert labels["path"] == 'ha"h\\a\nb' and value == 1


def test_parse_exposition_roundtrip():
    reg = MetricsRegistry()
    reg.counter("rafiki_tpu_node_x_total").inc(7, a="1", b="2")
    reg.histogram("rafiki_tpu_node_y_seconds",
                  buckets=(0.5,)).observe(0.2, op="p")
    parsed = parse_exposition(reg.expose())
    assert ({"a": "1", "b": "2"}, 7.0) in parsed["rafiki_tpu_node_x_total"]
    buckets = parsed["rafiki_tpu_node_y_seconds_bucket"]
    assert ({"op": "p", "le": "0.5"}, 1.0) in buckets
    assert ({"op": "p", "le": "+Inf"}, 1.0) in buckets


# --- Histogram bucket math ---

def test_histogram_bucket_assignment_and_sums():
    h = Histogram("rafiki_tpu_node_h_seconds", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.01, 0.05, 0.5, 5.0):
        h.observe(v)
    cum = h.cumulative_buckets()
    # cumulative: <=0.01 -> 2 (0.005, 0.01 on the boundary), <=0.1 -> 3,
    # <=1.0 -> 4, +Inf -> 5
    assert cum == [(0.01, 2), (0.1, 3), (1.0, 4), (math.inf, 5)]
    assert h.count() == 5
    assert h.sum() == pytest.approx(5.565)


def test_histogram_percentile_interpolation():
    h = Histogram("rafiki_tpu_node_p_seconds", buckets=(1.0, 2.0, 4.0))
    for _ in range(50):
        h.observe(0.5)   # first bucket
    for _ in range(50):
        h.observe(3.0)   # third bucket
    # median at the first bucket's upper bound
    assert h.percentile(0.5) == pytest.approx(1.0)
    # p99 interpolates inside (2.0, 4.0]
    p99 = h.percentile(0.99)
    assert 2.0 < p99 <= 4.0
    # quantile landing in +Inf reports the last finite bound
    h2 = Histogram("rafiki_tpu_node_q_seconds", buckets=(1.0,))
    h2.observe(10.0)
    assert h2.percentile(0.5) == 1.0
    # empty histogram -> None
    assert Histogram("rafiki_tpu_node_r_seconds").percentile(0.5) is None


def test_bucket_percentile_edge_cases():
    assert bucket_percentile([], 0.5) is None
    assert bucket_percentile([(1.0, 0), (math.inf, 0)], 0.5) is None
    # single bucket, all mass: interpolates within [0, bound]
    assert bucket_percentile([(2.0, 10), (math.inf, 10)], 0.5) == \
        pytest.approx(1.0)


# --- Label context (per-trial attribution) ---

def test_label_context_nests_and_restores():
    assert bound_labels() == {}
    with label_context(trial="t1"):
        assert bound_labels() == {"trial": "t1"}
        with label_context(extra="x"):
            assert bound_labels() == {"trial": "t1", "extra": "x"}
        assert bound_labels() == {"trial": "t1"}
    assert bound_labels() == {}


# --- /metrics on JsonHttpServer services ---

def test_metrics_route_on_any_json_http_server():
    from rafiki_tpu.utils.service import JsonHttpServer

    registry().counter("rafiki_tpu_node_probe_total").inc()
    server = JsonHttpServer(
        [("GET", "/", lambda p, b, c: (200, {"ok": True}))],
        host="127.0.0.1", name="test-svc").start()
    try:
        r = requests.get(
            f"http://127.0.0.1:{server.port}/metrics", timeout=10)
        assert r.status_code == 200
        assert r.headers["Content-Type"].startswith("text/plain")
        assert "rafiki_tpu_node_probe_total" in r.text
        # the request we just made was itself instrumented
        r2 = requests.get(
            f"http://127.0.0.1:{server.port}/metrics", timeout=10)
        assert 'service="test-svc"' in r2.text
        assert "rafiki_tpu_http_request_seconds_bucket" in r2.text
    finally:
        server.stop()


def test_metrics_route_on_predictor_service():
    from rafiki_tpu.bus import MemoryBus
    from rafiki_tpu.predictor.app import PredictorService

    svc = PredictorService("msvc", "job", meta=None, bus=MemoryBus(),
                           host="127.0.0.1")
    svc._http.start()
    try:
        r = requests.get(f"http://127.0.0.1:{svc.port}/metrics",
                         timeout=10)
        assert r.status_code == 200
        assert "# TYPE" in r.text
    finally:
        svc._http.stop()
        if svc.batcher is not None:
            svc.batcher.stop()


def test_worker_runner_metrics_server():
    """Subprocess worker runners get a standalone metrics-only server
    (container/services.py wires it from RAFIKI_TPU_METRICS_PORT)."""
    server = serve_metrics(host="127.0.0.1", port=0)
    try:
        base = f"http://127.0.0.1:{server.port}"
        assert requests.get(base + "/", timeout=10).json() == {
            "status": "ok"}
        r = requests.get(base + "/metrics", timeout=10)
        assert r.status_code == 200 and "# TYPE" in r.text
    finally:
        server.stop()


def test_metrics_env_disables_route(monkeypatch):
    from rafiki_tpu.utils.service import JsonHttpServer

    monkeypatch.setenv("RAFIKI_TPU_METRICS", "0")
    assert not metrics_enabled()
    server = JsonHttpServer(
        [("GET", "/", lambda p, b, c: (200, {}))],
        host="127.0.0.1", name="off-svc").start()
    try:
        r = requests.get(f"http://127.0.0.1:{server.port}/metrics",
                         timeout=10)
        assert r.status_code == 404
    finally:
        server.stop()
    monkeypatch.delenv("RAFIKI_TPU_METRICS")
    assert metrics_enabled()


# --- ServingStats folded into the registry ---

def test_serving_stats_backed_by_registry():
    from rafiki_tpu.observe import ServingStats

    s = ServingStats()
    s.admitted(4)
    s.admitted(2)
    s.backpressured()
    s.set_queue_depth(6)
    s.dispatched(2, 6, fill_s=0.004, scatter_s=0.001, inflight=1)
    s.gathered(0.02, inflight=0)
    snap = s.snapshot()
    assert snap["requests"] == 2 and snap["queries"] == 6
    assert snap["rejected"] == 1
    assert snap["coalescing_factor"] == 2.0
    assert snap["queue_depth_peak"] == 6
    assert snap["fill"]["count"] == 1
    assert snap["fill"]["mean_ms"] == pytest.approx(4.0, rel=0.01)
    assert snap["gather"]["p95_ms"] > 0
    # the same numbers are in the shared registry under this service's
    # label — /stats and /metrics cannot disagree
    c = registry().counter("rafiki_tpu_serving_requests_total")
    assert c.value(service=s.service) == 2
    # a second instance gets its own series
    s2 = ServingStats()
    assert s2.requests == 0 and s2.service != s.service
    # close() releases the label sets (deploy/stop churn must not grow
    # the registry forever)
    label = s.service
    s.close()
    assert not any(lbl.get("service") == label
                   for lbl, _ in c.samples())
    hist = registry().find("rafiki_tpu_serving_stage_seconds")
    assert hist.count(service=label, stage="fill") == 0


def test_series_remove_matches_label_subset():
    reg = MetricsRegistry()
    c = reg.counter("rafiki_tpu_node_rm_total")
    c.inc(1, service="a", route="/x")
    c.inc(1, service="a", route="/y")
    c.inc(1, service="b", route="/x")
    c.remove(service="a")
    assert c.value(service="a", route="/x") == 0
    assert c.value(service="a", route="/y") == 0
    assert c.value(service="b", route="/x") == 1


def test_trial_gauge_cleared_when_trial_ends():
    """A finished trial's MFU series must not read as live utilization
    forever (TrialRunner removes it in its trial-finally)."""
    g = registry().gauge("rafiki_tpu_train_mfu_ratio")
    g.set(0.5, trial="abcdef123456")
    g.set(0.6, trial="other0000000")
    g.remove(trial="abcdef123456")  # what the runner does
    assert not any(lbl.get("trial") == "abcdef123456"
                   for lbl, _ in g.samples())
    assert g.value(trial="other0000000") == 0.6
    g.remove(trial="other0000000")


# --- Naming convention (tier-1 static check) ---

def test_metric_naming_convention_check_passes():
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO_ROOT, "scripts", "check_metrics_names.py"),
         REPO_ROOT],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "all metric names conform" in proc.stdout


def test_naming_check_catches_violations(tmp_path):
    bad = tmp_path / "rafiki_tpu" / "bad.py"
    bad.parent.mkdir()
    bad.write_text(
        'reg.counter("rafiki_tpu_serving_widgets")\n'        # no unit
        'reg.gauge("rafiki_tpu_mystery_thing_ratio")\n'      # subsystem
        'reg.histogram("rafiki_tpu_bus_wait_seconds")\n')    # ok
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO_ROOT, "scripts", "check_metrics_names.py"),
         str(tmp_path)],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 1
    assert "rafiki_tpu_serving_widgets" in proc.stdout
    assert "rafiki_tpu_mystery_thing_ratio" in proc.stdout
    assert "rafiki_tpu_bus_wait_seconds" not in proc.stdout


# --- Bus instrumentation ---

def test_bus_ops_land_in_histogram():
    from rafiki_tpu.bus import MemoryBus

    h = registry().find("rafiki_tpu_bus_op_seconds")
    bus = MemoryBus()
    before = h.count(backend="memory", op="push", kind="query") if h else 0
    bus.push("q:w9", 1)
    bus.pop("q:w9")
    bus.push_many([("r:abc", 1), ("r:abc", 2)])
    bus.pop_all("r:abc")
    h = registry().find("rafiki_tpu_bus_op_seconds")
    assert h is not None
    assert h.count(backend="memory", op="push", kind="query") == before + 1
    assert h.count(backend="memory", op="push_many", kind="reply") >= 1
    assert h.count(backend="memory", op="pop_all", kind="reply") >= 1


def test_bus_tcp_client_ops_instrumented():
    from rafiki_tpu.bus import BusClient, BusServer

    server = BusServer().start()
    client = BusClient(server.host, server.port)
    try:
        client.push("q:tcp1", {"v": 1})
        assert client.pop("q:tcp1") == {"v": 1}
        # push_many (the serving scatter) must record kind="query"
        # exactly like the memory backend, not "other"
        client.push_many([("q:tcp2", 1), ("q:tcp3", 2)])
        h = registry().find("rafiki_tpu_bus_op_seconds")
        assert h.count(backend="tcp", op="push", kind="query") >= 1
        assert h.count(backend="tcp", op="pop", kind="query") >= 1
        assert h.count(backend="tcp", op="push_many", kind="query") >= 1
    finally:
        client.close()
        server.stop()


# --- Exemplars + exposition hardening (ISSUE r17) ---

def _expose_parse(reg):
    return parse_exposition(reg.expose())


def test_parse_exposition_escaped_label_values_roundtrip():
    """Label values containing ", \\n and \\\\ survive expose -> parse
    exactly (the backslash-run escape scan; a value ENDING in a
    backslash is the case a single-char look-behind gets wrong)."""
    reg = MetricsRegistry()
    c = reg.counter("rafiki_tpu_node_escapes_total")
    values = ['plain', 'has"quote', 'new\nline', 'back\\slash',
              'trailing\\', 'mix\\"both\\', 'a,b{c}d']
    for i, v in enumerate(values):
        c.inc(i + 1, tricky=v)
    parsed = _expose_parse(reg)["rafiki_tpu_node_escapes_total"]
    got = {labels["tricky"]: v for labels, v in parsed}
    assert got == {v: float(i + 1) for i, v in enumerate(values)}


def test_parse_exposition_tolerates_exemplar_annotations():
    from rafiki_tpu.observe.metrics import strip_exemplar

    text = (
        'rafiki_tpu_http_request_seconds_bucket{le="0.25"} 41 '
        '# {trace_id="9f31aa"} 0.187 1754300000.0\n'
        'rafiki_tpu_http_request_seconds_bucket{le="+Inf"} 42 '
        '# {trace_id="9f31aa"} 3.0\n'
        'rafiki_tpu_http_request_seconds_count 42\n'
        # a # INSIDE a quoted value is data, not an annotation
        'rafiki_tpu_node_odd_total{v="a # b"} 7\n')
    out = parse_exposition(text)
    buckets = out["rafiki_tpu_http_request_seconds_bucket"]
    assert [v for _, v in buckets] == [41.0, 42.0]
    assert out["rafiki_tpu_node_odd_total"][0][0]["v"] == "a # b"
    assert strip_exemplar('x{v="a # b"} 7') == 'x{v="a # b"} 7'


def test_histogram_exemplars_record_expose_and_api(monkeypatch):
    from rafiki_tpu.observe import metrics as m
    from rafiki_tpu.observe import trace

    monkeypatch.setenv(m.EXEMPLARS_ENV, "1")
    m.reset_exemplars_for_tests()
    try:
        reg = MetricsRegistry()
        h = reg.histogram("rafiki_tpu_http_request_seconds")
        tid = "ab" * 16
        with trace.use(trace.TraceContext(tid)):
            h.observe(0.003, service="svc", route="/predict")
            h.observe(20.0, service="svc", route="/predict")  # +Inf
        h.observe(0.003, service="svc", route="/other")  # untraced
        ex = h.exemplars(service="svc", route="/predict")
        assert ex["0.005"]["trace_id"] == tid
        assert ex["+Inf"]["trace_id"] == tid
        assert ex["0.005"]["value"] == 0.003
        assert h.exemplars(service="svc", route="/other") == {}
        # Annotations live ONLY in the negotiated OpenMetrics
        # exposition; the classic 0.0.4 text stays clean (a stock
        # Prometheus parser would reject annotated lines).
        text = reg.expose(exemplars=True)
        assert f'# {{trace_id="{tid}"}} 0.003' in text
        assert "trace_id" not in reg.expose()
        # the annotated exposition still parses (bucket values intact)
        parsed = parse_exposition(text)
        buckets = parsed["rafiki_tpu_http_request_seconds_bucket"]
        by_le = {la["le"]: v for la, v in buckets
                 if la.get("route") == "/predict"}
        assert by_le["+Inf"] == 2.0
        # remove() clears the exemplars with the series
        h.remove(service="svc")
        assert h.exemplars(service="svc", route="/predict") == {}
        assert "trace_id" not in reg.expose(exemplars=True)
    finally:
        m.reset_exemplars_for_tests()


def test_metrics_route_exemplars_are_explicit_opt_in(monkeypatch):
    """GET /metrics stays clean classic 0.0.4 text for every scrape —
    including one that NEGOTIATES OpenMetrics via Accept, which stock
    Prometheus does by default — even with exemplars ON; only the
    explicit ?exemplars=1 debug view is annotated."""
    from rafiki_tpu.observe import metrics as m
    from rafiki_tpu.observe import trace
    from rafiki_tpu.utils.service import JsonHttpServer

    monkeypatch.setenv(m.EXEMPLARS_ENV, "1")
    m.reset_exemplars_for_tests()
    server = JsonHttpServer([], host="127.0.0.1",
                            name="exemplar-svc").start()
    try:
        tid = "ef" * 16
        with trace.use(trace.TraceContext(tid)):
            registry().histogram(
                "rafiki_tpu_http_request_seconds").observe(
                    0.004, service="exemplar-svc", route="/x")
        base = f"http://127.0.0.1:{server.port}/metrics"
        classic = requests.get(base, timeout=10)
        assert "version=0.0.4" in classic.headers["Content-Type"]
        assert " # {" not in classic.text
        # a stock-Prometheus-style Accept must NOT flip the format
        neg = requests.get(base, timeout=10, headers={
            "Accept": "application/openmetrics-text; version=1.0.0"})
        assert "version=0.0.4" in neg.headers["Content-Type"]
        assert " # {" not in neg.text
        annotated = requests.get(base + "?exemplars=1", timeout=10)
        assert f'# {{trace_id="{tid}"}}' in annotated.text
        assert parse_exposition(annotated.text)  # still parses
    finally:
        server.stop()
        registry().find("rafiki_tpu_http_request_seconds").remove(
            service="exemplar-svc")
        m.reset_exemplars_for_tests()


def test_exemplars_disabled_by_default(monkeypatch):
    from rafiki_tpu.observe import metrics as m
    from rafiki_tpu.observe import trace

    monkeypatch.delenv(m.EXEMPLARS_ENV, raising=False)
    m.reset_exemplars_for_tests()
    try:
        reg = MetricsRegistry()
        h = reg.histogram("rafiki_tpu_http_request_seconds")
        with trace.use(trace.TraceContext("cd" * 16)):
            h.observe(0.003, service="svc")
        assert h.exemplars(service="svc") == {}
        assert " # {" not in reg.expose()
    finally:
        m.reset_exemplars_for_tests()


def test_exemplars_skip_tail_dropped_traces(tmp_path, monkeypatch):
    """An exemplar must never link a trace whose tail verdict dropped
    its spans (the link would resolve to an empty timeline): pending
    and dropped tail traces are skipped, kept ones qualify."""
    from rafiki_tpu.observe import metrics as m
    from rafiki_tpu.observe import trace

    monkeypatch.setenv(m.EXEMPLARS_ENV, "1")
    monkeypatch.setenv(trace.TRACE_TAIL_SAMPLE_ENV, "0")
    monkeypatch.setenv(trace.TRACE_TAIL_SLOW_MS_ENV, "100")
    m.reset_exemplars_for_tests()
    trace.reset_tail_for_tests()
    trace.configure(str(tmp_path))
    try:
        reg = MetricsRegistry()
        h = reg.histogram("rafiki_tpu_http_request_seconds")
        # Pending: no verdict yet -> no exemplar.
        ctx = trace.start_trace(None)
        assert ctx is not None and ctx.tail
        with trace.use(ctx):
            h.observe(0.001, service="s")
        assert h.exemplars(service="s") == {}
        # Dropped: still no exemplar.
        trace.complete(ctx, 0.001, error=False)
        with trace.use(ctx):
            h.observe(0.001, service="s")
        assert h.exemplars(service="s") == {}
        # Kept (slow): exemplar recorded.
        kept = trace.start_trace(None)
        trace.complete(kept, 0.5, error=False)
        with trace.use(kept):
            h.observe(0.5, service="s")
        ex = h.exemplars(service="s")
        assert any(v["trace_id"] == kept.trace_id for v in ex.values())
    finally:
        trace.configure(None)
        trace.reset_tail_for_tests()
        m.reset_exemplars_for_tests()
