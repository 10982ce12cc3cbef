"""End-to-end request tracing: ids, envelope carry, spans, stitching.

Covers the ISSUE-2 test checklist: trace-id propagation across the
memory and tcp buses (including the old-frame fallback), the HTTP edge
(mint + honor + echo of ``X-Trace-Id``), span recording through the
shared JSONL sink, and the admin's ``GET /trace/<id>`` stitcher.
"""

import json
import os
import threading
import time

import pytest
import requests

from rafiki_tpu.bus import BusClient, BusServer, MemoryBus
from rafiki_tpu.cache import Cache
from rafiki_tpu.observe import trace


@pytest.fixture()
def span_sink(tmp_path):
    """Point the process span sink at a temp dir; always restore."""
    trace.configure(str(tmp_path))
    yield str(tmp_path)
    trace.configure(None)


@pytest.fixture(params=["memory", "tcp"])
def bus(request):
    if request.param == "memory":
        yield MemoryBus()
        return
    server = BusServer().start()
    client = BusClient(server.host, server.port)
    yield client
    client.close()
    server.stop()


# --- Context / header parsing ---

def test_start_trace_mints_and_parses():
    ctx = trace.start_trace(None)
    assert ctx is not None and len(ctx.trace_id) == 32
    parsed = trace.start_trace(f"{ctx.trace_id}-{ctx.span_id}")
    assert parsed.trace_id == ctx.trace_id
    assert parsed.parent_id == ctx.span_id
    bare = trace.start_trace("sometid")
    assert bare.trace_id == "sometid" and bare.parent_id is None
    # a standard dashed UUID is taken WHOLE, never split at its dashes
    dashed = "550e8400-e29b-41d4-a716-446655440000"
    got = trace.start_trace(dashed)
    assert got.trace_id == dashed and got.parent_id is None


def test_sample_rate_zero_suppresses_fresh_traces(monkeypatch):
    monkeypatch.setenv(trace.TRACE_SAMPLE_ENV, "0")
    assert trace.start_trace(None) is None
    # ...but an incoming id is ALWAYS honored
    assert trace.start_trace("abc123").trace_id == "abc123"
    monkeypatch.setenv(trace.TRACE_SAMPLE_ENV, "not-a-number")
    assert trace.sample_rate() == 1.0


def test_thread_local_current_context():
    assert trace.current() is None
    ctx = trace.TraceContext("t1")
    with trace.use(ctx):
        assert trace.current() is ctx
        with trace.use(None):
            assert trace.current() is None
        assert trace.current() is ctx
    assert trace.current() is None


# --- Envelope inject/extract (old-frame fallback) ---

def test_inject_extract_roundtrip():
    ctxs = [trace.TraceContext("t" * 32), trace.TraceContext("u" * 32)]
    frame = {"batch_id": "b1", "queries": [1, 2],
             trace.ENVELOPE_KEY: trace.inject(ctxs)}
    out = trace.extract(frame)
    assert [c.trace_id for c in out] == ["t" * 32, "u" * 32]
    # extraction CONTINUES the propagated span: downstream child spans
    # parent onto the sender's span
    assert out[0].span_id == ctxs[0].span_id
    # envelope is POPPED: downstream frame handling never sees it
    assert trace.ENVELOPE_KEY not in frame


def test_old_frames_and_malformed_envelopes_fall_back():
    assert trace.extract({"batch_id": "b", "queries": []}) == []
    assert trace.extract("not-a-dict") == []
    assert trace.extract({trace.ENVELOPE_KEY: "garbage"}) == []
    assert trace.extract({trace.ENVELOPE_KEY: {"ids": "nope"}}) == []
    assert trace.inject([]) is None
    assert trace.inject([None]) is None


def test_envelope_caps_trace_count():
    ctxs = [trace.TraceContext(f"t{i}") for i in range(100)]
    env = trace.inject(ctxs)
    assert len(env["ids"]) == trace.MAX_ENVELOPE_TRACES


# --- Propagation across the bus (memory + tcp) ---

def test_trace_rides_bus_envelope(bus):
    cache = Cache(bus)
    ctx = trace.TraceContext("cafe" * 8)
    cache.send_query_batch_fanout(["wA", "wB"], [{"v": 1}],
                                  trace_ctxs=[ctx])
    for w in ("wA", "wB"):
        items = cache.pop_queries(w, timeout=5.0)
        assert len(items) == 1
        got = trace.extract(items[0])
        assert [c.trace_id for c in got] == ["cafe" * 8]
        assert got[0].span_id == ctx.span_id
        # payload untouched by the envelope
        assert items[0]["queries"] == [{"v": 1}]


def test_ambient_context_injected_on_direct_path(bus):
    cache = Cache(bus)
    with trace.use(trace.TraceContext("beef" * 8)):
        cache.send_query_batch_fanout(["wC"], [1, 2])
        cache.send_query("wC", 3)
    items = cache.pop_queries("wC", timeout=5.0)
    assert len(items) == 2
    for it in items:
        assert [c.trace_id for c in trace.extract(it)] == ["beef" * 8]


def test_untraced_frames_stay_old_shape(bus):
    """No ambient context -> the frame has NO trace key at all (an old
    consumer sees byte-identical frames)."""
    cache = Cache(bus)
    cache.send_query_batch_fanout(["wD"], [{"v": 1}])
    item = cache.pop_queries("wD", timeout=5.0)[0]
    assert trace.ENVELOPE_KEY not in item


# --- Span sink + stitching ---

def test_record_and_collect_spans(span_sink):
    tid = "deadbeef" * 4
    ctx = trace.TraceContext(tid)
    t0 = time.time()
    trace.record_event("http POST /predict", "admin", [ctx], t0, 0.010,
                       child=False)
    trace.record_event("worker.predict", "w1", [ctx], t0 + 0.002, 0.005,
                       attrs={"n_queries": 4})
    out = trace.collect_trace(span_sink, tid)
    assert out["n_spans"] == 2
    names = [s["name"] for s in out["spans"]]
    assert names == ["http POST /predict", "worker.predict"]  # ordered
    assert out["spans"][0]["offset_ms"] == 0.0
    assert out["spans"][1]["offset_ms"] == pytest.approx(2.0, abs=1.0)
    # the child span parents onto the propagated span
    assert out["spans"][1]["parent_id"] == ctx.span_id
    assert out["spans"][1]["attrs"]["n_queries"] == 4
    # unknown trace -> empty, not an error
    assert trace.collect_trace(span_sink, "nope")["n_spans"] == 0


def test_collect_skips_corrupt_lines(span_sink):
    tid = "feed" * 8
    with open(trace.span_log_path(span_sink), "a") as f:
        f.write(f"{tid} not json\n")
        f.write(json.dumps({"trace_id": tid, "name": "ok",
                            "start_s": 1.0, "dur_ms": 1}) + "\n")
    out = trace.collect_trace(span_sink, tid)
    assert out["n_spans"] == 1 and out["spans"][0]["name"] == "ok"


def _spam(ctx, n, name="spam"):
    for _ in range(n):
        trace.record_event(name, "s", [ctx], 1.0, 0.001)


def test_multi_segment_store_indexed_read(span_sink, monkeypatch):
    """ISSUE r17 acceptance: a multi-segment store serves
    GET /trace/<id> via the sidecar index — frozen segments are seek+
    readline at indexed offsets, never a full-file scan — including a
    trace whose spans straddle a segment roll."""
    monkeypatch.setenv(trace.TRACE_MAX_MB_ENV, str(1 / 1024))  # 1 KiB
    monkeypatch.setenv(trace.TRACE_RETAIN_SEGMENTS_ENV, "3")
    straddle = trace.TraceContext("ab" * 16)
    filler = trace.TraceContext("cd" * 16)
    # One straddle span early, spam until at least two rolls happened,
    # one straddle span late: its spans now live in a frozen segment
    # AND the active file.
    trace.record_event("first", "s", [straddle], 1.0, 0.001)
    path = trace.span_log_path(span_sink)
    for _ in range(100):
        _spam(filler, 5)
        if os.path.exists(path + ".2"):
            break
    assert os.path.exists(path + ".1") and os.path.exists(path + ".2")
    trace.record_event("last", "s", [straddle], 2.0, 0.001)
    # Roll-time sidecar indexes exist for the frozen generations.
    assert os.path.exists(trace.index_path(path + ".1"))
    out = trace.collect_trace(span_sink, straddle.trace_id)
    names = {s["name"] for s in out["spans"]}
    assert "first" in names and "last" in names
    # The read-path evidence: every frozen segment was an INDEXED
    # read, and the bytes it cost are the matching lines only — far
    # below the segment size (the no-full-scan pin).
    frozen = [d for d in out["segments"]
              if d["segment"] != trace.SPAN_FILE]
    assert frozen, out["segments"]
    for diag in frozen:
        assert diag["mode"] == "index", out["segments"]
        seg = os.path.join(span_sink, diag["segment"])
        if diag["n_spans"] == 0:
            assert diag["bytes_read"] == 0, diag
        else:
            assert diag["bytes_read"] < os.path.getsize(seg) / 2, diag
    # The filler trace is found through the same index path.
    assert trace.collect_trace(span_sink,
                               filler.trace_id)["n_spans"] > 0
    # Warm repeat on the ACTIVE segment scans zero new bytes (the
    # incremental cache only ever reads the appended tail).
    again = trace.collect_trace(span_sink, straddle.trace_id)
    active = [d for d in again["segments"]
              if d["segment"] == trace.SPAN_FILE]
    assert active and active[0]["mode"] == "scan_tail"
    span_bytes = sum(d["n_spans"] for d in again["segments"])
    assert span_bytes  # sanity: the trace is still found


def test_index_rebuilt_when_sidecar_missing(span_sink, monkeypatch):
    """A frozen segment whose .idx vanished (partial copy, manual
    cleanup) is re-indexed lazily — and the rebuilt sidecar persists
    for the next reader."""
    monkeypatch.setenv(trace.TRACE_MAX_MB_ENV, str(1 / 1024))
    ctx = trace.TraceContext("ee" * 16)
    _spam(ctx, 20)
    path = trace.span_log_path(span_sink)
    assert os.path.exists(path + ".1")
    os.remove(trace.index_path(path + ".1"))
    out = trace.collect_trace(span_sink, ctx.trace_id)
    assert out["n_spans"] > 0
    modes = {d["segment"]: d["mode"] for d in out["segments"]}
    assert modes.get(trace.SPAN_FILE + ".1") == "index_rebuilt"
    assert os.path.exists(trace.index_path(path + ".1"))
    out2 = trace.collect_trace(span_sink, ctx.trace_id)
    modes2 = {d["segment"]: d["mode"] for d in out2["segments"]}
    assert modes2.get(trace.SPAN_FILE + ".1") == "index"


def test_retention_bounds_segments_and_bytes(span_sink, monkeypatch):
    """The generation chain is bounded by BOTH knobs: at most
    RETAIN_SEGMENTS rolled files, and oldest generations are deleted
    when the rolled chain exceeds RETAIN_MB (the newest rolled segment
    always survives)."""
    monkeypatch.setenv(trace.TRACE_MAX_MB_ENV, str(1 / 1024))
    monkeypatch.setenv(trace.TRACE_RETAIN_SEGMENTS_ENV, "2")
    ctx = trace.TraceContext("aa" * 16)
    path = trace.span_log_path(span_sink)
    _spam(ctx, 200)
    assert os.path.exists(path + ".1")
    assert os.path.exists(path + ".2")
    assert not os.path.exists(path + ".3")  # count bound enforced
    # Byte budget below one segment: only .1 survives the next roll.
    monkeypatch.setenv(trace.TRACE_RETAIN_MB_ENV, str(0.5 / 1024))
    _spam(ctx, 40)
    assert os.path.exists(path + ".1")
    assert not os.path.exists(path + ".2"), "byte budget not enforced"


def test_tail_sampling_verdicts(span_sink, monkeypatch):
    """Error and slow traces always persist; fast ones drop at
    sample=0 — and a straggler span arriving after the drop verdict is
    suppressed, not resurrected as an orphan."""
    monkeypatch.setenv(trace.TRACE_TAIL_SAMPLE_ENV, "0")
    monkeypatch.setenv(trace.TRACE_TAIL_SLOW_MS_ENV, "100")
    trace.reset_tail_for_tests()
    try:
        # Error outcome: buffered spans flush.
        err = trace.start_trace(None)
        assert err is not None and err.tail
        trace.record_event("edge", "svc", [err], 1.0, 0.01, child=False)
        assert trace.collect_trace(span_sink,
                                   err.trace_id)["n_spans"] == 0
        trace.complete(err, 0.01, error=True)
        assert trace.collect_trace(span_sink,
                                   err.trace_id)["n_spans"] == 1
        # Slow outcome: kept despite sample=0.
        slow = trace.start_trace(None)
        trace.record_event("edge", "svc", [slow], 1.0, 0.2, child=False)
        trace.complete(slow, 0.2, error=False)
        assert trace.collect_trace(span_sink,
                                   slow.trace_id)["n_spans"] == 1
        # Fast + ok at sample 0: dropped, late spans suppressed.
        fast = trace.start_trace(None)
        trace.record_event("edge", "svc", [fast], 1.0, 0.001,
                           child=False)
        trace.complete(fast, 0.001, error=False)
        assert trace.collect_trace(span_sink,
                                   fast.trace_id)["n_spans"] == 0
        trace.record_event("late.worker", "w", [fast], 1.1, 0.001)
        assert trace.collect_trace(span_sink,
                                   fast.trace_id)["n_spans"] == 0
        # An honored X-Trace-Id bypasses tail sampling entirely.
        honored = trace.start_trace("ff" * 16)
        assert honored is not None and not honored.tail
        trace.record_event("edge", "svc", [honored], 1.0, 0.001,
                           child=False)
        assert trace.collect_trace(span_sink,
                                   "ff" * 16)["n_spans"] == 1
    finally:
        trace.reset_tail_for_tests()


def test_tail_sampling_seeded_rate(span_sink, monkeypatch):
    """Fast traces keep at exactly the seeded RNG's decision sequence
    for the configured rate — 100% of error/slow traces survive a
    seeded mixed workload while fast ones sample (the r17 acceptance
    shape)."""
    import random as _random

    rate = 0.3
    monkeypatch.setenv(trace.TRACE_TAIL_SAMPLE_ENV, str(rate))
    monkeypatch.setenv(trace.TRACE_TAIL_SLOW_MS_ENV, "50")
    trace.reset_tail_for_tests()
    trace.seed_tail(42)
    try:
        kept_fast = 0
        n_fast = 0
        rng = _random.Random(42)  # mirror of the module's seeded rng
        expected_kept = 0
        for i in range(60):
            ctx = trace.start_trace(None)
            assert ctx is not None
            trace.record_event("edge", "svc", [ctx], 1.0, 0.001,
                               child=False)
            if i % 5 == 0:   # error: must survive
                trace.complete(ctx, 0.001, error=True)
                assert trace.collect_trace(
                    span_sink, ctx.trace_id)["n_spans"] == 1
            elif i % 5 == 1:  # slow: must survive
                trace.complete(ctx, 0.5, error=False)
                assert trace.collect_trace(
                    span_sink, ctx.trace_id)["n_spans"] == 1
            else:            # fast: seeded coin
                n_fast += 1
                if rng.random() < rate:
                    expected_kept += 1
                trace.complete(ctx, 0.001, error=False)
                kept_fast += trace.collect_trace(
                    span_sink, ctx.trace_id)["n_spans"]
        assert kept_fast == expected_kept
        assert 0 < kept_fast < n_fast  # genuinely sampling
    finally:
        trace.reset_tail_for_tests()


def test_tail_pending_overflow_flushes(span_sink, monkeypatch):
    """A pending trace overflowing the per-trace span cap (an edge
    that never completes) flushes to the store — retain on doubt,
    never silent loss."""
    monkeypatch.setenv(trace.TRACE_TAIL_SAMPLE_ENV, "0")
    trace.reset_tail_for_tests()
    try:
        ctx = trace.start_trace(None)
        for _ in range(trace._PENDING_MAX_SPANS + 5):
            trace.record_event("s", "svc", [ctx], 1.0, 0.001)
        out = trace.collect_trace(span_sink, ctx.trace_id)
        assert out["n_spans"] > trace._PENDING_MAX_SPANS
        # Completion after the overflow is a no-op (already flushed).
        trace.complete(ctx, 0.001, error=False)
        assert trace.collect_trace(span_sink,
                                   ctx.trace_id)["n_spans"] > 0
    finally:
        trace.reset_tail_for_tests()


def test_tail_sampling_at_http_edge(span_sink, monkeypatch):
    """The JsonHttpServer edge delivers the verdict: a 5xx response
    keeps its trace's spans, a fast 200 at sample=0 drops them."""
    from rafiki_tpu.utils.service import JsonHttpServer

    monkeypatch.setenv(trace.TRACE_TAIL_SAMPLE_ENV, "0")
    monkeypatch.setenv(trace.TRACE_TAIL_SLOW_MS_ENV, "60000")
    trace.reset_tail_for_tests()

    def ok(params, body, ctx):
        return 200, {"ok": True}

    def boom(params, body, ctx):
        raise RuntimeError("kaput")

    server = JsonHttpServer([("GET", "/ok", ok), ("GET", "/boom", boom)],
                            host="127.0.0.1", name="tail-svc").start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        r_ok = requests.get(base + "/ok", timeout=10)
        tid_ok = r_ok.headers["X-Trace-Id"].split("-")[0]
        r_boom = requests.get(base + "/boom", timeout=10)
        assert r_boom.status_code == 500
        tid_boom = r_boom.headers["X-Trace-Id"].split("-")[0]
        assert trace.collect_trace(span_sink, tid_ok)["n_spans"] == 0
        out = trace.collect_trace(span_sink, tid_boom)
        assert out["n_spans"] == 1
        assert out["spans"][0]["attrs"]["status"] == 500
    finally:
        server.stop()
        trace.reset_tail_for_tests()


def test_span_log_rotates_at_size_cap(span_sink, monkeypatch):
    """The sink rolls spans.jsonl to one .1 generation at the size cap
    (a client forcing X-Trace-Id must not be able to fill the disk),
    and collect_trace reads both generations."""
    monkeypatch.setenv(trace.TRACE_MAX_MB_ENV, str(1 / 1024))  # 1 KiB
    old_tid = "aa" * 16
    ctx = trace.TraceContext(old_tid)
    for _ in range(20):  # ~170 bytes/line -> crosses 1 KiB
        trace.record_event("spam", "s", [ctx], 1.0, 0.001)
    assert os.path.exists(trace.span_log_path(span_sink) + ".1")
    new_tid = "bb" * 16
    trace.record_event("after-roll", "s", [trace.TraceContext(new_tid)],
                       2.0, 0.001)
    # both generations are stitched
    assert trace.collect_trace(span_sink, old_tid)["n_spans"] > 0
    assert trace.collect_trace(span_sink, new_tid)["n_spans"] == 1
    # total on-disk span data stays bounded (~2 generations of the cap)
    total = sum(os.path.getsize(p)
                for p in (trace.span_log_path(span_sink),
                          trace.span_log_path(span_sink) + ".1")
                if os.path.exists(p))
    assert total < 3 * 1024


def test_span_context_manager_noops_without_sink():
    trace.configure(None)
    with trace.span("x", service="s"):  # no sink, no ctx: pure no-op
        pass
    with trace.use(trace.TraceContext("t1")):
        with trace.span("y", service="s"):
            pass  # sink unconfigured: still a no-op, no crash


# --- HTTP edge (JsonHttpServer) ---

def test_http_edge_mints_echoes_and_honors_trace_ids(span_sink):
    from rafiki_tpu.utils.service import JsonHttpServer

    seen = []

    def handler(params, body, ctx):
        seen.append(trace.current())
        return 200, {"ok": True}

    server = JsonHttpServer([("GET", "/thing/<id>", handler)],
                            host="127.0.0.1", name="edge-svc").start()
    base = f"http://127.0.0.1:{server.port}"
    try:
        # Fresh mint: response echoes the new id, handler saw the ctx.
        r = requests.get(base + "/thing/a", timeout=10)
        tid = r.headers["X-Trace-Id"].split("-")[0]
        assert len(tid) == 32
        assert seen[-1] is not None and seen[-1].trace_id == tid
        # Incoming id honored end to end.
        r = requests.get(base + "/thing/b", timeout=10,
                         headers={"X-Trace-Id": "abc" + "0" * 29})
        assert r.headers["X-Trace-Id"].startswith("abc" + "0" * 29)
        # The edge span landed in the sink, labeled by route PATTERN.
        out = trace.collect_trace(span_sink, tid)
        assert out["n_spans"] == 1
        assert out["spans"][0]["name"] == "http GET /thing/<id>"
        assert out["spans"][0]["service"] == "edge-svc"
    finally:
        server.stop()


# --- Through the serving path (predictor frontend + worker shape) ---

class _EchoWorker:
    """Bus-level stand-in mirroring InferenceWorker's frame handling."""

    def __init__(self, bus, worker_id="w1", job_id="job"):
        self.cache = Cache(bus)
        self.worker_id = worker_id
        self.stop_flag = threading.Event()
        self.trace_ids = []
        self.cache.register_worker(job_id, worker_id,
                                   info={"trial_id": "t1"})
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def _loop(self):
        while not self.stop_flag.is_set():
            items = self.cache.pop_queries(self.worker_id, timeout=0.1)
            ctxs = trace.extract_frames(items)
            self.trace_ids.extend(c.trace_id for c in ctxs)
            for it in items:
                self.cache.send_prediction_batch(
                    it["batch_id"], self.worker_id,
                    [[float(q), 0.0] for q in it["queries"]])

    def stop(self):
        self.stop_flag.set()
        self._thread.join(timeout=5)


def test_predict_trace_visible_at_edge_envelope_and_spans(span_sink):
    """The acceptance shape: one /predict through the micro-batcher
    yields ONE trace id at the HTTP edge, inside the bus envelope, and
    in the span log (edge + scatter + gather spans)."""
    from rafiki_tpu.predictor.app import PredictorService

    bus = MemoryBus()
    worker = _EchoWorker(bus)
    svc = PredictorService("tsvc", "job", meta=None, bus=bus,
                           host="127.0.0.1")
    svc.predictor.worker_wait_timeout = 5.0
    svc.predictor.gather_timeout = 5.0
    svc.batcher.start()
    svc._http.start()
    try:
        r = requests.post(f"http://127.0.0.1:{svc.port}/predict",
                          json={"queries": [1, 2]}, timeout=30)
        assert r.status_code == 200
        tid = r.headers["X-Trace-Id"].split("-")[0]
        deadline = time.time() + 5
        while time.time() < deadline and tid not in worker.trace_ids:
            time.sleep(0.05)
        assert tid in worker.trace_ids, "envelope never reached worker"
        # gather span is recorded after the response is sliced out;
        # give the gather thread a beat.
        for _ in range(50):
            out = trace.collect_trace(span_sink, tid)
            if out["n_spans"] >= 3:
                break
            time.sleep(0.05)
        names = {s["name"] for s in out["spans"]}
        assert "http POST /predict" in names
        assert "predictor.scatter" in names
        assert "predictor.gather" in names
    finally:
        svc._http.stop()
        svc.batcher.stop()
        worker.stop()


def test_inference_worker_records_predict_span(span_sink):
    """The real InferenceWorker's dispatch/complete path pops the
    envelope and records the worker span."""
    from rafiki_tpu.worker.inference import InferenceWorker

    bus = MemoryBus()
    worker = InferenceWorker("wsvc", "job", "t1", meta=None, params=None,
                            bus=bus)

    class _Model:
        def predict_submit(self, queries):
            return lambda: [[float(q)] for q in queries]

    worker._model = _Model()
    ctx = trace.TraceContext("ab" * 16)
    items = [{"batch_id": "b1", "queries": [1, 2],
              trace.ENVELOPE_KEY: trace.inject([ctx])}]
    handle = worker._dispatch_batch(items)
    worker._complete_batch(*handle)
    out = trace.collect_trace(span_sink, "ab" * 16)
    assert out["n_spans"] == 1
    span = out["spans"][0]
    assert span["name"] == "worker.predict"
    assert span["service"] == "wsvc"
    assert span["parent_id"] == ctx.span_id
    assert span["attrs"]["trial_id"] == "t1"
    # the reply actually went out
    reply = bus.pop("r:b1", timeout=2.0)
    assert reply["predictions"] == [[1.0], [2.0]]


# --- Admin stitching over REST ---

def test_admin_trace_route_and_metrics(tmp_path):
    """GET /trace/<id> on admin stitches the platform's span log; GET
    /metrics serves the registry (the admin-frontend acceptance leg)."""
    from rafiki_tpu.platform import LocalPlatform

    platform = LocalPlatform(workdir=str(tmp_path / "plat"), http=True,
                             supervise_interval=0)
    try:
        tid = "11" * 16
        ctx = trace.TraceContext(tid)
        trace.record_event("http POST /predict", "predictor-x", [ctx],
                           time.time(), 0.02, child=False)
        trace.record_event("worker.predict", "w1", [ctx],
                           time.time() + 0.001, 0.01)
        base = f"http://127.0.0.1:{platform.app.port}"
        tok = requests.post(base + "/tokens", json={
            "email": "superadmin@rafiki", "password": "rafiki"},
            timeout=10).json()["token"]
        hdr = {"Authorization": f"Bearer {tok}"}
        out = requests.get(f"{base}/trace/{tid}", headers=hdr,
                           timeout=10).json()
        assert out["trace_id"] == tid and out["n_spans"] == 2
        assert out["spans"][0]["name"] == "http POST /predict"
        # unauthenticated -> 401 like every other admin read
        assert requests.get(f"{base}/trace/{tid}",
                            timeout=10).status_code == 401
        # /metrics needs no auth (scrape endpoint) and is valid text
        m = requests.get(base + "/metrics", timeout=10)
        assert m.status_code == 200 and "# TYPE" in m.text
        assert "rafiki_tpu_http_request_seconds" in m.text
        # /status surfaces the mfu map (empty here, but present)
        status = requests.get(base + "/status", headers=hdr,
                              timeout=10).json()
        assert "mfu" in status
        # /trial_phases feeds the dashboard's phase-breakdown panel:
        # every phase present (zero-count trial phases here — no
        # resident trials),
        # in PHASES' order (parents before their children, which is the
        # order the panel's rows come out in), and authenticated like
        # every other admin read.
        from rafiki_tpu.observe import phases

        tp = requests.get(base + "/trial_phases", headers=hdr,
                          timeout=10).json()
        assert list(tp["phases"]) == list(phases.PHASES)
        assert set(tp["phases"]) == {
            "trial", "propose", "open", "init", "train", "load",
            "stage", "step_setup", "step_dispatch", "step_wait", "eval",
            "dump", "feedback", "handover", "persist",
            # set-up, once a process or job, and jax's compile stages
            "backend_init", "platform_start", "model_upload",
            "job_submit", "worker_start",
            "jit_trace", "jit_lower", "jit_compile", "jit_cache_load"}
        assert set(tp["caches"]) == {"dataset", "stage", "step"}
        assert set(tp["dump_leaves"]) == {"device", "host"}
        assert "resident" in tp and "enabled" in tp
        assert requests.get(base + "/trial_phases",
                            timeout=10).status_code == 401
    finally:
        platform.shutdown()
        trace.configure(None)


# --- Advisor RPC trace propagation (ISSUE-3 satellite) ---

def test_advisor_rpc_carries_trace_context(span_sink):
    """RemoteAdvisor injects the caller's context into proposal and
    feedback frames; the AdvisorWorker records advisor.<op> spans under
    the same trace id. Old frames (no envelope) stay span-free."""
    from rafiki_tpu.advisor import RandomAdvisor
    from rafiki_tpu.advisor.worker import AdvisorWorker, RemoteAdvisor
    from rafiki_tpu.model.knobs import IntegerKnob

    bus = MemoryBus()
    advisor = RandomAdvisor({"x": IntegerKnob(1, 9)})
    worker = AdvisorWorker(advisor, bus, "sub1").start()
    remote = RemoteAdvisor(bus, "sub1", timeout=10.0)
    try:
        tid = "ad" * 16
        with trace.use(trace.TraceContext(tid)):
            prop = remote.propose()
            assert prop is not None
            remote.feedback(prop, 0.5)
        # feedback is fire-and-forget; give the worker a beat
        deadline = time.time() + 5
        names = set()
        while time.time() < deadline and len(names) < 2:
            out = trace.collect_trace(span_sink, tid)
            names = {s["name"] for s in out["spans"]}
            time.sleep(0.05)
        assert names == {"advisor.propose", "advisor.feedback"}, names
        for s in trace.collect_trace(span_sink, tid)["spans"]:
            assert s["service"].startswith("advisor-")
        # Untraced caller -> old-shape frames -> no spans, RPC still fine
        assert remote.propose() is not None
        assert trace.collect_trace(span_sink, "ee" * 16)["n_spans"] == 0
    finally:
        worker.stop()


# --- Cross-process tail verdicts (ISSUE r19 satellite) -----------------

def test_envelope_carries_tail_marks_and_old_consumers_survive():
    edge = trace.TraceContext("aa" * 16, tail=True)
    plain = trace.TraceContext("bb" * 16)
    env = trace.inject([plain, edge])
    assert env["ids"] == [["bb" * 16, plain.span_id],
                          ["aa" * 16, edge.span_id]]
    assert env["tail"] == [1]
    out = trace.extract({"_trace": dict(env)})
    assert [c.tail for c in out] == [False, True]
    # an old consumer reading only "ids" loses nothing: the pair shape
    # is unchanged, the extra key is additive
    legacy = [(tid, sid) for tid, sid in env["ids"]]
    assert len(legacy) == 2
    # malformed tail marks degrade to untailed, never to no-trace
    out = trace.extract({"_trace": {"ids": [["cc" * 16, "d" * 16]],
                                    "tail": ["bogus", 7]}})
    assert len(out) == 1 and not out[0].tail


def test_remote_worker_honors_edge_verdict(span_sink, monkeypatch):
    """The orphan-rate satellite: a subprocess worker's spans for a
    tail-pending trace it did NOT mint hold until the edge's verdict
    sidecar line says kept/dropped — a dropped trace's worker spans no
    longer survive as orphans."""
    monkeypatch.setenv(trace.TRACE_TAIL_SAMPLE_ENV, "0")
    trace.reset_tail_for_tests()
    try:
        from rafiki_tpu.observe.metrics import registry as _registry

        c0 = _registry().find("rafiki_tpu_trace_tail_total")
        base_dropped = (c0.value(verdict="remote_dropped")
                        if c0 is not None else 0.0)
        # Worker side: contexts arrive via the envelope with the tail
        # mark; their ids are unknown to this process's pending buffer
        # (exactly the subprocess case).
        dropped_tid, kept_tid = "ab" * 16, "cd" * 16
        for tid in (dropped_tid, kept_tid):
            [ctx] = trace.extract(
                {"_trace": {"ids": [[tid, "e" * 16]], "tail": [0]}})
            trace.record_event("worker.predict", "w1", [ctx],
                               time.time(), 0.002)
        # neither trace's spans hit the store yet (held)
        for tid in (dropped_tid, kept_tid):
            assert trace.collect_trace(span_sink, tid)["n_spans"] == 0
        # the edge (another process) writes its verdicts
        trace._write_verdict(dropped_tid, "dropped")
        trace._write_verdict(kept_tid, "kept")
        trace.flush_remote_tail()
        assert trace.collect_trace(span_sink,
                                   dropped_tid)["n_spans"] == 0
        assert trace.collect_trace(span_sink,
                                   kept_tid)["n_spans"] == 1
        c = _registry().find("rafiki_tpu_trace_tail_total")
        assert c.value(verdict="remote_dropped") == base_dropped + 1
        # a STRAGGLER span arriving after the known drop verdict is
        # suppressed immediately (no re-hold)
        [late] = trace.extract(
            {"_trace": {"ids": [[dropped_tid, "f" * 16]],
                        "tail": [0]}})
        trace.record_event("worker.late", "w1", [late], time.time(),
                           0.001)
        trace.flush_remote_tail()
        assert trace.collect_trace(span_sink,
                                   dropped_tid)["n_spans"] == 0
    finally:
        trace.reset_tail_for_tests()


def test_remote_hold_expires_to_retain_on_doubt(span_sink,
                                                monkeypatch):
    monkeypatch.setenv(trace.TRACE_TAIL_SAMPLE_ENV, "0")
    monkeypatch.setattr(trace, "_REMOTE_HOLD_S", 0.05)
    trace.reset_tail_for_tests()
    try:
        tid = "ef" * 16
        [ctx] = trace.extract(
            {"_trace": {"ids": [[tid, "a" * 16]], "tail": [0]}})
        trace.record_event("worker.predict", "w1", [ctx],
                           time.time(), 0.002)
        assert trace.collect_trace(span_sink, tid)["n_spans"] == 0
        time.sleep(0.1)
        # the sweep rides the next span write; no verdict ever came
        trace.record_event("other", "w1",
                           [trace.TraceContext("ba" * 16)],
                           time.time(), 0.001)
        assert trace.collect_trace(span_sink, tid)["n_spans"] == 1
    finally:
        trace.reset_tail_for_tests()


def test_remote_hold_caps_spans_per_trace(span_sink, monkeypatch):
    """The remote hold is bounded per TRACE, not just per trace count:
    one dense remote trace hits the same span cap as the local pending
    buffer and overflows to disk (retain-on-doubt), never growing an
    unbounded in-memory list for the hold window."""
    monkeypatch.setenv(trace.TRACE_TAIL_SAMPLE_ENV, "0")
    monkeypatch.setattr(trace, "_PENDING_MAX_SPANS", 5)
    trace.reset_tail_for_tests()
    try:
        tid = "fe" * 16
        for i in range(8):
            [ctx] = trace.extract(
                {"_trace": {"ids": [[tid, "a" * 16]], "tail": [0]}})
            trace.record_event(f"worker.s{i}", "w1", [ctx],
                               time.time(), 0.001)
        # spans 1..5 buffered; the 6th overflowed all six to disk;
        # 7..8 re-hold (bounded again) awaiting a verdict
        assert trace.collect_trace(span_sink, tid)["n_spans"] == 6
        with trace._tail_lock:
            held = trace._remote_pending.get(tid)
            assert held is not None and len(held[1]) == 2
    finally:
        trace.reset_tail_for_tests()


def test_edge_complete_writes_verdict_sidecar(span_sink, monkeypatch):
    monkeypatch.setenv(trace.TRACE_TAIL_SAMPLE_ENV, "0")
    trace.reset_tail_for_tests()
    try:
        dropped = trace.start_trace(None)
        trace.complete(dropped, 0.001)           # fast/ok -> dropped
        kept = trace.start_trace(None)
        trace.complete(kept, 0.001, error=True)  # error -> kept
        lines = [json.loads(x) for x in
                 open(os.path.join(span_sink,
                                   trace.VERDICT_FILE))]
        verdicts = {r["t"]: r["v"] for r in lines}
        assert verdicts[dropped.trace_id] == "dropped"
        assert verdicts[kept.trace_id] == "kept"
    finally:
        trace.reset_tail_for_tests()


# --- Segment compaction (ISSUE r19 satellite) --------------------------

def test_compaction_rewrites_frozen_segment_and_marks_index(
        span_sink, monkeypatch):
    path = os.path.join(span_sink, trace.SPAN_FILE)
    for tid in ("aa" * 16, "bb" * 16, "cc" * 16):
        trace.record_event("worker.predict", "w",
                           [trace.TraceContext(tid)], time.time(),
                           0.001)
    os.replace(path, path + ".1")  # freeze (as a roll would)
    trace._build_index(path + ".1")
    assert not trace.segment_compacted(path + ".1")
    trace._write_verdict("bb" * 16, "dropped")
    [out] = trace.compact_segments(span_sink)
    assert (out["removed"], out["kept"]) == (1, 2)
    assert trace.segment_compacted(path + ".1")
    content = open(path + ".1").read()
    assert "bb" * 16 not in content and "aa" * 16 in content
    # diagnostics report the compacted marker; the surviving trace
    # still stitches via the rebuilt index
    res = trace.collect_trace(span_sink, "aa" * 16)
    assert res["n_spans"] == 1
    assert [d.get("compacted") for d in res["segments"]
            if d["segment"].endswith(".1")] == [True]
    # a second pass skips the already-compacted segment
    assert trace.compact_segments(span_sink) == []
    from rafiki_tpu.observe.metrics import registry as _registry

    c = _registry().find("rafiki_tpu_trace_store_total")
    assert c.value(event="compact") >= 1
    # a later KEPT verdict for the same id protects it from erasure
    trace._write_verdict("aa" * 16, "dropped")
    trace._write_verdict("aa" * 16, "kept")
    assert "aa" * 16 not in trace._dropped_verdict_ids()


def test_stale_index_is_detected_and_rebuilt(span_sink):
    """A reader racing compaction (segment already replaced, index not
    yet) must not seek the old generation's offsets into the new file:
    the index records its segment's byte size, a mismatch loads as
    missing, and the lookup rebuilds from the file it actually has."""
    path = os.path.join(span_sink, trace.SPAN_FILE)
    for tid in ("aa" * 16, "bb" * 16, "cc" * 16):
        trace.record_event("worker.predict", "w",
                           [trace.TraceContext(tid)], time.time(),
                           0.001)
    os.replace(path, path + ".1")
    trace._build_index(path + ".1")
    # simulate the compaction window: rewrite the segment (first line
    # removed, every later offset shifted) leaving the OLD index
    with open(path + ".1", "rb") as f:
        lines = f.readlines()
    with open(path + ".1.tmp", "wb") as f:
        f.write(b"".join(lines[1:]))
    os.replace(path + ".1.tmp", path + ".1")
    assert trace._load_index_data(path + ".1") is None  # stale by size
    res = trace.collect_trace(span_sink, "cc" * 16)
    assert res["n_spans"] == 1
    [d] = [d for d in res["segments"] if d["segment"].endswith(".1")]
    assert d["mode"] == "index_rebuilt"


def test_roll_triggers_compaction_of_older_segment(span_sink,
                                                   monkeypatch):
    """The idle-time trigger: with tail sampling armed, each roll
    compacts one OLDER frozen segment — never the just-rolled .1
    (verdicts may still be pending) and never .2 (a co-writing
    process's append handle may still chase the renames into it; an
    inode-swapping rewrite under that handle would lose its spans)."""
    monkeypatch.setenv(trace.TRACE_TAIL_SAMPLE_ENV, "0.5")
    monkeypatch.setenv(trace.TRACE_MAX_MB_ENV, "0.0005")  # ~500 bytes
    trace.reset_tail_for_tests()
    try:
        path = os.path.join(span_sink, trace.SPAN_FILE)
        # two frozen generations; the ORPHAN sits in the older one
        # (.2, about to shift to .3 — the compaction candidate)
        trace.record_event("orphan", "w",
                           [trace.TraceContext("dd" * 16)],
                           time.time(), 0.001)
        os.replace(path, path + ".2")
        trace.configure(span_sink)  # reopen: the handle chased the move
        trace._build_index(path + ".2")
        trace.record_event("recent", "w",
                           [trace.TraceContext("cc" * 16)],
                           time.time(), 0.001)
        os.replace(path, path + ".1")
        trace.configure(span_sink)
        trace._build_index(path + ".1")
        trace._write_verdict("dd" * 16, "dropped")
        # now overflow the active file so a real roll fires:
        # .2 -> .3, .1 -> .2, active -> .1
        big_attrs = {"pad": "x" * 200}
        for i in range(5):
            trace.record_event("spanny", "w",
                               [trace.TraceContext("ee" * 16)],
                               time.time(), 0.001, attrs=big_attrs)
        deadline = time.time() + 5
        while not os.path.exists(path + ".3") and \
                time.time() < deadline:
            trace.record_event("spanny", "w",
                               [trace.TraceContext("ee" * 16)],
                               time.time(), 0.001, attrs=big_attrs)
        assert os.path.exists(path + ".3")
        # the roll compacted the shifted .3: the orphan is gone —
        # while the two newest generations stayed untouched
        assert trace.segment_compacted(path + ".3")
        assert "dd" * 16 not in open(path + ".3").read()
        assert not trace.segment_compacted(path + ".2")
    finally:
        trace.reset_tail_for_tests()
