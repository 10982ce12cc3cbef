"""Serving attribution ledger (ISSUE r17): per-bin / per-tenant
request accounting, the ``_tenant`` envelope carry, series lifecycle
(zero series when off, dropped on stop), and the on-demand device
profiling control frame.
"""

import os
import threading
import time

import pytest
import requests

from rafiki_tpu.bus import MemoryBus
from rafiki_tpu.cache import Cache
from rafiki_tpu.observe import attribution as attr
from rafiki_tpu.observe import trace
from rafiki_tpu.observe.metrics import registry

FAMILIES = (
    "rafiki_tpu_serving_bin_queries_total",
    "rafiki_tpu_serving_bin_queue_seconds_total",
    "rafiki_tpu_serving_bin_rejected_total",
    "rafiki_tpu_serving_bin_requests_total",
    "rafiki_tpu_serving_bin_compute_seconds_total",
    "rafiki_tpu_serving_bin_device_seconds",
    "rafiki_tpu_serving_tenant_requests_total",
    "rafiki_tpu_serving_tenant_device_seconds_total",
    "rafiki_tpu_serving_tenant_request_seconds",
)


def _samples(name):
    m = registry().find(name)
    if m is None:
        return []
    if hasattr(m, "samples"):
        return m.samples()
    with m._lock:  # histogram: series keys stand in for samples
        return [(dict(k), None) for k in m._series]


def _wipe():
    """Remove every ledger series from the process registry (tests
    share one registry; each test starts from a clean slate)."""
    for name in FAMILIES:
        m = registry().find(name)
        if m is not None:
            m.remove()


@pytest.fixture()
def ledger(monkeypatch):
    monkeypatch.setenv(attr.ATTRIBUTION_ENV, "1")
    attr.reset_for_tests()
    _wipe()
    yield attr
    _wipe()
    attr.reset_for_tests()


@pytest.fixture()
def ledger_off(monkeypatch):
    monkeypatch.delenv(attr.ATTRIBUTION_ENV, raising=False)
    attr.reset_for_tests()
    yield attr
    attr.reset_for_tests()


# --- Unit: keys, envelope, gating ------------------------------------

def test_tenant_key_is_bounded_hash():
    k = attr.tenant_key("client-api-key-SECRET")
    assert k and len(k) == 12 and "SECRET" not in k
    assert attr.tenant_key("client-api-key-SECRET") == k  # stable
    assert attr.tenant_key("") is None and attr.tenant_key(None) is None


def test_tenant_envelope_roundtrip_cap_and_malformed():
    env = attr.inject_tenants([("a", 3), ("b", 1), ("a", 2)])
    assert env == [["a", 5], ["b", 1]]  # merged, largest first
    frame = {"batch_id": "x", attr.ENVELOPE_KEY: env}
    assert attr.extract_tenants(frame) == [("a", 5), ("b", 1)]
    assert attr.ENVELOPE_KEY not in frame  # popped
    # cap: only the top MAX_ENVELOPE_TENANTS ride
    many = [(f"t{i:02d}", i + 1) for i in range(20)]
    env = attr.inject_tenants(many)
    assert len(env) == attr.MAX_ENVELOPE_TENANTS
    assert env[0] == ["t19", 20]
    # malformed / absent / old frames degrade to []
    assert attr.inject_tenants(None) is None
    assert attr.inject_tenants([("", 3), ("x", 0)]) is None
    assert attr.extract_tenants({"batch_id": "x"}) == []
    assert attr.extract_tenants({attr.ENVELOPE_KEY: "bogus"}) == []
    assert attr.extract_tenants({attr.ENVELOPE_KEY: [["a"]]}) == []
    merged = attr.extract_frames_tenants([
        {attr.ENVELOPE_KEY: [["a", 2]]},
        {attr.ENVELOPE_KEY: [["a", 1], ["b", 4]]}, {"old": 1}])
    assert merged == [("b", 4), ("a", 3)]


def test_disabled_ledger_is_inert(ledger_off):
    assert attr._families() is None
    before = {n: len(_samples(n)) for n in FAMILIES}
    attr.open_owner()
    attr.account_admitted("deadbeef", 3)
    attr.account_rejected("svc", "queue_full")
    attr.account_scatter("svc", {"t1": 4}, queue_wait_s=0.5)
    attr.account_burst("job", "t1", 4, 0.01, bucket=8, dtype="f32")
    attr.account_tenant_device([("x", 2)], 0.01, 4)
    attr.close_service("svc")
    attr.close_worker("job", "t1")
    assert {n: len(_samples(n)) for n in FAMILIES} == before


# --- Unit: accounting + lifecycle ------------------------------------

def test_ledger_accounts_and_lifecycle(ledger):
    attr.open_owner()  # the frontend
    attr.open_owner()  # the worker
    t = attr.tenant_key("alice")
    attr.account_admitted(t)
    attr.account_admitted(t)
    attr.account_scatter("svcA", {"t1": 4, "t2": 4}, queue_wait_s=0.25)
    attr.account_rejected("svcA", "client_share")
    attr.account_burst("job12345", "t1", 4, 0.02, bucket=8,
                       dtype="float32", quant="int8", mode="stacked")
    attr.account_tenant_device([(t, 2)], 0.02, 4)

    q = registry().find("rafiki_tpu_serving_bin_queries_total")
    assert q.value(service="svcA", bin="t1") == 4
    assert q.value(service="svcA", bin="t2") == 4
    w = registry().find("rafiki_tpu_serving_bin_queue_seconds_total")
    assert w.value(service="svcA", bin="t1") == pytest.approx(0.25)
    r = registry().find("rafiki_tpu_serving_tenant_requests_total")
    assert r.value(tenant=t) == 2
    b = registry().find("rafiki_tpu_serving_bin_requests_total")
    assert b.value(job="job12345", bin="t1") == 4
    h = registry().find("rafiki_tpu_serving_bin_device_seconds")
    assert h.count(job="job12345", bin="t1", bucket="8",
                   dtype="float32", quant="int8", mode="stacked") == 1
    d = registry().find(
        "rafiki_tpu_serving_tenant_device_seconds_total")
    assert d.value(tenant=t) == pytest.approx(0.02 * 2 / 4)

    # Frontend stop drops ITS service-labeled series only.
    attr.close_service("svcA")
    assert q.value(service="svcA", bin="t1") == 0
    assert b.value(job="job12345", bin="t1") == 4  # worker side intact
    assert r.value(tenant=t) == 2  # one owner still open
    # Last owner out clears the process-global tenant rollup.
    attr.close_worker("job12345", "t1")
    assert b.value(job="job12345", bin="t1") == 0
    assert _samples("rafiki_tpu_serving_tenant_requests_total") == []
    assert _samples(
        "rafiki_tpu_serving_tenant_device_seconds_total") == []


def test_restack_drops_old_bin_series_without_owner_close(ledger):
    """The promote-path restack swaps a live worker's bin in place:
    the OLD bin's (job, bin) series must drop (promotion churn can
    never grow the scrape), but the worker stays an owner — the
    tenant rollup must survive."""
    attr.open_owner()
    t = attr.tenant_key("carol")
    attr.account_admitted(t)
    attr.account_burst("jobP", "tOLD", 4, 0.01)
    attr.drop_worker_bin("jobP", "tOLD")
    b = registry().find("rafiki_tpu_serving_bin_requests_total")
    assert all(labels.get("bin") != "tOLD" for labels, _ in b.samples())
    # owner refcount untouched: the tenant rollup is still live
    r = registry().find("rafiki_tpu_serving_tenant_requests_total")
    assert r.value(tenant=t) == 1
    attr.close_worker("jobP", "tNEW")
    assert _samples("rafiki_tpu_serving_tenant_requests_total") == []


def test_close_worker_matches_truncated_labels(ledger):
    """account_burst truncates job/bin labels to 12 chars (bounded
    cardinality); close_worker must truncate identically or the
    removal never matches the series (regression: real ids are 32-hex
    uuids)."""
    job = "a" * 32
    bin_id = "b" * 32 + "," + "c" * 32  # a packed multi-member bin
    attr.open_owner()
    attr.account_burst(job, bin_id, 4, 0.01)
    b = registry().find("rafiki_tpu_serving_bin_requests_total")
    assert b.value(job=job[:12], bin=bin_id[:12]) == 4
    attr.close_worker(job, bin_id)
    assert _samples("rafiki_tpu_serving_bin_requests_total") == []
    assert _samples(
        "rafiki_tpu_serving_bin_compute_seconds_total") == []


def test_tenant_lru_cap_evicts_series(ledger):
    attr.open_owner()
    try:
        for i in range(attr.TENANT_CAP + 10):
            attr.account_admitted(f"tenant{i:03d}")
        rollup = _samples("rafiki_tpu_serving_tenant_requests_total")
        assert len(rollup) == attr.TENANT_CAP
        tenants = {labels["tenant"] for labels, _ in rollup}
        assert "tenant000" not in tenants  # oldest evicted
        assert f"tenant{attr.TENANT_CAP + 9:03d}" in tenants
        # touching keeps a tenant alive
        attr.account_admitted(f"tenant{attr.TENANT_CAP + 9:03d}")
        assert len(_samples(
            "rafiki_tpu_serving_tenant_requests_total")) == attr.TENANT_CAP
    finally:
        attr.close_owner()


# --- Worker side: envelope -> (job, bin) + tenant device time ---------

def test_worker_burst_accounts_bin_and_tenants(ledger):
    from rafiki_tpu.worker.inference import InferenceWorker

    bus = MemoryBus()
    worker = InferenceWorker("wsvc", "jobXYZ", "t1", meta=None,
                             params=None, bus=bus)

    class _Model:
        def predict_submit(self, queries):
            return lambda: [[float(q), 0.0] for q in queries]

    worker._model = _Model()
    t = attr.tenant_key("bob")
    items = [{"batch_id": "b1", "queries": [1, 2, 3],
              attr.ENVELOPE_KEY: [[t, 3]]}]
    handle = worker._dispatch_batch(items)
    worker._complete_batch(*handle)
    b = registry().find("rafiki_tpu_serving_bin_requests_total")
    assert b.value(job="jobXYZ", bin="t1") == 3
    c = registry().find("rafiki_tpu_serving_bin_compute_seconds_total")
    assert c.value(job="jobXYZ", bin="t1") > 0
    d = registry().find(
        "rafiki_tpu_serving_tenant_device_seconds_total")
    assert d.value(tenant=t) > 0
    h = registry().find("rafiki_tpu_serving_bin_device_seconds")
    assert h.count(job="jobXYZ", bin="t1", bucket="-", dtype="-",
                   quant="-", mode="single") == 1
    # the reply still went out, untouched by the envelope pop
    reply = bus.pop("r:b1", timeout=2.0)
    assert len(reply["predictions"]) == 3


# --- Frontend e2e: header -> tenant hash -> envelope -> series --------

class _LedgerEchoWorker:
    """Bus-level worker recording the tenant envelopes it receives."""

    def __init__(self, bus, worker_id="w1", job_id="job",
                 trial_id="t1", score=None):
        self.cache = Cache(bus)
        self.worker_id = worker_id
        self.stop_flag = threading.Event()
        self.tenants = []
        info = {"trial_id": trial_id}
        if score is not None:
            info["score"] = score
        self.cache.register_worker(job_id, worker_id, info=info)
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def _loop(self):
        while not self.stop_flag.is_set():
            items = self.cache.pop_queries(self.worker_id, timeout=0.1)
            self.tenants.extend(attr.extract_frames_tenants(items))
            for it in items:
                if "queries" not in it:
                    continue
                self.cache.send_prediction_batch(
                    it["batch_id"], self.worker_id,
                    [[float(q), 0.0] for q in it["queries"]],
                    shard=it.get("shard"))

    def stop(self):
        self.stop_flag.set()
        self._thread.join(timeout=5)


def test_frontend_attribution_e2e_and_stop_drops_series(ledger):
    from rafiki_tpu.predictor.app import PredictorService

    bus = MemoryBus()
    worker = _LedgerEchoWorker(bus)
    svc = PredictorService("asvc", "job", meta=None, bus=bus,
                           host="127.0.0.1", client_header="X-Client")
    svc.predictor.worker_wait_timeout = 5.0
    svc.predictor.gather_timeout = 5.0
    svc.batcher.start()
    svc._http.start()
    try:
        r = requests.post(
            f"http://127.0.0.1:{svc.port}/predict",
            json={"queries": [1, 2]},
            headers={"X-Client": "alice"}, timeout=30)
        assert r.status_code == 200
        t = attr.tenant_key("alice")
        # tenant rollup accounted at admission
        tr = registry().find("rafiki_tpu_serving_tenant_requests_total")
        assert tr.value(tenant=t) == 1
        # per-bin frontend series under THIS frontend's service label
        service = svc.stats.service
        q = registry().find("rafiki_tpu_serving_bin_queries_total")
        assert q.value(service=service, bin="t1") == 2
        qw = registry().find(
            "rafiki_tpu_serving_bin_queue_seconds_total")
        assert qw.value(service=service, bin="t1") > 0
        # the tenant envelope reached the worker's frames
        deadline = time.time() + 5
        while time.time() < deadline and not worker.tenants:
            time.sleep(0.05)
        assert (t, 2) in worker.tenants
        # an anonymous request accounts no tenant but still scatters
        r = requests.post(f"http://127.0.0.1:{svc.port}/predict",
                          json={"queries": [3]}, timeout=30)
        assert r.status_code == 200
        assert q.value(service=service, bin="t1") == 3
        assert tr.value(tenant=t) == 1
        # a malformed body (400) must not inflate the tenant rollup
        r = requests.post(f"http://127.0.0.1:{svc.port}/predict",
                          json={"bogus": 1},
                          headers={"X-Client": "alice"}, timeout=30)
        assert r.status_code == 400
        assert tr.value(tenant=t) == 1
    finally:
        svc._http.stop()
        svc.batcher.stop()
        svc.stats.close()
        svc.predictor.close()
        worker.stop()
    # stop dropped the frontend's series; last owner cleared tenants
    q = registry().find("rafiki_tpu_serving_bin_queries_total")
    assert all(labels.get("service") != service
               for labels, _ in q.samples())
    assert _samples("rafiki_tpu_serving_tenant_requests_total") == []


def test_tiered_escalation_carries_tenant_envelope(ledger):
    """ISSUE r19 satellite (the r17 'under-attributed by design'
    carry): the tiered path's SECOND scatter re-derives the escalated
    subset's tenant mix from the per-query tenant column, so the
    escalation bin's worker receives a ``_tenant`` envelope too —
    before the fix it received none and the escalated queries' device
    time went unattributed."""
    from rafiki_tpu.predictor.predictor import Predictor

    bus = MemoryBus()
    best = _LedgerEchoWorker(bus, worker_id="wbest", trial_id="tbest",
                             score=0.9)
    other = _LedgerEchoWorker(bus, worker_id="wother",
                              trial_id="tother", score=0.5)
    pred = Predictor("job", bus, gather_timeout=5.0,
                     worker_wait_timeout=5.0, tier_threshold=0.5)
    try:
        ta, tb = attr.tenant_key("alice"), attr.tenant_key("bob")
        # echo replies carry NO confidence -> every query escalates;
        # alice owns queries 0-1, bob query 2.
        out = pred.predict([1, 2, 3],
                           tenants=[(ta, 2), (tb, 1)],
                           tenant_rows=[ta, ta, tb])
        assert len(out) == 3 and all(v is not None for v in out)
        deadline = time.time() + 5
        while time.time() < deadline and \
                (not best.tenants or not other.tenants):
            time.sleep(0.05)
        # phase 1 (best bin) carried the whole batch's mix...
        assert (ta, 2) in best.tenants and (tb, 1) in best.tenants
        # ...and the ESCALATION scatter carried the subset's own mix
        assert (ta, 2) in other.tenants and (tb, 1) in other.tenants
        # counter-pinned: the escalation bin's scatter accounted its
        # per-bin queries under the frontend label too
        q = registry().find("rafiki_tpu_serving_bin_queries_total")
        assert q.value(service=pred.service, bin="tbest") == 3
        assert q.value(service=pred.service, bin="tother") == 3
    finally:
        pred.close()
        best.stop()
        other.stop()


def test_zero_series_when_attribution_off_e2e(ledger_off):
    """The acceptance gate at the service level: a full serve with the
    ledger OFF adds not one bin/tenant sample."""
    from rafiki_tpu.predictor.app import PredictorService

    before = {n: len(_samples(n)) for n in FAMILIES}
    bus = MemoryBus()
    worker = _LedgerEchoWorker(bus)
    svc = PredictorService("zsvc", "job", meta=None, bus=bus,
                           host="127.0.0.1", client_header="X-Client")
    svc.predictor.worker_wait_timeout = 5.0
    svc.predictor.gather_timeout = 5.0
    svc.batcher.start()
    svc._http.start()
    try:
        r = requests.post(
            f"http://127.0.0.1:{svc.port}/predict",
            json={"queries": [1, 2]},
            headers={"X-Client": "alice"}, timeout=30)
        assert r.status_code == 200
    finally:
        svc._http.stop()
        svc.batcher.stop()
        svc.stats.close()
        svc.predictor.close()
        worker.stop()
    assert {n: len(_samples(n)) for n in FAMILIES} == before


# --- On-demand device profiling (worker serve loop) -------------------

class _FakeMeta:
    def update_service(self, *a, **k):
        pass

    def update_inference_job_worker(self, *a, **k):
        pass


def test_profile_control_frame_on_live_worker(tmp_path, ledger_off):
    """A ``__profile__`` frame starts a bounded jax.profiler session on
    the live serve loop: the artifact dir fills with a readable
    profile, and serving is undisturbed (every query before, during,
    and after the session is answered) — the r17 acceptance leg at the
    worker level; the admin route is exercised in test_platform."""
    from rafiki_tpu.worker.inference import InferenceWorker

    class _Model:
        def predict_submit(self, queries):
            import jax.numpy as jnp

            x = jnp.ones((8, 8))
            y = (x @ x).sum()  # real device work inside the window
            return lambda: [[float(q), float(y) * 0.0]
                            for q in queries]

    class _Worker(InferenceWorker):
        def _load_model(self):
            return _Model()

    bus = MemoryBus()
    worker = _Worker("psvc", "job", "t1", meta=_FakeMeta(),
                     params=None, bus=bus, batch_timeout=0.1,
                     pipeline=False)
    worker.start()
    cache = Cache(bus)
    out_dir = str(tmp_path / "prof")
    try:
        deadline = time.time() + 30
        while time.time() < deadline and \
                not cache.running_workers("job"):
            time.sleep(0.05)
        assert cache.running_workers("job") == ["psvc"]

        def ask(n, tag):
            bid = cache.send_query_batch_fanout(
                ["psvc"], list(range(n)), batch_id=f"{tag}")
            replies = cache.gather_prediction_batches(bid, 1,
                                                      timeout=10)
            assert replies and len(replies[0]["predictions"]) == n, tag

        ask(4, "before")
        cache.send_profile("psvc", out_dir, duration_s=1.0)
        ask(4, "during1")
        ask(4, "during2")
        time.sleep(1.5)  # session deadline passes; loop stops it
        ask(4, "after")
        # the artifact is a readable profile (TensorBoard layout)
        deadline = time.time() + 15
        files = []
        while time.time() < deadline and not files:
            files = [os.path.join(r, f)
                     for r, _, fs in os.walk(out_dir) for f in fs]
            time.sleep(0.1)
        assert any("profile" in f or f.endswith(".pb") for f in files), \
            files
        # counter-proven: the session started AND stopped, and every
        # request during it was answered (asserted in ask()).
        sessions = registry().find("rafiki_tpu_profile_sessions_total")
        assert sessions is not None
        assert sessions.value(event="start") >= 1
        assert sessions.value(event="stop") >= 1
    finally:
        worker.stop()
