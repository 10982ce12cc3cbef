"""Continuous micro-batching in the serving path (predictor/batcher.py).

Real components, no mocks: a MemoryBus, a worker thread speaking the
cache protocol, the actual PredictorService HTTP frontend. The
invariants under test are the ones concurrency breaks silently:
per-request slicing (no cross-request result bleed), bounded admission
(429 + Retry-After instead of unbounded pileup), and a race-free
replica rotation.
"""

import threading
import time

import pytest
import requests

from rafiki_tpu.bus import MemoryBus
from rafiki_tpu.cache import Cache
from rafiki_tpu.predictor import Backpressure, MicroBatcher, Predictor
from rafiki_tpu.predictor.app import PredictorService


class EchoWorker:
    """Minimal InferenceWorker stand-in: pops query batches off the bus
    and replies ``[value, value + 0.5]`` per query (so a reply is
    attributable to its query). ``delay`` simulates model latency;
    ``trial_id`` sets the replica bin; ``dead=True`` swallows frames (a
    replica that crashed mid-gather); ``echo_shard=False`` mimics a
    pre-shard worker that doesn't echo the shard id."""

    def __init__(self, bus, worker_id="w1", job_id="job", delay=0.0,
                 trial_id="t1", dead=False, echo_shard=True):
        self.cache = Cache(bus)
        self.worker_id = worker_id
        self.delay = delay
        self.dead = dead
        self.echo_shard = echo_shard
        self.stop_flag = threading.Event()
        self.served_batches = 0
        self.served_sizes = []
        self.cache.register_worker(job_id, worker_id,
                                   info={"trial_id": trial_id})
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def _loop(self):
        while not self.stop_flag.is_set():
            items = self.cache.pop_queries(self.worker_id, timeout=0.1)
            for it in items:
                if self.dead:
                    continue
                if self.delay:
                    time.sleep(self.delay)
                self.served_batches += 1
                self.served_sizes.append(len(it["queries"]))
                self.cache.send_prediction_batch(
                    it["batch_id"], self.worker_id,
                    [[float(q), float(q) + 0.5] for q in it["queries"]],
                    shard=it.get("shard") if self.echo_shard else None)

    def stop(self):
        self.stop_flag.set()
        self._thread.join(timeout=5)


@pytest.fixture()
def bus():
    return MemoryBus()


def _predictor(bus, **kw):
    kw.setdefault("worker_wait_timeout", 5.0)
    kw.setdefault("gather_timeout", 5.0)
    return Predictor("job", bus, **kw)


def _service(bus, **kw):
    """PredictorService on a free port, lifecycle managed by the test
    (meta is not exercised: the routes under test never touch it)."""
    svc = PredictorService("svc", "job", meta=None, bus=bus,
                           host="127.0.0.1", **kw)
    svc.predictor.worker_wait_timeout = 5.0
    svc.predictor.gather_timeout = 5.0
    if svc.batcher is not None:
        svc.batcher.start()
    svc._http.start()
    return svc


def _teardown(svc):
    svc._http.stop()
    if svc.batcher is not None:
        svc.batcher.stop()


def test_concurrent_predict_no_cross_request_bleed(bus):
    """N handler threads hammering one PredictorService must each get
    exactly their own slice of the coalesced super-batch."""
    worker = EchoWorker(bus)
    svc = _service(bus)
    url = f"http://127.0.0.1:{svc.port}/predict"
    results = {}
    errors = []

    def client(i):
        try:
            qs = [i * 100 + j for j in range(1 + i % 4)]  # ragged sizes
            r = requests.post(url, json={"queries": qs}, timeout=30)
            r.raise_for_status()
            results[i] = (qs, r.json()["predictions"])
        except Exception as e:  # pragma: no cover - surfaced below
            errors.append(e)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(16)]
    try:
        [t.start() for t in threads]
        [t.join(timeout=30) for t in threads]
        assert not errors, errors
        assert len(results) == 16
        for i, (qs, preds) in results.items():
            assert preds == [[float(q), float(q) + 0.5] for q in qs], \
                f"client {i} got another request's slice"
    finally:
        _teardown(svc)
        worker.stop()


def test_microbatcher_coalesces_concurrent_requests(bus):
    """Concurrent submits within one fill window ride ONE scatter-gather
    super-batch (requests >> batches; worker sees few batch frames)."""
    worker = EchoWorker(bus)
    p = _predictor(bus)
    mb = MicroBatcher(p, fill_window=0.05, max_batch=256,
                      max_inflight=2, queue_cap=1024).start()
    try:
        out = {}
        barrier = threading.Barrier(12)

        def client(i):
            barrier.wait()
            out[i] = mb.submit([i, i + 1000], timeout=15)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(12)]
        [t.start() for t in threads]
        [t.join(timeout=30) for t in threads]
        assert len(out) == 12
        for i in range(12):
            assert out[i] == [[float(i), float(i) + 0.5],
                              [float(i + 1000), float(i + 1000) + 0.5]]
        snap = mb.stats.snapshot()
        assert snap["requests"] == 12
        assert snap["batches"] < 12, "no coalescing happened"
        assert snap["coalescing_factor"] > 1.5
        # the worker saw one frame per super-batch, not one per request
        assert worker.served_batches == snap["batches"]
    finally:
        mb.stop()
        worker.stop()


def test_keep_n_in_flight_overlaps_gather_with_next_scatter(bus):
    """With a slow worker and max_inflight=2, super-batch K+1 must be
    scattered while K's gather is still blocking."""
    worker = EchoWorker(bus, delay=0.15)
    p = _predictor(bus)
    mb = MicroBatcher(p, fill_window=0.01, max_batch=2,
                      max_inflight=2, queue_cap=1024).start()
    try:
        threads = [threading.Thread(
            target=lambda i=i: mb.submit([i], timeout=30))
            for i in range(8)]
        [t.start() for t in threads]
        [t.join(timeout=30) for t in threads]
        snap = mb.stats.snapshot()
        assert snap["inflight_peak"] == 2, snap
    finally:
        mb.stop()
        worker.stop()


def test_backpressure_returns_429_with_retry_after(bus):
    """Sustained overload must bounce with 429 + Retry-After while the
    admission queue stays bounded — not grow latency without bound."""
    worker = EchoWorker(bus, delay=0.25)  # each super-batch is slow
    svc = _service(bus, queue_cap=6, max_inflight=1, fill_window=0.01,
                   max_batch=4)
    url = f"http://127.0.0.1:{svc.port}/predict"
    codes = []
    codes_lock = threading.Lock()

    def client(i):
        r = requests.post(url, json={"queries": [i, i, i]}, timeout=60)
        with codes_lock:
            codes.append((r.status_code, r.headers.get("Retry-After"),
                          r.json()))

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(24)]
    try:
        [t.start() for t in threads]
        [t.join(timeout=60) for t in threads]
        assert len(codes) == 24
        rejected = [c for c in codes if c[0] == 429]
        served = [c for c in codes if c[0] == 200]
        assert rejected, "overload never produced a 429"
        assert served, "every request was rejected"
        for status, retry_after, body in rejected:
            assert retry_after is not None and int(retry_after) >= 1
            assert body["queue_cap"] == 6
        # bounded queue: admitted depth never exceeded the cap
        assert svc.stats.queue_depth_peak <= 6
        assert svc.stats.rejected == len(rejected)
    finally:
        _teardown(svc)
        worker.stop()


def test_microbatch_disabled_restores_direct_path(bus):
    """RAFIKI_TPU_SERVING_MICROBATCH=0: no batcher, requests scatter
    directly."""
    worker = EchoWorker(bus)
    svc = _service(bus, microbatch=False)
    url = f"http://127.0.0.1:{svc.port}"
    try:
        assert svc.batcher is None
        r = requests.post(f"{url}/predict", json={"queries": [1, 2]},
                          timeout=30)
        assert r.status_code == 200
        assert r.json()["predictions"] == [[1.0, 1.5], [2.0, 2.5]]
        stats = requests.get(f"{url}/stats", timeout=10).json()
        assert stats["microbatch"] is False
        assert stats["batches"] == 0 and stats["requests"] == 1
    finally:
        _teardown(svc)
        worker.stop()


def test_microbatch_env_toggle(bus, monkeypatch):
    monkeypatch.delenv("RAFIKI_TPU_SERVING_MICROBATCH", raising=False)
    assert PredictorService("s", "j", None, bus).batcher is not None
    monkeypatch.setenv("RAFIKI_TPU_SERVING_MICROBATCH", "0")
    assert PredictorService("s", "j", None, bus).batcher is None
    # constructor arg beats env
    assert PredictorService("s", "j", None, bus,
                            microbatch=True).batcher is not None
    # knob envs reach the batcher
    monkeypatch.setenv("RAFIKI_TPU_SERVING_MICROBATCH", "1")
    monkeypatch.setenv("RAFIKI_TPU_SERVING_FILL_WINDOW", "0.02")
    monkeypatch.setenv("RAFIKI_TPU_SERVING_QUEUE_CAP", "99")
    b = PredictorService("s", "j", None, bus).batcher
    assert b.fill_window == 0.02 and b.queue_cap == 99


def test_choose_workers_race_free(bus):
    """_rr/_bins are mutated from every handler thread in batcher-off
    mode; concurrent rotation must lose no increments and the per-bin
    replica pick must stay valid throughout."""
    cache = Cache(bus)
    cache.register_worker("job", "wA1", info={"trial_id": "tA"})
    cache.register_worker("job", "wA2", info={"trial_id": "tA"})
    cache.register_worker("job", "wB", info={"trial_id": "tB"})
    p = _predictor(bus)
    bad = []

    def spin():
        for _ in range(50):
            pick = p._choose_workers()
            if len(pick) != 2 or "wB" not in pick or \
                    (("wA1" in pick) == ("wA2" in pick)):
                bad.append(pick)

    threads = [threading.Thread(target=spin) for _ in range(8)]
    [t.start() for t in threads]
    [t.join(timeout=30) for t in threads]
    assert not bad, bad[:3]
    assert p._rr == 8 * 50, "lost round-robin increments under races"


def test_backpressure_exception_fields():
    e = Backpressure(2.0, depth=10, cap=8)
    assert e.retry_after == 2.0 and e.depth == 10 and e.cap == 8
    assert "retry after" in str(e)


def test_stop_fails_waiters_fast_and_rejects_late_submits(bus):
    """stop() must promptly fail BOTH queued requests and already-
    scattered super-batches (never leave a handler blocked until its
    full timeout), and submits after stop must raise immediately."""
    cache = Cache(bus)
    cache.register_worker("job", "w1", info={"trial_id": "t1"})
    # no worker thread: scattered batches never get replies
    p = _predictor(bus, gather_timeout=30.0)
    mb = MicroBatcher(p, fill_window=0.01, max_batch=2, max_inflight=1,
                      queue_cap=64).start()
    outcomes = []

    def client(i):
        t0 = time.time()
        try:
            mb.submit([i], timeout=60)
            outcomes.append(("ok", time.time() - t0))
        except RuntimeError as e:
            outcomes.append((str(e), time.time() - t0))

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(4)]
    [t.start() for t in threads]
    time.sleep(0.5)  # first batch scattered + in flight, rest queued
    mb.stop()
    [t.join(timeout=10) for t in threads]
    assert len(outcomes) == 4
    for msg, elapsed in outcomes:
        assert "micro-batcher stopped" in msg
        assert elapsed < 15, "waiter hung past stop()"
    with pytest.raises(RuntimeError, match="stopped"):
        mb.submit([1], timeout=5)


# --- Replica-sharded scatter (data-parallel serving) ---


def _expected(qs):
    return [[float(q), float(q) + 0.5] for q in qs]


def test_shard_split_across_same_bin_replicas(bus):
    """With 2 same-bin replicas, one batch is sliced across BOTH (each
    sees a strict subset) and reassembles in request order."""
    wa = EchoWorker(bus, "wA1", trial_id="tA")
    wb = EchoWorker(bus, "wA2", trial_id="tA")
    p = _predictor(bus)
    try:
        qs = list(range(10))
        assert p.predict(qs) == _expected(qs)
        assert wa.served_sizes and wb.served_sizes, \
            "a replica idled through a sharded batch"
        assert max(wa.served_sizes) < 10 and max(wb.served_sizes) < 10
        assert sum(wa.served_sizes) + sum(wb.served_sizes) == 10
    finally:
        wa.stop()
        wb.stop()


def test_shard_uneven_replica_counts_and_order(bus):
    """Bins with 3 and 1 replicas: every query still gets exactly one
    vote per bin, results in request order, ensemble across bins."""
    workers = [EchoWorker(bus, f"wA{i}", trial_id="tA")
               for i in range(3)]
    workers.append(EchoWorker(bus, "wB", trial_id="tB"))
    p = _predictor(bus)
    try:
        for n in (1, 2, 7):  # fewer queries than replicas, uneven splits
            qs = list(range(100, 100 + n))
            assert p.predict(qs) == _expected(qs), f"n={n}"
        # the single-replica bin always served full batches
        assert all(s in (1, 2, 7)
                   for s in workers[-1].served_sizes)
    finally:
        [w.stop() for w in workers]


def test_shard_replicas_off_restores_one_pick_per_bin(bus):
    """shard_replicas=False: the pre-shard behavior — one rotating
    replica serves the WHOLE batch."""
    wa = EchoWorker(bus, "wA1", trial_id="tA")
    wb = EchoWorker(bus, "wA2", trial_id="tA")
    p = _predictor(bus, shard_replicas=False)
    try:
        qs = list(range(8))
        assert p.predict(qs) == _expected(qs)
        sizes = wa.served_sizes + wb.served_sizes
        assert sizes == [8], sizes
    finally:
        wa.stop()
        wb.stop()


def test_shard_env_knob(bus, monkeypatch):
    monkeypatch.setenv("RAFIKI_TPU_SERVING_SHARD_REPLICAS", "0")
    assert _predictor(bus).shard_replicas is False
    monkeypatch.setenv("RAFIKI_TPU_SERVING_SHARD_REPLICAS", "1")
    assert _predictor(bus).shard_replicas is True
    # constructor beats env
    assert _predictor(bus, shard_replicas=False).shard_replicas is False


def test_replica_death_mid_gather_resubmits_to_sibling(bus):
    """A dead replica's shard is resubmitted to its sibling at the
    partial-gather deadline: the batch completes with FULL results,
    well before the full gather timeout, and the dead replica is
    latency-penalized out of the next plan."""
    dead = EchoWorker(bus, "wA1", trial_id="tA", dead=True)
    live = EchoWorker(bus, "wA2", trial_id="tA")
    p = _predictor(bus, gather_timeout=4.0)
    try:
        qs = list(range(8))
        t0 = time.monotonic()
        assert p.predict(qs) == _expected(qs)
        elapsed = time.monotonic() - t0
        assert elapsed < 3.5, \
            f"resubmit did not beat the full gather timeout ({elapsed})"
        # the penalized replica gets no slice on the next batch
        live.served_sizes.clear()
        dead_sizes_before = list(dead.served_sizes)
        assert p.predict(qs) == _expected(qs)
        assert live.served_sizes == [8]
        assert dead.served_sizes == dead_sizes_before
    finally:
        dead.stop()
        live.stop()


def test_resubmit_skips_co_missing_siblings(bus):
    """Two replicas dying in the SAME batch must both resubmit to the
    remaining live sibling — never to each other (a co-missing worker
    is no rescue, whatever its historical EWMA says)."""
    dead1 = EchoWorker(bus, "wA1", trial_id="tA", dead=True)
    dead2 = EchoWorker(bus, "wA2", trial_id="tA", dead=True)
    live = EchoWorker(bus, "wA3", trial_id="tA")
    p = _predictor(bus, gather_timeout=4.0)
    qs = list(range(9))
    try:
        t0 = time.monotonic()
        assert p.predict(qs) == _expected(qs)
        assert time.monotonic() - t0 < 3.5
        assert sum(live.served_sizes) == 9, live.served_sizes
    finally:
        dead1.stop()
        dead2.stop()
        live.stop()


def test_penalized_replica_recovers_after_probe_interval(bus):
    """One transient timeout must not starve a replica forever: the
    penalty (whose ~zero slice means its latency EWMA can never
    refresh on its own) expires after one probe interval and the
    recovered replica rejoins the plan."""
    flaky = EchoWorker(bus, "wA1", trial_id="tA", dead=True)
    steady = EchoWorker(bus, "wA2", trial_id="tA")
    p = _predictor(bus, gather_timeout=1.0)
    qs = list(range(8))
    try:
        assert p.predict(qs) == _expected(qs)  # resubmit covered it
        assert "wA1" in p._penalized
        flaky.dead = False  # the replica comes back
        assert p.predict(qs) == _expected(qs)
        assert not flaky.served_sizes, "penalty ignored"
        time.sleep(1.1)  # one probe interval (== gather_timeout)
        assert p.predict(qs) == _expected(qs)
        assert flaky.served_sizes, "recovered replica never rejoined"
        assert "wA1" not in p._penalized
    finally:
        flaky.stop()
        steady.stop()


def test_quarantine_backoff_doubles_per_strike_and_resets(bus):
    """A still-dead replica must stop costing one partial deadline per
    gather timeout: each consecutive missed probe doubles its
    quarantine (capped), and one real reply resets the ladder."""
    from rafiki_tpu.predictor.predictor import _QUARANTINE_MAX_MULT

    p = _predictor(bus, gather_timeout=1.0)
    try:
        p._penalize("w")
        assert p._quarantine_s("w") == 1.0  # first strike: one timeout
        p._penalize("w")
        assert p._quarantine_s("w") == 2.0  # probe missed again
        for _ in range(10):
            p._penalize("w")
        assert p._quarantine_s("w") == float(_QUARANTINE_MAX_MULT)
        p._note_latency("w", 0.01)  # a real reply proves it alive
        assert "w" not in p._strikes
        p._penalize("w")
        assert p._quarantine_s("w") == 1.0  # ladder starts over
        # Strikes outlive penalty expiry on purpose: expiry IS the
        # probe, so only a reply (not mere re-planning) resets them.
        p._penalized.pop("w")
        p._penalize("w")
        assert p._quarantine_s("w") == 2.0
    finally:
        p.close()


def test_partial_bin_degrades_not_stalls(bus):
    """A dead single-replica bin (no sibling to resubmit to) costs only
    its own vote: the other bin's predictions still come back."""
    dead = EchoWorker(bus, "wA", trial_id="tA", dead=True)
    live = EchoWorker(bus, "wB", trial_id="tB")
    p = _predictor(bus, gather_timeout=2.0)
    try:
        qs = [1, 2, 3]
        out = p.predict(qs)
        assert out == _expected(qs), out  # tB's votes survived
    finally:
        dead.stop()
        live.stop()


def test_old_worker_without_shard_echo_still_matches(bus):
    """Pre-shard workers reply without the shard id; the gatherer falls
    back to matching by worker id (one shard per worker per batch)."""
    wa = EchoWorker(bus, "wA1", trial_id="tA", echo_shard=False)
    wb = EchoWorker(bus, "wA2", trial_id="tA", echo_shard=False)
    p = _predictor(bus)
    try:
        qs = list(range(6))
        assert p.predict(qs) == _expected(qs)
    finally:
        wa.stop()
        wb.stop()


def test_latency_weighted_split_prefers_fast_replica(bus):
    """A slow replica's EWMA shrinks its slice: after a few batches the
    fast replica serves most of the queries."""
    slow = EchoWorker(bus, "wA1", trial_id="tA", delay=0.20)
    fast = EchoWorker(bus, "wA2", trial_id="tA")
    p = _predictor(bus)
    try:
        qs = list(range(12))
        for _ in range(4):
            assert p.predict(qs) == _expected(qs)
        # steady state: the fast replica served most of the queries
        # (the slow one may even drop out of the plan entirely)
        assert sum(fast.served_sizes) > sum(slow.served_sizes), \
            (fast.served_sizes, slow.served_sizes)
    finally:
        slow.stop()
        fast.stop()


def test_sharded_scatter_through_microbatcher(bus):
    """End to end: concurrent ragged requests through the micro-batcher
    over 2 same-bin replicas — per-request slices intact (the
    order-preserving reassembly under mixed request sizes)."""
    wa = EchoWorker(bus, "wA1", trial_id="tA")
    wb = EchoWorker(bus, "wA2", trial_id="tA")
    p = _predictor(bus)
    mb = MicroBatcher(p, fill_window=0.05, max_batch=256,
                      max_inflight=2, queue_cap=1024).start()
    try:
        out = {}
        errors = []
        barrier = threading.Barrier(10)

        def client(i):
            try:
                barrier.wait()
                qs = [i * 100 + j for j in range(1 + i % 5)]
                out[i] = (qs, mb.submit(qs, timeout=15))
            except Exception as e:  # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(10)]
        [t.start() for t in threads]
        [t.join(timeout=30) for t in threads]
        assert not errors, errors
        assert len(out) == 10
        for i, (qs, preds) in out.items():
            assert preds == _expected(qs), \
                f"client {i} got another request's slice"
        assert wa.served_sizes and wb.served_sizes
    finally:
        mb.stop()
        wa.stop()
        wb.stop()


# --- Adaptive fill window ---


def test_adaptive_window_converges_trickle_vs_burst(bus):
    """Trickle arrivals (inter-arrival >> ceiling) collapse the window
    to the floor; a tight burst opens it toward the ceiling."""
    worker = EchoWorker(bus)
    p = _predictor(bus)
    mb = MicroBatcher(p, fill_window_min=0.0, fill_window_max=0.05,
                      max_batch=256, max_inflight=2,
                      queue_cap=1024).start()
    try:
        # Trickle: arrivals 0.1s apart, far beyond the 50ms ceiling.
        for i in range(6):
            mb.submit([i], timeout=10)
            time.sleep(0.1)
        assert mb.current_fill_window() <= 0.005, \
            mb.current_fill_window()
        trickle_stats = mb.stats.snapshot()
        assert trickle_stats["fill_window_s"] <= 0.005
        # Burst: concurrent clients hammering — the EWMA tightens and
        # the window opens.
        barrier = threading.Barrier(8)

        def client(i):
            barrier.wait()
            for j in range(6):
                mb.submit([i * 10 + j], timeout=10)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(8)]
        [t.start() for t in threads]
        [t.join(timeout=30) for t in threads]
        assert mb.current_fill_window() > 0.02, \
            mb.current_fill_window()
    finally:
        mb.stop()
        worker.stop()


def test_pinned_window_stays_fixed(bus):
    """fill_window_min == fill_window_max restores the fixed window
    regardless of load."""
    worker = EchoWorker(bus)
    p = _predictor(bus)
    mb = MicroBatcher(p, fill_window_min=0.02, fill_window_max=0.02,
                      max_batch=64, max_inflight=2,
                      queue_cap=256).start()
    try:
        for i in range(3):
            mb.submit([i], timeout=10)
            time.sleep(0.05)
        assert mb.current_fill_window() == 0.02
    finally:
        mb.stop()
        worker.stop()


def test_adaptive_window_env_knobs(bus, monkeypatch):
    monkeypatch.setenv("RAFIKI_TPU_SERVING_FILL_WINDOW_MIN", "0.001")
    monkeypatch.setenv("RAFIKI_TPU_SERVING_FILL_WINDOW_MAX", "0.03")
    b = PredictorService("s", "j", None, bus).batcher
    assert b.fill_window_min == 0.001 and b.fill_window_max == 0.03
    # ceiling defaults to the legacy fixed knob when MAX is unset
    monkeypatch.delenv("RAFIKI_TPU_SERVING_FILL_WINDOW_MAX")
    monkeypatch.setenv("RAFIKI_TPU_SERVING_FILL_WINDOW", "0.02")
    b = PredictorService("s", "j", None, bus).batcher
    assert b.fill_window_max == 0.02


# --- Per-client fairness under backpressure ---


def test_client_share_caps_one_client_not_others(bus):
    """With fairness on, one client key may hold at most its share of
    the admission queue: its overflow bounces with
    reason=client_share while other clients keep being admitted."""
    worker = EchoWorker(bus, delay=0.3)  # slow: the queue backs up
    p = _predictor(bus)
    mb = MicroBatcher(p, fill_window_min=0.0, fill_window_max=0.01,
                      max_batch=4, max_inflight=1, queue_cap=40,
                      client_share=0.25).start()  # 10 queries per key
    results = {"hog_429": 0, "hog_ok": 0, "other_ok": 0,
               "other_429": 0}
    lock = threading.Lock()

    def hog(i):
        try:
            mb.submit([i] * 5, timeout=30, client="hog")
            with lock:
                results["hog_ok"] += 1
        except Backpressure as e:
            assert e.reason == "client_share", e.reason
            with lock:
                results["hog_429"] += 1

    def other(i):
        try:
            mb.submit([i], timeout=30, client=f"c{i}")
            with lock:
                results["other_ok"] += 1
        except Backpressure:
            with lock:
                results["other_429"] += 1

    try:
        hogs = [threading.Thread(target=hog, args=(i,))
                for i in range(8)]
        [t.start() for t in hogs]
        time.sleep(0.15)  # hog floods first
        others = [threading.Thread(target=other, args=(i,))
                  for i in range(6)]
        [t.start() for t in others]
        [t.join(timeout=60) for t in hogs + others]
        assert results["hog_429"] > 0, results
        assert results["other_ok"] == 6, results
        snap = mb.stats.snapshot()
        assert snap["rejected_by_reason"].get("client_share", 0) == \
            results["hog_429"]
    finally:
        mb.stop()
        worker.stop()


def test_client_share_off_by_default(bus):
    """Without a client_share knob the client key is ignored — no
    per-key bound, only the global cap."""
    worker = EchoWorker(bus)
    p = _predictor(bus)
    mb = MicroBatcher(p, fill_window=0.01, max_batch=64,
                      queue_cap=64).start()
    try:
        assert mb.submit([1, 2, 3], timeout=10,
                         client="x") == _expected([1, 2, 3])
        assert mb._client_pending == {}
    finally:
        mb.stop()
        worker.stop()


def test_client_header_knob_reaches_service(bus, monkeypatch):
    monkeypatch.setenv("RAFIKI_TPU_SERVING_CLIENT_HEADER",
                       "X-Client-Id")
    monkeypatch.setenv("RAFIKI_TPU_SERVING_CLIENT_SHARE", "0.5")
    svc = PredictorService("s", "j", None, bus)
    assert svc.client_header == "X-Client-Id"
    assert svc.batcher.client_share == 0.5
    monkeypatch.delenv("RAFIKI_TPU_SERVING_CLIENT_HEADER")
    svc = PredictorService("s", "j", None, bus)
    assert svc.client_header == ""
    assert svc.batcher.client_share == 0.0  # fairness off sans header


def test_empty_and_oversized_requests(bus):
    """Empty submit returns []; a single request larger than the whole
    queue cap is still admitted when the queue is empty (it could never
    be served otherwise)."""
    worker = EchoWorker(bus)
    p = _predictor(bus)
    mb = MicroBatcher(p, fill_window=0.01, max_batch=4, max_inflight=1,
                      queue_cap=4).start()
    try:
        assert mb.submit([], timeout=5) == []
        big = list(range(10))  # > queue_cap AND > max_batch
        out = mb.submit(big, timeout=15)
        assert out == [[float(q), float(q) + 0.5] for q in big]
    finally:
        mb.stop()
        worker.stop()


# --- Straggler detection: latency-relative resubmit deadline (r9) ---

def test_partial_wait_latency_relative_with_full_ewma(bus):
    """With every planned replica measured, the straggler deadline is
    K x the slowest planned EWMA (floored), not the fixed half-timeout
    fraction — a fast fleet resubmits in milliseconds."""
    from rafiki_tpu.predictor import predictor as pred_mod
    from rafiki_tpu.predictor.predictor import _Shard

    p = _predictor(bus, gather_timeout=30.0)
    p._note_latency("wA1", 0.010)
    p._note_latency("wA2", 0.020)
    plan = [_Shard("wA1", "tA", 0, 4), _Shard("wA2", "tA", 4, 4)]
    wait = p._partial_wait(plan)
    assert wait == pytest.approx(
        max(pred_mod._STRAGGLER_K * 0.020, pred_mod._STRAGGLER_MIN))
    assert wait < 1.0  # nowhere near 0.5 * 30s


def test_partial_wait_falls_back_without_full_ewma(bus):
    """Any never-measured replica in the plan means no honest latency
    basis yet: the fixed fraction stays — and it is also the ceiling
    when EWMAs are huge (a penalized replica's inflated value must not
    push the deadline PAST the fixed fraction)."""
    from rafiki_tpu.predictor import predictor as pred_mod
    from rafiki_tpu.predictor.predictor import _Shard

    p = _predictor(bus, gather_timeout=10.0)
    p._note_latency("wA1", 0.010)
    plan = [_Shard("wA1", "tA", 0, 4), _Shard("wA2", "tA", 4, 4)]
    assert p._partial_wait(plan) == pytest.approx(
        10.0 * pred_mod._RESUBMIT_AT)
    p._note_latency("wA2", 100.0)  # measured, but absurdly slow
    assert p._partial_wait(plan) == pytest.approx(
        10.0 * pred_mod._RESUBMIT_AT)


def test_fast_fleet_resubmits_well_before_fixed_fraction(bus):
    """End to end: once warm batches have given both replicas their
    EWMAs, a replica dying mid-gather is re-covered by its sibling on
    the deadline the predictor derives from them, far sooner than the
    fixed half-timeout deadline (10s here) would allow. Several warm
    batches, so that one slow round trip on a loaded box cannot set
    the deadline; and the elapsed time is held to the deadline the
    predictor computed, not to a bare number of seconds."""
    from rafiki_tpu.predictor import predictor as pred_mod
    from rafiki_tpu.predictor.predictor import _Shard

    w1 = EchoWorker(bus, "wA1", trial_id="tA")
    w2 = EchoWorker(bus, "wA2", trial_id="tA")
    p = _predictor(bus, gather_timeout=20.0)
    fixed = p.gather_timeout * pred_mod._RESUBMIT_AT
    qs = list(range(8))
    try:
        round_trips = []
        for _ in range(8):  # warm: EWMAs for both
            t0 = time.monotonic()
            assert p.predict(qs) == _expected(qs)
            round_trips.append(time.monotonic() - t0)
        w1.dead = True
        deadline = p._partial_wait(
            [_Shard("wA1", "tA", 0, 4), _Shard("wA2", "tA", 4, 4)])
        assert deadline < fixed / 2, \
            f"latency-relative deadline did not engage ({deadline:.2f}s)"
        t0 = time.monotonic()
        assert p.predict(qs) == _expected(qs)
        elapsed = time.monotonic() - t0
        # nothing re-covers the shard before the deadline; the sibling
        # answers within a round trip after it, and well before 10s
        assert deadline <= elapsed < fixed, (deadline, elapsed)
        assert elapsed < deadline + max(1.0, 4 * max(round_trips)), \
            (deadline, elapsed, round_trips)
    finally:
        w1.stop()
        w2.stop()


# --- Batcher-off direct path: per-client fairness (r9) ---

def test_direct_path_client_share_caps_inflight(bus):
    """With the micro-batcher OFF, the same client_share caps one
    client key's in-flight queries: the hog's overflow bounces with
    429 reason=client_share while another client keeps being served."""
    worker = EchoWorker(bus, delay=0.4)  # slow: requests stay in flight
    svc = _service(bus, microbatch=False, client_header="X-Client-Id",
                   client_share=0.25, queue_cap=16)  # cap = 4 queries
    url = f"http://127.0.0.1:{svc.port}/predict"
    results = {"hog_ok": 0, "hog_429": 0, "other_ok": 0}
    lock = threading.Lock()

    def post(n, client, key):
        r = requests.post(url, json={"queries": list(range(n))},
                          headers={"X-Client-Id": client}, timeout=30)
        if r.status_code == 429:
            body = r.json()
            assert body["reason"] == "client_share", body
            assert r.headers.get("Retry-After"), "missing Retry-After"
            with lock:
                results[key.replace("ok", "429")] += 1
        else:
            r.raise_for_status()
            with lock:
                results[key] += 1

    try:
        assert svc.batcher is None and svc._direct_cap == 4
        hogs = [threading.Thread(target=post, args=(3, "hog", "hog_ok"))
                for _ in range(6)]
        [t.start() for t in hogs]
        time.sleep(0.1)  # hog floods first; its slices are in flight
        others = [threading.Thread(target=post,
                                   args=(1, f"c{i}", "other_ok"))
                  for i in range(4)]
        [t.start() for t in others]
        [t.join(timeout=30) for t in hogs + others]
        assert results["hog_429"] > 0, results
        assert results["other_ok"] == 4, results
        assert svc.stats.snapshot()["rejected_by_reason"].get(
            "client_share", 0) == results["hog_429"]
        assert svc._direct_pending == {}  # fully released
    finally:
        _teardown(svc)
        worker.stop()


def test_direct_path_fairness_off_without_header(bus):
    """No client header configured -> no per-key bound on the direct
    path (pre-r9 behavior)."""
    worker = EchoWorker(bus)
    svc = _service(bus, microbatch=False)
    url = f"http://127.0.0.1:{svc.port}/predict"
    try:
        assert svc._direct_cap == 0
        r = requests.post(url, json={"queries": list(range(64))},
                          headers={"X-Client-Id": "hog"}, timeout=30)
        assert r.status_code == 200
    finally:
        _teardown(svc)
        worker.stop()
