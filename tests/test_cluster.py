"""Cluster serving fabric (docs/cluster.md): node registry, inter-node
relay, the shared edge-cache fabric, per-node scrape grouping, and the
``node.kill`` chaos site.

The zero-series / zero-thread contract is asserted at the CONSTRUCTION
level here (``relay_counter is None``, ``_fabric is False``) rather
than by grepping the process-global metrics registry, because sibling
tests in one pytest process legitimately register cluster series.
"""

import os
import threading
import time

import pytest
import requests

from rafiki_tpu import faults
from rafiki_tpu.admin.nodes import NodeRegistry, node_key
from rafiki_tpu.admin.scrape import (merge_worker_expositions,
                                     worker_scrape_targets)
from rafiki_tpu.bus import connect, serve_broker
from rafiki_tpu.bus.memory import MemoryBus
from rafiki_tpu.cache import Cache, encode_payload
from rafiki_tpu.constants import (BudgetOption, ServiceStatus, ServiceType,
                                  TaskType, UserType)
from rafiki_tpu.model import load_image_dataset
from rafiki_tpu.observe.metrics import registry as metrics_registry
from rafiki_tpu.platform import LocalPlatform
from rafiki_tpu.predictor.app import PredictorService
from rafiki_tpu.predictor.edge_cache import EdgeCache

FF_CLASS = "rafiki_tpu.models.feedforward:JaxFeedForward"


@pytest.fixture(autouse=True)
def _clean_fault_plane():
    faults.reset()
    yield
    faults.reset()


# --- Node registry ------------------------------------------------------


def _registry(bus, node_id, lease_s=5.0, bus_uri=""):
    return NodeRegistry(lambda: bus, node_id, n_chips=2,
                        bus_uri=bus_uri, lease_s=lease_s)


def test_node_registry_announce_live_withdraw():
    bus = MemoryBus()
    ra = _registry(bus, "vm/a", bus_uri="tcp://127.0.0.1:1")
    rb = _registry(bus, "vm/b", bus_uri="tcp://127.0.0.1:2")
    try:
        ra.announce()
        rb.announce()
        nodes = ra.nodes()
        assert set(nodes) == {"vm/a", "vm/b"}
        assert all(r["live"] for r in nodes.values())
        assert nodes["vm/b"]["chips"] == 2
        assert ra.live_nodes() == ["vm/a", "vm/b"]
        # relay_peers excludes self and carries the peer's broker URI.
        assert ra.relay_peers() == {"vm/b": "tcp://127.0.0.1:2"}
        # A heartbeat older than the lease stops counting as live...
        rec = bus.get(node_key("vm/b"))
        rec["hb"] = time.time() - 60.0
        bus.set(node_key("vm/b"), rec)
        assert ra.live_nodes() == ["vm/a"]
        assert ra.relay_peers() == {}
        # ...and a withdrawn node disappears outright.
        rb.withdraw()
        assert set(ra.nodes()) == {"vm/a"}
        snap = ra.snapshot()
        assert snap["enabled"] and snap["node_id"] == "vm/a"
        health = ra.health()
        assert health == {"fabric": True, "nodes_registered": 1,
                          "nodes_live": 1}
    finally:
        ra.close()
        rb.close()
    assert metrics_registry().find("rafiki_tpu_node_peers") is None or \
        not list(metrics_registry().find(
            "rafiki_tpu_node_peers").samples())


def test_node_registry_spread_vote_round_robin():
    """Exactly ONE node elects itself per pressure round, and it is
    always a node holding a minimum replica count — N nodes reacting
    to the same signal lay replicas across failure domains instead of
    N-fold over-provisioning one box."""
    bus = MemoryBus()
    regs = {n: _registry(bus, n) for n in ("vm/a", "vm/b", "vm/c")}
    try:
        for r in regs.values():
            r.announce()
        # Bin has one replica on vm/a: the minimum holders are b and c;
        # the deterministic tie-break elects exactly vm/b.
        counts = {"vm/a": 1}
        votes = {n: r.spread_ok(counts) for n, r in regs.items()}
        assert votes == {"vm/a": False, "vm/b": True, "vm/c": False}
        # Even coverage: the FIRST minimum holder in sorted order acts.
        counts = {"vm/a": 1, "vm/b": 1, "vm/c": 1}
        votes = {n: r.spread_ok(counts) for n, r in regs.items()}
        assert votes == {"vm/a": True, "vm/b": False, "vm/c": False}
        # A registry that cannot see its own node never blocks scaling.
        lone = _registry(bus, "vm/ghost")
        try:
            assert lone.spread_ok({"vm/a": 9})
        finally:
            lone.close()
    finally:
        for r in regs.values():
            r.close()


def test_get_nodes_disabled_and_enabled(tmp_path, monkeypatch):
    platform = LocalPlatform(workdir=str(tmp_path / "off"),
                             supervise_interval=0)
    try:
        assert platform.node_registry is None
        assert platform.admin.get_nodes() == {"enabled": False}
    finally:
        platform.shutdown()
    monkeypatch.setenv("RAFIKI_TPU_CLUSTER_FABRIC", "1")
    platform = LocalPlatform(workdir=str(tmp_path / "on"),
                             supervise_interval=0, node_id="vm/reg")
    try:
        assert platform.node_registry is not None
        body = platform.admin.get_nodes()
        assert body["enabled"] and body["node_id"] == "vm/reg"
        assert body["nodes"]["vm/reg"]["live"]
        status = platform.admin.get_status()
        assert status["cluster"]["nodes_live"] == 1
    finally:
        platform.shutdown()
    # Shutdown withdrew the record and dropped the registry's series.
    assert metrics_registry().find("rafiki_tpu_node_peers") is None or \
        not list(metrics_registry().find(
            "rafiki_tpu_node_peers").samples())


# --- Inter-node relay ---------------------------------------------------


def _relay_counts():
    c = metrics_registry().find("rafiki_tpu_bus_relay_total")
    if c is None:
        return {}
    return {lab["direction"]: int(v) for lab, v in c.samples()}


def test_remote_scatter_pays_one_relay_hop_per_leg():
    """A shard bound for a worker on another node crosses the node
    boundary exactly ONCE per direction: the query leg is one broker→
    broker forward, the reply leg one forward back."""
    broker_a = serve_broker("127.0.0.1", 0, native=False, node_id="vm/a")
    broker_b = serve_broker("127.0.0.1", 0, native=False, node_id="vm/b")
    stop = threading.Event()
    worker = None
    try:
        broker_a.add_peer("vm/b", broker_b.uri)
        broker_b.add_peer("vm/a", broker_a.uri)
        cache_a = Cache(connect(broker_a.uri))
        cache_b = Cache(connect(broker_b.uri))
        cache_b.register_worker("job-r", "wb",
                                info={"trial_id": "t", "score": 0.9})

        def serve():
            while not stop.is_set():
                for it in cache_b.pop_queries("wb", timeout=0.1):
                    cache_b.send_prediction_batch(
                        it["batch_id"], "wb",
                        [[1.0]] * len(it["queries"]),
                        shard=it.get("shard"),
                        origin_node=it.get("onode"))

        worker = threading.Thread(target=serve, daemon=True)
        worker.start()
        base = _relay_counts()
        bid = cache_a.send_query_shards(
            [("wb", 0, 1, 0)], [encode_payload([1.0, 2.0])],
            worker_nodes={"wb": "vm/b"}, local_node="vm/a")
        replies = cache_a.gather_prediction_batches(bid, 1, timeout=10.0)
        assert len(replies) == 1
        assert replies[0]["predictions"] == [[1.0]]
        # A broker counts "out" when its forward RETURNS: the reply can
        # reach the gatherer before broker b has counted its leg.
        deadline = time.monotonic() + 5.0
        after = _relay_counts()
        while (after.get("out", 0) - base.get("out", 0) < 2
               and time.monotonic() < deadline):
            time.sleep(0.01)
            after = _relay_counts()
        assert after.get("out", 0) - base.get("out", 0) == 2, (base, after)
        assert after.get("in", 0) - base.get("in", 0) == 2, (base, after)
        assert after.get("fallback", 0) == base.get("fallback", 0)
    finally:
        stop.set()
        if worker is not None:
            worker.join(timeout=5)
        broker_b.stop()
        broker_a.stop()


def test_relay_to_dead_node_degrades_to_local_fallback():
    """Satellite (d): a relay addressed to a dead node's broker must
    neither wedge the sender nor drop the frame — the inner op executes
    against the sender's own broker (the pre-cluster behavior), counted
    as direction=fallback."""
    broker_a = serve_broker("127.0.0.1", 0, native=False, node_id="vm/a")
    broker_b = serve_broker("127.0.0.1", 0, native=False, node_id="vm/b")
    try:
        broker_a.add_peer("vm/b", broker_b.uri)
        bus_a = connect(broker_a.uri)
        broker_b.stop()
        base = _relay_counts()
        t0 = time.monotonic()
        bus_a.relay_push("vm/b", "dead-q", {"v": 7})
        elapsed = time.monotonic() - t0
        after = _relay_counts()
        assert after.get("fallback", 0) - base.get("fallback", 0) == 1
        # The frame landed on the LOCAL broker's queue...
        assert bus_a.pop("dead-q", timeout=2.0) == {"v": 7}
        # ...and the sender was bounded by the per-peer retry budget,
        # not a gather-scale timeout.
        assert elapsed < 10.0, elapsed
    finally:
        broker_b.stop()
        broker_a.stop()


def test_single_node_construction_has_no_cluster_surface(tmp_path):
    """Zero-series contract at the construction level: a default broker
    registers no relay machinery, and a fabric-off frontend neither
    registers with the fleet nor owns a fabric counter handle."""
    assert not os.environ.get("RAFIKI_TPU_CLUSTER_FABRIC")
    broker = serve_broker("127.0.0.1", 0, native=False)
    try:
        assert broker.node_id == ""
        assert broker._server.relay_counter is None
    finally:
        broker.stop()
    svc = PredictorService("zero-fab", "job-z", meta=None,
                           bus=MemoryBus(), host="127.0.0.1",
                           cache_bytes=1 << 16, microbatch=False)
    try:
        assert svc._fabric is False
        assert svc._m_fabric is None
        assert svc.edge_cache is not None  # the cache itself is r16
    finally:
        svc.stats.close()
        svc.predictor.close()
        svc.edge_cache.close()


# --- Edge-cache fabric --------------------------------------------------


def _make_frontend(bus, sid, job):
    svc = PredictorService(sid, job, meta=None, bus=bus,
                           host="127.0.0.1", cache_bytes=1 << 20,
                           cache_admit_after=1, microbatch=False)
    svc.predictor.worker_wait_timeout = 10.0
    svc.predictor.gather_timeout = 10.0
    svc._http.start()
    if svc._fabric:
        svc.predictor.cache.register_frontend(
            job, svc.stats.service, f"127.0.0.1:{svc.port}")
    return svc


def _stop_frontend(svc, job):
    if svc._fabric:
        svc.predictor.cache.unregister_frontend(job, svc.stats.service)
    svc._http.stop()
    svc.stats.close()
    svc.predictor.close()
    svc.edge_cache.close()
    if svc._m_fabric is not None:
        svc._m_fabric.remove(service=svc.stats.service)


def _fabric_events(svc):
    c = metrics_registry().find("rafiki_tpu_serving_fabric_total")
    if c is None:
        return {}
    return {lab["event"]: int(v) for lab, v in c.samples()
            if lab.get("service") == svc.stats.service}


def test_peer_hit_and_gossiped_invalidation(monkeypatch):
    """The fabric's two data paths over two live frontends: a miss on B
    converts to a peer hit against A's cache (no second scatter), and a
    promote-path invalidation on A gossips to B, whose next query of
    the same key MISSES and rescatters — a pre-promotion answer can
    never be served from a peer after the promotion."""
    monkeypatch.setenv("RAFIKI_TPU_CLUSTER_FABRIC", "1")
    monkeypatch.setenv("RAFIKI_TPU_CLUSTER_PROBE_TIMEOUT_S", "2.0")
    bus = MemoryBus()
    wcache = Cache(bus)
    served = {"n": 0}
    stop = threading.Event()
    wcache.register_worker("job-f", "wf",
                           info={"trial_id": "t", "score": 0.9})

    def serve():
        while not stop.is_set():
            for it in wcache.pop_queries("wf", timeout=0.1):
                n = len(it["queries"])
                served["n"] += n
                wcache.send_prediction_batch(
                    it["batch_id"], "wf", [[0.8, 0.2]] * n,
                    shard=it.get("shard"), compute_s=0.001 * n)

    worker = threading.Thread(target=serve, daemon=True)
    worker.start()
    fa = fb = None
    try:
        fa = _make_frontend(bus, "cfa", "job-f")
        fb = _make_frontend(bus, "cfb", "job-f")
        assert fa._fabric and fb._fabric
        q = encode_payload([3.0, 4.0])

        def post(svc, path, payload):
            r = requests.post(f"http://127.0.0.1:{svc.port}{path}",
                              json=payload, timeout=30)
            r.raise_for_status()
            return r.json()

        post(fa, "/predict", {"query": q})
        assert served["n"] == 1
        # B's first sight of the key: peer probe converts the miss.
        post(fb, "/predict", {"query": q})
        assert served["n"] == 1, "peer hit must not scatter"
        assert _fabric_events(fb).get("peer_hit") == 1
        # Promote-path invalidation on A gossips to B...
        epoch_b = fb.edge_cache.epoch
        post(fa, "/cache/invalidate", {})
        deadline = time.monotonic() + 5
        while fb.edge_cache.epoch <= epoch_b:
            assert time.monotonic() < deadline, "gossip never landed"
            time.sleep(0.01)
        assert _fabric_events(fa).get("gossip_sent") == 1
        assert _fabric_events(fb).get("gossip_recv") == 1
        # ...so B's next query MISSES and rescatters (and its peer
        # probe finds A empty too — no resurrected entry anywhere).
        post(fb, "/predict", {"query": q})
        assert served["n"] == 2, "stale entry survived the invalidation"
    finally:
        for svc in (fa, fb):
            if svc is not None:
                _stop_frontend(svc, "job-f")
        stop.set()
        worker.join(timeout=5)


def test_gossip_racing_local_insert_never_resurrects():
    """Satellite (d), the epoch race: a gossiped invalidation that
    lands AFTER a leader captured its epoch but BEFORE it resolves
    must drop the insert — the waiters still get the (pre-promotion)
    answer, the cache never does."""
    cache = EdgeCache(max_bytes=1 << 16, admit_after=1, service="race")
    try:
        kind, flight = cache.begin("k")
        assert kind == "lead"
        epoch = cache.epoch  # leader snapshot, pre-scatter
        # The gossiped invalidation lands mid-flight.
        cache.invalidate()
        cache.resolve("k", {"answer": "stale"}, epoch, flight=flight)
        # The waiter path still completes with the in-flight answer...
        assert flight.wait(1.0) == {"answer": "stale"}
        # ...but the entry was NOT inserted: the next begin is a fresh
        # leader, not a hit on a resurrected pre-promotion value.
        kind, _ = cache.begin("k")
        assert kind == "lead"
    finally:
        cache.close()


# --- Per-node scrape grouping (satellite a) -----------------------------


class _BusServices:
    def __init__(self, bus):
        self._bus = bus

    def serving_bus(self):
        return self._bus


def test_worker_scrape_targets_group_by_node_and_merge():
    bus = MemoryBus()
    bus.set("w:job1:s1", {"metrics": "127.0.0.1:9001", "node": "vm/a"})
    bus.set("w:job1:s2", {"metrics": "127.0.0.1:9002", "node": "vm/b"})
    bus.set("w:job1:s3", {"metrics": "127.0.0.1:9003", "node": "vm/b"})
    bus.set("w:job1:s4", {"trial_id": "t"})  # resident: no endpoint
    bus.set("w:job2:sx", {"metrics": "127.0.0.1:9009", "node": "vm/c"})
    by_node, silent = worker_scrape_targets(_BusServices(bus), "job1")
    assert by_node == {"vm/a": ["127.0.0.1:9001"],
                       "vm/b": ["127.0.0.1:9002", "127.0.0.1:9003"]}
    assert silent == 1

    calls = []

    def fetch(addr, path):
        calls.append((addr, path))
        if addr.endswith("9002"):
            raise OSError("connection refused")
        return f"# metrics from {addr}"

    text, fetched, failed = merge_worker_expositions(fetch, by_node)
    assert fetched == 2 and failed == 1
    assert "9001" in text and "9003" in text
    assert sorted(a for a, _ in calls) == [
        "127.0.0.1:9001", "127.0.0.1:9002", "127.0.0.1:9003"]
    assert all(p == "/metrics" for _, p in calls)


def test_worker_scrape_targets_empty_and_broken_bus():
    assert worker_scrape_targets(_BusServices(MemoryBus()),
                                 "job-none") == ({}, 0)

    class _Broken:
        def serving_bus(self):
            raise ConnectionError("broker down")

    # A scrape sweep must survive a broker outage: no targets, not an
    # exception into the SLO engine.
    assert worker_scrape_targets(_Broken(), "job1") == ({}, 0)


# --- node.kill chaos site (satellite b) ---------------------------------


def test_node_kill_bin_vote_survives_and_respawns(tmp_path,
                                                  synth_image_data):
    """The r11 chaos plane's new ``node.kill`` site, end to end: a
    secondary node hosting one replica of a served bin dies HARD (all
    its services killed, meta rows left RUNNING, registrations stale).
    The bin's vote survives — its sibling replica on the primary keeps
    answering — and the secondary's next supervise sweep detects the
    wreckage and respawns the replica, which rejoins the shard plan."""
    train_path, val_path = synth_image_data
    shared = str(tmp_path / "shared")
    broker = serve_broker("127.0.0.1", 0, native=False)
    faults.set_plan("")  # armed-quiet before any stack exists
    node_a = LocalPlatform(workdir=shared, bus_uri=broker.uri,
                           http=True, supervise_interval=0)
    node_b = None
    try:
        dev = node_a.admin.create_user("nodekill@x.c", "pw",
                                       UserType.MODEL_DEVELOPER)
        model = node_a.admin.create_model(
            dev["id"], "ff-nk", TaskType.IMAGE_CLASSIFICATION, FF_CLASS)
        job = node_a.admin.create_train_job(
            dev["id"], "nk", TaskType.IMAGE_CLASSIFICATION,
            [model["id"]], {BudgetOption.MODEL_TRIAL_COUNT: 1},
            train_path, val_path)
        assert node_a.admin.wait_until_train_job_done(job["id"],
                                                      timeout=600)
        inf = node_a.admin.create_inference_job(dev["id"], job["id"],
                                                max_models=1)
        host = node_a.admin.get_inference_job(
            inf["id"])["predictor_host"]
        pred_svc = next(s for s in node_a.meta.get_services()
                        if s["service_type"] == ServiceType.PREDICT)
        psvc = node_a.container.get(pred_svc["id"])
        psvc.predictor.gather_timeout = 4.0
        trial_id = node_a.services.active_inference_workers(
            inf["id"])[0]["trial_id"]

        # A secondary node attaches one REPLICA of the same bin.
        node_b = LocalPlatform(workdir=shared, bus_uri=broker.uri,
                               supervise_interval=0,
                               stop_jobs_on_shutdown=False,
                               node_id="vm/chaos-b")
        svc_b = node_b.services.add_inference_worker(inf["id"], trial_id)
        assert svc_b is not None

        ds = load_image_dataset(val_path)
        batch = [encode_payload(ds.images[i]) for i in range(3)]
        url = f"http://{host}/predict"

        def predict_full() -> bool:
            r = requests.post(url, json={"queries": batch}, timeout=60)
            if r.status_code != 200:
                return False
            preds = r.json().get("predictions") or []
            return len(preds) == len(batch) and \
                all(p is not None for p in preds)

        def replicas_in_plan() -> int:
            groups, _, _ = psvc.predictor._group_replicas()
            return sum(len(members) for members in groups.values())

        deadline = time.monotonic() + 120
        while replicas_in_plan() < 2:
            assert time.monotonic() < deadline, \
                "replica on the secondary node never joined the plan"
            predict_full()
            time.sleep(0.2)

        # --- Node B dies. The op match pins the blast radius: node A's
        # sweeps consult the same plan and never fire.
        faults.set_plan("node.kill:op=vm/chaos-b,n=1")
        assert node_a.services.supervise() == []
        node_b.services.supervise()
        # Hard death: container slot gone, meta row STILL RUNNING (the
        # wreckage shape supervise respawns from).
        assert node_b.container.get(svc_b["id"]) is None
        row = node_a.meta.get_service(svc_b["id"])
        assert row["status"] == ServiceStatus.RUNNING
        c = metrics_registry().find("rafiki_tpu_fault_injections_total")
        assert c is not None and c.value(site="node", kind="kill") >= 1

        # --- The bin's vote survives the node death: the sibling
        # replica on node A answers every query in full.
        assert predict_full(), \
            "bin lost its vote when the secondary node died"

        # --- Replan-and-respawn: node B's next sweep spots its own
        # stale wreckage and respawns the replica...
        deadline = time.monotonic() + 120
        respawned = []
        while not respawned:
            assert time.monotonic() < deadline, "respawn never happened"
            respawned = node_b.services.supervise()
            time.sleep(0.2)
        assert len(respawned) == 1
        # ...which rejoins the predictor's shard plan.
        deadline = time.monotonic() + 120
        while replicas_in_plan() < 2:
            assert time.monotonic() < deadline, \
                "respawned replica never rejoined the shard plan"
            predict_full()
            time.sleep(0.2)
        assert predict_full()
    finally:
        faults.set_plan(None)
        if node_b is not None:
            node_b.shutdown()
        node_a.shutdown()
        broker.stop()
