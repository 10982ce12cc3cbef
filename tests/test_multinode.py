"""Two-node scale-out rehearsal (VERDICT r1 item 9; SURVEY.md §2.10).

Node A (primary) runs the admin + advisor + one train worker; node B is
a real ``python -m rafiki_tpu join`` subprocess sharing A's meta store
(sqlite file), params dir and TCP bus across a socket boundary. One
train job's trials land on BOTH nodes' workers, coordinated by the one
bus-hosted advisor.
"""

import os
import subprocess
import sys
import time

import pytest

from rafiki_tpu.bus import serve_broker
from rafiki_tpu.constants import BudgetOption, TaskType, UserType
from rafiki_tpu.platform import LocalPlatform

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FF_CLASS = "rafiki_tpu.models.feedforward:JaxFeedForward"


@pytest.fixture()
def broker():
    server = serve_broker("127.0.0.1", 0, native=False)
    yield server
    server.stop()


@pytest.mark.slow
@pytest.mark.slower
def test_one_job_split_across_two_nodes(tmp_path, synth_image_data,
                                        broker):
    train_path, val_path = synth_image_data
    shared = str(tmp_path / "shared")

    node_a = LocalPlatform(workdir=shared, bus_uri=broker.uri,
                           supervise_interval=0)
    proc = None
    try:
        dev = node_a.admin.create_user("dev@x.c", "pw",
                                       UserType.MODEL_DEVELOPER)
        model = node_a.admin.create_model(
            dev["id"], "ff", TaskType.IMAGE_CLASSIFICATION, FF_CLASS)
        job = node_a.admin.create_train_job(
            dev["id"], "app", TaskType.IMAGE_CLASSIFICATION,
            [model["id"]], {BudgetOption.MODEL_TRIAL_COUNT: 10},
            train_path, val_path)

        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.Popen(
            [sys.executable, "-m", "rafiki_tpu", "join",
             "--workdir", shared, "--bus", broker.uri,
             "--train-job", job["id"], "--timeout", "540"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)

        assert node_a.admin.wait_until_train_job_done(job["id"],
                                                      timeout=600)
        out, _ = proc.communicate(timeout=120)
        assert proc.returncode == 0, out.decode()
        assert b"attached 1 worker" in out, out.decode()

        sub = node_a.meta.get_sub_train_jobs(job["id"])[0]
        trials = node_a.meta.get_trials(sub["id"])
        done = [t for t in trials if t["status"] == "COMPLETED"]
        assert len(done) == 10

        # Trials ran on BOTH nodes: the worker ids behind the completed
        # trials must span services from two distinct node_ids.
        node_ids = set()
        for t in done:
            svc = node_a.meta.get_service(t["worker_id"])
            if svc is not None:
                node_ids.add(svc["node_id"])
        assert len(node_ids) >= 2, (
            f"all trials ran on one node: {node_ids}")
    finally:
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.communicate()
        node_a.shutdown()


def test_secondary_shutdown_leaves_no_running_rows(tmp_path,
                                                   synth_image_data,
                                                   broker):
    """Review finding r2: a join node leaving mid-job (timeout, crash
    path through shutdown) must stop ITS services — leaked RUNNING rows
    would read as a live remote worker forever and block the primary's
    job-completion detection."""
    train_path, val_path = synth_image_data
    shared = str(tmp_path / "shared")
    node_a = LocalPlatform(workdir=shared, bus_uri=broker.uri,
                           supervise_interval=0)
    node_b = None
    try:
        dev = node_a.admin.create_user("dev@x.c", "pw",
                                       UserType.MODEL_DEVELOPER)
        model = node_a.admin.create_model(
            dev["id"], "ff", TaskType.IMAGE_CLASSIFICATION, FF_CLASS)
        job = node_a.admin.create_train_job(
            dev["id"], "app", TaskType.IMAGE_CLASSIFICATION,
            [model["id"]], {BudgetOption.MODEL_TRIAL_COUNT: 6},
            train_path, val_path)

        node_b = LocalPlatform(workdir=shared, bus_uri=broker.uri,
                               supervise_interval=0,
                               stop_jobs_on_shutdown=False,
                               node_id="vm/join-test")
        attached = node_b.admin.attach_workers(job["id"])
        assert attached
        node_b.shutdown()  # leaves mid-job
        node_b = None

        rows = node_a.meta.get_services(node_id="vm/join-test")
        assert rows and all(r["status"] not in
                            ("RUNNING", "DEPLOYING", "STARTED")
                            for r in rows), rows
        # And the primary still completes the job on its own workers.
        assert node_a.admin.wait_until_train_job_done(job["id"],
                                                      timeout=600)
    finally:
        if node_b is not None:
            node_b.shutdown()
        node_a.shutdown()


def test_restarted_node_sweeps_its_stale_rows(tmp_path):
    """Review finding r2: node identity is stable across restarts of
    the same host+workdir, so a crashed node's RUNNING rows are swept
    (not orphaned) by the restarted process's supervise."""
    from rafiki_tpu.constants import ServiceStatus, ServiceType

    from rafiki_tpu.store import MetaStore

    shared = str(tmp_path / "node")
    p1 = LocalPlatform(workdir=shared, supervise_interval=0)
    node_id = p1.services.node_id
    p1.shutdown()
    # Simulate a crash's aftermath: a RUNNING row (written before the
    # crash) whose container no restarted process knows.
    meta = MetaStore(shared + "/meta.db")
    stale = meta.create_service(ServiceType.ADVISOR,
                                ServiceStatus.RUNNING,
                                container_id="gone", node_id=node_id)
    meta.close()

    p2 = LocalPlatform(workdir=shared, supervise_interval=0)
    try:
        assert p2.services.node_id == node_id  # stable identity
        p2.services.supervise()
        assert p2.meta.get_service(stale["id"])["status"] == \
            ServiceStatus.ERRORED
    finally:
        p2.shutdown()


def test_dead_foreign_node_lease_expires(tmp_path):
    """Review finding r2: a join node that dies WITHOUT shutdown
    (SIGKILL, power loss) must not block the primary forever — its
    RUNNING rows are credible only while its heartbeat lease is fresh;
    expiry makes train_services_active False and supervise marks the
    rows ERRORED."""
    import time as _time

    from rafiki_tpu.constants import ServiceStatus, ServiceType

    p = LocalPlatform(workdir=str(tmp_path / "n"), supervise_interval=0)
    try:
        job = p.meta.create_train_job("u", "app", "IMAGE_CLASSIFICATION",
                                      {}, "tr", "va", status="RUNNING")
        sub = p.meta.create_sub_train_job(job["id"], "m",
                                          status="RUNNING")
        svc = p.meta.create_service(ServiceType.TRAIN,
                                    ServiceStatus.RUNNING,
                                    container_id="gone",
                                    node_id="otherhost/deadbeef")
        p.meta.add_train_job_worker(svc["id"], sub["id"])

        # Fresh lease (set at creation): trusted as live.
        assert p.services.train_services_active(job["id"])
        p.services.supervise()
        assert p.meta.get_service(svc["id"])["status"] == \
            ServiceStatus.RUNNING

        # Lease expires: no longer live; sweep marks it errored.
        p.meta.update_service(
            svc["id"],
            heartbeat_at=_time.time() - p.services.NODE_LEASE - 1)
        assert not p.services.train_services_active(job["id"])
        p.services.supervise()
        assert p.meta.get_service(svc["id"])["status"] == \
            ServiceStatus.ERRORED

        # A heartbeat refreshes the lease for a node's own rows.
        svc2 = p.meta.create_service(ServiceType.TRAIN,
                                     ServiceStatus.RUNNING,
                                     node_id="otherhost/deadbeef")
        p.meta.update_service(
            svc2["id"],
            heartbeat_at=_time.time() - p.services.NODE_LEASE - 1)
        p.meta.touch_node_services("otherhost/deadbeef")
        fresh = p.meta.get_service(svc2["id"])["heartbeat_at"]
        assert _time.time() - fresh < 5
    finally:
        p.shutdown()


def test_jax_distributed_cpu_pair(tmp_path):
    """The multi-host wiring (jax.distributed.initialize, the flags the
    serve CLI passes) on a CPU pair: two processes, one coordinator,
    global device count = 2."""
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    code = (
        "import sys\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "jax.distributed.initialize(\n"
        "    coordinator_address='127.0.0.1:%d',\n"
        "    num_processes=2, process_id=int(sys.argv[1]))\n"
        "print('GLOBAL', jax.device_count(), 'LOCAL',\n"
        "      jax.local_device_count())\n" % port)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # 1 local CPU device per process
    procs = [subprocess.Popen([sys.executable, "-c", code, str(i)],
                              env=env, stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT)
             for i in range(2)]
    outs = []
    deadline = time.time() + 180
    for p in procs:
        try:
            out, _ = p.communicate(timeout=max(5.0,
                                               deadline - time.time()))
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
        outs.append(out.decode())
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out
        assert "GLOBAL 2 LOCAL 1" in out, out


def test_status_reports_cluster_nodes(tmp_path, synth_image_data,
                                      broker):
    """/status carries the per-node cluster view when several nodes
    share the meta store: each node's service count + heartbeat age.

    Trials block on a gate file until the joined node has been observed
    in /status — without the gate, node_a's workers can spend the whole
    4-trial budget before node_b's worker ever reaches RUNNING, and the
    poll below can never succeed (the r4 flake)."""
    train_path, val_path = synth_image_data
    shared = str(tmp_path / "shared")
    gate = str(tmp_path / "gate")
    gated_source = (
        "import os, time\n"
        "from rafiki_tpu.model import BaseModel, FixedKnob\n"
        "class GatedFF(BaseModel):\n"
        "    @staticmethod\n"
        "    def get_knob_config():\n"
        "        return {'k': FixedKnob(1)}\n"
        "    def train(self, p, **kw):\n"
        f"        while not os.path.exists({gate!r}):\n"
        "            time.sleep(0.05)\n"
        "    def evaluate(self, p): return 0.5\n"
        "    def predict(self, qs): return [0.0 for _ in qs]\n"
        "    def dump_parameters(self): return {}\n"
        "    def load_parameters(self, p): pass\n")
    node_a = LocalPlatform(workdir=shared, bus_uri=broker.uri,
                           supervise_interval=0)
    node_b = None
    try:
        dev = node_a.admin.create_user("dev@x.c", "pw",
                                       UserType.MODEL_DEVELOPER)
        model = node_a.admin.create_model(
            dev["id"], "ff", TaskType.IMAGE_CLASSIFICATION, "GatedFF",
            model_source=gated_source)
        job = node_a.admin.create_train_job(
            dev["id"], "app", TaskType.IMAGE_CLASSIFICATION,
            [model["id"]], {BudgetOption.MODEL_TRIAL_COUNT: 4},
            train_path, val_path)
        node_b = LocalPlatform(workdir=shared, bus_uri=broker.uri,
                               supervise_interval=0,
                               stop_jobs_on_shutdown=False,
                               node_id="vm/join-status")
        assert node_b.admin.attach_workers(job["id"])
        # The joined worker reaches RUNNING asynchronously — poll. It
        # CANNOT exit early: every trial is blocked on the gate file, so
        # the budget is still open when it starts.
        deadline = time.monotonic() + 120
        status = node_a.admin.get_status()
        while "vm/join-status" not in status["nodes"] \
                and time.monotonic() < deadline:
            time.sleep(0.2)
            status = node_a.admin.get_status()
        assert status["node_id"] == node_a.services.node_id
        assert "vm/join-status" in status["nodes"]
        joined = status["nodes"]["vm/join-status"]
        assert joined["services"] >= 1
        assert joined["heartbeat_age_s"] is not None
        assert joined["heartbeat_age_s"] < 60
        with open(gate, "w"):
            pass  # open the gate: let all trials complete
        assert node_a.admin.wait_until_train_job_done(job["id"],
                                                      timeout=600)
    finally:
        # The gate must open even when an assertion above failed, or
        # every blocked trial thread would spin on os.path.exists for
        # the rest of the pytest session.
        with open(gate, "w"):
            pass
        if node_b is not None:
            node_b.shutdown()
        node_a.shutdown()


@pytest.mark.slow
def test_broker_restart_mid_serving_recovers(tmp_path, synth_image_data,
                                             monkeypatch):
    """SURVEY.md §2.10 durability (r2 verdict item 4): the broker holds
    queue/registry state in memory, so killing it mid-serving forgets
    every worker registration. Workers must re-register against the
    restarted broker (lease-style re-assertion + error-path recovery)
    and serving must resume — no supervise restart, no stranded
    workers."""
    import requests

    from rafiki_tpu.bus import serve_broker
    from rafiki_tpu.cache import encode_payload
    from rafiki_tpu.model import load_image_dataset

    monkeypatch.setenv("RAFIKI_TPU_WORKER_REREGISTER", "1.0")
    train_path, val_path = synth_image_data
    broker = serve_broker("127.0.0.1", 0, native=False)
    port = broker.port
    platform = LocalPlatform(workdir=str(tmp_path / "plat"),
                             bus_uri=broker.uri, http=True,
                             supervise_interval=0)
    try:
        user = platform.admin.create_user("b@x.c", "pw",
                                          UserType.MODEL_DEVELOPER)
        model = platform.admin.create_model(
            user["id"], "ff", TaskType.IMAGE_CLASSIFICATION, FF_CLASS)
        job = platform.admin.create_train_job(
            user["id"], "serve", TaskType.IMAGE_CLASSIFICATION,
            [model["id"]], {BudgetOption.MODEL_TRIAL_COUNT: 1},
            train_path, val_path)
        assert platform.admin.wait_until_train_job_done(job["id"],
                                                        timeout=600)
        inf = platform.admin.create_inference_job(user["id"], job["id"],
                                                  max_models=1)
        host = platform.admin.get_inference_job(
            inf["id"])["predictor_host"]
        ds = load_image_dataset(val_path)
        batch = [encode_payload(ds.images[i]) for i in range(4)]

        def predict_ok(timeout: float) -> bool:
            try:
                r = requests.post(f"http://{host}/predict",
                                  json={"queries": batch},
                                  timeout=timeout)
                return (r.status_code == 200
                        and len(r.json()["predictions"]) == 4)
            except Exception:
                return False

        deadline = time.time() + 120
        while not predict_ok(60) and time.time() < deadline:
            time.sleep(0.5)
        assert predict_ok(60), "serving never became ready"

        # Kill the broker: every registration and queued burst dies
        # with its in-memory state. Restart EMPTY on the same port.
        broker.stop()
        time.sleep(1.0)
        broker = serve_broker("127.0.0.1", port, native=False)

        # QPS must recover: the workers' 1s re-registration lease
        # re-populates the fresh broker's registry, and the predictor's
        # next scan finds them.
        deadline = time.time() + 60
        recovered = False
        while time.time() < deadline:
            if predict_ok(30):
                recovered = True
                break
            time.sleep(1.0)
        assert recovered, "serving did not recover after broker restart"
        platform.admin.stop_inference_job(inf["id"])
    finally:
        platform.shutdown()
        broker.stop()


def test_persistent_bus_op_error_escalates_to_errored():
    """ADVICE r3: a broker that persistently REPORTS op failures
    (protocol/version skew — BusOpError, not a transport outage) must
    not leave the worker warn-looping as RUNNING forever: after
    max_op_errors consecutive laps with no successful iteration the
    serve loop re-raises and the service goes ERRORED. Transport
    failures (ConnectionError) keep retrying indefinitely."""
    from rafiki_tpu.bus import BusOpError, MemoryBus
    from rafiki_tpu.worker.inference import InferenceWorker

    class FakeMeta:
        def __init__(self):
            self.statuses = []

        def update_service(self, service_id, **fields):
            self.statuses.append(fields.get("status"))

    def make_worker(exc_factory, fail_forever=True, n_failures=0):
        w = InferenceWorker("svc", "ij", "tr", FakeMeta(), None,
                            MemoryBus(), batch_timeout=0.0)
        w.max_op_errors = 3
        w._load_model = lambda: type(
            "M", (), {"predict_submit": staticmethod(
                lambda q: (lambda: [0] * len(q)))})()
        calls = {"n": 0}

        class FlakyCache:
            def register_worker(self, *a, **k):
                pass

            def unregister_worker(self, *a, **k):
                pass

            def pop_queries(self, *a, **k):
                calls["n"] += 1
                if fail_forever or calls["n"] <= n_failures:
                    raise exc_factory()
                w.stop_flag.set()
                return []

        w.cache = FlakyCache()
        # Recovery laps sleep via stop_flag.wait(1.0); shrink it so the
        # test runs in well under a second.
        real_wait = w.stop_flag.wait
        w.stop_flag.wait = lambda t=None: real_wait(0.01)
        return w

    # Persistent op errors: escalates after max_op_errors laps.
    w = make_worker(lambda: BusOpError("bus error: unknown op"))
    with pytest.raises(BusOpError):
        w.run()
    assert w.meta.statuses[-1] == "ERRORED"

    # Transport errors beyond the cap: never escalates; a later stop
    # lands STOPPED.
    w2 = make_worker(lambda: ConnectionError("broker down"),
                     fail_forever=False, n_failures=6)
    w2.run()
    assert w2.meta.statuses[-1] == "STOPPED"
