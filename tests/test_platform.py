"""End-to-end platform tests: the §3.1/§3.2/§3.3 call stacks for real.

No mocks (SURVEY.md §4): real advisor + train workers (threads), real
stores, real bus, real HTTP predictor — scaled down to the 8-virtual-CPU
mesh and a tiny synthetic dataset.
"""

import time

import numpy as np
import pytest
import requests

from rafiki_tpu.constants import (BudgetOption, ServiceStatus, ServiceType,
                                  TaskType, TrialStatus, UserType)
from rafiki_tpu.model import load_image_dataset
from rafiki_tpu.platform import LocalPlatform

FF_CLASS = "rafiki_tpu.models.feedforward:JaxFeedForward"


@pytest.fixture()
def platform(tmp_path):
    p = LocalPlatform(workdir=str(tmp_path / "plat"), http=True,
                      supervise_interval=0)
    yield p
    p.shutdown()


def _register_model(platform, name="ff"):
    dev = platform.admin.create_user("dev@x.c", "pw",
                                     UserType.MODEL_DEVELOPER)
    model = platform.admin.create_model(
        dev["id"], name, TaskType.IMAGE_CLASSIFICATION, FF_CLASS)
    return dev, model


def test_full_automl_job_and_serving(platform, synth_image_data):
    train_path, val_path = synth_image_data
    dev, model = _register_model(platform)

    job = platform.admin.create_train_job(
        dev["id"], "fashion-app", TaskType.IMAGE_CLASSIFICATION,
        [model["id"]], {BudgetOption.MODEL_TRIAL_COUNT: 2},
        train_path, val_path)

    assert platform.admin.wait_until_train_job_done(job["id"], timeout=600)
    detail = platform.admin.get_train_job(job["id"])
    assert detail["status"] == "STOPPED"
    assert detail["sub_train_jobs"][0]["n_completed"] == 2
    assert detail["sub_train_jobs"][0]["n_errored"] == 0

    best = platform.admin.get_best_trials(job["id"], max_count=2)
    assert len(best) == 2 and best[0]["score"] >= best[1]["score"]
    # trial logs made it into the meta store
    logs = platform.admin.get_trial_logs(best[0]["id"])
    assert any(r["record"].get("type") == "plot" for r in logs)

    # chips were released after the job stopped
    assert platform.allocator.free_chips == platform.allocator.n_chips

    # --- Serving (§3.2 + §3.3) ---
    inf = platform.admin.create_inference_job(dev["id"], job["id"],
                                              max_models=2)
    inf_detail = platform.admin.get_inference_job(inf["id"])
    assert inf_detail["status"] == "RUNNING"
    host = inf_detail["predictor_host"]
    assert host

    # wait for workers to warm up + register
    from rafiki_tpu.cache import Cache
    cache = Cache(platform.bus)
    deadline = time.monotonic() + 300
    while time.monotonic() < deadline:
        if len(cache.running_workers(inf["id"])) == 2:
            break
        time.sleep(0.2)
    assert len(cache.running_workers(inf["id"])) == 2

    val = load_image_dataset(synth_image_data[1])
    from rafiki_tpu.cache import encode_payload
    resp = requests.post(
        f"http://{host}/predict",
        json={"queries": [encode_payload(val.images[i]) for i in range(8)]},
        timeout=120)
    assert resp.status_code == 200, resp.text
    preds = resp.json()["predictions"]
    assert len(preds) == 8
    acc = np.mean([int(np.argmax(p)) == val.labels[i]
                   for i, p in enumerate(preds)])
    assert acc > 0.3  # ensembled learnable-synth accuracy

    # --- On-demand device profiling (r17): the admin path queues a
    # __profile__ control frame on a LIVE worker; the artifact appears
    # and serving is undisturbed — every request during the session is
    # answered (counter-proven against the frontend's own stats).
    import os

    before = requests.get(f"http://{host}/stats",
                          timeout=30).json()["requests"]
    out = platform.admin.profile_inference_job(inf["id"],
                                               duration_s=1.0)
    assert out["service_id"] and out["profile_dir"]
    for _ in range(4):  # traffic INSIDE and after the session window
        resp = requests.post(
            f"http://{host}/predict",
            json={"queries": [encode_payload(val.images[0])]},
            timeout=120)
        assert resp.status_code == 200, resp.text
        time.sleep(0.4)
    after = requests.get(f"http://{host}/stats",
                         timeout=30).json()["requests"]
    assert after - before == 4  # nothing rejected, nothing stalled
    deadline = time.monotonic() + 20
    files = []
    while time.monotonic() < deadline and not files:
        files = [os.path.join(r, f)
                 for r, _, fs in os.walk(out["profile_dir"])
                 for f in fs]
        time.sleep(0.2)
    assert files, "profile session produced no artifact"
    # a bogus duration clamps instead of erroring; a stopped job 400s
    with pytest.raises(ValueError):
        platform.admin.profile_inference_job("nope", duration_s=1.0)

    platform.admin.stop_inference_job(inf["id"])
    assert platform.admin.get_inference_job(inf["id"])["status"] == "STOPPED"
    # all chips free again
    assert platform.allocator.free_chips == platform.allocator.n_chips


def test_rest_client_roundtrip(platform, synth_image_data):
    """The same flow through the REST API + Client SDK (upstream
    quickstart shape)."""
    from rafiki_tpu.client import Client

    train_path, val_path = synth_image_data
    client = Client(admin_port=platform.admin_port)
    client.login("superadmin@rafiki", "rafiki")
    client.create_user("mdev@x.c", "pw", UserType.MODEL_DEVELOPER)

    client2 = Client(admin_port=platform.admin_port)
    client2.login("mdev@x.c", "pw")
    model = client2.create_model("ff-rest", TaskType.IMAGE_CLASSIFICATION,
                                 FF_CLASS)
    models = client2.get_models(task=TaskType.IMAGE_CLASSIFICATION)
    assert any(m["id"] == model["id"] for m in models)

    job = client2.create_train_job(
        "rest-app", TaskType.IMAGE_CLASSIFICATION, [model["id"]],
        {BudgetOption.MODEL_TRIAL_COUNT: 1}, train_path, val_path)
    done = client2.wait_until_train_job_done(job["id"], timeout=600)
    assert done["status"] == "STOPPED"
    best = client2.get_best_trials_of_train_job(job["id"], max_count=1)
    assert best and best[0]["score"] > 0.3

    inf = client2.create_inference_job(job["id"], max_models=1)
    host = client2.get_inference_job(inf["id"])["predictor_host"]

    val = load_image_dataset(val_path)
    out = client2.predict(host, query=val.images[0])
    assert len(out["prediction"]) == val.n_classes
    client2.stop_inference_job(inf["id"])
    client2.stop_train_job(job["id"])


def test_auth_rejections(platform):
    from rafiki_tpu.client import Client, ClientError

    client = Client(admin_port=platform.admin_port)
    with pytest.raises(ClientError) as e:
        client.login("superadmin@rafiki", "wrong")
    assert e.value.status == 401
    # no token → 401
    with pytest.raises(ClientError) as e:
        client.get_models()
    assert e.value.status == 401
    # app developer cannot create users
    client.login("superadmin@rafiki", "rafiki")
    client.create_user("app@x.c", "pw", UserType.APP_DEVELOPER)
    client3 = Client(admin_port=platform.admin_port)
    client3.login("app@x.c", "pw")
    with pytest.raises(ClientError) as e:
        client3.create_user("x@y.z", "pw", UserType.ADMIN)
    assert e.value.status == 403


def test_ownership_enforced(platform, synth_image_data):
    """A non-admin user cannot read or stop another user's jobs."""
    from rafiki_tpu.client import Client, ClientError

    train_path, val_path = synth_image_data
    dev, model = _register_model(platform, name="ff-own")
    job = platform.admin.create_train_job(
        dev["id"], "own-app", TaskType.IMAGE_CLASSIFICATION, [model["id"]],
        {BudgetOption.MODEL_TRIAL_COUNT: 1}, train_path, val_path)

    root = Client(admin_port=platform.admin_port)
    root.login("superadmin@rafiki", "rafiki")
    root.create_user("other@x.c", "pw", UserType.APP_DEVELOPER)
    other = Client(admin_port=platform.admin_port)
    other.login("other@x.c", "pw")
    for fn in (lambda: other.get_train_job(job["id"]),
               lambda: other.stop_train_job(job["id"]),
               lambda: other.get_best_trials_of_train_job(job["id"]),
               lambda: other.create_inference_job(job["id"])):
        with pytest.raises(ClientError) as e:
            fn()
        assert e.value.status == 403
    # admins can read anyone's job
    assert root.get_train_job(job["id"])["id"] == job["id"]
    platform.admin.wait_until_train_job_done(job["id"], timeout=600)


def test_failing_model_trips_circuit_breaker(platform, synth_image_data):
    """A deterministically failing model must not spin forever: the
    worker gives up after max_consecutive_errors."""
    train_path, val_path = synth_image_data
    dev = platform.admin.create_user("fdev@x.c", "pw",
                                     UserType.MODEL_DEVELOPER)
    model = platform.admin.create_model(
        dev["id"], "boom", TaskType.IMAGE_CLASSIFICATION, "AlwaysFails",
        model_source=(
            "from rafiki_tpu.model import BaseModel, FixedKnob\n"
            "class AlwaysFails(BaseModel):\n"
            "    @staticmethod\n"
            "    def get_knob_config():\n"
            "        return {'k': FixedKnob(1)}\n"
            "    def train(self, p, **kw): raise RuntimeError('broken')\n"
            "    def evaluate(self, p): return 0.0\n"
            "    def predict(self, qs): return []\n"
            "    def dump_parameters(self): return {}\n"
            "    def load_parameters(self, p): pass\n"))
    job = platform.admin.create_train_job(
        dev["id"], "boom-app", TaskType.IMAGE_CLASSIFICATION,
        [model["id"]], {BudgetOption.MODEL_TRIAL_COUNT: 100},
        train_path, val_path)
    assert platform.admin.wait_until_train_job_done(job["id"], timeout=120)
    trials = platform.meta.get_trials_of_train_job(job["id"])
    assert 1 <= len(trials) <= 5  # capped, not 100
    assert all(t["status"] == TrialStatus.ERRORED for t in trials)


def test_gpu_count_budget_alias(platform, synth_image_data):
    """Reference scripts pass GPU_COUNT; it maps to CHIP_COUNT."""
    from rafiki_tpu.admin.services_manager import normalize_budget

    b = normalize_budget({"GPU_COUNT": 4, "MODEL_TRIAL_COUNT": 2})
    assert b == {"CHIP_COUNT": 4, "MODEL_TRIAL_COUNT": 2}


def test_parallel_workers_respect_trial_budget(platform, synth_image_data):
    """N workers sharing one advisor must not overshoot MODEL_TRIAL_COUNT
    (the proposal-issuance cap lives in the advisor, the single
    coordinator — worker-side checks alone race)."""
    train_path, val_path = synth_image_data
    dev, model = _register_model(platform, name="ff-budget")
    job = platform.admin.create_train_job(
        dev["id"], "budget-app", TaskType.IMAGE_CLASSIFICATION,
        [model["id"]],
        {BudgetOption.MODEL_TRIAL_COUNT: 3, BudgetOption.CHIP_COUNT: 3},
        train_path, val_path)
    assert platform.admin.wait_until_train_job_done(job["id"], timeout=600)
    trials = platform.meta.get_trials_of_train_job(job["id"])
    assert len(trials) == 3
    assert all(t["status"] == TrialStatus.COMPLETED for t in trials)
    # three distinct workers existed
    train_svcs = [s for s in platform.meta.get_services()
                  if s["service_type"] == ServiceType.TRAIN]
    assert len(train_svcs) == 3


def test_weighted_ensemble_combiner():
    from rafiki_tpu.predictor.predictor import ensemble_predictions

    # A packed worker's reply (weight 2, already the mean of 2 members)
    # plus a single-model worker: result = unweighted mean over 3 trials.
    packed = [0.6, 0.4]   # mean of two members
    single = [0.0, 1.0]
    out = ensemble_predictions([packed, single], weights=[2, 1])
    np.testing.assert_allclose(out, [(0.6 * 2 + 0.0) / 3,
                                     (0.4 * 2 + 1.0) / 3])
    # errors are dropped with their weights
    out = ensemble_predictions([{"error": "x"}, single], weights=[2, 1])
    np.testing.assert_allclose(out, single)
    # non-numeric: weighted majority vote
    assert ensemble_predictions(["a", "b", "a"], weights=[1, 5, 1]) == "b"
    # packed non-numeric members arrive un-combined and vote per trial
    assert ensemble_predictions(
        [{"__members__": ["a", "b"]}, "b"], weights=[2, 1]) == "b"


def test_ensemble_packs_onto_one_chip_group(tmp_path, synth_image_data):
    """With 1 chip and a 2-model ensemble, one worker serves both trials
    (packed) and the endpoint still returns the full-ensemble mean."""
    train_path, val_path = synth_image_data
    p = LocalPlatform(workdir=str(tmp_path / "plat"), http=True,
                      n_chips=1, supervise_interval=0)
    try:
        dev, model = _register_model(p)
        job = p.admin.create_train_job(
            dev["id"], "pack-app", TaskType.IMAGE_CLASSIFICATION,
            [model["id"]], {BudgetOption.MODEL_TRIAL_COUNT: 2},
            train_path, val_path)
        assert p.admin.wait_until_train_job_done(job["id"], timeout=600)
        inf = p.admin.create_inference_job(dev["id"], job["id"],
                                           max_models=2)
        assert len(inf["trial_ids"]) == 2
        # One packed worker (plus the predictor service row), not two:
        workers = [w for w in p.meta.get_inference_job_workers(inf["id"])
                   if w["trial_id"] != "__predictor__"]
        assert len(workers) == 1
        assert set(workers[0]["trial_id"].split(",")) == \
            set(inf["trial_ids"])
        host = p.admin.get_inference_job(inf["id"])["predictor_host"]
        ds = load_image_dataset(val_path)
        from rafiki_tpu.cache import encode_payload
        r = requests.post(f"http://{host}/predict",
                          json={"queries": [encode_payload(ds.images[0])]},
                          timeout=300)
        r.raise_for_status()
        probs = r.json()["predictions"][0]
        assert len(probs) == ds.n_classes
        assert abs(sum(probs) - 1.0) < 1e-3
        p.admin.stop_inference_job(inf["id"])
    finally:
        p.shutdown()


def test_supervise_restarts_dead_train_worker(platform, synth_image_data):
    train_path, val_path = synth_image_data
    dev, model = _register_model(platform, name="ff-sup")
    job = platform.admin.create_train_job(
        dev["id"], "sup-app", TaskType.IMAGE_CLASSIFICATION,
        [model["id"]], {BudgetOption.MODEL_TRIAL_COUNT: 3},
        train_path, val_path)

    # find the running TRAIN service and simulate a dead container: remove
    # it from the runtime without letting it update its status
    train_svcs = [s for s in platform.meta.get_services()
                  if s["service_type"] == ServiceType.TRAIN]
    assert len(train_svcs) == 1
    svc = train_svcs[0]
    worker = platform.container.get(svc["container_id"])
    worker.stop_flag.set()  # silence the thread
    # wait for the thread to die, then force status back to RUNNING as if
    # the process was SIGKILLed before it could report
    deadline = time.monotonic() + 120
    while worker.running and time.monotonic() < deadline:
        time.sleep(0.1)
    with platform.container._lock:
        platform.container._services.pop(svc["id"], None)
    platform.meta.update_service(svc["id"], status=ServiceStatus.RUNNING)

    restarted = platform.services.supervise()
    assert len(restarted) == 1
    assert platform.meta.get_service(svc["id"])["status"] == \
        ServiceStatus.ERRORED
    new_svc = platform.meta.get_service(restarted[0])
    assert new_svc["service_type"] == ServiceType.TRAIN

    # the restarted worker finishes the job
    assert platform.admin.wait_until_train_job_done(job["id"], timeout=600)
    completed = platform.meta.get_trials_of_train_job(
        job["id"], status=TrialStatus.COMPLETED)
    assert len(completed) == 3


def test_supervisor_thread_sweeps_automatically(tmp_path, synth_image_data):
    """A platform with a supervise interval detects a dead worker without
    anyone calling supervise() by hand (the serve-node path)."""
    train_path, val_path = synth_image_data
    p = LocalPlatform(workdir=str(tmp_path / "sup"),
                      supervise_interval=0.2)
    try:
        dev, model = _register_model(p, name="ff-auto-sup")
        job = p.admin.create_train_job(
            dev["id"], "auto-sup", TaskType.IMAGE_CLASSIFICATION,
            [model["id"]], {BudgetOption.MODEL_TRIAL_COUNT: 2},
            train_path, val_path)
        svc = [s for s in p.meta.get_services()
               if s["service_type"] == ServiceType.TRAIN][0]
        worker = p.container.get(svc["container_id"])
        worker.stop_flag.set()
        deadline = time.monotonic() + 120
        while worker.running and time.monotonic() < deadline:
            time.sleep(0.1)
        with p.container._lock:
            p.container._services.pop(svc["id"], None)
        p.meta.update_service(svc["id"], status=ServiceStatus.RUNNING)

        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if p.meta.get_service(svc["id"])["status"] == \
                    ServiceStatus.ERRORED:
                break
            time.sleep(0.2)
        assert p.meta.get_service(svc["id"])["status"] == \
            ServiceStatus.ERRORED, "supervisor thread never swept"
        assert p.admin.wait_until_train_job_done(job["id"], timeout=600)
    finally:
        p.shutdown()


def test_inference_pipeline_env_toggle(monkeypatch):
    """RAFIKI_TPU_SERVING_PIPELINE: 0/1 force the one-burst-in-flight
    overlap off/on;
    the default "auto" defers to a startup sync-latency measurement
    (pipeline is None until the worker's run() resolves it)."""
    from rafiki_tpu.bus import MemoryBus
    from rafiki_tpu.worker.inference import InferenceWorker

    bus = MemoryBus()
    # The operator env tunable may be exported in the ambient shell;
    # the default-behavior assertion needs it absent.
    monkeypatch.delenv("RAFIKI_TPU_SERVING_PIPELINE", raising=False)
    w = InferenceWorker("s", "j", "t", None, None, bus)
    assert w.pipeline is None  # default: auto, resolved at startup
    monkeypatch.setenv("RAFIKI_TPU_SERVING_PIPELINE", "0")
    assert InferenceWorker("s", "j", "t", None, None, bus).pipeline \
        is False
    monkeypatch.setenv("RAFIKI_TPU_SERVING_PIPELINE", "1")
    assert InferenceWorker("s", "j", "t", None, None, bus).pipeline \
        is True
    # An explicit constructor arg beats the env var.
    monkeypatch.setenv("RAFIKI_TPU_SERVING_PIPELINE", "0")
    assert InferenceWorker("s", "j", "t", None, None, bus,
                           pipeline=True).pipeline is True
    # The auto measurement itself: a tiny dispatch round-trip, finite
    # and non-negative (on the CPU test backend it is ~microseconds,
    # which correctly resolves auto to pipelining OFF).
    from rafiki_tpu.worker.inference import _sync_latency

    lat = _sync_latency()
    assert 0.0 <= lat < 5.0


def test_predictor_round_robins_same_bin_replicas():
    """Same-trial-bin workers are REPLICAS: each request picks one per
    bin (rotating), never all — replicas must not double-weight their
    trials in the ensemble."""
    from rafiki_tpu.bus import MemoryBus
    from rafiki_tpu.cache import Cache
    from rafiki_tpu.predictor.predictor import Predictor

    bus = MemoryBus()
    cache = Cache(bus)
    cache.register_worker("job", "wA1", info={"trial_id": "tA"})
    cache.register_worker("job", "wA2", info={"trial_id": "tA"})
    cache.register_worker("job", "wB", info={"trial_id": "tB"})
    p = Predictor("job", bus, worker_wait_timeout=1.0)
    picks = [tuple(sorted(p._choose_workers())) for _ in range(4)]
    for pick in picks:
        assert len(pick) == 2          # one per bin, not three workers
        assert "wB" in pick            # the singleton bin always serves
        assert ("wA1" in pick) != ("wA2" in pick)
    # The replica choice rotates across requests.
    assert len(set(picks)) == 2


def test_predictor_prunes_bins_of_departed_workers():
    """The worker->bin memo must not grow monotonically across worker
    restarts (a long-lived predictor under churn would otherwise leak a
    row per restart, forever)."""
    from rafiki_tpu.bus import MemoryBus
    from rafiki_tpu.cache import Cache
    from rafiki_tpu.predictor.predictor import Predictor

    bus = MemoryBus()
    cache = Cache(bus)
    cache.register_worker("job", "w-live", info={"trial_id": "t"})
    p = Predictor("job", bus, worker_wait_timeout=1.0)
    for i in range(40):  # churned-away workers, memoized then gone
        p._bins[f"w-dead-{i}"] = "t-old"
    assert p._choose_workers() == ["w-live"]
    assert set(p._bins) == {"w-live"}


def test_second_primary_on_same_workdir_is_refused(tmp_path):
    """Two primaries sharing one workdir share a node_id by design
    (restart stability) — so a LIVE second one must be refused at
    startup, before its supervise sweep can kill the first's workers."""
    from rafiki_tpu.platform import LocalPlatform

    p1 = LocalPlatform(workdir=str(tmp_path / "w"), supervise_interval=0)
    try:
        with pytest.raises(RuntimeError, match="another primary"):
            LocalPlatform(workdir=str(tmp_path / "w"),
                          supervise_interval=0)
    finally:
        p1.shutdown()
    # A clean restart of the SAME node (after shutdown) is legitimate.
    LocalPlatform(workdir=str(tmp_path / "w"),
                  supervise_interval=0).shutdown()


@pytest.mark.slow
def test_inference_replica_attach_keeps_ensemble_semantics(
        platform, synth_image_data):
    """attach_inference_workers adds a same-bin replica: predictions
    stay numerically consistent (no double weighting) and the extra
    worker takes live traffic."""
    import requests as rq

    from rafiki_tpu.cache import Cache, encode_payload
    from rafiki_tpu.model import load_image_dataset

    train_path, val_path = synth_image_data
    dev, model = _register_model(platform)
    job = platform.admin.create_train_job(
        dev["id"], "rep-app", TaskType.IMAGE_CLASSIFICATION,
        [model["id"]], {BudgetOption.MODEL_TRIAL_COUNT: 1},
        train_path, val_path)
    assert platform.admin.wait_until_train_job_done(job["id"], timeout=600)
    inf = platform.admin.create_inference_job(dev["id"], job["id"],
                                              max_models=1)
    host = platform.admin.get_inference_job(inf["id"])["predictor_host"]
    cache = Cache(platform.bus)
    deadline = time.monotonic() + 300
    while time.monotonic() < deadline and \
            len(cache.running_workers(inf["id"])) < 1:
        time.sleep(0.2)

    val = load_image_dataset(val_path)
    q = {"queries": [encode_payload(val.images[i]) for i in range(4)]}
    before = rq.post(f"http://{host}/predict", json=q,
                     timeout=120).json()["predictions"]

    attached = platform.admin.attach_inference_workers(inf["id"])
    assert len(attached) == 1
    while time.monotonic() < deadline and \
            len(cache.running_workers(inf["id"])) < 2:
        time.sleep(0.2)
    assert len(cache.running_workers(inf["id"])) == 2

    # Several requests: all succeed (both replicas serve) and match the
    # pre-replica ensemble output — a replica is capacity, not weight.
    for _ in range(4):
        after = rq.post(f"http://{host}/predict", json=q,
                        timeout=120).json()["predictions"]
        np.testing.assert_allclose(np.asarray(after),
                                   np.asarray(before), atol=1e-5)
    platform.admin.stop_inference_job(inf["id"])
