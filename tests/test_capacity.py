"""Trace-replay capacity engine (ISSUE r20): the workload recorder's
store + gating, the deterministic simulator, the policy regression
gate (mutation-red), the predictive scale-ahead A/B, and the
``python -m rafiki_tpu.capacity`` CLI.
"""

import json
import os
import subprocess
import sys
import threading
import time

import pytest
import requests

from rafiki_tpu.admin import capacity
from rafiki_tpu.admin.autoscaler import PolicyKnobs
from rafiki_tpu.bus import MemoryBus
from rafiki_tpu.cache import Cache
from rafiki_tpu.observe import replay, workload
from rafiki_tpu.observe.metrics import registry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _series() -> int:
    m = registry().find("rafiki_tpu_workload_requests_total")
    return len(m.samples()) if m is not None else 0


@pytest.fixture()
def recorder(monkeypatch, tmp_path):
    """The recorder armed at a tmp sink; gate re-resolved both ways."""
    monkeypatch.setenv(workload.WORKLOAD_ENV, "1")
    workload.reset_for_tests()
    workload.configure(str(tmp_path))
    yield str(tmp_path)
    workload.reset_for_tests()


@pytest.fixture()
def recorder_off(monkeypatch, tmp_path):
    monkeypatch.delenv(workload.WORKLOAD_ENV, raising=False)
    workload.reset_for_tests()
    workload.configure(str(tmp_path))
    yield str(tmp_path)
    workload.reset_for_tests()


def _commit_some(n=5):
    for i in range(n):
        req = workload.open_request("job-abc", f"tenant{i % 2}", i + 1)
        assert req is not None
        workload.note_queue_wait(req, 0.002 * i)
        workload.commit(req, 200 if i % 3 else 429, 0.01 + 0.001 * i,
                        reason="" if i % 3 else "queue_full",
                        bins=["t1"])


# --- Recorder: store round-trip, determinism, gating -------------------


def test_recorder_round_trip_is_deterministic(recorder):
    _commit_some(6)
    path = workload.workload_path(recorder)
    assert os.path.exists(path)
    first = workload.load(recorder)
    assert len(first) == 6
    # load() twice: identical records on one re-based timeline
    assert workload.load(recorder) == first
    assert first[0]["off_s"] == 0.0
    assert [r["off_s"] for r in first] == \
        sorted(r["off_s"] for r in first)
    for r in first:
        assert r["job"] == "job-abc"
        assert r["status"] in (200, 429)
        assert r["size"] == workload.size_class(r["n"])
        assert r["dur_ms"] >= r["queue_ms"] >= 0
        assert r["compute_ms"] == pytest.approx(
            r["dur_ms"] - r["queue_ms"], abs=0.01)
    rejected = [r for r in first if r["status"] == 429]
    assert rejected and all(r["reason"] == "queue_full"
                            for r in rejected)
    # the counter accounted every commit, split by outcome
    m = registry().find("rafiki_tpu_workload_requests_total")
    assert int(sum(v for _, v in m.samples())) == 6
    assert m.value(status="backpressure") == 2


def test_recorder_rolls_and_merges_segments(recorder, monkeypatch):
    monkeypatch.setenv(workload.WORKLOAD_MAX_MB_ENV, "0.0001")  # ~105 B
    monkeypatch.setenv(workload.WORKLOAD_RETAIN_SEGMENTS_ENV, "3")
    _commit_some(12)
    segs = workload.segment_paths(recorder)
    # every write rolls at this cap; the LAST write may have frozen the
    # active file too, so only the generation chain is guaranteed
    assert len(segs) > 1 and segs[0].endswith(".3")
    merged = workload.load(recorder)
    assert merged  # bounded retention MAY drop the oldest segments
    assert [r["off_s"] for r in merged] == \
        sorted(r["off_s"] for r in merged)
    # retention bound held: never more than retain + active segments
    assert len(segs) <= 4


def test_recorder_tolerates_torn_tail_and_junk(recorder):
    _commit_some(4)
    path = workload.workload_path(recorder)
    with open(path, "a", encoding="utf-8") as f:
        f.write("not json at all\n")
        f.write('{"no_off_s": true}\n')
        f.write('{"off_s": 9.9, "t": 99.9, "n": 1')  # torn tail
    # junk skipped, torn tail dropped: exactly the 4 committed records
    assert len(workload.load(recorder)) == 4


def test_recorder_off_means_zero_everything(recorder_off):
    before = _series()
    assert not workload.active()
    assert workload.open_request("job", None, 4) is None
    workload.commit(None, 200, 0.01)  # the off path: a no-op
    assert _series() == before
    assert not os.path.exists(workload.workload_path(recorder_off))
    assert workload.load(recorder_off) == []


def test_size_class_vocabulary():
    assert [workload.size_class(n) for n in (1, 2, 3, 4, 5, 8, 9)] == \
        [1, 2, 4, 4, 8, 8, 16]


# --- Recorder at the predictor edge (live mini-stack) ------------------


class _EchoWorker:
    """Bus-level worker answering every scatter (test_attribution's)."""

    def __init__(self, bus):
        self.cache = Cache(bus)
        self.stop_flag = threading.Event()
        self.cache.register_worker("job", "w1", info={"trial_id": "t1"})
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def _loop(self):
        while not self.stop_flag.is_set():
            for it in self.cache.pop_queries("w1", timeout=0.1):
                if "queries" in it:
                    self.cache.send_prediction_batch(
                        it["batch_id"], "w1",
                        [[float(q), 0.0] for q in it["queries"]],
                        shard=it.get("shard"))

    def stop(self):
        self.stop_flag.set()
        self._thread.join(timeout=5)


def _serve(n_requests):
    """One predictor frontend + echo worker; POST n_requests."""
    from rafiki_tpu.predictor.app import PredictorService

    bus = MemoryBus()
    worker = _EchoWorker(bus)
    svc = PredictorService("csvc", "job", meta=None, bus=bus,
                           host="127.0.0.1")
    svc.predictor.worker_wait_timeout = 5.0
    svc.predictor.gather_timeout = 5.0
    svc.batcher.start()
    svc._http.start()
    statuses = []
    try:
        for i in range(n_requests):
            r = requests.post(
                f"http://127.0.0.1:{svc.port}/predict",
                json={"queries": [1, 2, 3, 4]}, timeout=30)
            statuses.append(r.status_code)
    finally:
        svc._http.stop()
        svc.batcher.stop()
        svc.stats.close()
        svc.predictor.close()
        worker.stop()
    return statuses


def test_edge_records_and_simulator_calibrates(recorder):
    """The tentpole loop in miniature: serve through a live mini-stack
    with the recorder on, replay the recorded trace against a fleet
    model fit from the trace's own compute column, and the simulated
    p99 must land in the same band as the live p99 (the simulator is a
    policy ranker, not a latency oracle — docs/capacity.md)."""
    statuses = _serve(31)
    assert statuses == [200] * 31
    trace = workload.load(recorder)
    assert len(trace) == 31
    warm = trace[1:]  # drop the cold-start request from both sides
    live_ms = sorted(r["dur_ms"] for r in warm)
    live_p99 = live_ms[-1]
    live_p50 = live_ms[len(live_ms) // 2]
    fleet = replay.FleetModel.from_trace(warm)
    assert fleet is not None
    report = replay.simulate(warm, fleet=fleet,
                             policy=PolicyKnobs(max_replicas=1))
    assert report["served"] == 30 and report["rejected"] == 0
    sim_p99 = report["latency_ms"]["p99"]
    assert sim_p99 is not None
    # Band anchors: the lower bound keys off the MEDIAN, not the max —
    # one scheduler pause in 30 wall-clock samples inflates live_p99
    # several-fold, and the sim (fit from the compute column) must not
    # be required to reproduce host scheduling noise.
    assert live_p50 / 4 <= sim_p99 <= live_p99 * 4, \
        (sim_p99, live_p50, live_p99)
    # determinism: byte-for-byte identical re-run
    again = replay.simulate(warm, fleet=fleet,
                            policy=PolicyKnobs(max_replicas=1))
    assert json.dumps(report, sort_keys=True) == \
        json.dumps(again, sort_keys=True)


def test_edge_zero_series_when_recorder_off(recorder_off):
    before = _series()
    statuses = _serve(3)
    assert statuses == [200] * 3
    assert _series() == before
    assert not os.path.exists(workload.workload_path(recorder_off))


# --- Simulator + the policy regression gate ----------------------------


def test_policy_gate_green_then_mutation_red():
    """The gate's whole point: the shipped defaults hold the canned
    ramp; a plausibly-bad policy mutation (sluggish scale-up) goes
    RED — loudly, with named violations."""
    good = capacity.policy_gate()
    assert good["ok"] is True and good["violations"] == []
    json.dumps(good)  # the whole report is a JSON-able CI artifact
    bad = capacity.policy_gate(policy=PolicyKnobs(
        queue_high=0.98, max_replicas=1, up_cooldown_s=60.0))
    assert bad["ok"] is False
    assert bad["violations"], bad
    assert bad["rejected"] > good["rejected"]


def test_policy_gate_is_deterministic():
    a = capacity.policy_gate()
    b = capacity.policy_gate()
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)


def test_predictive_scale_ahead_ab_in_sim():
    """Reactive vs predictive on the canned ramp under a slow-
    provisioning fleet: the predictive side must apply >= 1
    ``scale_up:predicted`` and reject STRICTLY fewer."""
    trace = capacity.canned_trace("ramp")
    table = capacity.learn_periodicity(trace, period_s=120.0,
                                       bin_s=10.0)
    sim = replay.SimKnobs(provision_delay_s=6.0, queue_cap=48.0)
    reactive = replay.simulate(trace, sim=sim, policy=PolicyKnobs(),
                               periodicity=table)
    predictive = replay.simulate(
        trace, sim=sim, policy=PolicyKnobs(predict_horizon_s=15.0),
        periodicity=table)
    assert predictive["actions"].get("scale_up:predicted", 0) >= 1, \
        predictive["actions"]
    assert predictive["rejected"] < reactive["rejected"], \
        (predictive["rejected"], reactive["rejected"])


def test_make_policy_rejects_unknown_knobs():
    assert capacity.make_policy({"queue_high": 0.5}).queue_high == 0.5
    with pytest.raises(ValueError, match="unknown policy knob"):
        capacity.make_policy({"queue_hgih": 0.5})


def test_periodicity_learn_load_and_lookup(tmp_path):
    trace = capacity.canned_trace("ramp")
    table = capacity.learn_periodicity(trace, period_s=120.0,
                                       bin_s=10.0)
    # the ramp's tail bins must expect materially more than its head
    assert max(table["qps"][6:]) > 2 * table["qps"][0]
    p = tmp_path / "table.json"
    p.write_text(json.dumps(table))
    loaded = capacity.load_periodicity(str(p))
    assert loaded == table
    assert capacity.expected_qps(table, t=0.0, horizon_s=115.0) == \
        table["qps"][-1]
    # malformed tables fail LOUDLY (NodeConfig.validate relies on it)
    p.write_text(json.dumps({"period_s": 120, "bin_s": 10,
                             "qps": [1.0]}))
    with pytest.raises(ValueError, match="bins"):
        capacity.load_periodicity(str(p))


# --- CLI: python -m rafiki_tpu.capacity --------------------------------
#
# One REAL subprocess proves the `python -m` entrypoint; every other
# case drives cli.main(argv) in-process — same code path past argv,
# without paying a fresh interpreter + jax import per case (the suite
# runs on a 1-core box against a wall-clock budget).


def _cli(capsys, *argv):
    from rafiki_tpu import capacity as cli
    rc = cli.main(list(argv))
    cap = capsys.readouterr()
    return rc, cap.out, cap.err


def test_cli_entrypoint_subprocess_green(tmp_path):
    r = subprocess.run(
        [sys.executable, "-m", "rafiki_tpu.capacity",
         "score", "--trace", "ramp"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    report = json.loads(r.stdout)
    assert report["ok"] is True and report["violations"] == []


def test_cli_score_green_red_and_error_exits(capsys):
    rc, out, _ = _cli(capsys, "score", "--trace", "ramp")
    assert rc == 0
    report = json.loads(out)
    assert report["ok"] is True and report["violations"] == []
    assert "replica_timeline" not in report  # trimmed without --full

    rc, out, _ = _cli(capsys, "score", "--trace", "ramp", "--policy",
                      '{"queue_high": 0.98, "max_replicas": 1, '
                      '"up_cooldown_s": 60.0}')
    assert rc == 1  # the gate went red
    assert json.loads(out)["ok"] is False

    rc, _, err = _cli(capsys, "score", "--trace", "no-such-trace")
    assert rc == 2 and "error:" in err

    rc, _, err = _cli(capsys, "score", "--trace", "ramp", "--policy",
                      '{"bogus_knob": 1}')
    assert rc == 2 and "unknown policy knob" in err


def test_cli_learn_then_score_with_periodicity(capsys, tmp_path):
    out_path = tmp_path / "periodicity.json"
    rc, _, _ = _cli(capsys, "learn", "--trace", "ramp", "--period",
                    "120", "--bin", "10", "--out", str(out_path))
    assert rc == 0
    table = json.loads(out_path.read_text())
    assert len(table["qps"]) == 12
    rc, out, _ = _cli(capsys, "score", "--trace", "ramp",
                      "--provision-delay", "6.0", "--queue-cap", "48",
                      "--periodicity", str(out_path),
                      "--policy", '{"predict_horizon_s": 15.0}')
    assert rc == 0
    report = json.loads(out)
    assert report["actions"].get("scale_up:predicted", 0) >= 1


def test_cli_score_recorded_store(capsys, recorder):
    _commit_some(8)
    rc, out, _ = _cli(capsys, "score", "--trace", str(recorder))
    assert rc == 0
    report = json.loads(out)
    assert report["requests"] == 8
