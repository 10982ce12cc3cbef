"""Trial-lifecycle spans (``observe/phases.py:span``): one helper times
every phase into the phase histogram, holds a profiler annotation on the
trace's own clock, and (for the per-trial phases) appends an event to
the span store under the trial id. Plus the benchmark's seven readers of
those phases, on hand-made records.
"""

import glob
import os
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

from rafiki_tpu.advisor.base import Proposal
from rafiki_tpu.constants import BudgetOption, TrialStatus
from rafiki_tpu.datasets import make_synthetic_token_dataset
from rafiki_tpu.model.base import BaseModel
from rafiki_tpu.model.knobs import FixedKnob
from rafiki_tpu.models import JaxTransformerLM
from rafiki_tpu.observe import phases, trace
from rafiki_tpu.store import MetaStore, ParamStore
from rafiki_tpu.worker.runner import TrialRunner

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: 16 steps at 8 a dispatch: two dispatches a trial.
TINY = {"d_model": 256, "n_layers": 2, "seq_len": 256, "batch_size": 4,
        "learning_rate": 1e-2, "train_steps": 16, "vocab_size": 512,
        "quick_train": False}
DISPATCHES = 2

#: Direct children of ``trial`` / of ``train`` (PHASES' comment).
OF_TRIAL = ("propose", "open", "init", "train", "eval", "dump",
            "feedback", "handover")
OF_TRAIN = ("step_setup", "step_dispatch", "step_wait")


class TinyLM(JaxTransformerLM):
    @staticmethod
    def get_knob_config():
        knobs = dict(JaxTransformerLM.get_knob_config())
        knobs.update({name: FixedKnob(v) for name, v in TINY.items()})
        return knobs


class _FixedAdvisor:
    def __init__(self):
        self.n = 0

    def propose(self):
        self.n += 1
        return Proposal(trial_no=self.n, knobs={})

    def feedback(self, proposal, score):
        pass


def _grown(before, after):
    return {p: {k: after[p][k] - before[p][k] for k in ("sum", "count")}
            for p in after}


def _wait_out_train_workers(timeout=90.0):
    """The phase histogram and the trace's host plane are the whole
    process's, and under ``--dist loadfile`` this process ran other
    test files first. One of them may have left train workers behind:
    after ``platform.shutdown()`` a worker that was training finishes
    its trial, then sits in ``advisor.propose()`` until the RPC's 60-s
    timeout and closes its ``trial`` span (``tests/test_autoscaler.py``'s
    donor job leaves two). Their spans are not this file's: let them
    end before anything is counted."""
    deadline = time.monotonic() + timeout
    for thread in threading.enumerate():
        if thread.name.startswith("train-"):
            thread.join(max(0.0, deadline - time.monotonic()))


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """Two pipelined trials of a tiny LM inside one profiler session at
    the benchmark driver's levels; the span store on. Returns the trial
    rows, the ``rafiki.trial.*`` events of the host plane as (name,
    start_ns, end_ns, stats, thread line), the growth of the phase
    totals, and the span store's directory."""
    tmp = tmp_path_factory.mktemp("spans")
    train, val = make_synthetic_token_dataset(
        str(tmp), n_train=1 << 13, n_val=1 << 11, vocab_size=512,
        branching=2)
    meta = MetaStore(":memory:")
    params = ParamStore(str(tmp / "params"))
    runner = TrialRunner(TinyLM, _FixedAdvisor(), train, val, meta,
                         params, "sub-spans", worker_id="w-spans",
                         budget={BudgetOption.MODEL_TRIAL_COUNT: 2},
                         pipeline_persist=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    trace.configure(str(tmp / "logs"))
    _wait_out_train_workers()
    before = phases.phase_totals()
    steps_before = phases.cache_counts("step")
    jax.profiler.start_trace(str(tmp / "trace"), profiler_options=options)
    try:
        rows = runner.run()
        runner.close()
    finally:
        jax.profiler.stop_trace()
        trace.configure(None)
    grown = _grown(before, phases.phase_totals())
    steps = phases.cache_counts("step")
    meta.close()
    params.close()
    (path,) = glob.glob(str(tmp / "trace" / "**" / "*.xplane.pb"),
                        recursive=True)
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for index, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("rafiki.trial."):
                    events.append((e.name[len("rafiki.trial."):],
                                   e.start_ns, e.start_ns + e.duration_ns,
                                   dict(e.stats), index))
    return {"rows": rows, "events": events, "grown": grown,
            "step_lookups": {e: steps.get(e, 0) - steps_before.get(e, 0)
                             for e in ("hit", "miss")},
            "log_dir": str(tmp / "logs")}


def test_annotations_on_the_host_plane_nest_and_name_their_trial(
        traced_run):
    rows, events = traced_run["rows"], traced_run["events"]
    assert [r["status"] for r in rows] == [TrialStatus.COMPLETED] * 2
    ids = {r["id"][:12] for r in rows}
    # every span of this run's two threads (the line of its ``trial``
    # events, the line of its ``persist`` events) names its trial
    lines = {e[4] for e in events if e[0] in ("trial", "persist")
             and e[3].get("trial") in ids}
    assert len(lines) == 2
    events = [e for e in events if e[4] in lines]
    nameless = [e for e in events if e[3].get("trial") not in ids]
    assert not nameless, nameless
    for tid in ids:
        mine = [e for e in events if e[3]["trial"] == tid]

        def of(name):
            return [e for e in mine if e[0] == name]

        (trial,) = of("trial")  # one per trial
        (train,) = of("train")
        for name in OF_TRIAL:
            (child,) = of(name)
            assert trial[1] <= child[1] <= child[2] <= trial[2], name
            assert child[4] == trial[4], name  # the trial thread
        # direct children of one parent do not overlap
        ordered = sorted((of(n)[0] for n in OF_TRIAL), key=lambda e: e[1])
        assert all(a[2] <= b[1] for a, b in zip(ordered, ordered[1:]))
        assert len(of("step_setup")) == 1
        assert len(of("step_dispatch")) == DISPATCHES
        assert len(of("step_wait")) == DISPATCHES
        for name in OF_TRAIN:
            for child in of(name):
                assert train[1] <= child[1] <= child[2] <= train[2], name
        # the tail runs on the persist thread and names the trial that
        # caused it; it starts once that trial's span has ended
        (persist,) = of("persist")
        (handover,) = of("handover")
        assert persist[4] != trial[4]
        assert handover[1] <= trial[2] <= persist[1]


def test_phase_totals_hold_every_phase_and_count_dispatches(traced_run):
    grown = traced_run["grown"]
    assert set(grown) == set(phases.PHASES)
    assert grown["trial"]["count"] == 2
    for name in OF_TRIAL + ("persist", "step_setup"):
        assert grown[name]["count"] == 2, name
    # the in-trial progress counter: dispatches completed
    assert grown["step_wait"]["count"] == 2 * DISPATCHES
    assert grown["step_dispatch"]["count"] == 2 * DISPATCHES
    children = sum(grown[name]["sum"] for name in OF_TRIAL)
    assert grown["trial"]["sum"] >= children
    # ... and little of a trial lies outside them
    assert grown["trial"]["sum"] - children < 0.05 * grown["trial"]["sum"]
    assert grown["train"]["sum"] >= sum(grown[n]["sum"] for n in OF_TRAIN)


def test_step_cache_counts_two_lookups_a_trial(traced_run):
    """A job of N congruent trials (TinyLM is this file's own class, so
    nothing had its programs before): the train chunk and the
    evaluation's program are built in the first trial and found by
    every later one — 2 misses, 2N - 2 hits."""
    assert traced_run["step_lookups"] == {"miss": 2, "hit": 2}


def test_span_store_holds_the_per_trial_phases_under_the_trial_id(
        traced_run):
    for row in traced_run["rows"]:
        out = trace.collect_trace(traced_run["log_dir"], row["id"])
        names = [s["name"] for s in out["spans"]]
        # the per-trial phases, once each; the per-chunk ones stay out
        assert sorted(names) == sorted(
            f"trial.{p}" for p in OF_TRIAL + ("trial", "persist"))
        by_name = {s["name"]: s for s in out["spans"]}
        assert all(s["attrs"]["trial"] == row["id"][:12]
                   and s["service"] == "w-spans"
                   for s in out["spans"])
        assert {"log_flush_ms", "params_save_ms", "meta_commit_ms"} <= \
            set(by_name["trial.persist"]["attrs"])


# --- hand-over wait ---------------------------------------------------

class _FakeModel(BaseModel):
    @staticmethod
    def get_knob_config():
        return {"width": FixedKnob(32)}

    def train(self, path, *, shared_params=None, **kw):
        self._params = {"w": np.asarray(1.0)}

    def evaluate(self, path):
        return 0.5

    def predict(self, queries):
        return [0 for _ in queries]

    def dump_parameters(self):
        return dict(self._params)

    def load_parameters(self, params):
        self._params = dict(params)


def test_handover_grows_by_the_time_the_previous_tail_is_held(
        tmp_path, monkeypatch):
    hold = 0.4
    meta = MetaStore(":memory:")
    params = ParamStore(str(tmp_path / "p"))
    save = params.save
    calls = []

    def slow_first_save(ps, **kw):
        calls.append(1)
        if len(calls) == 1:
            time.sleep(hold)
        return save(ps, **kw)

    monkeypatch.setattr(params, "save", slow_first_save)
    runner = TrialRunner(_FakeModel, _FixedAdvisor(), "tr", "va", meta,
                         params, "sub-hold",
                         budget={BudgetOption.MODEL_TRIAL_COUNT: 2},
                         pipeline_persist=True)
    before = phases.phase_totals()
    runner.run_one()  # its tail holds the stage for ``hold`` seconds
    first = _grown(before, phases.phase_totals())["handover"]
    runner.run_one()  # must wait for it
    runner.close()
    grown = _grown(before, phases.phase_totals())
    assert first["count"] == 1 and first["sum"] < 0.1 * hold
    second = grown["handover"]["sum"] - first["sum"]
    # the fake trial itself takes milliseconds, so it waits nearly all
    assert 0.7 * hold < second < hold + 0.5
    assert grown["persist"]["count"] == 2
    assert grown["persist"]["sum"] >= hold
    meta.close()
    params.close()


def test_pipeline_off_handover_contains_persist(tmp_path):
    meta = MetaStore(":memory:")
    params = ParamStore(str(tmp_path / "p"))
    runner = TrialRunner(_FakeModel, _FixedAdvisor(), "tr", "va", meta,
                         params, "sub-inline")
    before = phases.phase_totals()
    row = runner.run_one()
    grown = _grown(before, phases.phase_totals())
    assert row["status"] == TrialStatus.COMPLETED
    assert grown["handover"]["sum"] >= grown["persist"]["sum"] > 0
    meta.close()
    params.close()


def test_span_observes_on_exception_and_metrics_off(monkeypatch):
    before = phases.phase_totals()["eval"]["count"]
    with pytest.raises(RuntimeError):
        with phases.span("eval"):
            raise RuntimeError("boom")
    assert phases.phase_totals()["eval"]["count"] == before + 1
    monkeypatch.setenv("RAFIKI_TPU_METRICS", "0")
    with phases.span("eval"):
        pass
    assert phases.phase_totals()["eval"]["count"] == before + 1


# --- the benchmark's readers ------------------------------------------

@pytest.fixture()
def load_reader():
    """``benchmarks/metrics/<name>.py`` as ``run.py`` loads it."""
    bench = os.path.join(ROOT, "benchmarks")
    sys.path.insert(0, bench)
    try:
        from harness import load_module
        yield lambda name: load_module("metrics", name)
    finally:
        sys.path.remove(bench)
        sys.modules.pop("harness", None)


def _record(grow, trials=4):
    """A driver's record in which each phase's sum grew by ``grow``
    seconds over ``trials`` observations; phases not named stand still."""
    def totals(scale):
        return {p: {"sum": 10.0 + scale * grow.get(p, 0.0),
                    "count": 3 + scale * (trials if p in grow else 0)}
                for p in phases.PHASES}
    return {"window": {"trials": trials, "seconds": 64.0},
            "phase_open": totals(0), "phase_close": totals(1)}


GROWN = {"trial": 64.0, "propose": 0.08, "open": 0.04, "init": 0.02,
         "train": 50.0, "step_wait": 48.0, "eval": 4.8, "dump": 5.2,
         "feedback": 0.06, "handover": 3.6, "persist": 20.0}

READERS = {
    "propose_ms": (20.0, "propose"),
    "eval_ms": (1200.0, "eval"),
    "dump_ms": (1300.0, "dump"),
    "handover_wait_ms": (900.0, "handover"),
    "persist_ms": (5000.0, "persist"),
    "train_host_ms": (500.0, "step_wait"),
    # 64 - (0.08 + 0.04 + 0.02 + 50 + 4.8 + 5.2 + 0.06 + 3.6) = 0.2 s
    "trial_unattributed_ms": (50.0, "feedback"),
}


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_gives_ms_per_trial(load_reader, name):
    expected, _ = READERS[name]
    assert load_reader(name).read(_record(GROWN)) == \
        pytest.approx(expected, rel=1e-9)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_gives_none_where_there_is_nothing_to_read(load_reader,
                                                          name):
    read = load_reader(name).read
    _, needs = READERS[name]
    # a phase it reads was not observed in the window
    still = {p: s for p, s in GROWN.items() if p != needs}
    assert read(_record(still)) is None
    # a parent commit's program has no such phase at all
    old = _record(GROWN)
    for edge in ("phase_open", "phase_close"):
        old[edge] = {p: old[edge][p] for p in
                     ("propose", "load", "stage", "train", "eval",
                      "persist")}
    if needs not in old["phase_open"]:
        assert read(old) is None
    # no snapshot, no trial
    assert read(dict(_record(GROWN), phase_open=None)) is None
    assert read(dict(_record(GROWN),
                     window={"trials": 0, "seconds": 64.0})) is None


def test_benchmark_lists_each_reader_for_its_cells():
    """Bare for ``lm14-final``, ``search.`` for ``lm14-search``, ``moe.``
    for the sparse-expert cell, ``lfm2.`` for the hybrid convolution /
    attention one, ``nemo.`` for the hybrid Mamba-2 / attention one."""
    import json

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {"": "lm14-final", "search.": "lm14-search",
             "moe.": "joyai-flash-final", "lfm2.": "lfm2-moe-final",
             "nemo.": "nemotron-mamba-final"}
    mine = [m for m in bench["per_layer"]
            if m["name"].rsplit(".", 1)[-1] in READERS]
    assert sorted(m["name"] for m in mine) == sorted(
        [f"{prefix}{n}" for prefix in cells for n in READERS])
    for m in mine:
        prefix = m["name"][:m["name"].rfind(".") + 1]
        assert m["moves"] == ("search." if prefix == "search." else "") \
            + "trials_per_hour"
        assert m["workloads"] == [cells[prefix]]
        assert (m["unit"], m["better"], m["source"]) == \
            ("ms", "lower", "program_counter")


# --- no jax -----------------------------------------------------------

def test_phases_import_and_span_work_where_jax_is_never_imported():
    code = (
        "import sys\n"
        "from rafiki_tpu.observe import phases\n"
        "with phases.span('eval', trial='abc') as sp:\n"
        "    sp.attrs['x'] = 1\n"
        "assert phases.phase_totals()['eval']['count'] == 1\n"
        "assert set(phases.phase_totals()) == set(phases.PHASES)\n"
        "assert not any(m == 'jax' or m.startswith('jax.')\n"
        "               for m in sys.modules), 'jax was imported'\n"
        "print('ok')\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"
