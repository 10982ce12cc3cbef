"""Set-up phases and jax's compile stages as phases
(``observe/phases.py``): the spans that split a job's time to its first
completed trial, the ``jax.monitoring`` listener that turns jax's own
trace / lower / compile / cache-load events into phases, and the
benchmark's readers of both.
"""

import collections
import json
import os
import subprocess
import sys
import threading
import time

import jax
import jax.monitoring
import numpy as np
import pytest

from rafiki_tpu.admin import Admin
from rafiki_tpu.constants import BudgetOption, TaskType, TrialStatus, UserType
from rafiki_tpu.observe import phases
from rafiki_tpu.platform import LocalPlatform
from rafiki_tpu.store import MetaStore, ParamStore

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: The set-up phases a job records in the process that holds it; the
#: fifth, ``backend_init``, once a process (a subprocess test below).
JOB_SETUP = ("platform_start", "model_upload", "job_submit", "worker_start")

TRACE = "/jax/core/compile/jaxpr_trace_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"

QUICK = (
    "import numpy as np\n"
    "from rafiki_tpu.model import BaseModel, FixedKnob\n"
    "class Quick(BaseModel):\n"
    "    @staticmethod\n"
    "    def get_knob_config():\n"
    "        return {'k': FixedKnob(1)}\n"
    "    def train(self, p, **kw): self._w = np.ones(3, np.float32)\n"
    "    def evaluate(self, p): return 0.5\n"
    "    def predict(self, qs): return [0 for _ in qs]\n"
    "    def dump_parameters(self): return {'w': self._w}\n"
    "    def load_parameters(self, p): self._w = p['w']\n")


def _wait_out_train_workers(timeout=90.0):
    """Other test files of this process may have left train workers
    behind, whose spans are not this file's (test_trial_spans.py has the
    story): let them end first."""
    deadline = time.monotonic() + timeout
    for thread in threading.enumerate():
        if thread.name.startswith("train-"):
            thread.join(max(0.0, deadline - time.monotonic()))


@pytest.fixture()
def ended_spans(monkeypatch):
    """Every span that ends while the test runs, as (phase, start, end,
    attrs) on the monotonic clock: the span's own edges."""
    seen = []
    exit_ = phases.span.__exit__

    def recording_exit(self, *exc):
        seen.append((self.phase, self._t0, time.monotonic(),
                     dict(self.attrs)))
        return exit_(self, *exc)

    monkeypatch.setattr(phases.span, "__exit__", recording_exit)
    return seen


@pytest.fixture()
def observed(monkeypatch):
    """The phases the listener observes on THIS thread, as (phase,
    seconds): other threads of the process may compile meanwhile. The
    thread's memory of earlier intervals starts empty."""
    phases.watch_jit()
    monkeypatch.setattr(phases, "_jit_local", threading.local())
    me = threading.get_ident()
    seen = []
    observe = phases.observe_phase

    def recording(phase, seconds):
        if threading.get_ident() == me:
            seen.append((phase, seconds))
        observe(phase, seconds)

    monkeypatch.setattr(phases, "observe_phase", recording)
    return seen


# --- the spans of a job ------------------------------------------------

def test_a_job_records_each_setup_phase_once_and_none_overlap(
        tmp_path, ended_spans):
    _wait_out_train_workers()
    before = phases.phase_totals()
    platform = LocalPlatform(workdir=str(tmp_path / "plat"),
                             supervise_interval=0)
    try:
        dev = platform.admin.create_user("setup@x.c", "pw",
                                         UserType.MODEL_DEVELOPER)
        model = platform.admin.create_model(
            dev["id"], "quick", TaskType.IMAGE_CLASSIFICATION, "Quick",
            model_source=QUICK)
        job = platform.admin.create_train_job(
            dev["id"], "setup-app", TaskType.IMAGE_CLASSIFICATION,
            [model["id"]], {BudgetOption.MODEL_TRIAL_COUNT: 1}, "tr", "va")
        assert platform.admin.wait_until_train_job_done(job["id"],
                                                        timeout=120)
        (trial,) = platform.meta.get_trials_of_train_job(job["id"])
    finally:
        platform.shutdown()
    assert trial["status"] == TrialStatus.COMPLETED
    after = phases.phase_totals()
    for phase in JOB_SETUP:
        assert after[phase]["count"] - before[phase]["count"] == 1, phase
    tid = trial["id"][:12]
    mine = [s for s in ended_spans if s[0] in JOB_SETUP
            or (s[0] in ("trial", "persist") and s[3].get("trial") == tid)]
    assert sorted(s[0] for s in mine) == sorted(
        JOB_SETUP + ("trial", "persist"))
    # pairwise disjoint, in this order
    mine.sort(key=lambda s: s[1])
    assert [s[0] for s in mine] == list(JOB_SETUP) + ["trial", "persist"]
    for a, b in zip(mine, mine[1:]):
        assert a[2] <= b[1], (a, b)


@pytest.mark.parametrize("metrics_on", [True, False])
def test_backend_init_once_a_process_and_one_listener_pair(metrics_on):
    """The test process's backend is up already: a fresh process calls
    ``ensure_platform`` twice; the first resolve is timed once, the
    listeners are installed once, and neither under
    ``RAFIKI_TPU_METRICS=0``."""
    code = (
        "from jax._src import monitoring as m\n"
        "from rafiki_tpu import jaxenv\n"
        "from rafiki_tpu.observe import phases\n"
        "jaxenv.ensure_platform()\n"
        "jaxenv.ensure_platform()\n"
        "spans = sum(f is phases._on_jit_span\n"
        "            for f in m.get_event_time_span_listeners())\n"
        "events = sum(f is phases._on_jit_event\n"
        "             for f in m.get_event_listeners())\n"
        "print(phases.phase_totals()['backend_init']['count'], spans,"
        " events)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               RAFIKI_TPU_METRICS="1" if metrics_on else "0")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == (["1", "1", "1"] if metrics_on
                                  else ["0", "0", "0"])


def test_resident_reads_trial_phases_only(tmp_path, monkeypatch):
    """A process that ran no trial (subprocess workers) still counts its
    own set-up and compile phases: ``GET /trial_phases`` must not read
    that as resident workers."""
    meta = MetaStore(":memory:")
    admin = Admin(meta, ParamStore(str(tmp_path / "p")), services=None)

    def totals(*counted):
        return {p: {"sum": 1.0 if p in counted else 0.0,
                    "count": int(p in counted)} for p in phases.PHASES}

    monkeypatch.setattr(phases, "phase_totals",
                        lambda: totals("backend_init", "platform_start",
                                       "jit_trace", "jit_compile"))
    assert admin.get_trial_phases()["resident"] is False
    monkeypatch.setattr(phases, "phase_totals",
                        lambda: totals("platform_start", "trial"))
    assert admin.get_trial_phases()["resident"] is True
    meta.close()


# --- the listener --------------------------------------------------------

@pytest.mark.parametrize("hit", [True, False])
def test_backend_compile_is_a_cache_load_when_a_hit_fired_inside(
        observed, hit):
    start = time.time()
    if hit:
        jax.monitoring.record_event(phases.CACHE_HIT_EVENT)
    end = time.time() + 0.25
    jax.monitoring.record_event_time_span(COMPILE, start, end,
                                          fun_name="synthetic")
    assert observed == [("jit_cache_load" if hit else "jit_compile",
                         pytest.approx(end - start))]
    # the next compile holds no hit of its own
    later = time.time() + 1.0
    jax.monitoring.record_event_time_span(COMPILE, later, later + 0.5,
                                          fun_name="synthetic")
    assert observed[-1] == ("jit_compile", pytest.approx(0.5))


def test_an_inner_trace_reported_first_counts_once(observed):
    s = time.time()
    record = jax.monitoring.record_event_time_span
    record(TRACE, s + 0.1, s + 0.3, fun_name="inner")
    record(TRACE, s + 0.4, s + 0.5, fun_name="inner2")
    record(TRACE, s, s + 1.0, fun_name="outer")  # contains both
    record(TRACE, s + 2.0, s + 2.25, fun_name="sibling")
    assert [p for p, _ in observed] == ["jit_trace"] * 4
    seconds = [x for _, x in observed]
    assert seconds[:3] == pytest.approx([0.2, 0.1, 0.7])
    assert sum(seconds[:3]) == pytest.approx(1.0)  # the outer's time
    assert seconds[3] == pytest.approx(0.25)


def test_a_fresh_jit_grows_trace_lower_and_compile_by_one(observed):
    x = np.arange(4.0, dtype=np.float32)
    fresh = jax.jit(lambda a: jax.lax.mul(a, a))  # no jit inside
    before = phases.phase_totals()
    del observed[:]
    np.testing.assert_allclose(np.asarray(fresh(x)), x * x)
    assert collections.Counter(p for p, _ in observed) == {
        "jit_trace": 1, "jit_lower": 1, "jit_compile": 1}
    after = phases.phase_totals()
    for phase in ("jit_trace", "jit_lower", "jit_compile"):
        assert after[phase]["count"] >= before[phase]["count"] + 1
        assert after[phase]["sum"] > before[phase]["sum"]
    # a second call dispatches the compiled program: no event at all
    fresh(x)
    assert len(observed) == 3


# --- the benchmark's readers ---------------------------------------------

def _record():
    """A benchmark run's record: what the program's totals held when the
    window opened, and 2 trials' growth of tracing and lowering by its
    close."""
    at_open = {"backend_init": (5.0, 1), "platform_start": (0.5, 1),
               "model_upload": (0.25, 1), "job_submit": (0.125, 1),
               "worker_start": (1.0, 1), "trial": (14.0, 1),
               "persist": (2.0, 1), "jit_trace": (3.0, 40),
               "jit_lower": (1.5, 40), "jit_compile": (0.75, 5),
               "jit_cache_load": (6.0, 30)}
    grown = {"jit_trace": (4.0, 20), "jit_lower": (1.0, 20)}

    def totals(extra):
        out = {}
        for p in phases.PHASES:
            s, n = at_open.get(p, (0.0, 0))
            gs, gn = extra.get(p, (0.0, 0))
            out[p] = {"sum": s + gs, "count": n + gn}
        return out
    return {"setup_s": 40.0, "window": {"trials": 2, "seconds": 51.0},
            "phase_open": totals({}), "phase_close": totals(grown)}


READERS = {
    "setup_backend_s": 5.0,
    "setup_platform_s": 0.5,
    "setup_submit_s": 1.375,
    "setup_warmup_s": 16.0,
    "setup_trace_s": 4.5,
    "setup_compile_s": 0.75,
    "setup_cache_load_s": 6.0,
    # 40 - (5 + 0.5 + 1.375 + 16)
    "setup_unattributed_s": 17.125,
    # (4 + 1) / 2 trials
    "trace_s_per_trial": 2.5,
}

#: The phases a parent's program has: those before this set-up split.
PARENT = phases.PHASES[:phases.PHASES.index("persist") + 1]


@pytest.mark.parametrize("name", sorted(READERS))
def test_setup_reader_reads_a_record_and_none_on_a_parent(name):
    bench = os.path.join(ROOT, "benchmarks")
    sys.path.insert(0, bench)
    try:
        from harness import load_module

        read = load_module("metrics", name).read
        assert read(_record()) == pytest.approx(READERS[name], rel=1e-12)
        parent = _record()
        for edge in ("phase_open", "phase_close"):
            parent[edge] = {p: parent[edge][p] for p in PARENT}
        assert read(parent) is None
        # nothing observed: the program's metrics were off
        off = _record()
        for edge in ("phase_open", "phase_close"):
            off[edge] = {p: {"sum": 0.0, "count": 0}
                         for p in phases.PHASES}
        assert read(off) is None
        if name == "setup_warmup_s":  # the warm-up trial alone, or None
            twice = _record()
            twice["phase_open"]["persist"]["count"] = 2
            assert read(twice) is None
    finally:
        sys.path.remove(bench)
        sys.modules.pop("harness", None)


def test_benchmark_appends_the_nine_setup_entries():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = ["lm14-final", "lm14-search", "joyai-flash-final",
             "lfm2-moe-final", "nemotron-mamba-final"]
    assert [c["name"] for c in bench["workloads"]] == cells
    layers = {"setup_backend_s": "device",
              "setup_platform_s": "admin + scheduler",
              "setup_submit_s": "admin + scheduler",
              "setup_warmup_s": "train worker",
              "setup_trace_s": "model step",
              "setup_compile_s": "model step",
              "setup_cache_load_s": "model step",
              "setup_unattributed_s": "client / HTTP edge"}
    new = bench["per_layer"][-9:]
    assert [m["name"] for m in new] == list(layers) + [
        "search.trace_s_per_trial"]
    for m in new[:8]:
        assert m == {"name": m["name"], "unit": "s", "better": "lower",
                     "source": "program_counter",
                     "layer": layers[m["name"]], "moves": "setup_s",
                     "workloads": cells}
    assert new[8] == {"name": "search.trace_s_per_trial", "unit": "s",
                      "better": "lower", "source": "program_counter",
                      "layer": "train worker",
                      "moves": "search.trials_per_hour",
                      "workloads": ["lm14-search"]}
    for m in new:
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "metrics",
            m["name"].rsplit(".", 1)[-1] + ".py"))
    # nothing before them reads a set-up phase
    assert not any(m["name"].rsplit(".", 1)[-1] in READERS
                   for m in bench["per_layer"][:-9])
