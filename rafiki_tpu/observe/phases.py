"""Trial-lifecycle, set-up and compile-stage phase metrics, folded into
the unified registry.

The trial hot loop (propose -> load -> stage -> train -> eval ->
persist) is where the training plane's trials/hour lives; where its
time goes today is ``PERF.md`` §5 (the image zoo's short trials have not
been re-measured since 2026-07-31: ``PERF.md`` §7 row 1). These series
make the breakdown measurable the same way the serving stage histogram
did for the frontend:

- ``rafiki_tpu_trial_phase_seconds{phase=}`` — wall time per phase,
  every one but the ``jit_*`` ones timed by ``span`` below (``PHASES``
  lists them with their nesting). The TrialRunner records the
  lifecycle steps of ``trial``; ``load`` (dataset parse from disk) and
  ``stage`` (full-dataset host->device transfer) are SUB-SPANS recorded
  inside ``model.train()``/``model.evaluate()`` — they are contained in the
  train/eval phases, not additive with them. With the residency caches
  warm, load+stage collapse to ~0 for trial 2..N of a sub-train-job.
  The count of ``step_wait`` is a trial's progress from outside:
  dispatches completed (times ``steps_per_dispatch`` = steps).
  The same histogram holds the set-up phases, timed by ``span`` where
  the work happens, once a process or once a job: the TPU runtime's
  start (``backend_init``), the platform's (``platform_start``), a
  model's upload, a job's submission and its worker's start. Between
  them and the warm-up trial's ``trial`` and ``persist`` they split a
  job's time to its first completed trial, with no overlap. And it
  holds jax's compile stages as jax itself times them (``watch_jit``):
  ``jit_trace``, ``jit_lower``, and the backend compile as
  ``jit_compile`` (a real XLA compile) or ``jit_cache_load`` (a read
  from the persistent cache), so the two counts are the process's
  cache misses and hits. Those are nested in whatever span was open.
- ``rafiki_tpu_trial_dataset_cache_total{event=hit|miss|evict}`` and
  ``rafiki_tpu_trial_stage_cache_total{event=hit|miss|evict}`` — the
  host dataset cache (``model/dataset.py``) and device staging cache
  (``model/jax_model.py``) hit/miss/eviction counters. Trial 2..N of a
  job performing ZERO disk loads and ZERO full-dataset H2D shows up as
  misses staying flat while hits grow (``tests/test_trial_pipeline.py::
  test_trial_2_zero_disk_loads_and_zero_h2d``).
- ``rafiki_tpu_trial_step_cache_total{event=hit|miss}`` — lookups of
  the compiled-step cache (``model/jax_model.py:_step_cache_get``), one
  per train / init / eval program a trial asks for, whatever the model
  class. A job of congruent trials shows its misses staying flat after
  the first trial; a miss per trial is a program built (and, where its
  constants differ, compiled) per trial.
- ``rafiki_tpu_trial_dump_leaves_total{where=device|host}`` — how a
  finished trial's parameters reached the persist stage
  (``dump_leaves``, once a trial, by leaf): still on the device, so
  that the stage copies them to the host behind the next trial's steps
  (span ``persist``, attrs ``d2h_ms`` / ``d2h_bytes``), or already host
  arrays, which the model paid for inside span ``dump`` with the chip
  idle. A runner without the stage counts nothing.
- ``rafiki_tpu_moe_assignments_total{where=held|absent}`` and
  ``rafiki_tpu_moe_busiest_expert_total`` — what a sparse-expert
  model's train steps routed (``moe_routed``, once a dispatch, from the
  sums the step returns beside loss and accuracy): token-to-expert
  assignments to experts this rank holds and computes, to experts it
  does not, and the busiest held expert's assignments summed over
  steps and sparse blocks. held + absent = tokens x experts a token x
  sparse blocks, exactly; busiest x experts held / held = the held
  experts' load imbalance.
- ``rafiki_tpu_lm_layers_total{kind=conv|attention|ssm|none,
  ffn=dense|sparse|none}`` — the layers a hybrid LM's trial really
  built, by sequence operator and feed-forward (``lm_layers``, once a
  trial, from the stacks of the parameter tree the train step is
  handed): a run says which layer pattern it trained. A layer of one
  mixer alone (Nemotron-H's) counts ``none`` for what it lacks: a
  Mamba-2 layer ``kind=ssm,ffn=none``, an experts layer
  ``kind=none,ffn=sparse``.
- ``rafiki_tpu_trial_dataset_cache_bytes`` /
  ``rafiki_tpu_trial_stage_cache_bytes`` — current cache occupancy
  against the ``RAFIKI_TPU_DATASET_CACHE_BYTES`` /
  ``RAFIKI_TPU_STAGE_CACHE_BYTES`` budgets.

Stdlib-only (this module is imported by ``model/dataset.py``, which
must stay importable without jax). Labels are bounded: phase names and
cache event kinds only — deliberately NOT per-trial, so the families
never need per-trial series cleanup and the benchmark can read
cumulative sums across a whole window.
"""

from __future__ import annotations

import collections
import sys
import threading
import time
from typing import Any, Dict, Optional

from . import metrics
from . import trace as _trace

#: Every phase ``span`` times. The helper records no parent: nesting is
#: by time on one thread, as indented here. Direct children of
#: ``trial`` never overlap, so ``trial`` minus their sum is what no span
#: covers. A ``propose`` that ends the search (it returns None) still
#: closes one ``trial`` around itself.
#:
#:   trial            the whole of TrialRunner.run_one       trial thread
#:     propose        advisor.propose()
#:     open           shared-params retrieve, validate_knobs, trial row
#:     init           model_class(**knobs)
#:     train          model.train
#:       load, stage    dataset parse / H2D (also inside eval)
#:       step_setup     LM: entry to the loop (params, step cache, opt)
#:       step_dispatch  LM, each chunk: windows, device_put, train_chunk
#:                      (its trace and compile on a step-cache miss)
#:       step_wait      LM, each chunk: host blocked on the device
#:     eval           model.evaluate
#:     dump           model.dump_parameters(): the leaves handed on
#:                    (LM, image zoo: device arrays as they lie, no
#:                    copy started, no wait for the bytes)
#:     feedback       advisor.feedback
#:     handover       wait for the previous trial's tail to leave the
#:                    persist stage (pipeline off: contains persist)
#:   persist          the tail: log flush, the device leaves' copy to
#:                    the host, param save, meta commit (persist
#:                    thread; starts once its trial's span has ended,
#:                    overlaps the NEXT trial)
#:
#: Set-up phases, once a process or once a job, not per trial. In a
#: job's first seconds they follow one another and never overlap, and
#: the warm-up trial's ``trial`` and ``persist`` come after them:
#:
#:   backend_init     jaxenv.ensure_platform's first resolve: the
#:                    compile cache placed, jax.devices() (the TPU
#:                    runtime's start); once a process
#:   platform_start   the whole of LocalPlatform.__init__
#:   model_upload     Admin.create_model
#:   job_submit       Admin.create_train_job up to the services'
#:                    launch (validation, rows); the launch starts the
#:                    worker thread, so it is left to worker_start
#:   worker_start     TrainWorker.run from entry to the TrialRunner
#:                    built (meta reads, model class, chip binding,
#:                    advisor); the runner's first ``trial`` follows
#:
#: jax's compile stages (``watch_jit``), observed as jax reports them,
#: wherever they happen (set-up, a trial's ``step_dispatch``, a serving
#: worker): each stage's sum is the union of its intervals on a thread.
#:
#:   jit_trace        Python tracing to a jaxpr
#:   jit_lower        jaxpr to an MLIR module
#:   jit_compile      an XLA compile (no persistent-cache hit inside)
#:   jit_cache_load   a read from the persistent compile cache
PHASES = ("trial", "propose", "open", "init", "train", "load", "stage",
          "step_setup", "step_dispatch", "step_wait", "eval", "dump",
          "feedback", "handover", "persist",
          "backend_init", "platform_start", "model_upload", "job_submit",
          "worker_start",
          "jit_trace", "jit_lower", "jit_compile", "jit_cache_load")

#: The phases of a trial (``PHASES`` up to ``persist``): they count only
#: in a process that runs trials.
TRIAL_PHASES = PHASES[:PHASES.index("persist") + 1]

#: jax's compile-stage events (``jax._src.dispatch``) and the phase
#: each becomes; a backend compile with a persistent-cache hit inside
#: it on its thread becomes ``jit_cache_load``.
JIT_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit_lower",
    "/jax/core/compile/backend_compile_duration": "jit_compile",
}
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

#: Intervals a thread remembers a stage by, to take an inner event's
#: time out of the outer one that contains it: the newest top-level
#: ones. An outer event over more inner ones than this counts the
#: oldest twice.
JIT_REMEMBERED = 256

#: The counted caches (``cache_event`` / ``cache_counts``): the host
#: dataset cache, the device staging cache, the compiled-step cache.
CACHES = ("dataset", "stage", "step")

#: Trial phases span four orders of magnitude more than a bus push:
#: a warm load/stage is sub-millisecond, a real train phase minutes.
PHASE_BUCKETS = (0.001, 0.005, 0.025, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                 10.0, 30.0, 60.0, 300.0, 1800.0)

_m: Optional[Dict[str, object]] = None


def _reg() -> Dict[str, object]:
    global _m
    if _m is None:
        r = metrics.registry()
        _m = {
            "phase": r.histogram(
                "rafiki_tpu_trial_phase_seconds",
                "Wall time of one trial-lifecycle phase, of one set-up "
                "phase (once a process or job) or of one of jax's "
                "compile stages (jit_*) (phase=" + "|".join(PHASES) +
                "; nesting: observe/phases.py PHASES)",
                buckets=PHASE_BUCKETS),
            "dataset_cache": r.counter(
                "rafiki_tpu_trial_dataset_cache_total",
                "Host dataset cache events (event=hit|miss|evict)"),
            "stage_cache": r.counter(
                "rafiki_tpu_trial_stage_cache_total",
                "Device staging cache events (event=hit|miss|evict)"),
            "step_cache": r.counter(
                "rafiki_tpu_trial_step_cache_total",
                "Compiled-step cache lookups (event=hit|miss)"),
            "dump_leaves": r.counter(
                "rafiki_tpu_trial_dump_leaves_total",
                "Parameter leaves of finished trials as the persist "
                "stage found them (where=device: a jax.Array, copied "
                "to the host there, behind the next trial; host: the "
                "model had copied it inside its dump)"),
            "moe_assignments": r.counter(
                "rafiki_tpu_moe_assignments_total",
                "Token-to-expert assignments a sparse-expert model's "
                "train steps routed (where=held: to an expert this "
                "rank holds and computes; absent: to one it does not)"),
            "moe_busiest": r.counter(
                "rafiki_tpu_moe_busiest_expert_total",
                "Sum over train steps and sparse blocks of the busiest "
                "held expert's assignments (x experts held / held "
                "assignments = load imbalance)"),
            "lm_layers": r.counter(
                "rafiki_tpu_lm_layers_total",
                "Layers of the stacks a hybrid LM's trials built "
                "(kind=conv|attention|ssm|none: the sequence operator; "
                "ffn=dense|sparse|none)"),
            "dataset_cache_bytes": r.gauge(
                "rafiki_tpu_trial_dataset_cache_bytes",
                "Bytes held by the host dataset cache"),
            "stage_cache_bytes": r.gauge(
                "rafiki_tpu_trial_stage_cache_bytes",
                "Bytes held by the device staging cache"),
        }
    return _m


def observe_phase(phase: str, seconds: float) -> None:
    """Record one phase duration. Always-on cheap (one histogram
    observe); ``RAFIKI_TPU_METRICS=0`` disables it wholesale."""
    if metrics.metrics_enabled():
        # rta: disable=RTA301 phase is drawn from the fixed PHASES tuple; deliberately immortal (module docstring)
        _reg()["phase"].observe(seconds, phase=phase)


class span:
    """``with phases.span("eval"): ...`` — the one way a trial phase is
    timed. On exit, normal or by exception, the duration goes to the
    phase histogram (``observe_phase``). For its duration the block
    holds a ``jax.profiler.TraceAnnotation("rafiki.trial.<phase>")``,
    so a profiler session (``RAFIKI_TPU_TRACE_DIR``, an on-demand
    profile) shows the phase on the device trace's own clock; with no
    session the annotation is inert. Taken only when jax is already
    imported: this module stays stdlib-only.

    ``attrs`` become the annotation's stats. Every span of one trial
    carries ``trial=<first 12 of the id>``: given, or else the label
    the TrialRunner bound on this thread (``metrics.label_context``),
    which is how a model's spans name a trial they know nothing of.

    ``ctx`` (a ``TraceContext``) also appends a ``trial.<phase>`` event
    to the span store under that trace, with ``self.attrs`` as they
    stand at exit, so a block can add what it learned
    (``sp.attrs["params_save_ms"] = ...``)."""

    __slots__ = ("phase", "attrs", "_ctx", "_service", "_annotation",
                 "_wall", "_t0")

    def __init__(self, phase: str, *, ctx: Optional[Any] = None,
                 service: str = "", **attrs: Any):
        self.phase = phase
        self.attrs = attrs
        self._ctx = ctx
        self._service = service

    def __enter__(self) -> "span":
        if "trial" not in self.attrs:
            trial = metrics.bound_labels().get("trial")
            if trial:
                self.attrs["trial"] = trial
        profiler = sys.modules.get("jax.profiler")
        self._annotation = None if profiler is None else \
            profiler.TraceAnnotation(f"rafiki.trial.{self.phase}",
                                     **self.attrs)
        self._wall = time.time()
        self._t0 = time.monotonic()
        if self._annotation is not None:
            self._annotation.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        seconds = time.monotonic() - self._t0
        observe_phase(self.phase, seconds)
        if self._ctx is not None:
            _trace.record_event(f"trial.{self.phase}", self._service,
                                [self._ctx], self._wall, seconds,
                                attrs=self.attrs or None)
        return False


_jit_lock = threading.Lock()
_jit_watched = False
#: Per thread: ``hit_at``, the instant of its last persistent-cache hit;
#: ``counted``, {event: intervals already observed} (``JIT_REMEMBERED``).
_jit_local = threading.local()


def watch_jit() -> None:
    """Turn jax's own compile-stage events into the ``jit_*`` phases:
    one ``jax.monitoring`` time-span listener and one event listener,
    registered once a process (``jaxenv.ensure_platform`` calls this on
    both its branches). Inert under ``RAFIKI_TPU_METRICS=0``. jax emits
    these events only when it traces, lowers or compiles, never on a
    dispatch of a compiled program."""
    global _jit_watched
    if not metrics.metrics_enabled():
        return
    with _jit_lock:
        if _jit_watched:
            return
        import jax.monitoring

        jax.monitoring.register_event_listener(_on_jit_event)
        jax.monitoring.register_event_time_span_listener(_on_jit_span)
        _jit_watched = True


def _on_jit_event(event: str, **_: Any) -> None:
    if event == CACHE_HIT_EVENT:
        _jit_local.hit_at = time.time()  # jax's spans are on this clock


def _on_jit_span(event: str, start: float, end: float, **_: Any) -> None:
    """One stage's event, reported as it ends, on the thread that did
    the work. jax reports an inner jit's trace before its caller's,
    which contains it: the intervals of this stage already observed on
    this thread that start inside this one lie inside it (events nest
    on a thread), so their time comes off this one's and the stage's
    sum is the union of its intervals."""
    phase = JIT_EVENTS.get(event)
    if phase is None:
        return
    if phase == "jit_compile" and \
            start <= getattr(_jit_local, "hit_at", start - 1.0) <= end:
        phase = "jit_cache_load"
    counted = getattr(_jit_local, "counted", None)
    if counted is None:
        counted = _jit_local.counted = {}
    seen = counted.setdefault(
        event, collections.deque(maxlen=JIT_REMEMBERED))
    inner = 0.0
    while seen and seen[-1][0] >= start:
        s, e = seen.pop()
        inner += e - s
    seen.append((start, end))
    observe_phase(phase, max(0.0, end - start - inner))


def cache_event(cache: str, event: str, n: int = 1) -> None:
    """``cache`` is one of ``CACHES`` (``"dataset"``, ``"stage"`` or
    ``"step"``); ``event`` one of hit/miss/evict (``"step"``: hit/miss)."""
    if metrics.metrics_enabled():
        # rta: disable=RTA301 event is hit|miss|evict; deliberately immortal (module docstring)
        _reg()[f"{cache}_cache"].inc(n, event=event)


def set_cache_bytes(cache: str, n_bytes: int) -> None:
    if metrics.metrics_enabled():
        _reg()[f"{cache}_cache_bytes"].set(n_bytes)


def cache_counts(cache: str) -> Dict[str, int]:
    """Current {event: count} for one cache family — what the
    zero-disk-load / zero-H2D tests and ``GET /trial_phases`` read."""
    return _by_label(_reg()[f"{cache}_cache"], "event")


def _by_label(counter: Any, label: str) -> Dict[str, int]:
    return {labels.get(label, ""): int(value)
            for labels, value in counter.samples()}


def dump_leaves(device: int, host: int) -> None:
    """One finished trial's leaves, as its model handed them to the
    persist stage."""
    if metrics.metrics_enabled():
        m = _reg()["dump_leaves"]
        m.inc(device, where="device")
        m.inc(host, where="host")


def dump_leaf_counts() -> Dict[str, int]:
    """{"device", "host"}: this process's cumulative totals."""
    return {"device": 0, "host": 0,
            **_by_label(_reg()["dump_leaves"], "where")}


def moe_routed(held: float, absent: float, busiest: float) -> None:
    """One train dispatch's sums of a sparse-expert model."""
    if metrics.metrics_enabled():
        m = _reg()
        m["moe_assignments"].inc(held, where="held")
        m["moe_assignments"].inc(absent, where="absent")
        m["moe_busiest"].inc(busiest)


def moe_counts() -> Dict[str, int]:
    """{"held", "absent", "busiest"}: this process's cumulative totals."""
    m = _reg()
    return {"held": 0, "absent": 0,
            "busiest": int(sum(v for _, v in m["moe_busiest"].samples())),
            **_by_label(m["moe_assignments"], "where")}


def lm_layers(kind: str, ffn: str, n: int) -> None:
    """One trial's ``n`` layers of one kind: ``kind`` is ``conv``,
    ``attention``, ``ssm`` or ``none``, ``ffn`` ``dense``, ``sparse``
    or ``none`` (models/lm_lfm2.py, models/lm_nemotron.py)."""
    if metrics.metrics_enabled():
        # rta: disable=RTA301 kind and ffn take a few fixed values each
        _reg()["lm_layers"].inc(n, kind=kind, ffn=ffn)


def lm_layer_counts() -> Dict[tuple, int]:
    """{(kind, ffn): layers}: this process's cumulative totals."""
    return {(labels.get("kind", ""), labels.get("ffn", "")): int(value)
            for labels, value in _reg()["lm_layers"].samples()}


def phase_totals() -> Dict[str, Dict[str, float]]:
    """{phase: {"sum": seconds, "count": n}} — snapshot-diffable, which
    is how the benchmark's readers (``benchmarks/metrics/``) derive a
    per-trial phase breakdown."""
    h = _reg()["phase"]
    return {p: {"sum": h.sum(phase=p), "count": h.count(phase=p)}
            for p in PHASES}
