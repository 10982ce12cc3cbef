"""Trial-lifecycle phase metrics, folded into the unified registry.

The trial hot loop (propose -> load -> stage -> train -> eval ->
persist) is where the training plane's trials/hour lives; where its
time goes today is ``PERF.md`` §5 (the image zoo's short trials have not
been re-measured since 2026-07-31: ``PERF.md`` §7 row 1). These series
make the breakdown measurable the same way the serving stage histogram
did for the frontend:

- ``rafiki_tpu_trial_phase_seconds{phase=}`` — wall time per phase,
  every one timed by ``span`` below (``PHASES`` lists them with their
  nesting). The TrialRunner records the lifecycle steps of ``trial``;
  ``load`` (dataset parse from disk) and ``stage`` (full-dataset
  host->device transfer) are SUB-SPANS recorded inside
  ``model.train()``/``model.evaluate()`` — they are contained in the
  train/eval phases, not additive with them. With the residency caches
  warm, load+stage collapse to ~0 for trial 2..N of a sub-train-job.
  The count of ``step_wait`` is a trial's progress from outside:
  dispatches completed (times ``steps_per_dispatch`` = steps).
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
- ``rafiki_tpu_lm_layers_total{kind=conv|attention,ffn=dense|sparse}``
  — the layers a hybrid LM's trial really built, by sequence operator
  and feed-forward (``lm_layers``, once a trial, from the stacks of the
  parameter tree the train step is handed): a run says which layer
  pattern it trained.
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

import sys
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
#:                    thread; overlaps the NEXT trial)
PHASES = ("trial", "propose", "open", "init", "train", "load", "stage",
          "step_setup", "step_dispatch", "step_wait", "eval", "dump",
          "feedback", "handover", "persist")

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
                "Wall time of one trial-lifecycle phase (phase=" +
                "|".join(PHASES) + "; nesting: observe/phases.py "
                "PHASES)", buckets=PHASE_BUCKETS),
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
                "(kind=conv|attention: the sequence operator; "
                "ffn=dense|sparse)"),
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


def lm_layers(op: str, ffn: str, n: int) -> None:
    """One trial's ``n`` layers of one kind: ``op`` is ``conv`` or
    ``attn``, ``ffn`` ``dense`` or ``sparse`` (models/lm_lfm2.py)."""
    if metrics.metrics_enabled():
        # rta: disable=RTA301 kind and ffn are two fixed values each
        _reg()["lm_layers"].inc(
            n, kind="conv" if op == "conv" else "attention", ffn=ffn)


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
