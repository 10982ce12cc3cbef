"""TrialRunner: the propose → train → evaluate → persist hot loop.

Parity: SURVEY.md §3.1 — the system's primary hot loop, factored out of the
TrainWorker so the same code runs in-process (tests, local
dev — upstream's ``test_model_class`` writ large) and inside a distributed
TrainWorker bound to a chip group. The runner is advisor-transport-agnostic:
it accepts anything with ``propose()/feedback()`` (an in-process advisor or
a bus-backed remote proxy).
"""

from __future__ import annotations

import functools
import hashlib
import json
import logging
import os
import shutil
import threading
import time
import traceback
import uuid
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional, Type

import jax
import numpy as np

from ..advisor.base import Proposal
from ..constants import BudgetOption, TrialStatus
from ..model.base import BaseModel
from ..model.dataset import stage_owner
from ..model.logger import logger
from ..observe import metrics, trace_session, trial_trace_dir
from ..observe import phases as _phases
from ..observe import trace as _trace
from ..store import MetaStore, ParamStore

_log = logging.getLogger(__name__)


class _PersistStage:
    """Single-slot background stage for the completed-trial persist
    tail (trial-log flush, the dumped parameters' copy off the device,
    ``ParamStore.save``, ``mark_trial_completed``, spent-checkpoint
    sweep).

    Exactly ONE trial's tail may be in flight: ``submit`` first waits
    for the previous tail to finish — strict per-trial ordering (trial
    N's meta writes land before trial N+1's) with exactly one trial of
    overlap, which is all the pipeline needs: trial N+1's propose/
    validate/init runs while trial N persists.

    Budget accounting: a submitted-but-uncommitted tail is a completion
    the meta store can't see yet. ``completed_count`` folds the pending
    count into the caller's COMPLETED query under the same lock the
    tail's commit point holds, so the runner's budget check neither
    double-counts a completion racing its own commit nor proposes an
    extra trial past ``MODEL_TRIAL_COUNT``.

    Tails never raise: the closure built in ``run_one`` catches its own
    failures and retroactively marks the trial errored (the score was
    real and the advisor already got its feedback — only persistence
    failed)."""

    def __init__(self):
        self._pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="trial-persist")
        self._last: Optional[Future] = None
        self._held: Optional[Callable[[Callable], None]] = None
        self._lock = threading.Lock()
        self._pending = 0
        self._failures = 0
        self._failed_ids: set = set()
        self._commits = 0

    def note_failure(self, trial_id: str = "") -> None:
        """Called by a tail that errored its trial retroactively. The
        runner's loop folds this into the consecutive-error circuit
        breaker — otherwise a persistently failing tail (disk full)
        would never trip it (run_one's row snapshot still says RUNNING)
        and a trial-count budget would never be satisfied: an infinite
        loop."""
        with self._lock:
            self._failures += 1
            if trial_id:
                self._failed_ids.add(str(trial_id))

    def failure_count(self) -> int:
        with self._lock:
            return self._failures

    def has_failed(self, trial_id: str) -> bool:
        """Whether this trial's OWN tail already noted a failure — the
        breaker's dedupe: a fast tail can error its trial before
        run_one snapshots the row, and counting that trial via the
        ERRORED snapshot AND the failure-count delta tripped the
        breaker a trial early. The tail notes the failure strictly
        before it marks the row, so a tail-errored snapshot implies
        membership here by the time the loop asks."""
        with self._lock:
            return str(trial_id) in self._failed_ids

    def commit_count(self) -> int:
        """Tails that committed (trial genuinely COMPLETED) — the
        breaker's RESET signal. Resetting on anything weaker races: a
        fast-failing tail can land before its own iteration's
        failure-count read, and the next iteration's "no new failure"
        must not read as success mid-streak."""
        with self._lock:
            return self._commits

    def submit(self, fn: Callable[[Callable], None]) -> None:
        """``fn(commit)`` runs on the persist thread once ``release``
        is called; it must call ``commit(meta_write)`` at most once —
        the meta write and the pending-count decrement happen
        atomically. Waits for the previous tail first (single slot)."""
        if self._last is not None:
            self._last.result()  # single slot; tails don't raise
        with self._lock:
            self._pending += 1
        self._held = fn

    def release(self) -> None:
        """Start the submitted tail: the trial thread calls this once
        the trial's own span has ended, so that span and the tail's
        ``persist`` never overlap. No-op with nothing submitted."""
        fn, self._held = self._held, None
        if fn is None:
            return

        def run() -> None:
            committed = [False]

            def commit(meta_write: Callable[[], None]) -> None:
                with self._lock:
                    meta_write()
                    self._pending -= 1
                    self._commits += 1
                committed[0] = True

            try:
                fn(commit)
            finally:
                if not committed[0]:
                    with self._lock:
                        self._pending -= 1

        self._last = self._pool.submit(run)

    def completed_count(self, count_fn: Callable[[], int]) -> int:
        """``count_fn()`` (the meta COMPLETED query) plus the pending
        tails, read atomically against commits."""
        with self._lock:
            return int(count_fn()) + self._pending

    def drain(self) -> None:
        """Block until the in-flight tail (if any) has finished — after
        this, no trial row of a submitted tail is left RUNNING."""
        self.release()
        if self._last is not None:
            self._last.result()

    def close(self) -> None:
        self.drain()
        self._pool.shutdown(wait=True)


class BudgetTracker:
    """Budget enforcement for one sub-train-job.

    Parity: upstream budgets ``MODEL_TRIAL_COUNT`` and ``TIME_HOURS``
    (SURVEY.md §2 "Constants"). ``GPU_COUNT``/``CHIP_COUNT`` govern service
    sizing in the ServicesManager, not the trial loop.
    """

    def __init__(self, budget: Optional[Dict[str, Any]] = None):
        budget = dict(budget or {})
        self.max_trials = int(budget.get(BudgetOption.MODEL_TRIAL_COUNT, 5))
        self.max_hours = float(budget.get(BudgetOption.TIME_HOURS, 0) or 0)
        self._t0 = time.time()

    def exhausted(self, n_trials_done: int) -> bool:
        if n_trials_done >= self.max_trials:
            return True
        if self.max_hours > 0 and \
                (time.time() - self._t0) >= self.max_hours * 3600:
            return True
        return False


class TrialRunner:
    """Runs trials for one (sub_train_job, model_class) against the stores."""

    def __init__(self, model_class: Type[BaseModel], advisor: Any,
                 train_dataset_path: str, val_dataset_path: str,
                 meta_store: MetaStore, param_store: ParamStore,
                 sub_train_job_id: str, model_id: str = "",
                 worker_id: str = "local",
                 budget: Optional[Dict[str, Any]] = None,
                 stop_flag: Optional[Any] = None,
                 max_consecutive_errors: int = 3,
                 pipeline_persist: bool = False):
        self.model_class = model_class
        self.advisor = advisor
        self.train_dataset_path = train_dataset_path
        self.val_dataset_path = val_dataset_path
        self.meta = meta_store
        self.params = param_store
        self.sub_train_job_id = sub_train_job_id
        self.model_id = model_id
        self.worker_id = worker_id
        self.budget = BudgetTracker(budget)
        # threading.Event-like; lets a supervisor stop the loop mid-job.
        self.stop_flag = stop_flag
        # Circuit breaker: a model that fails deterministically would
        # otherwise loop forever, since errored trials refund their budget
        # slot (advisor.forget) and never count as completed.
        self.max_consecutive_errors = max_consecutive_errors
        # Pipelined trial tail (docs/training.md): the persist tail of
        # a completed trial runs on a single-slot background stage so
        # the NEXT trial's propose/validate/init overlaps it. Off by
        # default for direct construction (tests/benches that inspect
        # meta right after run_one); the TrainWorker turns it on. With
        # it on, run_one may return a still-RUNNING row whose tail is
        # in flight — run() and drain_persist() settle them.
        self._persist = _PersistStage() if pipeline_persist else None

    # --- Loop ---

    def run(self) -> List[Dict[str, Any]]:
        """Run trials until the budget is exhausted; returns trial rows.

        Always drains the persist stage on the way out (budget spent,
        stop flag, crash): no trial row is left RUNNING with its tail
        still queued."""
        done: List[Dict[str, Any]] = []
        consecutive_errors = 0
        tail_failures_seen = 0
        tail_commits_seen = 0
        finished = False
        try:
            while not finished:
                while not self._should_stop():
                    row = self.run_one()
                    if row is None:
                        finished = True  # advisor: search is over
                        break
                    done.append(row)
                    # Fold failed persist tails into the breaker (they
                    # error trials RETROactively — after run_one
                    # snapshotted the row as RUNNING) by DELTA, and
                    # reset only on an actual COMMIT: a fast-failing
                    # tail can land before its own iteration's
                    # failure-count read (this check sees +2, the next
                    # sees +0), and treating that +0 as success reset
                    # an unbroken failure streak — a deterministic
                    # disk-full tail could run a dozen-plus trials
                    # before tripping instead of max_consecutive.
                    # The SAME fast tail can also land before run_one's
                    # snapshot, making the row read ERRORED while its
                    # failure rides the delta too — has_failed dedupes
                    # that trial so it counts once, not twice (double
                    # counting tripped the breaker a trial early).
                    new_failures = int(
                        row["status"] == TrialStatus.ERRORED
                        and not (self._persist is not None
                                 and self._persist.has_failed(
                                     row["id"])))
                    new_commits = 0
                    if self._persist is not None:
                        f = self._persist.failure_count()
                        new_failures += f - tail_failures_seen
                        tail_failures_seen = f
                        c = self._persist.commit_count()
                        new_commits = c - tail_commits_seen
                        tail_commits_seen = c
                    else:
                        new_commits = int(not new_failures)
                    if new_commits:
                        # Reset BEFORE counting this check's failures:
                        # ordering across one sweep is unknowable, and
                        # biasing toward keeping the streak is the
                        # safe direction for a deterministic failure.
                        consecutive_errors = 0
                    if new_failures:
                        consecutive_errors += new_failures
                        if consecutive_errors >= \
                                self.max_consecutive_errors:
                            _log.error(
                                "worker %s: %d consecutive trial "
                                "failures; giving up on %s",
                                self.worker_id, consecutive_errors,
                                self.sub_train_job_id)
                            finished = True
                            break
                if finished:
                    break
                # The budget LOOKED satisfied, but an in-flight persist
                # tail counted toward it optimistically. Settle it and
                # re-check: a tail that failed turned its trial ERRORED
                # — the slot is refunded (as the pre-pipelining inline
                # error path did) and the loop runs a replacement trial
                # instead of under-delivering the trial count.
                self.drain_persist()
                if self._should_stop():
                    finished = True
        finally:
            self.drain_persist()
        if self._persist is not None:
            # run_one snapshotted pipelined rows BEFORE their tails
            # committed; after the drain every trial is terminal in the
            # meta store — return what it actually says, not a stale
            # RUNNING/params_id=None view.
            done = [self.meta.get_trial(row["id"]) or row
                    for row in done]
        return done

    def drain_persist(self) -> None:
        """Wait for the in-flight persist tail (no-op when the pipeline
        is off). After this every submitted trial row is terminal."""
        if self._persist is not None:
            self._persist.drain()

    def close(self) -> None:
        if self._persist is not None:
            self._persist.close()

    def _should_stop(self) -> bool:
        if self.stop_flag is not None and self.stop_flag.is_set():
            return True

        def n_completed() -> int:
            return len(self.meta.get_trials(self.sub_train_job_id,
                                            status=TrialStatus.COMPLETED))

        # A pending persist tail is a completion the meta store can't
        # see yet; counting it keeps the budget exact under pipelining.
        n_done = (self._persist.completed_count(n_completed)
                  if self._persist is not None else n_completed())
        return self.budget.exhausted(n_done)

    # --- One trial ---

    def run_one(self, proposal: Optional[Proposal] = None,
                ) -> Optional[Dict[str, Any]]:
        # The id is minted here, not by the meta store, so that every
        # span of the trial, ``propose`` included, can carry it; the
        # label context hands it to the spans the model opens.
        trial_id = uuid.uuid4().hex
        # Span-store context, resolved on THIS (trial) thread: the
        # ambient context when one exists (an admin-triggered run),
        # else a context whose trace id IS the trial id — so
        # ``GET /trace/<trial_id>`` shows the trial's timeline, the
        # persist tail included (the thread-local is lost across the
        # persist-stage hop, hence the capture here).
        ctx = _trace.current() or _trace.TraceContext(trial_id)
        span = functools.partial(_phases.span, ctx=ctx,
                                 service=self.worker_id)
        try:
            with metrics.label_context(trial=trial_id[:12]), \
                    span("trial"):
                if proposal is None:
                    with span("propose"):
                        proposal = self.advisor.propose()
                if proposal is None:  # advisor side says: search is over
                    return None
                return self._run_trial(trial_id, proposal, span)
        finally:
            if self._persist is not None:
                self._persist.release()  # the tail starts after ``trial``

    def _run_trial(self, trial_id: str, proposal: Proposal,
                   span: Callable[..., Any]) -> Dict[str, Any]:
        """One proposed trial, inside ``run_one``'s ``trial`` span;
        ``span`` is ``phases.span`` bound to the trial's span-store
        context."""
        with span("open"):
            # Warm-start params are resolved BEFORE knob validation: a
            # proposal may carry reduced knobs that are only valid with
            # the warm start (PBT rounds train delta epochs) plus
            # ``cold_start_knobs`` overrides to apply when the shared
            # params are legitimately absent (expired store, fresh
            # node). A retrieval ERROR is different from absence:
            # silently cold-starting would feed an artificially poor
            # score back into the search (e.g. the ENAS controller), so
            # it errs the trial and refunds the proposal like any other
            # trial failure.
            params_scope = proposal.meta.get("params_scope") \
                or self.worker_id
            try:
                shared = self.params.retrieve(
                    proposal.params_type,
                    session_id=self.sub_train_job_id,
                    worker_id=params_scope)
            except Exception:
                err = traceback.format_exc()
                self.meta.create_trial(
                    self.sub_train_job_id, self.model_id,
                    no=proposal.trial_no, status=TrialStatus.RUNNING,
                    worker_id=self.worker_id,
                    knobs=_jsonable_knobs(proposal.knobs),
                    proposal=proposal.to_json(), trial_id=trial_id)
                self.meta.mark_trial_errored(trial_id, err)
                forget = getattr(self.advisor, "forget", None)
                if forget is not None:
                    forget(proposal)
                _log.warning("trial #%d: shared-params retrieval "
                             "failed:\n%s", proposal.trial_no, err)
                return self.meta.get_trial(trial_id)
            raw_knobs = dict(proposal.knobs)
            if shared is None:
                raw_knobs.update(
                    proposal.meta.get("cold_start_knobs") or {})
            knobs = self.model_class.validate_knobs(raw_knobs)
            # The RECORDED knobs are the reproducible configuration
            # (``record_knobs`` overlays e.g. ASHA's cumulative budget
            # over the executed delta).
            recorded = {**knobs,
                        **(proposal.meta.get("record_knobs") or {})}
            self.meta.create_trial(
                self.sub_train_job_id, self.model_id,
                no=proposal.trial_no, status=TrialStatus.RUNNING,
                worker_id=self.worker_id,
                knobs=_jsonable_knobs(recorded),
                proposal=proposal.to_json(), trial_id=trial_id)

            # Save + chain whatever sink this thread already had (a
            # test capture, a caller's probe): the
            # trial's records go to the meta store AND keep flowing
            # outward, and the prior binding is restored afterwards
            # instead of nulled. With the persist pipeline on, the
            # meta-store writes are BUFFERED and flushed by the trial's
            # persist tail (one less sqlite insert interleaved with
            # device dispatch); the chained outward flow stays live
            # either way.
            prior_sink = logger.current_sink()
            buffering = self._persist is not None
            log_buffer: List[Any] = []

            def _trial_sink(rec, _tid=trial_id, _prior=prior_sink):
                if buffering:
                    log_buffer.append(rec)
                else:
                    self.meta.add_trial_log(_tid, rec)
                if _prior is not None:
                    _prior(rec)

        logger.set_sink(_trial_sink)
        t0 = time.time()
        try:
            with span("init"):
                model = self.model_class(**knobs)
            # Opt-in mid-trial checkpointing (RAFIKI_TPU_CKPT=1): the dir
            # is keyed by (sub_train_job, knobs), not trial id, so the
            # re-proposed trial after a worker crash resumes the crashed
            # attempt's epochs instead of repaying them (SURVEY.md §5).
            #
            # A proposal may instead pin its OWN checkpoint scope
            # (``ckpt_scope``): successive-halving rungs of one
            # configuration share a scope, so each rung resumes the
            # previous rung's final state — optimizer moments, early-
            # stop counters and the per-epoch data order all continue,
            # making the rung sequence step-identical to one
            # uninterrupted run (advisor/asha.py). Scoped checkpoints
            # persist across trials (the NEXT rung needs them) and are
            # always on, independent of RAFIKI_TPU_CKPT.
            ckpt_scope = proposal.meta.get("ckpt_scope")
            if ckpt_scope:
                ckpt_dir = os.path.join(
                    self.params.params_dir, "ckpt",
                    f"{self.sub_train_job_id}-{ckpt_scope}")
            else:
                ckpt_dir = self._ckpt_dir(knobs)
            train_kwargs = {"checkpoint_dir": ckpt_dir} if ckpt_dir else {}
            if ckpt_scope:
                train_kwargs["checkpoint_final_epoch"] = True
            train_kwargs.update(proposal.meta.get("train_kwargs") or {})
            try:
                # Opt-in per-trial profiler trace (RAFIKI_TPU_TRACE_DIR);
                # each trial's trace lands in its own TensorBoard-readable
                # subdirectory (SURVEY.md §5 tracing plan). The metrics
                # label context attributes the train loop's MFU gauge /
                # step-time histogram to THIS trial — the loop itself
                # has no idea which trial it runs for.
                # stage_owner marks the residency-cache entries this
                # trial stages as THIS sub-train-job's, so evictions
                # under budget pressure prefer other jobs' datasets
                # (model/dataset.py ByteBudgetLRU). The span opens
                # inside the session, so the trial's own trace holds it.
                with stage_owner(self.sub_train_job_id), \
                        trace_session(trial_trace_dir(trial_id)), \
                        span("train"):
                    model.train(self.train_dataset_path,
                                shared_params=shared, **train_kwargs)
                with stage_owner(self.sub_train_job_id), span("eval"):
                    score = float(model.evaluate(self.val_dataset_path))
                # A proposal may retrieve from one scope and save under
                # another (PBT exploitation inherits the winner's
                # weights but keeps writing its own lineage).
                save_scope = proposal.meta.get("params_save_scope") \
                    or params_scope
                # ``dump`` is the model handing its leaves on. The LM
                # and the image zoo return them as they lie on the
                # device, no copy started: the persist stage turns
                # them into host arrays behind the next trial's steps
                # (``_to_host``), or, with no stage, the ParamStore's
                # write-behind writer pulls them. A model that returns
                # host arrays paid for them here, with the chip idle.
                with span("dump"):
                    dumped = model.dump_parameters()
            finally:
                model.destroy()
            # Spend the unscoped crash-resume checkpoint dir NOW, by a
            # synchronous metadata-cheap rename: it is keyed by
            # (sub_train_job, knobs), not trial id, so with the
            # pipelined tail a same-knobs successor trial could
            # otherwise resume THIS trial's final checkpoint (training
            # zero epochs) — or have its own fresh dir rmtree'd from
            # under it. The bulky recursive delete of the tombstone
            # stays in the tail.
            ckpt_tomb = None
            if ckpt_dir and not ckpt_scope:
                tomb = f"{ckpt_dir}.spent-{trial_id[:8]}"
                try:
                    os.rename(ckpt_dir, tomb)
                    ckpt_tomb = tomb
                except OSError:
                    pass  # no checkpoint was ever written
            # Feedback is NOT deferred behind persistence: the score is
            # final once evaluate returned, and the (possibly
            # prefetching) advisor folds it in while the tail flushes.
            # It runs BEFORE the tail submission on purpose: once the
            # tail owns the trial's log buffer and terminal status, no
            # later exception on this thread may touch them (the except
            # below would race the persist thread's writes).
            with span("feedback"):
                self.advisor.feedback(proposal, score)
            # As the trial thread sees it: the wait for the previous
            # trial's tail to leave the single-slot persist stage (with
            # the pipeline off, the whole tail).
            with span("handover"):
                self._finish_trial(trial_id, score, dumped, save_scope,
                                   log_buffer, ckpt_tomb, span)
            _log.info("trial %s #%d done: score=%.4f (%.1fs)", trial_id[:8],
                      proposal.trial_no, score, time.time() - t0)
        except Exception:
            err = traceback.format_exc()
            for rec in log_buffer:  # buffered records outlive the error
                self.meta.add_trial_log(trial_id, rec)
            self.meta.mark_trial_errored(trial_id, err)
            # The advisor will never get feedback for this proposal; let it
            # release per-proposal state (e.g. ENAS pending REINFORCE meta).
            forget = getattr(self.advisor, "forget", None)
            if forget is not None:
                forget(proposal)
            _log.warning("trial %s #%d errored:\n%s", trial_id[:8],
                         proposal.trial_no, err)
        finally:
            logger.set_sink(prior_sink)
            # The train metrics are "current trial" series: a finished
            # (or errored) trial must stop reporting its last values as
            # live, and the per-trial labels must not accumulate in the
            # registry forever. Trial logs keep the history.
            for name in ("rafiki_tpu_train_mfu_ratio",
                         "rafiki_tpu_train_step_seconds"):
                m = metrics.registry().find(name)
                if m is not None:
                    m.remove(trial=trial_id[:12])
        return self.meta.get_trial(trial_id)

    def _finish_trial(self, trial_id: str, score: float, dumped: Any,
                      save_scope: str, log_buffer: List[Any],
                      ckpt_tomb: Optional[str],
                      span: Callable[..., Any]) -> None:
        """The completed-trial persist tail: flush the buffered trial
        logs, copy the dumped parameters off the device (on the stage
        only), hand them to the ParamStore, mark the trial COMPLETED,
        sweep the spent (already tombstone-renamed) crash-resume
        checkpoint dir.

        Runs inline when the pipeline is off; on the single-slot
        persist stage otherwise — trial N+1's propose/validate/init
        then overlaps trial N's persistence. A tail failure
        retroactively marks the trial ERRORED (the advisor's feedback
        stands — the score was real; only persistence failed)."""
        def tail(commit: Callable[[Callable], None]) -> None:
            # The label context that names the trial is thread-local
            # and does not cross the persist-stage hop: this span is
            # GIVEN the id, so that a span on another thread names the
            # trial that caused it. Its attrs carry the tail's stages.
            with span("persist", trial=trial_id[:12]) as sp:
                persist(commit, sp.attrs)

        def persist(commit: Callable[[Callable], None],
                    took: Dict[str, Any]) -> None:
            def ms_since(t: float) -> float:
                return round((time.monotonic() - t) * 1e3, 3)

            try:
                t = time.monotonic()
                for rec in log_buffer:
                    self.meta.add_trial_log(trial_id, rec)
                took["log_flush_ms"] = ms_since(t)
                if self._persist is not None:
                    # The stage owns the copy off the device, so the
                    # store sees host arrays and writes file and index
                    # row before it returns: the row below turns
                    # COMPLETED only once both exist.
                    t = time.monotonic()
                    took["d2h_bytes"] = _to_host(dumped)
                    took["d2h_ms"] = ms_since(t)
                t = time.monotonic()
                params_id = self.params.save(
                    dumped, session_id=self.sub_train_job_id,
                    worker_id=save_scope, score=score)
                took["params_save_ms"] = ms_since(t)
                t = time.monotonic()
                commit(lambda: self.meta.mark_trial_completed(
                    trial_id, score, params_id))
                took["meta_commit_ms"] = ms_since(t)
                # Scoped checkpoints outlive the trial — the
                # configuration's next rung resumes them;
                # cleanup_scoped_checkpoints() runs when the sub-job is
                # done. The spent unscoped dir was tombstone-renamed on
                # the trial thread; only its deletion is deferred here.
                if ckpt_tomb:
                    shutil.rmtree(ckpt_tomb, ignore_errors=True)
            except Exception:
                err = traceback.format_exc()
                _log.warning("trial %s: persist tail failed; marking "
                             "errored:\n%s", trial_id[:8], err)
                if self._persist is not None:
                    self._persist.note_failure(trial_id)
                try:
                    self.meta.mark_trial_errored(trial_id, err)
                except Exception:
                    _log.exception("trial %s: could not record persist "
                                   "failure", trial_id[:8])

        if self._persist is not None:
            self._persist.submit(tail)
        else:
            tail(lambda meta_write: meta_write())

    def cleanup_scoped_checkpoints(self) -> None:
        """Remove every scoped checkpoint dir of this sub-train-job.

        Scoped dirs (``<params_dir>/ckpt/<sub_id>-<scope>``) persist
        across trials by design — successive-halving rungs of one
        configuration resume each other — so nothing inside the trial
        loop may delete them. Without a terminal sweep they would grow
        one dir per halving configuration forever; the TrainWorker calls
        this once its sub-job's budget is exhausted, and the
        ServicesManager sweeps equivalently on every job stop path
        (explicit stop, error termination, wind-down), covering jobs
        that never exhaust their budget. Racing a still-
        running sibling worker is benign: a trial that loses its scope
        dir mid-flight cold-starts its full proposed budget, which is
        the documented fallback and stays rung-comparable.
        """
        root = os.path.join(self.params.params_dir, "ckpt")
        if not os.path.isdir(root):
            return
        prefix = f"{self.sub_train_job_id}-"
        for name in os.listdir(root):
            if name.startswith(prefix):
                shutil.rmtree(os.path.join(root, name),
                              ignore_errors=True)

    def _ckpt_dir(self, knobs: Dict[str, Any]) -> Optional[str]:
        if os.environ.get("RAFIKI_TPU_CKPT") != "1":
            return None
        digest = hashlib.sha1(json.dumps(
            {"sub": self.sub_train_job_id,
             "knobs": _jsonable_knobs(knobs)},
            sort_keys=True, default=str).encode()).hexdigest()[:16]
        return os.path.join(self.params.params_dir, "ckpt", digest)


def _to_host(dumped: Dict[str, Any]) -> int:
    """Turn the device leaves of ``dumped`` into host arrays, in place
    and leaf by leaf: a leaf's device buffer is dropped as soon as its
    host copy exists, so what a finished trial keeps on the device
    only shrinks while the next trial trains. (Not the packed pull of
    ``parallel.device_get_tree``: it concatenates a second copy of the
    whole tree on the device first.) Returns the bytes copied, and
    counts the leaves by where the stage found them."""
    n_bytes = n_device = 0
    for name in dumped:
        if isinstance(dumped[name], jax.Array):
            dumped[name] = np.asarray(dumped[name])
            n_bytes += dumped[name].nbytes
            n_device += 1
    _phases.dump_leaves(device=n_device, host=len(dumped) - n_device)
    return n_bytes


def _jsonable_knobs(knobs: Dict[str, Any]) -> Dict[str, Any]:
    out = {}
    for k, v in knobs.items():
        if hasattr(v, "item") and getattr(v, "ndim", 1) == 0:
            v = v.item()
        out[k] = v
    return out
