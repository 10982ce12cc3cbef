"""TrainWorker: a trial-executing service bound to a chip group.

Parity: SURVEY.md §2 "TrainWorker" + §3.1 — upstream's worker container
entrypoint reads its service env (``SUB_TRAIN_JOB_ID``,
``CUDA_VISIBLE_DEVICES``), then loops the trial lifecycle until the budget
is exhausted. Here the env contract is ``rafiki_tpu.constants.EnvVars``
(``RAFIKI_TPU_CHIPS`` replaces ``CUDA_VISIBLE_DEVICES``); the worker pins
its chip group via the env var so every model it instantiates builds its
Mesh from exactly those chips, resolves its model class from the meta
store, proxies the advisor over the bus, and delegates the loop to
``TrialRunner``.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Any, Dict, Optional

from ..advisor.prefetch import PrefetchAdvisor
from ..advisor.worker import RemoteAdvisor
from ..bus import BaseBus, connect
from ..config import _parse_bool
from ..constants import EnvVars, ServiceStatus, TrialStatus
from ..observe import phases
from ..parallel.chips import ChipGroup
from ..store import MetaStore, ParamStore
from ..utils.model_loader import load_model_class
from .runner import TrialRunner

_log = logging.getLogger(__name__)

#: Opt-out knob for the worker's advisor-prefetch pipelining
#: (NodeConfig.advisor_prefetch; docs/training.md). Default ON: the
#: next proposal computes on a background thread while the current
#: trial trains — the one-observation staleness this introduces is the
#: same asynchrony N parallel workers sharing one advisor already have.
ADVISOR_PREFETCH_ENV = "RAFIKI_TPU_ADVISOR_PREFETCH"


class TrainWorker:
    def __init__(self, service_id: str, sub_train_job_id: str,
                 meta: MetaStore, params: ParamStore, bus: BaseBus,
                 chips: Optional[ChipGroup] = None,
                 advisor: Optional[Any] = None):
        self.service_id = service_id
        self.sub_id = sub_train_job_id
        self.meta = meta
        self.params = params
        self.bus = bus
        self.chips = chips
        # Injectable for resident-runner mode; defaults to the bus proxy.
        self.advisor = advisor or RemoteAdvisor(bus, sub_train_job_id)
        self.stop_flag = threading.Event()
        self._thread: Optional[threading.Thread] = None

    @classmethod
    def from_env(cls, environ: Optional[Dict[str, str]] = None,
                 ) -> "TrainWorker":
        env = environ if environ is not None else dict(os.environ)
        meta = MetaStore(env[EnvVars.META_URI])
        params = ParamStore(env[EnvVars.PARAMS_DIR])
        bus = connect(env.get(EnvVars.BUS_URI, ""))
        chips = ChipGroup.from_env(env.get(EnvVars.CHIPS))
        return cls(env[EnvVars.SERVICE_ID], env[EnvVars.SUB_TRAIN_JOB_ID],
                   meta, params, bus, chips=chips)

    # --- Service lifecycle ---

    def start(self) -> "TrainWorker":
        self._thread = threading.Thread(
            target=self.run, name=f"train-{self.service_id[:8]}", daemon=True)
        self._thread.start()
        return self

    def stop(self, join_timeout: float = 10.0) -> None:
        self.stop_flag.set()
        if self._thread is not None:
            self._thread.join(timeout=join_timeout)

    def join(self, timeout: Optional[float] = None) -> None:
        if self._thread is not None:
            self._thread.join(timeout=timeout)

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    # --- The loop ---

    def run(self) -> None:
        # Route this thread's records to the service log file, if the
        # launcher assigned one (dashboard per-service log view).
        from ..utils.service_logs import bind_service_log

        bind_service_log(getattr(self, "log_path", None))
        # The set-up phase ``worker_start``: up to the runner's loop,
        # whose first ``trial`` span follows.
        with phases.span("worker_start"):
            sub = self.meta.get_sub_train_job(self.sub_id)
            if sub is None:
                raise ValueError(f"unknown sub_train_job {self.sub_id}")
            job = self.meta.get_train_job(sub["train_job_id"])
            model_row = self.meta.get_model(sub["model_id"])
            model_class = load_model_class(model_row["model_class"],
                                           model_row.get("model_source"))
            # Pin this service's chip group for every Mesh built by
            # models on this thread (thread-local, so resident-runner
            # workers sharing a process never race on the env var).
            if self.chips is not None:
                self.chips.bind_to_thread()
            self.meta.update_service(self.service_id,
                                     status=ServiceStatus.RUNNING)
            # Pipeline the advisor by default (opt-out via
            # RAFIKI_TPU_ADVISOR_PREFETCH=0): the next proposal computes
            # while the current trial trains. close() runs on EVERY exit
            # path — stop flag, budget exhaustion, crash — so the
            # dangling prefetched proposal is always forget-ed back to
            # the strategy.
            advisor = self.advisor
            prefetch: Optional[PrefetchAdvisor] = None
            if _parse_bool(os.environ.get(ADVISOR_PREFETCH_ENV, "1")):
                advisor = prefetch = PrefetchAdvisor(advisor)
            runner = TrialRunner(
                model_class, advisor, job["train_dataset_path"],
                job["val_dataset_path"], self.meta, self.params,
                self.sub_id, model_id=sub["model_id"],
                worker_id=self.service_id, budget=job["budget"],
                stop_flag=self.stop_flag, pipeline_persist=True)
        try:
            runner.run()
            # The job is truly over (budget spent, not a mid-job stop
            # or crash): sweep the scoped rung checkpoints this job's
            # halving configurations accumulated (runner docstring).
            if not self.stop_flag.is_set() and runner.budget.exhausted(
                    len(self.meta.get_trials(
                        self.sub_id, status=TrialStatus.COMPLETED))):
                runner.cleanup_scoped_checkpoints()
            self.meta.update_service(self.service_id,
                                     status=ServiceStatus.STOPPED)
        except Exception:
            _log.exception("train worker %s crashed", self.service_id)
            self.meta.update_service(self.service_id,
                                     status=ServiceStatus.ERRORED)
            raise
        finally:
            # run() already drained the persist stage; close() stops
            # its worker thread, and the prefetch close refunds the
            # never-handed-out proposal.
            runner.close()
            if prefetch is not None:
                prefetch.close()
