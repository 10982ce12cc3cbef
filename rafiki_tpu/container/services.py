"""Service entrypoints: env contract → running worker object.

Parity: SURVEY.md §3.1 — upstream's worker image has one entrypoint that
reads ``SERVICE_TYPE`` and friends from the container env and starts the
right loop. ``build_service`` is that entrypoint as a function; the
``ProcessContainerManager`` wraps it in ``python -m
rafiki_tpu.container.services`` with the env vars set, while the
``ThreadContainerManager`` calls it in-process against shared stores.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional

from ..bus import BaseBus, connect
from ..constants import EnvVars, ServiceType
from ..parallel.chips import ChipGroup
from ..store import MetaStore, ParamStore


@dataclass
class SystemContext:
    """The shared substrate every service programs against."""

    meta: MetaStore
    params: ParamStore
    bus: BaseBus

    @staticmethod
    def from_env(env: Dict[str, str]) -> "SystemContext":
        return SystemContext(
            meta=MetaStore(env[EnvVars.META_URI]),
            params=ParamStore(env[EnvVars.PARAMS_DIR]),
            bus=connect(env.get(EnvVars.BUS_URI, "")))


def build_service(env: Dict[str, str], ctx: Optional[SystemContext] = None,
                  ) -> Any:
    """Construct (not start) the worker object for a service env."""
    ctx = ctx or SystemContext.from_env(env)
    service_type = env[EnvVars.SERVICE_TYPE]
    service_id = env[EnvVars.SERVICE_ID]
    chips = (ChipGroup.from_env(env[EnvVars.CHIPS])
             if env.get(EnvVars.CHIPS) else None)
    service = _build(service_type, service_id, env, ctx, chips)
    # Thread-mode log capture: the worker binds its own thread to this
    # file at run() start (utils/service_logs; dashboard log view).
    if env.get(EnvVars.LOG_DIR):
        from ..utils.service_logs import service_log_path

        service.log_path = service_log_path(env[EnvVars.LOG_DIR],
                                            service_id)
    return service


def _build(service_type: str, service_id: str, env: Dict[str, str],
           ctx: SystemContext, chips: Optional[ChipGroup]) -> Any:
    if service_type == ServiceType.TRAIN:
        from ..worker.train import TrainWorker

        return TrainWorker(service_id, env[EnvVars.SUB_TRAIN_JOB_ID],
                           ctx.meta, ctx.params, ctx.bus, chips=chips)
    if service_type == ServiceType.ADVISOR:
        return _build_advisor_service(service_id,
                                      env[EnvVars.SUB_TRAIN_JOB_ID], ctx,
                                      env)
    if service_type == ServiceType.INFERENCE:
        from ..worker.inference import InferenceWorker

        return InferenceWorker(service_id, env[EnvVars.INFERENCE_JOB_ID],
                               env[EnvVars.TRIAL_ID], ctx.meta, ctx.params,
                               ctx.bus, chips=chips)
    if service_type == ServiceType.PREDICT:
        from ..predictor.app import PredictorService

        return PredictorService(service_id, env[EnvVars.INFERENCE_JOB_ID],
                                ctx.meta, ctx.bus,
                                port=int(env.get("RAFIKI_TPU_PORT", "0")))
    raise ValueError(f"unknown service type: {service_type!r}")


def _build_advisor_service(service_id: str, sub_id: str,
                           ctx: SystemContext,
                           env: Optional[Dict[str, str]] = None) -> Any:
    """AdvisorWorker wired to the sub-train-job's model + budget."""
    from ..advisor import make_advisor
    from ..advisor.worker import AdvisorWorker
    from ..constants import BudgetOption
    from ..utils.model_loader import load_model_class

    sub = ctx.meta.get_sub_train_job(sub_id)
    job = ctx.meta.get_train_job(sub["train_job_id"])
    model_row = ctx.meta.get_model(sub["model_id"])
    model_class = load_model_class(model_row["model_class"],
                                   model_row.get("model_source"))
    total = job["budget"].get(BudgetOption.MODEL_TRIAL_COUNT)
    advisor = make_advisor(model_class.get_knob_config(),
                           advisor_type=sub.get("advisor_type"),
                           total_trials=total)
    import os

    from ..config import _parse_bool

    # The SERVICE env dict is the contract every tunable here rides
    # (docker children never inherit the admin's os.environ); the
    # process env is the fallback for direct construction.
    raw = (env or {}).get("RAFIKI_TPU_ADVISOR_PREFETCH") \
        or os.environ.get("RAFIKI_TPU_ADVISOR_PREFETCH", "1")
    if _parse_bool(raw):
        # The bus-hosted advisor serves MANY workers, whose proposals
        # already race feedback — prefetching the next proposal (so a
        # GP refit never blocks a requesting TrainWorker's chip) adds
        # no staleness that fan-out hasn't already introduced.
        # RAFIKI_TPU_ADVISOR_PREFETCH=0 opts out.
        from ..advisor import PrefetchAdvisor

        advisor = PrefetchAdvisor(advisor)
    worker = AdvisorWorker(advisor, ctx.bus, sub_id)
    worker.service_id = service_id
    return worker


def main() -> None:
    """Subprocess entrypoint: build from os.environ, run in the
    foreground until the process is signalled."""
    import logging
    import os
    import signal

    from ..jaxenv import ensure_platform

    # A service process resolves its platform like any entry point:
    # JAX_PLATFORMS=cpu (inherited from the node) or a TPU of its own.
    ensure_platform()
    # Subprocess/docker mode: the whole process IS the service, so its
    # log file captures every thread via a root FileHandler (the
    # thread-bound handler is for resident-runner mode).
    env = dict(os.environ)
    if env.get(EnvVars.LOG_DIR):
        from ..observe import trace
        from ..utils.service_logs import attach_process_log, \
            service_log_path

        attach_process_log(service_log_path(
            env[EnvVars.LOG_DIR], env[EnvVars.SERVICE_ID]))
        # Span sink: the SHARED <log_dir>/spans.jsonl (O_APPEND lines
        # interleave safely with the admin process and sibling
        # services), so Admin.get_trace sees this worker's spans.
        trace.configure(env[EnvVars.LOG_DIR])
        # Workload-recorder sink (dormant unless the env gate is on):
        # a subprocess predictor's arrival records land in the same
        # shared log dir the capacity engine replays from.
        from ..observe import workload as _workload

        _workload.configure(env[EnvVars.LOG_DIR])
        # The root FileHandler above now owns the file; dropping the
        # env var stops build_service from ALSO binding the thread-
        # routing handler to it (every record would land twice).
        env.pop(EnvVars.LOG_DIR)
    # Worker runners (train/inference) have no HTTP surface of their
    # own; RAFIKI_TPU_METRICS_PORT starts a metrics-only JsonHttpServer
    # so every subprocess/docker service is scrapable. Port 0 picks a
    # free port (logged); the resident runner doesn't need this — the
    # admin frontend already exposes the shared process registry.
    metrics_port = env.get("RAFIKI_TPU_METRICS_PORT")
    if metrics_port is not None and metrics_port != "":
        from ..observe import metrics as obs_metrics

        if not obs_metrics.metrics_enabled():
            # RAFIKI_TPU_METRICS=0 suppresses the /metrics route, so a
            # server here would answer 404 to the very scrape the port
            # was configured for — refuse loudly instead.
            logging.getLogger(__name__).warning(
                "RAFIKI_TPU_METRICS_PORT=%s ignored: RAFIKI_TPU_METRICS "
                "disables metrics for this process", metrics_port)
        else:
            try:
                server = obs_metrics.serve_metrics(
                    port=int(metrics_port),
                    name=f"metrics-{env.get(EnvVars.SERVICE_ID, '?')[:8]}")
                logging.getLogger(__name__).info(
                    "metrics server on port %d", server.port)
                # Advertise the BOUND address (port 0 picks one) so
                # this worker's bus registration can carry it and the
                # admin's SLO engine can scrape worker-owned families
                # (serving_bin_device_seconds lives in THIS process's
                # registry, invisible to the frontend's exposition —
                # docs/observability.md). gethostname covers docker
                # networks; loopback covers same-host subprocesses.
                import socket

                try:
                    host = socket.gethostbyname(socket.gethostname())
                except OSError:
                    host = "127.0.0.1"
                os.environ[EnvVars.METRICS_ADDR] = \
                    f"{host}:{server.port}"
            except (OSError, ValueError):
                # A node-wide fixed port collides when several services
                # share one host (or the value is garbage): metrics are
                # a convenience and must degrade to "none", never kill
                # the worker before it starts.
                logging.getLogger(__name__).warning(
                    "metrics server on port %s unavailable; continuing "
                    "without", metrics_port, exc_info=True)
    service = build_service(env)
    stop = getattr(service, "stop", None)
    if stop is not None:
        signal.signal(signal.SIGTERM, lambda *_: stop())
    service.run()


if __name__ == "__main__":
    main()
