"""LocalPlatform: the whole stack wired for one host / one TPU slice.

The resident-runner deployment (SURVEY.md §7 hard-parts): a single process
owns every chip, services run as threads via ``ThreadContainerManager``,
state lives in sqlite + safetensors files, traffic rides the in-process
bus. The same components re-wire onto subprocess/docker managers and
tcp/postgres backends without code changes — this module is just the
composition root, and the integration-test seam (SURVEY.md §4: real
multi-worker tests on one host, no mocks).
"""

from __future__ import annotations

import logging
import os
import tempfile
import threading
from typing import Optional

from .admin import Admin, ServicesManager
from .admin.app import AdminApp
from .bus import BusServer, MemoryBus, connect
from .container import SystemContext, ThreadContainerManager
from .observe import trace as observe_trace
from .observe import workload as observe_workload
from .parallel.chips import ChipAllocator
from .store import MetaStore, ParamStore

_log = logging.getLogger(__name__)


class LocalPlatform:
    """Everything needed to run jobs on this host.

    ``workdir=None`` → a temp dir (tests); meta/params live under it.
    ``n_chips=None`` → all of ``jax.devices()``.
    ``http=True`` also starts the Admin REST frontend (port 0 = ephemeral).
    """

    def __init__(self, workdir: Optional[str] = None,
                 n_chips: Optional[int] = None, http: bool = False,
                 admin_port: int = 0, bus_uri: str = "",
                 supervise_interval: float = 10.0,
                 stop_jobs_on_shutdown: bool = True,
                 node_id: str = "", adopt_unowned: bool = True):
        # A secondary (join) node sharing another node's meta store must
        # not stop the cluster's jobs when it leaves.
        self.stop_jobs_on_shutdown = stop_jobs_on_shutdown
        self._tmp = None
        if workdir is None:
            self._tmp = tempfile.TemporaryDirectory(prefix="rafiki_tpu_")
            workdir = self._tmp.name
        self.workdir = workdir
        os.makedirs(workdir, exist_ok=True)

        # Node identity must be STABLE across restarts of the same node
        # (host + workdir), or a crashed node's RUNNING service rows
        # would be orphaned forever: the pid-scoped supervise sweep of a
        # restarted process would never match them. Secondary (join)
        # nodes pass an explicit unique node_id instead — they share the
        # primary's workdir and must not collide with it.
        self._lock_fd = None
        if not node_id:
            import hashlib
            import socket

            wd = hashlib.sha1(
                os.path.abspath(workdir).encode()).hexdigest()[:8]
            node_id = f"{socket.gethostname()}/{wd}"
            # Identity is shared by DESIGN across restarts — but two
            # live primaries on the same workdir would each judge the
            # other's services through their own container manager and
            # kill healthy workers. An exclusive flock held for the
            # process lifetime makes the second startup fail fast
            # instead — BEFORE this process opens the running primary's
            # meta.db/bus (a doomed duplicate must not touch them, and
            # the refusal path must have nothing to leak). Join nodes
            # pass explicit unique ids and share the workdir
            # legitimately.
            self._lock_fd = os.open(os.path.join(workdir, "node.lock"),
                                    os.O_CREAT | os.O_RDWR, 0o644)
            import fcntl

            try:
                fcntl.flock(self._lock_fd,
                            fcntl.LOCK_EX | fcntl.LOCK_NB)
            except OSError:
                os.close(self._lock_fd)
                self._lock_fd = None
                raise RuntimeError(
                    f"another primary node already serves workdir "
                    f"{workdir!r} (node_id {node_id}); a second one "
                    f"would supervise-kill the first's workers. Use a "
                    f"different workdir, or join the cluster with "
                    f"`rafiki_tpu join`.") from None

        meta_uri = os.path.join(workdir, "meta.db")
        params_dir = os.path.join(workdir, "params")
        self.meta = MetaStore(meta_uri)
        self.params = ParamStore(params_dir)
        self.bus = connect(bus_uri)
        self.ctx = SystemContext(meta=self.meta, params=self.params,
                                 bus=self.bus)
        self.container = ThreadContainerManager(self.ctx)
        self.allocator = ChipAllocator(n_chips)
        self.services = ServicesManager(
            self.meta, self.container, self.allocator,
            meta_uri=meta_uri, params_dir=params_dir, bus_uri=bus_uri,
            node_id=node_id, adopt_unowned=adopt_unowned,
            log_dir=os.path.join(workdir, "logs"))
        # Span sink for the whole resident-runner process: every
        # service thread (HTTP edges, batcher, workers) appends to
        # <logs>/spans.jsonl, which Admin.get_trace stitches. Subprocess
        # services configure their own sink from RAFIKI_TPU_LOG_DIR
        # (container/services.py) — same file, O_APPEND interleaving.
        observe_trace.configure(self.services.log_dir)
        # Workload-recorder sink (observe/workload.py): dormant unless
        # RAFIKI_TPU_WORKLOAD_RECORD is on — configure just points the
        # would-be <logs>/workload.jsonl at the same shared log dir.
        observe_workload.configure(self.services.log_dir)
        self.admin = Admin(self.meta, self.params, self.services,
                           datasets_dir=os.path.join(workdir, "datasets"))
        # Metrics-driven autoscaler (docs/autoscaling.md): constructed
        # ONLY when RAFIKI_TPU_AUTOSCALE is on (NodeConfig.apply_env
        # exports it; env is the transport so tests flip it the
        # same way the serve CLI does). Off = services.autoscaler stays
        # None: supervise pays one attribute check, zero new series.
        self.autoscaler = None
        from .config import _parse_bool as _pb

        if _pb(os.environ.get("RAFIKI_TPU_AUTOSCALE", "0")):
            from .admin.autoscaler import Autoscaler

            self.autoscaler = Autoscaler.from_env(self.services,
                                                  self.meta)
            self.services.autoscaler = self.autoscaler
        # SLO engine (docs/observability.md "SLOs & alerting"):
        # constructed ONLY when RAFIKI_TPU_SLO_RULES names objectives
        # (apply_env pops it when empty). Off = services.slo_engine
        # stays None: supervise pays one attribute check, zero
        # rafiki_tpu_slo_* series.
        self.slo_engine = None
        if os.environ.get("RAFIKI_TPU_SLO_RULES", "").strip():
            from .admin.slo_engine import SloEngine

            self.slo_engine = SloEngine.from_env(self.services,
                                                 self.meta)
            self.services.slo_engine = self.slo_engine
        # Cluster node registry (docs/cluster.md): constructed ONLY
        # when RAFIKI_TPU_CLUSTER_FABRIC is on (NodeConfig apply_env
        # exports it). Off = services.node_registry stays None: no
        # rafiki_tpu_node_* series, no registry bus traffic, and the
        # heartbeat loop pays one attribute check. The announce rides
        # the EXISTING heartbeat cadence; the eager first announce
        # makes the node visible before the first beat fires.
        self.node_registry = None
        if _pb(os.environ.get("RAFIKI_TPU_CLUSTER_FABRIC", "0")):
            from .admin.nodes import NodeRegistry

            self.node_registry = NodeRegistry(
                self.services.serving_bus,
                node_id=self.services.node_id,
                n_chips=self.allocator.n_chips,
                bus_uri=bus_uri, lease_s=self.services.NODE_LEASE)
            self.services.node_registry = self.node_registry
            try:
                self.node_registry.announce()
            except (ConnectionError, OSError, RuntimeError):
                _log.warning("initial node registry announce failed; "
                             "the heartbeat loop will retry",
                             exc_info=True)
        self.app: Optional[AdminApp] = None
        if http:
            self.app = AdminApp(self.admin, port=admin_port).start()

        # Failure detection (SURVEY.md §5): sweep for dead worker
        # services and restart train workers on fresh chip groups.
        # Interval 0 disables (tests drive supervise() directly).
        self._stop_supervisor = threading.Event()
        self._supervisor: Optional[threading.Thread] = None
        if supervise_interval > 0:
            def _loop() -> None:
                while not self._stop_supervisor.wait(supervise_interval):
                    try:
                        self.services.supervise()
                    except Exception:
                        _log.exception("supervision sweep failed")

            self._supervisor = threading.Thread(
                target=_loop, name="supervisor", daemon=True)
            self._supervisor.start()

        # Liveness heartbeat: ALWAYS on (independent of the supervise
        # interval — disabling the sweep must not silently let this
        # node's lease lapse and make peers judge its live workers
        # dead). Cadence well inside ServicesManager.NODE_LEASE.
        def _beat() -> None:
            interval = self.services.NODE_LEASE / 4.0
            while not self._stop_supervisor.wait(interval):
                try:
                    self.services.heartbeat()
                except Exception:
                    _log.exception("heartbeat failed")

        self._heartbeat = threading.Thread(
            target=_beat, name="heartbeat", daemon=True)
        self._heartbeat.start()

    @classmethod
    def from_config(cls, cfg, http: bool = False) -> "LocalPlatform":
        """Construct from one validated ``NodeConfig`` (SURVEY.md §5
        config plan) — the serve CLI's composition path."""
        return cls(workdir=cfg.workdir, n_chips=cfg.n_chips, http=http,
                   admin_port=cfg.port, bus_uri=cfg.bus_uri,
                   supervise_interval=cfg.supervise_interval)

    @property
    def admin_port(self) -> int:
        assert self.app is not None, "platform started without http=True"
        return self.app.port

    def shutdown(self) -> None:
        self._stop_supervisor.set()
        if self._supervisor is not None:
            self._supervisor.join(timeout=5)
        self._heartbeat.join(timeout=5)
        if self.autoscaler is not None:
            self.services.autoscaler = None
            self.autoscaler.close()  # drop the autoscale series
        if self.slo_engine is not None:
            self.services.slo_engine = None
            self.slo_engine.close()  # drop the slo series
        if self.node_registry is not None:
            self.services.node_registry = None
            try:
                self.node_registry.close()  # withdraw + drop series
            except (ConnectionError, OSError, RuntimeError):
                pass  # broker may already be gone at teardown
        if self.app is not None:
            self.app.stop()
        if self.stop_jobs_on_shutdown:
            for job in self.meta.get_train_jobs(status="RUNNING"):
                self.services.stop_train_services(job["id"])
            for job in self.meta.get_inference_jobs(status="RUNNING"):
                self.services.stop_inference_services(job["id"])
        # Either way, stop what THIS node launched: a leaving join node
        # must not leak RUNNING rows into the shared meta store (they
        # would read as a live remote worker forever and block the
        # primary's job-completion detection).
        self.services.stop_own_services()
        self.meta.close()
        self.params.close()
        if isinstance(self.bus, MemoryBus):
            MemoryBus.reset_shared()
        if self._lock_fd is not None:  # releases the flock too
            os.close(self._lock_fd)
            self._lock_fd = None
        if self._tmp is not None:
            self._tmp.cleanup()
