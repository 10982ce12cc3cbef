"""Admin orchestration: users, models, train jobs, inference jobs.

Parity: SURVEY.md §2 "Admin" + §3.1/§3.2 call stacks (upstream
``rafiki/admin/admin.py``). The REST frontend (``rafiki_tpu.admin.app``)
is a thin shell over this class; everything here is also directly usable
in-process (the resident-runner deployment and the test seam).
"""

from __future__ import annotations

import logging
import time
from typing import Any, Dict, List, Optional

from ..constants import (BudgetOption, InferenceJobStatus, ModelAccessRight,
                         TrainJobStatus, TrialStatus, UserType)
from ..model.knobs import knob_config_to_json
from ..observe import phases
from ..store import MetaStore, ParamStore
from ..utils import auth
from ..utils.model_loader import load_model_class
from .services_manager import ServicesManager, normalize_budget

_log = logging.getLogger(__name__)


class Admin:
    def __init__(self, meta: MetaStore, params: ParamStore,
                 services: ServicesManager, jwt_secret: str = "rafiki-tpu",
                 superadmin_email: str = "superadmin@rafiki",
                 superadmin_password: str = "rafiki",
                 datasets_dir: str = ""):
        self.meta = meta
        self.params = params
        self.services = services
        self.jwt_secret = jwt_secret
        # Uploaded datasets land here (REST/browser upload path); empty
        # disables uploads — jobs can always reference datasets by
        # filesystem path directly.
        self.datasets_dir = datasets_dir
        if self.meta.get_user_by_email(superadmin_email) is None:
            self.meta.create_user(
                superadmin_email, auth.hash_password(superadmin_password),
                UserType.SUPERADMIN)
        # Serializes promote_trial: its validate -> launch -> wait ->
        # swap sequence spans a registration wait, and two concurrent
        # promotes of the same trial would BOTH pass the already-served
        # check and both burn a chip allocation. Promotion is a rare
        # control-plane act; one node-wide lock is the simple fix.
        import threading

        self._promote_lock = threading.Lock()

    # --- Auth / users ---

    def authenticate(self, email: str, password: str) -> Dict[str, Any]:
        user = self.meta.get_user_by_email(email)
        if user is None or not auth.verify_password(password,
                                                   user["password_hash"]):
            raise PermissionError("invalid email or password")
        if user["banned_at"] is not None:
            raise PermissionError("user is banned")
        token = auth.encode_token(
            {"user_id": user["id"], "user_type": user["user_type"]},
            self.jwt_secret)
        return {"user_id": user["id"], "user_type": user["user_type"],
                "token": token}

    def authorize(self, token: str) -> Dict[str, Any]:
        """Decode a bearer token AND re-check the user row: a ban must
        revoke existing sessions immediately, not at token expiry."""
        try:
            claims = auth.decode_token(token, self.jwt_secret)
        except ValueError as e:
            raise PermissionError(f"invalid token: {e}")
        user = self.meta.get_user(claims.get("user_id", ""))
        if user is None or user["banned_at"] is not None:
            raise PermissionError("user is banned or deleted")
        return claims

    def create_user(self, email: str, password: str,
                    user_type: str) -> Dict[str, Any]:
        user = self.meta.create_user(email, auth.hash_password(password),
                                     user_type)
        return {"id": user["id"], "email": email, "user_type": user_type}

    # --- Access control ---

    @staticmethod
    def check_access(claims: Optional[Dict[str, Any]],
                     owner_user_id: str) -> None:
        """Resource-level authorization: the owner, or a platform admin.

        ``claims=None`` means an in-process trusted caller (resident
        runner / tests); the REST layer always passes the token claims.
        """
        if claims is None:
            return
        if claims.get("user_id") == owner_user_id:
            return
        if claims.get("user_type") in (UserType.SUPERADMIN, UserType.ADMIN):
            return
        err = PermissionError("not the owner of this resource")
        err.status = 403  # the REST layer maps this to Forbidden, not 401
        raise err

    def _owned_train_job(self, train_job_id: str,
                         claims: Optional[Dict[str, Any]]) -> Dict[str, Any]:
        job = self.meta.get_train_job(train_job_id)
        if job is None:
            raise ValueError(f"unknown train job {train_job_id}")
        self.check_access(claims, job["user_id"])
        return job

    def _owned_inference_job(self, job_id: str,
                             claims: Optional[Dict[str, Any]],
                             ) -> Dict[str, Any]:
        job = self.meta.get_inference_job(job_id)
        if job is None:
            raise ValueError(f"unknown inference job {job_id}")
        self.check_access(claims, job["user_id"])
        return job

    # --- Models ---

    def create_model(self, user_id: str, name: str, task: str,
                     model_class: str, model_source: Optional[str] = None,
                     dependencies: Optional[Dict[str, str]] = None,
                     access_right: str = ModelAccessRight.PRIVATE,
                     ) -> Dict[str, Any]:
        with phases.span("model_upload"):
            # Resolve now: a model that doesn't import/declare knobs
            # must be rejected at upload, not at trial time.
            cls = load_model_class(model_class, model_source)
            knob_config = knob_config_to_json(cls.get_knob_config())
            row = self.meta.create_model(
                user_id, name, task, model_class, knob_config,
                model_source=model_source, dependencies=dependencies,
                access_right=access_right)
        return {"id": row["id"], "name": name, "task": task}

    def get_models(self, user_id: str,
                   task: Optional[str] = None) -> List[Dict[str, Any]]:
        return [_public_model(m) for m in self.meta.get_models(user_id, task)]

    # --- Datasets ---

    def create_dataset(self, user_id: str, name: str, task: str,
                       data: bytes, filename: str = "") -> Dict[str, Any]:
        """Store an uploaded dataset file (the browser/REST upload path)
        and return its row — ``path`` is what train-job forms submit as
        ``train/val_dataset_path``. Format validation stays with the
        model SDK loaders at train time (the dataset zip is
        task-specific); the upload only persists bytes."""
        import os
        import re

        if not self.datasets_dir:
            raise ValueError("this node has no datasets dir configured")
        if not data:
            raise ValueError("empty dataset upload")
        os.makedirs(self.datasets_dir, exist_ok=True)
        # The stored filename is server-generated; only the extension
        # survives from the client (sanitized), so an hostile filename
        # cannot traverse out of the datasets dir.
        ext = os.path.splitext(filename or "")[1]
        if not re.fullmatch(r"\.[A-Za-z0-9]{1,8}", ext or ""):
            ext = ".zip"
        safe = re.sub(r"[^A-Za-z0-9._-]", "_", name)[:48] or "dataset"
        import sqlite3
        import uuid

        # Bytes land on disk BEFORE the meta row commits: a failed write
        # (ENOSPC, permissions) must not leave a pathless row squatting
        # on the unique name with no delete API to recover it.
        path = os.path.join(self.datasets_dir,
                            f"{uuid.uuid4().hex[:12]}-{safe}{ext}")
        with open(path, "wb") as f:
            f.write(data)
        try:
            row = self.meta.create_dataset(user_id, name, task, path,
                                           len(data))
        except sqlite3.IntegrityError:
            os.unlink(path)
            # The dashboard defaults the name to the filename, so
            # re-uploads are routine — answer with a clear 400, not an
            # opaque constraint error.
            raise ValueError(
                f"you already have a dataset named {name!r}; pick "
                f"another name")
        return dict(row)

    def get_datasets(self, user_id: str,
                     task: Optional[str] = None) -> List[Dict[str, Any]]:
        return self.meta.get_datasets(user_id, task=task)

    # --- Services (dashboard log view) ---

    def _sees_all_services(self,
                           claims: Optional[Dict[str, Any]]) -> bool:
        return claims is None or claims.get("user_type") in (
            UserType.SUPERADMIN, UserType.ADMIN)

    def get_services(self, claims: Optional[Dict[str, Any]] = None,
                     ) -> List[Dict[str, Any]]:
        """Service rows, newest first (dashboard services table).
        Admins see the whole cluster; other users see only services
        working for THEIR jobs — another tenant's worker list (and the
        job structure it implies) is not theirs to read."""
        rows = self.meta.get_services()
        if not self._sees_all_services(claims):
            owned = self.meta.get_owned_service_ids(claims.get("user_id"))
            rows = [r for r in rows if r["id"] in owned]
        rows.sort(key=lambda r: r["created_at"], reverse=True)
        return [{k: r.get(k) for k in
                 ("id", "service_type", "status", "chips", "node_id",
                  "created_at", "stopped_at")} for r in rows]

    def get_service_logs(self, service_id: str, max_bytes: int = 65536,
                         claims: Optional[Dict[str, Any]] = None,
                         ) -> Dict[str, Any]:
        """Tail of one service's captured log (utils/service_logs).
        Same visibility rule as ``get_services``: logs carry trial
        knobs/scores/dataset paths, so only the owning user or an admin
        may read them."""
        from ..utils.service_logs import service_log_path, tail_log

        svc = self.meta.get_service(service_id)
        if svc is None:
            raise ValueError(f"unknown service {service_id}")
        if not self._sees_all_services(claims):
            owner = self.meta.get_service_owner(service_id)
            self.check_access(claims, owner or "")
        text = None
        if self.services.log_dir:
            text = tail_log(
                service_log_path(self.services.log_dir, service_id),
                max_bytes=max_bytes)
        return {"service_id": service_id, "status": svc["status"],
                "log": text,
                "captured": text is not None}

    # --- Train jobs (§3.1) ---

    def create_train_job(self, user_id: str, app: str, task: str,
                         model_ids: List[str], budget: Dict[str, Any],
                         train_dataset_path: str, val_dataset_path: str,
                         advisor_type: Optional[str] = None,
                         ) -> Dict[str, Any]:
        # The set-up phase ``job_submit`` ends where the services'
        # launch begins: the launch starts the worker thread, whose own
        # span (``worker_start``) would otherwise overlap this one.
        with phases.span("job_submit"):
            budget = normalize_budget(budget)
            budget.setdefault(BudgetOption.MODEL_TRIAL_COUNT, 5)
            if not model_ids:
                raise ValueError("model_ids must be non-empty")
            # Validate everything BEFORE inserting rows: a failed
            # validation must not leave an orphaned STARTED job burning
            # the app-version.
            for model_id in model_ids:
                model = self.meta.get_model(model_id)
                if model is None:
                    raise ValueError(f"unknown model {model_id}")
                if model["task"] != task:
                    raise ValueError(
                        f"model {model['name']} is for task "
                        f"{model['task']}, not {task}")
            job = self.meta.create_train_job(
                user_id, app, task, budget, train_dataset_path,
                val_dataset_path, TrainJobStatus.STARTED)
            for model_id in model_ids:
                self.meta.create_sub_train_job(
                    job["id"], model_id, "STARTED",
                    advisor_type=advisor_type)
        self.services.create_train_services(job["id"])
        self.meta.update_train_job(job["id"], status=TrainJobStatus.RUNNING)
        return {"id": job["id"], "app": job["app"],
                "app_version": job["app_version"]}

    def get_train_job(self, train_job_id: str,
                      claims: Optional[Dict[str, Any]] = None,
                      ) -> Dict[str, Any]:
        job = self._owned_train_job(train_job_id, claims)
        self._refresh_train_job_status(job)
        job = self.meta.get_train_job(train_job_id)
        subs = []
        for sub in self.meta.get_sub_train_jobs(train_job_id):
            trials = self.meta.get_trials(sub["id"])
            subs.append({
                "id": sub["id"], "model_id": sub["model_id"],
                "n_trials": len(trials),
                "n_completed": sum(t["status"] == TrialStatus.COMPLETED
                                   for t in trials),
                "n_errored": sum(t["status"] == TrialStatus.ERRORED
                                 for t in trials),
            })
        return {"id": job["id"], "app": job["app"],
                "app_version": job["app_version"], "task": job["task"],
                "status": job["status"], "budget": job["budget"],
                "sub_train_jobs": subs}

    def _refresh_train_job_status(self, job: Dict[str, Any]) -> None:
        if job["status"] != TrainJobStatus.RUNNING:
            return
        if not self.services.train_services_active(job["id"]):
            # Budget exhausted and every worker wound down on its own:
            # tear the services down (releases their chip ranges).
            self.services.stop_train_services(job["id"])
            self.meta.update_train_job(job["id"],
                                       status=TrainJobStatus.STOPPED,
                                       stopped_at=time.time())

    def get_train_jobs(self, user_id: str) -> List[Dict[str, Any]]:
        return [{"id": j["id"], "app": j["app"],
                 "app_version": j["app_version"], "task": j["task"],
                 "status": j["status"]}
                for j in self.meta.get_train_jobs(user_id)]

    def stop_train_job(self, train_job_id: str,
                       claims: Optional[Dict[str, Any]] = None) -> None:
        self._owned_train_job(train_job_id, claims)
        self.services.stop_train_services(train_job_id)
        self.meta.update_train_job(train_job_id,
                                   status=TrainJobStatus.STOPPED,
                                   stopped_at=time.time())

    def get_best_trials(self, train_job_id: str, max_count: int = 2,
                        claims: Optional[Dict[str, Any]] = None,
                        ) -> List[Dict[str, Any]]:
        self._owned_train_job(train_job_id, claims)
        return [_public_trial(t) for t in
                self.meta.get_best_trials_of_train_job(train_job_id,
                                                       max_count)]

    def get_trials(self, train_job_id: str,
                   claims: Optional[Dict[str, Any]] = None,
                   ) -> List[Dict[str, Any]]:
        self._owned_train_job(train_job_id, claims)
        return [_public_trial(t) for t in
                self.meta.get_trials_of_train_job(train_job_id)]

    def get_trial_logs(self, trial_id: str,
                       claims: Optional[Dict[str, Any]] = None,
                       ) -> List[Dict[str, Any]]:
        trial = self.meta.get_trial(trial_id)
        if trial is None:
            raise ValueError(f"unknown trial {trial_id}")
        if claims is not None:
            sub = self.meta.get_sub_train_job(trial["sub_train_job_id"])
            self._owned_train_job(sub["train_job_id"], claims)
        return self.meta.get_trial_logs(trial_id)

    def wait_until_train_job_done(self, train_job_id: str,
                                  timeout: float = 3600.0,
                                  poll: float = 1.0) -> bool:
        """Block until every train worker stops; False on timeout."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if not self.services.train_services_active(train_job_id):
                job = self.meta.get_train_job(train_job_id)
                self._refresh_train_job_status(job)
                return True
            time.sleep(poll)
        return False

    def attach_workers(self, train_job_id: str, chips_per_trial: int = 1,
                       ) -> List[Dict[str, Any]]:
        """Elastic scale-out (SURVEY.md §2.10 multi-host plan): attach
        one extra train worker per sub-job of a RUNNING job on THIS
        node's chips. Called on a secondary node sharing the meta store,
        params dir and bus (the ``join`` CLI); the new workers pull
        proposals from the job's existing bus-hosted advisor."""
        job = self.meta.get_train_job(train_job_id)
        if job is None:
            raise ValueError(f"unknown train job {train_job_id}")
        if job["status"] != TrainJobStatus.RUNNING:
            raise ValueError(f"train job {train_job_id} is not RUNNING")
        attached = []
        for sub in self.meta.get_sub_train_jobs(train_job_id):
            svc = self.services.add_train_worker(sub["id"], chips_per_trial)
            if svc is not None:
                attached.append(svc)
        return attached

    def attach_inference_workers(self, inference_job_id: str,
                                 chips_per_worker: int = 1,
                                 ) -> List[Dict[str, Any]]:
        """Elastic serving scale-out: attach one REPLICA worker per
        served trial bin of a RUNNING inference job on THIS node's
        chips (the ``join --inference-job`` path). The Predictor
        shards each super-batch across same-bin replicas
        (latency-weighted data parallelism), so QPS scales with
        unchanged ensemble semantics."""
        job = self.meta.get_inference_job(inference_job_id)
        if job is None:
            raise ValueError(f"unknown inference job {inference_job_id}")
        if job["status"] != InferenceJobStatus.RUNNING:
            raise ValueError(
                f"inference job {inference_job_id} is not RUNNING")
        from .services_manager import PREDICTOR_TRIAL

        bins = {w["trial_id"]
                for w in self.meta.get_inference_job_workers(
                    inference_job_id)
                if w["trial_id"] != PREDICTOR_TRIAL}
        attached = []
        for trial_id in sorted(bins):
            svc = self.services.add_inference_worker(
                inference_job_id, trial_id, chips_per_worker)
            if svc is not None:
                attached.append(svc)
        return attached

    # --- Inference jobs (§3.2) ---

    def create_inference_job(self, user_id: str, train_job_id: str,
                             max_models: int = 2,
                             chips_per_worker: int = 1,
                             claims: Optional[Dict[str, Any]] = None,
                             ) -> Dict[str, Any]:
        """``chips_per_worker > 1`` deploys each serving worker on a
        LARGER chip group — with a group spanning the node's slice,
        the whole best-N ensemble packs onto ONE worker (the compiled
        megabatch shape: stacked same-family bins serve as one vmapped
        dispatch over the full dp width; docs/serving.md)."""
        self._owned_train_job(train_job_id, claims)
        best = self.meta.get_best_trials_of_train_job(train_job_id,
                                                      max_models)
        if not best:
            raise ValueError(
                f"train job {train_job_id} has no completed trials")
        from ..observe import lm as obs_lm

        if obs_lm.generate_enabled():
            # A model class that says it cannot generate fails the
            # deploy here, with its reason, not in a worker thread.
            for model_id in {trial["model_id"] for trial in best}:
                row = self.meta.get_model(model_id)
                refusal = getattr(load_model_class(
                    row["model_class"], row.get("model_source")),
                    "GENERATE_REFUSAL", None)
                if refusal:
                    raise ValueError(refusal)
        inf = self.meta.create_inference_job(user_id, train_job_id,
                                             InferenceJobStatus.STARTED)
        try:
            self.services.create_inference_services(
                inf["id"], [t["id"] for t in best],
                chips_per_worker=chips_per_worker)
        except Exception:
            self.meta.update_inference_job(inf["id"],
                                           status=InferenceJobStatus.ERRORED)
            raise
        self.meta.update_inference_job(inf["id"],
                                       status=InferenceJobStatus.RUNNING)
        return {"id": inf["id"], "train_job_id": train_job_id,
                "trial_ids": [t["id"] for t in best]}

    def get_inference_job(self, inference_job_id: str,
                          claims: Optional[Dict[str, Any]] = None,
                          ) -> Dict[str, Any]:
        return dict(self._owned_inference_job(inference_job_id, claims))

    def promote_trial(self, inference_job_id: str, trial_id: str,
                      replace_trial_id: Optional[str] = None,
                      register_timeout: float = 180.0,
                      claims: Optional[Dict[str, Any]] = None,
                      ) -> Dict[str, Any]:
        """Promote a trained trial into a RUNNING inference job's
        serving ensemble — the online half of train→serve, without a
        job restart.

        A worker for ``trial_id`` is launched and *waited for* (its bus
        registration is the moment the Predictor can plan shards onto
        it); only then are ``replace_trial_id``'s workers stopped (omit
        it for an additive promotion that grows the ensemble by one
        bin). Finally the predictor frontend's edge cache is
        invalidated — synchronously, BEFORE this call returns — so no
        request arriving after the promotion can be answered from a
        pre-promotion cache entry: the epoch bump also voids any
        still-in-flight pre-promotion scatter's insert. In-flight
        requests (including coalesced cache waiters) that scattered
        before the swap complete against the old ensemble, exactly like
        any request racing a deploy.

        Promotions are serialized node-wide (``_promote_lock``): the
        validate→launch→wait→swap sequence spans a registration wait,
        so a concurrent duplicate promote would otherwise pass the
        already-served check too and double-allocate.
        """
        with self._promote_lock:
            return self._promote_trial_locked(
                inference_job_id, trial_id, replace_trial_id,
                register_timeout, claims)

    def _promote_trial_locked(self, inference_job_id: str,
                              trial_id: str,
                              replace_trial_id: Optional[str],
                              register_timeout: float,
                              claims: Optional[Dict[str, Any]],
                              ) -> Dict[str, Any]:
        job = self._owned_inference_job(inference_job_id, claims)
        if job["status"] != InferenceJobStatus.RUNNING:
            raise ValueError(
                f"inference job {inference_job_id} is not RUNNING")
        trial = self.meta.get_trial(trial_id)
        if trial is None:
            raise ValueError(f"unknown trial {trial_id}")
        if trial["status"] != TrialStatus.COMPLETED or \
                not trial.get("params_id"):
            raise ValueError(
                f"trial {trial_id} is not COMPLETED with saved params")
        sub = self.meta.get_sub_train_job(trial["sub_train_job_id"])
        if sub is None or sub["train_job_id"] != job["train_job_id"]:
            raise ValueError(
                f"trial {trial_id} does not belong to train job "
                f"{job['train_job_id']}")
        rows = self.services.active_inference_workers(inference_job_id)
        served_bins = {w["trial_id"] for w in rows}
        if any(trial_id in str(b).split(",") for b in served_bins):
            raise ValueError(
                f"trial {trial_id} is already served by this job")
        old_rows: List[Dict[str, Any]] = []
        multi_rows: List[Dict[str, Any]] = []
        if replace_trial_id is not None:
            for w in rows:
                members = str(w["trial_id"]).split(",")
                if replace_trial_id not in members:
                    continue
                (multi_rows if len(members) > 1 else old_rows).append(w)
            if not old_rows and not multi_rows:
                raise ValueError(
                    f"trial {replace_trial_id} is not a served bin of "
                    f"this job")
            if multi_rows and old_rows:
                raise ValueError(
                    f"trial {replace_trial_id} is served both alone "
                    f"and inside a packed bin; promotion cannot "
                    f"target that mix")
        if multi_rows:
            # Surgical member replacement inside a packed bin — only
            # for workers that advertise ``stacked: true``: their
            # vmap-stacked weights swap ONE member's slices in place
            # (worker-side restack), the other members stay
            # device-resident, and no new worker launches. Per-member
            # runners cannot do this safely (the r12 refusal stands).
            # rta: disable=RTA105 deliberate (r12 rationale): holding _promote_lock across the restack wait is what serializes concurrent promotes of one trial; see promote_trial's docstring
            result = self._restack_packed_bins(
                job, trial_id, replace_trial_id, multi_rows,
                register_timeout)
            self._invalidate_predictor_cache(job)
            return result
        # Launch + wait-for-registration + teardown live in the
        # ServicesManager now (swap_inference_worker, the public
        # hot-swap seam): the new bin must be LIVE on the bus before
        # the old one stops, or the swap would drop the bin's vote —
        # and the incoming worker re-reads the serving env at load, so
        # e.g. int8 quant scales are recomputed for the promoted bin.
        # rta: disable=RTA105 deliberate (r12): holding _promote_lock across the registration wait IS the double-allocation fix; see promote_trial's docstring
        swap = self.services.swap_inference_worker(
            inference_job_id, trial_id,
            replace_service_ids=[w["service_id"] for w in old_rows],
            register_timeout=register_timeout)
        self._invalidate_predictor_cache(job)
        _log.info("promoted trial %s into inference job %s (replaced "
                  "%s; stopped %d worker(s))", trial_id,
                  inference_job_id, replace_trial_id,
                  len(swap["stopped_service_ids"]))
        return {"inference_job_id": inference_job_id,
                "promoted_trial_id": trial_id,
                "replaced_trial_id": replace_trial_id,
                "new_service_id": swap["new_service"]["id"],
                "stopped_service_ids": swap["stopped_service_ids"]}

    def _restack_packed_bins(self, job: Dict[str, Any],
                             trial_id: str, replace_trial_id: str,
                             multi_rows: List[Dict[str, Any]],
                             register_timeout: float,
                             ) -> Dict[str, Any]:
        """The stacked promote path: push a ``__restack__`` marker to
        every worker serving the packed bin, then WAIT for each
        worker's re-registration to show the new member (the worker
        re-registers only after the member's weights are swapped into
        the stacked device arrays — the moment the new bin serves).
        A worker whose restack fails (incongruent family, load error)
        keeps its old registration, so the poll times out and this
        raises — after converging the REST of the replicas back: any
        worker that already confirmed gets a reverse restack
        (new → old) so a multi-replica bin does not keep serving
        split-brain, and the predictor edge cache is invalidated
        best-effort (a still-queued marker on a backlogged worker may
        apply after this raises; the predictor's serving-vector
        self-check is the backstop for any answer cached across that
        late swap)."""
        import time as _time

        from ..cache import Cache as _BusCache

        inference_job_id = job["id"]
        cache = _BusCache(self.services.serving_bus())
        info = cache.running_worker_info(inference_job_id)
        not_stacked = [w["service_id"] for w in multi_rows
                       if not (info.get(w["service_id"]) or {})
                       .get("stacked")]
        if not_stacked:
            raise ValueError(
                f"bin {multi_rows[0]['trial_id']!r} packs several "
                f"trials and worker(s) "
                f"{[s[:8] for s in not_stacked]} serve it per-member; "
                f"promotion cannot surgically replace one member — "
                f"replace the whole bin (stacked workers restack in "
                f"place; see docs/serving.md)")
        for w in multi_rows:
            cache.send_restack(w["service_id"], replace_trial_id,
                               trial_id)
        deadline = _time.monotonic() + register_timeout
        pending = {w["service_id"] for w in multi_rows}
        confirmed: List[str] = []
        while pending:
            if _time.monotonic() >= deadline:
                self._rollback_restacks(cache, inference_job_id,
                                        confirmed, trial_id,
                                        replace_trial_id, job)
                raise RuntimeError(
                    f"worker(s) {[s[:8] for s in sorted(pending)]} did "
                    f"not confirm the restack within "
                    f"{register_timeout}s; confirmed replica(s) "
                    f"{[s[:8] for s in confirmed]} were rolled back "
                    f"(reverse restack) so the old member set keeps "
                    f"serving")
            info = cache.running_worker_info(inference_job_id)
            for sid in list(pending):
                members = str((info.get(sid) or {})
                              .get("trial_id", "")).split(",")
                if trial_id in members and \
                        replace_trial_id not in members:
                    pending.discard(sid)
                    confirmed.append(sid)
            if pending:
                # rta: disable=RTA102 deliberate (r12 rationale): the registration-confirm poll must complete under _promote_lock or a concurrent promote could double-target the bin mid-swap
                _time.sleep(0.1)
        _log.info("promoted trial %s into inference job %s by "
                  "restacking %d packed worker(s) (replaced %s in "
                  "place)", trial_id, inference_job_id,
                  len(multi_rows), replace_trial_id)
        return {"inference_job_id": inference_job_id,
                "promoted_trial_id": trial_id,
                "replaced_trial_id": replace_trial_id,
                "new_service_id": None,
                "restacked_service_ids": [w["service_id"]
                                          for w in multi_rows],
                "stopped_service_ids": []}

    def _rollback_restacks(self, cache, inference_job_id: str,
                           confirmed: List[str], trial_id: str,
                           replace_trial_id: str,
                           job: Dict[str, Any]) -> None:
        """Failure half of the surgical promote: reverse-restack every
        replica that already swapped (so the bin converges back to the
        OLD member set instead of serving split-brain) and invalidate
        the predictor edge cache — answers computed during the partial
        window must not outlive it. Both are best-effort: the promote
        is raising anyway, and the reverse marker rides the same
        queue-ordered mechanism as the forward one."""
        for sid in confirmed:
            try:
                cache.send_restack(sid, trial_id, replace_trial_id)
            except (ConnectionError, OSError, RuntimeError):
                _log.exception(
                    "reverse restack to %s failed; the replica keeps "
                    "the promoted member until the next promote",
                    sid[:8])
        if confirmed:
            try:
                self._invalidate_predictor_cache(job)
            except RuntimeError:
                _log.exception("edge-cache invalidation after a "
                               "partial restack failed")

    def _invalidate_predictor_cache(self, job: Dict[str, Any]) -> None:
        """Synchronous edge-cache invalidation on the job's predictor
        frontend — the promotion-correctness step. Failure raises: the
        ensemble already changed, and an unreachable frontend means
        cached pre-promotion answers could outlive the swap (the
        predictor's serving-vector cross-check would catch it on the
        next miss, but 'eventually' is not the promotion contract)."""
        import json as _json
        from urllib.request import Request, urlopen

        # Cluster fabric (docs/cluster.md): with several frontends the
        # job-row predictor_host names only the last-started one, so
        # the synchronous invalidate fans out to EVERY frontend in the
        # bus registry — each must acknowledge, or a peer could keep
        # serving (or re-exporting, via peer probes) pre-promotion
        # answers for its whole TTL. Single-node deploys have no
        # registry entries and keep the one-host path.
        hosts = []
        try:
            from ..cache import Cache as _BusCache

            hosts = sorted(_BusCache(self.services.serving_bus())
                           .frontends(job["id"]).values())
        except (ConnectionError, OSError, RuntimeError):
            _log.warning("frontend registry unreachable; falling back "
                         "to the job-row predictor host", exc_info=True)
        if not hosts:
            host = job.get("predictor_host")
            if not host:
                return  # no frontend deployed yet — nothing caches
            hosts = [host]
        for host in hosts:
            try:
                req = Request(f"http://{host}/cache/invalidate",
                              data=b"{}",
                              headers={"Content-Type":
                                       "application/json"},
                              method="POST")
                with urlopen(req, timeout=10) as resp:
                    _json.loads(resp.read())
            except OSError as e:
                raise RuntimeError(
                    f"promotion applied but the predictor at {host} "
                    f"did not acknowledge cache invalidation: {e}"
                ) from None

    def get_inference_job_stats(self, inference_job_id: str,
                                claims: Optional[Dict[str, Any]] = None,
                                ) -> Dict[str, Any]:
        """The job's predictor ``/stats`` snapshot, proxied server-side
        so the dashboard (same-origin against admin) can render queue
        depth / coalescing / per-stage latency without CORS and with
        the same ownership check every other job read gets."""
        import json as _json
        from urllib.request import urlopen

        job = self._owned_inference_job(inference_job_id, claims)
        host = job.get("predictor_host")
        if not host:
            raise ValueError(
                f"inference job {inference_job_id} has no predictor yet")
        try:
            with urlopen(f"http://{host}/stats", timeout=5) as resp:
                stats = _json.loads(resp.read())
        except OSError as e:
            raise ValueError(
                f"predictor at {host} unreachable: {e}") from None
        stats["inference_job_id"] = inference_job_id
        # Exemplars (when RAFIKI_TPU_METRICS_EXEMPLARS is on): the
        # frontend's /predict latency buckets each remember the last
        # traced observation, so the dashboard can link a p99 bucket
        # straight to its stitched GET /trace/<id> timeline. Resident-
        # runner visibility: the predictor shares this process's
        # registry; a subprocess frontend's exemplars ride its own
        # /metrics and this proxy simply reports none.
        from ..observe import metrics as obs_metrics

        hist = obs_metrics.registry().find(
            "rafiki_tpu_http_request_seconds")
        if hist is not None and stats.get("http_service"):
            stats["exemplars"] = hist.exemplars(
                service=stats["http_service"], route="/predict")
        return stats

    def profile_inference_job(self, inference_job_id: str,
                              duration_s: float = 5.0,
                              claims: Optional[Dict[str, Any]] = None,
                              ) -> Dict[str, Any]:
        """Trigger a bounded on-demand ``jax.profiler`` session on ONE
        live inference worker of the job (``POST
        /inference_jobs/<id>/profile``). The request travels as a
        queue-ordered ``__profile__`` control frame — exactly the
        drain/restack mechanism — so the worker starts the session
        between bursts and its serve loop stops it at the deadline:
        serving is never paused, the profile just observes the bursts
        that run inside its window. The artifact lands under the
        service log dir (``profiles/<job>/<ts>``, TensorBoard's
        profile plugin reads it); a worker whose profiler is busy (a
        trial trace in flight) skips the request, which the caller
        sees as an empty artifact dir."""
        import os as _os
        import uuid as _uuid

        from ..cache import Cache
        from ..observe.profiling import PROFILE_MAX_S

        job = self._owned_inference_job(inference_job_id, claims)
        if job["status"] != InferenceJobStatus.RUNNING:
            raise ValueError(
                f"inference job {inference_job_id} is not RUNNING")
        try:
            duration_s = float(duration_s)
        except (TypeError, ValueError):
            raise ValueError(f"duration_s {duration_s!r} is not a "
                             f"number") from None
        # Bounded by contract: the profiler holds device buffers and a
        # process-wide lock, so an abusive duration must clamp, not
        # honor.
        duration_s = min(max(0.5, duration_s), PROFILE_MAX_S)
        rows = self.services.active_inference_workers(inference_job_id)
        if not rows:
            raise ValueError(
                f"inference job {inference_job_id} has no active "
                f"workers to profile")
        target = rows[0]["service_id"]
        base = self.services.log_dir
        if not base:  # log capture disabled; still give the artifact
            import tempfile as _tempfile  # a well-known place to land

            base = _os.path.join(_tempfile.gettempdir(),
                                 "rafiki_tpu_profiles")
        out_dir = _os.path.join(
            base, "profiles", inference_job_id[:8],
            f"{int(time.time())}-{_uuid.uuid4().hex[:6]}")
        Cache(self.services.serving_bus()).send_profile(
            target, out_dir, duration_s)
        _log.info("profile session queued on worker %s of job %s "
                  "(%.1fs into %s)", target[:8], inference_job_id[:8],
                  duration_s, out_dir)
        return {"inference_job_id": inference_job_id,
                "service_id": target,
                "duration_s": duration_s,
                "profile_dir": out_dir}

    def get_trace(self, trace_id: str,
                  claims: Optional[Dict[str, Any]] = None,
                  ) -> Dict[str, Any]:
        """Stitch one trace's span events (collected from the service
        log dir's ``spans.jsonl``) into an ordered timeline — the
        answer to "why was this /predict slow" as one call."""
        # Spans carry timing + service/trial ids only; visible to any
        # authenticated user (the trace id itself is an unguessable
        # 128-bit capability handed to the caller that issued the
        # traced request).
        from ..observe import trace as trace_mod

        log_dir = self.services.log_dir
        if not log_dir:
            return {"trace_id": trace_id, "n_spans": 0, "spans": []}
        return trace_mod.collect_trace(log_dir, trace_id)

    def get_trial_phases(self) -> Dict[str, Any]:
        """Cumulative trial-lifecycle phase breakdown + residency-cache
        counters for the dashboard's trial view. Same visibility caveat
        as the /status MFU gauge: resident-runner mode puts the workers
        in THIS process so the registry has the series; subprocess
        workers publish the same families on their own /metrics, which
        this endpoint cannot see — ``resident`` says which case this is
        so the UI can label an all-zero table honestly."""
        from ..observe import metrics as obs_metrics
        from ..observe import phases as obs_phases

        totals = obs_phases.phase_totals()
        # Only a trial phase says a worker ran here: every process
        # counts its own set-up and compile phases.
        resident = any(totals[p]["count"] for p in obs_phases.TRIAL_PHASES)
        phases = {
            p: {"count": int(v["count"]),
                "total_s": round(v["sum"], 3),
                "mean_ms": round(v["sum"] / v["count"] * 1e3, 1)
                if v["count"] else 0.0}
            for p, v in totals.items()}
        caches = {c: obs_phases.cache_counts(c)
                  for c in obs_phases.CACHES}
        return {"enabled": obs_metrics.metrics_enabled(),
                "resident": resident, "phases": phases,
                "caches": caches, "moe": obs_phases.moe_counts(),
                "dump_leaves": obs_phases.dump_leaf_counts()}

    def get_autoscale(self) -> Dict[str, Any]:
        """The autoscaler's decision ring + per-bin targets (the
        ``GET /autoscale`` body; docs/autoscaling.md). Disabled nodes
        answer ``enabled: false`` — the dashboard renders the panel
        only when the loop is actually closed."""
        scaler = getattr(self.services, "autoscaler", None)
        if scaler is None:
            return {"enabled": False}
        return scaler.snapshot()

    def get_nodes(self) -> Dict[str, Any]:
        """The cluster node registry snapshot (the ``GET /nodes``
        body; docs/cluster.md). Single-node deployments answer
        ``enabled: false`` — the fabric is opt-in and the dashboard
        renders the cluster view only when a registry exists."""
        registry = getattr(self.services, "node_registry", None)
        if registry is None:
            return {"enabled": False}
        return registry.snapshot()

    def get_slo(self) -> Dict[str, Any]:
        """The SLO engine's objective/instance snapshot (the
        ``GET /slo`` body; docs/observability.md "SLOs & alerting").
        Disabled nodes answer ``enabled: false`` — the dashboard
        renders the panel only when the plane is armed."""
        engine = getattr(self.services, "slo_engine", None)
        if engine is None:
            return {"enabled": False}
        return engine.snapshot()

    def get_alerts(self) -> Dict[str, Any]:
        """The SLO engine's alert-transition ring (``GET /alerts``),
        newest first; ``enabled: false`` on unarmed nodes."""
        engine = getattr(self.services, "slo_engine", None)
        if engine is None:
            return {"enabled": False}
        return engine.alerts_snapshot()

    def get_capacity(self) -> Dict[str, Any]:
        """The capacity engine's snapshot (``GET /capacity``;
        docs/capacity.md): the node's recorded-workload inventory plus
        a canned-ramp policy-gate run of the policy this node would
        apply. Always enabled — the gate needs no live traffic, only
        the simulator."""
        from . import capacity as capacity_mod

        return capacity_mod.admin_snapshot(self.services)

    def get_inference_jobs(self, user_id: str) -> List[Dict[str, Any]]:
        return [dict(j) for j in self.meta.get_inference_jobs(user_id)]

    def get_status(self) -> Dict[str, Any]:
        """Node status for operators: chip allocation, live services,
        and — with several nodes sharing this meta store — a per-node
        cluster view (service counts + heartbeat age, so a stalled
        join node is visible before its lease expires)."""
        alloc = self.services.allocator
        running = self.meta.get_services(status="RUNNING")
        by_type: Dict[str, int] = {}
        nodes: Dict[str, Dict[str, Any]] = {}
        now = time.time()
        this_node = self.services.node_id
        for s in running:
            by_type[s["service_type"]] = by_type.get(s["service_type"],
                                                     0) + 1
            # NULL node_id rows (pre-upgrade databases) attribute to
            # whoever adopted them — the same ownership rule the
            # supervisor applies — so a one-node cluster never renders
            # a phantom "(unowned)" second node.
            own = self.services._ownership(s)
            nid = this_node if own == "local" else (
                s.get("node_id") or "(unowned)")
            node = nodes.setdefault(nid, {"services": 0,
                                          "heartbeat_age_s": None})
            node["services"] += 1
            hb = self.services.last_heartbeat(s)
            if hb:
                age = round(max(0.0, now - hb), 1)
                if node["heartbeat_age_s"] is None \
                        or age < node["heartbeat_age_s"]:
                    node["heartbeat_age_s"] = age
        nodes.setdefault(this_node, {"services": 0,
                                     "heartbeat_age_s": 0.0})
        # Per-trial chip utilization: the train loop publishes an MFU
        # gauge into the process registry (resident-runner mode puts
        # the workers in THIS process; subprocess workers expose the
        # same series on their own /metrics).
        from ..observe import metrics as obs_metrics

        mfu: Dict[str, float] = {}
        gauge = obs_metrics.registry().find("rafiki_tpu_train_mfu_ratio")
        if gauge is not None:
            for labels, value in gauge.samples():
                mfu[labels.get("trial", "(unlabeled)")] = round(value, 4)
        out = {
            "n_chips": alloc.n_chips,
            "free_chips": alloc.free_chips,
            "chip_allocation": round(alloc.utilization(), 4),
            "services_running": by_type,
            "node_id": this_node,
            "nodes": nodes,
            "mfu": mfu,
        }
        # Cluster fabric fold (docs/cluster.md): the meta-derived node
        # view above only sees nodes with RUNNING services; the
        # registry also counts idle-but-live peers, so operators see a
        # joined-but-empty node here before it serves anything.
        registry = getattr(self.services, "node_registry", None)
        if registry is not None:
            try:
                out["cluster"] = registry.health()
            except (ConnectionError, OSError, RuntimeError):
                out["cluster"] = {"fabric": True, "error": "registry "
                                  "unreachable"}
        return out

    # --- User administration (ADMIN-only; enforced by the REST layer) ---

    def get_users(self) -> List[Dict[str, Any]]:
        return [{"id": u["id"], "email": u["email"],
                 "user_type": u["user_type"],
                 "banned": u["banned_at"] is not None}
                for u in self.meta.get_users()]

    def ban_user(self, user_id: str,
                 claims: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        target = self.meta.get_user(user_id)
        if target is None:
            raise ValueError(f"unknown user {user_id}")
        # The root account must stay recoverable (there is no unban
        # route), and self-bans lock out the very session issuing them.
        if target["user_type"] == UserType.SUPERADMIN:
            raise PermissionError("the superadmin cannot be banned")
        if claims is not None and claims.get("user_id") == user_id:
            raise PermissionError("cannot ban yourself")
        self.meta.ban_user(user_id)
        return {"banned": user_id}

    def stop_inference_job(self, inference_job_id: str,
                           claims: Optional[Dict[str, Any]] = None) -> None:
        self._owned_inference_job(inference_job_id, claims)
        self.services.stop_inference_services(inference_job_id)
        self.meta.update_inference_job(inference_job_id,
                                       status=InferenceJobStatus.STOPPED,
                                       stopped_at=time.time())


def _public_model(m: Dict[str, Any]) -> Dict[str, Any]:
    return {"id": m["id"], "name": m["name"], "task": m["task"],
            "model_class": m["model_class"],
            "access_right": m["access_right"]}


def _public_trial(t: Dict[str, Any]) -> Dict[str, Any]:
    return {"id": t["id"], "no": t["no"], "score": t["score"],
            "knobs": t["knobs"], "status": t["status"],
            "params_id": t["params_id"]}
