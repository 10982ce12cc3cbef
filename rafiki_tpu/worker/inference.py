"""InferenceWorker: serves one trained trial's model from its chip group.

Parity: SURVEY.md §2 "InferenceWorker" + §3.3 — loads a trial's params,
registers itself with the cache, then loops: pop a burst of queries from
its queue, run ``predict`` (batched on the chip; ``JaxModel`` AOT-compiles
per batch bucket so variable load never retraces), push each prediction to
the query's reply queue.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Any, Optional

# numpy is hoisted to module level on purpose (r13 satellite): the old
# per-call ``import numpy`` in prediction_confidence paid an
# import-machinery check per PREDICTION on the burst path. The module
# already pulls numpy transitively (``..cache`` imports it at top), so
# this costs nothing at import time.
import numpy as np

from .. import faults
from ..bus import BaseBus, BusOpError
from ..cache import DRAIN_KEY as _CACHE_DRAIN_KEY
from ..cache import PROFILE_KEY as _CACHE_PROFILE_KEY
from ..cache import RESTACK_KEY as _CACHE_RESTACK_KEY
from ..cache import WIRE_NDBATCH, Cache
from ..constants import ServiceStatus
from ..observe import attribution as _attr
from ..observe import lm as _lm_obs
from ..observe import trace
from ..observe import wire as _wire
from ..parallel.chips import ChipGroup
from ..store import MetaStore, ParamStore
from ..utils.model_loader import load_model_class

_log = logging.getLogger(__name__)

# jax.numpy, lazily bound once (the _SYNC_PROBE pattern): the worker
# module must stay importable without dragging the accelerator runtime
# in, but a resolved global costs the burst path zero import checks.
_jnp = None


def _jnp_mod():
    global _jnp
    if _jnp is None:
        import jax.numpy

        _jnp = jax.numpy
    return _jnp


def prediction_confidence(pred: Any) -> Optional[float]:
    """Per-query confidence for the tiered serving path: the softmax
    margin (top-1 minus top-2 probability) when the prediction exposes
    a flat numeric vector, else None — sk-style label outputs, packed
    ``__members__`` envelopes, and error dicts all degrade gracefully
    to "no confidence" (the Predictor escalates those)."""
    try:
        if isinstance(pred, np.ndarray):
            arr = pred
        elif isinstance(pred, (list, tuple)) and len(pred) >= 2 and \
                not isinstance(pred[0], (list, tuple, dict, str)):
            arr = np.asarray(pred)
        else:
            return None
        if arr.ndim != 1 or arr.size < 2 or \
                not np.issubdtype(arr.dtype, np.number):
            return None
        arr = arr.astype(np.float64, copy=False)
        if not np.isfinite(arr).all():
            return None
        top2 = np.partition(arr, arr.size - 2)[-2:]
        return float(top2[1] - top2[0])
    except (TypeError, ValueError):
        return None


def _sync_probe_fn():
    """One process-wide jitted probe (a fresh lambda per call would
    re-compile inside every worker's startup)."""
    global _SYNC_PROBE
    if _SYNC_PROBE is None:
        import jax

        _SYNC_PROBE = jax.jit(lambda a: (a + 1.0).sum())
    return _SYNC_PROBE


_SYNC_PROBE = None


def _sync_latency(n: int = 3) -> float:
    """Best-of-n device->host round-trip time for a tiny dispatch —
    the constant the one-burst-in-flight overlap can hide."""
    import time

    jnp = _jnp_mod()
    f = _sync_probe_fn()
    x = jnp.zeros((8, 8), jnp.float32)
    np.asarray(f(x))  # compile outside the timed window
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        np.asarray(f(x))
        best = min(best, time.perf_counter() - t0)
    return best


class _PackedEnsemble:
    """Several trial models sharing one chip group, served as one unit.

    ``predict_submit`` dispatches every member's compute back-to-back
    (all async) before any result readback, so members overlap on the
    device. With a STACKED group (``stacked`` — same-family members
    whose weights rode one device_put as a vmap-stacked pytree,
    ``model/jax_model.stack_members``) the whole burst is instead ONE
    compiled dispatch producing per-member probabilities; the
    per-member finishers it yields slice one shared readback, so
    ``_finish_members`` consumes both modes unchanged. The finisher
    pre-averages numeric (probability) predictions and reports
    ``last_weight`` = surviving member count, so the Predictor's
    weighted cross-worker mean equals the unweighted mean over all
    trials; non-numeric predictions ship un-combined in a
    ``__members__`` envelope (the Predictor votes over individual
    trials — pre-voting would lose the member distribution). A failing
    member drops ONLY its own vote: the other packed trials keep
    serving (per-member fault isolation — in stacked mode via the
    member-validity mask, and a burst the stacked program cannot take
    falls back to the per-member runners below).
    """

    def __init__(self, models: list, stacked: Optional[Any] = None):
        self.models = models
        self.stacked = stacked
        self.last_weight = len(models)
        # Dispatch-variant breakdown for the attribution ledger:
        # "stacked" (one vmapped program served the burst), "fallback"
        # (stacked-capable worker served per-member), or "members"
        # (plain packed ensemble — no stacked group formed).
        self.last_mode = "members"

    def _stacked_usable(self) -> bool:
        return self.stacked is not None and self.stacked.n_valid > 0

    def _count_fallback(self, n_dispatches: int, n_queries: int) -> None:
        """Per-member dispatch accounting on a stacked-CAPABLE worker
        (the evidence half of the dispatch-count gate); a plain packed
        ensemble (no stacked group formed / knob off) records nothing
        — the off side must expose zero stacked series."""
        if self.stacked is not None:
            self.last_mode = "fallback"
            _wire.count_stacked_dispatch("fallback", n_dispatches)
            _wire.observe_dispatches_per_query(n_dispatches, n_queries)
        else:
            self.last_mode = "members"

    def predict_submit(self, queries: list):
        if self._stacked_usable():
            try:
                handles = self.stacked.submit(queries)
            except Exception:
                _log.exception("stacked dispatch failed; serving this "
                               "burst per-member")
            else:
                self.last_mode = "stacked"
                _wire.count_stacked_dispatch("stacked", len(handles))
                _wire.observe_dispatches_per_query(len(handles),
                                                   len(queries))
                return self._finish_members(
                    self.stacked.member_finishers(handles),
                    len(queries))
        finishers = []
        for m in self.models:
            try:
                finishers.append(m.predict_submit(queries))
            except Exception:
                _log.exception("packed member dispatch failed; dropping "
                               "its vote")
        self._count_fallback(len(finishers), len(queries))
        return self._finish_members(finishers, len(queries))

    def predict_bucket(self, n: int, dtype: Any = None) -> Optional[int]:
        """Staged-path negotiation for the whole pack: every member
        must take the burst at the SAME bucket (they share one chip
        group, so same dp — differing buckets would mean mismatched
        staging shapes); any member without a staged entry, or any
        disagreement, falls the burst back to the per-query path. A
        stacked group answers once for everyone (congruence guarantees
        agreement)."""
        if self._stacked_usable():
            return self.stacked.predict_bucket(n, dtype)
        buckets = set()
        for m in self.models:
            fn = getattr(m, "predict_bucket", None)
            if fn is None:
                return None
            b = fn(n, dtype)
            if b is None:
                return None
            buckets.add(b)
        return buckets.pop() if len(buckets) == 1 else None

    def predict_staged_submit(self, buf, n: int):
        """Staged dispatch for the pack: every member device_puts from
        the SAME shared staging buffer (one host buffer per burst for
        the whole ensemble — the per-member ``np.stack`` of the legacy
        path is gone entirely), overlapping on the device exactly like
        ``predict_submit``. A stacked group collapses even that: ONE
        device_put, ONE vmapped dispatch for the whole member group."""
        if self._stacked_usable():
            try:
                handle = self.stacked.staged_submit(buf, n)
            except Exception:
                _log.exception("stacked staged dispatch failed; "
                               "serving this burst per-member")
            else:
                self.last_mode = "stacked"
                _wire.count_stacked_dispatch("stacked", 1)
                _wire.observe_dispatches_per_query(1, n)
                return self._finish_members(
                    self.stacked.member_finishers([handle]), n)
        finishers = []
        for m in self.models:
            try:
                finishers.append(m.predict_staged_submit(buf, n))
            except Exception:
                _log.exception("packed member staged dispatch failed; "
                               "dropping its vote")
        self._count_fallback(len(finishers), n)
        return self._finish_members(finishers, n)

    def replace_member(self, index: int, model: Any) -> None:
        """The promote-path restack: swap ONE member while the others
        stay device-resident. Stacked groups swap the member's slices
        inside the stacked device arrays (no recompile, no re-upload
        of the other members — ``StackedMembers.update_member``; an
        incongruent incoming model raises BEFORE any state changes);
        per-member groups just swap the model."""
        old = self.models[index]
        if self.stacked is not None:
            self.stacked.update_member(index, model)
        self.models[index] = model
        try:
            old.destroy()
        except Exception:  # freeing the outgoing member is best-effort
            _log.exception("replaced member destroy failed")

    def _finish_members(self, finishers: list, n: int):
        """The shared gather half of both dispatch paths: per-member
        fault isolation, numeric pre-averaging, ``__members__``
        envelopes for non-numeric votes."""

        def finish() -> list:
            member_preds = []
            for f in finishers:
                try:
                    member_preds.append(f())
                except Exception:
                    _log.exception("packed member predict failed; "
                                   "dropping its vote")
            if not member_preds:
                raise RuntimeError("every packed ensemble member failed")
            self.last_weight = len(member_preds)
            out = []
            for i in range(n):
                votes = [p[i] for p in member_preds]
                try:
                    arr = np.asarray(votes, dtype=np.float64)
                    if not np.isnan(arr).any():
                        out.append(np.mean(arr, axis=0).tolist())
                        continue
                except (ValueError, TypeError):
                    pass
                out.append({"__members__": votes})
            return out

        return finish

    def predict(self, queries: list) -> list:
        return self.predict_submit(queries)()

    def warmup(self) -> None:
        if self.stacked is not None:
            # The stacked program is what serves; warming the N
            # per-member runners too would pay N extra XLA compiles
            # for a path only taken on a fallback burst (which then
            # compiles lazily, logged).
            self.stacked.warmup()
            return
        for m in self.models:
            warm = getattr(m, "warmup", None)
            if warm is not None:
                warm()

    def destroy(self) -> None:
        if self.stacked is not None:
            self.stacked.destroy()
        for m in self.models:
            m.destroy()


class _HostStager:
    """Reusable host staging buffers, TWO per ``(bucket, shape,
    dtype)`` — allocated on first use, reused across bursts forever
    (bounded: buckets are the model's power-of-two ladder, dtypes the
    staged vocabulary, shapes the served models' input shapes). Rows
    past a burst's count keep stale bytes on purpose; the compiled
    predict slices their outputs away, and re-zeroing would be exactly
    the per-burst copy this buffer exists to avoid.

    Double-buffered because of the one-burst-in-flight overlap:
    ``jax.device_put`` may still be reading burst N's buffer when
    burst N+1 is staged (the transfer is async), so successive bursts
    alternate buffers. Two is exactly enough — ``_complete_batch(N)``
    (a full result sync, which fences N's input transfer) always runs
    before burst N+2 is staged."""

    def __init__(self):
        self._bufs: dict = {}

    def buffer(self, bucket: int, shape: tuple, dtype) -> Any:
        key = (bucket, tuple(shape), np.dtype(dtype).str)
        entry = self._bufs.get(key)
        if entry is None:
            entry = [np.empty((bucket, *shape), dtype),
                     np.empty((bucket, *shape), dtype), 0]
            self._bufs[key] = entry
        entry[2] ^= 1
        return entry[entry[2]]


class InferenceWorker:
    def __init__(self, service_id: str, inference_job_id: str, trial_id: str,
                 meta: MetaStore, params: ParamStore, bus: BaseBus,
                 chips: Optional[ChipGroup] = None,
                 batch_timeout: float = 0.5, max_batch: int = 512,
                 pipeline: Optional[bool] = None):
        self.service_id = service_id
        self.inference_job_id = inference_job_id
        self.trial_id = trial_id
        self.meta = meta
        self.params = params
        self.cache = Cache(bus)
        self.chips = chips
        self.batch_timeout = batch_timeout
        self.max_batch = max_batch
        # One-burst-in-flight pipelining (overlap burst N's readback
        # with burst N+1's device compute). Tri-state: True / False
        # force it; None ("auto", the default) measures the device->
        # host sync latency at startup and pipelines only when there is
        # latency worth hiding (above pipeline_sync_min); below that
        # the handoff costs a few percent for nothing.
        # RAFIKI_TPU_SERVING_PIPELINE=1/0/auto; falsy spellings as
        # NodeConfig ("0"/"false"/"no"/"off").
        if pipeline is None:
            from ..config import parse_tristate_bool

            pipeline = parse_tristate_bool(os.environ.get(
                "RAFIKI_TPU_SERVING_PIPELINE", "auto"))
        self.pipeline = pipeline
        # Auto threshold: pipeline when a round-trip sync costs more
        # than this many seconds.
        # NodeConfig.pipeline_sync_min (promoted from env-only in r15);
        # env stays the transport so spawned children inherit it.
        self.pipeline_sync_min = float(os.environ.get(
            "RAFIKI_TPU_PIPELINE_SYNC_MIN", "0.02"))
        # The bus registration is a LEASE, not a one-shot: it is
        # re-asserted at this cadence so a broker restart (whose fresh
        # in-memory state forgot every registration) re-learns this
        # worker without anyone noticing — the Predictor's next
        # registry scan finds it again within one interval.
        # NodeConfig.worker_reregister (promoted from env-only in r12);
        # env stays the transport so spawned children inherit it.
        self.reregister_interval = float(os.environ.get(
            "RAFIKI_TPU_WORKER_REREGISTER", "5.0"))
        # Per-query confidence only matters to a tiering Predictor:
        # with RAFIKI_TPU_SERVING_TIER_THRESHOLD unset/0 (the default)
        # the serving burst path pays one attribute check, not a numpy
        # margin per prediction (the r11 disabled-means-free
        # discipline). A tier-on predictor against a tier-off worker
        # degrades gracefully: no confidence ⇒ every query escalates.
        self.send_confidence = float(os.environ.get(
            "RAFIKI_TPU_SERVING_TIER_THRESHOLD", "0") or 0) > 0
        # Packed-wire capability, snapshotted at construction
        # (NodeConfig.serving_packed_wire; "on" advertises ndbatch1 in
        # the bus registration — "compat"/"off" keep this worker on the
        # per-query format, the mixed-fleet/rollback story).
        self._wire_formats = ([WIRE_NDBATCH]
                              if _wire.packed_wire_mode() == "on" else [])
        # Serving quantization request (NodeConfig.serving_quant).
        # Applied at model-load time — so the worker a promotion spawns
        # recomputes the incoming bin's scales by construction — and
        # only where the model supports it; _quant_active reflects what
        # actually happened and rides the registration.
        self._quant_req = _wire.quant_mode()
        self._quant_active = False
        # Stacked-ensemble request (NodeConfig.serving_stacked,
        # default on): a multi-member same-family bin serves as ONE
        # vmapped device dispatch per burst; _stacked_active reflects
        # whether the congruence probe actually formed a group and
        # rides the registration (the admin's surgical promote path
        # keys restacks on it).
        self._stacked_req = _wire.stacked_mode()
        self._stacked_active = False
        self._stager = _HostStager()
        # Generative serving (token-level continuous batching):
        # gate + engine geometry snapshotted at construction
        # (NodeConfig knobs; env is the transport, like every serving
        # knob above). The engine and its decode loop are built in
        # run() AFTER the model loads — and only when the model
        # exposes make_generator; classifier bins ignore all of this.
        self._gen_enabled = _lm_obs.generate_enabled()
        self._gen_cfg = {
            "page_size": int(os.environ.get(
                "RAFIKI_TPU_GENERATE_PAGE_SIZE", "16")),
            "n_pages": int(os.environ.get(
                "RAFIKI_TPU_GENERATE_POOL_PAGES", "256")),
            "decode_batch": int(os.environ.get(
                "RAFIKI_TPU_GENERATE_DECODE_BATCH", "8")),
            "max_new_cap": int(os.environ.get(
                "RAFIKI_TPU_GENERATE_MAX_NEW", "128")),
        }
        self._gen_sched: Optional[Any] = None
        self._gen_thread: Optional[threading.Thread] = None
        self._staging_mode: Optional[str] = None
        # Broker-REPORTED op failures (BusOpError) this many times in a
        # row — with zero successful iterations in between — mean
        # protocol skew, not an outage: the serve loop escalates to
        # ERRORED so supervision notices (at 1 s per recovery lap, the
        # default is ~30 s of a persistently rejecting broker).
        self.max_op_errors = int(os.environ.get(
            "RAFIKI_TPU_WORKER_MAX_OP_ERRORS", "30"))
        self.stop_flag = threading.Event()
        # node.kill (chaos plane): a hard kill must NOT run the clean
        # shutdown tail — the run() loop re-checks this after the serve
        # loop exits and dies through the injected-crash path instead.
        self.hard_killed = False
        self._thread: Optional[threading.Thread] = None
        self._model: Optional[Any] = None
        self._bin_score: Optional[float] = None  # set by _load_model
        # On-demand device profiling (__profile__ control frame): the
        # active bounded session, stopped by the serve loop at its
        # deadline — None almost always.
        self._profile: Optional[Any] = None
        # Attribution-owner close must be idempotent: the clean-exit
        # path closes it, and a meta-store failure right after would
        # re-enter through the generic crash handler — a double
        # decrement would clear the process tenant rollup out from
        # under a still-serving sibling owner.
        self._attr_closed = False
        # None when the fault plane is disabled (construction-time):
        # the dispatch path then pays one attribute check per burst.
        self._fault = faults.site_hook("worker")

    # --- Lifecycle ---

    def start(self) -> "InferenceWorker":
        self._thread = threading.Thread(
            target=self.run, name=f"infer-{self.service_id[:8]}", daemon=True)
        self._thread.start()
        return self

    def stop(self, join_timeout: float = 10.0) -> None:
        self.stop_flag.set()
        if self._thread is not None:
            self._thread.join(timeout=join_timeout)

    def kill(self, join_timeout: float = 10.0) -> None:
        """Hard kill: the serve loop exits at its next poll and dies
        through the injected-crash path — meta row left RUNNING, bus
        registration stale — the wreckage a real node death leaves. A
        thread can't be pre-empted mid-burst, so an in-flight batch
        still completes; "hard" here means the shutdown protocol
        (pending flush aside) is skipped, not that the thread stops
        instantly."""
        # rta: disable=RTA106 monotonic one-way bool (False -> True once) read by the serve loop after it exits — the documented benign flag case
        self.hard_killed = True
        self.stop_flag.set()
        if self._thread is not None:
            self._thread.join(timeout=join_timeout)

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    # --- Setup + loop ---

    def _load_member(self, tid: str):
        """Load ONE trial's model (+ serving quantization when
        requested); returns ``(model, score-or-None)``. Shared by the
        initial load and the promote-path restack, so a restacked
        member re-derives per-bin state (int8 scales in particular)
        exactly like a fresh worker would."""
        trial = self.meta.get_trial(tid)
        if trial is None:
            raise ValueError(f"unknown trial {tid}")
        score = (float(trial["score"])
                 if isinstance(trial.get("score"), (int, float))
                 else None)
        model_row = self.meta.get_model(trial["model_id"])
        model_class = load_model_class(model_row["model_class"],
                                       model_row.get("model_source"))
        model = model_class(
            **model_class.validate_knobs(trial["knobs"]))
        model.load_parameters(self.params.load(trial["params_id"]))
        if self._quant_req:
            enable = getattr(model, "enable_serving_quant", None)
            if enable is None:
                _log.warning(
                    "trial %s: %s has no serving quantization; "
                    "serving f32", tid, type(model).__name__)
            else:
                report = enable(self._quant_req)
                self._quant_active = True
                _log.info(
                    "trial %s quantized for serving: mode=%s "
                    "int8=%d f32-fallback=%d", tid, report["mode"],
                    report.get("n_int8", 0), report.get("n_f32", 0))
        return model, score

    def _load_model(self) -> Any:
        """Load the worker's trial model(s); ``trial_id`` may be a
        comma-joined list when the scheduler packed an ensemble onto one
        chip group (see ServicesManager.create_inference_services).
        Same-family multi-member bins additionally try STACKED
        formation (``RAFIKI_TPU_SERVING_STACKED``, default on): the
        member weights stack along a leading model axis and every
        burst serves as ONE vmapped dispatch; incongruent or sk-style
        members fall back to the per-member runners unchanged."""
        models = []
        scores = []
        for tid in str(self.trial_id).split(","):
            model, score = self._load_member(tid)
            if score is not None:
                scores.append(score)
            models.append(model)
        # The bin's tracked eval score (max over packed members) rides
        # the bus registration so the Predictor's tiered path can rank
        # bins without a meta-store dependency.
        self._bin_score = max(scores) if scores else None
        if len(models) == 1:
            return models[0]
        stacked = None
        if self._stacked_req:
            from ..model.jax_model import stack_members

            stacked = stack_members(models)
            if stacked is not None:
                _log.info(
                    "inference worker %s: %d same-family members "
                    "stacked — one vmapped dispatch per burst",
                    self.service_id, stacked.n_members)
        self._stacked_active = stacked is not None
        return _PackedEnsemble(models, stacked=stacked)

    def run(self) -> None:
        from ..utils.service_logs import bind_service_log

        bind_service_log(getattr(self, "log_path", None))
        if self.chips is not None:
            self.chips.bind_to_thread()
        try:
            self._model = self._load_model()
            # Warm the compile cache before taking traffic so the first
            # query isn't a 20-40s TPU compile.
            warm = getattr(self._model, "warmup", None)
            if warm is not None:
                warm()
            sync_ms = None
            if self.pipeline is None:
                latency = _sync_latency()
                sync_ms = round(latency * 1e3, 3)
                self.pipeline = latency >= self.pipeline_sync_min
                _log.info(
                    "inference worker %s: sync latency %.1f ms -> "
                    "pipelining %s", self.service_id, latency * 1e3,
                    "ON" if self.pipeline else "OFF")
            self.meta.update_service(self.service_id,
                                     status=ServiceStatus.RUNNING)
            # The trial bin rides the registration so the Predictor can
            # treat same-bin workers as REPLICAS (one is chosen per
            # request) instead of extra ensemble members. The pipeline
            # decision (and the measured sync latency that drove an
            # "auto" decision) rides along so a reader of the
            # registration can tell which serving mode actually ran.
            # "wire" is the packed-format negotiation: only workers
            # that LIST ndbatch1 ever receive packed frames, so an old
            # worker (no key) and a compat-mode one are
            # indistinguishable to the predictor — both keep the
            # per-query format. "quant" records what this worker
            # actually serves (debug evidence, not negotiation).
            # "stacked" advertises that this worker's multi-member bin
            # serves via ONE vmapped program — the admin's promote
            # path may then restack a single member in place
            # (send_restack) instead of refusing surgical replacement.
            # "metrics" advertises this process's bound metrics server
            # (subprocess/docker entrypoints export METRICS_ADDR after
            # binding — container/services.py) so the admin's SLO
            # engine can scrape worker-owned families; resident-runner
            # workers leave it unset (shared registry, nothing extra
            # to scrape).
            from ..constants import EnvVars as _EnvVars

            # "gen" advertises token-level generation capability (the
            # engine geometry a Predictor's /generate route needs to
            # pick a worker); None for classifier bins or when the
            # gate is off. "staging" records which host→device path
            # the per-step token upload actually took (pinned vs
            # pageable — debug evidence, not negotiation).
            gen_info = self._start_generate() if self._gen_enabled \
                else None
            self._reg_info = {"trial_id": self.trial_id,
                              "pipeline": bool(self.pipeline),
                              "sync_latency_ms": sync_ms,
                              "score": self._bin_score,
                              "wire": self._wire_formats,
                              "quant": (self._quant_req
                                        if self._quant_active else None),
                              "stacked": self._stacked_active,
                              "gen": gen_info,
                              "staging": self._staging_mode,
                              "metrics": os.environ.get(
                                  _EnvVars.METRICS_ADDR) or None,
                              # "node" identifies the cluster node that
                              # placed this worker (docs/cluster.md):
                              # frontends use it to route shards via
                              # the per-node brokers and to prefer
                              # same-node replicas. None on a
                              # single-node deployment.
                              "node": os.environ.get(
                                  _EnvVars.NODE_ID) or None}
            self.cache.register_worker(self.inference_job_id,
                                       self.service_id,
                                       info=self._reg_info)
            # Attribution ledger owner (no-op when the ledger is off):
            # this worker's (job, bin) series exist only while it
            # serves; close_worker on the way out drops them.
            _attr.open_owner()
        except Exception:
            _log.exception("inference worker %s failed to start",
                           self.service_id)
            self.meta.update_service(self.service_id,
                                     status=ServiceStatus.ERRORED)
            raise
        try:
            # One burst stays in flight: dispatch burst N+1's compute to
            # the device BEFORE blocking on burst N's result readback
            # (predict_submit), hiding the device->host sync latency
            # behind the next burst's compute.
            #
            # Bus failures do NOT kill the worker: the broker holds all
            # queue/registry state in memory, so a broker restart both
            # drops this worker's blocked pop (a ConnectionError/
            # RuntimeError here) AND forgets its registration. The loop
            # absorbs the error, re-registers, and resumes — in-flight
            # bursts on the dead broker are lost (their clients time
            # out and retry), but the worker itself recovers without a
            # supervise restart. The periodic re-registration covers
            # the quieter case where the restart happens BETWEEN pops
            # and no error ever surfaces on this side.
            import time as _time

            pending = None
            last_reg = _time.monotonic()
            # Transport failures (broker dead/restarting) heal when the
            # broker returns, so they retry forever. A broker-REPORTED
            # op failure (BusOpError: protocol/version skew) normally
            # clears within one recovery lap — a restarted broker that
            # forgot this worker's registration reports errors until the
            # re-register lands — but a PERSISTENT one never will, so a
            # run of them without a single successful loop iteration
            # escalates to ERRORED instead of warning forever.
            consecutive_op_errors = 0
            while not self.stop_flag.is_set():
                try:
                    if (_time.monotonic() - last_reg
                            >= self.reregister_interval):
                        self.cache.register_worker(
                            self.inference_job_id, self.service_id,
                            info=self._reg_info)
                        last_reg = _time.monotonic()
                    items = self.cache.pop_queries(
                        self.service_id, max_items=self.max_batch,
                        timeout=0.0 if pending is not None
                        else self.batch_timeout)
                    # Graceful drain (ServicesManager.
                    # drain_inference_worker): everything enqueued
                    # BEFORE the marker is in this burst or an earlier
                    # one — serve it, then exit the loop cleanly (the
                    # run() tail completes the pending burst, marks
                    # STOPPED, and unregisters).
                    draining = any(_CACHE_DRAIN_KEY in it
                                   for it in items)
                    if draining:
                        items = [it for it in items
                                 if _CACHE_DRAIN_KEY not in it]
                    # Promote-path restack markers (queue-ordered like
                    # drain): everything enqueued before the marker
                    # serves from the OLD member set — this burst
                    # included — and the swap applies right after.
                    restacks = [it[_CACHE_RESTACK_KEY] for it in items
                                if _CACHE_RESTACK_KEY in it]
                    if restacks:
                        items = [it for it in items
                                 if _CACHE_RESTACK_KEY not in it]
                    # On-demand profiling markers: start a bounded
                    # jax.profiler session between bursts; the expiry
                    # check below stops it — serving never pauses.
                    profiles = [it[_CACHE_PROFILE_KEY] for it in items
                                if _CACHE_PROFILE_KEY in it]
                    if profiles:
                        items = [it for it in items
                                 if _CACHE_PROFILE_KEY not in it]
                        for p in profiles:
                            self._start_profile(p)
                    # Token-generation requests route to the decode
                    # scheduler's admission queue and return
                    # immediately — the decode loop owns them from
                    # here; classifier bursts below are untouched.
                    gens = [it for it in items
                            if it.get("op") == "generate"]
                    if gens:
                        items = [it for it in items
                                 if it.get("op") != "generate"]
                        for g in gens:
                            self._route_generate(g)
                    handle = (self._dispatch_batch(items) if items
                              else None)
                    for r in restacks:
                        self._restack_member(r)
                        last_reg = _time.monotonic()
                    if not self.pipeline and handle is not None:
                        self._complete_batch(*handle)
                        handle = None
                    if pending is not None:
                        self._complete_batch(*pending)
                    pending = handle
                    consecutive_op_errors = 0
                    # Remote tail-verdict holds resolve on span WRITES;
                    # an idle worker writes none, so sweep here — a
                    # quiet worker's held spans honor the edge's
                    # retain/drop verdict within one poll interval
                    # (no-op one lock check when nothing is pending).
                    trace.flush_remote_expired()
                    if self._profile is not None and \
                            self._profile.expired(_time.monotonic()):
                        self._stop_profile()
                    if draining:
                        _log.info("inference worker %s draining: "
                                  "served the queue, exiting",
                                  self.service_id)
                        break
                except (ConnectionError, OSError, RuntimeError) as e:
                    if isinstance(e, BusOpError):
                        consecutive_op_errors += 1
                        if consecutive_op_errors > self.max_op_errors:
                            raise
                    else:
                        consecutive_op_errors = 0
                    _log.warning(
                        "inference worker %s lost the bus; "
                        "re-registering and resuming", self.service_id,
                        exc_info=True)
                    if pending is not None:  # drain device work; the
                        try:                 # reply push may also fail
                            self._complete_batch(*pending)
                        except (ConnectionError, OSError, RuntimeError):
                            pass             # burst lost; client retries
                        pending = None
                    self.stop_flag.wait(1.0)
                    try:
                        self.cache.register_worker(
                            self.inference_job_id, self.service_id,
                            info=self._reg_info)
                        last_reg = _time.monotonic()
                    except (ConnectionError, OSError, RuntimeError):
                        pass  # broker still down; retry next iteration
            if self.hard_killed:
                raise faults.InjectedCrash(
                    "injected: node.kill — hard node death")
            if pending is not None:
                self._complete_batch(*pending)
            self._stop_profile()
            self._stop_generate()
            self._close_attr_owner()
            self.meta.update_service(self.service_id,
                                     status=ServiceStatus.STOPPED)
        except faults.InjectedCrash:
            # Injected kill -9: die HARD — no ERRORED meta update, no
            # bus unregistration. The meta row stays RUNNING and the
            # registration stays stale, exactly the wreckage a real
            # hard kill leaves, so the supervise sweep (dead thread ->
            # ERRORED -> respawn) and the Predictor's quarantine are
            # what recovery actually exercises. PROCESS-LOCAL
            # resources are different: a real kill takes the profiler
            # lock and the ledger owner slot with the process, but a
            # thread-level crash in a resident runner would leak them
            # for the process's life (every later trial trace blocked,
            # the tenant rollup never cleared) — release those.
            self._stop_profile()
            self._stop_generate()
            self._close_attr_owner()
            _log.error("inference worker %s: injected crash; dying "
                       "hard (row left RUNNING, registration stale)",
                       self.service_id)
            raise
        except Exception:
            _log.exception("inference worker %s crashed", self.service_id)
            self._stop_profile()
            self._stop_generate()
            self._close_attr_owner()
            self.meta.update_service(self.service_id,
                                     status=ServiceStatus.ERRORED)
            self._unregister_best_effort()
            raise
        else:
            self._unregister_best_effort()

    # --- Generative serving (token-level decode loop) ---

    def _start_generate(self) -> Optional[dict]:
        """Build the paged-KV engine and its continuous-batching loop
        for a generate-enabled bin; returns the registration payload
        (engine geometry) or None when this bin can't serve tokens —
        never fatal: a classifier bin with the gate on just serves
        classification, and an engine-construction failure degrades the
        same way (logged, advertised as non-generative)."""
        make = getattr(self._model, "make_generator", None)
        if make is None:
            _log.info("inference worker %s: generate gate on but %s "
                      "has no make_generator; serving without it",
                      self.service_id, type(self._model).__name__)
            return None
        try:
            from ..parallel.mesh import replicated
            from ..parallel.transfer import make_host_stager
            from .decode_scheduler import DecodeScheduler

            stager, self._staging_mode = make_host_stager(
                replicated(self._model.mesh))
            engine = make(stager=stager, **self._gen_cfg)
            self._gen_sched = DecodeScheduler(engine, self.cache,
                                              self.service_id)
        except Exception:
            _log.exception("inference worker %s: generate engine "
                           "construction failed; serving without it",
                           self.service_id)
            self._gen_sched = None
            self._staging_mode = None
            return None
        self._gen_thread = threading.Thread(
            target=self._gen_sched.loop,
            name=f"decode-{self.service_id[:8]}", daemon=True)
        self._gen_thread.start()
        _log.info("inference worker %s: generative serving up "
                  "(decode_batch=%d, pool=%d pages x %d tokens, "
                  "staging=%s)", self.service_id,
                  self._gen_cfg["decode_batch"],
                  self._gen_cfg["n_pages"], self._gen_cfg["page_size"],
                  self._staging_mode)
        return dict(self._gen_cfg)

    def _route_generate(self, item: dict) -> None:
        """Hand one popped generate frame to the decode scheduler; a
        bin not serving tokens answers with a terminal error frame so
        the client fails fast instead of timing out."""
        if self._gen_sched is not None:
            self._gen_sched.submit(item)
            return
        qid = item.get("query_id")
        if qid:
            try:
                self.cache.send_token_frame(
                    qid, self.service_id,
                    {"seq": 0, "tok": [], "done": True,
                     "finish": "error", "n_tokens": 0,
                     "error": "generative serving not available on "
                              "this worker"})
            except (ConnectionError, OSError, RuntimeError):
                pass

    def _stop_generate(self) -> None:
        """Idempotent decode-loop teardown (every run() exit path):
        stop the loop, join its thread, release the engine's pages."""
        sched, self._gen_sched = self._gen_sched, None
        thread, self._gen_thread = self._gen_thread, None
        if sched is None:
            return
        try:
            sched.close(join=thread)
        except Exception:
            _log.exception("inference worker %s: decode loop "
                           "teardown failed", self.service_id)

    def _restack_member(self, req: Any) -> None:
        """Apply one promote-path restack request (``{"old": tid,
        "new": tid}``): load the incoming trial's model, swap it into
        the served ensemble IN PLACE (stacked groups swap device
        slices — the other members stay resident and no runner
        recompiles), then re-register with the updated bin so the
        admin's poll observes the swap. Every failure leaves the old
        member serving and the old registration standing — the admin's
        registration-poll timeout is the rollback signal."""
        old_tid = (req or {}).get("old")
        new_tid = (req or {}).get("new")
        tids = str(self.trial_id).split(",")
        if not new_tid or old_tid not in tids:
            _log.warning(
                "inference worker %s: stale restack request %r "
                "(serving %s); ignoring", self.service_id, req,
                self.trial_id)
            return
        if not isinstance(self._model, _PackedEnsemble):
            _log.warning(
                "inference worker %s: restack requested but the bin "
                "is not a packed ensemble; ignoring", self.service_id)
            return
        try:
            model, _score = self._load_member(new_tid)
            self._model.replace_member(tids.index(old_tid), model)
        except Exception:
            _log.exception(
                "inference worker %s: restack %s -> %s failed; the "
                "old member set keeps serving", self.service_id,
                old_tid, new_tid)
            return
        old_bin = self.trial_id
        tids[tids.index(old_tid)] = new_tid
        self.trial_id = ",".join(tids)
        # The old bin label's ledger series must not outlive the swap
        # (each promotion would otherwise leak one (job, bin) label
        # set per family, forever, in a resident runner).
        _attr.drop_worker_bin(self.inference_job_id, old_bin)
        scores = [s for s in (self._trial_score(t) for t in tids)
                  if s is not None]
        self._bin_score = max(scores) if scores else None
        self._reg_info["trial_id"] = self.trial_id
        self._reg_info["score"] = self._bin_score
        # The meta mapping row follows the served bin (the admin's
        # active_inference_workers / promote validation read it), then
        # the re-registration makes the swap observable on the bus.
        try:
            self.meta.update_inference_job_worker(self.service_id,
                                                  self.trial_id)
        except Exception:
            _log.exception("restack meta update failed; registration "
                           "still reflects the swap")
        self.cache.register_worker(self.inference_job_id,
                                   self.service_id, info=self._reg_info)
        _log.info("inference worker %s restacked %s -> %s (bin now "
                  "%s)", self.service_id, old_tid, new_tid,
                  self.trial_id)

    def _start_profile(self, req: Any) -> None:
        """Apply one ``__profile__`` control frame: begin a bounded
        on-demand ``jax.profiler`` session (skipped — never fatal —
        when the profiler is busy, the request is malformed, or one is
        already running on this worker)."""
        out_dir = (req or {}).get("dir") if isinstance(req, dict) \
            else None
        if not out_dir:
            _log.warning("inference worker %s: malformed profile "
                         "request %r; ignoring", self.service_id, req)
            return
        if self._profile is not None:
            _log.info("inference worker %s: profile session already "
                      "active; request for %s skipped",
                      self.service_id, out_dir)
            return
        try:
            duration = float((req or {}).get("duration_s", 5.0) or 5.0)
        except (TypeError, ValueError):
            duration = 5.0
        try:
            from ..observe import profiling

            self._profile = profiling.start_device_profile(out_dir,
                                                           duration)
        except Exception:
            _log.exception("inference worker %s: profile session "
                           "start failed", self.service_id)

    def _close_attr_owner(self) -> None:
        if not self._attr_closed:
            self._attr_closed = True
            _attr.close_worker(self.inference_job_id, self.trial_id)

    def _stop_profile(self) -> None:
        if self._profile is not None:
            try:
                self._profile.stop()
            except Exception:
                _log.exception("profile session stop failed")
            self._profile = None

    def _trial_score(self, tid: str) -> Optional[float]:
        trial = self.meta.get_trial(tid)
        score = (trial or {}).get("score")
        return float(score) if isinstance(score, (int, float)) else None

    def _unregister_best_effort(self) -> None:
        """Drop this worker's bus registration on the way out (crash or
        clean stop — NOT an injected crash, which must leave it stale).
        A dead/restarted broker forgot it anyway."""
        try:
            self.cache.unregister_worker(self.inference_job_id,
                                         self.service_id)
        except (ConnectionError, OSError, RuntimeError):
            pass  # broker gone; nothing to unregister from

    def _dispatch_batch(self, items: list):
        """Flatten a burst into ONE chip-side predict dispatch; returns
        (finisher, spans, n, trace_ctxs, t0) for ``_complete_batch``. A
        burst may mix packed batch frames, per-query batch frames, and
        single-query frames; their trace envelopes (absent on old
        frames) are popped here so the span covering this burst's
        device time lands in the span log under every trace id the
        burst carried.

        An all-packed burst of one shape/dtype takes the STAGED fast
        path: frames are copied (one memcpy each) into the reusable
        host staging buffer and dispatched via the model's
        ``predict_staged_submit`` — no per-query objects, no
        ``np.stack``, no pad-``concatenate``. Anything else (mixed
        formats, differing shapes, models without a staged entry) falls
        back to the flat per-query path, with packed frames unrolled
        into row views."""
        import time as _time

        if self._fault is not None:
            # worker.slow sleeps inside the hook (a straggling
            # replica); worker.crash raises InjectedCrash through the
            # serve loop — crash-on-nth-predict counts these dispatch
            # calls, so n= targets an exact burst.
            self._fault(op="predict")
        trace_ctxs = trace.extract_frames(items)
        # Tenant envelope (attribution ledger): popped whether the
        # ledger is on or not — the key must not leak into decode
        # paths — and merged across the burst's frames.
        tenants = _attr.extract_frames_tenants(items)
        # Corrupt packed frames (pop_queries left batch=None +
        # batch_error) are answered IMMEDIATELY with per-query error
        # dicts — a bad producer poisons its own frame, never the
        # burst's co-batched queries, and never the worker.
        good = []
        for it in items:
            if "batch" in it and it["batch"] is None:
                err = {"error": f"ValueError: "
                                f"{it.get('batch_error', 'corrupt packed frame')}"}
                self.cache.send_prediction_batch(
                    it["batch_id"], self.service_id,
                    [err] * max(1, int(it.get("n", 1) or 1)),
                    shard=it.get("shard"),
                    origin_node=it.get("onode"))
            else:
                good.append(it)
        finisher = None
        spans: list = []  # (item, start, count, is_batch)
        n = 0
        attr_bucket = attr_dtype = None
        arrays = [it["batch"] for it in good
                  if isinstance(it.get("batch"), np.ndarray)]
        if arrays and len(arrays) == len(good):
            first = arrays[0]
            total = sum(a.shape[0] for a in arrays)
            bucket = None
            if all(a.shape[1:] == first.shape[1:]
                   and a.dtype == first.dtype for a in arrays[1:]):
                bucket_fn = getattr(self._model, "predict_bucket", None)
                if bucket_fn is not None:
                    bucket = bucket_fn(total, first.dtype)
            if bucket is not None:
                attr_bucket, attr_dtype = bucket, str(first.dtype)
                buf = self._stager.buffer(bucket, first.shape[1:],
                                          first.dtype)
                start = 0
                for it, a in zip(good, arrays):
                    spans.append((it, start, a.shape[0], True))
                    buf[start:start + a.shape[0]] = a
                    start += a.shape[0]
                # The staging fill is ONE bulk memcpy per frame —
                # counted per row ("assemble") so the packed side's
                # copy evidence stays symmetric with the legacy
                # per-query stack count.
                _wire.count_copies("assemble", total)
                n = total
                try:
                    finisher = self._model.predict_staged_submit(buf,
                                                                 total)
                except Exception as e:
                    _log.exception("staged predict dispatch failed on "
                                   "batch of %d", total)
                    err = {"error": f"{type(e).__name__}: {e}"}
                    finisher = lambda k=total: [err] * k  # noqa: E731
        if finisher is None:
            flat: list = []
            spans = []
            for it in good:
                if isinstance(it.get("batch"), np.ndarray):
                    a = it["batch"]
                    spans.append((it, len(flat), a.shape[0], True))
                    flat.extend(a[i] for i in range(a.shape[0]))
                elif "queries" in it:
                    spans.append((it, len(flat), len(it["queries"]),
                                  True))
                    flat.extend(it["queries"])
                else:
                    spans.append((it, len(flat), 1, False))
                    flat.append(it["query"])
            n = len(flat)
            if not flat:
                finisher = lambda: []  # noqa: E731 - all-corrupt burst
            else:
                try:
                    finisher = self._model.predict_submit(flat)
                except Exception as e:
                    _log.exception("predict dispatch failed on batch "
                                   "of %d", n)
                    err = {"error": f"{type(e).__name__}: {e}"}
                    finisher = lambda k=n: [err] * k  # noqa: E731
        # The dispatch MODE and the serving BIN are captured here, not
        # at completion: with pipelining on, burst N+1 is dispatched
        # (and may flip last_mode) before burst N's _complete_batch
        # runs, and a same-poll restack rewrites trial_id between this
        # burst's dispatch (old members served it) and its completion.
        return (finisher, spans, n, trace_ctxs,
                (_time.time(), _time.monotonic()),
                {"tenants": tenants, "bucket": attr_bucket,
                 "dtype": attr_dtype, "bin": self.trial_id,
                 "mode": getattr(self._model, "last_mode", "single")})

    def _complete_batch(self, finisher, spans: list, n: int,
                        trace_ctxs: list = (), t0=None,
                        attr: Optional[dict] = None) -> None:
        import time as _time

        try:
            predictions = finisher()
        except Exception as e:
            _log.exception("predict failed on batch of %d", n)
            predictions = [{"error": f"{type(e).__name__}: {e}"}] * n
        wall, mono = t0 if t0 else (_time.time(), _time.monotonic())
        burst_s = _time.monotonic() - mono
        if trace_ctxs:
            # The span covers dispatch -> readback complete (with
            # pipelining on, that includes the deliberate overlap wait).
            trace.record_event(
                "worker.predict", self.service_id, trace_ctxs, wall,
                burst_s,
                attrs={"n_queries": n, "trial_id": str(self.trial_id)})
        weight = int(getattr(self._model, "last_weight", 1))
        if self._quant_active:
            _wire.count_quant(n, self._quant_req)
        if n:
            # Attribution ledger (no-op when off): this burst's device
            # time lands on the worker's (job, bin) with the dispatch-
            # variant breakdown, and is prorated over the tenant mix
            # the burst's frames carried.
            attr = attr or {}
            _attr.account_burst(
                self.inference_job_id, attr.get("bin", self.trial_id),
                n, burst_s,
                bucket=attr.get("bucket"), dtype=attr.get("dtype"),
                quant=self._quant_req if self._quant_active else "",
                mode=attr.get("mode", "single"))
            tenants = attr.get("tenants")
            if tenants:
                _attr.account_tenant_device(tenants, burst_s, n)
        # Per-query confidence (softmax margin; None for sk-style
        # outputs) rides batch replies for the Predictor's tiered
        # escalation — computed ONLY when tiering is on (see
        # send_confidence); compute_s is this burst's device time
        # prorated over the slice, feeding the chip-seconds-avoided
        # estimate.
        confidence = ([prediction_confidence(p) for p in predictions]
                      if self.send_confidence else None)
        for it, start, count, is_batch in spans:
            if is_batch:
                # Echo the shard id of a sharded super-batch slice so
                # the Predictor's gather can match this reply to its
                # shard plan entry (a resubmitted shard may land on a
                # worker that already served its own slice of the same
                # batch, making worker_id alone ambiguous). Un-sharded
                # frames have no "shard" key and reply without one.
                # packed_ok: the query frame's "rw" list is the reply-
                # direction negotiation — only senders that can decode
                # packed replies ever advertise it.
                self.cache.send_prediction_batch(
                    it["batch_id"], self.service_id,
                    predictions[start:start + count], weight=weight,
                    shard=it.get("shard"),
                    confidence=(confidence[start:start + count]
                                if confidence is not None else None),
                    compute_s=round(burst_s * count / max(n, 1), 6),
                    packed_ok=WIRE_NDBATCH in (it.get("rw") or ()),
                    # A cross-node shard carries its origin node: the
                    # reply relays back to THAT node's broker.
                    origin_node=it.get("onode"))
            else:
                self.cache.send_prediction(it["query_id"], self.service_id,
                                           predictions[start],
                                           weight=weight)
