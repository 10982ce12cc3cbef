"""Predictor core: scatter queries to workers, gather, ensemble.

Parity: SURVEY.md §3.3 — upstream's Predictor broadcasts each query to
every live InferenceWorker via Redis queues, polls for the per-worker
predictions with a timeout, and combines them (mean class probabilities →
label for image classification). Same shape here over the bus/cache; the
HTTP frontend lives in ``rafiki_tpu.predictor.app``.
"""

from __future__ import annotations

import logging
import os
import threading
import uuid
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ..bus import BaseBus
from ..cache import WIRE_NDBATCH, Cache, PackedBatch
from ..observe import attribution as _attr
from ..observe import metrics as _metrics
from ..observe import wire as _wire_obs

_log = logging.getLogger(__name__)

#: EWMA smoothing for per-replica gather latency: ~5 replies dominate.
_LAT_ALPHA = 0.3

#: Fraction of the gather timeout spent waiting for primary shards
#: before missing ones are resubmitted to sibling replicas (only when a
#: missing shard actually HAS a sibling; otherwise the full timeout is
#: spent waiting — there is nobody else to ask). This is the FALLBACK
#: (and ceiling) straggler deadline: when every planned replica has a
#: latency EWMA, the partial deadline is latency-RELATIVE instead
#: (``_STRAGGLER_K`` x the slowest planned replica's EWMA), so a fast
#: fleet resubmits a missing shard in milliseconds rather than waiting
#: out half of a 30s timeout.
_RESUBMIT_AT = 0.5

#: Multiplier over the slowest planned replica's gather-latency EWMA
#: for the latency-relative partial deadline: a healthy reply lands
#: within ~1x its EWMA, so 4x is a straggler with margin for jitter.
_STRAGGLER_K = 4.0

#: Floor (seconds) of the latency-relative deadline: sub-millisecond
#: EWMAs (in-process bus) would otherwise flap resubmits on scheduler
#: noise.
_STRAGGLER_MIN = 0.025

#: Ceiling on the quarantine backoff multiplier: a replica that missed
#: deadlines on N consecutive probes is quarantined for
#: ``gather_timeout * min(2**(N-1), _QUARANTINE_MAX_MULT)`` before the
#: next probe. Bounded so a long-dead replica whose registration was
#: never reaped still gets re-probed eventually (a respawn could reuse
#: its id), but a persistently dead one costs one partial deadline per
#: ~16 gather timeouts instead of one per timeout.
_QUARANTINE_MAX_MULT = 16


class _Shard:
    """One slice of a super-batch bound for one replica worker."""

    __slots__ = ("worker", "bin", "start", "count", "shard_id",
                 "reply", "resubmitted", "t_sent", "pair", "superseded")

    def __init__(self, worker: str, bin_id: str, start: int, count: int):
        self.worker = worker
        self.bin = bin_id
        self.start = start
        self.count = count
        self.shard_id = uuid.uuid4().hex[:12]
        self.reply: Optional[Dict[str, Any]] = None
        self.resubmitted = False
        self.t_sent = 0.0  # monotonic scatter time (latency EWMA)
        # A resubmitted shard and its original cover the SAME slice;
        # whichever replies first supersedes the other so the gather
        # stops waiting as soon as the slice is covered.
        self.pair: Optional["_Shard"] = None
        self.superseded = False

    def wire(self) -> Tuple[str, int, int, str]:
        return (self.worker, self.start, self.count, self.shard_id)


def ensemble_predictions(worker_predictions: List[Any],
                         weights: Optional[List[int]] = None) -> Any:
    """Combine one query's per-worker predictions.

    Numeric vectors (class probabilities) → elementwise mean, the
    reference's image-classification combiner; ``weights`` (ensemble
    members already averaged inside each reply — packed workers) make it
    an unweighted mean over trials. Non-numeric predictions → majority
    vote (one vote per worker), falling back to the first (upstream
    serves the first worker's output for tasks without a combiner).
    """
    pairs = []
    for i, p in enumerate(worker_predictions):
        if isinstance(p, dict) and "error" in p:
            continue
        if isinstance(p, dict) and "__members__" in p:
            # Packed workers ship non-numeric member predictions
            # un-combined so each trial gets its own vote here.
            pairs.extend((m, 1) for m in p["__members__"])
            continue
        pairs.append((p, weights[i] if weights else 1))
    if not pairs:
        return None
    preds = [p for p, _ in pairs]
    try:
        arr = np.asarray(preds, dtype=np.float64)
        if not np.isnan(arr).any():
            w = np.asarray([w for _, w in pairs], dtype=np.float64)
            return np.average(arr, axis=0, weights=w).tolist()
    except (ValueError, TypeError):
        pass
    # Non-numeric: majority vote by value (repr as the equality key),
    # each entry voting its weight; ties broken by arrival order.
    from collections import Counter

    counts: Counter = Counter()
    for p, w in pairs:
        counts[repr(p)] += int(w)
    winner = counts.most_common(1)[0][0]
    return next(p for p, _ in pairs if repr(p) == winner)


#: Reassembly hole marker: a query position whose bin shard never
#: replied (shared by the full and tiered reassembly paths).
_HOLE = object()


class _WirePayload:
    """One super-batch's outbound wire representation.

    BOTH forms are lazy: the packed contiguous buffer materializes only
    when a plan actually targets a packed-capable worker (a tiered
    phase-1 against a legacy best bin must not pay the assembly for an
    escalation that usually never happens), and per-query frames only
    for legacy shards — a plan whose every shard lands on
    packed-capable workers never builds them, which is where the "one
    encode per shard instead of one per query" win comes from on the
    direct (numpy-in) path. The same payload object follows the batch
    through resubmits and the tiered escalation, so the formats can
    never diverge mid-flight."""

    __slots__ = ("capable", "_queries", "_pre_encoded", "_encoded",
                 "_packed", "_packed_done")

    def __init__(self, queries: List[Any], pre_encoded: bool,
                 capable: frozenset):
        self.capable = capable
        self._queries = queries
        self._pre_encoded = pre_encoded
        self._encoded: Optional[List[Any]] = None
        self._packed: Optional[PackedBatch] = None
        self._packed_done = False

    @property
    def packed(self) -> Optional[PackedBatch]:
        """The contiguous batch buffer, assembled on first demand
        (None when the queries are not packable — mixed shapes,
        non-tensors — or nobody in the fleet advertises the format)."""
        if not self._packed_done:
            self._packed_done = True
            if self.capable:
                self._packed = (
                    PackedBatch.from_encoded(self._queries)
                    if self._pre_encoded
                    else PackedBatch.from_arrays(self._queries))
        return self._packed

    @property
    def encoded(self) -> List[Any]:
        """Per-query wire frames, built on first use (legacy shards /
        mixed fleets only)."""
        if self._encoded is None:
            if self._pre_encoded:
                self._encoded = self._queries
            else:
                from ..cache import encode_payload

                _wire_obs.count_copies("encode", sum(
                    1 for q in self._queries
                    if isinstance(q, np.ndarray)))
                self._encoded = [encode_payload(q)
                                 for q in self._queries]  # once total
        return self._encoded

    def for_plan(self, plan: List["_Shard"],
                 ) -> Tuple[Optional[List[Any]],
                            Optional[PackedBatch]]:
        """``(encoded_queries, packed)`` for ONE plan, materializing
        only the representation(s) its shards actually need."""
        any_packed = any(s.worker in self.capable for s in plan)
        packed = self.packed if any_packed else None
        enc = (self.encoded if packed is None or
               any(s.worker not in self.capable for s in plan)
               else None)
        return enc, packed

    def take(self, indices: List[int]) -> "_WirePayload":
        """Row subset (the tiered escalation set), preserving whichever
        representations already materialized."""
        sub = _WirePayload([self._queries[i] for i in indices],
                           self._pre_encoded, self.capable)
        if self._packed_done and self._packed is not None:
            sub._packed = self._packed.take(indices)
            sub._packed_done = True
        if self._encoded is not None:
            sub._encoded = [self._encoded[i] for i in indices]
        return sub

#: EWMA smoothing for the per-bin compute-cost estimate (seconds per
#: query, from worker-reported burst compute time) that prices the
#: chip-seconds-avoided counters.
_COST_ALPHA = 0.3


class Predictor:
    def __init__(self, inference_job_id: str, bus: BaseBus,
                 gather_timeout: float = 30.0,
                 worker_wait_timeout: float = 120.0,
                 shard_replicas: Optional[bool] = None,
                 service: Optional[str] = None,
                 tier_threshold: Optional[float] = None):
        self.inference_job_id = inference_job_id
        self.cache = Cache(bus)
        self.gather_timeout = gather_timeout
        self.worker_wait_timeout = worker_wait_timeout
        # Confidence-tiered serving: scatter to the best bin (by
        # tracked eval score) first, escalate to the full ensemble
        # vote only for queries whose confidence falls below the
        # threshold. None/0 = off — predict_submit pays one attribute
        # check and no tier series is ever registered.
        if tier_threshold is None:
            tier_threshold = float(os.environ.get(
                "RAFIKI_TPU_SERVING_TIER_THRESHOLD", "0") or 0)
        self.tier_threshold = tier_threshold if tier_threshold > 0 \
            else None
        # Data-parallel replica sharding: each trial bin's slice of a
        # super-batch is spread across ALL live same-bin replicas
        # (latency-weighted) instead of all landing on one rotating
        # pick. Same ensemble semantics — each bin still contributes
        # exactly one vote per query — but replicas become serving
        # capacity instead of failover spares.
        if shard_replicas is None:
            from ..config import _parse_bool

            shard_replicas = _parse_bool(os.environ.get(
                "RAFIKI_TPU_SERVING_SHARD_REPLICAS", "1"))
        self.shard_replicas = shard_replicas
        # Cluster fabric (docs/cluster.md), construction-time snapshot
        # like every other knob here: this frontend's node identity
        # (injected by the placing ServicesManager) and the same-node
        # shard-weight boost. Fabric off = empty node, boost 1.0 —
        # every cluster branch below is a falsy check, byte-identical
        # single-node behavior.
        from ..config import NodeConfig, _parse_bool
        from ..constants import EnvVars as _EnvVars

        cluster_on = _parse_bool(os.environ.get(
            NodeConfig.env_name("cluster_fabric"), "0"))
        self._node = (os.environ.get(_EnvVars.NODE_ID) or "") \
            if cluster_on else ""
        self._locality_boost = float(os.environ.get(
            NodeConfig.env_name("cluster_locality_boost"), "1.0")
            or 1.0) if cluster_on else 1.0
        # worker_id -> node id from its registration ("" = unknown /
        # pre-cluster worker). Memoized with _bins.
        self._nodes: Dict[str, str] = {}
        self._rr = 0  # replica round-robin cursor
        # worker_id -> trial bin, memoized: registration info is
        # immutable per worker id, and per-request bus.get fan-out
        # would put O(workers) round-trips on the serving hot path.
        self._bins: Dict[str, str] = {}
        # worker_id -> advertises the packed batch wire (ndbatch1 in
        # its registration's "wire" list). Memoized with _bins; old
        # workers simply lack the key and stay on per-query frames.
        self._wire_ok: Dict[str, bool] = {}
        # Packed emission is a construction-time snapshot
        # (NodeConfig.serving_packed_wire): "on" packs toward
        # advertising workers; "compat"/"off" keep per-query frames
        # (compat keeps the wire accounting).
        self._packed_wire = _wire_obs.packed_wire_mode() == "on"
        # bin -> tracked eval score (from worker registration info; the
        # tiered path's "best bin"). Keyed by bin, bounded by the
        # number of served trials — no per-worker churn to prune.
        self._bin_score: Dict[str, float] = {}
        # bin -> EWMA of worker-reported compute seconds PER QUERY —
        # prices the chip-seconds-avoided counters (cache hits and
        # tier short-circuits). Bins with no estimate yet price as 0:
        # the counter under-reports rather than fabricates.
        self._bin_cost: Dict[str, float] = {}
        # The bin set of the most recent shard plan (sorted tuple) —
        # the serving "model-version vector" the edge cache
        # cross-checks for promotion-driven invalidation.
        self._last_bins: Optional[tuple] = None
        # worker_id -> EWMA of scatter->reply latency (seconds). Drives
        # the latency-weighted shard split; a timed-out shard penalizes
        # its replica so the next plan leans on its siblings.
        self._lat: Dict[str, float] = {}
        # worker_id -> monotonic time of its last penalty. A penalized
        # replica gets a zero slice (its EWMA only refreshes on
        # replies, which it no longer gets), so the penalty is dropped
        # after its quarantine interval — a recovered replica rejoins
        # the plan on the next probe.
        self._penalized: Dict[str, float] = {}
        # worker_id -> consecutive missed-deadline count. Drives the
        # exponential quarantine (see _quarantine_s): each failed probe
        # DOUBLES the next quarantine (capped), so a still-dead replica
        # stops costing one partial deadline per gather timeout.
        # Strikes outlive penalty expiry on purpose (expiry IS the
        # probe) and reset only on a real reply.
        self._strikes: Dict[str, int] = {}
        # ThreadingHTTPServer handler threads (batcher-off mode) and
        # the micro-batcher's scatter thread all route through
        # _choose_workers/_plan_for; the rr cursor, bin memo, and
        # latency map are guarded so concurrent requests can't lose
        # rotations or corrupt them.
        self._state_lock = threading.Lock()
        # Per-instance metrics label (two predictors for one job in one
        # process — test restarts — must not merge series); callers
        # that own a ServingStats pass its label so /metrics readers
        # can join the serving and shard families.
        self.service = service or f"pred-{uuid.uuid4().hex[:8]}"
        self._m_shards = self._m_resubmits = self._m_replica = None
        self._m_quarantines = self._m_tier = self._m_avoided = None
        if self.tier_threshold is not None and \
                _metrics.metrics_enabled():
            # Registered only when tiering is ON (the r11 discipline:
            # disabled => attribute check only, zero new series).
            reg = _metrics.registry()
            self._m_tier = reg.counter(
                "rafiki_tpu_serving_tier_total",
                "Per-query tiered-serving outcomes (outcome="
                "short_circuit|escalate|full)")
            self._m_avoided = reg.counter(
                "rafiki_tpu_serving_chip_seconds_avoided_total",
                "Estimated chip-seconds NOT spent thanks to a serving "
                "cut-through (source=cache|tier), from the per-bin "
                "compute-cost EWMA")
        if _metrics.metrics_enabled():
            reg = _metrics.registry()
            self._m_shards = reg.counter(
                "rafiki_tpu_serving_shards_total",
                "Shards scattered to replica workers")
            self._m_resubmits = reg.counter(
                "rafiki_tpu_serving_shard_resubmits_total",
                "Shards resubmitted to a sibling replica after their "
                "primary replica missed the partial-gather deadline")
            self._m_replica = reg.histogram(
                "rafiki_tpu_serving_replica_gather_seconds",
                "Per-replica scatter->reply latency (worker= short "
                "replica id)")
            self._m_quarantines = reg.counter(
                "rafiki_tpu_serving_replica_quarantines_total",
                "Replicas penalized out of the shard plan after a "
                "missed deadline (quarantine backs off exponentially "
                "per consecutive strike)")
        # Attribution ledger owner (no-op when the ledger is off):
        # this frontend's per-bin series live exactly as long as it
        # does — close() drops them, once (a double stop must not
        # double-decrement the owner refcount).
        self._attr_closed = False
        _attr.open_owner()

    def close(self) -> None:
        """Drop this predictor's metric series (per-instance ``service``
        label; a resident runner deploying/stopping frontends would
        otherwise grow the registry forever) — the attribution ledger's
        per-bin frontend series included."""
        for m in (self._m_shards, self._m_resubmits, self._m_replica,
                  self._m_quarantines, self._m_tier, self._m_avoided):
            if m is not None:
                m.remove(service=self.service)
        if not self._attr_closed:
            self._attr_closed = True
            _attr.close_service(self.service)

    def workers(self) -> List[str]:
        return self.cache.running_workers(self.inference_job_id)

    def _wait_workers(self) -> List[str]:
        """Workers register only after their (slow) first XLA compile;
        queries arriving during deploy wait instead of erroring."""
        import time
        deadline = time.monotonic() + self.worker_wait_timeout
        while True:
            workers = self.workers()
            if workers:
                return workers
            if time.monotonic() >= deadline:
                return []
            time.sleep(0.2)

    def _bin_of(self, worker_id: str) -> str:
        """Caller holds ``_state_lock``. The memoized bus.get is a
        round-trip, but only the FIRST request after a worker appears
        pays it; steady-state requests never leave the memo. The
        registration's tracked eval score (absent on pre-r12 workers)
        is captured per bin for the tiered path's best-bin pick."""
        bin_id = self._bins.get(worker_id)
        if bin_id is None:
            info = self.cache.bus.get(
                f"w:{self.inference_job_id}:{worker_id}") or {}
            bin_id = str(info.get("trial_id") or worker_id)
            self._bins[worker_id] = bin_id
            self._wire_ok[worker_id] = WIRE_NDBATCH in (
                info.get("wire") or ())
            self._nodes[worker_id] = str(info.get("node") or "")
            score = info.get("score")
            if isinstance(score, (int, float)):
                self._bin_score[bin_id] = float(score)
        return bin_id

    def _group_replicas(self) -> Tuple[Dict[str, List[str]], int,
                                       Dict[str, float]]:
        """The shared front half of every scatter plan: wait for
        workers, prune memo/latency rows of departed ones (a long-lived
        predictor under churn would otherwise leak a row per worker
        restart, forever), expire stale penalties, group live workers
        by trial bin, and advance the rotation cursor. Returns
        ``(groups, rr, lat_snapshot)``. The hot path costs one registry
        keys() scan; per-worker info reads are memoized."""
        import time

        workers = sorted(self._wait_workers())  # may block; lock-free
        if not workers:
            return {}, 0, {}
        with self._state_lock:
            if len(self._bins) > 2 * len(workers) + 8:
                live = set(workers)
                self._bins = {w: b for w, b in self._bins.items()
                              if w in live}
                self._wire_ok = {w: v for w, v in self._wire_ok.items()
                                 if w in live}
                self._nodes = {w: v for w, v in self._nodes.items()
                               if w in live}
                self._lat = {w: v for w, v in self._lat.items()
                             if w in live}
                self._penalized = {w: t for w, t
                                   in self._penalized.items()
                                   if w in live}
                self._strikes = {w: n for w, n
                                 in self._strikes.items()
                                 if w in live}
            # Expire penalties whose quarantine lapsed: a penalized
            # replica's slice is ~zero, so only dropping the penalty
            # lets its EWMA refresh — a recovered replica rejoins the
            # plan on this probe; a still-dead one strikes again and
            # its NEXT quarantine doubles (correctness is covered by
            # the resubmit either way).
            now = time.monotonic()
            for w in [w for w, t in self._penalized.items()
                      if now - t >= self._quarantine_s(w)]:
                del self._penalized[w]
                self._lat.pop(w, None)
            groups: Dict[str, List[str]] = {}
            for w in workers:
                groups.setdefault(self._bin_of(w), []).append(w)
            # Promotion churn retires bins: prune their score/cost rows
            # once they clearly outnumber the live set (same hysteresis
            # as the worker memo prune above).
            if len(self._bin_score) + len(self._bin_cost) > \
                    4 * len(groups) + 16:
                live = set(groups)
                self._bin_score = {b: v for b, v
                                   in self._bin_score.items()
                                   if b in live}
                self._bin_cost = {b: v for b, v
                                  in self._bin_cost.items()
                                  if b in live}
            self._rr += 1
            self._last_bins = tuple(sorted(groups))
            return groups, self._rr, dict(self._lat)

    @staticmethod
    def _rotate_pick(members: List[str], rr: int) -> str:
        """THE rotating per-bin replica pick — shared by the unsharded
        plan branch and _choose_workers so the rotation rule cannot
        diverge between the product path and its test surface."""
        return members[rr % len(members)]

    def _choose_workers(self) -> List[str]:
        """One worker per TRIAL BIN (the unsharded pick; what
        ``predict_submit`` does per bin when sharding is off or a bin
        has one replica). Same-bin workers are replicas; querying all
        of them would double-weight their trials in the ensemble, so
        each request picks one per bin, rotating across requests for
        load balance."""
        groups, rr, _ = self._group_replicas()
        return [self._rotate_pick(members, rr)
                for _, members in sorted(groups.items())]

    # --- Shard planning (data-parallel replica serving) ---

    def serving_vector(self) -> Optional[tuple]:
        """The bin set of the most recent shard plan (sorted tuple) —
        the serving ensemble's model-version vector. The edge cache
        compares it across scatters: a change means trial promotion
        swapped a served bin, so cached answers are stale."""
        with self._state_lock:
            return self._last_bins

    def estimate_query_cost(self,
                            exclude_bin: Optional[str] = None) -> float:
        """Estimated chip-seconds ONE full-ensemble query costs across
        the LIVE serving bins (sum of per-bin compute EWMAs over the
        current serving vector; bins with no estimate yet contribute 0,
        retired bins never count — a promotion must not leave a dead
        bin's cost inflating the avoided counters). Prices the tier
        short-circuit credit: all live bins but the best
        (``exclude_bin``)."""
        with self._state_lock:
            live = self._last_bins
            return sum(v for b, v in self._bin_cost.items()
                       if b != exclude_bin
                       and (live is None or b in live))

    def estimate_hit_cost(self) -> float:
        """Chip-seconds ONE cache hit (or coalesced wait) avoided. With
        tiering OFF that is the full-ensemble cost; with tiering ON the
        avoided miss would most likely have been a best-bin-only
        short-circuit, so only the best bin's cost is claimed — the
        cheapest honest estimate (escalations avoided more; the counter
        under-reports, never fabricates). Falls back to the full sum
        when the best bin is unknowable (a scoreless bin ⇒ misses fan
        out in full anyway)."""
        with self._state_lock:
            live = self._last_bins
            costs = {b: v for b, v in self._bin_cost.items()
                     if live is None or b in live}
            if self.tier_threshold is not None and live and \
                    len(live) > 1:
                scores = {b: self._bin_score.get(b) for b in live}
                if all(v is not None for v in scores.values()):
                    best = max(sorted(scores), key=lambda b: scores[b])
                    return costs.get(best, 0.0)
            return sum(costs.values())

    def _quarantine_s(self, worker_id: str) -> float:
        """Caller holds ``_state_lock``. Seconds a penalized replica
        sits out before its next probe: one gather timeout on the first
        strike, doubling per consecutive strike, capped."""
        strikes = self._strikes.get(worker_id, 1)
        return self.gather_timeout * float(
            min(1 << max(0, strikes - 1), _QUARANTINE_MAX_MULT))

    def _note_latency(self, worker_id: str, seconds: float) -> None:
        if seconds < 0:
            return
        with self._state_lock:
            prev = self._lat.get(worker_id)
            self._lat[worker_id] = (seconds if prev is None else
                                    _LAT_ALPHA * seconds +
                                    (1.0 - _LAT_ALPHA) * prev)
            # A real reply proves the replica alive: the strike count
            # resets so its next penalty (if any) starts the quarantine
            # ladder over at one gather timeout.
            self._strikes.pop(worker_id, None)
            # A penalized worker stays quarantined until the probe
            # expiry in _group_replicas even if a straggler reply lands
            # here: clearing the penalty early would leave the poisoned
            # EWMA in place with no refresh path (a ~zero slice means
            # no replies), starving the replica forever — expiry drops
            # the EWMA too, so recovery is bounded by one probe
            # interval instead.
        if self._m_replica is not None:
            self._m_replica.observe(seconds, service=self.service,
                                    worker=worker_id[:8])

    def _penalize(self, worker_id: str) -> None:
        """A shard timed out on this replica: inflate its EWMA so the
        next plans lean on siblings, and strike it. The penalty expires
        after its quarantine interval (exponential in consecutive
        strikes, capped — see ``_quarantine_s``): a penalized replica's
        slice is ~zero, so its EWMA would otherwise never refresh and
        one transient timeout would starve it forever; a replica that
        keeps missing probes backs off instead of costing one partial
        deadline per gather timeout."""
        import time

        with self._state_lock:
            prev = self._lat.get(worker_id, self.gather_timeout)
            self._lat[worker_id] = max(prev * 2.0, self.gather_timeout)
            self._penalized[worker_id] = time.monotonic()
            self._strikes[worker_id] = \
                self._strikes.get(worker_id, 0) + 1
        if self._m_quarantines is not None:
            self._m_quarantines.inc(service=self.service)

    def _plan_for(self, n: int, groups: Dict[str, List[str]], rr: int,
                  lat: Dict[str, float]) -> List[_Shard]:
        """Shard plan over the given bin groups (a subset for the
        tiered path; everything for the full plan). With sharding OFF
        (or a single replica in a bin) the bin's whole batch goes to
        one rotating pick — the pre-shard behavior. With sharding ON,
        the bin's batch is sliced across ALL its live replicas, sized
        inversely to each replica's gather-latency EWMA (even slices
        until latencies are known); a replica whose weighted slice
        rounds to zero is skipped.

        Cluster locality (docs/cluster.md): with the fabric on and
        ``cluster_locality_boost`` > 1, a same-node replica's weight is
        multiplied by the boost — it takes the larger slice while the
        measured latency gap stays under the boost factor, and the EWMA
        still rules beyond that (a slow local replica loses to a fast
        remote one)."""
        nodes: Dict[str, str] = {}
        if self._node and self._locality_boost > 1.0:
            with self._state_lock:
                nodes = dict(self._nodes)
        plan: List[_Shard] = []
        for bin_id, members in sorted(groups.items()):
            if not self.shard_replicas or len(members) == 1 or n == 1:
                plan.append(_Shard(self._rotate_pick(members, rr),
                                   bin_id, 0, n))
                continue
            # Rotate so equal-weight ties spread the larger remainder
            # slices across replicas over successive batches.
            k = rr % len(members)
            order = members[k:] + members[:k]
            known = [v for w in order
                     if (v := lat.get(w)) is not None and v > 0]
            default = sum(known) / len(known) if known else 1.0
            weights = [(self._locality_boost
                        if nodes.get(w) == self._node else 1.0)
                       / max(lat.get(w, default), 1e-6)
                       for w in order]
            total_w = sum(weights)
            raw = [n * w / total_w for w in weights]
            sizes = [int(r) for r in raw]
            for i in sorted(range(len(order)),
                            key=lambda i: raw[i] - sizes[i],
                            reverse=True)[:n - sum(sizes)]:
                sizes[i] += 1
            start = 0
            for w, size in zip(order, sizes):
                if size > 0:
                    plan.append(_Shard(w, bin_id, start, size))
                    start += size
        return plan

    def _partial_wait(self, plan: List[_Shard]) -> float:
        """Seconds to wait for primary shards before resubmitting
        missing ones (the straggler deadline). Latency-relative when
        every planned replica has a gather-latency EWMA —
        ``min(_RESUBMIT_AT x gather_timeout,
        _STRAGGLER_K x slowest planned EWMA)`` — so fast fleets react
        in milliseconds; the fixed fraction is both the fallback (a
        never-measured replica in the plan means there is no honest
        latency basis yet) and the ceiling (the relative deadline may
        only ever move the resubmit EARLIER)."""
        fixed = self.gather_timeout * _RESUBMIT_AT
        with self._state_lock:
            ewmas = [self._lat.get(s.worker) for s in plan]
        if any(v is None or v <= 0 for v in ewmas):
            return fixed
        return min(fixed, max(_STRAGGLER_K * max(ewmas),
                              _STRAGGLER_MIN))

    def _match_reply(self, reply: Dict[str, Any],
                     plan: List[_Shard]) -> None:
        """Attach one gathered reply to its plan entry. New workers
        echo the frame's shard id; old workers don't, so the fallback
        is the first reply-less shard sent to that worker (unambiguous
        unless a resubmit doubled up on it — and resubmits only target
        shard-echoing siblings of the same deployment)."""
        sid = reply.get("shard")
        shard = None
        if sid is not None:
            shard = next((s for s in plan if s.shard_id == sid), None)
        if shard is None and sid is None:
            wid = reply.get("worker_id")
            shard = next((s for s in plan
                          if s.worker == wid and s.reply is None), None)
        recv = reply.pop("_recv_mono", None)
        if recv is not None and shard is not None:
            self._note_latency(shard.worker, recv - shard.t_sent)
        if shard is not None and shard.reply is None:
            shard.reply = reply
            if shard.pair is not None:
                shard.pair.superseded = True
            # Worker-reported compute seconds for this shard's slice
            # (absent on pre-r12 workers) feed the per-bin per-query
            # cost EWMA that prices chip-seconds-avoided.
            compute_s = reply.get("compute_s")
            n_preds = len(reply.get("predictions") or ())
            if isinstance(compute_s, (int, float)) and compute_s >= 0 \
                    and n_preds:
                per_q = float(compute_s) / n_preds
                with self._state_lock:
                    prev = self._bin_cost.get(shard.bin)
                    self._bin_cost[shard.bin] = (
                        per_q if prev is None else
                        _COST_ALPHA * per_q +
                        (1.0 - _COST_ALPHA) * prev)

    def predict_submit(self, queries: List[Any], *,
                       pre_encoded: bool = False,
                       trace_ctxs: Optional[List[Any]] = None,
                       tenants: Optional[List[Any]] = None,
                       tenant_rows: Optional[List[Optional[str]]] = None,
                       queue_wait_s: float = 0.0,
                       ) -> Callable[[], List[Optional[Any]]]:
        """Scatter a batch of queries NOW; returns a finisher that
        gathers + ensembles when called.

        Batch-granular frames: ONE bus message per shard carries that
        replica's slice of the request, and each replica replies once —
        the scatter/gather cost is O(shards), not O(queries x workers),
        and the whole plan rides one ``push_many`` broker round-trip.
        The split lets the micro-batcher overlap super-batch K's gather
        with K+1's scatter (the frontend mirror of the worker's
        one-burst-in-flight trick).

        With replica sharding ON (the default), each trial bin's batch
        is spread across all live same-bin replicas — data-parallel
        serving with unchanged ensemble semantics. A replica that dies
        mid-gather gets its shard resubmitted to a sibling; a bin with
        no live sibling degrades to a partial-bin result (the other
        bins still vote) instead of stalling the batch.

        With confidence tiering ON (``tier_threshold``) and several
        bins serving, the plan is CHEAP-FIRST: phase 1 scatters only to
        the best bin (by tracked eval score); at gather time, queries
        whose best-bin confidence clears the threshold short-circuit
        with that single vote, and only the rest escalate to a second
        partial plan over the remaining bins (same shard/resubmit
        machinery) whose votes are merged with the best bin's — the
        escalated queries still get one vote per bin.

        ``pre_encoded=True`` means the queries are already bus-safe
        frames (e.g. straight off the HTTP body) — no decode/re-encode
        round-trip on the hot path. ``trace_ctxs`` carries the coalesced
        requests' trace contexts into the bus envelope (the
        micro-batcher's scatter thread has no ambient context; the
        direct path falls back to the calling thread's). ``tenants``
        (``[(tenant_hash, n_queries), ...]``) and ``queue_wait_s``
        (admission wait the batch accrued) feed the attribution ledger
        and the ``_tenant`` envelope carry — both no-ops when the
        ledger is off. ``tenant_rows`` is the optional PER-QUERY
        tenant column (None entries = unattributed): the tiered path's
        escalation scatter re-derives its subset's tenant mix from it,
        so an escalated query's second-phase device time lands on the
        right tenant instead of going unattributed (the r17
        "under-attributed by design" carry, closed).
        """
        n = len(queries)
        if not n:
            return lambda: []
        groups, rr, lat = self._group_replicas()
        if not groups:
            raise RuntimeError(
                f"no running inference workers for job "
                f"{self.inference_job_id}")
        wire = self._build_wire(queries, pre_encoded, groups)
        if self.tier_threshold is not None and len(groups) > 1:
            best = self._best_bin(groups)
            if best is not None:
                return self._submit_tiered(n, wire, groups, rr, lat,
                                           best, trace_ctxs,
                                           tenants=tenants,
                                           tenant_rows=tenant_rows,
                                           queue_wait_s=queue_wait_s)
            # No best-bin basis (a serving worker predates score
            # registration): the whole batch fans out in full.
            self._count_tier("full", n)
        plan = self._plan_for(n, groups, rr, lat)
        batch_id = self._scatter(plan, wire, trace_ctxs,
                                 tenants=tenants,
                                 queue_wait_s=queue_wait_s)

        def finish() -> List[Optional[Any]]:
            self._gather_shards(batch_id, plan, groups, wire,
                                trace_ctxs)
            return self._reassemble(n, plan)

        return finish

    def _build_wire(self, queries: List[Any], pre_encoded: bool,
                    groups: Dict[str, List[str]]) -> _WirePayload:
        """The super-batch's wire payload: the packed-capable worker
        set is resolved here (memoized registration info); both
        representations — the packed contiguous buffer and the
        per-query frames — materialize lazily, at most once, when a
        plan's shards first need them."""
        capable: frozenset = frozenset()
        if self._packed_wire:
            with self._state_lock:
                capable = frozenset(
                    w for members in groups.values() for w in members
                    if self._wire_ok.get(w))
        return _WirePayload(queries, pre_encoded, capable)

    def _plan_nodes(self, plan: List["_Shard"],
                    ) -> Optional[Dict[str, str]]:
        """Per-worker node map for one plan's scatter (None with the
        fabric off — the cache keeps its byte-identical single-broker
        path). Memoized registration reads only; unknown workers map to
        "" and stay on the local broker."""
        if not self._node:
            return None
        with self._state_lock:
            return {s.worker: self._nodes.get(s.worker, "")
                    for s in plan}

    def _scatter(self, plan: List[_Shard], wire: _WirePayload,
                 trace_ctxs: Optional[List[Any]],
                 batch_id: Optional[str] = None,
                 tenants: Optional[List[Any]] = None,
                 queue_wait_s: float = 0.0) -> str:
        """Stamp + send one shard plan (one ``push_many`` round-trip);
        shared by the full and tiered submit paths. Shards bound for
        packed-capable workers carry the contiguous ``batch`` frame;
        the rest get per-query slices — one plan may mix both (the
        mixed-fleet / rolling-promote case). The attribution ledger
        (no-op when off) accounts the plan's per-bin query counts here
        — the one place every scatter flavor funnels through — plus
        the super-batch's admission wait and the tenant carry."""
        import time

        now = time.monotonic()
        for s in plan:
            s.t_sent = now
        enc, packed = wire.for_plan(plan)
        batch_id = self.cache.send_query_shards(
            [s.wire() for s in plan], enc,
            batch_id=batch_id, trace_ctxs=trace_ctxs,
            packed=packed, packed_ok=wire.capable,
            tenants=tenants,
            worker_nodes=self._plan_nodes(plan),
            local_node=self._node)
        if self._m_shards is not None:
            self._m_shards.inc(len(plan), service=self.service)
        bin_queries: Dict[str, int] = {}
        for s in plan:
            bin_queries[s.bin] = bin_queries.get(s.bin, 0) + s.count
        _attr.account_scatter(self.service, bin_queries,
                              queue_wait_s=queue_wait_s)
        return batch_id

    # --- Confidence-tiered serving (cheap-first, escalate on doubt) ---

    def _best_bin(self, groups: Dict[str, List[str]]) -> Optional[str]:
        """The tiered path's phase-1 target: the served bin with the
        highest tracked eval score. None (fall back to a full scatter)
        unless EVERY bin has a score — a scoreless bin could be the
        best one, and silently demoting it would bias the ensemble."""
        with self._state_lock:
            scores = {b: self._bin_score.get(b) for b in groups}
        if not scores or any(v is None for v in scores.values()):
            return None
        return max(sorted(scores), key=lambda b: scores[b])

    def _count_tier(self, outcome: str, n: int) -> None:
        if self._m_tier is not None and n:
            self._m_tier.inc(n, service=self.service, outcome=outcome)

    def _submit_tiered(self, n: int, wire: _WirePayload,
                       groups: Dict[str, List[str]], rr: int,
                       lat: Dict[str, float], best: str,
                       trace_ctxs: Optional[List[Any]],
                       tenants: Optional[List[Any]] = None,
                       tenant_rows: Optional[List[Optional[str]]] = None,
                       queue_wait_s: float = 0.0,
                       ) -> Callable[[], List[Optional[Any]]]:
        """Cheap-first scatter: phase 1 covers only the best bin; the
        finisher escalates sub-threshold queries to the other bins as
        a second partial plan. Ensemble semantics are preserved: a
        short-circuit answer is the best bin's single vote, an
        escalated answer is one vote per bin, exactly like the full
        path."""
        import time

        best_groups = {best: groups[best]}
        plan1 = self._plan_for(n, best_groups, rr, lat)
        batch1 = self._scatter(plan1, wire, trace_ctxs,
                               tenants=tenants,
                               queue_wait_s=queue_wait_s)
        threshold = self.tier_threshold

        def finish() -> List[Optional[Any]]:
            wall = time.time()
            t0 = time.monotonic()
            self._gather_shards(batch1, plan1, best_groups, wire,
                                trace_ctxs)
            rows1, weights1, confs1 = self._collect_rows(n, plan1)
            best_row = rows1.get(best)
            best_conf = confs1.get(best)
            best_w = weights1.get(best, 1)
            results: List[Optional[Any]] = [None] * n
            esc: List[int] = []
            for i in range(n):
                v = best_row[i] if best_row is not None else _HOLE
                c = best_conf[i] if best_conf is not None else None
                # Escalate on a missing/error vote OR missing
                # confidence (sk-style models expose none) OR doubt.
                if v is _HOLE or c is None or c < threshold:
                    esc.append(i)
                else:
                    results[i] = ensemble_predictions([v],
                                                      weights=[best_w])
            short = n - len(esc)
            self._count_tier("short_circuit", short)
            self._count_tier("escalate", len(esc))
            if short and self._m_avoided is not None:
                avoided = short * self.estimate_query_cost(
                    exclude_bin=best)
                if avoided > 0:
                    self._m_avoided.inc(avoided, service=self.service,
                                        source="tier")
            if esc:
                other = {b: ms for b, ms in groups.items() if b != best}
                esc_wire = wire.take(esc)
                plan2 = self._plan_for(len(esc), other, rr, lat)
                # The escalation subset's OWN tenant mix rides the
                # second scatter (from the per-query tenant column):
                # without it, every escalated query's second-phase
                # device time was unattributed by design.
                esc_tenants = None
                if tenant_rows:
                    merged: Dict[str, int] = {}
                    for i in esc:
                        t = (tenant_rows[i]
                             if i < len(tenant_rows) else None)
                        if t:
                            merged[t] = merged.get(t, 0) + 1
                    if merged:
                        esc_tenants = sorted(
                            merged.items(),
                            key=lambda kv: (-kv[1], kv[0]))
                batch2 = self._scatter(plan2, esc_wire, trace_ctxs,
                                       tenants=esc_tenants)
                self._gather_shards(batch2, plan2, other, esc_wire,
                                    trace_ctxs)
                rows2, weights2, _ = self._collect_rows(len(esc), plan2)
                ordered2 = sorted(rows2.items())
                for j, i in enumerate(esc):
                    votes: List[Any] = []
                    wts: List[int] = []
                    if best_row is not None and \
                            best_row[i] is not _HOLE:
                        votes.append(best_row[i])
                        wts.append(best_w)
                    for b, row in ordered2:
                        if row[j] is not _HOLE:
                            votes.append(row[j])
                            wts.append(weights2.get(b, 1))
                    results[i] = ensemble_predictions(votes, weights=wts)
            if trace_ctxs:
                from ..observe import trace as _obs_trace

                _obs_trace.record_event(
                    "predictor.tier", self.service, trace_ctxs, wall,
                    time.monotonic() - t0,
                    attrs={"short_circuit": short,
                           "escalated": len(esc),
                           "best_bin": str(best)[:12]})
            return results

        return finish

    def _gather_shards(self, batch_id: str, plan: List[_Shard],
                       groups: Dict[str, List[str]],
                       wire: _WirePayload,
                       trace_ctxs: Optional[List[Any]]) -> None:
        """Collect replies until every shard is matched or the gather
        timeout lapses. When shards are still missing at the partial
        deadline AND have live siblings, they are resubmitted once —
        the batch degrades to waiting on the fastest sibling instead of
        stalling on a dead replica."""
        import time

        t0 = time.monotonic()
        deadline = t0 + self.gather_timeout
        can_resubmit = any(len(groups.get(s.bin, ())) > 1 for s in plan)
        partial = (t0 + self._partial_wait(plan)
                   if can_resubmit else deadline)
        resubmitted = False

        def drain(until: float) -> None:
            # One reply per pop: a bulk pop of "all pending" would
            # block the full timeout on a superseded shard's reply that
            # will never come, even after its pair already covered the
            # slice.
            while True:
                pending = sum(1 for s in plan
                              if s.reply is None and not s.superseded)
                remaining = until - time.monotonic()
                if not pending or remaining <= 0:
                    return
                replies = self.cache.gather_prediction_batches(
                    batch_id, n_workers=1, timeout=remaining,
                    reap=False, timestamps=True)
                if not replies:
                    return
                for r in replies:
                    self._match_reply(r, plan)

        drain(partial)
        missing = [s for s in plan if s.reply is None]
        if missing and can_resubmit:
            retries: List[_Shard] = []
            now = time.monotonic()
            for s in missing:
                self._penalize(s.worker)
            # Latency snapshot AFTER the penalties, and co-missing
            # workers excluded outright: a shard must never be
            # resubmitted to a sibling that just missed the same
            # deadline. Unknown (never-measured) siblings default to
            # ~1s — preferred over a penalized replica, not over a
            # measured-healthy one.
            with self._state_lock:
                lat = dict(self._lat)
            missing_workers = {s.worker for s in missing}
            for s in missing:
                siblings = [w for w in groups.get(s.bin, ())
                            if w != s.worker
                            and w not in missing_workers]
                if not siblings:
                    continue
                pick = min(siblings,
                           key=lambda w: lat.get(w, 1.0))
                retry = _Shard(pick, s.bin, s.start, s.count)
                retry.resubmitted = True
                retry.t_sent = now
                retry.pair = s
                s.pair = retry
                retries.append(retry)
            if retries:
                resubmitted = True
                enc, packed = wire.for_plan(retries)
                self.cache.send_query_shards(
                    [s.wire() for s in retries], enc,
                    batch_id=batch_id, trace_ctxs=trace_ctxs,
                    packed=packed, packed_ok=wire.capable,
                    worker_nodes=self._plan_nodes(retries),
                    local_node=self._node)
                plan.extend(retries)
                if self._m_resubmits is not None:
                    self._m_resubmits.inc(len(retries),
                                          service=self.service)
                _log.warning(
                    "batch %s: %d shard(s) missing at partial deadline;"
                    " resubmitted to sibling replicas", batch_id,
                    len(retries))
        drain(deadline)
        unmatched = [s for s in plan
                     if s.reply is None and not s.superseded]
        if unmatched:
            for s in unmatched:
                if not s.resubmitted:
                    self._penalize(s.worker)
            _log.warning("batch %s: %d/%d shards replied", batch_id,
                         len(plan) - len(unmatched), len(plan))
        # Stragglers (or the slower of an original/resubmit pair) may
        # still reply; the deferred sweep reaps their recreated queue
        # instead of leaking it. A fully-clean gather needs no sweep.
        self.cache.reap_reply_queue(
            batch_id, defer=bool(unmatched or resubmitted))

    def _collect_rows(self, n: int, plan: List[_Shard],
                      ) -> Tuple[Dict[str, List[Any]],
                                 Dict[str, int],
                                 Dict[str, List[Optional[float]]]]:
        """Stitch matched shard replies into per-bin prediction rows in
        request order (``_HOLE`` marks positions whose shard never
        replied), plus per-bin weights and per-position confidences
        (None where the reply carried none — pre-r12 workers and
        models without probabilities)."""
        rows: Dict[str, List[Any]] = {}
        confs: Dict[str, List[Optional[float]]] = {}
        bin_weight: Dict[str, int] = {}
        for s in plan:
            if s.reply is None:
                continue
            row = rows.get(s.bin)
            if row is None:
                row = rows[s.bin] = [_HOLE] * n
                confs[s.bin] = [None] * n
            crow = confs[s.bin]
            preds = s.reply.get("predictions") or []
            rconf = s.reply.get("confidence") or []
            for j in range(min(s.count, len(preds))):
                if row[s.start + j] is _HOLE:
                    row[s.start + j] = preds[j]
                    if j < len(rconf) and \
                            isinstance(rconf[j], (int, float)):
                        crow[s.start + j] = float(rconf[j])
            bin_weight[s.bin] = max(bin_weight.get(s.bin, 1),
                                    int(s.reply.get("weight", 1)))
        return rows, bin_weight, confs

    def _reassemble(self, n: int, plan: List[_Shard],
                    ) -> List[Optional[Any]]:
        """Ensemble across bins per query. A query whose bin shard
        never replied simply loses that bin's vote — the surviving bins
        still ensemble; a query with no votes at all comes back None
        (the pre-shard no-reply behavior)."""
        rows, bin_weight, _ = self._collect_rows(n, plan)
        results: List[Optional[Any]] = []
        ordered = sorted(rows.items())
        for i in range(n):
            votes = [(row[i], bin_weight[b]) for b, row in ordered
                     if row[i] is not _HOLE]
            results.append(ensemble_predictions(
                [v for v, _ in votes], weights=[w for _, w in votes]))
        return results

    def predict(self, queries: List[Any], *,
                pre_encoded: bool = False,
                tenants: Optional[List[Any]] = None,
                tenant_rows: Optional[List[Optional[str]]] = None,
                ) -> List[Optional[Any]]:
        """Scatter-gather-ensemble a batch of queries (blocking)."""
        return self.predict_submit(queries, pre_encoded=pre_encoded,
                                   tenants=tenants,
                                   tenant_rows=tenant_rows)()
