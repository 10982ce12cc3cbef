"""PredictorService: the HTTP frontend of one inference job.

Parity: SURVEY.md §3.3 — upstream's predictor is a Flask app with
``POST /predict``; app consumers send queries and receive the ensembled
result. Routes:

- ``GET  /``          → health + running worker count + queue depth
- ``POST /predict``   → ``{"query": ...}`` or ``{"queries": [...]}``;
  numpy-array queries use the cache's base64 frame encoding
  (``{"__nd__": ..., "dtype": ..., "shape": ...}``) or plain nested lists.
  Overload answers ``429`` with a ``Retry-After`` header.
- ``GET  /stats``     → micro-batcher counters (coalescing factor,
  queue depth, per-stage latency; ``observe.ServingStats``, fed from
  the unified metrics registry).
- ``GET  /metrics``   → Prometheus text exposition of the process
  registry (auto-wired by ``JsonHttpServer``); the ``service`` label
  in ``/stats`` names this frontend's ``rafiki_tpu_serving_*`` series.

Concurrent requests do NOT each pay their own worker scan + bus
scatter: a continuous micro-batcher (``predictor/batcher.py``)
coalesces everything arriving within one fill window into a single
scatter-gather super-batch and slices the ensembled results back out
per request. ``RAFIKI_TPU_SERVING_MICROBATCH=0`` restores the direct
one-scatter-per-request path (``tests/test_predictor_batcher.py::
test_microbatch_disabled_restores_direct_path``).
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import Any, Dict, Optional

from ..bus import BaseBus
from ..cache import decode_payload
from ..config import NodeConfig, _parse_bool
from ..constants import ServiceStatus
from ..observe import ServingStats, trace
from ..observe import attribution as _attr
from ..observe import workload as _workload
from ..store import MetaStore
from ..utils.service import JsonHttpServer, StreamResponse
from .batcher import Backpressure, MicroBatcher
from .edge_cache import EdgeCache, query_key
from .predictor import Predictor


def _env_knob(field: str, default: str) -> str:
    return os.environ.get(NodeConfig.env_name(field), default)


class PredictorService:
    def __init__(self, service_id: str, inference_job_id: str,
                 meta: MetaStore, bus: BaseBus, host: str = "0.0.0.0",
                 port: int = 0, microbatch: Optional[bool] = None,
                 fill_window: Optional[float] = None,
                 fill_window_min: Optional[float] = None,
                 fill_window_max: Optional[float] = None,
                 max_batch: Optional[int] = None,
                 max_inflight: Optional[int] = None,
                 queue_cap: Optional[int] = None,
                 shard_replicas: Optional[bool] = None,
                 client_header: Optional[str] = None,
                 client_share: Optional[float] = None,
                 cache_bytes: Optional[int] = None,
                 cache_ttl_s: Optional[float] = None,
                 cache_admit_after: Optional[int] = None,
                 tier_threshold: Optional[float] = None):
        import uuid

        self.service_id = service_id
        self.inference_job_id = inference_job_id
        self.meta = meta
        # The metrics label must be unique per INSTANCE (tests and
        # restarts reuse service ids within one process; two frontends
        # sharing a label would read each other's registry series), but
        # lead with the service id so a human can match /metrics series
        # to the service table.
        self.stats = ServingStats(
            service=f"{service_id[:12]}-{uuid.uuid4().hex[:4]}")
        # Knob precedence matches NodeConfig: explicit constructor arg >
        # RAFIKI_TPU_SERVING_* env (apply_env exports them) > default.
        if shard_replicas is None:
            shard_replicas = _parse_bool(
                _env_knob("serving_shard_replicas", "1"))
        self.predictor = Predictor(
            inference_job_id, bus, shard_replicas=shard_replicas,
            service=self.stats.service,
            tier_threshold=(
                tier_threshold if tier_threshold is not None else
                float(_env_knob("serving_tier_threshold", "0") or 0)))
        # Content-addressed edge cache in front of the batcher/scatter
        # (docs/serving.md). None when disabled: the hot path then pays
        # ONE attribute check and no cache series is ever registered.
        _cache_bytes = int(cache_bytes if cache_bytes is not None else
                           _env_knob("serving_cache_bytes", "0") or 0)
        self.edge_cache: Optional[EdgeCache] = None
        if _cache_bytes > 0:
            self.edge_cache = EdgeCache(
                _cache_bytes,
                ttl_s=float(cache_ttl_s if cache_ttl_s is not None else
                            _env_knob("serving_cache_ttl_s", "60")),
                admit_after=int(
                    cache_admit_after if cache_admit_after is not None
                    else _env_knob("serving_cache_admit_after", "2")),
                service=self.stats.service)
        # Cluster cache fabric (docs/cluster.md): construction-time
        # snapshot, active only when BOTH the fabric and the edge cache
        # are on. Off (the default) = plain bool checks on the miss
        # path, no frontend registration, zero fabric series
        # (tests/test_cluster.py::
        # test_single_node_construction_has_no_cluster_surface).
        self._fabric = False
        self._fabric_probe_timeout = 0.25
        self._m_fabric = None
        if self.edge_cache is not None and _parse_bool(
                _env_knob("cluster_fabric", "0")):
            self._fabric = True
            self._fabric_probe_timeout = float(
                _env_knob("cluster_probe_timeout_s", "0.25") or 0.25)
            from ..observe import metrics as obs_metrics

            if obs_metrics.metrics_enabled():
                self._m_fabric = obs_metrics.registry().counter(
                    "rafiki_tpu_serving_fabric_total",
                    "Cache-fabric events between peer frontends "
                    "(event=peer_hit|peer_miss|probe_error|"
                    "gossip_sent|gossip_recv)")
        if microbatch is None:
            microbatch = _parse_bool(_env_knob("serving_microbatch", "1"))
        self.microbatch = microbatch
        # Per-client fairness: the header that derives the client key
        # ("" = off) and the per-key share of the admission queue.
        self.client_header = (client_header
                              if client_header is not None else
                              _env_knob("serving_client_header", ""))
        # Attribution ledger (construction-time snapshot, r11
        # discipline): off = no tenant hashing, no account calls
        # beyond a None check inside the ledger.
        self._attribution = _attr.enabled()
        # Workload recorder (same snapshot discipline): off = one bool
        # check per request, no record dicts, zero workload series.
        self._workload = _workload.active()
        # Batcher-OFF fairness (the direct one-scatter-per-request
        # path has no admission queue): the same client_share caps one
        # client key's IN-FLIGHT queries instead, against the same
        # serving_queue_cap basis — so flipping
        # RAFIKI_TPU_SERVING_MICROBATCH does not silently drop the
        # fairness guarantee. Reuses the header-derived key and the
        # backpressure{reason="client_share"} accounting.
        # Resolved ONCE and shared with the MicroBatcher below, so the
        # batcher-on and batcher-off fairness caps can never
        # desynchronize.
        _share = (float(client_share if client_share is not None else
                        _env_knob("serving_client_share", "0.25"))
                  if self.client_header else 0.0)
        _qcap = int(queue_cap if queue_cap is not None else
                    _env_knob("serving_queue_cap", "4096"))
        self._direct_cap = max(1, int(_qcap * _share)) if _share > 0 \
            else 0
        self._direct_pending: Dict[str, int] = {}
        self._direct_lock = threading.Lock()
        self.batcher: Optional[MicroBatcher] = None
        if microbatch:
            fw = float(fill_window if fill_window is not None else
                       _env_knob("serving_fill_window", "0.005"))
            fw_max_env = _env_knob("serving_fill_window_max", "")
            self.batcher = MicroBatcher(
                self.predictor,
                fill_window=fw,
                fill_window_min=float(
                    fill_window_min if fill_window_min is not None else
                    _env_knob("serving_fill_window_min", "0.0")),
                fill_window_max=(
                    fill_window_max if fill_window_max is not None else
                    float(fw_max_env) if fw_max_env else None),
                max_batch=int(max_batch if max_batch is not None else
                              _env_knob("serving_max_batch", "1024")),
                max_inflight=int(max_inflight
                                 if max_inflight is not None else
                                 _env_knob("serving_max_inflight", "2")),
                queue_cap=_qcap,
                client_share=_share,
                stats=self.stats)
        # Generate-worker round robin (replicas of a generative bin
        # each run their own decode loop; spread streams across them).
        self._gen_rr = itertools.count()
        self._http = JsonHttpServer([
            # rta: disable=RTA702 liveness probe for supervisors/load-balancers, not in-tree code
            ("GET", "/", self._health),
            ("GET", "/stats", self._stats),
            ("POST", "/predict", self._predict),
            # rta: disable=RTA702 streamed generation is driven by external clients (tests hit it raw); no SDK wrapper yet
            ("POST", "/generate", self._generate),
            ("POST", "/cache/invalidate", self._cache_invalidate),
            ("GET", "/cache/peek", self._cache_peek),
        ], host=host, port=port,
            # Same per-INSTANCE uniqueness rule as the stats label (and
            # sharing its suffix): a reused service id would merge two
            # frontends' http series, and the old instance's stop()
            # would delete the live one's.
            name=f"predictor-{self.stats.service}")
        self.port = self._http.port

    # --- Service lifecycle (ContainerManager contract) ---

    def start(self) -> "PredictorService":
        if self.batcher is not None:
            self.batcher.start()
        self._http.start()
        host = f"127.0.0.1:{self.port}"
        self.meta.update_service(self.service_id,
                                 status=ServiceStatus.RUNNING,
                                 host="127.0.0.1", port=self.port)
        self.meta.update_inference_job(self.inference_job_id,
                                       predictor_host=host)
        if self._fabric:
            # Join the job's frontend registry so peers can probe this
            # cache and the admin's invalidate fan-out can reach it.
            # Keyed by the per-INSTANCE stats label (service ids are
            # reused within one test process).
            try:
                self.predictor.cache.register_frontend(
                    self.inference_job_id, self.stats.service, host)
            except (ConnectionError, OSError, RuntimeError):
                # Degraded but alive: this frontend still serves (and
                # probes peers); peers just cannot find IT until a
                # restart re-registers.
                import logging

                logging.getLogger(__name__).warning(
                    "cache-fabric frontend registration failed",
                    exc_info=True)
        return self

    def stop(self) -> None:
        if self._fabric:
            try:
                self.predictor.cache.unregister_frontend(
                    self.inference_job_id, self.stats.service)
            except (ConnectionError, OSError, RuntimeError):
                pass  # broker gone = registration gone with it
        self._http.stop()
        if self.batcher is not None:
            self.batcher.stop()
        # Release this frontend's registry series (serving counters,
        # the predictor's shard/replica series, the edge cache's AND
        # the http layer's per-service series): the labels are
        # per-deployment, so leaking them would grow every scrape with
        # deploy/stop churn.
        self.stats.close()
        self.predictor.close()
        if self.edge_cache is not None:
            self.edge_cache.close()
        if self._m_fabric is not None:
            # rta: disable=RTA106 handle bound once in __init__ and never rebound; remove()/inc() lock internally — a late fabric event racing stop-time series removal is benign
            self._m_fabric.remove(service=self.stats.service)
        from ..observe import metrics as obs_metrics

        for name in ("rafiki_tpu_http_request_seconds",
                     "rafiki_tpu_http_requests_total"):
            m = obs_metrics.registry().find(name)
            if m is not None:
                m.remove(service=self._http.name)
        self.meta.update_service(self.service_id,
                                 status=ServiceStatus.STOPPED)

    def run(self) -> None:
        """Foreground entrypoint (subprocess mode)."""
        self.start()
        threading.Event().wait()

    @property
    def running(self) -> bool:
        return self._http._thread is not None and \
            self._http._thread.is_alive()

    # --- Routes ---

    def _health(self, params, body, ctx):
        return 200, {"status": "ok",
                     "inference_job_id": self.inference_job_id,
                     "n_workers": len(self.predictor.workers()),
                     "microbatch": self.microbatch,
                     "queue_depth": self.stats.queue_depth}

    def _stats(self, params, body, ctx):
        snap = self.stats.snapshot()
        snap["microbatch"] = self.microbatch
        # The HTTP layer's own series (rafiki_tpu_http_request_seconds)
        # label by the server name — expose it so /metrics readers
        # can match this frontend's series without guessing.
        snap["http_service"] = self._http.name
        snap["shard_replicas"] = self.predictor.shard_replicas
        snap["tier_threshold"] = self.predictor.tier_threshold
        snap["cache"] = (self.edge_cache.info()
                         if self.edge_cache is not None else None)
        if self.batcher is not None:
            snap["knobs"] = {
                "fill_window": self.batcher.fill_window,
                "fill_window_min": self.batcher.fill_window_min,
                "fill_window_max": self.batcher.fill_window_max,
                "max_batch": self.batcher.max_batch,
                "max_inflight": self.batcher.max_inflight,
                "queue_cap": self.batcher.queue_cap,
                "client_share": self.batcher.client_share,
                "client_header": self.client_header,
            }
        return 200, snap

    def _cache_invalidate(self, params, body, ctx):
        """Drop every cached answer and bump the cache epoch — the
        admin promotion path calls this synchronously BEFORE answering
        the promote request, so no request after a promotion can be
        served a pre-promotion entry. Unauthenticated like every other
        predictor route (invalidation is a safe, idempotent act);
        answers ``enabled: false`` with no side effect when the cache
        is off.

        Cluster fabric: a DIRECT invalidation is gossiped (best-effort)
        to every peer frontend so a hot key invalidated here cannot be
        served stale from a peer's cache for its whole TTL. A gossiped
        frame carries ``{"gossip": true}`` and is NEVER re-forwarded —
        the fan-out is one hop deep by construction, no storms."""
        if self.edge_cache is None:
            return 200, {"enabled": False}
        gossip = bool(body and body.get("gossip"))
        epoch = self.edge_cache.invalidate()
        if self._fabric:
            if gossip:
                self._fabric_event("gossip_recv")
            else:
                self._gossip_invalidate()
        return 200, {"enabled": True, "epoch": epoch}

    def _cache_peek(self, params, body, ctx):
        """Read-only cache-fabric probe (docs/cluster.md): a PEER
        frontend asks whether this cache holds ``key`` before paying
        its own scatter. Side-effect free — see ``EdgeCache.peek``."""
        if self.edge_cache is None:
            return 200, {"enabled": False, "found": False}
        found, value = self.edge_cache.peek(ctx.query_one("key") or "")
        return 200, {"enabled": True, "found": found,
                     "value": value if found else None}

    # --- Cache fabric (docs/cluster.md) ---

    def _fabric_event(self, event: str) -> None:
        if self._m_fabric is not None:
            self._m_fabric.inc(service=self.stats.service, event=event)

    def _fabric_peers(self) -> list:
        """Sorted HTTP addrs of every OTHER registered frontend of this
        job. Read from the bus per miss batch (not memoized): frontend
        churn is deploy-rate, the kv read is one bus round-trip, and a
        stale peer list would turn every miss into a probe_error for
        the whole memo lifetime."""
        try:
            peers = self.predictor.cache.frontends(self.inference_job_id)
        except (ConnectionError, OSError, RuntimeError):
            return []
        return sorted(addr for inst, addr in peers.items()
                      if inst != self.stats.service)

    def _peer_probe(self, key: str) -> Any:
        """ONE bounded probe for a missed key: ask a single peer (picked
        by key hash, so N frontends spread probe load instead of all
        hammering peer[0]) whether it already holds the answer. Returns
        the peer's value or None; never raises — the miss path falls
        through to its own scatter, and the probe timeout
        (cluster_probe_timeout_s) bounds the added latency."""
        peers = self._fabric_peers()
        if not peers:
            return None
        addr = peers[int(key[:8] or "0", 16) % len(peers)]
        from urllib.parse import quote
        from urllib.request import urlopen

        try:
            with urlopen(f"http://{addr}/cache/peek?key={quote(key)}",
                         timeout=self._fabric_probe_timeout) as resp:
                reply = json.loads(resp.read())
        except (OSError, ValueError):
            self._fabric_event("probe_error")
            return None
        if reply.get("found"):
            self._fabric_event("peer_hit")
            return reply.get("value")
        self._fabric_event("peer_miss")
        return None

    def _gossip_invalidate(self) -> None:
        """Best-effort one-hop invalidation fan-out to peer frontends.
        The admin's synchronous promote-path fan-out is the correctness
        mechanism; gossip covers direct invalidations so peers converge
        within a probe timeout instead of a cache TTL. Failures are
        logged, never raised — a dead peer's cache dies with it."""
        from urllib.request import Request, urlopen

        for addr in self._fabric_peers():
            try:
                req = Request(f"http://{addr}/cache/invalidate",
                              data=b'{"gossip": true}',
                              headers={"Content-Type":
                                       "application/json"},
                              method="POST")
                with urlopen(req,
                             timeout=self._fabric_probe_timeout) as r:
                    r.read()
            except OSError:
                import logging

                logging.getLogger(__name__).warning(
                    "cache-fabric gossip to %s failed", addr,
                    exc_info=True)
                continue
            self._fabric_event("gossip_sent")

    def _run_queries(self, encoded_queries,
                     client: Optional[str] = None,
                     tenant: Optional[str] = None,
                     record: Optional[Dict[str, Any]] = None) -> list:
        """One request's queries → ensembled predictions. With the edge
        cache enabled, each query is first resolved against it: hits
        are answered without touching the batcher/bus, concurrent
        identical queries coalesce onto one in-flight scatter, and only
        genuine misses dispatch. Disabled cache = one attribute check,
        straight to dispatch."""
        if self.edge_cache is None:
            return self._dispatch_queries(encoded_queries, client,
                                          tenant=tenant, record=record)
        return self._run_cached(encoded_queries, client, tenant=tenant,
                                record=record)

    def _handler_timeout(self) -> float:
        """Bound a handler's wait by the worst honest path: worker
        warm-up wait + gather timeout + batching slack. A wedged
        batcher (or a stranded coalesced flight) then surfaces as a
        500, not a hung socket."""
        return (self.predictor.worker_wait_timeout
                + self.predictor.gather_timeout + 60.0)

    def _run_cached(self, encoded_queries,
                    client: Optional[str] = None,
                    tenant: Optional[str] = None,
                    record: Optional[Dict[str, Any]] = None) -> list:
        cache = self.edge_cache
        n = len(encoded_queries)
        results: list = [None] * n
        misses: list = []      # (position, key) this request leads
        lead_pos: dict = {}    # key -> leading position (intra-request)
        dups: list = []        # (position, leader position)
        waits: list = []       # (position, in-flight leader's flight)
        wall, t0 = time.time(), time.monotonic()
        n_hits = 0
        for i, q in enumerate(encoded_queries):
            key = query_key(q)
            if key in lead_pos:  # same key twice in ONE request
                dups.append((i, lead_pos[key]))
                continue
            kind, payload = cache.begin(key)
            if kind == "hit":
                results[i] = payload
                n_hits += 1
            elif kind == "wait":
                waits.append((i, payload))
            else:
                lead_pos[key] = i
                misses.append((i, key, payload))  # payload = our flight
        # The epoch is read BEFORE dispatch: an invalidation (trial
        # promotion) landing while the scatter is in flight bumps it,
        # and resolve() then drops the stale insert.
        epoch = cache.epoch
        if misses and self._fabric:
            # Cache fabric (docs/cluster.md): before paying a scatter,
            # ask ONE peer whether it already holds the key — a hot key
            # is then computed once per CLUSTER, not once per frontend.
            # The epoch was captured ABOVE, before the probe: a
            # gossiped invalidation racing the probe bumps it, and
            # resolve() drops the stale insert (this request still gets
            # the answer — same contract as an in-flight scatter).
            still = []
            for i, key, flight in misses:
                value = self._peer_probe(key)
                if value is not None:
                    results[i] = value
                    cache.resolve(key, value, epoch, flight=flight)
                else:
                    still.append((i, key, flight))
            misses = still
        if misses:
            try:
                sub = self._dispatch_queries(
                    [encoded_queries[i] for i, _, _ in misses], client,
                    tenant=tenant, record=record)
            except BaseException as e:
                for _, key, flight in misses:
                    cache.fail(key, e, flight=flight)
                raise
            # Cross-check the serving-bin vector the scatter actually
            # saw: a changed bin set (promotion observed from the
            # registry) invalidates even without the admin's POST.
            vector = self.predictor.serving_vector()
            if vector is not None:
                cache.note_vector(vector)
            for (i, key, flight), value in zip(misses, sub):
                results[i] = value
                cache.resolve(key, value, epoch, flight=flight)
        # Hits and coalesced waiters skip the scatter→gather: credit
        # the estimated chip-seconds a MISS would have cost (0 until
        # the per-bin cost EWMA warms; best-bin-only under tiering —
        # under-report, never fabricate). Waiters are credited only
        # AFTER their flight succeeds: a failed leader avoided nothing.
        est = (self.predictor.estimate_hit_cost()
               if (n_hits or waits) else 0.0)
        if n_hits:
            cache.note_avoided(n_hits * est)
        if waits:
            timeout = self._handler_timeout()
            for i, flight in waits:
                results[i] = flight.wait(timeout)
            # A leader whose ensemble FAILED resolves its flight with
            # None (never inserted): those waiters avoided nothing.
            cache.note_avoided(est * sum(
                1 for i, _ in waits if results[i] is not None))
        for i, lead in dups:
            results[i] = results[lead]
        if n_hits or waits or dups:
            ctx = trace.current()
            if ctx is not None:
                trace.record_event(
                    "predictor.cache", self.stats.service, [ctx], wall,
                    time.monotonic() - t0,
                    attrs={"hits": n_hits, "coalesced": len(waits),
                           "misses": len(misses)})
        return results

    def _dispatch_queries(self, encoded_queries,
                          client: Optional[str] = None,
                          tenant: Optional[str] = None,
                          record: Optional[Dict[str, Any]] = None,
                          ) -> list:
        """Cache-miss path: through the shared micro-batcher when
        enabled (frames stay wire-encoded all the way to the bus — no
        decode/re-encode on the hot path)."""
        if not encoded_queries:
            return []
        if self.batcher is not None:
            return self.batcher.submit(encoded_queries,
                                       timeout=self._handler_timeout(),
                                       client=client, tenant=tenant,
                                       record=record)
        n = len(encoded_queries)
        if client is not None and self._direct_cap:
            with self._direct_lock:
                held = self._direct_pending.get(client, 0)
                # Mirror of the batcher's oversized-request rule: a
                # single over-cap request is admitted when the client
                # holds nothing (it could never be served otherwise).
                if held > 0 and held + n > self._direct_cap:
                    self.stats.backpressured(reason="client_share")
                    raise Backpressure(1.0, held, self._direct_cap,
                                       reason="client_share")
                self._direct_pending[client] = held + n
        try:
            self.stats.admitted(n)
            return self.predictor.predict(
                [decode_payload(q) for q in encoded_queries],
                tenants=[(tenant, n)] if tenant else None,
                tenant_rows=[tenant] * n if tenant else None)
        finally:
            if client is not None and self._direct_cap:
                with self._direct_lock:
                    left = self._direct_pending.get(client, 0) - n
                    if left > 0:
                        self._direct_pending[client] = left
                    else:
                        self._direct_pending.pop(client, None)

    def _pick_generate_worker(self) -> Optional[str]:
        """Round-robin over workers advertising ``gen`` in their bus
        registration (the engine geometry a generative bin publishes);
        None when the job has no token-capable worker."""
        info = self.predictor.cache.running_worker_info(
            self.inference_job_id)
        gens = sorted(w for w, i in info.items()
                      if isinstance(i, dict) and i.get("gen"))
        if not gens:
            return None
        return gens[next(self._gen_rr) % len(gens)]

    def _generate(self, params, body, ctx):
        """Token generation, streamed: ``{"tokens": [...], "max_new":
        N, "temperature": t, "seed": s, "eos": id}`` → one NDJSON line
        per token frame (``{"seq": k, "tok": [t], "done": ...}``, the
        final line carrying ``finish`` + ``n_tokens``). The request
        rides the bus to ONE generate-capable worker whose decode loop
        admits it between steps; frames stream back through the reply
        queue and out of this handler as HTTP chunks while later
        tokens are still decoding. Prompt-prefix reuse happens
        worker-side (the engine's content-addressed prefix cache), so
        repeated prompts skip prefill without any edge coordination."""
        if not body or not isinstance(body.get("tokens"), list) \
                or not body["tokens"]:
            return 400, {"error":
                         "body needs 'tokens' (non-empty id list)"}
        try:
            tokens = [int(t) for t in body["tokens"]]
            max_new = int(body.get("max_new") or 16)
            temperature = float(body.get("temperature") or 0.0)
            seed = int(body.get("seed") or 0)
            eos = (int(body["eos"])
                   if body.get("eos") is not None else None)
        except (TypeError, ValueError):
            return 400, {"error": "malformed generation parameters"}
        worker = self._pick_generate_worker()
        if worker is None:
            return 503, {"error": "no generate-capable worker "
                                  "registered for this job"}
        cache = self.predictor.cache
        qid = cache.send_generate(worker, tokens, max_new=max_new,
                                  temperature=temperature, seed=seed,
                                  eos=eos)
        client = (ctx.headers.get(self.client_header)
                  if self.client_header else None)
        tenant = _attr.tenant_key(client) if self._attribution else None
        record = (_workload.open_request(self.inference_job_id, tenant,
                                         1)
                  if self._workload else None)
        timeout = self._handler_timeout()

        def frames():
            t0 = time.monotonic()
            deadline = t0 + timeout
            status, done = 200, False
            try:
                while not done and time.monotonic() < deadline:
                    for fr in cache.pop_token_frames(qid, timeout=0.25):
                        if fr.get("finish") == "error":
                            status = 502
                        yield json.dumps(fr) + "\n"
                        if fr.get("done"):
                            done = True
                if not done:
                    status = 504
                    yield json.dumps({"done": True,
                                      "finish": "timeout"}) + "\n"
            finally:
                # Runs on client disconnect too (StreamResponse closes
                # the iterator): the workload record reflects what the
                # stream actually did.
                dur = time.monotonic() - t0
                _workload.commit(record, status, dur)
                if tenant and status == 200:
                    _attr.account_admitted(tenant)
                    _attr.account_tenant_latency(
                        tenant, dur, service=self.stats.service)

        return 200, StreamResponse("application/x-ndjson", frames())

    def _predict(self, params, body, ctx):
        if not body:
            return 400, {"error": "missing JSON body"}
        single = "queries" not in body
        if single and "query" not in body:
            return 400, {"error": "body needs 'query' or 'queries'"}
        client = (ctx.headers.get(self.client_header)
                  if self.client_header else None)
        # Attribution: the hashed tenant key (never the raw header
        # value) for the per-tenant rollup and the bus-envelope carry.
        # The rollup counts requests actually SERVED — after the run,
        # so a malformed-body or 100%-throttled (429) hammer can
        # neither inflate a tenant's request count nor churn real
        # tenants out of the LRU while serving nothing.
        tenant = _attr.tenant_key(client) if self._attribution else None
        queries = [body["query"]] if single else body["queries"]
        # Workload recorder: one arrival record per request (429s
        # included — replay must reproduce the overload, not just the
        # served fraction). The record dict rides the dispatch path so
        # the micro-batcher can annotate the admission wait.
        record = (_workload.open_request(self.inference_job_id, tenant,
                                         len(queries))
                  if self._workload else None)
        t0 = time.monotonic()
        try:
            preds = self._run_queries(queries, client=client,
                                      tenant=tenant, record=record)
        except Backpressure as e:
            if self._attribution:
                _attr.account_rejected(self.stats.service, e.reason)
            _workload.commit(record, 429, time.monotonic() - t0,
                             reason=e.reason)
            return (429,
                    {"error": str(e), "queue_depth": e.depth,
                     "queue_cap": e.cap, "reason": e.reason,
                     "retry_after": e.retry_after},
                    {"Retry-After": str(int(e.retry_after))})
        dur_s = time.monotonic() - t0
        if tenant:
            _attr.account_admitted(tenant)
            # Tenant-labeled request latency (SERVED requests only):
            # what a tenant-scoped latency SLO reads.
            _attr.account_tenant_latency(tenant, dur_s,
                                         service=self.stats.service)
        _workload.commit(record, 200, dur_s,
                         bins=self.predictor.serving_vector())
        if single:
            return 200, {"prediction": preds[0]}
        return 200, {"predictions": preds}
