"""Continuous cross-request micro-batching for the serving frontend.

Parity+: upstream's Predictor scatter-gathers once per incoming request
(SURVEY.md §3.3); the reproduction kept that shape, so every concurrent
``/predict`` paid its own worker scan + bus scatter + blocking gather
(what that costs on the chip is not measured: ``PERF.md`` §7 row 5).
This module puts ONE
shared admission queue between the HTTP handlers and the Predictor:

- **Coalescing.** All requests arriving within a short fill window (or
  up to a query cap) ride ONE scatter-gather super-batch; per-request
  slices come back out via futures. N concurrent clients cost one
  worker scan and one bus round-trip per window, not N of each.
- **Keep-N-in-flight.** Super-batch K+1 is filled and scattered while
  K's gather is still blocking (a dedicated gather thread completes
  batches in dispatch order), mirroring the InferenceWorker's
  one-burst-in-flight overlap from the other side of the bus.
- **Backpressure.** The admission queue is bounded in QUERIES; when
  it is full, ``submit`` raises :class:`Backpressure` immediately and
  the HTTP route turns that into ``429 Retry-After`` — overload shows
  up as fast rejections, not unbounded handler-thread pileup.
- **Adaptive fill window.** The window is sized from the OBSERVED
  arrival rate (an inter-arrival EWMA; the resulting fill times land
  in the ``rafiki_tpu_serving_stage_seconds`` fill histogram, which is
  how an operator verifies convergence): near zero under trickle load,
  where waiting would only add latency nobody shares, growing toward
  ``fill_window_max`` as arrivals tighten and coalescing pays. Pin
  ``fill_window_min == fill_window_max`` to restore a fixed window.
- **Per-client fairness.** With a ``client_share`` cap and a client
  key passed by the caller (header-derived in the HTTP frontend;
  default off), no single client's queries can hold more than that
  share of the admission queue — one burst can't starve everyone else
  up to the 429 bound.

Knobs (``NodeConfig`` fields, ``RAFIKI_TPU_SERVING_*`` env parity):
``serving_microbatch`` (on/off), ``serving_fill_window`` (seconds;
the adaptive ceiling's default), ``serving_fill_window_min`` /
``serving_fill_window_max`` (adaptive bounds), ``serving_max_batch``
(queries per super-batch), ``serving_max_inflight``
(scattered-ungathered super-batches), ``serving_queue_cap`` (admission
bound, queries), ``serving_client_header`` / ``serving_client_share``
(fairness). Observability rides :class:`observe.ServingStats`.
"""

from __future__ import annotations

import collections
import logging
import math
import threading
import time
from typing import Any, Dict, List, Optional

from ..observe import ServingStats, trace
from ..observe import workload

_log = logging.getLogger(__name__)

#: Inter-arrival EWMA smoothing: ~the last dozen arrivals dominate —
#: fast enough to open the window within one burst, calm enough that a
#: single stray request doesn't slam it shut.
_ARRIVAL_ALPHA = 0.15


class Backpressure(RuntimeError):
    """Admission bound hit; retry after ``retry_after`` seconds.
    ``reason`` says WHICH bound: ``"queue_full"`` (the global queue
    cap) or ``"client_share"`` (one client key over its fair share)."""

    def __init__(self, retry_after: float, depth: int, cap: int,
                 reason: str = "queue_full"):
        super().__init__(
            f"serving queue full ({depth}/{cap} queries, {reason}); "
            f"retry after {retry_after:.1f}s")
        self.retry_after = retry_after
        self.depth = depth
        self.cap = cap
        self.reason = reason


class _Request:
    """One caller's slice of a super-batch."""

    __slots__ = ("queries", "event", "result", "error", "trace",
                 "client", "tenant", "t_admit", "record")

    def __init__(self, queries: List[Any],
                 client: Optional[str] = None,
                 tenant: Optional[str] = None,
                 record: Optional[Dict[str, Any]] = None):
        self.queries = queries
        self.event = threading.Event()
        self.result: Optional[List[Any]] = None
        self.error: Optional[BaseException] = None
        # The submitting (handler) thread's trace context: the batcher
        # and gather threads have none of their own, so the request
        # carries it across the thread hop into the bus envelope.
        self.trace = trace.current()
        self.client = client
        # Attribution: the hashed tenant key (None when the ledger is
        # off / the request carried no client header) and the
        # admission time — dispatch-minus-admit is the queue wait the
        # ledger charges per bin.
        self.tenant = tenant
        self.t_admit = time.monotonic()
        # The workload recorder's open per-request record (None when
        # the recorder is off): the batcher annotates the admission
        # wait into it at dispatch (observe/workload.py).
        self.record = record

    def resolve(self, result: List[Any]) -> None:
        self.result = result
        self.event.set()

    def fail(self, error: BaseException) -> None:
        self.error = error
        self.event.set()


class MicroBatcher:
    """Shared admission queue + batcher/gather thread pair in front of
    one :class:`~rafiki_tpu.predictor.predictor.Predictor`.

    ``submit`` blocks the calling (handler) thread until its slice of
    the ensembled results is ready; the batcher thread owns scatter,
    the gather thread owns gather — at most ``max_inflight``
    super-batches are scattered-but-ungathered at any moment.
    """

    def __init__(self, predictor: Any, *, fill_window: float = 0.005,
                 fill_window_min: float = 0.0,
                 fill_window_max: Optional[float] = None,
                 max_batch: int = 1024, max_inflight: int = 2,
                 queue_cap: int = 4096, pre_encoded: bool = True,
                 client_share: float = 0.0,
                 stats: Optional[ServingStats] = None):
        if fill_window < 0:
            raise ValueError("fill_window must be >= 0")
        if max_batch < 1 or max_inflight < 1 or queue_cap < 1:
            raise ValueError("max_batch, max_inflight and queue_cap "
                             "must be >= 1")
        self.predictor = predictor
        self.fill_window = fill_window
        # Adaptive window bounds: max defaults to the legacy fixed
        # knob, min to zero — so out of the box a trickle pays ~no
        # coalescing idle time while load still earns the full window.
        self.fill_window_min = fill_window_min
        self.fill_window_max = (fill_window if fill_window_max is None
                                else fill_window_max)
        if not (0 <= self.fill_window_min <= self.fill_window_max):
            raise ValueError("need 0 <= fill_window_min <= "
                             "fill_window_max")
        if not (0.0 <= client_share <= 1.0):
            raise ValueError("client_share must be within [0, 1]")
        self.max_batch = max_batch
        self.max_inflight = max_inflight
        self.queue_cap = queue_cap
        self.pre_encoded = pre_encoded
        # Fairness: one client key may hold at most this fraction of
        # the admission queue (0 = off). Only requests that CARRY a
        # client key are capped; anonymous traffic sees the global
        # bound alone.
        self.client_share = client_share
        self._client_cap = max(1, int(queue_cap * client_share)) \
            if client_share > 0 else 0
        self._client_pending: Dict[str, int] = {}
        self.stats = stats or ServingStats()

        self._cond = threading.Condition()
        self._queue: "collections.deque[_Request]" = collections.deque()
        self._pending_queries = 0
        # Inter-arrival EWMA (seconds between submits) — the adaptive
        # window's load signal. None until two arrivals happened.
        self._dt_ewma: Optional[float] = None
        self._last_arrival: Optional[float] = None
        self._inflight_sem = threading.Semaphore(max_inflight)
        self._inflight = 0  # gauge only; _inflight_sem is the limiter
        self._inflight_lock = threading.Lock()
        # Scattered-but-ungathered super-batches, completed in dispatch
        # order: (finisher, [requests]). Unbounded by construction —
        # the semaphore above already caps how much lands here.
        self._completions: "collections.deque" = collections.deque()
        self._completions_cond = threading.Condition()
        # The batch the gather thread is currently blocked on (guarded
        # by _completions_cond): stop() must be able to fail its
        # requests promptly instead of leaving them to the gather
        # timeout.
        self._gathering: Optional[List[_Request]] = None
        self._stop = threading.Event()
        self._batcher = threading.Thread(
            target=self._batch_loop, name="micro-batcher", daemon=True)
        self._gatherer = threading.Thread(
            target=self._gather_loop, name="micro-gather", daemon=True)
        self._started = False

    # --- Lifecycle ---

    def start(self) -> "MicroBatcher":
        with self._cond:  # idempotent under concurrent first submits
            if self._started:
                return self
            self._started = True
        self._batcher.start()
        self._gatherer.start()
        return self

    def stop(self, join_timeout: float = 10.0) -> None:
        self._stop.set()
        with self._cond:
            self._cond.notify_all()
        with self._completions_cond:
            self._completions_cond.notify_all()
        for t in (self._batcher, self._gatherer):
            if t.is_alive():
                t.join(timeout=join_timeout)
        # Fail whatever is still queued — AND any super-batch the
        # batcher scattered after the gather thread already exited — so
        # no handler thread hangs on a dead batcher.
        with self._cond:
            stranded = list(self._queue)
            self._queue.clear()
            self._pending_queries = 0
            self._client_pending.clear()
        with self._completions_cond:
            stranded.extend(req for _, batch in self._completions
                            for req in batch)
            self._completions.clear()
            # The in-gather batch may stay blocked on worker replies for
            # the remaining gather timeout; its callers must not. A late
            # finisher return then resolves already-failed requests,
            # which is harmless (their waiters are gone).
            if self._gathering:
                stranded.extend(self._gathering)
        for req in stranded:
            req.fail(RuntimeError("micro-batcher stopped"))

    # --- Caller side ---

    def submit(self, queries: List[Any],
               timeout: Optional[float] = None,
               client: Optional[str] = None,
               tenant: Optional[str] = None,
               record: Optional[Dict[str, Any]] = None) -> List[Any]:
        """Enqueue one request's queries; block until its slice of the
        super-batch results is ready. Raises :class:`Backpressure` when
        the admission queue is full — or, with fairness on, when
        ``client``'s share of it is (the caller maps it to HTTP 429).
        ``tenant`` is the hashed attribution key riding into the bus
        envelope (None = unattributed); ``record`` is the workload
        recorder's open request record (None = recorder off)."""
        # rta: disable=RTA101 unlocked fast-path peek; start() re-checks under _cond
        if not self._started:
            self.start()
        n = len(queries)
        if n == 0:
            return []
        if self._client_cap == 0:
            client = None
        req = _Request(queries, client=client, tenant=tenant,
                       record=record)
        with self._cond:
            # Checked under the lock: a request admitted after stop()'s
            # queue drain would sit in a queue no thread reads, blocking
            # its handler for the full timeout.
            if self._stop.is_set():
                raise RuntimeError("micro-batcher stopped")
            now = time.monotonic()
            if self._last_arrival is not None:
                # Clamp the gap: any dt beyond the ceiling already
                # means "window = floor", and an unclamped idle gap
                # (minutes) would poison the EWMA so badly that the
                # first ~dozens of a post-idle burst get no window.
                # At 2x the ceiling, a burst re-opens the window
                # within ~5 arrivals.
                dt = min(now - self._last_arrival,
                         2.0 * self.fill_window_max)
                self._dt_ewma = (dt if self._dt_ewma is None else
                                 _ARRIVAL_ALPHA * dt +
                                 (1.0 - _ARRIVAL_ALPHA) * self._dt_ewma)
            self._last_arrival = now
            # A request larger than the whole cap is only admitted when
            # the queue is empty (otherwise it could never be served);
            # everything else bounces as soon as the bound is crossed.
            if self._pending_queries > 0 and \
                    self._pending_queries + n > self.queue_cap:
                self.stats.backpressured()
                raise Backpressure(self._retry_after(),
                                   self._pending_queries, self.queue_cap)
            if client is not None:
                held = self._client_pending.get(client, 0)
                # A single client's first over-cap request is admitted
                # when it holds nothing (mirror of the global oversized
                # rule: it could never be served otherwise).
                if held > 0 and held + n > self._client_cap:
                    self.stats.backpressured(reason="client_share")
                    raise Backpressure(self._retry_after(), held,
                                       self._client_cap,
                                       reason="client_share")
                self._client_pending[client] = held + n
            self._queue.append(req)
            self._pending_queries += n
            self.stats.admitted(n)
            self.stats.set_queue_depth(self._pending_queries)
            self._cond.notify_all()
        if not req.event.wait(timeout):
            raise TimeoutError(
                f"micro-batched predict did not complete in {timeout}s")
        if req.error is not None:
            raise req.error
        return req.result if req.result is not None else []

    def _retry_after(self) -> float:
        """Advisory drain estimate for the 429 ``Retry-After`` header:
        a full queue is ~(cap / max_batch) super-batches, each at least
        one fill window deep. Clamped to whole seconds >= 1 (the header
        is integer seconds)."""
        batches = max(1.0, self.queue_cap / self.max_batch)
        return max(1.0, math.ceil(batches * max(self.fill_window, 0.05)))

    # --- Batcher thread: fill + scatter ---

    def current_fill_window(self) -> float:
        """The load-adaptive fill window: with arrivals slower than the
        ceiling, waiting can't coalesce anything — the window collapses
        to the floor; as the inter-arrival EWMA tightens, the window
        opens toward the ceiling (``max - ewma``, clamped), where one
        window holds many requests. Reading ``_dt_ewma`` races benignly
        with submit (a float read; a stale value sizes ONE window)."""
        lo, hi = self.fill_window_min, self.fill_window_max
        if lo >= hi:
            return lo  # pinned: fixed-window mode
        # rta: disable=RTA101 benign torn read (docstring): stale float sizes ONE window
        dt = self._dt_ewma
        if dt is None:
            return lo
        return min(hi, max(lo, hi - dt))

    def _drain_into(self, batch: List[_Request], total: int) -> int:
        """Pop whole queued requests into ``batch`` while they fit under
        the super-batch query cap (an oversized request is admitted
        only as the FIRST of a batch); returns the new query total.
        Caller holds ``self._cond``."""
        while self._queue and total < self.max_batch:
            nxt = len(self._queue[0].queries)
            if batch and total + nxt > self.max_batch:
                break
            req = self._queue.popleft()
            self._pending_queries -= nxt
            if req.client is not None:
                held = self._client_pending.get(req.client, 0) - nxt
                if held > 0:
                    self._client_pending[req.client] = held
                else:
                    self._client_pending.pop(req.client, None)
            batch.append(req)
            total += nxt
        self.stats.set_queue_depth(self._pending_queries)
        return total

    def _take_batch(self):
        """Block for the first request, then keep filling until the
        (adaptive) fill window closes or the query cap is hit. Returns
        ``(batch, t_first, window)`` where ``t_first`` is when filling
        began — idle time spent waiting for the first request is not
        fill time — and ``window`` is the adaptive window this batch
        filled under (recorded for observability)."""
        batch: List[_Request] = []
        total = 0
        with self._cond:
            while not self._queue:
                if self._stop.is_set():
                    return batch, time.monotonic(), 0.0
                self._cond.wait(0.1)
            t_first = time.monotonic()
            window = self.current_fill_window()
            deadline = t_first + window
            while True:
                total = self._drain_into(batch, total)
                remaining = deadline - time.monotonic()
                if total >= self.max_batch or remaining <= 0 \
                        or self._stop.is_set():
                    break
                self._cond.wait(remaining)
        return batch, t_first, window

    def _top_up(self, batch: List[_Request]) -> None:
        """After waiting for an in-flight slot, absorb whatever queued
        up meanwhile (still under the query cap) — under overload the
        slot wait IS the fill window, so coalescing scales with load."""
        with self._cond:
            self._drain_into(batch, sum(len(r.queries) for r in batch))

    def _batch_loop(self) -> None:
        while not self._stop.is_set():
            batch, t0, window = self._take_batch()
            if not batch:
                continue
            # Wait for an in-flight slot (keep-N-in-flight), topping the
            # batch up with anything that arrived during the wait.
            while not self._inflight_sem.acquire(timeout=0.5):
                if self._stop.is_set():
                    for req in batch:
                        req.fail(RuntimeError("micro-batcher stopped"))
                    return
            self._top_up(batch)
            now = time.monotonic()
            fill_s = now - t0
            flat: List[Any] = []
            ctxs: List[Any] = []
            tenants: dict = {}
            queue_wait_s = 0.0
            for req in batch:
                flat.extend(req.queries)
                if req.trace is not None:
                    ctxs.append(req.trace)
                # Summed per-request admission wait — the queue-time
                # signal the attribution ledger charges per bin.
                queue_wait_s += max(0.0, now - req.t_admit)
                if req.record is not None:
                    workload.note_queue_wait(
                        req.record, max(0.0, now - req.t_admit))
                if req.tenant:
                    tenants[req.tenant] = (tenants.get(req.tenant, 0)
                                           + len(req.queries))
            t1 = time.monotonic()
            wall = time.time()
            tenant_rows = None
            if tenants:
                # Per-query tenant column (only when someone in the
                # batch IS attributed): the tiered escalation scatter
                # needs per-index tenants to attribute its subset —
                # the batch-level mix alone cannot be sliced.
                tenant_rows = []
                for req in batch:
                    tenant_rows.extend([req.tenant] * len(req.queries))
            try:
                finisher = self.predictor.predict_submit(
                    flat, pre_encoded=self.pre_encoded,
                    trace_ctxs=ctxs,
                    tenants=sorted(tenants.items()) or None,
                    tenant_rows=tenant_rows,
                    queue_wait_s=queue_wait_s)
            except BaseException as e:  # noqa: BLE001 - forwarded to callers
                self._inflight_sem.release()
                for req in batch:
                    req.fail(e)
                continue
            scatter_s = time.monotonic() - t1
            if ctxs:
                trace.record_event(
                    "predictor.scatter", self.stats.service, ctxs, wall,
                    scatter_s, attrs={"requests": len(batch),
                                      "queries": len(flat),
                                      "fill_ms": round(fill_s * 1e3, 3)})
            with self._inflight_lock:
                self._inflight += 1
                inflight = self._inflight
            self.stats.dispatched(len(batch), len(flat), fill_s,
                                  scatter_s, inflight=inflight,
                                  fill_window=window)
            with self._completions_cond:
                self._completions.append((finisher, batch))
                self._completions_cond.notify_all()

    # --- Gather thread: finish + slice ---

    def _gather_loop(self) -> None:
        while True:
            with self._completions_cond:
                while not self._completions:
                    if self._stop.is_set():
                        return
                    self._completions_cond.wait(0.1)
                finisher, batch = self._completions.popleft()
                self._gathering = batch
            t0 = time.monotonic()
            wall = time.time()
            results = error = None
            try:
                results = finisher()
            except BaseException as e:  # noqa: BLE001 - forwarded to callers
                error = e
            finally:
                gather_s = time.monotonic() - t0
                with self._inflight_lock:
                    self._inflight -= 1
                    inflight = self._inflight
                self._inflight_sem.release()
                self.stats.gathered(gather_s, inflight=inflight)
                ctxs = [r.trace for r in batch if r.trace is not None]
                if ctxs:
                    trace.record_event("predictor.gather",
                                       self.stats.service, ctxs, wall,
                                       gather_s,
                                       attrs={"error": error is not None})
            offset = 0
            for req in batch:
                if error is not None:
                    req.fail(error)
                    continue
                n = len(req.queries)
                req.resolve(results[offset:offset + n])
                offset += n
            with self._completions_cond:
                self._gathering = None
