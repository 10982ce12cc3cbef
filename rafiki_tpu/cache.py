"""Cache: the serving data plane's queue conventions over the bus.

Parity: SURVEY.md §2 "Cache / queues" + §3.3 — upstream's Redis wrapper
gives the Predictor per-worker query queues, prediction return queues, and
a running-worker registry. Same contract here over ``rafiki_tpu.bus``:

- queries:   ``q:{worker_id}``          (Predictor → one InferenceWorker)
- replies:   ``r:{query_id}``           (workers → the waiting Predictor)
- registry:  ``w:{inference_job_id}:{worker_id}`` → worker info (kv)

Numpy query payloads (images) are framed as base64 so the bus stays
JSON-only; tensors at scale never ride the bus — InferenceWorkers decode
once and batch onto the chip themselves.

**Packed batch frames** (``__ndbatch__``, r13): when every query in a
shard is a same-shape/same-dtype tensor, the shard rides ONE contiguous
buffer + a shape/dtype/offsets header instead of N per-query ``__nd__``
frames — the predictor pays one base64 encode per shard, the worker one
decode per shard (a single ``np.frombuffer`` view), and the per-query
framing overhead disappears from the wire. Emission is NEGOTIATED: a
worker advertises ``"wire": ["ndbatch1"]`` in its bus registration and
only advertised workers receive packed frames (old workers keep the
per-query format; new workers accept both), so mixed fleets and rolling
promotes stay safe. ``rafiki_tpu_serving_wire_bytes_total`` and
``.._host_copies_total`` (``observe.wire``) account both formats.

Query frames additionally carry the requests' trace contexts under a
``"_trace"`` envelope key (``observe.trace``): senders inject the
explicit contexts a micro-batcher collected, or the calling thread's
ambient context on the direct path. Old frames simply lack the key and
old consumers ignore it — version skew in either direction degrades to
"no trace", never a failed query.
"""

from __future__ import annotations

import base64
import binascii
import math
import threading
import uuid
from typing import Any, Collection, Dict, List, Optional

import numpy as np

from .bus import BaseBus
from .observe import attribution as _attr
from .observe import trace as _trace
from .observe import wire as _wire

#: Negotiation token for the packed batch-tensor wire format. A worker
#: listing it under ``"wire"`` in its registration accepts ``"batch"``
#: frames; the version suffix means a future layout ships as ndbatch2
#: alongside, never as a silent change of this one.
WIRE_NDBATCH = "ndbatch1"

#: Upper bound on the per-query error replies a CORRUPT packed frame's
#: (untrusted) header can demand — far above any real shard, far below
#: an allocation attack.
_CORRUPT_REPLY_CAP = 4096

#: Graceful-drain marker frame key (ServicesManager.
#: drain_inference_worker): a worker popping a frame with this key
#: finishes the burst in hand and exits its serve loop cleanly.
DRAIN_KEY = "__drain__"

#: Promote-path restack marker frame key (Admin.promote_trial on a
#: stacked multi-member bin): the worker swaps ONE served member in
#: place — queue-ordered like the drain marker, so everything enqueued
#: before it serves from the old member set.
RESTACK_KEY = "__restack__"

#: On-demand profiling marker frame key (Admin.profile_inference_job):
#: the worker starts a bounded jax.profiler session between bursts —
#: queue-ordered like drain/restack, so the session observes real
#: serving traffic without ever pausing it.
PROFILE_KEY = "__profile__"


def encode_payload(value: Any) -> Any:
    """JSON-safe encoding; numpy arrays → base64 frames."""
    if isinstance(value, np.ndarray):
        return {"__nd__": base64.b64encode(
                    np.ascontiguousarray(value).tobytes()).decode(),
                "dtype": str(value.dtype), "shape": list(value.shape)}
    if isinstance(value, (list, tuple)):
        return [encode_payload(v) for v in value]
    if isinstance(value, dict):
        return {k: encode_payload(v) for k, v in value.items()}
    if isinstance(value, (np.integer, np.floating)):
        return value.item()
    return value


class PackedBatch:
    """A super-batch of same-shape tensors as ONE contiguous buffer.

    Built once at the predictor edge (the micro-batcher's coalesced
    super-batch assembles straight into it); ``slice`` cuts per-shard
    wire frames out of it with one base64 encode each — no per-query
    frames, no per-worker re-encode. Rows are C-contiguous, so a
    leading-dim slice is itself contiguous and ``tobytes`` is a single
    memcpy.
    """

    __slots__ = ("data",)

    def __init__(self, data: np.ndarray):
        self.data = data  # (n, *query_shape), C-contiguous

    @property
    def n(self) -> int:
        return int(self.data.shape[0])

    @classmethod
    def from_arrays(cls, arrays: List[Any]) -> Optional["PackedBatch"]:
        """Pack a list of ndarrays, or None when they are not packable
        (mixed shapes/dtypes, non-numeric, empty). Non-contiguous
        inputs are fine — the row assignment linearizes them. The
        per-row fills are counted as ``assemble`` copies so the packed
        side's evidence is symmetric with the legacy ``stack`` count
        (a gate passing by instrumentation gap would be no gate)."""
        if not arrays:
            return None
        first = arrays[0]
        if not isinstance(first, np.ndarray) or first.dtype.hasobject \
                or first.dtype.itemsize == 0:
            return None
        shape, dtype = first.shape, first.dtype
        for a in arrays[1:]:
            if not isinstance(a, np.ndarray) or a.shape != shape \
                    or a.dtype != dtype:
                return None
        buf = np.empty((len(arrays), *shape), dtype)
        for i, a in enumerate(arrays):
            buf[i] = a
        _wire.count_copies("assemble", len(arrays))
        return cls(buf)

    @classmethod
    def from_encoded(cls, encoded: List[Any]) -> Optional["PackedBatch"]:
        """Pack a list of per-query ``__nd__`` wire frames (the HTTP
        hot path: clients ship frames, the predictor re-packs them once
        per super-batch), or None when they are not all same-shape
        tensor frames. Pays one base64 decode per query HERE so every
        downstream worker pays one per SHARD instead of one per query
        (counted as ``site="decode"`` host copies)."""
        if not encoded:
            return None
        first = encoded[0]
        if not isinstance(first, dict) or "__nd__" not in first:
            return None
        try:
            dtype = np.dtype(first["dtype"])
            shape = tuple(int(x) for x in first["shape"])
        except (KeyError, TypeError, ValueError):
            return None
        if dtype.hasobject or dtype.itemsize == 0 \
                or any(s < 0 for s in shape):
            return None
        per = dtype.itemsize * int(math.prod(shape))
        # The shape header is UNTRUSTED client input: the batch buffer
        # is allocated only after the first payload's decoded length
        # vouches for it (a frame claiming shape [1e12] over a 1-byte
        # payload must be refused, not allocated).
        buf = None
        for i, q in enumerate(encoded):
            if not isinstance(q, dict) or "__nd__" not in q:
                return None
            if q is not first and (
                    q.get("dtype") != first["dtype"]
                    or list(q.get("shape") or ()) != list(first["shape"])):
                return None
            try:
                raw = base64.b64decode(q["__nd__"])
            except (TypeError, binascii.Error):
                return None
            if len(raw) != per:
                return None
            if buf is None:
                buf = np.empty((len(encoded), *shape), dtype)
            buf[i] = np.frombuffer(raw, dtype=dtype).reshape(shape)
        _wire.count_copies("decode", len(encoded))
        return cls(buf)

    def slice(self, start: int, count: int) -> Dict[str, Any]:
        """One shard's wire frame: header + a single base64 encode of
        the contiguous row range (counted as one ``encode`` copy — vs
        ``count`` of them on the per-query format)."""
        rows = self.data[start:start + count]
        per = int(self.data.dtype.itemsize
                  * math.prod(self.data.shape[1:]))
        _wire.count_copies("encode", 1)
        return {"__ndbatch__": base64.b64encode(rows.tobytes()).decode(),
                "v": 1,
                "dtype": str(self.data.dtype),
                "shape": list(self.data.shape[1:]),
                "n": count,
                "offsets": [i * per for i in range(count)]}

    def take(self, indices: List[int]) -> "PackedBatch":
        """Row-gathered sub-batch (the tiered path's escalation subset
        re-packs without touching per-query frames)."""
        return PackedBatch(np.ascontiguousarray(self.data[indices]))


def pack_prediction_rows(predictions: List[Any],
                         ) -> Optional[Dict[str, Any]]:
    """One reply batch's dense prediction vectors as a single
    ``__ndbatch__`` frame (the reply-direction packed wire, r14), or
    None when the batch is not packable — mixed shapes, error dicts,
    ``__members__`` envelopes, non-float outputs. Only 1-D FLOAT
    vectors (class probabilities, the dominant dense reply) pack:
    label/score outputs keep the per-query format so the ensemble's
    majority-vote equality semantics never see a type change."""
    if len(predictions) < 2:
        return None
    rows: List[np.ndarray] = []
    shape = dtype = None
    for p in predictions:
        if isinstance(p, np.ndarray):
            a = p
        elif isinstance(p, (list, tuple)) and len(p) >= 2:
            try:
                a = np.asarray(p)
            except (ValueError, TypeError):
                return None
        else:
            return None
        if a.ndim != 1 or a.shape[0] < 2 or a.dtype.kind != "f":
            return None
        if shape is None:
            shape, dtype = a.shape, a.dtype
        elif a.shape != shape or a.dtype != dtype:
            return None
        rows.append(a)
    packed = PackedBatch.from_arrays(rows)
    return packed.slice(0, packed.n) if packed is not None else None


def decode_batch(value: Dict[str, Any]) -> np.ndarray:
    """Strict decode of one ``__ndbatch__`` frame into an ``(n,
    *shape)`` array — ONE base64 decode + ONE ``np.frombuffer`` view
    (read-only; the worker copies rows into its reusable staging
    buffer). Raises ``ValueError`` on any header/payload disagreement:
    a truncated or corrupt frame must be rejected loudly, never served
    as silently wrong tensors."""
    if not isinstance(value, dict) or "__ndbatch__" not in value:
        raise ValueError("not a packed batch frame")
    if value.get("v") != 1:
        raise ValueError(f"unsupported packed-frame version "
                         f"{value.get('v')!r}")
    try:
        dtype = np.dtype(value["dtype"])
        shape = tuple(int(x) for x in value["shape"])
        n = int(value["n"])
    except (KeyError, TypeError, ValueError) as e:
        raise ValueError(f"malformed packed-frame header: {e}") from None
    if n < 0 or any(s < 0 for s in shape) or dtype.hasobject:
        raise ValueError("malformed packed-frame header")
    per = dtype.itemsize * int(math.prod(shape))
    offsets = value.get("offsets")
    if offsets is not None:
        # KeyError/IndexError included: a dict or short sequence here
        # (corrupt producer) must land in the ValueError contract, not
        # escape through the worker's serve loop.
        try:
            bad = len(offsets) != n or any(
                int(offsets[i]) != i * per for i in range(n))
        except (TypeError, ValueError, KeyError, IndexError):
            bad = True
        if bad:
            raise ValueError("packed-frame offsets disagree with the "
                             "shape/dtype header")
    try:
        raw = base64.b64decode(value["__ndbatch__"], validate=True)
    except (TypeError, binascii.Error) as e:
        raise ValueError(f"corrupt packed payload: {e}") from None
    if len(raw) != n * per:
        raise ValueError(
            f"packed payload is {len(raw)} bytes; header claims "
            f"{n} x {per}")
    return np.frombuffer(raw, dtype=dtype).reshape((n, *shape))


def _payload_nbytes(value: Any) -> int:
    """Cheap serialized-size ESTIMATE of a wire payload (b64 length +
    nominal per-frame framing overhead) for the wire-bytes counter —
    computed without re-serializing the frame, and only when the
    counter family is live."""
    if isinstance(value, dict):
        s = value.get("__nd__")
        if isinstance(s, str):
            return len(s) + 48  # dtype/shape keys + quoting
        s = value.get("__ndbatch__")
        if isinstance(s, str):
            return (len(s) + 64
                    + 12 * int(value.get("n", 0) or 0))  # offsets
        return 32 + sum(_payload_nbytes(v) for v in value.values())
    if isinstance(value, (list, tuple)):
        return 2 + sum(_payload_nbytes(v) for v in value)
    if isinstance(value, str):
        return len(value) + 2
    # A JSON float serializes to ~17-19 chars (repr round-trip); the
    # old flat 8 under-counted per-query float-list replies so badly
    # that the packed reply frame "lost" on bytes it actually wins.
    if isinstance(value, float):
        return 18
    return 8


def _trace_envelope(trace_ctxs: Optional[List] = None) -> Optional[Dict]:
    """The ``_trace`` field for an outgoing query frame: the explicit
    contexts when given (micro-batcher scatter), else the calling
    thread's ambient context (direct predict path), else None (the
    frame stays byte-identical to a pre-trace frame)."""
    if trace_ctxs is None:
        cur = _trace.current()
        trace_ctxs = [cur] if cur is not None else []
    return _trace.inject(trace_ctxs)


def decode_payload(value: Any) -> Any:
    if isinstance(value, dict):
        if "__nd__" in value:
            arr = np.frombuffer(base64.b64decode(value["__nd__"]),
                                dtype=np.dtype(value["dtype"]))
            return arr.reshape(value["shape"]).copy()
        return {k: decode_payload(v) for k, v in value.items()}
    if isinstance(value, list):
        return [decode_payload(v) for v in value]
    return value


class Cache:
    # A reply landing after its gather timed out (and deleted the queue)
    # recreates the queue with nobody left to pop it; deferred reaping
    # sweeps those orphans on later gather calls.
    _REAP_DELAY = 60.0

    def __init__(self, bus: BaseBus):
        self.bus = bus
        self._reap_later: List[tuple] = []  # (monotonic_ts, queue_key)
        # One Cache is shared by every handler thread of a predictor
        # frontend (and by the micro-batcher's scatter/gather threads);
        # the deferred-reap list is the only mutable state.
        self._reap_lock = threading.Lock()
        # Reply-direction packed wire (r14), construction-time snapshot
        # like every other packed-mode read. "on" makes batch QUERY
        # frames advertise `"rw": ["ndbatch1"]` — the worker may then
        # answer with ONE packed reply frame instead of per-query
        # payloads — and makes batch REPLIES from this side pack when
        # the query advertised. Old predictors never set "rw", so a new
        # worker never packs toward them; old workers ignore the key.
        self._packed_wire_on = _wire.packed_wire_mode() == "on"

    def _reap_stale(self, now: float) -> None:
        with self._reap_lock:
            due = [key for ts, key in self._reap_later
                   if now - ts >= self._REAP_DELAY]
            self._reap_later = [(ts, key) for ts, key in self._reap_later
                                if now - ts < self._REAP_DELAY]
        for key in due:
            self.bus.delete_queue(key)

    def _gather(self, queue_key: str, n_workers: int, timeout: float,
                decode: Any, reap: bool = True,
                timestamps: bool = False) -> List[Dict[str, Any]]:
        """Pop up to ``n_workers`` replies off a one-shot reply queue,
        then reap it; stragglers are swept by deferred reaping.

        ``reap=False`` leaves the queue alive — the sharded gather
        calls again after resubmitting missing shards to sibling
        replicas, and a delete between rounds could race away a reply
        already in flight. ``timestamps=True`` stamps each reply with
        ``"_recv_mono"`` (monotonic pop time) so the caller can feed
        per-replica latency tracking without re-timing the pops."""
        import time

        now = time.monotonic()
        self._reap_stale(now)
        out: List[Dict[str, Any]] = []
        deadline = now + timeout
        while len(out) < n_workers:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            item = self.bus.pop(queue_key, timeout=remaining)
            if item is None:
                break
            item = decode(item)
            if item is None:
                continue  # decoder rejected it (corrupt packed reply)
            if timestamps:
                item["_recv_mono"] = time.monotonic()
            out.append(item)
        if not reap:
            return out
        self.bus.delete_queue(queue_key)
        if len(out) < n_workers:
            with self._reap_lock:
                self._reap_later.append((time.monotonic(), queue_key))
        return out

    # --- Worker registry ---

    def register_worker(self, inference_job_id: str, worker_id: str,
                        info: Optional[Dict[str, Any]] = None) -> None:
        self.bus.set(f"w:{inference_job_id}:{worker_id}", info or {})

    def unregister_worker(self, inference_job_id: str,
                          worker_id: str) -> None:
        self.bus.delete(f"w:{inference_job_id}:{worker_id}")

    def running_workers(self, inference_job_id: str) -> List[str]:
        prefix = f"w:{inference_job_id}:"
        return [k[len(prefix):] for k in self.bus.keys(prefix)]

    def running_worker_info(self, inference_job_id: str,
                            ) -> Dict[str, Dict[str, Any]]:
        """worker_id -> registration info (e.g. the trial bin it
        serves); the Predictor groups replicas of the same bin by it."""
        prefix = f"w:{inference_job_id}:"
        out: Dict[str, Dict[str, Any]] = {}
        for k in self.bus.keys(prefix):
            out[k[len(prefix):]] = self.bus.get(k) or {}
        return out

    # --- Frontend registry (cluster cache fabric; docs/cluster.md) ---
    #
    # Predictor frontends of one job register their HTTP address under
    # ``f:{job}:{instance}`` so peers can probe each other's edge cache
    # and the admin's promotion invalidate can fan out to ALL of them.
    # Written only when the cluster fabric is on — a single-node deploy
    # never creates these keys.

    def register_frontend(self, inference_job_id: str, instance: str,
                          addr: str) -> None:
        self.bus.set(f"f:{inference_job_id}:{instance}", addr)

    def unregister_frontend(self, inference_job_id: str,
                            instance: str) -> None:
        self.bus.delete(f"f:{inference_job_id}:{instance}")

    def frontends(self, inference_job_id: str) -> Dict[str, str]:
        """instance -> HTTP addr of every registered frontend."""
        prefix = f"f:{inference_job_id}:"
        out: Dict[str, str] = {}
        for k in self.bus.keys(prefix):
            addr = self.bus.get(k)
            if addr:
                out[k[len(prefix):]] = str(addr)
        return out

    # --- Queries (Predictor side) ---

    def send_query(self, worker_id: str, query: Any,
                   query_id: Optional[str] = None) -> str:
        query_id = query_id or uuid.uuid4().hex
        frame = {"query_id": query_id, "query": encode_payload(query)}
        env = _trace_envelope()
        if env is not None:
            frame[_trace.ENVELOPE_KEY] = env
        self.bus.push(f"q:{worker_id}", frame)
        return query_id

    def gather_predictions(self, query_id: str, n_workers: int,
                           timeout: float = 5.0) -> List[Dict[str, Any]]:
        """Collect up to ``n_workers`` worker replies for one query."""
        def decode(item):
            item["prediction"] = decode_payload(item["prediction"])
            return item

        return self._gather(f"r:{query_id}", n_workers, timeout, decode)

    # --- Query batches (Predictor side) ---
    #
    # One message per (request, worker) instead of one per (query,
    # worker): the serving QPS ceiling is bus round-trips, not chip
    # compute, so the scatter/gather rides batch-granular frames.

    def send_query_batch_fanout(self, worker_ids: List[str],
                                encoded_queries: Optional[List[Any]],
                                batch_id: Optional[str] = None,
                                trace_ctxs: Optional[List] = None,
                                packed: Optional[PackedBatch] = None,
                                packed_ok: Collection[str] = (),
                                tenants: Optional[List] = None,
                                ) -> str:
        """Scatter ONE pre-encoded batch to every worker in one bus
        call (``push_many``). The encoded payload list is SHARED across
        the per-worker frames — encode once, serialize per queue, no
        per-worker deep copies; only the outer frame dict is fresh per
        worker (consumers decode by *replacing* the ``queries`` key, so
        the shared list itself is never mutated). ``trace_ctxs`` are
        the coalesced requests' trace contexts (the shared ``_trace``
        envelope rides every per-worker frame).

        ``packed`` + ``packed_ok``: workers in ``packed_ok`` (their
        registration advertises :data:`WIRE_NDBATCH`) receive the whole
        batch as ONE shared packed ``"batch"`` frame — encoded once for
        the entire fanout; the rest keep the per-query list.
        ``encoded_queries`` may be None only when every worker is in
        ``packed_ok``. ``tenants`` is the coalesced requests' tenant
        mix (``[(tenant_hash, n_queries), ...]``) — it rides every
        per-worker frame under the ``_tenant`` envelope key, exactly
        like the trace carry."""
        batch_id = batch_id or uuid.uuid4().hex
        env = _trace_envelope(trace_ctxs)
        tenant_env = _attr.inject_tenants(tenants)
        counting = _wire.counting()
        packed_frame = None
        if packed is not None and any(w in packed_ok
                                      for w in worker_ids):
            packed_frame = packed.slice(0, packed.n)
        frames = []
        for w in worker_ids:
            frame: Dict[str, Any] = {"batch_id": batch_id}
            if self._packed_wire_on:
                frame["rw"] = [WIRE_NDBATCH]
            if packed_frame is not None and w in packed_ok:
                frame["batch"] = packed_frame
                if counting:
                    _wire.count_bytes("packed", "scatter",
                                      _payload_nbytes(packed_frame))
            else:
                frame["queries"] = encoded_queries
                if counting:
                    _wire.count_bytes("perquery", "scatter",
                                      _payload_nbytes(encoded_queries))
            if env is not None:
                frame[_trace.ENVELOPE_KEY] = env
            if tenant_env is not None:
                frame[_attr.ENVELOPE_KEY] = tenant_env
            frames.append((f"q:{w}", frame))
        self.bus.push_many(frames)
        return batch_id

    def send_query_shards(self, shards: List[tuple],
                          encoded_queries: Optional[List[Any]],
                          batch_id: Optional[str] = None,
                          trace_ctxs: Optional[List] = None,
                          packed: Optional[PackedBatch] = None,
                          packed_ok: Collection[str] = (),
                          tenants: Optional[List] = None,
                          worker_nodes: Optional[Dict[str, str]] = None,
                          local_node: str = "") -> str:
        """Scatter per-SHARD slices of one pre-encoded batch — the
        data-parallel fanout behind ``Predictor``'s replica sharding.

        ``shards`` is ``[(worker_id, start, count, shard_id), ...]``;
        each frame carries its slice of the shared encoded list (a
        shallow slice — payload objects are shared, never copied) plus
        a ``"shard"`` id the worker echoes back in its reply so the
        gatherer can match replies to plan entries even when a
        resubmitted shard lands on a worker that already served its own
        (old workers simply don't echo; the gatherer falls back to
        matching by worker id). A full-batch shard reuses the shared
        list itself. One ``push_many`` round-trip for the whole plan,
        exactly like the unsharded fanout.

        With ``packed`` given, shards bound for a worker in
        ``packed_ok`` carry their slice as one contiguous ``"batch"``
        frame instead (one base64 encode per shard); other shards keep
        the per-query list — the same plan may mix both formats, which
        is exactly the rolling-promote / mixed-fleet case.
        ``encoded_queries`` may be None only when every planned worker
        is packed-capable (the caller materializes per-query frames
        lazily otherwise). ``tenants`` (the batch-level tenant mix)
        rides each shard frame SCALED to the shard's slice of the
        batch, so a worker prorating its burst's device time over the
        frame's counts attributes one shard's worth, not the whole
        batch's.

        ``worker_nodes`` + ``local_node`` (docs/cluster.md): with a
        node map given, shards bound for a worker REGISTERED ON ANOTHER
        NODE are grouped per node and forwarded through the bus relay
        (one ``relay_push_many`` — one inter-node hop — per remote
        node), stamped with ``"onode"`` so the worker relays its reply
        back to this node's broker. Local/unknown-node shards keep the
        plain ``push_many``. Default None = byte-identical single-node
        behavior."""
        batch_id = batch_id or uuid.uuid4().hex
        env = _trace_envelope(trace_ctxs)
        n = packed.n if packed is not None else len(encoded_queries)
        counting = _wire.counting()
        frames = []
        remote: Dict[str, List[tuple]] = {}
        for worker_id, start, count, shard_id in shards:
            frame: Dict[str, Any] = {"batch_id": batch_id,
                                     "shard": shard_id}
            wnode = (worker_nodes or {}).get(worker_id, "")
            if wnode and local_node and wnode != local_node:
                # Remote worker: route via its node's broker and tell
                # it where the reply queue lives.
                frame["onode"] = local_node
            if tenants:
                # FLOOR, no floor-of-one: a tenant whose scaled share
                # of this shard truncates to zero is simply
                # unattributed here (the under-report-never-fabricate
                # rule) — rounding up would let a shard frame carry
                # more attributed queries than it holds, and a
                # floor of one would charge a 1-query tenant a slice
                # of EVERY shard's device time.
                tenant_env = _attr.inject_tenants(
                    [(t, int(c * count / max(n, 1)))
                     for t, c in tenants])
                if tenant_env is not None:
                    frame[_attr.ENVELOPE_KEY] = tenant_env
            if self._packed_wire_on:
                frame["rw"] = [WIRE_NDBATCH]
            if packed is not None and worker_id in packed_ok:
                frame["batch"] = packed.slice(start, count)
                if counting:
                    _wire.count_bytes("packed", "scatter",
                                      _payload_nbytes(frame["batch"]))
            else:
                qs = (encoded_queries if start == 0 and count == n
                      else encoded_queries[start:start + count])
                frame["queries"] = qs
                if counting:
                    _wire.count_bytes("perquery", "scatter",
                                      _payload_nbytes(qs))
            if env is not None:
                frame[_trace.ENVELOPE_KEY] = env
            if "onode" in frame:
                remote.setdefault(wnode, []).append(
                    (f"q:{worker_id}", frame))
            else:
                frames.append((f"q:{worker_id}", frame))
        if frames:
            self.bus.push_many(frames)
        for wnode, items in remote.items():
            self.bus.relay_push_many(wnode, items)
        return batch_id

    def gather_prediction_batches(self, batch_id: str, n_workers: int,
                                  timeout: float = 5.0, reap: bool = True,
                                  timestamps: bool = False,
                                  ) -> List[Dict[str, Any]]:
        """Collect up to ``n_workers`` per-worker batch replies. A
        packed reply (``"batch"``, negotiated via the query frame's
        ``rw`` list) decodes with ONE base64+frombuffer into per-row
        float vectors; a corrupt packed reply is DROPPED outright (the
        decoder returns None and ``_gather`` skips it) so its shard
        reads as genuinely unanswered — attaching it with empty
        predictions would mark the shard answered, suppress the
        straggler resubmit, and could supersede a healthy in-flight
        retry."""
        def decode(item):
            if "batch" in item:
                try:
                    arr = decode_batch(item.pop("batch"))
                except ValueError:
                    import logging

                    logging.getLogger(__name__).warning(
                        "corrupt packed reply for batch %s dropped",
                        batch_id, exc_info=True)
                    return None
                item["predictions"] = [arr[i]
                                       for i in range(arr.shape[0])]
                _wire.count_copies("decode", 1)
            else:
                item["predictions"] = [decode_payload(p)
                                       for p in item["predictions"]]
            return item

        return self._gather(f"r:{batch_id}", n_workers, timeout, decode,
                            reap=reap, timestamps=timestamps)

    def reap_reply_queue(self, batch_id: str, defer: bool = True) -> None:
        """Finish a ``reap=False`` gather: delete the reply queue.
        ``defer=True`` additionally schedules the deferred sweep — for
        gathers that ended with stragglers or duplicate (resubmitted)
        shards still able to reply and recreate the queue."""
        import time

        self.bus.delete_queue(f"r:{batch_id}")
        if defer:
            with self._reap_lock:
                self._reap_later.append((time.monotonic(),
                                         f"r:{batch_id}"))

    # --- Graceful drain (ServicesManager.drain_inference_worker) ---

    def send_drain(self, worker_id: str) -> None:
        """Queue a drain marker: the worker serves everything enqueued
        BEFORE it, then exits its serve loop cleanly (unregistering on
        the way out). Ordering is the queue's — no side channel, so
        'let in-flight shards finish' is by construction."""
        self.bus.push(f"q:{worker_id}", {DRAIN_KEY: 1})

    def send_restack(self, worker_id: str, old_trial_id: str,
                     new_trial_id: str) -> None:
        """Queue a member-swap marker for a STACKED multi-member bin
        (the surgical promote path): the worker replaces
        ``old_trial_id``'s member with ``new_trial_id``'s in place —
        the other members stay device-resident — and re-registers with
        the updated bin. Queue ordering makes the cutover exact: every
        shard enqueued before the marker is answered by the old member
        set."""
        self.bus.push(f"q:{worker_id}", {RESTACK_KEY: {
            "old": str(old_trial_id), "new": str(new_trial_id)}})

    def send_profile(self, worker_id: str, out_dir: str,
                     duration_s: float) -> None:
        """Queue an on-demand profiling marker
        (``Admin.profile_inference_job``): the worker starts a bounded
        ``jax.profiler`` session into ``out_dir`` between bursts and
        its serve loop stops it once ``duration_s`` elapses — serving
        is never paused, the session just observes the bursts that run
        inside its window. A worker whose profiler is busy (a trial
        trace in flight) skips the request; old workers ignore the
        marker outright."""
        self.bus.push(f"q:{worker_id}", {PROFILE_KEY: {
            "dir": str(out_dir), "duration_s": float(duration_s)}})

    # --- Queries (InferenceWorker side) ---

    def pop_queries(self, worker_id: str, max_items: int = 0,
                    timeout: float = 1.0) -> List[Dict[str, Any]]:
        """Blocking batched pop: waits for the first item, drains the
        burst (the batched-TPU-inference pattern). Items are single
        queries (``query``), batches (``queries``), or packed batches
        (``batch`` → decoded to an ``(n, *shape)`` array view here, one
        base64 decode per shard). A corrupt packed frame is converted
        in place (``batch=None`` + ``batch_error`` + the header's ``n``
        best-effort) instead of raising — the worker answers it with
        per-query error dicts rather than dying on a bad producer."""
        items = self.bus.pop_all(f"q:{worker_id}", max_items=max_items,
                                 timeout=timeout)
        counting = _wire.counting()
        for it in items:
            if DRAIN_KEY in it or RESTACK_KEY in it or PROFILE_KEY in it:
                pass  # control marker; the worker's loop acts on it
            elif it.get("op") == "generate":
                pass  # token-level request; routed whole to the
                #      worker's decode scheduler (plain-JSON tokens,
                #      nothing to decode here)
            elif "batch" in it:
                raw = it["batch"]
                try:
                    it["batch"] = decode_batch(raw)
                    _wire.count_copies("decode", 1)
                except ValueError as e:
                    it["batch"] = None
                    it["batch_error"] = str(e)
                    # The header's n sizes the per-query error reply —
                    # CAPPED, because this header is by definition
                    # untrusted (a frame claiming n=1e9 must not make
                    # the error path allocate a billion error dicts;
                    # the gatherer only reads up to its shard's count
                    # anyway).
                    try:
                        it["n"] = max(0, min(int(raw.get("n", 0)),
                                             _CORRUPT_REPLY_CAP))
                    except (AttributeError, TypeError, ValueError):
                        it["n"] = 0
            elif "queries" in it:
                it["queries"] = [decode_payload(q) for q in it["queries"]]
                if counting:
                    _wire.count_copies("decode", sum(
                        1 for q in it["queries"]
                        if isinstance(q, np.ndarray)))
            else:
                it["query"] = decode_payload(it["query"])
        return items

    def send_prediction(self, query_id: str, worker_id: str,
                        prediction: Any, weight: int = 1) -> None:
        """``weight`` = how many ensemble members this worker's reply
        already averages (packed-ensemble workers report > 1 so the
        Predictor's cross-worker mean stays unweighted over trials)."""
        self.bus.push(f"r:{query_id}", {
            "worker_id": worker_id, "weight": int(weight),
            "prediction": encode_payload(prediction)})

    def send_prediction_batch(self, batch_id: str, worker_id: str,
                              predictions: List[Any], weight: int = 1,
                              shard: Optional[Any] = None,
                              confidence: Optional[List] = None,
                              compute_s: Optional[float] = None,
                              packed_ok: bool = False,
                              origin_node: Optional[str] = None) -> None:
        """``shard`` echoes the query frame's shard id (when the frame
        carried one) so a sharded gather can match this reply to its
        plan entry; un-sharded frames reply without the key, which is
        also what pre-shard workers produce. ``confidence`` (per-query
        softmax margins, None-padded) and ``compute_s`` (the worker's
        device seconds for this slice) feed the Predictor's tiered
        escalation and chip-seconds-avoided estimate; old workers omit
        both, old predictors ignore both — skew degrades to the
        pre-tier behavior, never a failed reply.

        ``packed_ok=True`` (the query frame advertised ``rw``) lets a
        dense reply ride ONE ``__ndbatch__`` frame — one base64 encode
        per reply batch instead of per-query payloads — gated on this
        side's own packed mode being "on" (compat/off keep per-query
        replies, the kill-switch story in both directions)."""
        frame: Dict[str, Any] = {"worker_id": worker_id,
                                 "weight": int(weight)}
        packed_frame = None
        if packed_ok and self._packed_wire_on:
            packed_frame = pack_prediction_rows(predictions)
        if packed_frame is not None:
            frame["batch"] = packed_frame
            if _wire.counting():
                _wire.count_bytes("packed", "reply",
                                  _payload_nbytes(packed_frame))
        else:
            frame["predictions"] = [encode_payload(p)
                                    for p in predictions]
            if _wire.counting():
                _wire.count_bytes("perquery", "reply",
                                  _payload_nbytes(frame["predictions"]))
        if shard is not None:
            frame["shard"] = shard
        if confidence is not None and any(c is not None
                                          for c in confidence):
            frame["confidence"] = confidence
        if compute_s is not None:
            frame["compute_s"] = compute_s
        if origin_node:
            # Cross-node shard (the query frame carried "onode"): the
            # reply queue lives on the ORIGIN node's broker — relay it
            # back (one hop; a single-broker topology degrades to the
            # local push via the relay fallback).
            self.bus.relay_push(origin_node, f"r:{batch_id}", frame)
        else:
            self.bus.push(f"r:{batch_id}", frame)

    # --- Generative serving (token streaming) ---
    #
    # A generate request is ONE frame on the worker's query queue
    # (op="generate"); the reply is MANY frames on the request's reply
    # queue — one per decode step that produced a token for this
    # sequence, each carrying a monotonically increasing "seq" index so
    # a consumer can detect loss/reordering, with the final frame
    # marked done=true (finish="eos"|"length"|"error"). Tokens are
    # plain ints end to end: no payload codec, the frames are small and
    # latency-bound, not bandwidth-bound.

    def send_generate(self, worker_id: str, tokens: List[int], *,
                      max_new: int, temperature: float = 0.0,
                      seed: int = 0, eos: Optional[int] = None,
                      query_id: Optional[str] = None) -> str:
        """Queue one token-generation request on ``worker_id``'s query
        queue; token frames stream back on ``r:{query_id}``."""
        query_id = query_id or uuid.uuid4().hex
        frame: Dict[str, Any] = {
            "query_id": query_id, "op": "generate",
            "gen": {"tokens": [int(t) for t in tokens],
                    "max_new": int(max_new),
                    "temperature": float(temperature),
                    "seed": int(seed),
                    "eos": int(eos) if eos is not None else None}}
        env = _trace_envelope()
        if env is not None:
            frame[_trace.ENVELOPE_KEY] = env
        self.bus.push(f"q:{worker_id}", frame)
        return query_id

    def send_token_frame(self, query_id: str, worker_id: str,
                         frame: Dict[str, Any]) -> None:
        """Push one token frame (worker side). ``frame`` carries
        ``seq``/``tok``/``done`` (+ ``finish``/``n_tokens``/``error``
        on the last one); the worker id rides along for debuggability,
        mirroring ``send_prediction``."""
        self.bus.push(f"r:{query_id}",
                      dict(frame, worker_id=worker_id))

    def pop_token_frames(self, query_id: str, timeout: float = 1.0,
                         max_items: int = 0) -> List[Dict[str, Any]]:
        """Blocking pop of whatever token frames have arrived for one
        generate request (edge side). The frames are plain dicts — no
        decode step — so this is just the bus pop with the reply-queue
        naming convention applied."""
        return self.bus.pop_all(f"r:{query_id}", max_items=max_items,
                                timeout=timeout)
