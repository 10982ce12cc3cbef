"""Cluster node registry: node identity + chip inventory over the bus.

The paper's Admin orchestrates workers across machines; this registry is
the piece that makes the node set *visible* to the serving plane. Each
node's :class:`~rafiki_tpu.admin.services_manager.ServicesManager`
announces one record on the serving bus under ``n:{node_id}`` — host,
pid, chip inventory, the node's broker URI, and a heartbeat stamp — and
every consumer (``GET /nodes``, the relay topology, failure-domain
spread placement) reads the same records back. The announce rides the
platform's EXISTING heartbeat cadence (``ServicesManager.heartbeat``),
so the registry adds zero threads.

Attached by the platform ONLY when ``RAFIKI_TPU_CLUSTER_FABRIC`` is on
(NodeConfig.cluster_fabric): off = ``services.node_registry`` stays
None — no ``rafiki_tpu_node_*`` series, no extra bus traffic,
byte-identical single-node behavior (docs/cluster.md).

Liveness here is registry-local and intentionally simpler than the
meta-store lease machinery: a record is *live* while its heartbeat is
younger than ``lease_s`` (the same NODE_LEASE window). A node that died
ungracefully stops influencing relay wiring and spread votes one lease
window later — exactly the staleness bound the supervise sweep already
accepts for foreign service rows.
"""

from __future__ import annotations

import logging
import os
import socket
import time
from typing import Any, Callable, Dict, List

from ..observe import metrics as _metrics

_log = logging.getLogger(__name__)

#: kv prefix for node records on the serving bus (vocabulary sibling of
#: the worker registration ``w:{job}:{service}`` keys).
NODE_KEY_PREFIX = "n:"


def node_key(node_id: str) -> str:
    return f"{NODE_KEY_PREFIX}{node_id}"


class NodeRegistry:
    """One node's view of the cluster membership (docs/cluster.md).

    ``bus_factory`` is a zero-arg callable returning the serving bus
    (``ServicesManager.serving_bus``) — lazy on purpose: construction
    must not open a connection the node may never need if the broker is
    still coming up.
    """

    def __init__(self, bus_factory: Callable[[], Any], node_id: str,
                 n_chips: int = 0, bus_uri: str = "",
                 lease_s: float = 120.0):
        self._bus_factory = bus_factory
        self.node_id = node_id
        self.n_chips = int(n_chips or 0)
        # This node's broker URI, published so peers can wire
        # BusServer.add_peer from the registry instead of static config.
        self.bus_uri = bus_uri
        self.lease_s = float(lease_s)
        # Gauge exists only while a registry does (fabric on): with
        # cluster_fabric off there are ZERO rafiki_tpu_node_* series
        # (tests/test_cluster.py::
        # test_single_node_construction_has_no_cluster_surface).
        self._peers_gauge = None
        if _metrics.metrics_enabled():
            self._peers_gauge = _metrics.registry().gauge(
                "rafiki_tpu_node_peers",
                "Nodes with a fresh heartbeat in the cluster node "
                "registry, as seen by this node")

    # --- Write side (rides ServicesManager.heartbeat) -----------------

    def announce(self) -> None:
        """Write/refresh this node's record. Called from the heartbeat
        path, so failures must not raise into the beat loop — the
        caller already isolates us, but a broker outage is expected
        during rolling restarts and only merits a warning."""
        rec = {"node": self.node_id, "host": socket.gethostname(),
               "pid": os.getpid(), "chips": self.n_chips,
               "bus": self.bus_uri, "hb": time.time()}
        self._bus_factory().set(node_key(self.node_id), rec)
        if self._peers_gauge is not None:
            self._peers_gauge.set(float(len(self.live_nodes())))

    def withdraw(self) -> None:
        """Delete this node's record (shutdown hygiene: a leaving node
        must not count as a spread-placement target for a full lease
        window)."""
        try:
            self._bus_factory().delete(node_key(self.node_id))
        except (ConnectionError, OSError, RuntimeError):
            pass  # broker gone = record gone with it

    # --- Read side ----------------------------------------------------

    def nodes(self) -> Dict[str, Dict[str, Any]]:
        """Every registered node's record, annotated with heartbeat age
        and the registry-local liveness verdict."""
        bus = self._bus_factory()
        now = time.time()
        out: Dict[str, Dict[str, Any]] = {}
        for key in bus.keys(prefix=NODE_KEY_PREFIX):
            rec = bus.get(key)
            if not isinstance(rec, dict):
                continue
            nid = str(rec.get("node") or key[len(NODE_KEY_PREFIX):])
            try:
                age = max(0.0, now - float(rec.get("hb") or 0.0))
            except (TypeError, ValueError):
                age = float("inf")
            out[nid] = {
                "host": rec.get("host"), "pid": rec.get("pid"),
                "chips": rec.get("chips"), "bus": rec.get("bus"),
                "heartbeat_age_s": round(min(age, 1e9), 1),
                "live": age <= self.lease_s,
            }
        return out

    def live_nodes(self) -> List[str]:
        return sorted(n for n, r in self.nodes().items() if r["live"])

    def relay_peers(self) -> Dict[str, str]:
        """``node_id -> broker URI`` for every OTHER live node — the
        wiring input for ``BusServer.add_peer`` (relay topology)."""
        return {n: str(r["bus"]) for n, r in self.nodes().items()
                if r["live"] and r.get("bus") and n != self.node_id}

    def spread_ok(self, replicas_by_node: Dict[str, int]) -> bool:
        """Failure-domain spread vote for ONE bin's scale-up.

        ``replicas_by_node`` counts the bin's active replicas per node
        (meta rows carry node_id). Place locally iff this node holds a
        MINIMUM count among live nodes AND is the first such node in
        sorted order — the deterministic tie-break means exactly one
        node acts per pressure round, so N nodes under the same signal
        lay replicas down round-robin across failure domains instead of
        N-fold over-provisioning one node. A registry that cannot see
        this node (broker outage, pre-announce races) votes True:
        spread is an optimization, never a liveness gate.
        """
        live = self.live_nodes()
        if not live or self.node_id not in live:
            return True
        counts = {n: int(replicas_by_node.get(n, 0)) for n in live}
        lo = min(counts.values())
        if counts[self.node_id] > lo:
            return False
        leaders = sorted(n for n, c in counts.items() if c == lo)
        return leaders[0] == self.node_id

    # --- Surfaces -----------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        """The ``GET /nodes`` body."""
        return {"enabled": True, "node_id": self.node_id,
                "lease_s": self.lease_s, "nodes": self.nodes()}

    def health(self) -> Dict[str, Any]:
        """The compact fold for ``GET /status`` (r20 health surface)."""
        nodes = self.nodes()
        return {"fabric": True, "nodes_registered": len(nodes),
                "nodes_live": sum(1 for r in nodes.values()
                                  if r["live"])}

    def close(self) -> None:
        """Withdraw + drop the registry's series (platform shutdown)."""
        self.withdraw()
        if self._peers_gauge is not None:
            self._peers_gauge.remove()
            self._peers_gauge = None
