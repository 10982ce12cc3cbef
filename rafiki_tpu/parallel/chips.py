"""Chip groups: the TPU replacement for per-service GPU assignment.

Parity: SURVEY.md §2 "ServicesManager / GPU scheduler" + §7 hard-part
"chip-range multi-tenancy". The reference Admin assigns device indices to
worker containers via ``CUDA_VISIBLE_DEVICES``; here the scheduler assigns a
**chip range** — a contiguous slice of ``jax.devices()`` — communicated to
the worker process via the ``RAFIKI_TPU_CHIPS`` env var (comma-separated
global device indices). The worker builds its ``jax.sharding.Mesh`` from
exactly those devices, so every trial's collectives ride ICI within its own
group and groups never contend.

Two placement regimes (SURVEY.md §7):

- **resident runner** (default here): one process owns all chips of the host
  and schedules trials onto ``Mesh`` subsets — no process isolation needed,
  works on any slice topology.
- **process-per-group**: workers are separate processes; each sees the full
  device list but only *uses* its assigned range. (True device isolation à
  la ``TPU_VISIBLE_CHIPS`` is runtime-dependent; the allocator's contract is
  identical either way.)
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

from ..constants import EnvVars


@dataclass(frozen=True)
class ChipGroup:
    """An ordered set of global device indices assigned to one service."""

    indices: tuple  # tuple[int, ...] into jax.devices()
    name: str = ""

    @property
    def n_chips(self) -> int:
        return len(self.indices)

    def devices(self) -> List:
        import jax

        all_devs = jax.devices()
        return [all_devs[i] for i in self.indices]

    def to_env(self) -> str:
        return ",".join(str(i) for i in self.indices)

    @staticmethod
    def from_env(value: Optional[str] = None) -> "ChipGroup":
        """Build the group from ``RAFIKI_TPU_CHIPS`` (or all devices)."""
        import jax

        if value is None:
            value = os.environ.get(EnvVars.CHIPS, "")
        if value:
            idx = tuple(int(x) for x in value.split(",") if x != "")
        else:
            idx = tuple(range(len(jax.devices())))
        return ChipGroup(indices=idx)

    # --- Thread-scoped binding (resident-runner mode) ---
    #
    # Worker threads sharing one process cannot partition devices via the
    # process-wide env var; each service thread binds its group here and
    # models resolve it via ``ChipGroup.current()`` (thread-local → env →
    # all devices).

    _tls = threading.local()

    def bind_to_thread(self) -> None:
        ChipGroup._tls.group = self

    @staticmethod
    def unbind_thread() -> None:
        ChipGroup._tls.group = None

    @staticmethod
    def current() -> "ChipGroup":
        group = getattr(ChipGroup._tls, "group", None)
        return group if group is not None else ChipGroup.from_env()


def discover_topology(devices: Sequence) -> Optional[List[tuple]]:
    """Per-device physical coords, or None when the backend has none.

    TPU devices expose ``.coords`` — ``(x, y, z)`` position on the slice's
    ICI torus (v5e: a 2-D torus, z == 0). Virtual CPU devices don't; the
    allocator then falls back to linear index adjacency.
    """
    coords = []
    for d in devices:
        c = getattr(d, "coords", None)
        if c is None or len(c) < 2:
            return None
        coords.append(tuple(int(v) for v in c))
    return coords if len(set(coords)) == len(coords) else None


def _rect_shapes(n: int):
    """(h, w) factorizations of n, squarest first (minimal ICI diameter)."""
    shapes = [(h, n // h) for h in range(1, n + 1) if n % h == 0]
    return sorted(shapes, key=lambda s: (max(s), abs(s[0] - s[1])))


def _box_shapes(n: int):
    """(d, h, w) factorizations of n, most cube-like first.

    Ordering minimizes the box's ICI diameter: smallest max extent,
    then smallest extent sum. On a z-flat (2-D) grid the d>1 shapes
    simply never fit and the search degrades to the rectangle order.
    """
    shapes = []
    for d in range(1, n + 1):
        if n % d:
            continue
        for h in range(1, n // d + 1):
            if (n // d) % h == 0:
                shapes.append((d, h, n // (d * h)))
    return sorted(shapes, key=lambda s: (max(s), sum(s)))


class ChipAllocator:
    """Carves a device list into non-overlapping chip groups.

    The Admin-side resource manager: thread-safe. Placement is
    **topology-aware** when the backend exposes device coords (TPU): a
    group of ``n`` chips is placed as the most cube-like free
    axis-aligned box on the slice's ICI torus — a rectangle on 2-D
    slices (v5e), a genuine d×h×w box on 3-D tori (v4/v5p) — so every
    intra-group collective rides single-hop ICI links (a linear index
    range can straddle torus rows — adjacent indices, distant chips).
    When fragmentation or an awkward size blocks every box, the group
    falls back to a connected free blob (still ICI-internal, larger
    diameter). Without coords (virtual CPU meshes) placement is
    contiguous-first-fit on the device index. ``allocate`` returns None
    when the request cannot be satisfied — callers queue and retry
    (scheduler fairness is handled one level up, in the
    ServicesManager).

    **Chip sharing (single-chip multi-tenancy).** ``allocate(...,
    shared_ok=True)`` adds a fallback tier: when no exclusive placement
    exists, the group may be placed on already-owned chips — least-
    subscribed cells first, never exceeding ``max_share`` owners per
    chip. In resident-runner mode every worker is a thread of ONE
    process sharing one jax backend, so co-owned chips are legal: the
    co-owners' dispatches interleave on the device queue (time-sliced
    tenancy — how a v5e-1 runs two concurrent jobs, BASELINE config[5]).
    Process/docker workers must NOT share (two processes cannot open
    one TPU chip); the ServicesManager gates ``shared_ok`` on the
    container manager's ``supports_chip_sharing``.
    """

    def __init__(self, n_chips: Optional[int] = None,
                 topology: Optional[Sequence[tuple]] = None):
        if n_chips is None or topology is None:
            import jax

            devices = jax.devices()
            if n_chips is None:
                n_chips = len(devices)
            if topology is None:
                topology = discover_topology(devices[:n_chips])
        self.n_chips = n_chips
        if topology is not None and len(topology) != n_chips:
            raise ValueError(f"topology has {len(topology)} entries for "
                             f"{n_chips} chips")
        # Normalize coords to (x, y, z): v5e slices report z == 0
        # everywhere; v4/v5p report a genuine 3-D torus position. The
        # box search below handles both (a z-flat grid only ever fits
        # depth-1 boxes, i.e. plain rectangles).
        self._topology = ([tuple(c[:3]) + (0,) * (3 - min(len(c), 3))
                           for c in topology] if topology else None)
        self._lock = threading.Lock()
        # Co-ownership: each chip carries a list of owner names (shared
        # tenancy appends; exclusive placement requires an empty list).
        self._owners: List[List[str]] = [[] for _ in range(n_chips)]
        self._groups: Dict[str, ChipGroup] = {}

    def allocate(self, n: int, name: str, *, shared_ok: bool = False,
                 max_share: Optional[int] = None) -> Optional[ChipGroup]:
        """Allocate ``n`` chips as an ICI-compact group; None if full.

        ``shared_ok`` adds the time-sliced fallback tier (docstring
        above): exclusive placement first, then least-subscribed shared
        placement up to ``max_share`` owners per chip (default 4;
        ``RAFIKI_TPU_MAX_CHIP_SHARE`` overrides — a dense box serving
        many replica workers per chip may deliberately oversubscribe).
        The env var is ``NodeConfig.max_chip_share`` (promoted from the
        env-only expert baseline in r14: the autoscaler's scale-up
        leans on time-sliced placement, making the cap a sizing
        decision); the allocator keeps reading env per call so it
        works without a NodeConfig and honors mid-run overrides.
        """
        if n <= 0:
            raise ValueError("n must be positive")
        if max_share is None:
            import os

            try:
                max_share = int(os.environ.get(
                    "RAFIKI_TPU_MAX_CHIP_SHARE", "4"))
            except ValueError:
                max_share = 4
        with self._lock:
            if name in self._groups:
                raise ValueError(
                    f"group {name!r} already holds chips; release it first")
            # With a known topology, placements must be ICI-connected:
            # a linear index run can straddle torus rows, putting one
            # group's collectives on other groups' ICI links. Axis-
            # aligned boxes first (minimal diameter); when no box fits
            # — the size has no box factorization (5 or 7 on a 2x4) or
            # fragmentation blocks every feasible box — fall back to a
            # connected free blob, which keeps every collective on
            # group-internal links at the cost of a non-minimal
            # diameter. Only a grid with no connected free region of n
            # cells returns None -> callers queue/retry. With
            # ``shared_ok``, ever-more-subscribed cells are admitted one
            # load tier at a time, so a shared group lands on the
            # least-loaded chips that fit it.
            idx = None
            caps = range(max_share if shared_ok else 1)
            for cap in caps:
                allowed = {i for i, o in enumerate(self._owners)
                           if len(o) <= cap}
                if len(allowed) < n:
                    continue
                if self._topology is not None:
                    idx = self._find_box(n, allowed)
                    if idx is None:
                        idx = self._find_blob(n, allowed)
                else:
                    idx = self._find_linear(n, allowed)
                if idx is not None:
                    break
            if idx is None:
                return None
            for j in idx:
                self._owners[j].append(name)
            group = ChipGroup(indices=idx, name=name)
            self._groups[name] = group
            return group

    def _find_box(self, n: int, allowed: set) -> Optional[tuple]:
        """Most cube-like free d×h×w box on the (x, y, z) coord grid.

        Returned indices are in BOUSTROPHEDON (snake) order — each row
        reversed relative to the previous, and each z-plane's whole
        traversal reversed relative to the plane below — so devices
        adjacent in group order are physically adjacent on the torus at
        every hop including row turns and plane turns; ``build_mesh``'s
        ring (``sp``) axis ppermutes between group-order neighbours,
        and plain row-major order would make those boundaries
        multi-hop diagonals. On a z-flat grid (v5e) only d == 1 boxes
        fit and this is exactly the 2-D rectangle search.
        """
        grid = {c: i for i, c in enumerate(self._topology)}
        free = {c for c, i in grid.items() if i in allowed}
        for d, h, w in _box_shapes(n):
            for (x0, y0, z0) in sorted(free, key=lambda c: (c[2], c[1],
                                                            c[0])):
                cells = []
                for dz in range(d):
                    plane = []
                    for dy in range(h):
                        xs = (range(w) if dy % 2 == 0
                              else range(w - 1, -1, -1))
                        plane.extend((x0 + dx, y0 + dy, z0 + dz)
                                     for dx in xs)
                    if dz % 2 == 1:
                        plane.reverse()
                    cells.extend(plane)
                if all(c in free for c in cells):
                    return tuple(grid[c] for c in cells)
        return None

    def _find_blob(self, n: int, allowed: set) -> Optional[tuple]:
        """Connected free region of n cells (BFS, 6-neighbour).

        Fallback when no axis-aligned box fits — whether because the
        size has no feasible factorization or because fragmentation
        blocks every feasible box: the group stays ICI-connected (every
        member reachable through group-internal links) even though its
        diameter is not minimal.
        """
        grid = {c: i for i, c in enumerate(self._topology)}
        free = {c for c, i in grid.items() if i in allowed}
        for anchor in sorted(free):
            blob, frontier = [anchor], [anchor]
            seen = {anchor}
            while frontier and len(blob) < n:
                x, y, z = frontier.pop(0)
                for nxt in ((x + 1, y, z), (x - 1, y, z), (x, y + 1, z),
                            (x, y - 1, z), (x, y, z + 1), (x, y, z - 1)):
                    if nxt in free and nxt not in seen:
                        seen.add(nxt)
                        blob.append(nxt)
                        frontier.append(nxt)
                        if len(blob) == n:
                            break
            if len(blob) == n:
                return tuple(grid[c] for c in sorted(blob,
                                                     key=lambda c:
                                                     (c[2], c[1], c[0])))
        return None

    def _find_linear(self, n: int, allowed: set) -> Optional[tuple]:
        """First-fit contiguous index range (no-topology fallback)."""
        run_start, run_len = None, 0
        for i in range(self.n_chips):
            if i in allowed:
                run_start = i if run_len == 0 else run_start
                run_len += 1
                if run_len == n:
                    return tuple(range(run_start, run_start + n))
            else:
                run_len = 0
        return None

    def release(self, name: str) -> None:
        with self._lock:
            group = self._groups.pop(name, None)
            if group:
                for i in group.indices:
                    if name in self._owners[i]:
                        self._owners[i].remove(name)

    @property
    def free_chips(self) -> int:
        with self._lock:
            return sum(1 for o in self._owners if not o)

    def utilization(self) -> float:
        return 1.0 - self.free_chips / self.n_chips
