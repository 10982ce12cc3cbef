"""ParamStore: trial parameters on safetensors files + a sqlite index.

Parity: SURVEY.md §2 "Param store" — persists/retrieves serialized trial
parameters with sharing policies between trials (``ParamsType``:
LOCAL/GLOBAL x RECENT/BEST), the mechanism behind warm-starting and ENAS
weight sharing. The reference stores blobs in Redis + filesystem; here
each params dict is one ``.safetensors`` file (zero-copy mmap on load, no
pickle) and the policy index is sqlite (cross-process safe), so TrainWorkers
on different hosts can share a network volume.

Scoping: LOCAL policies resolve within one worker's saves; GLOBAL within
the whole session (a sub-train-job). Matches upstream's worker-local vs
cross-worker sharing semantics.

**Write-behind (r5, ordering fixed r6).** ``save`` accepts trees whose
leaves are still jax device arrays and flushes them to disk on a
background writer thread (packed single-transfer pull,
``parallel.device_get_tree``), with read-your-writes semantics
in-process:

- ``retrieve``/the policy queries see a pending save immediately and
  return the IN-MEMORY tree — for the ENAS weight-sharing loop this
  means the next trial warm-starts from device-resident arrays with no
  host round-trip at all, and the previous trial's device→host pull
  overlaps the next trial's compute instead of serializing with it
  (the pull was the dominant ENAS trial cost on a proxied transport:
  r5 profile, ~1.5 s of a ~3-6 s trial).
- ``load`` (the durable path: serving workers, cross-process readers)
  waits for the flush and then reads the file, keeping its strict
  numpy contract.

The sqlite index row is inserted by the WRITER thread, after
``save_file`` lands (r5 inserted it in ``save``, so a cross-process
reader on a shared volume could see the row seconds before the file
existed and crash on ``FileNotFoundError``). In-process visibility
during the flush window comes from the ``_pending`` map instead: the
policy queries merge pending saves (with their session/worker/score
metadata) into the sqlite candidates. File-then-row also closes the
``delete``-vs-writer race: the writer re-checks ``_pending`` under the
lock after the flush and unlinks its own file when the save was
deleted mid-flight — no orphaned ``.safetensors``, no row without a
file.

Who still saves device leaves: a ``TrialRunner`` without its persist
stage (the inline tail) and direct callers. The stage
(``worker/runner.py:_to_host``) copies a finished trial's leaves to the
host itself, behind the next trial's steps, and hands this store host
arrays, so under a TrainWorker ``save`` writes file and row before it
returns and a trial is COMPLETED only once both exist.

Durability is unchanged in kind: a crash between ``save`` returning
and the flush landing loses that save — exactly the window a crash
mid-``save_file`` always had, a few hundred ms wider.
``RAFIKI_TPU_PARAMS_WRITE_BEHIND=0`` makes saves synchronous again.
"""

from __future__ import annotations

import os
import queue
import sqlite3
import threading
import time
import uuid
from typing import Dict, Optional, Tuple

import numpy as np
from safetensors.numpy import load_file, save_file

from ..constants import ParamsType
from ..model.base import Params


class ParamStore:
    def __init__(self, params_dir: str):
        self.params_dir = params_dir
        os.makedirs(params_dir, exist_ok=True)
        # Write-behind state: params_id -> (tree, flushed-event,
        # index-row values). The writer thread is started lazily on the
        # first async save; it inserts the index row AFTER the file
        # lands (module docstring).
        self._pending: Dict[str, Tuple[Params, threading.Event,
                                       tuple]] = {}
        self._pending_lock = threading.Lock()
        self._write_queue: "queue.Queue" = queue.Queue()
        self._writer: Optional[threading.Thread] = None
        self._db = sqlite3.connect(os.path.join(params_dir, "index.db"),
                                   check_same_thread=False, timeout=30.0)
        self._lock = threading.RLock()
        with self._lock:
            self._db.execute("PRAGMA journal_mode=WAL")
            self._db.execute("PRAGMA busy_timeout=30000")
            self._db.execute("""
                CREATE TABLE IF NOT EXISTS params (
                    id TEXT PRIMARY KEY,
                    session_id TEXT NOT NULL,
                    worker_id TEXT NOT NULL,
                    score REAL NOT NULL,
                    created_at REAL NOT NULL
                )""")
            self._db.execute(
                "CREATE INDEX IF NOT EXISTS idx_params_session "
                "ON params (session_id)")
            self._db.commit()

    def close(self) -> None:
        with self._pending_lock:  # _writer is published under it
            writer = self._writer
        if writer is not None and writer.is_alive():
            self.flush()
            self._write_queue.put(None)  # writer-loop sentinel
            writer.join(timeout=10.0)
        with self._lock:
            self._db.close()

    def _path(self, params_id: str) -> str:
        return os.path.join(self.params_dir, f"{params_id}.safetensors")

    # --- Save / load by id ---

    def save(self, params: Params, *, session_id: str = "",
             worker_id: str = "", score: float = 0.0) -> str:
        """Persist one trial's parameters; returns the params_id.

        Leaves may be jax device arrays: the disk flush then happens on
        the background writer (module docstring) and this call returns
        without any device→host transfer.
        """
        params_id = uuid.uuid4().hex
        row = (params_id, session_id, worker_id, float(score), time.time())
        async_ok = os.environ.get(
            "RAFIKI_TPU_PARAMS_WRITE_BEHIND", "1") != "0"
        if async_ok and self._has_device_leaves(params):
            event = threading.Event()
            with self._pending_lock:
                self._pending[params_id] = (dict(params), event, row)
                if self._writer is None or not self._writer.is_alive():
                    self._writer = threading.Thread(
                        target=self._writer_loop, name="params-writer",
                        daemon=True)
                    self._writer.start()
            self._write_queue.put(params_id)
        else:
            self._flush_to_disk(params_id, params)
            self._insert_row(row)
        return params_id

    def _insert_row(self, row: tuple) -> None:
        with self._lock:
            self._db.execute(
                "INSERT INTO params (id, session_id, worker_id, score, "
                "created_at) VALUES (?, ?, ?, ?, ?)", row)
            self._db.commit()

    @staticmethod
    def _has_device_leaves(params: Params) -> bool:
        try:
            import jax
        except Exception:  # pragma: no cover - jax is a hard dep
            return False
        return any(isinstance(v, jax.Array) for v in params.values())

    def _flush_to_disk(self, params_id: str, params: Params) -> None:
        from ..parallel import device_get_tree

        # Packed single-transfer pull for device leaves, then the
        # safetensors contiguity normalisation.
        host = device_get_tree(dict(params))
        flat = {k: np.ascontiguousarray(np.asarray(v))
                for k, v in host.items()}
        save_file(flat, self._path(params_id))

    def _writer_loop(self) -> None:
        while True:
            params_id = self._write_queue.get()
            if params_id is None:  # close() sentinel
                return
            with self._pending_lock:
                entry = self._pending.get(params_id)
            if entry is None:  # deleted before flush
                continue
            tree, event, row = entry
            flushed = False
            try:
                self._flush_to_disk(params_id, tree)
                flushed = True
            except Exception:  # pragma: no cover - disk full etc.
                import logging

                logging.getLogger(__name__).exception(
                    "write-behind flush failed for %s", params_id)
            # File-then-row, atomically vs delete(): holding the
            # pending lock across the presence re-check AND the row
            # insert means a concurrent delete() either ran before (no
            # entry -> the file we just wrote is ours to unlink) or
            # runs after (sees the row and the file; removes both).
            deleted_mid_flight = False
            with self._pending_lock:
                if params_id in self._pending:
                    if flushed:
                        self._insert_row(row)
                else:
                    deleted_mid_flight = True
            if deleted_mid_flight and flushed:
                try:
                    os.remove(self._path(params_id))
                except FileNotFoundError:  # pragma: no cover
                    pass
            event.set()
            with self._pending_lock:
                self._pending.pop(params_id, None)

    def flush(self, timeout: float = 120.0) -> None:
        """Block until every pending write-behind save is on disk."""
        with self._pending_lock:
            events = [entry[1] for entry in self._pending.values()]
        for e in events:
            e.wait(timeout)

    def load(self, params_id: str) -> Params:
        """Durable read: waits out a pending flush, then reads the file
        (strict numpy contract — serving workers and cross-process
        readers rely on it)."""
        with self._pending_lock:
            entry = self._pending.get(params_id)
        if entry is not None:
            entry[1].wait(timeout=120.0)
        return dict(load_file(self._path(params_id)))

    def get_in_memory(self, params_id: str) -> Optional[Params]:
        """The pending in-memory tree for a not-yet-flushed save (may
        hold device arrays), or None once flushed/unknown."""
        with self._pending_lock:
            entry = self._pending.get(params_id)
        return dict(entry[0]) if entry is not None else None

    def exists(self, params_id: str) -> bool:
        with self._pending_lock:
            if params_id in self._pending:
                return True
        return os.path.exists(self._path(params_id))

    def delete(self, params_id: str) -> None:
        with self._pending_lock:
            self._pending.pop(params_id, None)
        with self._lock:
            self._db.execute("DELETE FROM params WHERE id = ?", (params_id,))
            self._db.commit()
        try:
            os.remove(self._path(params_id))
        except FileNotFoundError:
            pass

    # --- Sharing policies (ParamsType) ---

    def retrieve(self, params_type: str, *, session_id: str,
                 worker_id: str = "") -> Optional[Params]:
        """Fetch shared params per the proposal's sharing policy.

        Returns None when the policy is NONE or nothing is saved yet (the
        trial then cold-starts — matches upstream's fall-through).
        """
        if params_type == ParamsType.NONE:
            return None
        local = params_type in (ParamsType.LOCAL_RECENT, ParamsType.LOCAL_BEST)
        best = params_type in (ParamsType.LOCAL_BEST, ParamsType.GLOBAL_BEST)
        sql = ("SELECT id, score, created_at FROM params "
               "WHERE session_id = ?")
        args = [session_id]
        if local:
            sql += " AND worker_id = ?"
            args.append(worker_id)
        sql += " ORDER BY " + ("score DESC, created_at DESC"
                               if best else "created_at DESC")
        sql += " LIMIT 1"
        with self._lock:
            row = self._db.execute(sql, tuple(args)).fetchone()
        # Pending write-behind saves are not in the index yet (the
        # writer thread inserts the row after the file lands), so the
        # policy compares the sqlite winner against matching pending
        # candidates — in-process read-your-writes across the flush
        # window.
        candidates = [tuple(row)] if row is not None else []
        with self._pending_lock:
            for pid, (_, _, prow) in self._pending.items():
                if prow[1] == session_id and \
                        (not local or prow[2] == worker_id):
                    candidates.append((pid, prow[3], prow[4]))
        if not candidates:
            return None
        rank = (lambda c: (c[1], c[2])) if best else (lambda c: c[2])
        winner = max(candidates, key=rank)[0]
        # Read-your-writes fast path: a pending write-behind save is
        # served straight from memory — possibly as device arrays, so
        # an in-process warm start (the ENAS weight-sharing loop) skips
        # BOTH host round-trips.
        mem = self.get_in_memory(winner)
        if mem is not None:
            return mem
        try:
            return self.load(winner)
        except FileNotFoundError:
            # Indexed but evicted (GC, cleanup): absence, not an error —
            # the caller cold-starts, exactly as if nothing was saved.
            return None

    def session_params_ids(self, session_id: str) -> list:
        with self._lock:
            rows = self._db.execute(
                "SELECT id, created_at FROM params WHERE session_id = ? "
                "ORDER BY created_at", (session_id,)).fetchall()
        entries = [(r[1], r[0]) for r in rows]
        # Pending write-behind saves are visible in-process before
        # their index row lands (same contract as retrieve()).
        indexed = {pid for _, pid in entries}
        with self._pending_lock:
            entries.extend(
                (prow[4], pid) for pid, (_, _, prow)
                in self._pending.items()
                if prow[1] == session_id and pid not in indexed)
        return [pid for _, pid in sorted(entries)]
