"""Durable storage engine — the ``emqx_ds`` / mnesia-disc analog.

Behavioral reference (SURVEY.md §2.1 persistent session, §5.4): the
reference persists retained messages, sessions, banned and delayed
tables in mnesia ``disc_copies`` (4.x) or RocksDB via ``emqx_ds``
(5.4+), with *generations* — immutable snapshot + append log — per
shard.  This is the same log-structured shape in plain files:

* one directory per table;
* ``snapshot.jsonl`` — the compacted key/value state (one record per
  line, crash-tolerant: a torn tail line is dropped on load);
* ``wal.jsonl`` — puts/deletes appended since the snapshot, replayed
  over it on open (bootstrap-then-replay, the same discipline as the
  mria rlog and the device NFA mirror);
* compaction rewrites the snapshot atomically (tmp + rename) and
  truncates the wal once it outgrows the snapshot.

Values are JSON-safe dicts; binary fields ride base64 via the codec
helpers in :mod:`emqx_tpu.storage.codec`.
"""

from __future__ import annotations

import json
import logging
import os
from typing import Any, Dict, Iterator, Optional, Tuple

log = logging.getLogger(__name__)

__all__ = ["Store", "Table"]


class Table:
    """One persistent key→value table (snapshot + wal)."""

    def __init__(
        self,
        path: str,
        compact_ratio: float = 2.0,
        fsync_interval_s: float = 0.0,
    ) -> None:
        """``fsync_interval_s`` bounds the durability window of WAL
        appends: 0 (default) fsyncs every append — a crash loses at most
        the torn tail line; ``t > 0`` fsyncs at most once per ``t``
        seconds (the documented loss bound is then one interval's worth
        of appends, the RocksDB ``bytes_per_sync`` trade the reference's
        ``emqx_durable_storage`` makes [U])."""
        self.path = path
        self.compact_ratio = compact_ratio
        self.fsync_interval_s = fsync_interval_s
        os.makedirs(path, exist_ok=True)
        self._snap_path = os.path.join(path, "snapshot.jsonl")
        self._wal_path = os.path.join(path, "wal.jsonl")
        self._data: Dict[str, Any] = {}
        self._wal_records = 0
        self._wal = None
        self._last_fsync = 0.0
        self._load()

    # -- open / replay -------------------------------------------------

    def _read_lines(self, path: str) -> Iterator[Tuple[str, Any]]:
        if not os.path.exists(path):
            return
        with open(path, "r", encoding="utf-8") as f:
            for ln in f:
                ln = ln.strip()
                if not ln:
                    continue
                try:
                    rec = json.loads(ln)
                except json.JSONDecodeError:
                    # torn tail write from a crash: drop the remainder
                    log.warning("%s: dropping torn record", path)
                    return
                yield rec.get("op", "put"), rec

    def _load(self) -> None:
        for _op, rec in self._read_lines(self._snap_path):
            self._data[rec["k"]] = rec["v"]
        for op, rec in self._read_lines(self._wal_path):
            if op == "put":
                self._data[rec["k"]] = rec["v"]
            else:
                self._data.pop(rec["k"], None)
            self._wal_records += 1
        self._wal = open(self._wal_path, "a", encoding="utf-8")

    # -- mutation ------------------------------------------------------

    def put(self, key: str, value: Any) -> None:
        self._data[key] = value
        self._append({"op": "put", "k": key, "v": value})

    def delete(self, key: str) -> bool:
        existed = self._data.pop(key, None) is not None
        if existed:
            self._append({"op": "del", "k": key})
        return existed

    def write_batch(
        self, puts: Dict[str, Any], dels: Optional[list] = None
    ) -> None:
        """Apply many mutations with ONE flush+fsync at the end —
        identical durability for a reconciliation pass (the caller acks
        nothing until the whole batch returns) at 1/N the fsync cost."""
        for k, v in puts.items():
            self._data[k] = v
            self._wal.write(
                json.dumps({"op": "put", "k": k, "v": v},
                           separators=(",", ":")) + "\n")
            self._wal_records += 1
        for k in dels or ():
            # key-membership, not value truthiness: a stored None value
            # must still produce a del record or it resurrects on replay
            if k in self._data:
                del self._data[k]
                self._wal.write(
                    json.dumps({"op": "del", "k": k},
                               separators=(",", ":")) + "\n")
                self._wal_records += 1
        self._wal.flush()
        os.fsync(self._wal.fileno())
        if self._wal_records > max(64, self.compact_ratio * len(self._data)):
            self.compact()

    def _append(self, rec: Dict[str, Any]) -> None:
        self._wal.write(json.dumps(rec, separators=(",", ":")) + "\n")
        self._wal.flush()
        # durability: fsync per append (default), or rate-limited with a
        # bounded loss window
        if self.fsync_interval_s <= 0:
            os.fsync(self._wal.fileno())
        else:
            import time as _time

            now = _time.monotonic()
            if now - self._last_fsync >= self.fsync_interval_s:
                os.fsync(self._wal.fileno())
                self._last_fsync = now
        self._wal_records += 1
        if self._wal_records > max(64, self.compact_ratio * len(self._data)):
            self.compact()

    # -- read ----------------------------------------------------------

    def get(self, key: str, default: Any = None) -> Any:
        return self._data.get(key, default)

    def items(self):
        return self._data.items()

    def keys(self):
        return list(self._data)

    def __len__(self) -> int:
        return len(self._data)

    def __contains__(self, key: str) -> bool:
        return key in self._data

    # -- maintenance ---------------------------------------------------

    def compact(self) -> None:
        """Rewrite the snapshot atomically; reset the wal."""
        tmp = self._snap_path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            for k, v in self._data.items():
                f.write(json.dumps({"k": k, "v": v},
                                   separators=(",", ":")) + "\n")
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self._snap_path)
        # make the rename itself durable BEFORE truncating the wal —
        # otherwise a power cut can surface the old snapshot beside an
        # empty wal, losing fsync-acked writes
        dfd = os.open(self.path, os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)
        self._wal.close()
        self._wal = open(self._wal_path, "w", encoding="utf-8")
        self._wal_records = 0

    def close(self) -> None:
        if self._wal is not None:
            self.compact()
            self._wal.close()
            self._wal = None

    def clear(self) -> None:
        self._data.clear()
        self.compact()


class Store:
    """Directory of named tables under the node's data dir."""

    def __init__(self, data_dir: str, fsync_interval_s: float = 0.0) -> None:
        self.data_dir = data_dir
        self.fsync_interval_s = fsync_interval_s
        os.makedirs(data_dir, exist_ok=True)
        self._tables: Dict[str, Table] = {}

    def table(self, name: str) -> Table:
        t = self._tables.get(name)
        if t is None:
            t = self._tables[name] = Table(
                os.path.join(self.data_dir, name),
                fsync_interval_s=self.fsync_interval_s,
            )
        return t

    def close(self) -> None:
        for t in self._tables.values():
            t.close()
        self._tables.clear()

    def table_names(self):
        return list(self._tables)
