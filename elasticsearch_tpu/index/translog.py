"""Translog: per-shard write-ahead log for durability and recovery.

Reference analog: index/translog/Translog.java (op types Create/Index/
Delete at :290/:432/:578, Snapshot streaming view :192) and the fs impl
(index/translog/fs/FsTranslog.java) with buffered/simple variants,
fsync policies, and rotation at flush.

Record format (binary, little-endian):
    [u32 length][u32 crc32-of-payload][payload: JSON]
A TORN TAIL (the file ends inside a record — the residue of a crash
mid-append) is truncated on open, counted under
`translog_truncated_bytes`, like the reference's recovery tolerating a
torn last write. A COMPLETE record that fails its crc or parse is NOT
a torn write — it is mid-log corruption of a durable record, and
replaying past it (or silently truncating everything after it) would
lose acked ops: that raises TranslogCorruptedError and the engine
CONTAINS the shard (ref: TranslogCorruptedException vs the tolerated
truncated-translog case).

Durability modes (`index.translog.durability`):
  * ``request`` (default) — fsync after every add_many (one op, or a
    bulk request's batch for this shard): an op is on disk before its
    caller sees the ack. Survives kill -9 AND power loss.
  * ``async``  — flush (page cache) per add_many, fsync only at explicit
    sync()/flush/rotate: an op survives kill -9 (the page cache
    belongs to the OS, not the process) but power loss may drop the
    window since the last sync. `_synced_size` tracks the known-
    durable prefix; the crash_point `unsynced=drop` simulation
    truncates back to it — the deterministic power-loss adversary.

Generations: translog-<gen>.log; flush rotates to a new generation and
deletes the old ones once the segments it covers are durable. Every
append/fsync/rotate write boundary and every recovery read is hooked
into utils/faults.py.
"""

from __future__ import annotations

import json
import os
import struct
import zlib
from dataclasses import dataclass
from typing import Iterator

from ..utils import faults
from ..utils.errors import ElasticsearchTpuError
from . import durability as durability_stats

_HEADER = struct.Struct("<II")
_json_str = json.encoder.encode_basestring_ascii    # a str as json.dumps has it

OP_INDEX = "index"
OP_DELETE = "delete"

DURABILITY_REQUEST = "request"
DURABILITY_ASYNC = "async"


class TranslogCorruptedError(ElasticsearchTpuError):
    """A DURABLE translog record (complete on disk) failed its crc or
    parse — mid-log corruption, not a torn tail. Replay stops and the
    shard is contained instead of silently dropping acked ops."""

    status = 500


@dataclass
class TranslogOp:
    op: str                       # index | delete
    doc_id: str
    version: int
    source: bytes | None = None   # for index ops

    def to_payload(self) -> bytes:
        if type(self.doc_id) is str and type(self.version) is int:
            # what the encoder below prints for these types, put together
            # by hand (tests/test_bulk_batch.py holds the two to the
            # same bytes)
            src = "" if self.source is None else \
                ',"src":' + _json_str(self.source.decode("utf-8"))
            return (f'{{"op":{_json_str(self.op)},"id":'
                    f'{_json_str(self.doc_id)},"v":{self.version}{src}}}'
                    ).encode("utf-8")
        d = {"op": self.op, "id": self.doc_id, "v": self.version}
        if self.source is not None:
            d["src"] = self.source.decode("utf-8")
        return json.dumps(d, separators=(",", ":")).encode("utf-8")

    @classmethod
    def from_payload(cls, payload: bytes) -> "TranslogOp":
        d = json.loads(payload)
        src = d.get("src")
        return cls(op=d["op"], doc_id=d["id"], version=d["v"],
                   source=src.encode("utf-8") if src is not None else None)


class Translog:
    """Append-only op log with crc-checked records and generations.

    When the native layer is available (native/src/estnative.cpp), appends
    go through est_wal_write — one write() per add_many, the records
    framed here — and fsyncs through est_wal_sync; the record format on
    disk is identical, so either implementation can recover the other's
    files.
    """

    def __init__(self, path: str, sync_each_op: bool = False,
                 durability: str | None = None,
                 index: str | None = None, shard: int | None = None):
        self.dir = path
        if durability is None:
            durability = (DURABILITY_REQUEST if sync_each_op
                          else DURABILITY_ASYNC)
        if durability not in (DURABILITY_REQUEST, DURABILITY_ASYNC):
            from ..utils.errors import IllegalArgumentError
            raise IllegalArgumentError(
                f"index.translog.durability must be "
                f"[{DURABILITY_REQUEST}] or [{DURABILITY_ASYNC}], "
                f"got [{durability}]")
        self.durability = durability
        self.sync_each_op = durability == DURABILITY_REQUEST
        self.index = index
        self.shard = shard
        self.truncated_bytes = 0
        os.makedirs(path, exist_ok=True)
        gens = self._generations()
        self.generation = gens[-1] if gens else 1
        self._ops_in_gen = 0
        self._size_in_gen = 0
        # recover tail sanity before appending
        existing = self._recover_file(self._file_for(self.generation))
        self._ops_in_gen = len(existing)
        self._fh = None
        self._wal = None
        self._lib = None
        try:
            from ..native import get_lib
            self._lib = get_lib()
        except Exception:
            self._lib = None
        if self._lib is not None:
            self._wal = self._lib.est_wal_open(
                self._file_for(self.generation).encode())
        if self._wal is None:
            self._lib = None
            self._fh = open(self._file_for(self.generation), "ab")
            self._size_in_gen = self._fh.tell()
        else:
            self._size_in_gen = self._lib.est_wal_size(self._wal)
        # the known-durable prefix: everything that existed at open is
        # on disk (the previous process flushed-or-died; what survived
        # IS the durable state), everything after only once fsynced
        self._synced_size = self._size_in_gen

    # -- paths -------------------------------------------------------------
    def _file_for(self, gen: int) -> str:
        return os.path.join(self.dir, f"translog-{gen}.log")

    def _generations(self) -> list[int]:
        out = []
        for name in os.listdir(self.dir):
            if name.startswith("translog-") and name.endswith(".log"):
                try:
                    out.append(int(name[len("translog-"):-len(".log")]))
                except ValueError:
                    pass
        return sorted(out)

    def min_generation(self) -> int | None:
        """Oldest generation still on disk — the commit-coverage
        witness: recovery may fall back to a commit point C only when
        min_generation() <= C's recorded translog generation + 1
        (every op since C is then still replayable)."""
        gens = self._generations()
        return gens[0] if gens else None

    # -- write path --------------------------------------------------------
    def add(self, op: TranslogOp) -> None:
        self.add_many([op])

    def add_many(self, ops: list[TranslogOp]) -> None:
        """Append a batch: the records one op at a time would write,
        byte for byte and in order, joined and written with ONE write.
        `request` durability fsyncs once, after the write and before
        this returns, so before the caller acknowledges any op of the
        batch (the reference syncs the translog once a shard bulk
        request); `async` hands the bytes to the page cache and leaves
        the fsync to sync()/flush/rotate."""
        if not ops:
            return
        records = []
        for op in ops:
            payload = op.to_payload()
            records.append(
                _HEADER.pack(len(payload), zlib.crc32(payload)) + payload)
        if faults.active().rules:
            self._append_crash_sites(records)
        blob = b"".join(records)
        if self._wal is not None:
            if self._lib.est_wal_write(self._wal, blob, len(blob)) < 0:
                raise OSError("translog append failed")
        else:
            self._fh.write(blob)
            self._fh.flush()
        self._ops_in_gen += len(records)
        self._size_in_gen += len(blob)
        if self.sync_each_op:
            self.sync()

    def _append_crash_sites(self, records: list[bytes]) -> None:
        """One `append` crash site a record, as when each record was a
        write of its own: a rule that fires at record i leaves what a
        death in the middle of the batch's write leaves, the records
        before it whole and the first half of record i, which
        recovery's torn-tail truncation chews. The native WAL has not
        written yet, so the tear lands via a throwaway append fd (the
        process "dies" right after)."""
        for i, rec in enumerate(records):
            def torn_append():
                torn = b"".join(records[:i]) + rec[: max(len(rec) // 2, 1)]
                if self._fh is not None:
                    self._fh.write(torn)
                    self._fh.flush()
                else:
                    with open(self._file_for(self.generation), "ab") as f:
                        f.write(torn)
            faults.on_storage_write("translog", "append", index=self.index,
                                    shard=self.shard, partial=torn_append,
                                    unsynced_drop=self._drop_unsynced)

    def _drop_unsynced(self) -> None:
        """Power-loss simulation (crash_point `unsynced=drop`): the OS
        page cache dies with the machine, so everything written after
        the last fsync vanishes — truncate back to the known-durable
        prefix. In `request` mode the prefix IS the file, so this is a
        no-op: that asymmetry is the per-mode guarantee the durability
        tests pin."""
        if self._fh is not None:
            self._fh.flush()
        # works for the native WAL too: est_wal_write is a plain
        # write(), so unfsynced bytes live in the page cache (the
        # file), and the "power loss" truncates the file itself — the
        # process is dead right after, nobody writes through the stale
        # handle again
        path = self._file_for(self.generation)
        if os.path.exists(path) \
                and os.path.getsize(path) > self._synced_size:
            os.truncate(path, self._synced_size)

    def sync(self) -> None:
        faults.on_storage_write("translog", "fsync", index=self.index,
                                shard=self.shard,
                                unsynced_drop=self._drop_unsynced)
        if self._wal is not None:
            self._lib.est_wal_sync(self._wal)
            self._synced_size = self._size_in_gen
            return
        self._fh.flush()
        os.fsync(self._fh.fileno())
        self._synced_size = self._size_in_gen

    # -- snapshot / recovery ----------------------------------------------
    def snapshot(self) -> list[TranslogOp]:
        """All ops across live generations, in order (the recovery replay
        stream — ref Translog.Snapshot)."""
        if self._fh is not None:
            self._fh.flush()
        ops: list[TranslogOp] = []
        for gen in self._generations():
            ops.extend(self._recover_file(self._file_for(gen)))
        return ops

    def _recover_file(self, path: str) -> list[TranslogOp]:
        """Replay one generation file. A TORN TAIL (file ends inside a
        record) is truncated and counted; a COMPLETE record failing crc
        or parse is mid-log corruption of a durable record and raises
        TranslogCorruptedError — truncating past it would silently drop
        every acked op behind it."""
        ops: list[TranslogOp] = []
        if not os.path.exists(path):
            return ops
        faults.on_storage_read("translog", "read", path,
                               index=self.index, shard=self.shard)
        good_end = 0
        with open(path, "rb") as f:
            data = f.read()
        off = 0
        while off + _HEADER.size <= len(data):
            length, crc = _HEADER.unpack_from(data, off)
            start = off + _HEADER.size
            end = start + length
            if end > len(data):
                break  # torn tail: the record never finished hitting disk
            payload = data[start:end]
            if zlib.crc32(payload) != crc:
                durability_stats.on_corruption_detected()
                raise TranslogCorruptedError(
                    f"translog [{os.path.basename(path)}] record at "
                    f"offset {off} failed crc (durable record "
                    f"corrupted; {len(data) - off} bytes at risk)")
            try:
                ops.append(TranslogOp.from_payload(payload))
            except Exception as e:
                durability_stats.on_corruption_detected()
                raise TranslogCorruptedError(
                    f"translog [{os.path.basename(path)}] record at "
                    f"offset {off} unparseable: {e}") from e
            off = end
            good_end = end
        if good_end < len(data):
            torn = len(data) - good_end
            with open(path, "r+b") as f:  # truncate torn tail
                f.truncate(good_end)
            self.truncated_bytes += torn
            durability_stats.on_translog_truncated(torn)
        return ops

    # -- rotation (flush) --------------------------------------------------
    def rotate(self) -> None:
        """Start a new generation and drop old ones (called after a commit
        makes the covered ops durable in segments)."""
        # crash BEFORE the rotation: the commit is already durable and
        # every old generation survives — replay re-applies ops the
        # commit covers, which the versioned replay converges (same
        # ids, same versions); nothing is lost, nothing doubles
        faults.on_storage_write("translog", "rotate", index=self.index,
                                shard=self.shard,
                                unsynced_drop=self._drop_unsynced)
        old_gens = self._generations()
        if self._wal is not None:
            self._lib.est_wal_close(self._wal)
        else:
            self._fh.close()
        self.generation = (old_gens[-1] if old_gens else 0) + 1
        if self._lib is not None:
            self._wal = self._lib.est_wal_open(
                self._file_for(self.generation).encode())
        if self._wal is None:
            self._fh = open(self._file_for(self.generation), "ab")
        self._ops_in_gen = 0
        self._size_in_gen = 0
        self._synced_size = 0
        for gen in old_gens:
            try:
                os.remove(self._file_for(gen))
            except OSError:
                pass

    @property
    def num_ops(self) -> int:
        return self._ops_in_gen

    @property
    def size_in_bytes(self) -> int:
        return self._size_in_gen

    def close(self) -> None:
        try:
            if self._wal is not None:
                self._lib.est_wal_close(self._wal)
                self._wal = None
            elif self._fh is not None:
                self._fh.flush()
                self._fh.close()
        except Exception:
            pass

    def stats(self) -> dict:
        return {"operations": self._ops_in_gen,
                "size_in_bytes": self._size_in_gen,
                "generation": self.generation,
                "durability": self.durability,
                "truncated_bytes": self.truncated_bytes}
