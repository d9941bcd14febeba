"""Needle maps — in-memory needleId -> (offset, size) indexes.

The reference offers several variants (weed/storage/needle_map.go):
CompactMap (sectioned sorted arrays), LevelDB, sorted-file, and a btree
MemDb used for EC index sorting. Here:

  * NeedleMap        — dict-backed (Python dicts are compact open-addressing
                       tables; the CompactMap exists in the reference to
                       dodge Go GC overheads that don't apply here), plus
                       the same append-to-.idx write-through discipline
                       (reference needle_map.go:51 baseNeedleMapper).
  * MemDb            — sorted in-memory db for .idx -> .ecx sorting
                       (reference needle_map/memdb.go): a record array
                       ascending by key, loaded from the log in one sort
                       (idx_array), not a record a Python iteration.

(The sorted-file binary search over 16B records lives with its only
consumer: ec/ec_volume.search_needle_from_sorted_index.)
"""

from __future__ import annotations

import operator
import os
import struct
import time
from typing import Iterator, Optional, Tuple

import numpy as np

from .idx_array import (columns, is_put, last_per_key, read_idx_records,
                        records_of, replay_idx)
from .types import (NEEDLE_ENTRY_SIZE, OFFSET_SIZE, TOMBSTONE_FILE_SIZE,
                    bytes_to_offset, bytes_to_needle_id, entry_size,
                    needle_id_to_bytes, offset_to_bytes)


def entry_to_bytes(nid: int, offset: int, size: int,
                   offset_width: int = OFFSET_SIZE) -> bytes:
    return needle_id_to_bytes(nid) + offset_to_bytes(offset, offset_width) \
        + struct.pack(">I", size)


def bytes_to_entry(b: bytes) -> Tuple[int, int, int]:
    """Record width implies the offset width (16 -> 4B, 17 -> 5B)."""
    return (bytes_to_needle_id(b[0:8]), bytes_to_offset(b[8:-4]),
            struct.unpack(">I", b[-4:])[0])


class NeedleValue:
    __slots__ = ("offset", "size")

    def __init__(self, offset: int, size: int):
        self.offset = offset
        self.size = size


_OFFSET_OF = operator.attrgetter("offset")
_SIZE_OF = operator.attrgetter("size")


class NeedleMap:
    """Write-through needle map: in-memory dict + append-only .idx log."""

    kind = "memory"

    def __init__(self, idx_path: Optional[str] = None,
                 offset_width: int = OFFSET_SIZE):
        self._m: dict = {}
        self.idx_path = idx_path
        self.offset_width = offset_width
        self._idx_file = None
        self.file_counter = 0
        self.file_byte_counter = 0
        self.deletion_counter = 0
        self.deletion_byte_counter = 0
        self.maximum_file_key = 0
        if idx_path is not None:
            self._idx_file = open(idx_path, "ab")

    # -- loading -----------------------------------------------------------
    @classmethod
    def load(cls, idx_path: str,
             offset_width: int = OFFSET_SIZE) -> "NeedleMap":
        """Replay the .idx as one record array (idx_array.replay_idx:
        the last record of a key wins, the counters are _apply's event
        tally). The dict comes out ascending by key, not in log order;
        every caller that needs an order asks snapshot_live_items(...,
        by_offset=True) for it."""
        from ..ops import telemetry
        t0 = time.perf_counter()
        nm = cls.__new__(cls)
        nm.idx_path = idx_path
        nm.offset_width = offset_width
        records = read_idx_records(idx_path, offset_width)
        live, counters = replay_idx(records)
        nm.__dict__.update(counters)
        keys, offsets, sizes = columns(live)
        nm._m = dict(zip(keys.tolist(),
                         map(NeedleValue, offsets.tolist(), sizes.tolist())))
        nm._idx_file = open(idx_path, "ab")
        telemetry.STATS.add_mirror(len(records), time.perf_counter() - t0)
        return nm

    def _apply(self, nid: int, offset: int, size: int):
        self.maximum_file_key = max(self.maximum_file_key, nid)
        if size != TOMBSTONE_FILE_SIZE and offset != 0:
            old = self._m.get(nid)
            self._m[nid] = NeedleValue(offset, size)
            self.file_counter += 1
            self.file_byte_counter += size
            if old is not None:
                self.deletion_counter += 1
                self.deletion_byte_counter += old.size
        else:
            old = self._m.pop(nid, None)
            if old is not None:
                self.deletion_counter += 1
                self.deletion_byte_counter += old.size

    # -- mutations ---------------------------------------------------------
    def put(self, nid: int, offset: int, size: int):
        self._apply(nid, offset, size)
        if self._idx_file is not None:
            self._idx_file.write(
                entry_to_bytes(nid, offset, size, self.offset_width))
            self._idx_file.flush()

    def delete(self, nid: int):
        """Tombstone: offset 0, size TOMBSTONE (reference appends an entry
        with size=TombstoneFileSize)."""
        old = self._m.pop(nid, None)
        if old is not None:
            self.deletion_counter += 1
            self.deletion_byte_counter += old.size
        if self._idx_file is not None:
            self._idx_file.write(
                entry_to_bytes(nid, 0, TOMBSTONE_FILE_SIZE,
                               self.offset_width))
            self._idx_file.flush()

    def get(self, nid: int) -> Optional[NeedleValue]:
        return self._m.get(nid)

    def __contains__(self, nid: int) -> bool:
        return nid in self._m

    def __len__(self) -> int:
        return len(self._m)

    def items(self) -> Iterator[Tuple[int, NeedleValue]]:
        return iter(self._m.items())

    def live_columns(self):
        """The live set as (keys uint64, byte offsets uint64, sizes
        uint32) arrays, each filled in one C-level pass over the dict:
        what the native plane's mirror takes (call under the volume
        lock — the dict mutates under writes)."""
        n = len(self._m)
        values = self._m.values()
        return (np.fromiter(self._m, np.uint64, n),
                np.fromiter(map(_OFFSET_OF, values), np.uint64, n),
                np.fromiter(map(_SIZE_OF, values), np.uint32, n))

    @property
    def content_size(self) -> int:
        return self.file_byte_counter

    @property
    def deleted_size(self) -> int:
        return self.deletion_byte_counter

    def sync(self):
        """fdatasync the .idx append log — the Python write path's half
        of the SW_PLANE_FSYNC_MODE durability contract (the native
        plane's committer fdatasyncs the .idx it owns the same way)."""
        if self._idx_file is not None:
            os.fdatasync(self._idx_file.fileno())

    def close(self):
        if self._idx_file is not None:
            self._idx_file.close()
            self._idx_file = None


class MemDb:
    """Sorted needle db for building .ecx files (reference memdb.go):
    the last put of every key of a log as one record array ascending by
    key, less the keys deleted since."""

    def __init__(self, offset_width: int = OFFSET_SIZE):
        self.offset_width = offset_width
        self._records = records_of(b"", offset_width)
        self._deleted: set = set()
        # .idx records load_from_idx dropped: deletes and zero offsets
        self.tombstones = 0

    def __len__(self) -> int:
        return len(self._live())

    def delete(self, nid: int):
        self._deleted.add(nid)

    def _live(self) -> np.ndarray:
        if self._deleted:
            gone = np.fromiter(self._deleted, np.uint64, len(self._deleted))
            self._records = self._records[
                ~np.isin(self._records["nid"], gone)]
            self._deleted = set()
        return self._records

    @classmethod
    def load_from_idx(cls, idx_path: str,
                      offset_width: int = OFFSET_SIZE) -> "MemDb":
        """The log's replay, no record a Python iteration: the last put
        of every key as one array, then `delete` once for each key whose
        last record is a tombstone or a zero offset."""
        db = cls(offset_width)
        with open(idx_path, "rb") as f:
            records = records_of(f.read(), offset_width)
        puts = is_put(records)
        db._records = last_per_key(records[puts])
        db.tombstones = len(records) - int(puts.sum())
        last = last_per_key(records)
        for nid in last["nid"][~is_put(last)].tolist():
            db.delete(nid)
        return db

    def save_to_idx(self, path: str) -> int:
        """Write the live entries ascending by key; returns the bytes."""
        live = self._live()
        with open(path, "wb") as f:
            live.tofile(f)
        return live.nbytes


def walk_index_file(idx_path: str, offset_width: int = OFFSET_SIZE):
    """Stream (needle_id, offset, size) from a .idx file — 16B records
    with 4-byte offsets, 17B with 5-byte
    (reference weed/storage/idx/walk.go:14)."""
    rec = entry_size(offset_width)
    with open(idx_path, "rb") as f:
        while True:
            chunk = f.read(rec * 1024)
            if not chunk:
                break
            for i in range(0, len(chunk) - rec + 1, rec):
                yield bytes_to_entry(chunk[i:i + rec])
