"""RAM-bounded needle maps (reference weed/storage/needle_map/).

The dict-backed NeedleMap costs ~100+ bytes of heap per needle; a 30GB
volume of 4KB needles (~7.5M needles) would pin GBs of RAM per volume.
The reference solves this with CompactMap (sectioned sorted arrays,
compact_map.go:10-37) and a sorted-file map backed by disk
(needle_map_sorted_file.go). The numpy-native equivalents here:

  * CompactNeedleMap — three parallel sorted numpy columns
    (nid u8, offset u4, size u4 = 16B/needle) + a small dict overflow
    for recent writes, merged down when it grows. Lookup is a binary
    search (np.searchsorted); bulk load parses the whole .idx in one
    vectorized pass (no per-record Python loop).
  * SortedFileNeedleMap — the same sorted columns written to a .sdx
    sidecar and memory-mapped, so steady-state RAM is page cache only;
    deletes tombstone the mapped record in place (like the reference's
    sorted-file markAsDeleted); new writes go to a dict overflow.

Both share the .idx append-log write-through discipline and the counter
semantics of NeedleMap (file/deletion counters tally events, not live
entries), so Volume can swap them per its -index flag.
"""

from __future__ import annotations

import os
import time
from typing import Iterator, Optional, Tuple

import numpy as np

from .idx_array import IDX_DTYPE, columns, read_idx_records, replay_idx
from .needle_map import NeedleValue, entry_to_bytes
from .types import NEEDLE_PADDING_SIZE, TOMBSTONE_FILE_SIZE

_DELETED = NeedleValue(0, TOMBSTONE_FILE_SIZE)  # overflow tombstone marker


class _SortedBase:
    """Shared: sorted record array + dict overflow + .idx write-through."""

    MERGE_THRESHOLD = 8192

    def __init__(self, idx_path: Optional[str] = None):
        self._base = np.empty(0, dtype=IDX_DTYPE)
        self._overflow: dict = {}
        self.idx_path = idx_path
        self._idx_file = open(idx_path, "ab") if idx_path else None
        self.file_counter = 0
        self.file_byte_counter = 0
        self.deletion_counter = 0
        self.deletion_byte_counter = 0
        self.maximum_file_key = 0

    # -- lookup ------------------------------------------------------------
    def _base_find(self, nid: int) -> int:
        """Index of nid in the sorted base, or -1."""
        base = self._base
        if len(base) == 0:
            return -1
        i = int(np.searchsorted(base["nid"], nid))
        if i < len(base) and int(base["nid"][i]) == nid:
            return i
        return -1

    def get(self, nid: int) -> Optional[NeedleValue]:
        ov = self._overflow.get(nid)
        if ov is not None:
            return None if ov is _DELETED else ov
        i = self._base_find(nid)
        if i < 0:
            return None
        size = int(self._base["size"][i])
        off = int(self._base["off"][i])
        # off == 0 marks an in-place sorted-file tombstone (size kept for
        # deleted-byte accounting); no live needle sits at stored offset 0
        if size == TOMBSTONE_FILE_SIZE or off == 0:
            return None
        return NeedleValue(off * NEEDLE_PADDING_SIZE, size)

    def _live_mask(self) -> np.ndarray:
        return (self._base["size"] != TOMBSTONE_FILE_SIZE) & \
            (self._base["off"] != 0)

    def __contains__(self, nid: int) -> bool:
        return self.get(nid) is not None

    def __len__(self) -> int:
        # live = unshadowed live base entries + live overflow entries
        base_live = int(self._live_mask().sum()) if len(self._base) else 0
        shadowed = sum(1 for nid in self._overflow if self._base_live(nid))
        live_ov = sum(1 for ov in self._overflow.values()
                      if ov is not _DELETED)
        return base_live - shadowed + live_ov

    def _base_live(self, nid: int) -> bool:
        i = self._base_find(nid)
        return i >= 0 and \
            int(self._base["size"][i]) != TOMBSTONE_FILE_SIZE and \
            int(self._base["off"][i]) != 0

    def items(self) -> Iterator[Tuple[int, NeedleValue]]:
        for rec in self._base:
            nid = int(rec["nid"])
            if nid in self._overflow:
                continue
            size = int(rec["size"])
            off = int(rec["off"])
            if size != TOMBSTONE_FILE_SIZE and off != 0:
                yield nid, NeedleValue(off * NEEDLE_PADDING_SIZE, size)
        for nid, ov in self._overflow.items():
            if ov is not _DELETED:
                yield nid, ov

    def _live_records(self) -> np.ndarray:
        """The live set as one record array: the base's live records
        and, where the overflow holds anything, its live entries in
        place of the base records they shadow, ascending by key."""
        live = self._base[self._live_mask()]
        if not self._overflow:
            return live
        recent = self._overflow
        shadowed = np.isin(live["nid"].astype(np.uint64),
                           np.fromiter(recent, np.uint64, len(recent)))
        more = np.array([(nid, nv.offset // NEEDLE_PADDING_SIZE, nv.size)
                         for nid, nv in recent.items()
                         if nv is not _DELETED], dtype=IDX_DTYPE)
        merged = np.concatenate((live[~shadowed], more))
        merged.sort(order="nid")
        return merged

    def live_columns(self):
        """The live set as (keys uint64, byte offsets uint64, sizes
        uint32) arrays, NeedleMap.live_columns's contract: what the
        native plane's mirror takes in one bulk put (call under the
        volume lock). Straight after a load the overflow is empty and
        these are the base's own columns."""
        return columns(self._live_records())

    # -- mutations ---------------------------------------------------------
    def put(self, nid: int, offset: int, size: int):
        old = self.get(nid)
        self.maximum_file_key = max(self.maximum_file_key, nid)
        self.file_counter += 1
        self.file_byte_counter += size
        if old is not None:
            self.deletion_counter += 1
            self.deletion_byte_counter += old.size
        self._overflow[nid] = NeedleValue(offset, size)
        self._maybe_merge()
        if self._idx_file is not None:
            self._idx_file.write(entry_to_bytes(nid, offset, size))
            self._idx_file.flush()

    def delete(self, nid: int):
        old = self.get(nid)
        if old is not None:
            self.deletion_counter += 1
            self.deletion_byte_counter += old.size
            self._tombstone(nid)
        if self._idx_file is not None:
            self._idx_file.write(entry_to_bytes(nid, 0, TOMBSTONE_FILE_SIZE))
            self._idx_file.flush()

    def _tombstone(self, nid: int):
        self._overflow[nid] = _DELETED
        self._maybe_merge()

    def _maybe_merge(self):
        pass  # CompactNeedleMap folds the overflow down; mmap variant keeps it

    @property
    def content_size(self) -> int:
        return self.file_byte_counter

    @property
    def deleted_size(self) -> int:
        return self.deletion_byte_counter

    def sync(self):
        """fdatasync the .idx append log (SW_PLANE_FSYNC_MODE parity
        with NeedleMap.sync)."""
        if self._idx_file is not None:
            os.fdatasync(self._idx_file.fileno())

    def close(self):
        if self._idx_file is not None:
            self._idx_file.close()
            self._idx_file = None


class CompactNeedleMap(_SortedBase):
    """Sorted-column map, ~16B/needle steady state."""

    kind = "compact"

    @classmethod
    def load(cls, idx_path: str) -> "CompactNeedleMap":
        from ..ops import telemetry
        t0 = time.perf_counter()
        nm = cls.__new__(cls)
        _SortedBase.__init__(nm, None)
        records = read_idx_records(idx_path)
        live, counters = replay_idx(records)
        nm._base = live
        nm.__dict__.update(counters)
        nm.idx_path = idx_path
        nm._idx_file = open(idx_path, "ab")
        telemetry.STATS.add_mirror(len(records), time.perf_counter() - t0)
        return nm

    def _maybe_merge(self):
        if len(self._overflow) >= self.MERGE_THRESHOLD:
            self._base = self._live_records()
            self._overflow = {}

    @property
    def index_nbytes(self) -> int:
        """Steady-state footprint of the index arrays (diagnostics)."""
        return self._base.nbytes


class SortedFileNeedleMap(_SortedBase):
    """Binary search over an mmap'd .sdx sidecar; RAM = page cache.

    Freshness protocol: a ``.sdx.meta`` sidecar records the .idx byte
    size the .sdx covers plus the counters. On load, if the .idx hasn't
    grown past that watermark the .sdx is mmap'd as-is — no replay, no
    rewrite (the large-readonly-volume fast path). Otherwise one
    vectorized .idx replay regenerates it. Deletes tombstone the mapped
    record in place by zeroing its offset (size stays for deleted-byte
    accounting) and advance the watermark, so a delete-only session
    still reloads without a replay. New writes live in the dict
    overflow and invalidate the meta — the map is meant for
    rarely-written (readonly/EC-bound) volumes.
    """

    kind = "sortedfile"

    @classmethod
    def load(cls, idx_path: str) -> "SortedFileNeedleMap":
        import json
        nm = cls.__new__(cls)
        _SortedBase.__init__(nm, None)
        sdx_path = os.path.splitext(idx_path)[0] + ".sdx"
        meta_path = sdx_path + ".meta"
        nm.idx_path = idx_path
        nm.sdx_path = sdx_path
        nm.meta_path = meta_path
        idx_size = os.path.getsize(idx_path) \
            if os.path.exists(idx_path) else 0
        # the .idx bytes this map has seen: what it loaded plus its own
        # appends, not the file's size — under the native plane's write
        # lease the log grows behind the map, and a watermark taken from
        # the file would vouch for records the .sdx never saw
        nm._idx_seen = idx_size
        meta = None
        if os.path.exists(meta_path) and os.path.exists(sdx_path):
            try:
                with open(meta_path) as f:
                    candidate = json.load(f)
                if candidate.get("idx_size") == idx_size:
                    meta = candidate
            except (ValueError, OSError):
                meta = None
        if meta is not None:  # fast path: mmap the existing sidecar
            for k in ("file_counter", "file_byte_counter",
                      "deletion_counter", "deletion_byte_counter",
                      "maximum_file_key"):
                setattr(nm, k, int(meta.get(k, 0)))
        else:
            live, counters = replay_idx(read_idx_records(idx_path))
            nm.__dict__.update(counters)
            live.tofile(sdx_path)
        if os.path.getsize(sdx_path) if os.path.exists(sdx_path) else 0:
            nm._base = np.memmap(sdx_path, dtype=IDX_DTYPE, mode="r+")
        else:
            nm._base = np.empty(0, dtype=IDX_DTYPE)
        nm._idx_file = open(idx_path, "ab")
        nm._save_meta()
        return nm

    def _save_meta(self):
        """Valid only while every mutation since is reflected in the
        .sdx itself (i.e. the overflow is empty)."""
        import json
        if self._overflow:
            if os.path.exists(self.meta_path):
                os.remove(self.meta_path)
            return
        if isinstance(self._base, np.memmap):
            # the watermark asserts the .sdx covers the .idx — in-place
            # tombstones must be durable BEFORE the meta says so, or a
            # crash resurrects the needle on the no-replay fast path
            self._base.flush()
        self._idx_file.flush()
        state = {"idx_size": self._idx_seen,
                 "file_counter": self.file_counter,
                 "file_byte_counter": self.file_byte_counter,
                 "deletion_counter": self.deletion_counter,
                 "deletion_byte_counter": self.deletion_byte_counter,
                 "maximum_file_key": self.maximum_file_key}
        with open(self.meta_path, "w") as f:
            json.dump(state, f)

    def _tombstone(self, nid: int):
        i = self._base_find(nid)
        if i >= 0 and isinstance(self._base, np.memmap):
            self._base["off"][i] = 0  # in-place on disk; size kept
            self._overflow.pop(nid, None)
        else:
            self._overflow[nid] = _DELETED

    def put(self, nid: int, offset: int, size: int):
        super().put(nid, offset, size)
        self._idx_seen += IDX_DTYPE.itemsize

    def delete(self, nid: int):
        super().delete(nid)
        self._idx_seen += IDX_DTYPE.itemsize
        self._save_meta()  # advance the watermark past the tombstone

    def close(self):
        if isinstance(self._base, np.memmap):
            self._base.flush()
        if self._idx_file is not None:
            self._save_meta()
        super().close()


NEEDLE_MAP_KINDS = {"memory", "compact", "sortedfile", "disk"}


class SnapshotItems:
    """Uniform closeable handle over a live-set snapshot: either the
    disk map's private-connection cursor or a plain in-memory list.
    Iterate it directly, or use as a context manager / call close() in
    a finally so the sqlite WAL snapshot connection is released the
    moment the walk ends rather than at GC (a pinned snapshot blocks
    checkpoint truncation for as long as it lives)."""

    def __init__(self, items):
        self._items = items

    def __iter__(self):
        return iter(self._items)

    def close(self):
        close = getattr(self._items, "close", None)
        self._items = ()
        if close is not None:
            close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


def snapshot_live_items(nm, by_offset: bool = False) -> SnapshotItems:
    """Consistent live-set snapshot of ANY needle-map variant; the
    caller must hold the volume lock across this call. Disk maps
    flush pending state then stream from a pinned private-connection
    snapshot (RAM-bounded — flush-before-read is mandatory and lives
    HERE so no caller can forget it); in-memory maps list-copy.
    by_offset orders by .dat offset (the vacuum merge-walk's need);
    leave it False where order doesn't matter — for the disk map that
    skips a whole-table sort. Close the returned handle (context
    manager or try/finally) when done."""
    snap = getattr(nm, "items_snapshot", None)
    if snap is not None:
        nm.flush()
        return SnapshotItems(snap(by_offset=by_offset))
    items = list(nm.items())
    if by_offset:
        items.sort(key=lambda kv: kv[1].offset)
    return SnapshotItems(items)


def load_needle_map(idx_path: str, kind: str = "memory",
                    offset_width: int = 4):
    """Factory selecting the needle-map variant, like the reference's
    volume -index flag (memory | compact | sortedfile | disk —
    the last mirroring -index leveldb, needle_map_leveldb.go:15-120).

    5-byte-offset volumes (17B .idx records) use the dict map unless
    the disk map was asked for: the numpy fast paths here are wired for
    the 16B layout, and the disk map is exactly the variant meant for
    volumes too big to hold an in-RAM index.

    The kind is the caller's: a Volume asks for its configured kind,
    except that a frozen (read-only) volume of a memory index reloads
    into the compact map and gets the dict back when it is thawed
    (storage/volume.Volume._reload_kind).
    """
    if kind == "disk":
        from .needle_map_disk import DiskNeedleMap
        return DiskNeedleMap.load(idx_path, offset_width)
    if offset_width != 4:
        from .needle_map import NeedleMap
        return NeedleMap.load(idx_path, offset_width)
    if kind == "memory":
        from .needle_map import NeedleMap
        return NeedleMap.load(idx_path)
    if kind == "compact":
        return CompactNeedleMap.load(idx_path)
    if kind == "sortedfile":
        return SortedFileNeedleMap.load(idx_path)
    raise ValueError(f"unknown needle map kind {kind!r} "
                     f"(want one of {sorted(NEEDLE_MAP_KINDS)})")
