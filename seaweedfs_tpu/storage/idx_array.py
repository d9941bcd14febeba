"""An index file read as one record array.

A `.idx` log and a sorted `.ecx` are packed big-endian records: an
8-byte key, the STORED offset (real byte offset / 8, reference
types/needle_types.go) in 4 or 5 bytes, a 4-byte size. The file
already is the array; the dtype of its record width views it, and
whoever loads an index (a needle map, the native plane's mirror, the
`.ecx` build of a sealing volume: needle_map.MemDb) takes columns from
that view instead of a record a Python iteration
(needle_map.walk_index_file, which stays for the callers that stream).

The width is the volume's own (`offset_width`, from its superblock):
one algorithm, a dtype chosen by a parameter read from the input.
"""

from __future__ import annotations

import os

import numpy as np

from .types import NEEDLE_PADDING_SIZE, TOMBSTONE_FILE_SIZE

IDX_DTYPE = np.dtype([("nid", ">u8"), ("off", ">u4"), ("size", ">u4")])
# 5-byte offsets are plain big-endian (types.offset_to_bytes): a high
# byte, then the low four
IDX_DTYPE_5 = np.dtype([("nid", ">u8"), ("off_hi", "u1"),
                        ("off", ">u4"), ("size", ">u4")])
_DTYPES = {4: IDX_DTYPE, 5: IDX_DTYPE_5}


def records_of(raw, offset_width: int = 4) -> np.ndarray:
    """View bytes as index records (no copy); a trailing partial
    record is left out, as walk_index_file leaves it."""
    dtype = _DTYPES[offset_width]
    return np.frombuffer(raw, dtype=dtype, count=len(raw) // dtype.itemsize)


def read_idx_records(idx_path: str, offset_width: int = 4) -> np.ndarray:
    """Every whole record of an index file; none where it is missing."""
    if not os.path.exists(idx_path):
        return np.empty(0, dtype=_DTYPES[offset_width])
    with open(idx_path, "rb") as f:
        return records_of(f.read(), offset_width)


def stored_offsets(records: np.ndarray) -> np.ndarray:
    """The records' stored offsets as native uint64."""
    low = records["off"].astype(np.uint64)
    if "off_hi" in records.dtype.names:
        return (records["off_hi"].astype(np.uint64) << np.uint64(32)) | low
    return low


def columns(records: np.ndarray):
    """(keys uint64, real byte offsets uint64, sizes uint32): native,
    contiguous arrays of every record, tombstones included — what
    swhp_put_bulk / swhp_ec_put_bulk take, and bytes_to_entry's values
    entry for entry."""
    return (records["nid"].astype(np.uint64),
            stored_offsets(records) * np.uint64(NEEDLE_PADDING_SIZE),
            records["size"].astype(np.uint32))


def is_put(records: np.ndarray) -> np.ndarray:
    """Mask of the records that name a needle: neither a tombstone size
    nor a zero offset (NeedleMap._apply's rule)."""
    return (records["size"] != TOMBSTONE_FILE_SIZE) & \
        (stored_offsets(records) != 0)


def last_per_key(records: np.ndarray) -> np.ndarray:
    """The last record of every key, ascending by key. One unstable
    sort of the keys (a stable one takes five times as long): a key's
    records come out side by side in any order, and the last of them is
    the largest position of the run."""
    if not len(records):
        return records
    keys = records["nid"].astype(np.uint64)
    order = np.argsort(keys)
    keys = keys[order]
    starts = np.flatnonzero(np.concatenate(([True], keys[1:] != keys[:-1])))
    return records[np.maximum.reduceat(order, starts)]


def replay_idx(records: np.ndarray):
    """One-pass replay of a `.idx` log: returns (live records sorted by
    key, counters dict). Last event per needle wins; counters match the
    dict map's event-tally semantics exactly:
      deletion_counter = puts - live,  deletion_bytes = put_bytes - live_bytes
    (every non-final put is superseded exactly once; deletes of dead
    needles tally nothing — same as NeedleMap._apply)."""
    counters = {"file_counter": 0, "file_byte_counter": 0,
                "deletion_counter": 0, "deletion_byte_counter": 0,
                "maximum_file_key": 0}
    n = len(records)
    if n == 0:
        return records, counters
    puts = is_put(records)
    counters["maximum_file_key"] = int(records["nid"].max())
    counters["file_counter"] = int(puts.sum())
    counters["file_byte_counter"] = int(
        records["size"][puts].sum(dtype=np.uint64))
    last = last_per_key(records)
    live = last[is_put(last)]
    counters["deletion_counter"] = \
        counters["file_counter"] - len(live)
    counters["deletion_byte_counter"] = \
        counters["file_byte_counter"] - int(live["size"].sum(dtype=np.uint64))
    return live, counters
