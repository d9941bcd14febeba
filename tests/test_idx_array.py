"""An index read as one record array (storage/idx_array) equals the
record loop it replaced, which stays here as the plain reference:
NeedleMap.load entry for entry and counter for counter, the three
arrays the native plane's .ecx mirror is handed, and the .ecx a sealing
volume's log leaves (ec/encoder.write_sorted_file_from_idx through
needle_map.MemDb), against a replay written here."""

import ctypes
import io
import os
import random
import threading
from types import SimpleNamespace

import numpy as np
import pytest

from seaweedfs_tpu.ec.encoder import write_sorted_file_from_idx
from seaweedfs_tpu.ops import telemetry
from seaweedfs_tpu.server.native_plane import NativeReadPlane
from seaweedfs_tpu.storage import idx_array, needle_map
from seaweedfs_tpu.storage.needle_map import (MemDb, NeedleMap,
                                              bytes_to_entry,
                                              entry_to_bytes,
                                              walk_index_file)
from seaweedfs_tpu.storage.super_block import (FLAG_5_BYTE_OFFSETS,
                                               SuperBlock)
from seaweedfs_tpu.storage.types import TOMBSTONE_FILE_SIZE, entry_size
from seaweedfs_tpu.util import tracing
from seaweedfs_tpu.util.profiling import StageTimer

COUNTERS = ("file_counter", "file_byte_counter", "deletion_counter",
            "deletion_byte_counter", "maximum_file_key")


def load_by_record_loop(idx_path: str, offset_width: int) -> NeedleMap:
    """NeedleMap.load as it was: walk_index_file + _apply a record."""
    nm = NeedleMap(None, offset_width)
    if os.path.exists(idx_path):
        for nid, offset, size in walk_index_file(idx_path, offset_width):
            nm._apply(nid, offset, size)
    return nm


def random_log(seed: int, offset_width: int, records: int) -> bytes:
    """A seeded .idx log over a small key space, so keys are overwritten,
    deleted, put again after a delete and deleted while absent; some
    puts carry a zero offset (a delete by the map's rule), and 17-byte
    records reach offsets only the fifth byte holds."""
    rng = random.Random(seed)
    keys = max(4, records // 3)
    top = (1 << (8 * offset_width)) - 1
    out = []
    for _ in range(records):
        key = rng.randrange(1, keys) if rng.random() < 0.97 \
            else rng.randrange(1 << 40, 1 << 63)
        kind = rng.random()
        if kind < 0.62:
            stored = rng.randrange(1, top + 1)
            out.append(entry_to_bytes(key, stored * 8,
                                      rng.randrange(0, 1 << 24),
                                      offset_width))
        elif kind < 0.70:   # a zero offset with a live size
            out.append(entry_to_bytes(key, 0, rng.randrange(1, 4096),
                                      offset_width))
        elif kind < 0.75:   # a tombstone size at a live offset
            out.append(entry_to_bytes(key, rng.randrange(1, top + 1) * 8,
                                      TOMBSTONE_FILE_SIZE, offset_width))
        else:
            out.append(entry_to_bytes(key, 0, TOMBSTONE_FILE_SIZE,
                                      offset_width))
    return b"".join(out)


LOGS = {
    "missing": None,
    "empty": lambda w: b"",
    "partial-only": lambda w: entry_to_bytes(7, 64, 9, w)[:-3],
    "one-put": lambda w: entry_to_bytes(7, 64, 9, w),
    "delete-of-absent": lambda w: entry_to_bytes(5, 0, TOMBSTONE_FILE_SIZE,
                                                 w),
    "put-delete-put": lambda w: b"".join((
        entry_to_bytes(3, 8, 100, w),
        entry_to_bytes(3, 0, TOMBSTONE_FILE_SIZE, w),
        entry_to_bytes(3, 800, 50, w))),
    "trailing-partial": lambda w: random_log(11, w, 300) + b"\x00" * 7,
    "seed-1": lambda w: random_log(1, w, 40),
    "seed-2": lambda w: random_log(2, w, 2500),
    "seed-3": lambda w: random_log(3, w, 5000),
    "seed-4": lambda w: random_log(2147485021, w, 1025),
}


@pytest.mark.parametrize("offset_width", [4, 5])
@pytest.mark.parametrize("log", list(LOGS))
def test_needle_map_load_equals_the_record_loop(tmp_path, log, offset_width):
    path = str(tmp_path / "v.idx")
    if LOGS[log] is not None:
        with open(path, "wb") as f:
            f.write(LOGS[log](offset_width))
    want = load_by_record_loop(path, offset_width)
    before = telemetry.STATS.snapshot()
    got = NeedleMap.load(path, offset_width)
    moved = telemetry.delta(before)
    try:
        assert {k: (v.offset, v.size) for k, v in got.items()} == \
            {k: (v.offset, v.size) for k, v in want.items()}
        assert list(got._m) == sorted(want._m)     # ascending by key
        for name in COUNTERS:
            assert getattr(got, name) == getattr(want, name), name
        assert got.offset_width == offset_width and got.idx_path == path
        whole = os.path.getsize(path) // entry_size(offset_width)
        assert moved["mirror_entries"] == whole
        assert moved["mirror_loop_entries"] == 0
        # the map that was loaded appends where the log ended
        got.put(99, 8 * 99, 5)
        assert got.get(99).offset == 8 * 99 and \
            got.file_counter == want.file_counter + 1
        keys, offsets, sizes = got.live_columns()
        assert (keys.dtype, offsets.dtype, sizes.dtype) == \
            (np.uint64, np.uint64, np.uint32)
        assert list(zip(keys.tolist(), offsets.tolist(), sizes.tolist())) \
            == [(k, v.offset, v.size) for k, v in got.items()]
    finally:
        got.close()


class RecordingLib:
    """Stands in for libseaweed_http.so: keeps what each bulk put was
    handed, read back through the pointers as the library would."""

    def __init__(self):
        self.calls = []

    def swhp_ec_put_bulk(self, handle, vid, keys, offsets, sizes, count):
        def read(pointer, ctype):
            return list(ctypes.cast(pointer,
                                    ctypes.POINTER(ctype * count)).contents)
        self.calls.append((vid, read(keys, ctypes.c_uint64),
                           read(offsets, ctypes.c_uint64),
                           read(sizes, ctypes.c_uint32)))
        return 0


def plane_over(lib) -> NativeReadPlane:
    plane = NativeReadPlane.__new__(NativeReadPlane)
    plane._lib, plane._h = lib, 1
    return plane


def ec_volume_of(raw: bytes, offset_width: int, size=None):
    return SimpleNamespace(vid=9, offset_width=offset_width,
                           ecx_lock=threading.Lock(),
                           ecx_file=io.BytesIO(raw),
                           ecx_size=len(raw) if size is None else size)


@pytest.mark.parametrize("offset_width", [4, 5])
def test_ecx_mirror_is_handed_the_loops_arrays(offset_width):
    rng = random.Random(offset_width)
    top = (1 << (8 * offset_width)) - 1
    entries = []
    for key in sorted(rng.sample(range(1, 1 << 62), 700)):
        if rng.random() < 0.2:      # deleted in place: the size alone
            entries.append((key, rng.randrange(1, top + 1) * 8,
                            TOMBSTONE_FILE_SIZE))
        else:
            entries.append((key, rng.randrange(1, top + 1) * 8,
                            rng.randrange(0, TOMBSTONE_FILE_SIZE)))
    entries[0] = (entries[0][0], top * 8, 1)
    raw = b"".join(entry_to_bytes(*e, offset_width) for e in entries)
    rec = entry_size(offset_width)
    # the loop _bulk_load_ecx was: bytes_to_entry a slice of the snapshot
    want = [bytes_to_entry(raw[pos:pos + rec])
            for pos in range(0, len(raw) - rec + 1, rec)]
    assert want == entries
    lib = RecordingLib()
    before = telemetry.STATS.snapshot()
    # ecx_size, not the file's length, bounds the snapshot
    ev = ec_volume_of(raw + b"\xff" * rec, offset_width, size=len(raw))
    assert plane_over(lib)._bulk_load_ecx(ev)
    moved = telemetry.delta(before)
    (vid, keys, offsets, sizes), = lib.calls
    assert vid == 9
    assert list(zip(keys, offsets, sizes)) == want
    assert moved["mirror_entries"] == len(want)
    assert moved["mirror_loop_entries"] == 0


def test_ecx_mirror_of_an_empty_index_calls_nothing():
    lib = RecordingLib()
    assert plane_over(lib)._bulk_load_ecx(ec_volume_of(b"", 4))
    assert lib.calls == []


def test_ecx_mirror_goes_in_chunks_of_at_most_2_pow_20():
    n = (1 << 20) + 5
    records = np.zeros(n, dtype=idx_array.IDX_DTYPE)
    records["nid"] = np.arange(1, n + 1)
    records["off"] = np.arange(n) % 1000 + 1
    records["size"] = np.arange(n) % 77
    lib = RecordingLib()
    assert plane_over(lib)._bulk_load_ecx(ec_volume_of(records.tobytes(), 4))
    assert [len(call[1]) for call in lib.calls] == [1 << 20, 5]
    assert lib.calls[0][1][:2] == [1, 2] and lib.calls[1][1] == \
        list(range((1 << 20) + 1, n + 1))
    assert lib.calls[1][2] == [(i % 1000 + 1) * 8
                               for i in range(1 << 20, n)]
    assert lib.calls[1][3] == [i % 77 for i in range(1 << 20, n)]


# -- the .ecx of a sealing volume ------------------------------------------

def replay_by_record_loop(log: bytes, offset_width: int, deletes=True):
    """(.ecx bytes, records dropped) a log must leave, read a record at
    a time from the bytes alone: a later record of a key replaces the
    earlier, a tombstone size or a zero offset removes the key — or,
    with ``deletes`` off, is skipped, as a builder that forgot what a
    delete means would."""
    rec = 8 + offset_width + 4
    live, dropped = {}, 0
    for at in range(0, len(log) - rec + 1, rec):
        key = int.from_bytes(log[at:at + 8], "big")
        stored = int.from_bytes(log[at + 8:at + 8 + offset_width], "big")
        size = int.from_bytes(log[at + rec - 4:at + rec], "big")
        if size == TOMBSTONE_FILE_SIZE or stored == 0:
            dropped += 1
            if deletes:
                live.pop(key, None)
        else:
            live[key] = log[at:at + rec]
    return b"".join(live[key] for key in sorted(live)), dropped


def tombstone(key: int, w: int) -> bytes:
    return entry_to_bytes(key, 0, TOMBSTONE_FILE_SIZE, w)


ECX_LOGS = {
    "empty": lambda w: b"",
    "keys-out-of-order": lambda w: b"".join(
        entry_to_bytes(key, 8 * (at + 1), key % 7, w)
        for at, key in enumerate((9, 2, 1 << 63, 5, 1, 70000))),
    "key-put-twice": lambda w: b"".join((
        entry_to_bytes(4, 8, 1, w), entry_to_bytes(6, 16, 2, w),
        entry_to_bytes(4, 24, 3, w))),
    "put-delete": lambda w: b"".join((
        entry_to_bytes(6, 16, 2, w), entry_to_bytes(4, 8, 1, w),
        tombstone(6, w))),
    "put-delete-put": LOGS["put-delete-put"],
    "delete-of-absent": lambda w: b"".join((
        tombstone(5, w), entry_to_bytes(4, 8, 1, w))),
    "zero-offset": lambda w: b"".join((
        entry_to_bytes(4, 8, 1, w), entry_to_bytes(6, 16, 2, w),
        entry_to_bytes(4, 0, 77, w))),
    "only-tombstones": lambda w: b"".join((
        tombstone(5, w), entry_to_bytes(2, 0, 3, w), tombstone(5, w))),
    "trailing-partial": LOGS["trailing-partial"],
    "top-offset": lambda w: entry_to_bytes(
        1, ((1 << (8 * w)) - 1) * 8, TOMBSTONE_FILE_SIZE - 1, w),
    "seed-2": LOGS["seed-2"],
    "seed-4": LOGS["seed-4"],
}


def sealing_volume(tmp_path, log: bytes, offset_width: int) -> str:
    """A volume's base name with the two files the build reads: the
    superblock that names the width, and the log."""
    base = str(tmp_path / "7")
    flags = FLAG_5_BYTE_OFFSETS if offset_width == 5 else 0
    with open(base + ".dat", "wb") as f:
        f.write(SuperBlock(flags=flags).to_bytes())
    with open(base + ".idx", "wb") as f:
        f.write(log)
    return base


def build_ecx(base: str):
    """write_sorted_file_from_idx under a stream's timer: (the .ecx, its
    span, what the process counted)."""
    got = []
    tracing.add_finish_hook(got.append)
    before = telemetry.STATS.snapshot()
    try:
        with tracing.span("ec.encode.stream") as root:
            timer = StageTimer(root=root)
            write_sorted_file_from_idx(base, timer=timer)
    finally:
        tracing.remove_finish_hook(got.append)
    span, = (s for s in got if s["name"] == "ec.encode.index")
    assert timer.totals["index"] == pytest.approx(span["duration_s"])
    with open(base + ".ecx", "rb") as f:
        return f.read(), span, telemetry.delta(before)


@pytest.mark.parametrize("offset_width", [4, 5])
@pytest.mark.parametrize("log", list(ECX_LOGS))
def test_the_ecx_is_the_logs_replay(tmp_path, log, offset_width):
    raw = ECX_LOGS[log](offset_width)
    want, dropped = replay_by_record_loop(raw, offset_width)
    ecx, span, moved = build_ecx(sealing_volume(tmp_path, raw, offset_width))
    assert ecx == want
    entries = len(want) // entry_size(offset_width)
    assert span["tags"]["entries"] == moved["index_entries"] == entries
    assert span["tags"]["tombstones"] == dropped
    assert span["tags"]["bytes"] == len(want)
    assert moved["index_us"] == pytest.approx(span["duration_s"] * 1e6,
                                              abs=1)


def test_a_volume_without_its_log_builds_no_ecx(tmp_path):
    base = sealing_volume(tmp_path, b"", 4)
    os.remove(base + ".idx")
    with pytest.raises(FileNotFoundError):
        write_sorted_file_from_idx(base)
    assert not os.path.exists(base + ".ecx")


@pytest.mark.parametrize("offset_width", [4, 5])
def test_a_delete_reaches_the_ecx_through_memdb_delete(
        tmp_path, monkeypatch, offset_width):
    """The seam the benchmark's control `keep_tombstones_in_ecx` breaks
    the program at: with MemDb.delete a no-op every deleted key stays in
    the .ecx with the offset and size of its last put."""
    raw = b"".join((
        random_log(5, offset_width, 900),   # its keys are all over 0
        entry_to_bytes(0, 16, 2, offset_width),
        entry_to_bytes(1 << 63, 24, 3, offset_width),
        entry_to_bytes(0, 32, 5, offset_width),
        tombstone(0, offset_width),
        entry_to_bytes(1 << 63, 0, 3, offset_width),
        tombstone((1 << 63) + 1, offset_width)))
    base = sealing_volume(tmp_path, raw, offset_width)
    sound, _, _ = build_ecx(base)
    assert sound == replay_by_record_loop(raw, offset_width)[0]
    monkeypatch.setattr(needle_map.MemDb, "delete", lambda self, nid: None)
    broken, span, _ = build_ecx(base)
    want, dropped = replay_by_record_loop(raw, offset_width, deletes=False)
    assert broken == want != sound
    kept = {nid: (offset, size) for nid, offset, size
            in walk_index_file(base + ".ecx", offset_width)}
    assert kept[0] == (32, 5) and kept[1 << 63] == (24, 3)
    assert (1 << 63) + 1 not in kept
    assert span["tags"]["tombstones"] == dropped
    assert span["tags"]["entries"] == len(kept)


@pytest.mark.parametrize("offset_width", [4, 5])
def test_memdb_drops_the_keys_deleted_after_a_load(tmp_path, offset_width):
    raw = random_log(9, offset_width, 600)
    path = str(tmp_path / "v.idx")
    with open(path, "wb") as f:
        f.write(raw)
    db = MemDb.load_from_idx(path, offset_width)
    loaded, _ = replay_by_record_loop(raw, offset_width)
    rec = entry_size(offset_width)
    assert len(db) == len(loaded) // rec
    first, second = (int.from_bytes(loaded[at:at + 8], "big")
                     for at in (0, rec))
    db.delete(second)
    db.delete(12345678901)          # never put
    db.delete(first)
    db.delete(first)                # twice
    want, _ = replay_by_record_loop(
        raw + tombstone(first, offset_width) + tombstone(second,
                                                         offset_width),
        offset_width)
    assert want == loaded[2 * rec:]
    assert len(db) == len(want) // rec
    out = str(tmp_path / "v.ecx")
    assert db.save_to_idx(out) == len(want)
    with open(out, "rb") as f:
        assert f.read() == want
