"""Volume engine tests (reference volume_vacuum_test.go style)."""

import os

import numpy as np
import pytest

from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.needle_map import (MemDb, NeedleMap,
                                              entry_to_bytes,
                                              walk_index_file)
from seaweedfs_tpu.storage.types import TOMBSTONE_FILE_SIZE
from seaweedfs_tpu.storage.volume import NotFound, Volume


def _mk_needle(nid, size=100, seed=None):
    rng = np.random.default_rng(seed if seed is not None else nid)
    return Needle(cookie=0x1000 + nid, id=nid,
                  data=rng.integers(0, 256, size).astype(np.uint8).tobytes())


def test_volume_write_read_delete(tmp_path):
    v = Volume(str(tmp_path), "", 1, create=True)
    needles = [_mk_needle(i, 50 + i) for i in range(1, 20)]
    for n in needles:
        v.write_needle(n)
    for n in needles:
        got = v.read_needle(Needle(id=n.id, cookie=n.cookie))
        assert got.data == n.data
    # wrong cookie rejected
    with pytest.raises(NotFound):
        v.read_needle(Needle(id=1, cookie=0xBAD))
    # delete then read fails
    v.delete_needle(Needle(id=5, cookie=0x1005))
    with pytest.raises(NotFound):
        v.read_needle(Needle(id=5, cookie=0x1005))
    v.close()


def test_volume_reload_from_disk(tmp_path):
    v = Volume(str(tmp_path), "col", 7, create=True)
    for i in range(1, 11):
        v.write_needle(_mk_needle(i))
    v.delete_needle(Needle(id=3, cookie=0x1003))
    v.close()

    v2 = Volume(str(tmp_path), "col", 7)
    assert v2.file_count() == 10
    assert v2.deleted_count() >= 1
    for i in range(1, 11):
        if i == 3:
            with pytest.raises(NotFound):
                v2.read_needle(Needle(id=3, cookie=0x1003))
        else:
            got = v2.read_needle(Needle(id=i, cookie=0x1000 + i))
            assert got.data == _mk_needle(i).data
    assert v2.max_file_key() == 10
    v2.close()


def test_volume_overwrite_same_id(tmp_path):
    v = Volume(str(tmp_path), "", 2, create=True)
    v.write_needle(_mk_needle(1, seed=1))
    n2 = _mk_needle(1, size=200, seed=2)
    v.write_needle(n2)
    got = v.read_needle(Needle(id=1, cookie=0x1001))
    assert got.data == n2.data
    v.close()


def test_vacuum_reclaims_space(tmp_path):
    v = Volume(str(tmp_path), "", 3, create=True)
    for i in range(1, 31):
        v.write_needle(_mk_needle(i, 500))
    for i in range(1, 21):
        v.delete_needle(Needle(id=i, cookie=0x1000 + i))
    size_before = v.size()
    assert v.garbage_level() > 0.3
    v.compact()
    v.commit_compact()
    assert v.size() < size_before
    assert v.garbage_level() == 0.0
    assert v.file_count() == 10
    for i in range(21, 31):
        got = v.read_needle(Needle(id=i, cookie=0x1000 + i))
        assert got.data == _mk_needle(i, 500).data
    for i in range(1, 21):
        with pytest.raises(NotFound):
            v.read_needle(Needle(id=i, cookie=0x1000 + i))
    v.close()


def test_torn_tail_truncated(tmp_path):
    v = Volume(str(tmp_path), "", 4, create=True)
    v.write_needle(_mk_needle(1))
    v.close()
    # simulate a crash mid-append: garbage unaligned tail
    with open(v.dat_path, "ab") as f:
        f.write(b"\x01\x02\x03")
    v2 = Volume(str(tmp_path), "", 4)
    assert v2.size() % 8 == 0
    got = v2.read_needle(Needle(id=1, cookie=0x1001))
    assert got.data == _mk_needle(1).data
    v2.close()


def test_compact_survives_torn_aligned_garbage(tmp_path):
    """A torn-but-8-aligned garbage record in the .dat must not cause
    compact() to drop live needles appended after it."""
    v = Volume(str(tmp_path), "", 9, create=True)
    v.write_needle(_mk_needle(1))
    v.close()
    with open(v.dat_path, "ab") as f:
        f.write(b"\xde\xad\xbe\xef" * 6)  # 24 bytes, aligned garbage
    v2 = Volume(str(tmp_path), "", 9)
    for i in range(2, 6):
        v2.write_needle(_mk_needle(i))
    v2.delete_needle(Needle(id=2, cookie=0x1002))
    v2.compact()
    v2.commit_compact()
    assert v2.file_count() == 4
    for i in (1, 3, 4, 5):
        assert v2.read_needle(Needle(id=i, cookie=0x1000 + i)).data \
            == _mk_needle(i).data
    v2.close()


def test_idx_entry_past_dat_end_truncated(tmp_path):
    """Crash kept .idx pages but lost .dat pages: stale idx tail entries
    must be dropped at boot, surviving entries still readable."""
    v = Volume(str(tmp_path), "", 10, create=True)
    v.write_needle(_mk_needle(1))
    v.write_needle(_mk_needle(2))
    dat_size_after_1 = None
    v.close()
    # chop the .dat back to just after needle 1 (simulate lost pages)
    import os as _os
    nv1_end = None
    from seaweedfs_tpu.storage.needle_map import walk_index_file
    from seaweedfs_tpu.storage.needle import get_actual_size
    entries = list(walk_index_file(v.idx_path))
    nv1_end = entries[0][1] + get_actual_size(entries[0][2], 3)
    with open(v.dat_path, "r+b") as f:
        f.truncate(nv1_end)
    v2 = Volume(str(tmp_path), "", 10)
    assert v2.read_needle(Needle(id=1, cookie=0x1001)).data \
        == _mk_needle(1).data
    with pytest.raises(NotFound):
        v2.read_needle(Needle(id=2, cookie=0x1002))
    v2.close()


def test_needle_map_counters(tmp_path):
    p = str(tmp_path / "t.idx")
    nm = NeedleMap(p)
    nm.put(1, 8, 100)
    nm.put(2, 120, 200)
    nm.put(1, 328, 150)  # overwrite
    assert nm.file_counter == 3
    assert nm.deletion_counter == 1
    nm.delete(2)
    assert nm.get(2) is None
    assert nm.get(1).size == 150
    nm.close()
    # reload replays the idx log to identical state
    nm2 = NeedleMap.load(p)
    assert nm2.get(1).size == 150
    assert nm2.get(2) is None
    assert len(nm2) == 1
    entries = list(walk_index_file(p))
    assert entries[-1][2] == TOMBSTONE_FILE_SIZE
    nm2.close()


def test_memdb_sorted(tmp_path):
    log = str(tmp_path / "log.idx")
    with open(log, "wb") as f:
        for nid in (5, 1, 9, 3, 7):
            f.write(entry_to_bytes(nid, nid * 8, 10))
    db = MemDb.load_from_idx(log)
    assert len(db) == 5 and db.tombstones == 0
    db.delete(7)
    assert len(db) == 4
    p = str(tmp_path / "sorted.idx")
    assert db.save_to_idx(p) == 4 * 16
    assert list(walk_index_file(p)) == [(nid, nid * 8, 10)
                                        for nid in (1, 3, 5, 9)]
    assert MemDb().save_to_idx(p) == 0 and os.path.getsize(p) == 0


def test_volume_scan(tmp_path):
    v = Volume(str(tmp_path), "", 5, create=True)
    for i in range(1, 6):
        v.write_needle(_mk_needle(i))
    records = list(v.scan())
    assert [n.id for n, _ in records] == [1, 2, 3, 4, 5]
    v.close()
