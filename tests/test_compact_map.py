"""CompactNeedleMap / SortedFileNeedleMap vs the dict-backed NeedleMap
(reference needle_map/compact_map.go,
needle_map_sorted_file.go)."""

import os
import random

import numpy as np
import pytest

from seaweedfs_tpu.storage.compact_map import (CompactNeedleMap,
                                               SortedFileNeedleMap,
                                               load_needle_map)
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.needle_map import NeedleMap
from seaweedfs_tpu.storage.types import TOMBSTONE_FILE_SIZE
from seaweedfs_tpu.storage.volume import Volume

KINDS = ["compact", "sortedfile", "disk"]


def random_workload(nm, rng, n_ops=3000, key_space=500):
    """Apply an identical random put/delete stream to any map."""
    for _ in range(n_ops):
        nid = rng.randrange(1, key_space)
        if rng.random() < 0.25:
            nm.delete(nid)
        else:
            nm.put(nid, rng.randrange(1, 1 << 27) * 8,  # 8B-aligned offsets
                   rng.randrange(1, 65536))


def assert_maps_equal(a, b):
    assert len(a) == len(b)
    assert dict((k, (v.offset, v.size)) for k, v in a.items()) == \
        dict((k, (v.offset, v.size)) for k, v in b.items())
    for f in ("file_counter", "file_byte_counter", "deletion_counter",
              "deletion_byte_counter", "maximum_file_key"):
        assert getattr(a, f) == getattr(b, f), f


@pytest.mark.parametrize("kind", KINDS)
def test_random_workload_matches_dict_map(tmp_path, kind):
    ref = NeedleMap(str(tmp_path / "ref.idx"))
    nm = load_needle_map(str(tmp_path / "new.idx"), kind)
    # identical op streams (two rngs with the same seed)
    random_workload(ref, random.Random(5))
    random_workload(nm, random.Random(5))
    assert_maps_equal(ref, nm)
    # lookups agree, including misses
    for nid in range(1, 500):
        rv, cv = ref.get(nid), nm.get(nid)
        assert (rv is None) == (cv is None), nid
        if rv is not None:
            assert (rv.offset, rv.size) == (cv.offset, cv.size)


@pytest.mark.parametrize("kind", KINDS)
def test_cold_load_matches_dict_load(tmp_path, kind):
    """The vectorized .idx replay must equal the record-by-record one —
    counters included (last-wins, overwrite/delete tallies)."""
    path = str(tmp_path / "w.idx")
    nm = NeedleMap(path)
    random_workload(nm, random.Random(9), n_ops=5000)
    nm.close()
    ref = NeedleMap.load(path)
    cold = load_needle_map(path, kind)
    assert_maps_equal(ref, cold)


def test_compact_merge_threshold(tmp_path):
    nm = CompactNeedleMap.load(str(tmp_path / "m.idx"))
    nm.MERGE_THRESHOLD = 64
    for i in range(1, 200):
        nm.put(i, i * 8, 100)
    assert len(nm._overflow) < 64  # merged down at least twice
    assert len(nm) == 199
    nm.delete(50)
    assert nm.get(50) is None and len(nm) == 198


def test_footprint_16_bytes_per_needle(tmp_path):
    """1M-needle .idx loads into ~16B/needle of index arrays (the
    footprint bar), via the vectorized bulk path (no per-record loop)."""
    from seaweedfs_tpu.storage.compact_map import IDX_DTYPE
    n = 1_000_000
    arr = np.zeros(n, dtype=IDX_DTYPE)
    arr["nid"] = np.arange(1, n + 1)
    arr["off"] = np.arange(1, n + 1)
    arr["size"] = 4096
    path = str(tmp_path / "big.idx")
    arr.tofile(path)
    nm = CompactNeedleMap.load(path)
    assert len(nm) == n
    assert nm.index_nbytes == 16 * n
    assert nm.file_byte_counter == 4096 * n
    v = nm.get(123_456)
    assert v is not None and v.size == 4096
    nm.close()


def test_sorted_file_map_persistent_tombstone(tmp_path):
    path = str(tmp_path / "s.idx")
    nm = NeedleMap(path)
    for i in range(1, 100):
        nm.put(i, i * 8, 50)
    nm.close()
    sf = SortedFileNeedleMap.load(path)
    sf.delete(10)  # tombstones the mmap'd .sdx record in place
    assert sf.get(10) is None
    sf.close()
    # the delete also hit the .idx log, so any variant reloads without it
    again = load_needle_map(path, "memory")
    assert again.get(10) is None and len(again) == 98


@pytest.mark.parametrize("kind", KINDS)
def test_volume_roundtrip_with_index_kind(tmp_path, kind):
    """The existing volume lifecycle (write/read/overwrite/delete/vacuum/
    cold boot) on the alternative needle maps."""
    rng = np.random.default_rng(3)
    v = Volume(str(tmp_path), "", 1, create=True, index_kind=kind)
    payloads = {}
    for i in range(1, 60):
        data = rng.integers(0, 256, int(rng.integers(10, 5000))
                            ).astype(np.uint8).tobytes()
        v.write_needle(Needle(id=i, cookie=7, data=data))
        payloads[i] = data
    # overwrite + delete
    v.write_needle(Needle(id=5, cookie=7, data=b"fresh"))
    payloads[5] = b"fresh"
    v.delete_needle(Needle(id=9, cookie=7))
    del payloads[9]
    for i, data in payloads.items():
        assert v.read_needle(Needle(id=i, cookie=7)).data == data
    # vacuum keeps the survivors
    v.compact()
    v.commit_compact()
    for i, data in payloads.items():
        assert v.read_needle(Needle(id=i, cookie=7)).data == data
    v.close()
    # cold boot on the same kind
    v2 = Volume(str(tmp_path), "", 1, index_kind=kind)
    for i, data in payloads.items():
        assert v2.read_needle(Needle(id=i, cookie=7)).data == data
    assert v2.read_needle.__self__.nm.kind == kind \
        if hasattr(v2.nm, "kind") else True
    v2.close()


def test_sorted_file_fast_reload_skips_replay(tmp_path, monkeypatch):
    """Clean shutdown -> reload must mmap the existing .sdx (meta
    watermark matches) without replaying the .idx; delete-only sessions
    keep the fast path because in-place tombstones advance the meta."""
    import seaweedfs_tpu.storage.compact_map as cm
    path = str(tmp_path / "f.idx")
    nm = NeedleMap(path)
    for i in range(1, 500):
        nm.put(i, i * 8, 75)
    nm.close()
    sf = SortedFileNeedleMap.load(path)   # builds .sdx + meta
    sf.delete(42)                          # in-place tombstone
    counters = (sf.file_counter, sf.deletion_counter,
                sf.deletion_byte_counter)
    sf.close()

    def boom(_):
        raise AssertionError("full .idx replay on a fresh .sdx")

    monkeypatch.setattr(cm, "replay_idx", boom)
    again = SortedFileNeedleMap.load(path)
    assert again.get(42) is None and again.get(41).size == 75
    assert (again.file_counter, again.deletion_counter,
            again.deletion_byte_counter) == counters
    again.put(600, 4800, 10)  # a write invalidates the meta
    again.close()
    monkeypatch.undo()
    third = SortedFileNeedleMap.load(path)  # replays (meta gone)
    assert third.get(600).size == 10 and third.get(42) is None


def test_unknown_kind_rejected(tmp_path):
    with pytest.raises(ValueError, match="unknown needle map"):
        load_needle_map(str(tmp_path / "x.idx"), "leveldb")


# -- disk map (-index disk; reference needle_map_leveldb.go:15-120) -------

def test_disk_map_survives_restart_without_full_replay(tmp_path,
                                                       monkeypatch):
    """Clean close -> reopen must serve from the sqlite checkpoint (no
    .idx replay); puts and deletes from the first session are all
    there."""
    from seaweedfs_tpu.storage.needle_map_disk import DiskNeedleMap
    path = str(tmp_path / "d.idx")
    nm = DiskNeedleMap.load(path)
    random_workload(nm, random.Random(11), n_ops=4000)
    counters = {f: getattr(nm, f) for f in
                ("file_counter", "file_byte_counter", "deletion_counter",
                 "deletion_byte_counter", "maximum_file_key")}
    live = {k: (v.offset, v.size) for k, v in nm.items()}
    nm.close()

    def boom(self, start, end):
        raise AssertionError("tail replay ran on a clean checkpoint")

    monkeypatch.setattr(DiskNeedleMap, "_replay_range", boom)
    again = DiskNeedleMap.load(path)
    assert {k: (v.offset, v.size) for k, v in again.items()} == live
    for f, want in counters.items():
        assert getattr(again, f) == want, f
    again.close()


def test_disk_map_tail_catch_up_after_crash(tmp_path):
    """Mutations past the last checkpoint (a 'crash' drops the final
    commit) are recovered from the .idx tail — not lost, not a full
    rebuild."""
    from seaweedfs_tpu.storage import needle_map_disk
    from seaweedfs_tpu.storage.needle_map_disk import DiskNeedleMap
    path = str(tmp_path / "c.idx")
    nm = DiskNeedleMap.load(path)
    for i in range(1, 200):
        nm.put(i, i * 8, 100)
    nm.close()
    # simulate a crash: append straight to the .idx behind the db's back
    from seaweedfs_tpu.storage.needle_map import entry_to_bytes
    from seaweedfs_tpu.storage.types import TOMBSTONE_FILE_SIZE as TOMB
    with open(path, "ab") as f:
        f.write(entry_to_bytes(500, 4000, 123))
        f.write(entry_to_bytes(7, 0, TOMB))
    again = DiskNeedleMap.load(path)
    assert again.get(500).size == 123
    assert again.get(7) is None
    assert again.get(199).size == 100
    # parity with a dict-map replay of the same .idx
    ref = NeedleMap.load(path)
    assert_maps_equal(ref, again)
    again.close()


def test_disk_map_rebuilds_after_idx_rewrite(tmp_path):
    """A shrunken .idx (vacuum rewrote it) invalidates the checkpoint:
    the map must rebuild, not trust a stale watermark."""
    from seaweedfs_tpu.storage.needle_map_disk import DiskNeedleMap
    path = str(tmp_path / "r.idx")
    nm = DiskNeedleMap.load(path)
    for i in range(1, 300):
        nm.put(i, i * 8, 64)
    nm.close()
    # vacuum analog: rewrite the .idx keeping only every third needle
    ref = NeedleMap.load(path)
    survivors = [(k, v.offset, v.size) for k, v in ref.items()
                 if k % 3 == 0]
    ref.close()
    fresh = NeedleMap(str(tmp_path / "tmp.idx"))
    for k, off, size in survivors:
        fresh.put(k, off, size)
    fresh.close()
    os.replace(str(tmp_path / "tmp.idx"), path)
    again = DiskNeedleMap.load(path)
    assert len(again) == len(survivors)
    assert again.get(3).size == 64 and again.get(4) is None
    again.close()


def test_disk_map_five_byte_offsets(tmp_path):
    """The disk map is exactly the variant meant for >32GB volumes, so
    it must speak the 17B record layout (5-byte offsets) end to end."""
    from seaweedfs_tpu.storage.needle_map_disk import DiskNeedleMap
    path = str(tmp_path / "five.idx")
    nm = DiskNeedleMap.load(path, offset_width=5)
    big = (1 << 38) // 8          # an offset only 5 bytes can hold
    nm.put(1, big, 4096)
    nm.put(2, big + 512, 77)
    nm.delete(2)
    nm.close()
    again = DiskNeedleMap.load(path, offset_width=5)
    assert again.get(1).offset == big
    assert again.get(2) is None
    # the .idx bytes themselves are 17B records any walker can read
    assert os.path.getsize(path) % 17 == 0
    ref = NeedleMap.load(path, offset_width=5)
    assert_maps_equal(ref, again)
    again.close()


def test_disk_map_detects_same_size_idx_rewrite(tmp_path):
    """offline compact/fix replace the .idx wholesale; if the new file
    is at least as long as the checkpoint's watermark, size alone can't
    catch it — the content fingerprint must force a rebuild."""
    from seaweedfs_tpu.storage.needle_map_disk import DiskNeedleMap
    path = str(tmp_path / "w.idx")
    nm = DiskNeedleMap.load(path)
    for i in range(1, 101):
        nm.put(i, i * 8, 50)
    nm.close()
    # rewrite: identical length (same record count), different offsets
    fresh = NeedleMap(str(tmp_path / "tmp.idx"))
    for i in range(1, 101):
        fresh.put(i, i * 16, 50)
    fresh.close()
    assert os.path.getsize(str(tmp_path / "tmp.idx")) == \
        os.path.getsize(path)
    os.replace(str(tmp_path / "tmp.idx"), path)
    again = DiskNeedleMap.load(path)
    assert again.get(5).offset == 5 * 16   # rebuilt, not stale
    ref = NeedleMap.load(path)
    assert_maps_equal(ref, again)
    again.close()


def test_disk_map_vacuum_streams_without_full_materialize(tmp_path):
    """Volume.compact on a disk-index volume streams from a pinned
    snapshot connection (snapshot_live_items -> items_snapshot), and
    the full volume lifecycle stays correct."""
    from seaweedfs_tpu.storage.needle_map_disk import DiskNeedleMap
    rng = np.random.default_rng(12)
    v = Volume(str(tmp_path), "", 1, create=True, index_kind="disk")
    assert isinstance(v.nm, DiskNeedleMap)
    payloads = {}
    for i in range(1, 50):
        data = rng.integers(0, 256, 1500).astype(np.uint8).tobytes()
        v.write_needle(Needle(id=i, cookie=9, data=data))
        payloads[i] = data
    for i in (3, 17, 40):
        v.delete_needle(Needle(id=i, cookie=9))
        del payloads[i]
    before = v.size()
    v.compact()
    v.commit_compact()
    assert v.size() < before
    for i, data in payloads.items():
        assert v.read_needle(Needle(id=i, cookie=9)).data == data
    v.close()
    # cold boot reuses the post-vacuum checkpoint-or-rebuild correctly
    v2 = Volume(str(tmp_path), "", 1, index_kind="disk")
    for i, data in payloads.items():
        assert v2.read_needle(Needle(id=i, cookie=9)).data == data
    v2.close()


def test_disk_map_truncates_torn_idx_tail(tmp_path):
    """A torn trailing .idx record must be truncated away, not merely
    skipped — the append handle writes at the physical end, and a
    half-record left in place would misframe every later record."""
    from seaweedfs_tpu.storage.needle_map_disk import DiskNeedleMap
    path = str(tmp_path / "t.idx")
    nm = DiskNeedleMap.load(path)
    for i in range(1, 20):
        nm.put(i, i * 8, 30)
    nm.close()
    with open(path, "ab") as f:
        f.write(b"\x00" * 7)               # torn half-record
    again = DiskNeedleMap.load(path)
    assert os.path.getsize(path) % 16 == 0  # truncated
    again.put(100, 800, 44)                 # lands record-aligned
    again.close()
    ref = NeedleMap.load(path)              # any variant reframes cleanly
    assert ref.get(100).offset == 800
    assert ref.get(19).size == 30
    assert_maps_equal(ref, DiskNeedleMap.load(path))


def test_disk_map_checkpoint_excludes_foreign_tail(tmp_path):
    """.idx records appended behind the map's back (exactly what the
    native write lease does) must stay PAST the checkpoint watermark so
    the next boot's tail replay ingests them — close() stamping
    getsize() would silently lose every lease-written needle."""
    from seaweedfs_tpu.storage.needle_map import entry_to_bytes
    from seaweedfs_tpu.storage.needle_map_disk import DiskNeedleMap
    path = str(tmp_path / "lease.idx")
    nm = DiskNeedleMap.load(path)
    for i in range(1, 11):
        nm.put(i, i * 8, 50)
    # foreign append while the map is open (lease analog)
    with open(path, "ab") as f:
        f.write(entry_to_bytes(99, 8000, 55))
    nm.close()     # checkpoint must NOT cover the foreign record
    again = DiskNeedleMap.load(path)
    assert again.get(99) is not None and again.get(99).size == 55
    ref = NeedleMap.load(path)
    assert_maps_equal(ref, again)

    # a live put AFTER another foreign append ingests both, in order
    with open(path, "ab") as f:
        f.write(entry_to_bytes(100, 8800, 66))
    again.put(101, 9600, 77)
    assert again.get(100).size == 66
    assert again.get(101).size == 77
    again.close()
    third = DiskNeedleMap.load(path)
    ref2 = NeedleMap.load(path)
    assert_maps_equal(ref2, third)
    third.close()


def test_volume_server_with_disk_index(tmp_path):
    """A live volume server on `-index disk`: writes/reads/deletes over
    HTTP (native plane bulk-registration included), then a cold restart
    serving the same data from the sqlite checkpoint."""
    from seaweedfs_tpu.client import operation as op
    from seaweedfs_tpu.server.http_util import HttpError
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    master = MasterServer(port=0, pulse_seconds=1).start()
    vs = VolumeServer(port=0, directories=[str(tmp_path / "v")],
                      master_url=master.url, pulse_seconds=1,
                      max_volume_counts=[8], ec_backend="numpy",
                      index_kind="disk").start()
    try:
        fids, rng = {}, random.Random(3)
        for i in range(25):
            data = bytes([rng.randrange(256)]) * rng.randrange(1, 9000)
            fid = op.upload_data(master.url, data, filename=f"d{i}.bin")
            fids[fid] = data
        doomed = sorted(fids)[:5]
        for fid in doomed:
            op.delete_file(master.url, fid)
            del fids[fid]
        for fid, data in fids.items():
            assert op.read_file(master.url, fid) == data
        port, d = vs.port, str(tmp_path / "v")
        vs.stop()
        # cold restart on the same dir: state comes from the checkpoint
        vs = VolumeServer(port=port, directories=[d],
                          master_url=master.url, pulse_seconds=1,
                          max_volume_counts=[8], ec_backend="numpy",
                          index_kind="disk").start()
        for fid, data in fids.items():
            assert op.read_file(master.url, fid) == data
        for fid in doomed:
            with pytest.raises(HttpError):
                op.read_file(master.url, fid)
    finally:
        vs.stop()
        master.stop()


@pytest.mark.skipif(not os.path.exists("/proc/self/status"),
                    reason="needs /proc VmRSS")
def test_disk_map_boots_million_needle_index_bounded(tmp_path):
    """The disk map's reason to exist: a large .idx boots without
    holding the index in RAM (current-RSS delta across the load stays
    far below the ~30MB a dict map would need for 1M entries —
    measured ~6.5MB: replay batches + sqlite page cache), and a clean
    reload hits the checkpoint — no replay, near-instant."""
    import gc
    import time as _time
    from seaweedfs_tpu.storage.compact_map import IDX_DTYPE
    from seaweedfs_tpu.storage.needle_map_disk import DiskNeedleMap

    def vmrss_mb():
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) / 1024

    n = 1_000_000
    arr = np.zeros(n, dtype=IDX_DTYPE)
    arr["nid"] = np.arange(1, n + 1)
    arr["off"] = np.arange(1, n + 1)
    arr["size"] = 4096
    path = str(tmp_path / "big.idx")
    arr.tofile(path)
    del arr
    gc.collect()
    rss0 = vmrss_mb()
    nm = DiskNeedleMap.load(path)
    gc.collect()
    rss1 = vmrss_mb()
    assert len(nm) == n
    assert nm.file_byte_counter == 4096 * n
    assert nm.get(500_000).size == 4096
    assert nm.get(n).offset == 8 * n   # .idx offsets are 8B units
    # bounded: current RSS (not a high-water mark, which earlier tests
    # in the same process inflate) must not grow by anything near a
    # 1M-entry in-RAM index
    assert rss1 - rss0 < 20, f"boot materialized the index? {rss1-rss0}"
    nm.close()
    t = _time.perf_counter()
    again = DiskNeedleMap.load(path)     # checkpoint hit: no replay
    assert _time.perf_counter() - t < 1.0
    assert len(again) == n and again.get(123_456).size == 4096
    again.close()
