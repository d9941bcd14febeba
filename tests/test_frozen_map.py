"""The map a volume reloads follows its writability
(storage/volume.Volume._reload_kind): a frozen volume of an in-memory
index keeps the record array its .idx was replayed into (the compact
map), hands the native plane its mirror as columns, serves reads from
the array, and gets the dict back when it is thawed, before a write.
Every other configured kind, and 5-byte offsets, reload what they had."""

import os
import random

import numpy as np
import pytest

from seaweedfs_tpu.ops import telemetry
from seaweedfs_tpu.server.http_util import (HttpError, http_call,
                                            post_json, post_multipart)
from seaweedfs_tpu.server.master import MasterServer
from seaweedfs_tpu.server.native_plane import available
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.storage import needle_map
from seaweedfs_tpu.storage.compact_map import (CompactNeedleMap,
                                               SortedFileNeedleMap)
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.needle_map import (NeedleMap, entry_to_bytes,
                                              walk_index_file)
from seaweedfs_tpu.storage.needle_map_disk import DiskNeedleMap
from seaweedfs_tpu.storage.types import (TOMBSTONE_FILE_SIZE,
                                         parse_file_id)
from seaweedfs_tpu.storage.volume import Volume, VolumeError

needs_plane = pytest.mark.skipif(
    not available(), reason="libseaweed_http.so unavailable")

COUNTERS = ("file_counter", "file_byte_counter", "deletion_counter",
            "deletion_byte_counter", "maximum_file_key")


def tombstone(key: int) -> bytes:
    return entry_to_bytes(key, 0, TOMBSTONE_FILE_SIZE)


def seeded_log(seed: int, records: int) -> bytes:
    """Keys overwritten, deleted, put again and deleted while absent;
    some puts carry a zero offset, which deletes by the map's rule."""
    rng = random.Random(seed)
    out = []
    for _ in range(records):
        key = rng.randrange(1, max(4, records // 3)) \
            if rng.random() < 0.97 else rng.randrange(1 << 40, 1 << 64)
        kind = rng.random()
        if kind < 0.65:
            out.append(entry_to_bytes(key, rng.randrange(1, 1 << 32) * 8,
                                      rng.randrange(0, 1 << 24)))
        elif kind < 0.75:
            out.append(entry_to_bytes(key, 0, rng.randrange(1, 4096)))
        else:
            out.append(tombstone(key))
    return b"".join(out)


LOGS = {
    "missing": None,
    "empty": b"",
    "one-put": entry_to_bytes(7, 64, 9),
    "overwrite": entry_to_bytes(3, 8, 100) + entry_to_bytes(3, 80, 7),
    "put-delete-put": entry_to_bytes(3, 8, 100) + tombstone(3) +
    entry_to_bytes(3, 800, 50),
    "zero-offset": entry_to_bytes(4, 8, 1) + entry_to_bytes(6, 16, 2) +
    entry_to_bytes(4, 0, 77),
    "only-tombstones": tombstone(5) + entry_to_bytes(2, 0, 3),
    "top-key": entry_to_bytes((1 << 64) - 1, 8, 1) + entry_to_bytes(0, 16, 2),
    "seed-1": seeded_log(1, 40),
    "seed-2": seeded_log(2, 2500),
    "seed-3": seeded_log(2147485021, 1025),
}


def sorted_by_key(cols):
    keys, offsets, sizes = cols
    assert (keys.dtype, offsets.dtype, sizes.dtype) == \
        (np.uint64, np.uint64, np.uint32)
    order = np.argsort(keys)
    return list(zip(keys[order].tolist(), offsets[order].tolist(),
                    sizes[order].tolist()))


@pytest.mark.parametrize("log", list(LOGS))
def test_array_map_columns_equal_the_dict_maps(tmp_path, log):
    path = str(tmp_path / "v.idx")
    if LOGS[log] is not None:
        with open(path, "wb") as f:
            f.write(LOGS[log])
    want = NeedleMap.load(path)
    before = telemetry.STATS.snapshot()
    got = CompactNeedleMap.load(path)
    moved = telemetry.delta(before)
    try:
        assert sorted_by_key(got.live_columns()) == \
            sorted_by_key(want.live_columns())
        for name in COUNTERS:
            assert getattr(got, name) == getattr(want, name), name
        assert len(got) == len(want)
        # the array map counts its replay as the dict map does
        whole = os.path.getsize(path) // 16 if LOGS[log] is not None else 0
        assert moved["mirror_entries"] == whole
        assert moved["mirror_loop_entries"] == 0
    finally:
        got.close()
        want.close()


@pytest.mark.parametrize("kind", ["compact", "sortedfile"])
def test_columns_follow_the_overflow(tmp_path, kind):
    """Writes since the load sit in the overflow dict: the columns take
    them in, an overwrite once, a delete not at all."""
    path = str(tmp_path / "v.idx")
    with open(path, "wb") as f:
        f.write(seeded_log(7, 600))
    cls = {"compact": CompactNeedleMap, "sortedfile": SortedFileNeedleMap}
    got, want = cls[kind].load(path), NeedleMap.load(path)
    try:
        first, second = sorted(k for k, _ in want.items())[:2]
        for nm in (got, want):
            nm.put(first, 8 * 12345, 77)        # overwrite of a base key
            nm.put((1 << 64) - 2, 8 * 999, 5)   # a new key
            nm.delete(second)                   # delete of a base key
            nm.put(1 << 50, 8, 1)
            nm.delete(1 << 50)                  # put and deleted since
        assert got._overflow
        assert sorted_by_key(got.live_columns()) == \
            sorted_by_key(want.live_columns())
        assert len(got.live_columns()[0]) == len(got)
    finally:
        got.close()
        want.close()


def test_sorted_file_watermark_vouches_only_for_what_the_map_saw(tmp_path):
    """The native plane's write lease appends to the .idx behind the
    map: a close must not record the file's size as covered, or the
    reload after the lease (a freeze) maps a stale .sdx and loses the
    lease's needles."""
    path = str(tmp_path / "v.idx")
    with open(path, "wb") as f:
        f.write(entry_to_bytes(1, 8, 10))
    nm = SortedFileNeedleMap.load(path)
    with open(path, "ab") as f:             # the lease's appends
        f.write(entry_to_bytes(2, 80, 20) + tombstone(1))
    nm.close()
    again = SortedFileNeedleMap.load(path)
    try:
        assert again.get(2).offset == 80 and again.get(1) is None
        again.delete(2)                     # the map's own append
        again.put(3, 160, 30)
    finally:
        again.close()
    third = SortedFileNeedleMap.load(path)
    try:
        assert sorted_by_key(third.live_columns()) == [(3, 160, 30)]
    finally:
        third.close()


def filled_volume(tmp_path, **kwargs) -> tuple:
    v = Volume(str(tmp_path), "", 1, create=True, **kwargs)
    payloads = {}
    for i in range(1, 30):
        payloads[i] = bytes([i]) * (200 + i)
        v.write_needle(Needle(id=i, cookie=9, data=payloads[i]))
    for i in (3, 17):
        v.delete_needle(Needle(id=i, cookie=9))
        del payloads[i]
    return v, payloads


def test_a_frozen_reload_is_the_array_map_and_a_thaw_the_dict(tmp_path):
    v, payloads = filled_volume(tmp_path)
    assert type(v.nm) is NeedleMap
    counters = {name: getattr(v.nm, name) for name in COUNTERS}
    before = telemetry.STATS.snapshot()
    v.readonly = True
    assert type(v.nm) is NeedleMap      # the flag alone reloads nothing
    with v.lock:
        v.reload_nm()
    assert type(v.nm) is CompactNeedleMap
    assert telemetry.delta(before)["frozen_array_maps"] == 1
    assert {name: getattr(v.nm, name) for name in COUNTERS} == counters
    for i, data in payloads.items():
        assert v.read_needle(Needle(id=i, cookie=9)).data == data
    with pytest.raises(Exception, match="not found"):
        v.read_needle(Needle(id=3, cookie=9))
    with pytest.raises(VolumeError, match="read only"):
        v.write_needle(Needle(id=99, cookie=9, data=b"x"))
    # the thaw: the dict is back before the flag lets a write in
    v.readonly = False
    assert type(v.nm) is NeedleMap
    assert telemetry.delta(before)["frozen_array_maps"] == 1
    assert {name: getattr(v.nm, name) for name in COUNTERS} == counters
    v.write_needle(Needle(id=99, cookie=9, data=b"after the thaw"))
    v.delete_needle(Needle(id=5, cookie=9))
    assert v.nm.get(99) is not None and v.nm.get(5) is None
    last = list(walk_index_file(v.idx_path))[-2:]
    assert [(nid, size == TOMBSTONE_FILE_SIZE) for nid, _, size in last] \
        == [(99, False), (5, True)]
    # a writable volume's reload (the poison demote's) keeps the dict
    with v.lock:
        v.reload_nm()
    assert type(v.nm) is NeedleMap
    assert telemetry.delta(before)["frozen_array_maps"] == 1
    v.close()


@pytest.mark.parametrize("kwargs, want", [
    ({"index_kind": "compact"}, CompactNeedleMap),
    ({"index_kind": "sortedfile"}, SortedFileNeedleMap),
    ({"index_kind": "disk"}, DiskNeedleMap),
    ({"index_kind": "memory", "offset_width": 5}, NeedleMap),
    ({"index_kind": "compact", "offset_width": 5}, NeedleMap),
], ids=["compact", "sortedfile", "disk", "memory-5-byte", "compact-5-byte"])
def test_other_kinds_reload_what_they_had(tmp_path, kwargs, want):
    v, payloads = filled_volume(tmp_path, **kwargs)
    assert type(v.nm) is want
    before = telemetry.STATS.snapshot()
    v.readonly = True
    with v.lock:
        v.reload_nm()
    assert type(v.nm) is want
    frozen = v.nm
    v.readonly = False
    assert v.nm is frozen               # nothing was swapped: no reload
    assert telemetry.delta(before)["frozen_array_maps"] == 0
    v.write_needle(Needle(id=99, cookie=9, data=b"still writable"))
    payloads[99] = b"still writable"
    for i, data in payloads.items():
        assert v.read_needle(Needle(id=i, cookie=9)).data == data
    v.close()


# -- through a volume server with the native plane -------------------------

@pytest.fixture
def cluster(tmp_path, request):
    kind = getattr(request, "param", "memory")
    master = MasterServer(port=0, pulse_seconds=1).start()
    vs = VolumeServer(port=0, directories=[str(tmp_path / "v0")],
                      master_url=master.url, pulse_seconds=1,
                      max_volume_counts=[10], ec_backend="numpy",
                      index_kind=kind).start()
    assert vs.fast_plane is not None, "plane should start by default"
    yield master, vs
    vs.stop()
    master.stop()


def upload_one_volume(master, vs, count=24):
    """`count` needles in one volume, two of them deleted: (vid, live
    {fid: bytes}, deleted fids)."""
    first = post_json(f"http://{master.url}/dir/assign?count={count}", {})
    fids = [first["fid"]] + [f"{first['fid']}_{i}" for i in range(1, count)]
    payloads = {}
    for i, fid in enumerate(fids):
        payloads[fid] = bytes([i + 1]) * (500 + i)
        post_multipart(f"http://{vs.url}/{fid}", "f.bin", payloads[fid],
                       "application/octet-stream")
    gone = fids[1:3]
    for fid in gone:
        http_call("DELETE", f"http://{vs.url}/{fid}")
        del payloads[fid]
    return int(first["fid"].split(",")[0]), payloads, gone


def status_of(hostport: str, fid: str):
    """(status, body) of a GET that follows no redirect."""
    import http.client
    c = http.client.HTTPConnection(hostport, timeout=10)
    c.request("GET", f"/{fid}")
    r = c.getresponse()
    out = (r.status, r.read())
    c.close()
    return out


def freeze(vs, vid: int, readonly: bool = True) -> dict:
    before = telemetry.STATS.snapshot()
    out = post_json(f"http://{vs.url}/admin/volume/readonly?volume={vid}"
                    f"&readonly={'true' if readonly else 'false'}", {})
    assert out["was_readonly"] is not readonly
    return telemetry.delta(before)


@needs_plane
def test_a_frozen_volume_serves_from_the_array_on_both_paths(cluster):
    master, vs = cluster
    vid, live, gone = upload_one_volume(master, vs)
    v = vs.store.find_volume(vid)
    assert v.fast_writer is not None and type(v.nm) is NeedleMap
    idx_records = os.path.getsize(v.idx_path) // 16
    assert idx_records == len(live) + 2 * len(gone)
    moved = freeze(vs, vid)
    assert type(v.nm) is CompactNeedleMap and v.fast_writer is None
    assert moved["frozen_array_maps"] == 1
    assert moved["mirror_loop_entries"] == 0
    # the replay read every record; register_volume pushed the live set
    assert moved["mirror_entries"] == idx_records + len(live)
    for hostport in (vs.fast_url, vs.url):
        for fid, data in live.items():
            assert status_of(hostport, fid) == (200, data), (hostport, fid)
        for fid in gone:
            # the plane's mirror holds no deleted key: its miss is a
            # redirect to the Python server, whose array says 404
            with pytest.raises(HttpError) as err:
                http_call("GET", f"http://{hostport}/{fid}")
            assert err.value.status == 404, (hostport, fid)
    assert status_of(vs.fast_url, gone[0])[0] == 307
    assert status_of(vs.url, gone[0])[0] == 404
    with pytest.raises(HttpError):
        post_multipart(f"http://{vs.url}/{gone[0]}", "f.bin", b"frozen",
                       "application/octet-stream")


@needs_plane
def test_a_thaw_puts_the_dict_back_before_the_next_write(cluster):
    master, vs = cluster
    vid, live, gone = upload_one_volume(master, vs)
    v = vs.store.find_volume(vid)
    freeze(vs, vid)
    assert type(v.nm) is CompactNeedleMap
    moved = freeze(vs, vid, readonly=False)
    assert type(v.nm) is NeedleMap
    assert moved["frozen_array_maps"] == 0
    assert moved["mirror_loop_entries"] == 0
    assert v.fast_writer is not None        # the lease went out again
    idx_before = os.path.getsize(v.idx_path)
    put, dropped = gone[0], next(iter(live))
    post_multipart(f"http://{vs.url}/{put}", "f.bin", b"after the thaw",
                   "application/octet-stream")
    http_call("DELETE", f"http://{vs.url}/{dropped}")
    # the lease holds the tails; taking it back reloads what it wrote
    vs._fast_unregister(vid)
    assert type(v.nm) is NeedleMap
    assert os.path.getsize(v.idx_path) == idx_before + 2 * 16
    put_key, dropped_key = (parse_file_id(f)[1] for f in (put, dropped))
    assert v.nm.get(put_key) is not None and v.nm.get(dropped_key) is None
    last = list(walk_index_file(v.idx_path))[-2:]
    assert [(nid, size == TOMBSTONE_FILE_SIZE) for nid, _, size in last] \
        == [(put_key, False), (dropped_key, True)]
    vs._fast_sync(vid)
    assert status_of(vs.url, put) == (200, b"after the thaw")
    assert status_of(vs.url, dropped)[0] == 404


@needs_plane
@pytest.mark.parametrize("cluster", ["compact", "sortedfile"], indirect=True)
def test_a_configured_array_map_freezes_as_itself(cluster, request):
    master, vs = cluster
    vid, live, gone = upload_one_volume(master, vs)
    v = vs.store.find_volume(vid)
    had = type(v.nm)
    assert had in (CompactNeedleMap, SortedFileNeedleMap)
    moved = freeze(vs, vid)
    assert type(v.nm) is had
    assert moved["frozen_array_maps"] == 0
    # its mirror goes as columns too, no entry a Python iteration
    assert moved["mirror_loop_entries"] == 0
    for hostport in (vs.fast_url, vs.url):
        for fid, data in live.items():
            assert status_of(hostport, fid) == (200, data), (hostport, fid)
    with pytest.raises(HttpError) as err:
        http_call("GET", f"http://{vs.fast_url}/{gone[0]}")
    assert err.value.status == 404
    freeze(vs, vid, readonly=False)
    assert type(v.nm) is had


def replay_by_record_loop(log: bytes, deletes: bool = True) -> bytes:
    """The .ecx a log leaves, a record an iteration: the last put of a
    key, dropped (or, for the broken control, kept) when deleted."""
    live = {}
    for at in range(0, len(log) - 15, 16):
        key = int.from_bytes(log[at:at + 8], "big")
        stored = int.from_bytes(log[at + 8:at + 12], "big")
        size = int.from_bytes(log[at + 12:at + 16], "big")
        if size == TOMBSTONE_FILE_SIZE or stored == 0:
            if deletes:
                live.pop(key, None)
        else:
            live[key] = log[at:at + 16]
    return b"".join(live[key] for key in sorted(live))


@needs_plane
@pytest.mark.parametrize("memdb_delete", ["sound", "no-op"])
def test_a_frozen_volumes_ecx_still_goes_through_memdb(
        cluster, monkeypatch, memdb_delete):
    """ec.encode of a volume frozen into the array map builds the .ecx
    from the .idx through MemDb, as
    test_idx_array.test_a_delete_reaches_the_ecx_through_memdb_delete
    pins it: the frozen map's base is not the route, so the benchmark's
    control `keep_tombstones_in_ecx` still breaks the program."""
    master, vs = cluster
    vid, live, gone = upload_one_volume(master, vs)
    v = vs.store.find_volume(vid)
    freeze(vs, vid)
    assert type(v.nm) is CompactNeedleMap
    with open(v.idx_path, "rb") as f:
        log = f.read()
    if memdb_delete == "no-op":
        monkeypatch.setattr(needle_map.MemDb, "delete",
                            lambda self, nid: None)
    out = post_json(f"http://{vs.url}/admin/ec/generate?volume={vid}", {})
    base = os.path.join(v.dir, out["base"])
    with open(base + ".ecx", "rb") as f:
        ecx = f.read()
    sound = replay_by_record_loop(log)
    assert len(sound) == 16 * len(live)
    if memdb_delete == "sound":
        assert ecx == sound
    else:
        assert ecx == replay_by_record_loop(log, deletes=False) != sound
        assert len(ecx) == 16 * (len(live) + len(gone))
