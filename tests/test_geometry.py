"""Where a volume's RS geometry lives, piece by piece: the `.vif` keys
and the flag's parser, the store's codec a geometry, the heartbeat field
and what the master and the topology make of it, the shell's spread.
tests/test_geometry_served.py runs the whole served path; tests/test_ec.py
the row arithmetic at each geometry.
"""

import json
import os

import numpy as np
import pytest

from seaweedfs_tpu.ec import layout as ec_layout
from seaweedfs_tpu.ec.constants import (DATA_SHARDS, MAX_SHARDS,
                                        PARITY_SHARDS, TOTAL_SHARDS,
                                        to_ext)
from seaweedfs_tpu.ops import telemetry
from seaweedfs_tpu.ops.codec import NumpyCodec

GEOMETRIES = [(6, 3), (10, 4), (20, 4)]
geometries = pytest.mark.parametrize(
    "k,m", GEOMETRIES, ids=[f"rs{k}-{m}" for k, m in GEOMETRIES])


def test_the_constants_name_the_default_only():
    assert (DATA_SHARDS, PARITY_SHARDS, TOTAL_SHARDS) == (10, 4, 14)
    assert MAX_SHARDS == 32 and NumpyCodec(6, 3).geometry == "6+3"


# -- the flag and the .vif ---------------------------------------------------

@pytest.mark.parametrize("text,want", [
    ("6,3", (6, 3)), ("10,4", (10, 4)), (" 20 , 4 ", (20, 4)),
    ([6, 3], (6, 3)), ((28, 4), (28, 4))])
def test_parse_geometry(text, want):
    assert ec_layout.parse_geometry(text) == want


@pytest.mark.parametrize("text", ["6", "6,3,1", "six,3", "0,3", "6,0",
                                  "29,4", "", None, [6]])
def test_parse_geometry_refuses_by_name(text):
    with pytest.raises(ValueError, match="geometry"):
        ec_layout.parse_geometry(text)


@geometries
def test_the_vif_carries_the_geometry_beside_the_layout(tmp_path, k, m):
    base = str(tmp_path / "7")
    ec_layout.write_layout_sidecars(base, "flat", version=3,
                                    offset_width=4, ec_data_shards=k,
                                    ec_parity_shards=m)
    with open(base + ".vif") as f:
        info = json.load(f)
    assert info == {"version": 3, "offset_width": 4, "ec_layout": "flat",
                    "ec_data_shards": k, "ec_parity_shards": m}
    assert ec_layout.volume_geometry(base) == (k, m)
    # a later merge-write of other keys keeps it
    ec_layout.write_layout_sidecars(base, "piggyback", window=1 << 20,
                                    pairs=3)
    assert ec_layout.volume_geometry(base) == (k, m)


@pytest.mark.parametrize("vif", [None, "{}", "not json",
                                 '{"ec_layout": "flat", "version": 3}',
                                 '{"ec_data_shards": 6}'])
def test_a_vif_without_a_geometry_means_10_4(tmp_path, vif):
    base = str(tmp_path / "7")
    if vif is not None:
        with open(base + ".vif", "w") as f:
            f.write(vif)
    assert ec_layout.volume_geometry(base) == (10, 4)
    assert ec_layout.volume_geometry(base, default=(6, 3)) == (6, 3)


# -- the store: a codec a geometry ------------------------------------------

def test_the_store_builds_one_codec_a_geometry_on_its_backend(tmp_path):
    from seaweedfs_tpu.storage.store import Store
    store = Store([str(tmp_path)], ec_backend="numpy")
    assert store.default_geometry == (10, 4)
    a, b = store.codec_for(6, 3), store.codec_for(20, 4)
    assert (a.k, a.m, b.k, b.m) == (6, 3, 20, 4)
    assert a.backend == b.backend == store.codec.backend == "numpy"
    assert store.codec_for(6, 3) is a and store.codec is store.codec_for(
        10, 4)
    # a codec handed in is its geometry's, and the default's
    mine = NumpyCodec(6, 3)
    store = Store([str(tmp_path)], codec=mine)
    assert store.codec is mine and store.default_geometry == (6, 3)
    assert type(store.codec_for(10, 4)) is NumpyCodec


def _sealed_volume(tmp_path, store, vid=3):
    from seaweedfs_tpu.storage.needle import Needle
    v = store.add_volume(vid)
    rng = np.random.default_rng(vid)
    for i in range(1, 9):
        v.write_needle(Needle(cookie=i, id=i, data=rng.integers(
            0, 256, 50_000).astype(np.uint8).tobytes()))
    store.mark_volume_readonly(vid)
    return v.file_name()


@geometries
def test_generate_stamps_the_geometry_and_rebuild_reads_it(tmp_path, k, m):
    """The local encode and rebuild of one store: k + m
    shard files, the .vif's keys, and a rebuild that takes the codec of
    the volume's geometry and not the store's default."""
    from seaweedfs_tpu.storage.store import Store
    store = Store([str(tmp_path)], ec_backend="numpy")
    base = _sealed_volume(tmp_path, store)
    store.generate_ec_shards(3, geometry=f"{k},{m}")
    assert [os.path.exists(base + to_ext(i)) for i in range(k + m + 1)] == \
        [True] * (k + m) + [False]
    assert store.volume_geometry(base) == (k, m)
    assert store.volume_codec(base) is store.codec_for(k, m)
    want = open(base + to_ext(k), "rb").read()
    os.remove(base + to_ext(k))
    os.remove(base + to_ext(0))
    stats = {}
    assert store.rebuild_ec_shards_streaming(3, stats=stats) == [0, k]
    assert (stats["k"], stats["m"], stats["lost"]) == (k, m, [0, k])
    assert open(base + to_ext(k), "rb").read() == want
    store.close()


def test_generate_without_a_geometry_is_10_4(tmp_path):
    from seaweedfs_tpu.storage.store import Store
    store = Store([str(tmp_path)], ec_backend="numpy")
    base = _sealed_volume(tmp_path, store)
    store.generate_ec_shards(3)
    assert sum(os.path.exists(base + to_ext(i))
               for i in range(MAX_SHARDS)) == 14
    assert store.volume_geometry(base) == (10, 4)
    store.close()


@pytest.mark.parametrize("geometry,named", [
    ("6,1", r"SW_EC_LAYOUT=piggyback unsupported for RS\(6,1\)"),
    ("1,1", r"SW_EC_LAYOUT=piggyback unsupported for RS\(1,1\)")])
def test_piggyback_with_a_geometry_it_does_not_cover_writes_nothing(
        tmp_path, monkeypatch, geometry, named):
    from seaweedfs_tpu.storage.store import Store, VolumeError
    monkeypatch.setenv("SW_EC_LAYOUT", "piggyback")
    store = Store([str(tmp_path)], ec_backend="numpy")
    base = _sealed_volume(tmp_path, store)
    before = sorted(os.listdir(tmp_path))
    with pytest.raises(VolumeError, match=named):
        store.generate_ec_shards(3, geometry=geometry)
    with pytest.raises(VolumeError, match=named):
        store.generate_ec_shards_streaming(3, assignment={},
                                           geometry=geometry)
    assert sorted(os.listdir(tmp_path)) == before      # not a byte
    assert not os.path.exists(base + ".ecx")
    store.close()


@pytest.mark.parametrize("geometry", ["6", "0,3", "30,4", "a,b"])
def test_generate_refuses_a_geometry_that_is_none_by_name(tmp_path,
                                                          geometry):
    from seaweedfs_tpu.storage.store import Store, VolumeError
    store = Store([str(tmp_path)], ec_backend="numpy")
    _sealed_volume(tmp_path, store)
    before = sorted(os.listdir(tmp_path))
    with pytest.raises(VolumeError, match="geometry"):
        store.generate_ec_shards(3, geometry=geometry)
    assert sorted(os.listdir(tmp_path)) == before
    store.close()


def test_a_piggyback_volume_of_rs6_3_is_the_layouts_own(tmp_path,
                                                        monkeypatch):
    """RS(6,3) is a geometry the construction covers (three pairs): the
    layout and the geometry are both the volume's, side by side."""
    from seaweedfs_tpu.storage.store import Store
    monkeypatch.setenv("SW_EC_LAYOUT", "piggyback")
    store = Store([str(tmp_path)], ec_backend="numpy")
    base = _sealed_volume(tmp_path, store)
    store.generate_ec_shards(3, geometry="6,3")
    li = store._volume_layout(base)
    assert li.piggyback and li.pairs == 3
    assert store.volume_geometry(base) == (6, 3)
    shards = [open(base + to_ext(i), "rb").read() for i in range(9)]
    for sid in (1, 7):
        os.remove(base + to_ext(sid))
    assert store.rebuild_ec_shards_streaming(3) == [1, 7]
    assert [open(base + to_ext(i), "rb").read() for i in range(9)] == shards
    store.close()


def test_the_heartbeat_names_each_ec_volumes_geometry(tmp_path):
    from seaweedfs_tpu.storage.store import Store
    store = Store([str(tmp_path)], ec_backend="numpy")
    for vid, geometry in ((3, "6,3"), (4, None)):
        _sealed_volume(tmp_path, store, vid)
        store.generate_ec_shards(vid, geometry=geometry)
        store.mount_ec_shards(vid, "", list(range(MAX_SHARDS)))
    hb = store.collect_heartbeat()
    assert hb["ec_geometries"] == {3: [6, 3], 4: [10, 4]}
    assert hb["ec_shards"] == {3: (1 << 9) - 1, 4: (1 << 14) - 1}
    # a shard is a k-th of a volume slot, k the volume's own
    loc = store.locations[0]
    assert loc.max_volume_count - len(loc.volumes) - (9 / 6 + 14 / 10) \
        == pytest.approx(store.find_free_location() and
                         loc.max_volume_count - len(loc.volumes) - 2.9)
    store.close()


# -- counters ----------------------------------------------------------------

def test_dispatches_are_counted_by_the_codecs_geometry():
    before = telemetry.STATS.snapshot()
    telemetry.STATS.add_dispatch("6+3", 100)
    telemetry.STATS.add_dispatch("6+3", 50)
    telemetry.STATS.add_dispatch("10+4", 7)
    moved = telemetry.delta(before)
    assert moved["dispatches"] == 3 and moved["device_bytes"] == 157
    assert moved["geometry_dispatches"] == {"6+3": 2, "10+4": 1}
    snap = telemetry.STATS.snapshot()["geometry_dispatches"]
    assert snap["6+3"] >= 2 and snap["10+4"] >= 1
    assert telemetry.delta(telemetry.STATS.snapshot())[
        "geometry_dispatches"] == {}


def test_the_metrics_mirror_labels_dispatches_by_geometry():
    """/metrics mirrors the map where it mirrors `dispatches`."""
    from seaweedfs_tpu.server.http_util import http_call
    from seaweedfs_tpu.server.volume_server import VolumeServer
    import tempfile
    telemetry.STATS.add_dispatch("6+3", 1)
    with tempfile.TemporaryDirectory() as d:
        vs = VolumeServer(port=0, directories=[d],
                          master_url="127.0.0.1:1", ec_backend="numpy")
        vs.server.start()
        try:
            body = http_call("GET", f"http://{vs.url}/metrics").decode()
        finally:
            vs.server.stop()
            vs.store.close()
    want = telemetry.STATS.snapshot()["geometry_dispatches"]["6+3"]
    line, = [ln for ln in body.splitlines() if ln.startswith(
        "SeaweedFS_volumeServer_ec_device_telemetry_total"
        '{kind="geometry_dispatches.6+3"}')]
    assert float(line.split()[-1]) >= 1 and want >= 1


# -- master and topology -----------------------------------------------------

class _Holder:
    def __init__(self, url):
        self.url = url


@geometries
def test_the_master_knows_a_volume_whole_at_k_plus_m(monkeypatch, k, m):
    """9, 14 and 24 shards: a stripe is a loss only once it was whole,
    and whole is the volume's own k + m."""
    monkeypatch.setenv("SW_REPAIR_INTERVAL_S", "0")   # no loop thread
    from seaweedfs_tpu.server.master import MasterServer
    master = MasterServer(port=0, pulse_seconds=1)
    total = k + m
    try:
        master.topology.ec_geometries[7] = (k, m)
        # one short of whole: mid-encode, no incident
        master.topology.ec_shard_map[7] = [
            [_Holder("h:1")] if s < total - 1 else [] for s in range(total)]
        master._repair_scan()
        assert not master.repair_queue.snapshot()["open"]
        assert 7 not in master._repair_seen_complete
        master.topology.ec_shard_map[7] = [
            [_Holder("h:1")] for _ in range(total)]
        master._repair_scan()
        assert 7 in master._repair_seen_complete
        master.topology.ec_shard_map[7][total - 1] = []
        master._repair_scan()
        assert [(i["kind"], i["volume"], i["shard"]) for i in
                master.repair_queue.snapshot()["open"]] == [
            ("lost_shard", 7, total - 1)]
    finally:
        master.stop()


def test_nine_shards_are_not_whole_for_a_volume_that_names_no_geometry(
        monkeypatch):
    monkeypatch.setenv("SW_REPAIR_INTERVAL_S", "0")
    from seaweedfs_tpu.server.master import MasterServer
    master = MasterServer(port=0, pulse_seconds=1)
    try:
        master.topology.ec_shard_map[7] = [
            [_Holder("h:1")] if s < 9 else [] for s in range(14)]
        master._repair_scan()
        assert 7 not in master._repair_seen_complete
    finally:
        master.stop()


@geometries
def test_the_topology_takes_the_geometry_from_the_heartbeat(k, m):
    from seaweedfs_tpu.topology.topology import Topology
    topo = Topology()
    total = k + m
    bits = [sum(1 << s for s in range(i, total, 3)) for i in range(3)]
    for i in range(3):
        topo.register_heartbeat(
            "", "", "127.0.0.1", 8000 + i, "", 8, [],
            ec_shards={5: bits[i]}, ec_collections={5: "c"},
            ec_geometries={5: [k, m]})
    assert topo.ec_geometry(5) == (k, m)
    assert len(topo.ec_shard_map[5]) == total
    assert sorted(topo.lookup_ec_shards(5)) == list(range(total))
    node = topo.find_node("127.0.0.1:8000")
    assert node.ec_geometry(5) == (k, m)
    held = len(range(0, total, 3))
    assert node.free_space() == pytest.approx(8 - held / k)
    # the holder drops the volume: gone from the map, geometry with it
    for i in range(3):
        topo.apply_heartbeat_delta(f"127.0.0.1:{8000 + i}", [], [],
                                   ec_shards={}, ec_collections={},
                                   ec_geometries={})
    assert 5 not in topo.ec_shard_map and 5 not in topo.ec_geometries
    assert topo.ec_geometry(5) == (10, 4)


def test_a_heartbeat_that_names_no_geometry_is_the_defaults():
    """An older volume server: its EC volumes stay 10 + 4."""
    from seaweedfs_tpu.topology.topology import Topology
    topo = Topology()
    topo.register_heartbeat("", "", "127.0.0.1", 8000, "", 8, [],
                            ec_shards={5: (1 << 14) - 1},
                            ec_collections={5: ""})
    assert topo.ec_geometry(5) == (10, 4)
    assert len(topo.ec_shard_map[5]) == 14
    assert topo.find_node("127.0.0.1:8000").free_space() == \
        pytest.approx(8 - 1.4)


# -- the shell's spread ------------------------------------------------------

@pytest.mark.parametrize("k,m,servers,want", [
    (6, 3, 3, [3, 3, 3]), (10, 4, 3, [5, 5, 4]), (10, 4, 4, [4, 4, 3, 3]),
    (20, 4, 3, [8, 8, 8])])
def test_the_shell_spreads_k_plus_m_shards_round_robin(k, m, servers, want):
    from seaweedfs_tpu.shell.command_ec import balanced_ec_distribution
    nodes = [{"url": f"h:{i}", "free": 8} for i in range(servers)]
    out = balanced_ec_distribution(nodes, (k, m))
    assert len(out) == k + m
    assert out == [f"h:{i % servers}" for i in range(k + m)]
    assert [out.count(f"h:{i}") for i in range(servers)] == want
    # RS(6,3) is the one of these a three-server cluster survives a
    # holder's loss under: no holder above m
    assert (max(want) <= m) == ((k, m, servers) in {(6, 3, 3),
                                                    (10, 4, 4)})


def test_the_shell_reads_a_volumes_geometry_from_the_master():
    from seaweedfs_tpu.shell.command_ec import _geometry_of
    assert _geometry_of({"data_shards": 6, "parity_shards": 3}) == (6, 3)
    assert _geometry_of({"shards": {}}) == (10, 4)      # an older master


def test_ec_encode_help_names_the_flag():
    import seaweedfs_tpu.shell  # noqa: F401 - registers the commands
    from seaweedfs_tpu.shell.command_env import HELP
    assert "-geometry <data>,<parity>" in HELP["ec.encode"]


# -- the location cache's tiers ---------------------------------------------

def test_the_location_cache_counts_enough_by_the_volumes_k():
    from seaweedfs_tpu.ec import shard_cache
    cache = shard_cache.EcShardLocationCache(
        lambda vid: {}, geometry=lambda vid: (6, 9) if vid == 1
        else (10, 14))
    six = {s: ["h"] for s in range(6)}
    assert cache._ttl(1, six) == shard_cache.ENOUGH_SHARDS_TTL
    assert cache._ttl(2, six) == shard_cache.FEW_SHARDS_TTL
    nine = {s: ["h"] for s in range(9)}
    assert cache._ttl(1, nine) == shard_cache.ALL_SHARDS_TTL
    assert shard_cache.EcShardLocationCache(lambda vid: {})._ttl(
        1, nine) == shard_cache.FEW_SHARDS_TTL
