"""Incremental heartbeats (SURVEY hard part #6; reference
master_grpc_server.go:94-152 incremental vs full sync)."""

import time

import pytest

from seaweedfs_tpu.client import operation as op
from seaweedfs_tpu.server.http_util import get_json, post_json
from seaweedfs_tpu.server.master import MasterServer
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.topology.topology import Topology


def hb_volume(vid, size=100, collection=""):
    return {"id": vid, "collection": collection, "size": size,
            "file_count": 1, "delete_count": 0, "deleted_byte_count": 0,
            "read_only": False, "replica_placement": "000", "ttl": 0,
            "version": 3, "compact_revision": 0, "modified_at": 0}


def test_topology_delta_apply_and_resync_signal():
    topo = Topology(pulse_seconds=1)
    events = []
    topo.location_listener = \
        lambda t, vid, url, pub, fast="": events.append((t, vid))
    # unknown node -> resync required
    assert not topo.apply_heartbeat_delta("1.2.3.4:80", [hb_volume(1)], [])
    topo.register_heartbeat(
        dc_id="", rack_id="", ip="1.2.3.4", port=80, public_url="",
        max_volume_count=10, volumes=[hb_volume(1), hb_volume(2)])
    assert ("new", 1) in events and ("new", 2) in events
    events.clear()
    # delta: volume 1 grows (no location event), 3 appears, 2 dies
    assert topo.apply_heartbeat_delta(
        "1.2.3.4:80", [hb_volume(1, size=5000), hb_volume(3)], [2])
    node = topo.find_node("1.2.3.4:80")
    assert set(node.volumes) == {1, 3}
    assert node.volumes[1].size == 5000
    assert events == [("new", 3), ("deleted", 2)]
    assert topo.lookup("", 2) in (None, [])


@pytest.fixture
def cluster(tmp_path):
    master = MasterServer(port=0, volume_size_limit_mb=64,
                          pulse_seconds=1).start()
    vs = VolumeServer(port=0, directories=[str(tmp_path / "v")],
                      master_url=master.url, pulse_seconds=1,
                      max_volume_counts=[20], ec_backend="numpy").start()
    yield master, vs
    vs.stop()
    master.stop()


# shared converge helper — poll across the pulse boundary, no sleeps
from conftest import wait_until  # noqa: E402


def test_deltas_carry_growth_and_deletion(cluster):
    master, vs = cluster
    a = op.assign(master.url)
    vid = int(a["fid"].split(",")[0])
    vs.heartbeat_once()          # ack baseline: later beats are deltas
    assert vs._hb_acked_volumes is not None
    payload = vs._heartbeat_payload(vs.store.collect_heartbeat(),
                                    vs.master_url)
    assert payload.get("delta") is True  # proves the wire format
    op.upload(a["url"], a["fid"], b"grow" * 5000, filename="g.bin")
    vs.heartbeat_once()          # delta carries the size change
    vols = get_json(f"http://{master.url}/cluster/volumes")["volumes"]
    assert vols[str(vid)][0]["size"] > 0
    # volume deletion flows through deleted_volumes
    post_json(f"http://{vs.url}/admin/delete_volume?volume={vid}")
    assert wait_until(lambda: str(vid) not in get_json(
        f"http://{master.url}/cluster/volumes")["volumes"])


def test_master_amnesia_forces_resync(cluster):
    """A master that lost the registration (restart/failover) must get
    the full state back on the next pulse, not a blind delta."""
    master, vs = cluster
    a = op.assign(master.url)
    vid = int(a["fid"].split(",")[0])
    vs.heartbeat_once()
    node = master.topology.find_node(vs.url)
    master.topology.unregister_node(node)   # simulated amnesia
    assert master.topology.find_node(vs.url) is None
    vs.heartbeat_once()                     # delta -> resync -> full
    assert master.topology.find_node(vs.url) is not None
    assert vid in master.topology.find_node(vs.url).volumes


def test_an_overtaken_heartbeat_is_dropped(cluster):
    """A server's heartbeats are posted side by side (the pulse thread,
    a handler that pushes a change): the master applies a state only if
    none collected after it was applied before, whichever arrives
    first, and the sender takes a dropped one for no acknowledgement."""
    master, vs = cluster
    a = op.assign(master.url)
    vid = int(a["fid"].split(",")[0])
    vs.heartbeat_once()
    node = master.topology.find_node(vs.url)
    first = node.hb_seq
    assert first == vs._hb_seq > 0
    vs.heartbeat_once()
    assert node.hb_seq == vs._hb_seq == first + 1
    # the state collected BEFORE the volume existed arrives after it
    old = vs.store.collect_heartbeat()
    old["volumes"], old["seq"] = [], first
    acked = dict(vs._hb_acked_volumes)
    resp = vs._post_heartbeat(old, vs.master_url)
    assert resp.get("stale") is True
    assert vid in node.volumes and node.hb_seq == first + 1
    assert vs._hb_acked_volumes == acked
    # equal or newer is applied; a server that sends no seq is as before
    old["seq"] = first + 1
    assert not post_json(f"http://{master.url}/cluster/heartbeat",
                         old).get("stale")
    assert vid not in node.volumes
    del old["seq"]
    old["volumes"] = [hb_volume(vid)]
    assert not post_json(f"http://{master.url}/cluster/heartbeat",
                         old).get("stale")
    assert vid in node.volumes and node.hb_seq == first + 1


def test_immediate_push_beats_the_pulse(tmp_path):
    """Volume create and EC shard mount must reach the master within
    milliseconds via the store change hook (reference store.go:40-64
    change channels + volume_grpc_client_to_master.go:57-185), NOT a
    pulse later — pulse here is 30s, so only the immediate push can
    explain propagation."""
    from seaweedfs_tpu.server.http_util import HttpError
    master = MasterServer(port=0, volume_size_limit_mb=64,
                          pulse_seconds=30).start()
    vs = VolumeServer(port=0, directories=[str(tmp_path / "v")],
                      master_url=master.url, pulse_seconds=30,
                      max_volume_counts=[20], ec_backend="numpy").start()
    try:
        t0 = time.monotonic()
        a = op.assign(master.url)
        vid = int(a["fid"].split(",")[0])
        op.upload(a["url"], a["fid"], b"x" * 200_000, filename="f.bin")
        post_json(f"http://{vs.url}/admin/volume/readonly?volume={vid}")
        post_json(f"http://{vs.url}/admin/ec/generate?volume={vid}")
        post_json(f"http://{vs.url}/admin/ec/mount?volume={vid}"
                  f"&shards={','.join(str(s) for s in range(14))}")

        def ec_known():
            try:
                out = get_json(f"http://{master.url}/cluster/ec_lookup"
                               f"?volumeId={vid}")
            except HttpError:
                return False
            return bool(out.get("shards"))

        assert wait_until(ec_known, timeout=5.0), \
            "ec shards did not reach the master without a pulse"
        # the whole flow must finish far below the 30s pulse period
        assert time.monotonic() - t0 < 20

        # deletion propagates immediately too
        post_json(f"http://{vs.url}/admin/ec/unmount?volume={vid}"
                  f"&shards={','.join(str(s) for s in range(14))}")
        assert wait_until(lambda: not ec_known(), timeout=5.0), \
            "ec unmount did not reach the master without a pulse"
    finally:
        vs.stop()
        master.stop()
