"""Master->client volume-location push (reference
KeepConnected master_grpc_server.go:180-234 + wdclient/vid_map.go)."""

import time

import pytest

from seaweedfs_tpu.client import operation as op
from seaweedfs_tpu.client.vid_map import VidMap
from seaweedfs_tpu.server.http_util import HttpError, get_json, http_call
from seaweedfs_tpu.server.master import MasterServer
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.server.watch_hub import WatchHub


# -- WatchHub unit -----------------------------------------------------------

def test_hub_snapshot_then_deltas():
    state = {"5": [{"url": "n1", "publicUrl": "n1"}]}
    hub = WatchHub(lambda: state)
    out = hub.wait(0)
    assert out["reset"] and out["locations"] == state
    hub.publish("new", 6, "n2")
    out2 = hub.wait(out["seq"], timeout=1)
    assert out2["events"] == [
        {"type": "new", "vid": 6, "url": "n2", "publicUrl": "n2"}]
    # caller at head blocks then times out empty
    t = time.monotonic()
    out3 = hub.wait(out2["seq"], timeout=0.2)
    assert out3["events"] == [] and time.monotonic() - t >= 0.2


def test_hub_gap_forces_reset():
    hub = WatchHub(lambda: {}, maxlen=4)
    for i in range(10):
        hub.publish("new", i, "n")
    # an old cursor fell off the 4-event buffer -> snapshot
    assert hub.wait(2, timeout=0.1).get("reset")
    # a cursor one-behind-head is still coverable -> single delta
    out = hub.wait(hub._seq - 1, timeout=0.1)
    assert [e["vid"] for e in out["events"]] == [9]


def test_hub_wakes_parked_waiter():
    import threading
    hub = WatchHub(lambda: {})
    got = {}

    def park():
        got["out"] = hub.wait(0 if False else hub._seq, timeout=5)

    th = threading.Thread(target=park)
    th.start()
    time.sleep(0.1)
    hub.publish("deleted", 3, "n1")
    th.join(2)
    assert not th.is_alive()
    assert got["out"]["events"][0]["vid"] == 3


def test_hub_epoch_regression_forces_reset():
    """A cursor from a previous master's hub (since > seq) must get a
    reset snapshot, not an empty 'caught up' answer — otherwise clients
    keep stale maps across master restart/failover."""
    hub = WatchHub(lambda: {"1": [{"url": "n1", "publicUrl": "n1"}]})
    out = hub.wait(500, timeout=0.1)
    assert out.get("reset") and "locations" in out


def test_hub_no_lock_inversion_with_topology():
    """Regression: wait() must not hold the hub condition while calling
    snapshot_fn — topology publishes under its own lock, and a snapshot
    that takes that same lock from inside the condition deadlocks the
    master (watch thread: cond->topology.lock; heartbeat thread:
    topology.lock->cond)."""
    import threading
    topo_lock = threading.Lock()
    entered = threading.Event()
    release = threading.Event()
    hub = None

    def snapshot():
        entered.set()
        release.wait(5)
        with topo_lock:
            return {}

    hub = WatchHub(snapshot)

    def watcher():
        hub.wait(0, timeout=5)

    def heartbeat():
        entered.wait(5)
        with topo_lock:  # topology.lock held...
            hub.publish("new", 1, "n1")  # ...then the hub condition
        release.set()

    t1 = threading.Thread(target=watcher)
    t2 = threading.Thread(target=heartbeat)
    t1.start(); t2.start()
    t1.join(8); t2.join(8)
    deadlocked = t1.is_alive() or t2.is_alive()
    release.set()
    assert not deadlocked, "watch/heartbeat lock-order inversion"


# -- live cluster ------------------------------------------------------------

@pytest.fixture
def cluster(tmp_path):
    master = MasterServer(port=0, volume_size_limit_mb=64,
                          pulse_seconds=1).start()
    servers = []
    for i in range(2):
        vs = VolumeServer(port=0, directories=[str(tmp_path / f"v{i}")],
                          master_url=master.url, pulse_seconds=1,
                          max_volume_counts=[20],
                          ec_backend="numpy").start()
        servers.append(vs)
    yield master, servers
    for vs in servers:
        vs.stop()
    master.stop()


def wait_until(pred, timeout=8.0, step=0.1):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(step)
    return False


def test_push_propagates_new_and_dead_locations(cluster):
    master, (vs0, vs1) = cluster
    a = op.assign(master.url, replication="001")
    vid = int(a["fid"].split(",")[0])
    op.upload(a["url"], a["fid"], b"watched" * 100, filename="w.bin")

    vm = VidMap(master.url).start()
    assert wait_until(lambda: vm.lookup(vid) is not None, 5), \
        "snapshot/new event never arrived"
    assert set(vm.lookup(vid)) == {vs0.url, vs1.url}

    # clean shutdown -> goodbye -> push -> the map drops the node well
    # inside the old 10s TTL window
    primary = vs0 if vs0.store.find_volume(vid) else vs1
    dead = vs1 if primary is vs0 else vs0
    dead.stop()
    t = time.monotonic()
    assert wait_until(lambda: vm.lookup(vid) == [primary.url], 5), \
        "deletion push never arrived"
    assert time.monotonic() - t < 5
    # reads keep working through the surviving replica via a watching cache
    cache = op.VidCache(master.url, watch=True)
    assert op.read_file(master.url, a["fid"], cache=cache) \
        == b"watched" * 100
    vm.stop()


def test_watch_endpoint_shape(cluster):
    master, _ = cluster
    out = get_json(f"http://{master.url}/cluster/watch?since=0&timeout=1")
    assert out.get("reset") is True and "locations" in out
    seq = out["seq"]
    out2 = get_json(
        f"http://{master.url}/cluster/watch?since={seq}&timeout=0.3")
    assert out2["events"] == []
