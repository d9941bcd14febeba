"""Round-3 regressions: parallel fan-out semantics, the tiered EC
shard-location cache, and delete-replication failures surfacing."""

import time

import numpy as np
import pytest

from seaweedfs_tpu.client import operation as op
from seaweedfs_tpu.ec.shard_cache import EcShardLocationCache
from seaweedfs_tpu.server.http_util import HttpError, http_call
from seaweedfs_tpu.server.master import MasterServer
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.util.fanout import fan_out, fan_out_must_succeed


# -- fan_out -----------------------------------------------------------------

def test_fan_out_preserves_order_and_errors():
    def work(x):
        if x == 3:
            raise ValueError("boom")
        return x * 2

    out = fan_out(work, [1, 2, 3, 4])
    assert [(i, r) for i, r, e in out if e is None] == [(1, 2), (2, 4),
                                                       (4, 8)]
    bad = [(i, e) for i, r, e in out if e is not None]
    assert len(bad) == 1 and bad[0][0] == 3
    assert isinstance(bad[0][1], ValueError)


def test_fan_out_actually_concurrent():
    import threading
    gate = threading.Barrier(4, timeout=5)

    def work(_):
        gate.wait()  # deadlocks unless all 4 run at once
        return True

    assert all(r for _, r, e in fan_out(work, list(range(4))))


def test_fan_out_must_succeed_whitelist():
    def work(x):
        raise HttpError(404 if x == "a" else 500, "nope")

    with pytest.raises(RuntimeError, match="b: "):
        fan_out_must_succeed(
            work, ["a", "b"], what="op",
            ok=lambda e: isinstance(e, HttpError) and e.status == 404)
    # all-benign failures pass
    fan_out_must_succeed(
        work, ["a"], what="op",
        ok=lambda e: isinstance(e, HttpError) and e.status == 404)


# -- EcShardLocationCache ----------------------------------------------------

def test_ec_cache_hits_and_forget():
    calls = []

    def fetch(vid):
        calls.append(vid)
        return {s: ["n1", "n2"] for s in range(14)}

    cache = EcShardLocationCache(fetch)
    first = cache.lookup(7)
    assert cache.lookup(7) == first and calls == [7]  # cached (37min tier)
    cache.forget(7, 3, "n1")
    assert cache.lookup(7)[3] == ["n2"] and calls == [7]  # no refetch
    assert cache.lookup(7)[4] == ["n1", "n2"]  # other shards untouched
    cache.invalidate(7)
    cache.lookup(7)
    assert calls == [7, 7]


def test_ec_cache_few_shards_expire_fast(monkeypatch):
    clock = [100.0]
    monkeypatch.setattr(time, "monotonic", lambda: clock[0])
    calls = []

    def fetch(vid):
        calls.append(vid)
        return {0: ["n1"]}  # < k shards known

    cache = EcShardLocationCache(fetch)
    cache.lookup(1)
    clock[0] += 5
    cache.lookup(1)
    assert calls == [1]  # < 11s: still fresh
    clock[0] += 7
    cache.lookup(1)
    assert calls == [1, 1]  # > 11s: refetched


# -- delete replication must surface failures --------------------------------

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


def test_failed_replica_delete_surfaces(cluster):
    """A replica that misses a delete silently resurrects the needle via
    read redirects; the primary must fail the delete instead of swallowing
    the error (reference ReplicatedDelete semantics)."""
    master, (vs0, vs1) = cluster
    a = op.assign(master.url, replication="001")
    payload = b"delete-me" * 50
    op.upload(a["url"], a["fid"], payload, filename="d.bin")
    vid = int(a["fid"].split(",")[0])
    primary = vs0 if vs0.store.find_volume(vid) else vs1
    replica = vs1 if primary is vs0 else vs0
    # prime the primary's lookup cache while both replicas are alive
    assert len(primary._other_replicas(vid)) == 1
    # simulate a CRASH (no /cluster/goodbye, heartbeats just stop): the
    # master still routes to the dead replica, so the fan-out must fail
    replica._stop.set()
    replica.server.stop()
    with pytest.raises(HttpError) as ei:
        http_call("DELETE", f"http://{primary.url}/{a['fid']}")
    assert ei.value.status == 500


def test_delete_404_on_replica_is_benign(cluster):
    """The goal state of a delete is 'gone on every replica' — a replica
    already missing the needle must not fail the client's delete."""
    master, (vs0, vs1) = cluster
    a = op.assign(master.url, replication="001")
    op.upload(a["url"], a["fid"], b"x" * 100, filename="x.bin")
    vid = int(a["fid"].split(",")[0])
    primary = vs0 if vs0.store.find_volume(vid) else vs1
    replica = vs1 if primary is vs0 else vs0
    # delete on the replica directly first (no fan-out from there)
    http_call("DELETE", f"http://{replica.url}/{a['fid']}?type=replicate")
    # now the primary's fan-out sees the needle already gone -> still 200
    http_call("DELETE", f"http://{primary.url}/{a['fid']}")
    with pytest.raises(HttpError):
        op.read_file(master.url, a["fid"])
