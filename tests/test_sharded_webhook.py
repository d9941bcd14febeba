"""Sharded filer store persistence + webhook notification publisher."""

import json
import threading

import pytest

from seaweedfs_tpu.filer import Entry, ShardedStore
from seaweedfs_tpu.notification import make_publisher
from seaweedfs_tpu.replication.sink import SinkError, make_sink


def test_sharded_store_persists_across_reopen(tmp_path):
    s = ShardedStore()
    s.initialize(path=str(tmp_path / "meta"), shards=4)
    paths = [f"/dir{i}/f{j}" for i in range(6) for j in range(3)]
    for p in paths:
        s.insert_entry(Entry(full_path=p))
    s.close()
    # shard files exist on disk and the namespace reloads intact
    dbs = list((tmp_path / "meta").glob("filer_*.db"))
    assert len(dbs) == 4
    s2 = ShardedStore()
    s2.initialize(path=str(tmp_path / "meta"), shards=4)
    for p in paths:
        assert s2.find_entry(p) is not None, p
    names = [e.name for e in
             s2.list_directory_entries("/dir3", "", False, 100)]
    assert names == ["f0", "f1", "f2"]
    s2.close()


def test_sharded_store_shard_count_is_sticky(tmp_path):
    """Reopening with a different `shards` must not re-route md5 % N and
    hide existing entries — the SHARDS marker wins."""
    s = ShardedStore()
    s.initialize(path=str(tmp_path / "meta"), shards=8)
    for i in range(12):
        s.insert_entry(Entry(full_path=f"/p{i}/f"))
    s.close()
    s2 = ShardedStore()
    s2.initialize(path=str(tmp_path / "meta"), shards=3)  # ignored
    assert s2._n == 8
    for i in range(12):
        assert s2.find_entry(f"/p{i}/f") is not None
    s2.close()


def test_sharded_store_spreads_directories(tmp_path):
    s = ShardedStore()
    s.initialize(path=str(tmp_path / "m"), shards=4)
    for i in range(40):
        s.insert_entry(Entry(full_path=f"/d{i}/x"))
    s.close()
    sizes = [p.stat().st_size for p in sorted((tmp_path / "m").glob("*.db"))]
    assert sum(1 for sz in sizes if sz > 0) >= 3  # >1 shard actually used


def test_webhook_publisher_delivers_and_signs():
    from seaweedfs_tpu.server.http_util import HttpServer, Request, Router
    got = []
    router = Router()

    def receive(req: Request):
        got.append((req.headers.get("X-Seaweed-Signature"), req.body))
        return {"ok": True}

    router.add("POST", "/hook", receive)
    srv = HttpServer(0, router, "127.0.0.1")
    srv.start()
    try:
        p = make_publisher("webhook",
                           url=f"http://127.0.0.1:{srv.port}/hook",
                           hmac_key="sekret")
        p.send("/buckets/b/file", {"type": "create", "size": 3})
        assert len(got) == 1
        sig, body = got[0]
        payload = json.loads(body)
        assert payload["key"] == "/buckets/b/file"
        assert payload["event"]["type"] == "create"
        import hashlib
        import hmac as hmac_mod
        assert sig == hmac_mod.new(b"sekret", body,
                                   hashlib.sha256).hexdigest()
    finally:
        srv.stop()


def test_webhook_publisher_retries_then_fails():
    p = make_publisher("webhook", url="http://127.0.0.1:9/hook",
                       retries=2, timeout=0.5)
    with pytest.raises(RuntimeError, match="after 2 attempts"):
        p.send("/k", {"type": "create"})


def test_sink_registry_shapes():
    # gcs/b2 construct real S3-compatible clients; azure errors clearly
    sink = make_sink({"type": "gcs", "bucket": "bkt",
                      "access_key": "a", "secret_key": "s"})
    assert "storage.googleapis.com" in sink.s3.endpoint
    sink2 = make_sink({"type": "b2", "bucket": "bkt"})
    assert "backblazeb2.com" in sink2.s3.endpoint
    # azure is now a real SharedKey sink; missing config still
    # surfaces as a SinkError
    with pytest.raises(SinkError, match="azure sink config"):
        make_sink({"type": "azure"})
    sink3 = make_sink({"type": "azure", "account": "acct",
                       "account_key": "a2V5", "container": "c"})
    assert sink3.endpoint == "https://acct.blob.core.windows.net"
    with pytest.raises(SinkError, match="unknown sink"):
        make_sink({"type": "nope"})
