"""FilerServer integration: master + volume servers + filer over HTTP.

Covers the reference's autoChunk write path
(filer_server_handlers_write_autochunk.go), streaming reads, listing,
recursive delete with chunk cleanup, rename, and the metadata event
long-poll (`weed watch` analog).
"""

import json

import pytest

from seaweedfs_tpu.client import operation as op
from seaweedfs_tpu.server.filer_server import FilerServer
from seaweedfs_tpu.server.http_util import (HttpError, get_json, http_call,
                                            post_multipart)
from seaweedfs_tpu.server.master import MasterServer
from seaweedfs_tpu.server.volume_server import VolumeServer

from conftest import wait_until


@pytest.fixture
def cluster(tmp_path):
    master = MasterServer(port=0, volume_size_limit_mb=64,
                          pulse_seconds=1).start()
    servers = [VolumeServer(port=0, directories=[str(tmp_path / f"v{i}")],
                            master_url=master.url, pulse_seconds=1,
                            max_volume_counts=[20],
                            ec_backend="numpy").start()
               for i in range(2)]
    filer = FilerServer(port=0, master_url=master.url,
                        chunk_size=1024).start()
    yield master, servers, filer
    filer.stop()
    for vs in servers:
        vs.stop()
    master.stop()


def furl(filer, path):
    return f"http://{filer.url}{path}"


def _gone(master, fid) -> bool:
    """The chunk no longer reads. Polled: the filer's own deletion loop
    may have taken the queue a moment before `flush_deletions()` did,
    and still be deleting when that returns with nothing to do."""
    try:
        op.read_file(master.url, fid)
    except HttpError:
        return True
    return False


def test_upload_read_small(cluster):
    _, _, filer = cluster
    data = b"hello filer world"
    r = post_multipart(furl(filer, "/docs/hello.txt"), "hello.txt", data,
                       "text/plain")
    assert r["size"] == len(data)
    got = http_call("GET", furl(filer, "/docs/hello.txt"))
    assert got == data


def test_empty_upload_round_trips(cluster):
    """PUT of an empty body stores an entry with no chunks (round-2
    advisor: the volume layer rejects zero-size needles — tombstone
    format — so empties must live purely at the filer layer)."""
    _, _, filer = cluster
    r = post_multipart(furl(filer, "/docs/empty.txt"), "empty.txt", b"",
                       "text/plain")
    assert r["size"] == 0
    entry = filer.filer.find_entry("/docs/empty.txt")
    assert entry.chunks == []
    assert http_call("GET", furl(filer, "/docs/empty.txt")) == b""


def test_chunked_upload_and_range(cluster):
    _, _, filer = cluster
    data = bytes(range(256)) * 20  # 5120 bytes -> 5 chunks of 1024
    post_multipart(furl(filer, "/big.bin"), "big.bin", data)
    entry = filer.filer.find_entry("/big.bin")
    assert len(entry.chunks) == 5
    assert http_call("GET", furl(filer, "/big.bin")) == data
    # range crossing a chunk boundary
    got = http_call("GET", furl(filer, "/big.bin"),
                    headers={"Range": "bytes=1000-3000"})
    assert got == data[1000:3001]
    # suffix range
    got = http_call("GET", furl(filer, "/big.bin"),
                    headers={"Range": "bytes=-100"})
    assert got == data[-100:]


def test_listing_pagination(cluster):
    _, _, filer = cluster
    for name in ["a.txt", "b.txt", "c.txt"]:
        post_multipart(furl(filer, f"/dir/{name}"), name, b"x")
    out = get_json(furl(filer, "/dir/?limit=2"))
    assert [e["FullPath"] for e in out["entries"]] == ["/dir/a.txt",
                                                      "/dir/b.txt"]
    assert out["shouldDisplayLoadMore"]
    out = get_json(furl(filer, "/dir/?limit=2&lastFileName=b.txt"))
    assert [e["FullPath"] for e in out["entries"]] == ["/dir/c.txt"]


def test_overwrite_deletes_old_chunks(cluster):
    master, _, filer = cluster
    post_multipart(furl(filer, "/f.bin"), "f.bin", b"version-one")
    old_fid = filer.filer.find_entry("/f.bin").chunks[0].fid
    post_multipart(furl(filer, "/f.bin"), "f.bin", b"version-two!")
    assert http_call("GET", furl(filer, "/f.bin")) == b"version-two!"
    filer.flush_deletions()
    assert wait_until(lambda: _gone(master, old_fid))


def test_delete_recursive_cleans_chunks(cluster):
    master, _, filer = cluster
    post_multipart(furl(filer, "/tree/x/1.bin"), "1.bin", b"one")
    post_multipart(furl(filer, "/tree/2.bin"), "2.bin", b"two")
    fid = filer.filer.find_entry("/tree/x/1.bin").chunks[0].fid
    # non-recursive delete of non-empty dir -> 409
    with pytest.raises(HttpError):
        http_call("DELETE", furl(filer, "/tree"))
    http_call("DELETE", furl(filer, "/tree?recursive=true"))
    with pytest.raises(HttpError):
        http_call("GET", furl(filer, "/tree/2.bin"))
    filer.flush_deletions()
    assert wait_until(lambda: _gone(master, fid))


def test_rename(cluster):
    _, _, filer = cluster
    post_multipart(furl(filer, "/old/name.txt"), "name.txt", b"data")
    http_call("POST", furl(filer, "/old/name.txt?mv.to=/new/name2.txt"))
    assert http_call("GET", furl(filer, "/new/name2.txt")) == b"data"
    with pytest.raises(HttpError):
        http_call("GET", furl(filer, "/old/name.txt"))


def test_upload_into_directory_path(cluster):
    # POST /dir/ with a multipart file stores /dir/<filename>
    _, _, filer = cluster
    post_multipart(furl(filer, "/incoming/"), "x.jpg", b"jpegbytes")
    assert http_call("GET", furl(filer, "/incoming/x.jpg")) == b"jpegbytes"


def test_bad_range_is_416_not_500(cluster):
    _, _, filer = cluster
    post_multipart(furl(filer, "/r.bin"), "r.bin", b"0123456789")
    for bad in ("bytes=abc-", "bytes=5-2"):
        with pytest.raises(HttpError) as e:
            http_call("GET", furl(filer, "/r.bin"),
                      headers={"Range": bad})
        assert e.value.status == 416, bad


def test_mkdir_and_head(cluster):
    _, _, filer = cluster
    http_call("POST", furl(filer, "/emptydir?op=mkdir"))
    out = get_json(furl(filer, "/emptydir"))
    assert out["entries"] == []
    post_multipart(furl(filer, "/h.bin"), "h.bin", b"x" * 100)
    # HEAD does not stream the body
    assert http_call("HEAD", furl(filer, "/h.bin")) == b""


def test_events_longpoll(cluster):
    _, _, filer = cluster
    post_multipart(furl(filer, "/ev.txt"), "ev.txt", b"x")
    out = get_json(furl(filer, "/filer/events?since=0&timeout=2"))
    paths = [e["event"]["newEntry"]["path"] for e in out["events"]
             if e["event"]["newEntry"]]
    assert "/ev.txt" in paths
    # nothing new after the last ts -> empty after timeout
    last = out["events"][-1]["ts"]
    out2 = get_json(furl(filer, f"/filer/events?since={last}&timeout=0.2"))
    assert out2["events"] == []


def test_sqlite_store_persistence(cluster, tmp_path):
    master, _, _ = cluster
    db = str(tmp_path / "filer.db")
    f1 = FilerServer(port=0, master_url=master.url, store="sqlite",
                     store_options={"path": db}).start()
    post_multipart(f"http://{f1.url}/persist.txt", "persist.txt", b"keep")
    f1.stop()
    f2 = FilerServer(port=0, master_url=master.url, store="sqlite",
                     store_options={"path": db}).start()
    assert http_call("GET", f"http://{f2.url}/persist.txt") == b"keep"
    f2.stop()


def test_multipart_preserves_trailing_newlines(cluster):
    """Regression: the multipart parser must strip exactly one CRLF per
    boundary side — payloads ending in newline bytes arrive intact."""
    _, _, filer = cluster
    data = b"line one\nline two\n\r\n"
    post_multipart(furl(filer, "/nl.txt"), "nl.txt", data, "text/plain")
    assert http_call("GET", furl(filer, "/nl.txt")) == data
    data2 = b"\r\nstarts and ends with crlf\r\n"
    post_multipart(furl(filer, "/nl2.bin"), "nl2.bin", data2)
    assert http_call("GET", furl(filer, "/nl2.bin")) == data2


def test_cli_filer_copy(cluster, tmp_path):
    """weed filer.copy walks local trees into the filer (reference
    weed/command/filer_copy.go)."""
    import os
    import subprocess
    import sys
    _, _, filer = cluster
    src = tmp_path / "tree"
    (src / "sub").mkdir(parents=True)
    (src / "a.txt").write_bytes(b"alpha")
    (src / "sub" / "b.pdf").write_bytes(b"%PDF beta")
    (src / "sub" / "skip.bin").write_bytes(b"nope")
    single = tmp_path / "single.txt"
    single.write_bytes(b"solo")
    out = subprocess.run(
        [sys.executable, "-m", "seaweedfs_tpu.command.cli",
         "filer.copy", str(src), str(single),
         f"http://{filer.url}/imported/"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr
    assert http_call("GET", furl(filer, "/imported/tree/a.txt")) == \
        b"alpha"
    assert http_call("GET", furl(filer, "/imported/tree/sub/b.pdf")) == \
        b"%PDF beta"
    assert http_call("GET", furl(filer, "/imported/single.txt")) == \
        b"solo"
    # -include filters
    out = subprocess.run(
        [sys.executable, "-m", "seaweedfs_tpu.command.cli",
         "filer.copy", "-include", "*.pdf", str(src),
         f"http://{filer.url}/pdfonly/"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr
    assert http_call("GET", furl(filer, "/pdfonly/tree/sub/b.pdf")) == \
        b"%PDF beta"
    import pytest as _pytest
    from seaweedfs_tpu.server.http_util import HttpError
    with _pytest.raises(HttpError):
        http_call("GET", furl(filer, "/pdfonly/tree/a.txt"))


def test_upload_retries_past_frozen_volume(cluster):
    """A volume frozen between assign and upload (maintenance window)
    must not fail the client's write: split_and_upload re-assigns."""
    import seaweedfs_tpu.client.operation as op_mod
    master, servers, filer = cluster
    # prime: make at least one writable volume exist
    post_multipart(furl(filer, "/warm/x.bin"), "x.bin", b"warm")
    # freeze EVERY current volume directly on the holders (the master
    # won't know until the next pulse — exactly the race window)
    frozen = []
    for vs in servers:
        for loc in vs.store.locations:
            for vid, v in list(loc.volumes.items()):
                if not v.readonly:
                    v.readonly = True
                    frozen.append((vs, vid))
    assert frozen
    try:
        # first upload attempt(s) will hit a frozen volume and 500;
        # thaw after the first rejection so a retry can land (mimics
        # the maintenance window ending / master rerouting)
        orig_upload = op_mod.upload
        state = {"rejections": 0}

        def flaky_upload(url, fid, data, **kw):
            try:
                return orig_upload(url, fid, data, **kw)
            except Exception:
                state["rejections"] += 1
                for vs, vid in frozen:
                    vs.store.mark_volume_readonly(vid, False)
                raise

        op_mod.upload = flaky_upload
        try:
            r = post_multipart(furl(filer, "/warm/retry.bin"),
                               "retry.bin", b"written-through-freeze")
        finally:
            op_mod.upload = orig_upload
        assert r["size"] == len(b"written-through-freeze")
        assert state["rejections"] >= 1, "freeze never hit: test vacuous"
        got = http_call("GET", furl(filer, "/warm/retry.bin"))
        assert got == b"written-through-freeze"
    finally:
        for vs, vid in frozen:
            vs.store.mark_volume_readonly(vid, False)


def test_fresh_assign_blacklist_re_rolls(monkeypatch):
    """_fresh_assign skips blacklisted volumes and nodes, and falls
    back to the last roll when everything is blacklisted."""
    from seaweedfs_tpu.filer.upload import _fresh_assign

    picks = [{"fid": "3,aa", "url": "dead:1"},
             {"fid": "5,bb", "url": "live:1"},
             {"fid": "7,cc", "url": "live:2"}]
    i = [0]

    def fake_assign(master_url, **kw):
        a = picks[i[0] % len(picks)]
        i[0] += 1
        return a

    import seaweedfs_tpu.client.operation as op_mod
    monkeypatch.setattr(op_mod, "assign", fake_assign)
    # vid 3 blacklisted -> lands on the next pick
    a = _fresh_assign("m", "", "", "", {"3"}, set())
    assert a["fid"] == "5,bb"
    # node blacklisted -> skips every volume it fronts
    i[0] = 0
    a = _fresh_assign("m", "", "", "", set(), {"dead:1"})
    assert a["url"] != "dead:1"
    # everything blacklisted -> still returns a pick (last roll)
    i[0] = 0
    a = _fresh_assign("m", "", "", "", {"3", "5", "7"}, set())
    assert a is not None


def test_assign_level_failures_retry(monkeypatch):
    """A master mid leader-transition (503) or an all-frozen moment
    (406) during ASSIGN retries instead of failing the write."""
    from seaweedfs_tpu.filer.upload import _assign_and_upload
    from seaweedfs_tpu.server.http_util import HttpError

    import seaweedfs_tpu.client.operation as op_mod
    calls = {"assign": 0, "upload": 0}

    def flaky_assign(master_url, **kw):
        calls["assign"] += 1
        if calls["assign"] == 1:
            raise HttpError(503, "no raft leader elected yet")
        if calls["assign"] == 2:
            raise HttpError(406, "no free volumes")
        return {"fid": "9,dd", "url": "srv:1"}

    def ok_upload(url, fid, data, **kw):
        calls["upload"] += 1
        return {"size": len(data)}

    monkeypatch.setattr(op_mod, "assign", flaky_assign)
    monkeypatch.setattr(op_mod, "upload", ok_upload)
    monkeypatch.setattr("time.sleep", lambda s: None)
    a, up = _assign_and_upload("m", b"x", "f", "t", "", "", "")
    assert a["fid"] == "9,dd" and calls["upload"] == 1
    # a 400-class assign error is NOT retried
    def fatal_assign(master_url, **kw):
        raise HttpError(400, "bad replication")
    monkeypatch.setattr(op_mod, "assign", fatal_assign)
    with pytest.raises(HttpError) as ei:
        _assign_and_upload("m", b"x", "f", "t", "", "", "")
    assert ei.value.status == 400


def test_ec_read_never_serves_wrong_needle(cluster, tmp_path):
    """A blob that parses as a VALID needle with the wrong id must 500,
    not be served (cookies can collide; id is the identity)."""
    import numpy as np

    from seaweedfs_tpu.client import operation as op
    from seaweedfs_tpu.server.http_util import HttpError, post_json
    master, servers, _ = cluster
    a = op.assign(master.url, collection="wrid")
    vid = int(a["fid"].split(",")[0])
    rng = np.random.default_rng(3)
    for i in range(1, 6):
        op.upload(a["url"], f"{vid},{i:x}00000001",
                  rng.integers(0, 256, 50_000).astype(np.uint8).tobytes(),
                  filename=f"f{i}")
    holder = next(vs for vs in servers if vs.store.find_volume(vid))
    post_json(f"http://{holder.url}/admin/volume/readonly?volume={vid}")
    post_json(f"http://{holder.url}/admin/ec/generate?volume={vid}"
              f"&collection=wrid")
    post_json(f"http://{holder.url}/admin/ec/mount?volume={vid}"
              f"&collection=wrid&shards="
              + ",".join(str(s) for s in range(14)))
    post_json(f"http://{holder.url}/admin/delete_volume?volume={vid}")
    # sanity: EC reads serve the right needles
    from seaweedfs_tpu.server.http_util import http_call
    assert http_call("GET", f"http://{holder.url}/{vid},100000001")
    # monkey-wrench the index lookup to return needle 2's location for
    # needle 1: the id check must refuse to serve it
    ev = holder.store.find_ec_volume(vid)
    real_locate = ev.locate_needle

    def wrong_locate(key):
        return real_locate(2) if key == 1 else real_locate(key)

    ev.locate_needle = wrong_locate
    with pytest.raises(HttpError) as ei:
        http_call("GET", f"http://{holder.url}/{vid},100000001")
    assert ei.value.status == 500 and "assembled needle" in str(ei.value)
    ev.locate_needle = real_locate


def test_mode_param_and_skip_chunk_deletion(cluster):
    """Reference parity: ?mode= octal on writes
    (filer_server_handlers_write.go:156) and ?skipChunkDeletion=true
    on deletes (metadata-only removal, chunks left alive)."""
    master, vs, fs = cluster
    http_call("PUT", f"http://{fs.url}/moded.bin?mode=755",
              body=b"moded-content")
    entry = fs.filer.find_entry("/moded.bin")
    assert entry.attr.mode == 0o755
    fid = entry.chunks[0].fid
    # delete metadata only; the chunk must still be readable
    http_call("DELETE", f"http://{fs.url}/moded.bin?skipChunkDeletion=true")
    with pytest.raises(HttpError):
        http_call("GET", f"http://{fs.url}/moded.bin")
    # drain the deletion queue synchronously: skipChunkDeletion must
    # have queued nothing, so the chunk survives a full sweep
    fs.flush_deletions()
    assert not fs.filer._deletion_queue
    assert op.read_file(master.url, fid) == b"moded-content"


def test_events_path_prefix_filter(cluster):
    """Server-side prefix filter (reference watch -pathPrefix) plus the
    cursor that prevents a busy loop when a batch filters to empty."""
    from seaweedfs_tpu.replication import EventSubscriber
    _, _, filer = cluster
    post_multipart(furl(filer, "/pfx/in.txt"), "in.txt", b"a")
    post_multipart(furl(filer, "/other/out.txt"), "out.txt", b"b")
    # component boundary: a sibling tree sharing the prefix string must
    # NOT match (/pfx must not capture /pfxother), while the watched
    # root itself must
    post_multipart(furl(filer, "/pfxother/sib.txt"), "sib.txt", b"c")
    out = get_json(furl(filer,
                        "/filer/events?since=0&timeout=2&prefix=/pfx"))
    paths = [(e["event"].get("newEntry") or
              e["event"].get("oldEntry") or {}).get("path")
             for e in out["events"]]
    assert "/pfx/in.txt" in paths
    assert all(p == "/pfx" or str(p).startswith("/pfx/")
               for p in paths), paths
    # a trailing-slash prefix (FilerSource normalizes to '/pfx/') still
    # matches the root-dir event for /pfx itself
    out2 = get_json(furl(filer,
                         "/filer/events?since=0&timeout=2&prefix=/pfx/"))
    paths2 = [(e["event"].get("newEntry") or
               e["event"].get("oldEntry") or {}).get("path")
              for e in out2["events"]]
    assert "/pfx" in paths2  # the mkdir event of the watched root
    # cursor covers the filtered-out /other event too
    assert out["cursor"] >= max(
        e["ts"] for e in get_json(
            furl(filer, "/filer/events?since=0&timeout=0.2"))["events"])

    # a subscriber watching a prefix that matches NOTHING must advance
    # past foreign events rather than rescan them forever
    sub = EventSubscriber(filer.url, path_prefix="/nothing-matches",
                          poll_timeout=0.2)
    assert sub.poll_once() == []
    advanced = sub.since
    assert advanced > 0  # jumped to the scanned high-water mark
    assert sub.poll_once() == []
    assert sub.since >= advanced

    # the replicator pattern (advance=False, then commit) must also
    # advance past scanned-but-filtered batches via commit
    sub2 = EventSubscriber(filer.url, path_prefix="/nothing-matches",
                           poll_timeout=0.2)
    batch = sub2.poll_once(advance=False)
    assert batch == [] and sub2.since == 0.0
    sub2.commit(batch)
    assert sub2.since > 0  # commit consumed the scanned mark
