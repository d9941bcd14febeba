"""Native C++ read plane: byte/semantic parity with the Python server.

The plane (server/native/http_plane.cc) serves plain needle GETs on a
second port; everything it answers must be indistinguishable from the
Python server's answer for the same request, and everything it can't
serve must 307 to the Python server (which the pooled client follows
transparently for GET/HEAD).
"""

import json
import time
from types import SimpleNamespace

import pytest

from seaweedfs_tpu.server.http_util import (HttpError, get_json,
                                            http_call,
                                            http_get_with_headers,
                                            post_json, post_multipart)
from seaweedfs_tpu.server.master import MasterServer
from seaweedfs_tpu.server.native_plane import available
from seaweedfs_tpu.server.volume_server import VolumeServer

pytestmark = pytest.mark.skipif(
    not available(), reason="libseaweed_http.so unavailable")


@pytest.fixture
def cluster(tmp_path):
    master = MasterServer(port=0, pulse_seconds=1).start()
    vs = VolumeServer(port=0, directories=[str(tmp_path / "v0")],
                      master_url=master.url, pulse_seconds=1,
                      max_volume_counts=[10], ec_backend="numpy").start()
    assert vs.fast_plane is not None, "plane should start by default"
    yield master, vs
    vs.stop()
    master.stop()


def assign_and_upload(master, data, filename="f.bin",
                      ctype="application/octet-stream", headers=None):
    a = post_json(f"http://{master.url}/dir/assign", {})
    post_multipart(f"http://{a['url']}/{a['fid']}", filename, data, ctype,
                   headers=headers)
    return a["fid"], a["url"]


def wait_until(pred, timeout=5.0, interval=0.01):
    """Poll an asynchronously-updated condition. The plane records
    telemetry AFTER the response bytes are on the wire (the timing spans
    the full write), so a client can observe its reply before the
    counters or the slow ring move."""
    deadline = time.monotonic() + timeout
    while True:
        v = pred()
        if v or time.monotonic() >= deadline:
            return v
        time.sleep(interval)


def raw_get(hostport, path, headers=None, method="GET"):
    """Single-socket HTTP roundtrip WITHOUT redirect following, so
    the plane's own status codes are observable."""
    import http.client
    c = http.client.HTTPConnection(hostport, timeout=10)
    c.request(method, path, headers=headers or {})
    r = c.getresponse()
    body = r.read()
    out = (r.status, dict((k.lower(), v) for k, v in r.getheaders()), body)
    c.close()
    return out


class TestParity:
    def compare(self, vs, fid, headers=None, method="GET"):
        """Same request to both planes; status/body and the semantic
        headers must match."""
        ps, ph, pb = raw_get(vs.url, f"/{fid}", headers, method)
        fs, fh, fb = raw_get(vs.fast_url, f"/{fid}", headers, method)
        assert ps == fs
        if ps < 400:  # payloads must be identical; error TEXT may differ
            assert pb == fb
            for h in ("content-type", "etag", "content-disposition",
                      "content-range", "accept-ranges", "last-modified"):
                assert ph.get(h) == fh.get(h), \
                    f"{h}: {ph.get(h)!r} != {fh.get(h)!r}"
        return fs, fh, fb

    def test_plain_roundtrip(self, cluster):
        master, vs = cluster
        fid, _ = assign_and_upload(master, b"hello-native-plane" * 100)
        before = vs.fast_plane.served
        st, _, body = self.compare(vs, fid)
        assert st == 200 and body == b"hello-native-plane" * 100
        assert vs.fast_plane.served > before

    def test_named_mime_disposition(self, cluster):
        master, vs = cluster
        fid, _ = assign_and_upload(master, b"x" * 64, filename='we"ird.txt',
                                   ctype="text/plain")
        st, fh, _ = self.compare(vs, fid)
        assert st == 200
        assert fh["content-type"] == "text/plain"
        assert 'we\\"ird.txt' in fh["content-disposition"]

    def test_cookie_mismatch_404(self, cluster):
        master, vs = cluster
        fid, _ = assign_and_upload(master, b"data")
        bad = fid[:-8] + ("0" * 8 if not fid.endswith("0" * 8) else "1" * 8)
        st, _, _ = self.compare(vs, bad)
        assert st == 404

    def test_missing_needle_redirects_to_404(self, cluster):
        """An index miss is NOT authoritative on the plane (it could be
        a re-sync window): it 307s to Python, whose 404 is final."""
        master, vs = cluster
        fid, _ = assign_and_upload(master, b"data")
        vid = fid.split(",")[0]
        st, _, _ = raw_get(vs.fast_url, f"/{vid},deadbeef00000001")
        assert st == 307
        with pytest.raises(HttpError) as ei:
            http_get_with_headers(
                f"http://{vs.fast_url}/{vid},deadbeef00000001")
        assert ei.value.status == 404

    def test_deleted_needle_404(self, cluster):
        master, vs = cluster
        fid, url = assign_and_upload(master, b"to-die")
        http_call("DELETE", f"http://{url}/{fid}")
        st, _, _ = raw_get(vs.fast_url, f"/{fid}")
        assert st == 307  # deletion removed the mirror entry -> miss
        with pytest.raises(HttpError) as ei:
            http_get_with_headers(f"http://{vs.fast_url}/{fid}")
        assert ei.value.status == 404

    def test_range_request(self, cluster):
        master, vs = cluster
        fid, _ = assign_and_upload(master, bytes(range(200)))
        st, fh, body = self.compare(vs, fid,
                                    headers={"Range": "bytes=10-19"})
        assert st == 206 and body == bytes(range(10, 20))
        assert fh["content-range"] == "bytes 10-19/200"
        # suffix range
        st, _, body = self.compare(vs, fid, headers={"Range": "bytes=-5"})
        assert st == 206 and body == bytes(range(195, 200))

    def test_if_none_match_304(self, cluster):
        master, vs = cluster
        fid, _ = assign_and_upload(master, b"etag-me")
        _, h, _ = raw_get(vs.fast_url, f"/{fid}")
        etag = h["etag"]
        st, fh, body = self.compare(
            vs, fid, headers={"If-None-Match": etag})
        assert st == 304 and body == b""
        st, _, _ = self.compare(vs, fid, headers={"If-None-Match": "*"})
        assert st == 304

    def test_if_modified_since_304(self, cluster):
        master, vs = cluster
        fid, _ = assign_and_upload(master, b"dated")
        _, h, _ = raw_get(vs.fast_url, f"/{fid}")
        lm = h["last-modified"]
        st, fh, body = self.compare(
            vs, fid, headers={"If-Modified-Since": lm})
        assert st == 304 and body == b""
        # an older stamp does not suppress the body
        st, _, body = self.compare(
            vs, fid,
            headers={"If-Modified-Since":
                     "Mon, 01 Jan 2001 00:00:00 GMT"})
        assert st == 200 and body == b"dated"

    def test_head(self, cluster):
        master, vs = cluster
        fid, _ = assign_and_upload(master, b"head-me" * 10)
        st, fh, body = self.compare(vs, fid, method="HEAD")
        assert st == 200 and body == b""
        assert fh["content-length"] == str(70)

    def test_pairs_needle_redirects_but_serves(self, cluster):
        """Seaweed-* pairs are beyond the fast path: the plane must 307
        and the followed response must equal the Python answer."""
        master, vs = cluster
        fid, _ = assign_and_upload(master, b"pairs",
                                   headers={"Seaweed-color": "azure"})
        st, fh, _ = raw_get(vs.fast_url, f"/{fid}")
        assert st == 307
        assert fh["location"] == f"http://{vs.url}/{fid}"
        # the pooled client follows it and lands on the full semantics
        data, headers = http_get_with_headers(
            f"http://{vs.fast_url}/{fid}")
        assert data == b"pairs"
        assert {k.lower(): v for k, v in headers.items()}[
            "seaweed-color"] == "azure"

    def test_query_string_redirects(self, cluster):
        master, vs = cluster
        fid, _ = assign_and_upload(master, b"q")
        st, _, _ = raw_get(vs.fast_url, f"/{fid}?cm=false")
        assert st == 307

    def test_survives_compaction(self, cluster):
        master, vs = cluster
        keep, _ = assign_and_upload(master, b"keeper" * 50)
        die, url = assign_and_upload(master, b"victim" * 50)
        http_call("DELETE", f"http://{url}/{die}")
        vid = int(keep.split(",")[0])
        post_json(f"http://{vs.url}/admin/vacuum/compact?volume={vid}", {})
        post_json(f"http://{vs.url}/admin/vacuum/commit?volume={vid}", {})
        st, _, body = self.compare(vs, keep)
        assert st == 200 and body == b"keeper" * 50
        st, _, _ = raw_get(vs.fast_url, f"/{die}")
        assert st == 307  # compacted away -> mirror miss -> fallback
        with pytest.raises(HttpError) as ei:
            http_get_with_headers(f"http://{vs.fast_url}/{die}")
        assert ei.value.status == 404

    def test_unmounted_volume_redirects(self, cluster):
        master, vs = cluster
        fid, _ = assign_and_upload(master, b"bye")
        vid = int(fid.split(",")[0])
        post_json(f"http://{vs.url}/admin/volume/unmount?volume={vid}", {})
        st, _, _ = raw_get(vs.fast_url, f"/{fid}")
        assert st == 307  # plane no longer owns it; Python answers 404

    def test_post_redirects_with_body_drain(self, cluster):
        """Keep-alive connection: a POST (with body) then a GET on the
        same socket — the drained body must not desync parsing."""
        import http.client
        master, vs = cluster
        fid, _ = assign_and_upload(master, b"after-post")
        c = http.client.HTTPConnection(vs.fast_url, timeout=10)
        c.request("POST", f"/{fid}", body=b"x" * 4096,
                  headers={"Content-Type": "application/octet-stream"})
        r = c.getresponse()
        r.read()
        assert r.status == 307
        c.request("GET", f"/{fid}")
        r = c.getresponse()
        assert r.status == 200 and r.read() == b"after-post"
        c.close()


class TestDirectVolume:
    """Plane driven directly on a Volume (no servers): covers branches
    a live cluster can't easily reach."""

    def test_ttl_expired_needle_404(self, tmp_path):
        from seaweedfs_tpu.server.native_plane import NativeReadPlane
        from seaweedfs_tpu.storage.types import TTL
        from seaweedfs_tpu.storage.volume import Volume
        from seaweedfs_tpu.storage.needle import Needle
        v = Volume(str(tmp_path), "", 9, create=True)
        live = Needle(cookie=7, id=1, data=b"fresh")
        live.set_ttl(TTL.parse("1h"))
        live.set_last_modified()
        v.write_needle(live)
        dead = Needle(cookie=7, id=2, data=b"stale")
        dead.set_ttl(TTL.parse("1m"))
        dead.set_last_modified(int(time.time()) - 3600)  # an hour old
        v.write_needle(dead)
        plane = NativeReadPlane("127.0.0.1", 0, "127.0.0.1:1")
        try:
            assert plane.register_volume(v)
            hp = f"127.0.0.1:{plane.port}"
            st, _, body = raw_get(hp, "/9,0100000007")
            assert st == 200 and body == b"fresh"
            st, _, _ = raw_get(hp, "/9,0200000007")
            assert st == 404  # expired is authoritative: stored TTL says so
        finally:
            plane.stop()
            v.close()

    def test_connection_cap_503(self, tmp_path):
        import http.client
        from seaweedfs_tpu.server.native_plane import NativeReadPlane
        from seaweedfs_tpu.storage.volume import Volume
        from seaweedfs_tpu.storage.needle import Needle
        v = Volume(str(tmp_path), "", 3, create=True)
        v.write_needle(Needle(cookie=1, id=1, data=b"capped"))
        plane = NativeReadPlane("127.0.0.1", 0, "127.0.0.1:1",
                                max_conns=2)
        try:
            plane.register_volume(v)
            hp = f"127.0.0.1:{plane.port}"
            held = []
            for _ in range(2):   # occupy both slots with keep-alives
                c = http.client.HTTPConnection(hp, timeout=5)
                c.request("GET", "/3,0100000001")
                r = c.getresponse()
                assert r.status == 200 and r.read() == b"capped"
                held.append(c)
            deadline = time.time() + 5
            while True:          # the third connection is turned away
                c3 = http.client.HTTPConnection(hp, timeout=5)
                c3.request("GET", "/3,0100000001")
                st = c3.getresponse().status
                c3.close()
                if st == 503 or time.time() > deadline:
                    break
                time.sleep(0.1)  # accept-loop may lag the live count
            assert st == 503
            for c in held:       # freeing a slot restores service
                c.close()
            deadline = time.time() + 5
            while time.time() < deadline:
                c4 = http.client.HTTPConnection(hp, timeout=5)
                c4.request("GET", "/3,0100000001")
                r = c4.getresponse()
                ok = r.status == 200
                c4.close()
                if ok:
                    break
                time.sleep(0.1)
            assert ok
        finally:
            plane.stop()
            v.close()

    def test_metrics_expose_plane_counters(self, cluster):
        import re
        master, vs = cluster
        fid, _ = assign_and_upload(master, b"counted")
        before = vs.fast_plane.served
        raw_get(vs.fast_url, f"/{fid}")
        body = raw_get(vs.url, "/metrics")[2].decode()
        m = re.search(r'fast_plane_request_total\{outcome="served"\} '
                      r'(\d+)', body)
        assert m, body[-500:]
        assert int(m.group(1)) >= before + 1


class TestPlaneTelemetry:
    """In-plane counters, latency histogram, and the slow-request ring
    (ISSUE 14 native-plane telemetry)."""

    def test_concurrent_counter_consistency(self, cluster):
        """N threads of mixed traffic; the relaxed-atomic counters must
        sum exactly — a lost update would silently skew the fleet
        dashboards forever."""
        import threading
        master, vs = cluster
        fids = [assign_and_upload(master, b"count-%d" % i)[0]
                for i in range(8)]
        base = vs.fast_plane.stats()
        assert base is not None, "telemetry ABI missing"
        n_threads, per_thread = 8, 50

        def worker(tid):
            for i in range(per_thread):
                if i % 10 == 9:
                    # query string -> off-fast-path 307 (status_3xx +
                    # redirects both move)
                    raw_get(vs.fast_url,
                            f"/{fids[i % len(fids)]}?cm=false")
                else:
                    st, _, _ = raw_get(vs.fast_url,
                                       "/" + fids[(tid + i) % len(fids)])
                    assert st == 200

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert all(not t.is_alive() for t in threads)
        total = n_threads * per_thread
        redirects = n_threads * (per_thread // 10)
        wait_until(lambda: vs.fast_plane.stats()["requests"]
                   - base["requests"] >= total)
        snap = vs.fast_plane.stats()
        assert snap["requests"] - base["requests"] == total
        assert snap["status_2xx"] - base["status_2xx"] == \
            total - redirects
        assert snap["status_3xx"] - base["status_3xx"] == redirects
        assert snap["redirects"] - base["redirects"] == redirects
        assert snap["lat_count"] - base["lat_count"] == total
        # bucket counts are non-cumulative and must sum to lat_count
        assert sum(c for _, c in snap["buckets"]) == snap["lat_count"]
        assert snap["bytes_sent"] > base["bytes_sent"]

    def test_stats_disabled_freezes_counters(self, cluster):
        master, vs = cluster
        fid, _ = assign_and_upload(master, b"frozen")
        vs.fast_plane.set_stats_enabled(False)
        try:
            base = vs.fast_plane.stats()
            raw_get(vs.fast_url, f"/{fid}")
            snap = vs.fast_plane.stats()
            assert snap["requests"] == base["requests"]
            assert snap["lat_count"] == base["lat_count"]
        finally:
            vs.fast_plane.set_stats_enabled(True)
        raw_get(vs.fast_url, f"/{fid}")
        assert wait_until(lambda: vs.fast_plane.stats()["requests"]
                          > base["requests"])

    def test_slow_ring_and_admin_endpoint(self, cluster):
        """With the threshold floored, every request is 'slow': the
        ring captures it and GET /admin/plane/slow serves it newest-
        first through the Python server."""
        master, vs = cluster
        fid, _ = assign_and_upload(master, b"slowpoke" * 16)
        vs.fast_plane.set_slow_us(0)
        try:
            raw_get(vs.fast_url, f"/{fid}")
            slow = wait_until(vs.fast_plane.slow_requests)
            assert slow, "floored threshold captured nothing"
            hit = next(e for e in slow if e["target"] == f"/{fid}")
            assert hit["method"] == "GET"
            assert hit["status"] == 200
            assert hit["bytes"] > 0
            assert hit["unix_ms"] > 0
            view = get_json(f"http://{vs.url}/admin/plane/slow")
            assert view["plane"] is True
            assert any(e["target"] == f"/{fid}" for e in view["slow"])
            assert view["stats"]["requests"] > 0
        finally:
            # restore the default so later tests don't churn the ring
            vs.fast_plane.set_slow_us(10000)

    def test_plane_families_exported_on_metrics(self, cluster):
        import re
        master, vs = cluster
        fid, _ = assign_and_upload(master, b"famous")
        base = vs.fast_plane.stats()["status_2xx"]
        raw_get(vs.fast_url, f"/{fid}")
        assert wait_until(lambda: vs.fast_plane.stats()["status_2xx"]
                          > base)
        body = raw_get(vs.url, "/metrics")[2].decode()
        m = re.search(r'SeaweedFS_volumeServer_plane_request_total'
                      r'\{class="2xx"\} (\d+)', body)
        assert m and int(m.group(1)) >= 1, body[-800:]
        assert "SeaweedFS_volumeServer_plane_request_seconds_bucket" \
            in body
        assert "SeaweedFS_volumeServer_plane_bytes_total" in body
        # ^-anchored: the unanchored pattern would match the family's
        # own HELP text ("1 if the one-time g++ build ... failed")
        m = re.search(r'^SeaweedFS_volumeServer_plane_build_failed (\d)',
                      body, re.M)
        assert m and m.group(1) == "0"
        # histogram totals mirror the native lat_count exactly
        snap = vs.fast_plane.stats()
        m = re.search(r'SeaweedFS_volumeServer_plane_request_seconds_'
                      r'count (\d+)', body)
        assert m and int(m.group(1)) <= snap["lat_count"]


class TestHostileInput:
    def test_malformed_requests_never_kill_the_plane(self, cluster):
        """Garbage, truncation, header floods and pipelining abuse must
        leave the plane serving; the process must never die."""
        import random
        import socket
        master, vs = cluster
        fid, _ = assign_and_upload(master, b"survivor")
        host, port = vs.fast_url.split(":")
        rng = random.Random(7)

        probes = [
            b"",                                   # connect-and-close
            b"\r\n\r\n",
            b"GET\r\n\r\n",
            b"GET / HTTP/1.1\r\n\r\n",
            b"FROB /x HTTP/1.1\r\n\r\n",
            b"GET " + b"/" * 8000 + b" HTTP/1.1\r\n\r\n",
            b"GET /1,0 HTTP/1.1\r\n" + b"X: y\r\n" * 3000 + b"\r\n",
            b"GET /999999999999999999,00"
            b"deadbeefcafebabe12345678 HTTP/1.1\r\n\r\n",
            b"GET /%zz%00%ff,0 HTTP/1.1\r\n\r\n",
            b"POST /a HTTP/1.1\r\nContent-Length: 99999999\r\n\r\nhi",
            b"POST /a HTTP/1.1\r\nContent-Length: -5\r\n\r\n",
            b"GET /1,01234567890 HTTP/1.1\r\nRange: bytes=\xff\xfe\r\n"
            b"\r\n",
            bytes(rng.randrange(256) for _ in range(512)),
            b"GET /" + fid.encode() + b" HTTP/1.0\r\n\r\n",
            # pipelining: two requests in one segment, then garbage
            b"GET /" + fid.encode() + b" HTTP/1.1\r\n\r\n"
            b"GET /" + fid.encode() + b" HTTP/1.1\r\n\r\nxx\x01yy",
        ]
        for probe in probes:
            s = socket.create_connection((host, int(port)), timeout=5)
            try:
                s.sendall(probe)
                s.settimeout(2)
                try:
                    while s.recv(4096):
                        pass
                except socket.timeout:
                    pass
            except OSError:
                pass   # reset by the server is acceptable
            finally:
                s.close()
        # after all abuse, the plane still serves correct bytes
        st, _, body = raw_get(vs.fast_url, f"/{fid}")
        assert st == 200 and body == b"survivor"


class TestCoherenceUnderChurn:
    def test_no_wrong_bytes_under_writes_deletes_compaction(self, cluster):
        """The index mirror must never serve another needle's bytes or
        stale post-compaction offsets. Payloads embed their own fid, so
        any 200 is self-validating; 404/redirect-404 is legal for
        deleted fids and windows, wrong bytes never are."""
        import random
        import threading
        master, vs = cluster
        known = []          # fids whose payload is b"fid:<fid>|" * 40
        lock = threading.Lock()
        stop = threading.Event()
        errors = []
        writes = [0]

        def payload(fid):
            return (f"fid:{fid}|".encode()) * 40

        def writer():
            while not stop.is_set():
                try:
                    a = post_json(f"http://{master.url}/dir/assign", {},
                                  timeout=5)
                    post_multipart(f"http://{a['url']}/{a['fid']}",
                                   "c.bin", payload(a["fid"]),
                                   "application/octet-stream",
                                   timeout=5)
                    with lock:
                        known.append(a["fid"])
                        writes[0] += 1
                except Exception as e:  # noqa: BLE001
                    errors.append(f"write: {e}")

        def deleter():
            while not stop.is_set():
                time.sleep(0.05)
                with lock:
                    if len(known) < 10:
                        continue
                    fid = known.pop(random.randrange(len(known) // 2))
                try:
                    http_call("DELETE", f"http://{vs.url}/{fid}",
                              timeout=5)
                except Exception as e:  # noqa: BLE001
                    errors.append(f"delete: {e}")

        def vacuumer():
            while not stop.is_set():
                time.sleep(0.7)
                try:
                    with lock:
                        vids = {int(f.split(",")[0]) for f in known}
                    for vid in vids:
                        post_json(f"http://{vs.url}/admin/vacuum/"
                                  f"compact?volume={vid}", {}, timeout=5)
                        post_json(f"http://{vs.url}/admin/vacuum/"
                                  f"commit?volume={vid}", {}, timeout=5)
                except Exception as e:  # noqa: BLE001
                    errors.append(f"vacuum: {e}")

        def reader():
            while not stop.is_set():
                with lock:
                    fid = known[random.randrange(len(known))] \
                        if known else None
                if fid is None:
                    time.sleep(0.01)  # don't GIL-starve the writers
                    continue
                try:
                    data, _ = http_get_with_headers(
                        f"http://{vs.fast_url}/{fid}", timeout=5)
                    if data != payload(fid):
                        errors.append(
                            f"WRONG BYTES for {fid}: got "
                            f"{data[:40]!r}")
                        stop.set()
                except HttpError as e:
                    if e.status != 404:  # deleted-behind-us is legal
                        errors.append(f"read {fid}: {e.status}")

        threads = ([threading.Thread(target=writer) for _ in range(2)] +
                   [threading.Thread(target=deleter),
                    threading.Thread(target=vacuumer)] +
                   [threading.Thread(target=reader) for _ in range(3)])
        for t in threads:
            t.start()
        time.sleep(6)
        stop.set()
        for t in threads:
            t.join(timeout=15)
        # a leaked thread would keep mutating errors/known below and
        # hammer the fixture's stopped cluster during teardown
        assert all(not t.is_alive() for t in threads), "thread leaked"
        wrong = [e for e in errors if e.startswith("WRONG")]
        assert not wrong, wrong
        # incidental churn errors are tolerated, but not a flood
        assert len(errors) < 20, errors[:10]
        assert writes[0] > 50, f"only {writes[0]} writes landed"
        assert vs.fast_plane.served > 100


class TestClusterIntegration:
    def test_lookup_carries_fast_url_and_reads_use_it(self, cluster):
        master, vs = cluster
        from seaweedfs_tpu.client import operation
        fid, _ = assign_and_upload(master, b"routed-fast")
        out = post_json if False else None  # noqa: F841
        from seaweedfs_tpu.server.http_util import get_json
        vid = fid.split(",")[0]
        looked = get_json(
            f"http://{master.url}/dir/lookup?volumeId={vid}")
        assert looked["locations"][0].get("fastUrl") == vs.fast_url
        before = vs.fast_plane.served
        got = operation.read_file(master.url, fid)
        assert got == b"routed-fast"
        assert vs.fast_plane.served > before

    def test_read_routes_fall_back_to_python_url(self, cluster):
        """A broken fast plane must degrade to the holder's Python url,
        and discarding the fast route must not evict the holder."""
        from seaweedfs_tpu.client.vid_map import _read_routes
        locs = [{"url": "h1:80", "publicUrl": "h1:80",
                 "fastUrl": "h1:81"},
                {"url": "h2:80", "publicUrl": "h2:80"}]
        assert _read_routes(locs) == ["h1:81", "h1:80", "h2:80"]

    def test_discard_fast_url_keeps_holder(self, cluster):
        from seaweedfs_tpu.client.vid_map import VidMap
        vm = VidMap("unused:0")
        vm._locations = {7: [{"url": "h1:80", "publicUrl": "h1:80",
                              "fastUrl": "h1:81"}]}
        vm._ready.set()
        vm.discard_url(7, "h1:81")
        assert vm.lookup(7) == ["h1:80"]          # holder survives
        assert vm.lookup_read(7) == ["h1:80"]     # fast route gone
        vm.discard_url(7, "h1:80")
        assert vm.lookup(7) is None or vm.lookup(7) == []

    def test_watch_event_carries_fast_url(self, cluster):
        master, vs = cluster
        from seaweedfs_tpu.server.http_util import get_json
        fid, _ = assign_and_upload(master, b"watched")
        deadline = time.time() + 10
        while time.time() < deadline:
            snap = get_json(f"http://{master.url}/cluster/watch?since=0"
                            f"&timeout=1")
            locs = (snap.get("locations") or {}).get(fid.split(",")[0])
            if locs:
                assert locs[0].get("fastUrl") == vs.fast_url
                return
            time.sleep(0.2)
        raise AssertionError("volume never appeared in watch snapshot")


def test_plane_gated_off_under_read_auth(tmp_path):
    """The plane speaks open HTTP: an IP whitelist or TLS must disable
    it (and stop advertising a fastUrl)."""
    from seaweedfs_tpu.server.http_util import configure_tls, reset_tls
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    master = MasterServer(port=0, pulse_seconds=1).start()
    vs = VolumeServer(port=0, directories=[str(tmp_path / "w")],
                      master_url=master.url, pulse_seconds=1,
                      max_volume_counts=[3], ec_backend="numpy",
                      whitelist=["10.0.0.1"]).start()
    try:
        assert vs.fast_plane is None
        assert vs.fast_url == ""
    finally:
        vs.stop()
        master.stop()


def test_plane_disabled_by_flag(tmp_path):
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    master = MasterServer(port=0, pulse_seconds=1).start()
    vs = VolumeServer(port=0, directories=[str(tmp_path / "x")],
                      master_url=master.url, pulse_seconds=1,
                      max_volume_counts=[3], ec_backend="numpy",
                      fast_port=-1).start()
    try:
        assert vs.fast_plane is None
    finally:
        vs.stop()
        master.stop()


class TestPlaneHealthRatio:
    """The plane is fail-open by design: an index-mirror miss 307s to
    Python, so a wholesale silent degradation (e.g. a resync bug that
    permanently unregisters a volume) would quietly turn "12x reads"
    into 1x with zero errors. The redirect/served ratio is the
    alarm — this pins it under CI so a regression fails here, not in
    a re-benchmark months later."""

    LOADGEN = "seaweedfs_tpu/server/native/loadgen"

    def _loadgen(self, vs, paths, tmp_path, seconds="4", threads="8",
                 post_size=None):
        import json as _json
        import os
        import subprocess
        lg = os.path.abspath(self.LOADGEN)
        if not os.path.exists(lg):
            build = os.path.join(os.path.dirname(lg), "build.sh")
            subprocess.run(["sh", build], check=True, timeout=120,
                          capture_output=True)
        pf = tmp_path / f"paths{len(paths)}.txt"
        pf.write_text("\n".join(paths))
        host, port = vs.fast_url.split(":")
        cmd = [lg, host, port, seconds, threads, str(pf)]
        if post_size is not None:
            cmd += ["post", str(post_size)]
        out = subprocess.run(cmd, capture_output=True, text=True,
                             timeout=60)
        return _json.loads(out.stdout)

    def test_sustained_reads_keep_redirects_under_1pct(self, cluster,
                                                       tmp_path):
        master, vs = cluster
        paths = []
        for i in range(200):
            fid, _ = assign_and_upload(master, b"soak-%d" % i)
            paths.append("/" + fid)
        base_served = vs.fast_plane.served
        base_redir = vs.fast_plane.redirected
        stats = self._loadgen(vs, paths, tmp_path)
        served = vs.fast_plane.served - base_served
        redirected = vs.fast_plane.redirected - base_redir
        assert stats["requests"] > 1000, stats
        assert stats["errors"] == 0, stats
        total = served + redirected
        ratio = redirected / max(1, total)
        assert ratio < 0.01, \
            (f"index mirror degraded: {redirected}/{total} plain reads "
             f"redirected to Python — the fast plane is silently "
             f"handing back its traffic")

    def test_degraded_mirror_trips_the_ratio(self, cluster, tmp_path):
        """Prove the alarm actually fires: silently unregister the
        volumes (the failure mode the ratio exists to catch) and the
        same measurement must exceed the bound."""
        master, vs = cluster
        paths = []
        for i in range(50):
            fid, _ = assign_and_upload(master, b"degraded-%d" % i)
            paths.append("/" + fid)
        for vid in {int(p[1:].split(",")[0]) for p in paths}:
            vs.fast_plane.unregister_volume(vid)
        base_served = vs.fast_plane.served
        base_redir = vs.fast_plane.redirected
        self._loadgen(vs, paths, tmp_path, seconds="2")
        served = vs.fast_plane.served - base_served
        redirected = vs.fast_plane.redirected - base_redir
        ratio = redirected / max(1, served + redirected)
        assert ratio > 0.99, (served, redirected)
        # recovery: re-sync restores fast serving
        for vid in {int(p[1:].split(",")[0]) for p in paths}:
            vs._fast_sync(vid)
        st, _, body = raw_get(vs.fast_url, paths[0])
        assert st == 200 and body == b"degraded-0"

    def test_mixed_write_read_soak_zero_errors(self, cluster, tmp_path):
        """Writes then reads through the plane at loadgen rates: every
        write must land natively (written counter == requests), reads
        keep the redirect ratio under the same 1% alarm."""
        master, vs = cluster
        # small fid range + ONE writer connection: a single thread
        # cycles the path file sequentially, so >=2x the range in
        # requests guarantees complete coverage for the read phase
        # (and every wrap exercises the overwrite cookie-check path)
        a = post_json(f"http://{master.url}/dir/assign?count=400", {})
        paths = [f"/{a['fid']}_{i}" if i else "/" + a["fid"]
                 for i in range(400)]
        base_written = vs.fast_plane.written
        stats = self._loadgen(vs, paths, tmp_path, seconds="3",
                              threads="1", post_size=1024)
        assert stats["errors"] == 0, stats
        assert stats["requests"] >= 2 * len(paths), \
            (stats, "write phase too slow to cover the fid range")
        written = vs.fast_plane.written - base_written
        assert written == stats["requests"], \
            (written, stats, "some writes fell back to Python")
        # read back everything that was written
        base_served = vs.fast_plane.served
        base_redir = vs.fast_plane.redirected
        rstats = self._loadgen(vs, paths, tmp_path, seconds="2")
        assert rstats["errors"] == 0, rstats
        served = vs.fast_plane.served - base_served
        redirected = vs.fast_plane.redirected - base_redir
        assert redirected / max(1, served + redirected) < 0.01


class TestNativeBenchmarkMode:
    """`weed benchmark -native`: the C++ engine driven through
    run_native_benchmark against live in-process servers — the path
    the CLI takes."""

    def test_single_target_write_then_read(self, cluster, capsys):
        from seaweedfs_tpu.command.benchmark import run_native_benchmark
        master, vs = cluster
        before_written = vs.fast_plane.written
        read_errors = run_native_benchmark(
            master.url, file_size=512, concurrency=4, seconds=1.0,
            pool=64)
        assert read_errors == 0
        # every write landed on the native plane
        assert vs.fast_plane.written > before_written
        lines = [json.loads(raw) for raw
                 in capsys.readouterr().out.splitlines()
                 if raw.startswith("{")]
        phases = {p["phase"]: p for p in lines}
        assert phases["write"]["errors"] == 0
        assert phases["write"]["requests"] > 0
        assert phases["random read"]["errors"] == 0
        assert phases["write"]["connections"] == 4

    def test_two_targets_split_connections(self, cluster, tmp_path,
                                           capsys):
        from seaweedfs_tpu.command.benchmark import run_native_benchmark
        from seaweedfs_tpu.server.volume_server import VolumeServer
        master, vs = cluster
        vs2 = VolumeServer(port=0, directories=[str(tmp_path / "v1")],
                           master_url=master.url, pulse_seconds=1,
                           max_volume_counts=[10],
                           ec_backend="numpy").start()
        try:
            # wait until BOTH servers are registered — a fixed sleep
            # would let a loaded host degrade this into a single-target
            # run that tests nothing new
            deadline = time.time() + 15
            while time.time() < deadline:
                st = get_json(f"http://{master.url}/dir/status")
                # topology.to_dict: data_centers -> {dc: {rack: {url:
                # node}}}
                nodes = sum(len(nodes_by_url)
                            for dc in st["topology"]
                            .get("data_centers", {}).values()
                            for nodes_by_url in dc.values())
                if nodes >= 2:
                    break
                time.sleep(0.2)
            assert nodes >= 2, "second volume server never registered"
            # assigns spread over many volumes so with 256 fids both
            # servers get a share (growth allocates round-robin-ish)
            run_native_benchmark(master.url, file_size=512,
                                 concurrency=5, seconds=1.0, pool=256,
                                 assign_batch=16)
            lines = [json.loads(raw) for raw
                     in capsys.readouterr().out.splitlines()
                     if raw.startswith("{")]
            phases = {p["phase"]: p for p in lines}
            # exactly the requested connections, split across targets
            assert phases["write"]["connections"] == 5
            assert phases["write"]["errors"] == 0
            assert phases["random read"]["errors"] == 0
            assert phases["write"]["targets"] == 2, \
                "assign pool never spread over both servers"
            # both planes took native writes
            assert vs.fast_plane.written > 0
            assert vs2.fast_plane.written > 0
        finally:
            vs2.stop()


# -- reconstructed-slab cache + in-plane degraded serving (ISSUE 15) --------


class TestPlaneSlabCache:
    """The plane-resident slab cache ABI driven directly: byte budget,
    exact-count stats under concurrency, scoped invalidation."""

    def _plane(self, monkeypatch, budget):
        from seaweedfs_tpu.server.native_plane import NativeReadPlane
        monkeypatch.setenv("SW_PLANE_CACHE_BYTES", str(budget))
        return NativeReadPlane("127.0.0.1", 0, "127.0.0.1:1")

    def test_budget_eviction_and_invalidate(self, monkeypatch):
        plane = self._plane(monkeypatch, 8192)
        try:
            assert plane.cache_put(1, 0, 0, b"a" * 4096)
            assert plane.cache_put(1, 0, 1, b"b" * 4096)
            s = plane.cache_stats()
            assert (s["entries"], s["bytes"]) == (2, 8192)
            # a third slab breaches the budget: the LRU one is evicted
            assert plane.cache_put(1, 0, 2, b"c" * 4096)
            s = plane.cache_stats()
            assert s["evictions"] == 1
            assert s["entries"] == 2 and s["bytes"] <= s["max_bytes"]
            # a slab larger than the whole budget is refused outright
            assert not plane.cache_put(1, 0, 3, b"x" * 9000)
            # zero-length slab ("known empty past the tail") is valid
            assert plane.cache_put(1, 0, 4, b"")
            # overwrite replaces in place — bytes never double-count
            assert plane.cache_put(1, 0, 2, b"d" * 1024)
            s = plane.cache_stats()
            assert s["puts"] == 5
            assert s["entries"] == 3 and s["bytes"] == 4096 + 0 + 1024
            # shard-scoped invalidation drops exactly that shard's slabs
            assert plane.cache_put(2, 1, 0, b"e" * 512)
            assert plane.cache_invalidate(1, 0) == 3
            s = plane.cache_stats()
            assert s["entries"] == 1 and s["invalidated"] == 3
            # volume-scoped (sid < 0) sweeps the rest
            assert plane.cache_invalidate(2) == 1
            assert plane.cache_stats()["entries"] == 0
        finally:
            plane.stop()

    def test_zero_budget_disables_cache(self, monkeypatch):
        plane = self._plane(monkeypatch, 0)
        try:
            assert not plane.cache_put(1, 0, 0, b"zz")
            s = plane.cache_stats()
            assert s["max_bytes"] == 0 and s["puts"] == 0
        finally:
            plane.stop()

    def test_hammer_exact_counts(self, monkeypatch):
        """8 writer threads + a sweeper racing invalidations: every
        counter must balance exactly afterwards — the cache keeps its
        books under one mutex precisely so a lost update is
        impossible."""
        import threading
        plane = self._plane(monkeypatch, 64 << 20)
        try:
            n_threads, per_thread, slab = 8, 300, 1024
            stop = threading.Event()
            swept = [0]
            lock = threading.Lock()

            def writer(tid):
                blob = bytes([tid]) * slab
                for i in range(per_thread):
                    assert plane.cache_put(tid + 1, tid % 14, i, blob)

            def sweeper():
                while not stop.is_set():
                    for vid in range(1, n_threads + 1):
                        n = plane.cache_invalidate(vid)
                        with lock:
                            swept[0] += n
                    time.sleep(0.001)

            threads = [threading.Thread(target=writer, args=(t,))
                       for t in range(n_threads)]
            sw = threading.Thread(target=sweeper)
            sw.start()
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            stop.set()
            sw.join(timeout=60)
            assert all(not t.is_alive() for t in threads + [sw])
            # final sweep: everything still resident comes out counted
            for vid in range(1, n_threads + 1):
                swept[0] += plane.cache_invalidate(vid)
            total = n_threads * per_thread
            s = plane.cache_stats()
            assert s["puts"] == total
            assert s["put_bytes"] == total * slab
            assert s["entries"] == 0 and s["bytes"] == 0
            # ample budget + unique keys: every slab ever put was
            # removed exactly once, by an invalidation, never eviction
            assert s["evictions"] == 0
            assert s["invalidated"] == total
            assert swept[0] == total
        finally:
            plane.stop()


class TestPlaneDegradedServing:
    """Warm degraded reads served entirely in-plane: the cold read
    redirects to Python, whose reconstruction publishes the slabs back
    into the plane; the re-read then never leaves C++ (ISSUE 15)."""

    @pytest.fixture
    def ec_cluster(self, tmp_path):
        master = MasterServer(port=0, pulse_seconds=1).start()
        servers = [
            VolumeServer(port=0, directories=[str(tmp_path / f"e{i}")],
                         master_url=master.url, pulse_seconds=1,
                         max_volume_counts=[30],
                         ec_backend="numpy").start()
            for i in range(3)]
        yield master, servers
        for vs in servers:
            vs.stop()
        master.stop()

    def _setup_degraded(self, master, servers):
        """Upload, EC-encode, kill data shard 0 cluster-wide; returns
        (serving server, vid, {fid: payload}, lost sid)."""
        import io
        import os
        import numpy as np
        from seaweedfs_tpu.client import operation as op
        from seaweedfs_tpu.ec import to_ext
        from seaweedfs_tpu.shell.command_env import (CommandEnv,
                                                     run_command)
        rng = np.random.default_rng(23)
        payloads = {}
        for i in range(12):
            data = rng.integers(0, 256, 150_000).astype(
                np.uint8).tobytes()
            fid = op.upload_data(master.url, data, filename=f"p{i}",
                                 collection="pc")
            payloads[fid] = data
        by_vid = {}
        for f in payloads:
            by_vid.setdefault(int(f.split(",")[0]), []).append(f)
        vid = max(by_vid, key=lambda v: len(by_vid[v]))
        payloads = {f: payloads[f] for f in by_vid[vid]}
        env = CommandEnv(master.url, out=io.StringIO())
        assert run_command(env, f"ec.encode -volumeId {vid}")
        lost_sid = 0  # needle data starts at volume byte 0 -> shard 0
        victim = next(vs for vs in servers
                      if (ev := vs.store.find_ec_volume(vid)) is not None
                      and lost_sid in ev.shards)
        serving = next(vs for vs in servers if vs is not victim
                       and vs.store.find_ec_volume(vid) is not None)
        assert serving.fast_plane is not None
        victim.store.unmount_ec_shards(vid, [lost_sid])
        for loc in victim.store.locations:
            for f in os.listdir(loc.directory):
                if f.endswith(to_ext(lost_sid)):
                    os.remove(os.path.join(loc.directory, f))
        victim.heartbeat_once()
        assert wait_until(lambda: str(lost_sid) not in (
            (env.ec_volumes().get(str(vid)) or {"shards": {}})["shards"]
        ), timeout=10), "master never dropped the lost shard"
        serving._ec_loc_cache.invalidate(vid)
        return serving, vid, payloads, lost_sid

    def test_warm_degraded_reads_zero_redirect(self, ec_cluster):
        master, servers = ec_cluster
        serving, vid, payloads, lost_sid = self._setup_degraded(
            master, servers)
        cs0 = serving.fast_plane.cache_stats()
        assert cs0 is not None, "cache ABI missing"

        # -- cold pass: plane misses -> 307 -> Python reconstructs and
        # publishes the slabs back into the plane
        degraded_fids = []
        for f, want in payloads.items():
            before = serving.degraded.snapshot()["reads"]
            data, _ = http_get_with_headers(
                f"http://{serving.fast_url}/{f}")
            assert data == want, f
            if serving.degraded.snapshot()["reads"] > before:
                degraded_fids.append(f)
        assert degraded_fids, "no needle landed on the lost shard"
        cs1 = serving.fast_plane.cache_stats()
        assert cs1["puts"] > 0 and cs1["entries"] > 0
        assert cs1["degraded_redirected"] > cs0["degraded_redirected"]

        # a needle straddling into a healthy-but-remote shard still
        # redirects (the plane only preads LOCAL shards): keep the
        # fully cache-covered ones
        warm = [f for f in degraded_fids
                if raw_get(serving.fast_url, f"/{f}")[0] == 200]
        assert warm, "no degraded needle is fully cache-covered"

        # -- warm passes: zero redirects, zero Python reads, exact hit
        # accounting, bit-identical bytes
        base = serving.fast_plane.cache_stats()
        py_reads = serving.degraded.snapshot()["reads"]
        rounds = 3
        for _ in range(rounds):
            for f in warm:
                st, _, body = raw_get(serving.fast_url, f"/{f}")
                assert st == 200 and body == payloads[f], f
        snap = serving.fast_plane.cache_stats()
        assert snap["degraded_served"] - base["degraded_served"] == \
            rounds * len(warm)
        assert snap["degraded_redirected"] == base["degraded_redirected"]
        assert snap["hits"] > base["hits"]
        assert serving.degraded.snapshot()["reads"] == py_reads

        # -- a poisoned slab can never serve wrong bytes: the needle
        # checksum is verified before the first response byte, so a bad
        # slab demotes to a redirect and Python answers with truth
        hot = warm[0]
        slab = serving.degraded.slab
        nslabs = (1 << 20) // slab + 1
        for i in range(nslabs):
            assert serving.fast_plane.cache_put(
                vid, lost_sid, i, b"\x5a" * slab)
        st, _, _ = raw_get(serving.fast_url, f"/{hot}")
        assert st == 307, "corrupt slab must demote, never serve"
        data, _ = http_get_with_headers(
            f"http://{serving.fast_url}/{hot}")
        assert data == payloads[hot]

        # recover: drop the poison and force one re-reconstruction
        # (Python's own slab LRU would otherwise serve the redirect
        # without re-publishing)
        assert serving.fast_plane.cache_invalidate(vid) > 0
        serving.degraded.invalidate(vid)
        data, _ = http_get_with_headers(
            f"http://{serving.fast_url}/{hot}")
        assert data == payloads[hot]
        st, _, body = raw_get(serving.fast_url, f"/{hot}")
        assert st == 200 and body == payloads[hot]

        # -- SW_PLANE_STATS off: the degraded path stays correct and
        # exact-counted, with zero latency samples (no clock reads)
        serving.fast_plane.set_stats_enabled(False)
        try:
            # telemetry for the LAST stats-on response can land after
            # the client reads its reply (recorded after the bytes are
            # on the wire — see wait_until): settle before snapshotting
            def settled():
                r0 = serving.fast_plane.stats()["requests"]
                time.sleep(0.02)
                return serving.fast_plane.stats()["requests"] == r0
            assert wait_until(settled)
            tele0 = serving.fast_plane.stats()
            c0 = serving.fast_plane.cache_stats()
            st, _, body = raw_get(serving.fast_url, f"/{hot}")
            assert st == 200 and body == payloads[hot]
            # freshness holds on the stats-off path too: poison ->
            # demote, never wrong bytes
            for i in range(nslabs):
                serving.fast_plane.cache_put(vid, lost_sid, i,
                                             b"\x33" * slab)
            st, _, _ = raw_get(serving.fast_url, f"/{hot}")
            assert st == 307
            data, _ = http_get_with_headers(
                f"http://{serving.fast_url}/{hot}")
            assert data == payloads[hot]
            tele1 = serving.fast_plane.stats()
            assert tele1["requests"] == tele0["requests"]
            assert tele1["lat_count"] == tele0["lat_count"]
            c1 = serving.fast_plane.cache_stats()
            assert c1["degraded_served"] == c0["degraded_served"] + 1
        finally:
            serving.fast_plane.set_stats_enabled(True)
        serving.fast_plane.cache_invalidate(vid)
        serving.degraded.invalidate(vid)
        http_get_with_headers(f"http://{serving.fast_url}/{hot}")

        # -- rebuild + mount: the plane must flip from cache-serving to
        # local preads; the invalidation hook makes a stale slab
        # unreachable before any read can race it
        looked = get_json(
            f"http://{master.url}/cluster/ec_lookup?volumeId={vid}")
        sources = {s: urls for s, urls in looked["shards"].items()
                   if int(s) != lost_sid}
        out = post_json(
            f"http://{serving.url}/admin/ec/rebuild?volume={vid}"
            f"&collection=pc", {"sources": sources})
        assert lost_sid in [int(s) for s in out["rebuilt"]]
        post_json(f"http://{serving.url}/admin/ec/mount?volume={vid}"
                  f"&collection=pc&shards={lost_sid}", {})
        cbase = serving.fast_plane.cache_stats()
        assert cbase["invalidated"] > 0
        st, _, body = raw_get(serving.fast_url, f"/{hot}")
        assert st == 200 and body == payloads[hot]
        snap = serving.fast_plane.cache_stats()
        assert snap["ec_local_served"] - cbase["ec_local_served"] == 1
        assert snap["degraded_served"] == cbase["degraded_served"]

        # the cache families ride the volume /metrics export
        body = raw_get(serving.url, "/metrics")[2].decode()
        assert "SeaweedFS_volumeServer_plane_degraded_total" in body
        assert "SeaweedFS_volumeServer_plane_cache_bytes" in body

    def test_warm_serving_consistent_under_cache_churn(self, ec_cluster):
        """Publishers overwriting slabs + invalidations racing readers:
        every response is either the in-plane 200 or the Python-backed
        redirect, and the bytes are bit-identical every time — the
        plane hands readers refcounted slab copies, so a torn read is
        impossible by construction."""
        import threading
        master, servers = ec_cluster
        serving, vid, payloads, lost_sid = self._setup_degraded(
            master, servers)
        hot, want = None, None
        for f in payloads:
            http_get_with_headers(f"http://{serving.fast_url}/{f}")
            if raw_get(serving.fast_url, f"/{f}")[0] == 200:
                hot, want = f, payloads[f]
                break
        assert hot is not None, "no warm-servable degraded needle"
        slab = serving.degraded.slab
        nslabs = (1 << 20) // slab + 1
        correct = {i: serving.degraded.read(vid, lost_sid, i * slab,
                                            slab)
                   for i in range(nslabs)}
        stop = threading.Event()
        errors, hits, misses = [], [0], [0]

        def publisher():
            k = 0
            while not stop.is_set():
                k += 1
                if k % 50 == 0:
                    serving.fast_plane.cache_invalidate(vid, lost_sid)
                for i, data in correct.items():
                    serving.fast_plane.cache_put(vid, lost_sid, i, data)

        def reader():
            while not stop.is_set():
                try:
                    st, _, body = raw_get(serving.fast_url, f"/{hot}")
                except Exception as e:  # noqa: BLE001 - assert below
                    errors.append(f"read: {e}")
                    continue
                if st == 200:
                    if body != want:
                        errors.append(f"WRONG BYTES: {body[:32]!r}")
                        stop.set()
                    hits[0] += 1
                elif st == 307:
                    misses[0] += 1
                else:
                    errors.append(f"status {st}")

        threads = ([threading.Thread(target=publisher)] +
                   [threading.Thread(target=reader) for _ in range(4)])
        for t in threads:
            t.start()
        time.sleep(3)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        assert all(not t.is_alive() for t in threads), "thread leaked"
        wrong = [e for e in errors if e.startswith("WRONG")]
        assert not wrong, wrong
        assert not errors, errors[:5]
        assert hits[0] > 100, (hits, misses)


class TestMirrorsLoadFromArrays:
    """The plane's mirrors are filled from record arrays
    (storage/idx_array), not an entry a Python iteration: a freeze and
    an EC mount count what they loaded, and the mirror a mount filled
    answers a live, a deleted and an absent key as the loop's did."""

    @pytest.fixture
    def sealed(self, tmp_path):
        """Three servers, one collection volume of needles in shard 0's
        first row: two deleted before the seal, the rest live."""
        import io
        import os
        from seaweedfs_tpu.client import operation as op
        from seaweedfs_tpu.ops import telemetry
        from seaweedfs_tpu.shell.command_env import (CommandEnv,
                                                     run_command)
        master = MasterServer(port=0, pulse_seconds=1).start()
        servers = [
            VolumeServer(port=0, directories=[str(tmp_path / f"m{i}")],
                         master_url=master.url, pulse_seconds=1,
                         max_volume_counts=[30],
                         ec_backend="numpy").start()
            for i in range(3)]
        try:
            payloads = {}
            for i in range(40):
                data = bytes([i]) * (3000 + i)
                payloads[op.upload_data(master.url, data, filename=f"m{i}",
                                        collection="mc")] = data
            by_vid = {}
            for f in payloads:
                by_vid.setdefault(int(f.split(",")[0]), []).append(f)
            vid = max(by_vid, key=lambda v: len(by_vid[v]))
            fids = by_vid[vid]
            owner = next(vs for vs in servers
                         if vs.store.find_volume(vid) is not None)
            for f in fids[:2]:
                http_call("DELETE", f"http://{owner.url}/{f}")
            live = {f: payloads[f] for f in fids[2:]}
            assert len(live) >= 3
            idx_records = os.path.getsize(
                owner.store.find_volume(vid).idx_path) // 16
            assert idx_records == len(fids) + 2

            # -- the freeze: the lease comes back, the needle map is
            # replayed from the .idx and the plane's mirror refilled
            before = telemetry.STATS.snapshot()
            out = post_json(f"http://{owner.url}/admin/volume/readonly"
                            f"?volume={vid}", {})
            assert out["was_readonly"] is False
            freeze = telemetry.delta(before)

            before = telemetry.STATS.snapshot()
            env = CommandEnv(master.url, out=io.StringIO())
            assert run_command(env, f"ec.encode -volumeId {vid}")
            encode = telemetry.delta(before)
            yield SimpleNamespace(
                master=master, servers=servers, vid=vid, live=live,
                deleted_before=fids[:2], idx_records=idx_records,
                freeze=freeze, encode=encode)
        finally:
            for vs in servers:
                vs.stop()
            master.stop()

    def test_a_freeze_and_the_mounts_count_what_they_loaded(self, sealed):
        live = len(sealed.live)
        # NeedleMap.load read every record of the log; register_volume
        # pushed the live set
        assert sealed.freeze["mirror_entries"] == sealed.idx_records + live
        assert sealed.freeze["mirror_us"] > 0
        assert sealed.freeze["mirror_loop_entries"] == 0
        # ec.encode found the volume frozen (no second replay); each of
        # the three holders' mounts pushed the .ecx's entries
        holders = [vs for vs in sealed.servers
                   if vs.store.find_ec_volume(sealed.vid) is not None]
        assert len(holders) == 3
        assert sealed.encode["mirror_entries"] == 3 * live
        assert sealed.encode["mirror_loop_entries"] == 0
        body, _ = http_get_with_headers(f"http://{holders[0].url}/metrics")
        for kind in ("mirror_entries", "mirror_us", "mirror_loop_entries"):
            assert f'ec_device_telemetry_total{{kind="{kind}"}}' in \
                body.decode()

    def test_mounted_mirror_answers_live_deleted_absent(self, sealed):
        vid = sealed.vid
        serving = next(vs for vs in sealed.servers
                       if (ev := vs.store.find_ec_volume(vid)) is not None
                       and 0 in ev.shards)
        fids = list(sealed.live)
        gone, kept = fids[0], fids[1:]
        # a delete after the seal: a tombstone in the .ecx, which a
        # re-sync must push too or the needle comes back
        http_call("DELETE", f"http://{serving.url}/{gone}")
        mine = sorted(serving.store.find_ec_volume(vid).shards)
        for verb in ("unmount", "mount"):
            post_json(f"http://{serving.url}/admin/ec/{verb}?volume={vid}"
                      f"&collection=mc&shards={mine[-1]}", {})
        base = serving.fast_plane.cache_stats()
        for f in kept:      # live: served in the plane from shard 0
            st, _, body = raw_get(serving.fast_url, f"/{f}")
            assert st == 200 and body == sealed.live[f]
        snap = serving.fast_plane.cache_stats()
        assert snap["ec_local_served"] - base["ec_local_served"] == len(kept)
        absent = f"{vid},{'%x' % 0xabcdef01}00000000"
        for f in (gone, sealed.deleted_before[0], absent):
            st, _, _ = raw_get(serving.fast_url, f"/{f}")
            assert st == 307, f
            with pytest.raises(HttpError) as e:
                http_call("GET", f"http://{serving.url}/{f}")
            assert e.value.status == 404


# Frozen ABI manifest: every symbol http_plane.cc exports. Adding an
# export without extending this list (and binding it in native_plane.py)
# fails both this test and tools/analyze.py's plane-abi lint.
PLANE_ABI = (
    "swhp_start", "swhp_port", "swhp_stop",
    "swhp_add_volume", "swhp_remove_volume",
    "swhp_put", "swhp_put_bulk", "swhp_delete", "swhp_lookup",
    "swhp_enable_writer", "swhp_disable_writer",
    "swhp_set_accept_posts", "swhp_append", "swhp_writer_counters",
    "swhp_served", "swhp_redirected", "swhp_written",
    "swhp_stats_len", "swhp_stats", "swhp_lat_bounds",
    "swhp_set_stats_enabled", "swhp_set_slow_us", "swhp_slow_ring",
    "swhp_ec_register", "swhp_ec_set_data_shards", "swhp_ec_set_shard",
    "swhp_ec_put_bulk",
    "swhp_ec_delete", "swhp_ec_unregister",
    "swhp_cache_configure", "swhp_cache_put", "swhp_cache_invalidate",
    "swhp_cache_stats_len", "swhp_cache_stats",
    "swhp_set_sync_mode", "swhp_sync_stats_len", "swhp_sync_stats",
)


def test_abi_manifest_complete_and_bound():
    """The loaded library exposes every manifest symbol, and the source
    exports exactly the manifest — an unbound or untracked export is a
    signature change waiting to crash at runtime."""
    import os
    import re
    from seaweedfs_tpu.server import native_plane
    lib = native_plane._load()
    missing = [s for s in PLANE_ABI if not hasattr(lib, s)]
    assert not missing, f"manifest symbols absent from .so: {missing}"
    cc = os.path.join(os.path.dirname(native_plane.__file__),
                      "native", "http_plane.cc")
    with open(cc, encoding="utf-8") as f:
        src = f.read()
    block = src[src.index('extern "C" {'):]
    exported = set(re.findall(
        r'^[A-Za-z_][A-Za-z0-9_* ]*?\b(swhp_[a-z0-9_]+)\s*\(',
        block, re.M))
    assert exported == set(PLANE_ABI), (
        exported ^ set(PLANE_ABI),
        "exports drifted from the manifest")


def test_admin_plane_cache_endpoint(cluster):
    """GET /admin/plane/cache: the slab-cache books through the Python
    server, so operators can see budget/occupancy without a scrape."""
    master, vs = cluster
    view = get_json(f"http://{vs.url}/admin/plane/cache")
    assert view["plane"] is True
    assert set(view["cache"]) >= {"puts", "hits", "misses", "entries",
                                  "bytes", "max_bytes",
                                  "degraded_served"}
    assert view["cache"]["max_bytes"] > 0
