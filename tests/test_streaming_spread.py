"""Streaming EC encode+spread (ISSUE: push shard stripes to their
holders while later slabs are still encoding): the chunked
`/admin/ec/shard_write` protocol (append-at-expected-offset, `.part`
staging, atomic finalize), stream-vs-copy shard bit-identity across
backends, the bounded per-target send window, all-or-nothing failure
cleanup, dead-target failover to a spare, the end-to-end streaming
`ec.encode` over a live 3-server cluster, plus the
satellites: `/admin/ec/to_volume` roundtrip, SmallDispatchTuner opt-in
auto-apply, and the bench device-init retry cap/backoff."""

import hashlib
import os
import shutil
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu.ec import to_ext, write_ec_files
from seaweedfs_tpu.ec.encoder import write_ec_files_spread
from seaweedfs_tpu.ec.spread import (SpreadError, SpreadStats,
                                     StripedSpreadSink, spread_window)
from seaweedfs_tpu.ops.codec import NumpyCodec
from seaweedfs_tpu.server.http_util import (HttpError, HttpServer,
                                            Router, http_call,
                                            post_chunked, post_json)

LOCAL = "src.invalid:0"   # pseudo-url of the encoding source


# -- window env knob ---------------------------------------------------------

def test_spread_window_env(monkeypatch):
    monkeypatch.delenv("SW_EC_SPREAD_WINDOW", raising=False)
    assert spread_window() == 4
    monkeypatch.setenv("SW_EC_SPREAD_WINDOW", "2")
    assert spread_window() == 2
    monkeypatch.setenv("SW_EC_SPREAD_WINDOW", "0")
    assert spread_window() == 1     # floor, never unbounded-at-zero
    monkeypatch.setenv("SW_EC_SPREAD_WINDOW", "junk")
    assert spread_window() == 4


# -- fake target: the shard_write staging protocol ---------------------------

class FakeTarget:
    """Minimal holder implementing /admin/ec/shard_write against a flat
    directory of {vid}.ecNN files, with injectable delay/failure for
    the failover and abort drills. Counts every append it answers."""

    def __init__(self, directory):
        self.dir = directory
        self.delay = 0.0
        self.fail = False
        self.fail_after = None      # appends accepted before dying
        self.appends = 0
        self.finalized = 0
        self.aborted = 0
        self.seen = []              # (connection, shard, offset) an append
        self.sizes = []             # bytes of every append staged
        self._lock = threading.Lock()
        router = Router()
        router.add("POST", "/admin/ec/shard_write", self._shard_write)
        self.server = HttpServer(0, router).start()
        self.url = f"127.0.0.1:{self.server.port}"

    def _path(self, vid, sid):
        return os.path.join(self.dir, f"{vid}{to_ext(sid)}")

    def _shard_write(self, req):
        vid = int(req.query["volume"])
        action = req.query.get("action", "append")
        if action == "abort":
            req.drain()
            with self._lock:
                self.aborted += 1
            removed = []
            for f in os.listdir(self.dir):
                if f.endswith(".part"):
                    os.remove(os.path.join(self.dir, f))
                    removed.append(f)
            return {"volume": vid, "aborted": removed}
        sid = int(req.query["shard"])
        part = self._path(vid, sid) + ".part"
        if action == "finalize":
            req.drain()
            size = int(req.query["size"])
            staged = os.path.getsize(part) if os.path.exists(part) else -1
            if staged != size:
                raise HttpError(409, f"shard {sid} staged={staged} "
                                     f"expected={size}")
            os.replace(part, self._path(vid, sid))
            with self._lock:
                self.finalized += 1
            return {"volume": vid, "shard": sid, "finalized": True}
        if self.delay:
            time.sleep(self.delay)
        with self._lock:
            self.appends += 1
            n_seen = self.appends
            self.seen.append((req.handler.client_address,
                              int(req.query["shard"]),
                              int(req.query.get("offset", "0"))))
        self._arrived(n_seen)
        if self.fail or (self.fail_after is not None
                         and n_seen > self.fail_after):
            _ = req.body
            raise HttpError(503, "injected target failure")
        off = int(req.query.get("offset", "0"))
        staged = os.path.getsize(part) if os.path.exists(part) else 0
        if off != staged and off != 0:
            _ = req.body
            raise HttpError(409, f"shard {sid} offset mismatch: "
                                 f"staged={staged} offset={off}")
        data = req.body
        with open(part, "wb" if off == 0 else "ab") as f:
            f.write(data)
            staged = f.tell()
        with self._lock:
            self.sizes.append(len(data))
        return {"volume": vid, "shard": sid, "staged": staged}

    def _arrived(self, n_seen):
        """Hook: append number ``n_seen`` is at the door."""

    def connections(self):
        """{connection: the shards whose appends it carried}."""
        out = {}
        for conn, sid, _ in self.seen:
            out.setdefault(conn, set()).add(sid)
        return out

    def stop(self):
        self.server.stop()


ENC = dict(large_block=64 << 10, small_block=16 << 10, slab=16 << 10)


def _seed_oracle(dirpath, codec, nbytes, seed=7, enc=None, layout="flat"):
    """Write 1.dat in dirpath, encode it in a sibling oracle dir with
    the same codec/geometry, return (base, {sid: sha256})."""
    rng = np.random.default_rng(seed)
    payload = rng.integers(0, 256, nbytes, dtype=np.uint8).tobytes()
    base = os.path.join(str(dirpath), "1")
    with open(base + ".dat", "wb") as f:
        f.write(payload)
    odir = str(dirpath) + ".oracle"
    os.makedirs(odir, exist_ok=True)
    obase = os.path.join(odir, "1")
    shutil.copy(base + ".dat", obase + ".dat")
    write_ec_files(obase, codec=codec, layout=layout, **(enc or ENC))
    digests = {}
    for i in range(codec.total):
        with open(obase + to_ext(i), "rb") as f:
            digests[i] = hashlib.sha256(f.read()).hexdigest()
    return base, digests


def _digest(path):
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


# -- stream == copy, mixed local+remote, all backends ------------------------

@pytest.mark.parametrize("backend", ["numpy", "tpu", "mesh"])
def test_stream_vs_copy_bit_identical(tmp_path, backend):
    if backend == "tpu":
        from seaweedfs_tpu.ops.rs_tpu import TpuCodec as Codec
    elif backend == "mesh":
        from seaweedfs_tpu.parallel.mesh_codec import MeshCodec as Codec
    else:
        Codec = NumpyCodec
    k, m = 6, 3
    codec = Codec(k, m)
    src = tmp_path / "src"
    src.mkdir()
    base, oracle = _seed_oracle(src, codec, 6 * (64 << 10) + 70_001)
    t1dir, t2dir = tmp_path / "t1", tmp_path / "t2"
    t1dir.mkdir()
    t2dir.mkdir()
    a, b = FakeTarget(str(t1dir)), FakeTarget(str(t2dir))
    try:
        remote = {1: a.url, 4: a.url, 7: a.url, 2: b.url, 8: b.url}
        assignment = {sid: remote.get(sid, LOCAL) for sid in range(k + m)}
        stats = {}
        sink = StripedSpreadSink(1, base, assignment, k + m,
                                 local_url=LOCAL, window=2,
                                 slab=ENC["slab"])
        write_ec_files_spread(base, sink, codec=codec, stats=stats,
                              **ENC)
        # every shard bit-identical to the copy-mode oracle, each at its
        # holder, and remote-bound shards never touched the source disk
        for sid in range(k + m):
            holder = {a.url: str(t1dir), b.url: str(t2dir)}.get(
                remote.get(sid), str(src))
            assert _digest(os.path.join(holder, f"1{to_ext(sid)}")) \
                == oracle[sid], f"shard {sid} diverged"
        for sid in remote:
            assert not os.path.exists(base + to_ext(sid))
        for d in (str(src), str(t1dir), str(t2dir)):
            assert not [f for f in os.listdir(d) if f.endswith(".part")]
        assert stats["spread_remote_shards"] == len(remote)
        assert stats["spread_stripes"] >= 4
        assert stats["spread_bytes"] == stats["shard_size"] * (k + m)
        assert 0.0 <= stats["overlap_frac"] <= 1.0
        assert sink.assignment()[1] == a.url
        assert sink.assignment()[0] == ""
    finally:
        a.stop()
        b.stop()


# -- bounded send window (satellite: memory stays O(window*slab)) ------------

# The window counts bytes: ``window`` stripes of the sink's ``slab`` a
# shard. A stream that hands over slab-wide rows (one chip) queues as
# many rows as the window says; one that hands over quarter-slab rows
# (the mesh's piece-wise drain) queues four times as many, sends runs
# as long, and holds no more bytes.

@pytest.mark.parametrize("rows_a_slab", [1, 4])
def test_bounded_send_window(tmp_path, rows_a_slab):
    k, m, window = 6, 3, 1
    codec = NumpyCodec(k, m)
    src = tmp_path / "src"
    src.mkdir()
    n_stripes = 10 * rows_a_slab
    base, oracle = _seed_oracle(src, codec, k * (16 << 10) * n_stripes)
    tdir = tmp_path / "t"
    tdir.mkdir()
    tgt = FakeTarget(str(tdir))
    tgt.delay = 0.02        # slow holder: the encode must wait, not buffer
    try:
        assignment = {sid: tgt.url for sid in range(k + m)}
        stats = {}
        slab = rows_a_slab * ENC["slab"]    # the sink's; a row is ENC's
        sink = StripedSpreadSink(1, base, assignment, k + m,
                                 local_url=LOCAL, window=window,
                                 slab=slab)
        write_ec_files_spread(base, sink, codec=codec, stats=stats,
                              **ENC)
        for sid in range(k + m):
            assert _digest(os.path.join(str(tdir), f"1{to_ext(sid)}")) \
                == oracle[sid]
        # queued + in-hand batch + the stripe being routed — never the
        # whole volume (which is n_stripes windows deep)
        assert stats["peak_spread_buffer"] <= \
            (2 * window + 1) * (k + m) * slab
        assert stats["peak_spread_buffer"] < stats["spread_bytes"] // 2
        assert stats["spread_stripes"] == n_stripes
        # a run is a window of bytes a shard at most: one row where a
        # row is slab wide (a run a row, as a window of 1 always sent),
        # up to four where it is a quarter — and so fewer sends
        rows = (k + m) * n_stripes
        assert max(tgt.sizes) == window * slab
        assert stats["spread_sends"] == len(tgt.sizes)
        if rows_a_slab == 1:
            assert stats["spread_sends"] == rows
        else:
            assert rows // rows_a_slab <= stats["spread_sends"] <= rows // 2
        # a stalled spread shows up as encode-side blocked time, not as
        # phantom encode work: busy encode <= wall
        assert sink.blocked_s > 0
    finally:
        tgt.stop()


# -- all-or-nothing on mid-stream death --------------------------------------

def test_midstream_failure_leaves_no_partials(tmp_path):
    k, m = 6, 3
    codec = NumpyCodec(k, m)
    src = tmp_path / "src"
    src.mkdir()
    base, _ = _seed_oracle(src, codec, k * (16 << 10) * 8)
    tdir = tmp_path / "t"
    tdir.mkdir()
    tgt = FakeTarget(str(tdir))
    tgt.fail_after = 2      # dies after acking two appends: unreplayable
    try:
        assignment = {sid: tgt.url if sid in (3, 5) else LOCAL
                      for sid in range(k + m)}
        sink = StripedSpreadSink(1, base, assignment, k + m,
                                 local_url=LOCAL, window=1,
                                 slab=ENC["slab"])
        with pytest.raises(SpreadError):
            write_ec_files_spread(base, sink, codec=codec, **ENC)
        # no finalized shards and no .part stages anywhere — the failed
        # spread is invisible on every disk
        for d in (str(src), str(tdir)):
            leftovers = [f for f in os.listdir(d)
                         if ".ec" in f or f.endswith(".part")]
            assert leftovers == [], f"{d}: {leftovers}"
        assert tgt.aborted >= 1
    finally:
        tgt.stop()


# -- failover: dead-at-first-contact target -> spare -------------------------

def test_failover_reassigns_dead_target(tmp_path):
    k, m = 6, 3
    codec = NumpyCodec(k, m)
    src = tmp_path / "src"
    src.mkdir()
    base, oracle = _seed_oracle(src, codec, k * (16 << 10) * 6)
    ddir, sdir = tmp_path / "dead", tmp_path / "spare"
    ddir.mkdir()
    sdir.mkdir()
    dead, spare = FakeTarget(str(ddir)), FakeTarget(str(sdir))
    dead.fail = True
    try:
        assignment = {sid: dead.url if sid in (7, 8) else LOCAL
                      for sid in range(k + m)}
        stats = {}
        sink = StripedSpreadSink(1, base, assignment, k + m,
                                 local_url=LOCAL,
                                 spares=[spare.url], window=2,
                                 slab=ENC["slab"])
        write_ec_files_spread(base, sink, codec=codec, stats=stats,
                              **ENC)
        # the dead target's shards landed complete on the spare, and the
        # final placement reports the move
        for sid in (7, 8):
            assert _digest(os.path.join(str(sdir), f"1{to_ext(sid)}")) \
                == oracle[sid]
            assert sink.assignment()[sid] == spare.url
        assert stats["spread_failovers"] == 1
        assert not os.listdir(str(ddir))
    finally:
        dead.stop()
        spare.stop()


# -- the real endpoint: append / 409 / finalize / abort ----------------------

def test_shard_write_endpoint(tmp_path):
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    master = MasterServer(port=0, pulse_seconds=1).start()
    vs = VolumeServer(port=0, directories=[str(tmp_path / "v")],
                      master_url=master.url, pulse_seconds=1,
                      max_volume_counts=[5], ec_backend="numpy").start()
    try:
        import json
        url = f"http://{vs.url}/admin/ec/shard_write?volume=77&shard=0"
        p1, p2 = b"x" * 70_000, b"y" * 30_000
        out = json.loads(post_chunked(f"{url}&offset=0",
                                      [p1[:40_000], p1[40_000:]]))
        assert out["staged"] == len(p1)
        # offset mismatch: staged size comes back in the 409 message
        with pytest.raises(HttpError) as ei:
            post_chunked(f"{url}&offset=10", [b"z"])
        assert ei.value.status == 409
        assert "staged=70000" in str(ei.value)
        post_chunked(f"{url}&offset={len(p1)}", [p2])
        # finalize with the wrong size refuses; right size renames
        with pytest.raises(HttpError) as ei:
            http_call("POST", f"{url}&action=finalize&size=1")
        assert ei.value.status == 409
        http_call("POST",
                  f"{url}&action=finalize&size={len(p1) + len(p2)}")
        loc = vs.store.locations[0].directory
        final = os.path.join(loc, f"77{to_ext(0)}")
        assert os.path.getsize(final) == len(p1) + len(p2)
        with open(final, "rb") as f:
            assert f.read() == p1 + p2
        # offset 0 truncates: a replayed first range starts clean
        post_chunked(f"{url.replace('shard=0', 'shard=1')}&offset=0",
                     [b"a" * 100])
        post_chunked(f"{url.replace('shard=0', 'shard=1')}&offset=0",
                     [b"b" * 60])
        part1 = os.path.join(loc, f"77{to_ext(1)}.part")
        assert os.path.getsize(part1) == 60
        # abort drops every stage, leaves finalized shards alone
        http_call("POST", f"http://{vs.url}/admin/ec/shard_write"
                          f"?volume=77&action=abort")
        assert not os.path.exists(part1)
        assert os.path.exists(final)
    finally:
        vs.stop()
        master.stop()


def test_observe_spread_metrics():
    from seaweedfs_tpu.stats import metrics
    before = metrics.VOLUME_EC_SPREAD_COUNTER.value("bytes")
    metrics.observe_spread({
        "spread_bytes": 1 << 20, "spread_sends": 9, "spread_stripes": 3,
        "spread_retries": 1, "spread_failovers": 1,
        "spread_busy_s": 0.5, "spread_mbps": 88.5,
        "spread_send_s": 1.75, "spread_lanes": 6,
        "overlap_frac": 0.61})
    assert metrics.VOLUME_EC_SPREAD_COUNTER.value("bytes") - before \
        == 1 << 20
    assert metrics.VOLUME_EC_ENCODE_OVERLAP_FRAC_GAUGE.value() == 0.61
    assert metrics.VOLUME_EC_SPREAD_MBPS_GAUGE.value() == 88.5
    assert metrics.VOLUME_EC_SPREAD_LANES_GAUGE.value() == 6
    render = metrics.VOLUME_SERVER_GATHER.render()
    assert 'ec_spread_total{kind="bytes"}' in render
    assert "ec_encode_overlap_frac" in render
    assert "ec_spread_send_seconds_total" in render


# -- end-to-end: streaming ec.encode over a live cluster ---------------------

@pytest.fixture
def cluster3(tmp_path):
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    master = MasterServer(port=0, pulse_seconds=1).start()
    servers = [
        VolumeServer(port=0, directories=[str(tmp_path / f"v{i}")],
                     master_url=master.url, pulse_seconds=1,
                     max_volume_counts=[30], ec_backend="numpy").start()
        for i in range(3)]
    yield master, servers
    for vs in servers:
        vs.stop()
    master.stop()


def _cluster_shard_files(servers):
    """{sid: [paths]} of every .ecNN file across the cluster."""
    out = {}
    for vs in servers:
        for loc in vs.store.locations:
            for fname in os.listdir(loc.directory):
                for sid in range(14):
                    if fname.endswith(to_ext(sid)):
                        out.setdefault(sid, []).append(
                            os.path.join(loc.directory, fname))
    return out


def test_cluster_streaming_encode_end_to_end(cluster3, tmp_path):
    from seaweedfs_tpu.client import operation as op
    from seaweedfs_tpu.shell.command_env import CommandEnv
    from seaweedfs_tpu.shell.command_ec import do_ec_encode
    import io
    master, servers = cluster3
    rng = np.random.default_rng(11)
    fid = None
    for i in range(12):
        data = rng.integers(0, 256, 150_000).astype(np.uint8).tobytes()
        fid = op.upload_data(master.url, data, filename=f"f{i}",
                             collection="sp")
    vid = int(fid.split(",")[0])
    env = CommandEnv(master.url, out=io.StringIO())

    # numpy oracle BEFORE the encode (the original volume is deleted
    # after): encode a copy of the source .dat with the same geometry
    src_vs = next(vs for vs in servers
                  if vs.store.find_volume(vid) is not None)
    src_base = src_vs.store.find_volume(vid).file_name()
    odir = tmp_path / "oracle"
    odir.mkdir()
    obase = str(odir / "o")
    shutil.copy(src_base + ".dat", obase + ".dat")
    write_ec_files(obase, codec=NumpyCodec(10, 4), pipelined=False)
    oracle = {sid: _digest(obase + to_ext(sid)) for sid in range(14)}

    timings = {}
    do_ec_encode(env, vid, timings=timings)
    shell_log = env.out.getvalue()
    assert "streamed 14 shards" in shell_log
    assert "overlap_frac" in timings
    assert timings["spread_stripes"] >= 1
    assert timings["spread_bytes"] > 0
    assert "trace_id" in timings

    # every shard exists EXACTLY once cluster-wide, bit-identical to the
    # oracle, spread across all 3 nodes, with no .part stages left
    files = _cluster_shard_files(servers)
    assert sorted(files) == list(range(14))
    for sid, paths in files.items():
        assert len(paths) == 1, f"shard {sid} on several nodes: {paths}"
        assert _digest(paths[0]) == oracle[sid], f"shard {sid} diverged"
    holders = {os.path.dirname(p) for paths in files.values()
               for p in paths}
    assert len(holders) == 3
    for vs in servers:
        for loc in vs.store.locations:
            assert not [f for f in os.listdir(loc.directory)
                        if f.endswith(".part")]
        # the original volume is gone everywhere
        assert vs.store.find_volume(vid) is None

    # overlap telemetry is exported on /metrics
    body = http_call("GET", f"http://{src_vs.url}/metrics").decode()
    assert "ec_encode_overlap_frac" in body
    assert 'ec_spread_total{kind="bytes"}' in body

    # the cluster serves the data through EC reads
    assert http_call("GET", f"http://{servers[0].url}/{fid}") == data

    # decode satellite: pull all data shards onto one node and turn the
    # streamed shards back into a normal volume
    target = servers[0]
    info = env.ec_volumes()[str(vid)]
    shard_urls = {int(s): urls for s, urls in info["shards"].items()}
    held = set(target.store.find_ec_volume(vid).shard_ids()
               if target.store.find_ec_volume(vid) else [])
    for sid in range(10):
        if sid not in held:
            post_json(f"http://{target.url}/admin/ec/copy?volume={vid}"
                      f"&collection=sp&source={shard_urls[sid][0]}"
                      f"&shards={sid}")
    post_json(f"http://{target.url}/admin/ec/mount?volume={vid}"
              f"&collection=sp&shards="
              f"{','.join(str(s) for s in range(10) if s not in held)}")
    out = post_json(f"http://{target.url}/admin/ec/to_volume?volume={vid}"
                    f"&collection=sp")
    assert out["volume"] == vid
    assert target.store.find_volume(vid) is not None
    assert http_call("GET", f"http://{target.url}/{fid}") == data


# -- satellite: SmallDispatchTuner opt-in auto-apply -------------------------

def test_small_dispatch_auto_apply(monkeypatch):
    from seaweedfs_tpu.ops import codec as codec_mod
    from seaweedfs_tpu.stats import metrics

    def feed_spans():
        # fresh tuner: the global one may be saturated by other tests
        monkeypatch.setattr(metrics, "SMALL_DISPATCH_TUNER",
                            metrics.SmallDispatchTuner())
        for b in (1e4, 2e4, 3e4, 4e4):      # host: flat 1e8 B/s
            metrics.observe_span({"name": "reconstruct",
                                  "duration_s": b / 1e8,
                                  "tags": {"path": "host", "bytes": b}})
        for b in (1e6, 2e6, 4e6, 8e6):      # device: 1ms fixed + 1e-10/B
            metrics.observe_span({"name": "reconstruct",
                                  "duration_s": 1e-3 + 1e-10 * b,
                                  "tags": {"path": "device",
                                           "bytes": b}})

    codec_mod.set_small_dispatch_override(None)
    try:
        # without the opt-in the suggestion is published but NOT applied
        monkeypatch.delenv("SW_EC_SMALL_DISPATCH_AUTO", raising=False)
        feed_spans()
        assert metrics.SMALL_DISPATCH_SUGGESTED_GAUGE.value() > 0
        assert codec_mod.small_dispatch_override() is None

        monkeypatch.setenv("SW_EC_SMALL_DISPATCH_AUTO", "1")
        feed_spans()
        applied = codec_mod.small_dispatch_override()
        assert applied is not None
        # the fitted crossover (~1e-3 / (1e-8 - 1e-10) ~ 101kB) landed
        # inside the clamp and now IS the live threshold
        assert (64 << 10) <= applied <= (8 << 20)
        assert codec_mod.small_dispatch_default() == applied
    finally:
        codec_mod.set_small_dispatch_override(None)


# -- PR 30: a shard byte crosses each side of the socket once -----------------
# The sender queues views of a stripe's rows (no copies) and sends a run
# as those views on one kept connection; the holder streams the body from
# the socket through one reused buffer into the .part file.

class CuttingTarget(FakeTarget):
    """A holder whose first append dies in the middle of its body: it
    reads one piece of the run, then fails — nothing of the run is
    staged and the connection is closed under the sender."""

    def __init__(self, directory):
        super().__init__(directory)
        self.cuts = 1

    def _shard_write(self, req):
        if req.query.get("action", "append") == "append" and self.cuts:
            self.cuts -= 1
            next(req.body_pieces(memoryview(bytearray(4096))))
            raise HttpError(500, "injected cut in the middle of a run")
        return super()._shard_write(req)


def _encode_through(tmp_path, codec, targets, remote, window=2,
                    spares=None, nbytes=6 * (64 << 10) + 70_001,
                    slab=ENC["slab"]):
    src = tmp_path / "src"
    src.mkdir(parents=True)
    base, oracle = _seed_oracle(src, codec, nbytes)
    total = codec.total
    assignment = {sid: remote.get(sid, LOCAL) for sid in range(total)}
    stats = {}
    sink = StripedSpreadSink(1, base, assignment, total, local_url=LOCAL,
                             window=window, spares=spares, slab=slab)
    write_ec_files_spread(base, sink, codec=codec, stats=stats, **ENC)
    for sid, url in sink.assignment().items():
        holder = targets[url].dir if url else str(src)
        assert _digest(os.path.join(holder, f"1{to_ext(sid)}")) \
            == oracle[sid], f"shard {sid} diverged"
    return stats, sink


def test_one_connection_a_worker_and_holder(tmp_path):
    """`spread_connects`: a clean encode opens one connection a lane to
    each remote target (two lanes a target of two shards or more)
    however many runs it sends; a retry after a run cut in the middle
    opens one more, and so does a failover to a spare."""
    codec = NumpyCodec(6, 3)
    dirs = [tmp_path / n for n in ("a", "b", "cut", "dead", "spare")]
    for d in dirs:
        d.mkdir()
    a, b = FakeTarget(str(dirs[0])), FakeTarget(str(dirs[1]))
    cut = CuttingTarget(str(dirs[2]))
    dead, spare = FakeTarget(str(dirs[3])), FakeTarget(str(dirs[4]))
    dead.fail = True
    targets = {t.url: t for t in (a, b, cut, dead, spare)}
    try:
        clean, _ = _encode_through(
            tmp_path / "clean", codec, targets,
            {1: a.url, 4: a.url, 7: a.url, 2: b.url, 8: b.url})
        assert clean["spread_connects"] == 4
        assert len(a.connections()) == len(b.connections()) == 2
        assert clean["spread_sends"] > 2 * clean["spread_connects"]
        assert clean["spread_retries"] == 0
        assert clean["holder_fetches"][a.url] == a.appends >= 2

        # a run cut mid-body: nothing of it staged, the retry (a new
        # connection: the holder closed the one it cut) lands it whole
        retried, _ = _encode_through(
            tmp_path / "retried", codec, targets,
            {1: a.url, 4: a.url, 2: cut.url, 8: cut.url})
        assert retried["spread_retries"] == 1
        assert retried["spread_connects"] == 4 + 1

        # failover: the dead holder answered 503 on its one connection
        # (both attempts of the one lane that had the first run), the
        # spare gets that lane's next and the other lane's only one
        moved, sink = _encode_through(
            tmp_path / "moved", codec, targets,
            {1: a.url, 7: dead.url, 8: dead.url}, spares=[spare.url])
        assert moved["spread_failovers"] == 1
        assert sink.assignment()[7] == sink.assignment()[8] == spare.url
        assert moved["spread_connects"] == 3 + 1
        assert len(dead.connections()) == 1
    finally:
        for t in targets.values():
            t.stop()


@pytest.fixture
def one_server(tmp_path):
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    master = MasterServer(port=0, pulse_seconds=1).start()
    vs = VolumeServer(port=0, directories=[str(tmp_path / "v")],
                      master_url=master.url, pulse_seconds=1,
                      max_volume_counts=[5], ec_backend="numpy").start()
    yield vs
    vs.stop()
    master.stop()


def _raw_request(vs, head: bytes, body: bytes):
    """Send exactly these bytes, half-close, and read the reply: a
    sender that died in the middle of a body."""
    import socket
    host, port = vs.url.split(":")
    with socket.create_connection((host, int(port)), timeout=10) as s:
        s.sendall(head + body)
        s.shutdown(socket.SHUT_WR)
        reply = b""
        while chunk := s.recv(65536):
            reply += chunk
    return reply


@pytest.mark.parametrize("framing", ["content-length", "chunked"])
def test_a_run_is_appended_whole_or_not_at_all(one_server, framing):
    """A body cut in the middle leaves the stage at the request's
    offset, the sender's retry at that offset lands the run, and the
    409 `staged=` reply tells delivered-but-unacked from diverged —
    under both framings of the endpoint."""
    from seaweedfs_tpu.ec.transport import RemoteShardWriter
    from seaweedfs_tpu.server.http_util import KeptConnection
    vs = one_server
    target = "/admin/ec/shard_write?volume=78&collection=&shard=3"
    link = KeptConnection(vs.url)

    def send(off, parts):
        if framing == "chunked":
            return post_chunked(f"http://{vs.url}{target}&offset={off}",
                                parts)
        return link.post_parts(f"{target}&offset={off}", parts)

    p1, p2 = b"x" * 70_000, b"y" * 100_000
    send(0, [memoryview(p1)[:30_000], memoryview(p1)[30_000:]])
    part = os.path.join(vs.store.locations[0].directory,
                        f"78{to_ext(3)}.part")
    assert os.path.getsize(part) == len(p1)

    # the run's head arrives, the rest never does
    if framing == "chunked":
        head = (f"POST {target}&offset={len(p1)} HTTP/1.1\r\n"
                f"Host: x\r\nTransfer-Encoding: chunked\r\n\r\n").encode()
        body = b"%x\r\n" % 40_000 + p2[:40_000] + b"\r\n" \
            + b"%x\r\n" % 60_000 + p2[40_000:55_000]
    else:
        head = (f"POST {target}&offset={len(p1)} HTTP/1.1\r\nHost: x\r\n"
                f"Content-Length: {len(p2)}\r\n\r\n").encode()
        body = p2[:55_000]
    reply = _raw_request(vs, head, body)
    assert reply.startswith(b"HTTP/1.1 400"), reply[:200]
    assert b"Connection: close" in reply
    assert os.path.getsize(part) == len(p1)     # rolled back

    # the retry at the same offset lands the run, through the writer
    # the spread uses (its 409 handling included)
    w = RemoteShardWriter(78, 3)
    if framing == "chunked":
        send(len(p1), [p2])
    else:
        assert w.send(vs.url, len(p1), [memoryview(p2)], link) == len(p2)
    assert os.path.getsize(part) == len(p1) + len(p2)
    with open(part, "rb") as f:
        assert f.read() == p1 + p2

    # delivered but the ack was lost: the same run again is a 409 whose
    # staged size covers it — the writer takes that as delivered
    with pytest.raises(HttpError) as ei:
        send(len(p1), [p2])
    assert ei.value.status == 409
    assert f"staged={len(p1) + len(p2)}" in str(ei.value)
    assert w.send(vs.url, len(p1), [memoryview(p2)], link) == len(p2)
    # diverged: a run the stage does not end with stays an error
    with pytest.raises(HttpError) as ei:
        w.send(vs.url, 10, [memoryview(b"z" * 7)], link)
    assert ei.value.status == 409 and "staged=170000" in str(ei.value)
    assert os.path.getsize(part) == len(p1) + len(p2)
    # the kept connection outlived every 409 (their bodies were consumed)
    assert framing == "chunked" or link.connects == 1
    link.close()


def test_holder_never_holds_more_than_its_buffer(one_server, monkeypatch):
    """A run larger than the holder's buffer goes through it piece by
    piece (`pieces` on the server span), into one buffer that the next
    request finds again."""
    from seaweedfs_tpu.server import volume_server
    from seaweedfs_tpu.server.http_util import KeptConnection
    from seaweedfs_tpu.util import tracing
    vs = one_server
    monkeypatch.setattr(volume_server, "SHARD_WRITE_PIECE", 64 << 10)
    rng = np.random.default_rng(30)
    run = rng.integers(0, 256, (3 << 16) + 5, dtype=np.uint8)
    rows = [memoryview(run[:100_000]), memoryview(run[100_000:])]
    spans = []
    tracing.add_finish_hook(spans.append)
    link = KeptConnection(vs.url)
    try:
        target = "/admin/ec/shard_write?volume=79&collection=&shard=0"
        link.post_parts(f"{target}&offset=0", rows)
        link.post_parts(f"{target}&offset={run.size}", rows)
        post_chunked(f"http://{vs.url}{target}&offset={2 * run.size}",
                     [bytes(r) for r in rows])
    finally:
        tracing.remove_finish_hook(spans.append)
        link.close()
    appends = [s for s in spans
               if s["name"] == "POST /admin/ec/shard_write"]
    assert [s["tags"]["bytes"] for s in appends] == [run.size] * 3
    # Content-Length: the buffer full but for the tail; chunked: a chunk
    # never shares a piece with the next
    assert [s["tags"]["pieces"] for s in appends] == [4, 4, 2 + 2]
    assert len(vs._shard_write_bufs) == 1
    assert len(vs._shard_write_bufs[0]) == 64 << 10
    part = os.path.join(vs.store.locations[0].directory,
                        f"79{to_ext(0)}.part")
    with open(part, "rb") as f:
        assert f.read() == run.tobytes() * 3
    assert link.connects == 1


@pytest.mark.parametrize("case", ["flat", "mesh-pieces", "piggyback",
                                  "piggyback-recut"])
def test_slab_goes_back_only_after_its_last_row_is_acknowledged(
        tmp_path, monkeypatch, case):
    """The spread sends views of a slab's rows, so the slab is the
    sink's until the last of them is on its holder's disk: with a slow
    holder and a reader that writes into every recycled slab, no slab
    is handed back to the pool before the holder has staged its stripe,
    and the shards come out bit-identical to the copy flow's."""
    if case == "flat":
        from seaweedfs_tpu.ops.rs_tpu import TpuCodec
        # pipelined: the reader runs ahead of the spread
        _slab_case(tmp_path, monkeypatch, TpuCodec(6, 3))
    elif case == "mesh-pieces":
        from test_mesh_codec import _private_programs
        from seaweedfs_tpu.parallel.mesh import make_codec_mesh
        from seaweedfs_tpu.parallel.mesh_codec import MeshCodec
        with _private_programs():
            codec = MeshCodec(6, 3, mesh=make_codec_mesh(width_devices=4),
                              mesh_shard_min_bytes=0,
                              small_dispatch_bytes=0)
            pieces, sizes = _slab_case(tmp_path, monkeypatch, codec)
        # four pieces a dispatch, each a stripe of its own
        assert pieces.count((16 << 10) // 4) >= 4 * 24
        # ... and a run is as long as a whole stripe's would be: the
        # window of 1 is a slab of bytes a shard, four pieces
        assert max(sizes) == 16 << 10 and sizes.count(16 << 10) >= 8
    else:
        # slab 2048: whole windows, every stripe is the reader's slab;
        # 3000: the window re-cut makes the stripes of copies
        _slab_case(tmp_path, monkeypatch, NumpyCodec(10, 4),
                   layout="piggyback",
                   enc=dict(large_block=4096, small_block=512,
                            slab=2048 if case == "piggyback" else 3000))


def _slab_case(tmp_path, monkeypatch, codec, layout="flat", enc=ENC):
    from seaweedfs_tpu.ec import encoder
    k, m = codec.k, codec.m
    src, tdir = tmp_path / "src", tmp_path / "t"
    src.mkdir()
    tdir.mkdir()
    nbytes = 77_003 if layout == "piggyback" \
        else k * (16 << 10) * 24 + 333
    base, oracle = _seed_oracle(src, codec, nbytes, enc=enc, layout=layout)
    tgt = FakeTarget(str(tdir))
    tgt.delay = 0.004
    remote = [sid for sid in range(k + m) if sid % 3]
    ends, given, reused, early, pieces = {}, [], [], [], []

    def staged(sid):
        p = os.path.join(str(tdir), f"1{to_ext(sid)}")
        p = p + ".part" if os.path.exists(p + ".part") else p
        return os.path.getsize(p) if os.path.exists(p) else 0

    class NotingSink(StripedSpreadSink):
        def write_stripe(self, data, parity, done=None):
            ends[id(data.base)] = self.offset + data.shape[1]
            pieces.append(data.shape[1])
            super().write_stripe(data, parity, done)

    real_give, real_take = encoder._give_slab, encoder._take_slab

    # piggyback's window re-cut makes its stripes of copies: the slabs
    # go back as they are copied out of and none is ever a stripe
    recut = layout == "piggyback" and enc["slab"] % enc["small_block"]

    def checked_give(data):
        if not recut:
            end = ends[id(data.base)]
            behind = [sid for sid in remote if staged(sid) < end]
            if behind:
                early.append((end, behind))
        given.append(id(data.base))
        real_give(data)

    def noting_take(kk, width, **kw):
        out = real_take(kk, width, **kw)
        if id(out.base) in given:
            reused.append(id(out.base))
        return out

    monkeypatch.setattr(encoder, "_give_slab", checked_give)
    monkeypatch.setattr(encoder, "_take_slab", noting_take)
    encoder._SLAB_POOL.clear()
    try:
        assignment = {sid: tgt.url if sid in remote else LOCAL
                      for sid in range(k + m)}
        stats = {}
        sink = NotingSink(1, base, assignment, k + m, local_url=LOCAL,
                          window=1, slab=enc["slab"])
        write_ec_files_spread(base, sink, codec=codec, stats=stats,
                              layout=layout, **enc)
        for sid in range(k + m):
            holder = str(tdir) if sid in remote else str(src)
            assert _digest(os.path.join(holder, f"1{to_ext(sid)}")) \
                == oracle[sid], f"shard {sid} diverged"
        assert early == [], "slabs handed back before their rows were " \
                            f"on the holder: {early[:3]}"
        if layout == "flat":
            assert len(given) == 25          # every slab came back once
        # piggyback's are recycled like the flat layout's since PR 33
        assert reused, "no recycled slab was read into again"
        assert len(encoder._SLAB_POOL) <= encoder._SLAB_POOL.maxlen
        # one connection a lane: the target's shards ride two
        assert stats["spread_connects"] == 2
    finally:
        tgt.stop()
        encoder._SLAB_POOL.clear()
    return pieces, tgt.sizes


# -- PR 39: a target's shards ride two lanes ----------------------------------
# A lane is a worker thread with a queue and a kept connection of its own;
# a target's shards are dealt to its lanes alternately in shard order, the
# url and the first run are the target's.

def _holders(tmp_path, names, cls=FakeTarget):
    out = []
    for n in names:
        (tmp_path / n).mkdir()
        out.append(cls(str(tmp_path / n)))
    return out


@pytest.mark.parametrize("held, lanes", [
    (5, [[0, 2, 4], [1, 3]]), (4, [[0, 2], [1, 3]]),
    (3, [[0, 2], [1]]), (1, [[0]])])
def test_a_holders_shards_are_dealt_to_its_lanes(tmp_path, held, lanes):
    """3 + 2 / 2 + 2 / 2 + 1 / 1: every shard's appends arrive on one
    connection, in ascending contiguous offsets, and a connection
    carries exactly one lane's shards."""
    codec = NumpyCodec(6, 3)
    tgt, = _holders(tmp_path, ["t"])
    try:
        remote = {sid: tgt.url for sid in range(held)}
        stats, sink = _encode_through(tmp_path / "run", codec,
                                      {tgt.url: tgt}, remote, window=1)
        assert sorted(sorted(sids) for sids in
                      tgt.connections().values()) == sorted(lanes)
        by_shard = {}
        for conn, sid, off in tgt.seen:
            by_shard.setdefault(sid, []).append((conn, off))
        for sid, appends in by_shard.items():
            assert len({conn for conn, _ in appends}) == 1
            offs = [off for _, off in appends]
            assert offs == sorted(offs) and offs[0] == 0 and len(offs) > 1
        assert stats["spread_connects"] == len(lanes)
        # the local target's shards are laned the same way
        assert stats["spread_lanes"] == len(lanes) + min(2, 9 - held)
        assert [sorted(w.sids) for w in sink.workers
                if w.target.url == tgt.url] == lanes
    finally:
        tgt.stop()


class MeetingTarget(FakeTarget):
    """A holder whose second append (the first after the target's first
    run) waits at the door for another append to arrive beside it: a
    sender with one run in flight a holder never brings the second, the
    barrier times out and the append fails."""

    def __init__(self, directory):
        super().__init__(directory)
        self.door = threading.Barrier(2, timeout=10)
        self.met = []

    def _arrived(self, n_seen):
        if n_seen in (2, 3):
            try:
                self.door.wait()
            except threading.BrokenBarrierError:
                raise HttpError(500, "no second run arrived beside "
                                     "this one") from None
            with self._lock:
                self.met.append(self.seen[n_seen - 1][0])


def test_two_runs_to_one_holder_are_in_flight_at_once(tmp_path):
    """Shown with a barrier, not a clock: the holder lets neither of
    two appends in until both are there, on two connections."""
    codec = NumpyCodec(6, 3)
    tgt, = _holders(tmp_path, ["t"], MeetingTarget)
    try:
        stats, _ = _encode_through(
            tmp_path / "run", codec, {tgt.url: tgt},
            {1: tgt.url, 4: tgt.url, 7: tgt.url}, window=1)
        assert len(tgt.met) == 2 and tgt.met[0] != tgt.met[1]
        assert stats["spread_retries"] == 0
        # the target's first run went out alone, on one of the two
        assert tgt.seen[0][2] == 0
    finally:
        tgt.stop()


def test_failover_moves_every_lane_to_one_spare(tmp_path):
    """A holder dead at first contact: the lane that had the target's
    first run fails over, every shard of the target lands on the one
    spare, and the other lane never opens a connection to the dead
    holder (it sends only once the first run is acknowledged)."""
    codec = NumpyCodec(6, 3)
    dead, s1, s2 = _holders(tmp_path, ["dead", "s1", "s2"])
    dead.fail = True
    targets = {t.url: t for t in (dead, s1, s2)}
    try:
        remote = {sid: dead.url for sid in (1, 3, 4, 7, 8)}
        stats, sink = _encode_through(
            tmp_path / "run", codec, targets, remote,
            spares=[s1.url, s2.url])
        final = sink.assignment()
        assert len({final[sid] for sid in remote}) == 1
        spare = targets[final[1]]
        assert spare is not dead
        assert stats["spread_failovers"] == 1
        assert len(dead.connections()) == 1
        assert sorted(sorted(c) for c in spare.connections().values()) \
            == [[1, 4, 8], [3, 7]]
        other = s2 if spare is s1 else s1
        assert other.appends == 0 and not os.listdir(other.dir)
        assert not os.listdir(dead.dir)
    finally:
        for t in targets.values():
            t.stop()


def test_a_hedge_won_by_the_spare_gives_it_every_lane(tmp_path,
                                                      monkeypatch):
    codec = NumpyCodec(6, 3)
    slow, fast = _holders(tmp_path, ["slow", "fast"])
    slow.delay = 0.6
    monkeypatch.setenv("SW_EC_HEDGE_MS", "60")
    targets = {slow.url: slow, fast.url: fast}
    try:
        remote = {sid: slow.url for sid in (2, 5, 8)}
        stats, sink = _encode_through(tmp_path / "run", codec, targets,
                                      remote, spares=[fast.url])
        assert stats["hedges_won"] == 1 and stats["spread_failovers"] == 1
        assert {sink.assignment()[sid] for sid in remote} == {fast.url}
        # the slow holder saw the first run and nothing else, and its
        # stage is aborted once that duplicate has drained
        from conftest import wait_until
        assert wait_until(lambda: slow.aborted == 1, timeout=5)
        assert slow.appends == 1 and not os.listdir(slow.dir)
    finally:
        slow.stop()
        fast.stop()


class ShardFailingTarget(FakeTarget):
    """Fails every append of one shard from a given offset on."""

    fail_shard, fail_from = None, 0

    def _arrived(self, n_seen):
        _, sid, off = self.seen[n_seen - 1]
        if sid == self.fail_shard and off >= self.fail_from:
            raise HttpError(503, "injected lane failure")


@pytest.mark.parametrize("lane", [0, 1])
def test_a_failed_lane_finalizes_nothing_and_leaves_no_part(tmp_path,
                                                            lane):
    """After the target's first acknowledgement a failed run ends the
    spread: `finish()` raises with every lane joined and not one shard
    renamed into place — on the holder whose other lane ended clean, on
    the healthy holder, locally — and `abort()` leaves no `.part`."""
    from seaweedfs_tpu.ec.transport import (LocalShardWriter,
                                            RemoteShardWriter, StripedPush)
    bad, = _holders(tmp_path, ["bad"], ShardFailingTarget)
    good, = _holders(tmp_path, ["good"])
    w = 4096
    bad.fail_shard, bad.fail_from = (1, 3)[lane], 2 * w
    (tmp_path / "src").mkdir()
    local = [str(tmp_path / "src" / f"1{to_ext(sid)}") for sid in (0, 5)]
    writers = [LocalShardWriter(local[0]), RemoteShardWriter(1, 1),
               RemoteShardWriter(1, 2), RemoteShardWriter(1, 3),
               RemoteShardWriter(1, 4), LocalShardWriter(local[1])]
    try:
        sink = StripedPush(writers, {None: [0, 5], bad.url: [1, 3],
                                     good.url: [2, 4]}, window=1,
                           slab=w)
        rows = np.arange(6 * w, dtype=np.uint8).reshape(6, w)
        # the producer may see the failure before `finish()` does
        with pytest.raises(SpreadError):
            for _ in range(6):
                sink.write_stripe(rows[:4], rows[4:])
            sink.finish()
        assert bad.finalized == good.finalized == 0
        for d in (bad.dir, good.dir, str(tmp_path / "src")):
            assert not [f for f in os.listdir(d)
                        if not f.endswith(".part")], d
        sink.abort()
        assert not [t for t in sink.workers if t.is_alive()]
        for d in (bad.dir, good.dir, str(tmp_path / "src")):
            assert os.listdir(d) == [], d
        assert sink.stats.failovers == 0
    finally:
        bad.stop()
        good.stop()


@pytest.mark.parametrize("k, m, servers, lanes, connects", [
    (10, 4, 3, 6, 4), (6, 3, 3, 6, 4), (10, 4, 4, 8, 6)])
def test_laned_spread_is_bit_identical(tmp_path, k, m, servers, lanes,
                                       connects):
    """RS(10,4) 5+5+4, RS(6,3) 3+3+3 and RS(10,4) 4+4+3+3, round-robin
    as the shell lays them (the encoding node one of the holders): every
    shard file equals the copy flow's, and the stats name the lanes."""
    codec = NumpyCodec(k, m)
    others = _holders(tmp_path, [f"h{i}" for i in range(1, servers)])
    urls = [LOCAL] + [t.url for t in others]
    try:
        remote = {sid: urls[sid % servers] for sid in range(k + m)
                  if sid % servers}
        stats, sink = _encode_through(
            tmp_path / "run", codec, {t.url: t for t in others}, remote,
            nbytes=k * (64 << 10) + 70_001)
        assert stats["spread_lanes"] == lanes == len(sink.workers)
        assert stats["spread_connects"] == connects
        assert stats["spread_send_s"] >= stats["spread_busy_s"] > 0
        assert stats["spread_bytes"] == stats["shard_size"] * (k + m)
        for t in others:
            assert t.finalized == len(
                [s for s, u in remote.items() if u == t.url])
            assert len(t.connections()) == 2
    finally:
        for t in others:
            t.stop()
