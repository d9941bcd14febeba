"""Streaming striped survivor gather (ISSUE: overlap the network fetch
with the pipelined decode): ranged `/admin/ec/shard_read` with suffix
ranges and Content-Range, bounded-window striped gather, hedged reads
against straggler holders, connection-pool idle eviction, and the
end-to-end streaming `ec.rebuild` over a live 3-server cluster staying
bit-identical to the numpy oracle with no temp survivor copies."""

import hashlib
import http.client
import os
import shutil
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu.ec import to_ext, write_ec_files
from seaweedfs_tpu.ec.encoder import rebuild_ec_files_streaming
from seaweedfs_tpu.ec.gather import (GatherStats, LocalShardReader,
                                     RemoteShardReader,
                                     StripedGatherSource,
                                     probe_shard_size)
from seaweedfs_tpu.ops.codec import NumpyCodec
from seaweedfs_tpu.server.http_util import (HttpError, HttpServer,
                                            Response, Router, http_call,
                                            parse_range)


# -- auto slab sizing --------------------------------------------------------

def test_auto_slab_targets_multiple_stripes():
    from seaweedfs_tpu.ec.gather import auto_slab
    # volume-scale shards keep the full default slab
    assert auto_slab(256 << 20) == 8 << 20
    # a shard near one default slab shrinks so the stream still has
    # ~4 stripes to overlap (the 64 MB-volume case: 6.4 MB shards)
    small = auto_slab(6 << 20)
    assert (1 << 20) <= small < (6 << 20)
    assert -(-(6 << 20) // small) >= 4
    # dust-sized shards stay single-stripe on the default slab
    assert auto_slab(1 << 20) == 8 << 20
    # never below the 1 MB floor
    assert auto_slab(3 << 20) >= 1 << 20


# -- parse_range edge cases (satellite: suffix / overlong / empty) ----------

def test_parse_range_edge_cases():
    assert parse_range("", 100) is None
    assert parse_range("items=0-5", 100) is None
    assert parse_range("bytes=0-9", 100) == (0, 10)
    assert parse_range("bytes=90-", 100) == (90, 10)
    # suffix range: last N bytes
    assert parse_range("bytes=-10", 100) == (90, 10)
    # overlong suffix clamps to the whole resource
    assert parse_range("bytes=-1000", 100) == (0, 100)
    # end past EOF clamps
    assert parse_range("bytes=50-1000", 100) == (50, 50)
    for bad in ("bytes=", "bytes=abc-", "bytes=200-", "bytes=9-2"):
        with pytest.raises(HttpError) as ei:
            parse_range(bad, 100)
        assert ei.value.status == 416


# -- fake holder: shard_read with query + Range forms -----------------------

class FakeHolder:
    """Minimal holder serving /admin/ec/shard_read from a directory of
    {vid}.ecNN files, with injectable delay/failure for straggler
    drills. Counts every shard_read it answers."""

    def __init__(self, directory):
        self.dir = directory
        self.delay = 0.0
        self.fail = False
        self.calls = 0
        self._lock = threading.Lock()
        router = Router()
        router.add("GET", "/admin/ec/shard_read", self._shard_read)
        router.add("GET", "/ping", lambda req: {})
        self.server = HttpServer(0, router).start()
        self.url = f"127.0.0.1:{self.server.port}"

    def _shard_read(self, req):
        with self._lock:
            self.calls += 1
        if self.delay:
            time.sleep(self.delay)
        if self.fail:
            raise HttpError(503, "injected failure")
        vid = int(req.query["volume"])
        sid = int(req.query["shard"])
        path = os.path.join(self.dir, f"{vid}{to_ext(sid)}")
        if not os.path.exists(path):
            raise HttpError(404, f"shard {vid}.{sid} not here")
        total = os.path.getsize(path)
        rng = parse_range(req.headers.get("Range", ""), total)
        with open(path, "rb") as f:
            if rng is None:
                off = int(req.query.get("offset", 0))
                n = int(req.query.get("size", 0))
                f.seek(off)
                return Response(f.read(n),
                                headers={"Accept-Ranges": "bytes"})
            off, n = rng
            f.seek(off)
            return Response(
                f.read(n), status=206,
                headers={"Accept-Ranges": "bytes",
                         "Content-Range":
                             f"bytes {off}-{off + n - 1}/{total}"})

    def stop(self):
        self.server.stop()


def _seed_shards(dirpath, k, m, nbytes, seed=3):
    """RS(k,m) shard files for volume 1 in dirpath; returns (base,
    shard digests)."""
    rng = np.random.default_rng(seed)
    base = os.path.join(str(dirpath), "1")
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, nbytes, dtype=np.uint8).tobytes())
    write_ec_files(base, codec=NumpyCodec(k, m), large_block=64 << 10,
                   small_block=8 << 10, slab=32 << 10, pipelined=False)
    os.remove(base + ".dat")
    digests = {}
    for i in range(k + m):
        with open(base + to_ext(i), "rb") as f:
            digests[i] = hashlib.sha256(f.read()).hexdigest()
    return base, digests


# -- remote reader: round-robin + size probe --------------------------------

def test_round_robin_and_size_probe(tmp_path):
    base, _ = _seed_shards(tmp_path, 6, 3, 100_000)
    shard_size = os.path.getsize(base + to_ext(0))
    a, b = FakeHolder(str(tmp_path)), FakeHolder(str(tmp_path))
    try:
        assert probe_shard_size(1, 0, [a.url]) == shard_size
        stats = GatherStats()
        r = RemoteShardReader(1, 0, [a.url, b.url], stats, hedge_ms=0)
        with open(base + to_ext(0), "rb") as f:
            ref = f.read()
        chunk = 16 << 10
        got = b"".join(
            r.read(off, min(chunk, shard_size - off), stripe_idx=i)
            for i, off in enumerate(range(0, shard_size, chunk)))
        assert got == ref
        # consecutive stripes lead with alternating holders
        assert a.calls > 0 and b.calls > 0
        assert stats.fetches == -(-shard_size // chunk)
        assert stats.bytes == shard_size
    finally:
        a.stop()
        b.stop()


def test_failover_to_second_holder(tmp_path):
    base, _ = _seed_shards(tmp_path, 6, 3, 60_000)
    a, b = FakeHolder(str(tmp_path)), FakeHolder(str(tmp_path))
    try:
        a.fail = True
        stats = GatherStats()
        r = RemoteShardReader(1, 2, [a.url, b.url], stats, hedge_ms=0)
        with open(base + to_ext(2), "rb") as f:
            ref = f.read(4096)
        assert r.read(0, 4096, stripe_idx=0) == ref
        assert stats.retries >= 1
    finally:
        a.stop()
        b.stop()


# -- hedging (satellite: straggler holder drill) ----------------------------

def test_hedge_fires_on_straggler(tmp_path):
    base, _ = _seed_shards(tmp_path, 6, 3, 60_000)
    a, b = FakeHolder(str(tmp_path)), FakeHolder(str(tmp_path))
    try:
        a.delay = 0.4  # straggler leads every even stripe
        stats = GatherStats()
        r = RemoteShardReader(1, 1, [a.url, b.url], stats, hedge_ms=50)
        with open(base + to_ext(1), "rb") as f:
            ref = f.read(8192)
        t0 = time.perf_counter()
        assert r.read(0, 8192, stripe_idx=0) == ref
        # won by the hedge, not by waiting out the straggler
        assert time.perf_counter() - t0 < 0.35
        assert stats.hedges_fired >= 1
        assert stats.hedges_won >= 1
    finally:
        a.stop()
        b.stop()


# -- streaming rebuild vs oracle, mixed local+remote, both backends ---------

@pytest.mark.parametrize("backend", ["tpu", "mesh"])
def test_streaming_rebuild_bit_identical(tmp_path, backend):
    if backend == "tpu":
        from seaweedfs_tpu.ops.rs_tpu import TpuCodec as Codec
    else:
        from seaweedfs_tpu.parallel.mesh_codec import MeshCodec as Codec
    k, m, lost = 6, 3, (1, 4, 7)
    holder_dir = tmp_path / "holder"
    holder_dir.mkdir()
    _, ref = _seed_shards(holder_dir, k, m, 150_000 + 53)
    rebuild_dir = tmp_path / "rebuilder"
    rebuild_dir.mkdir()
    base = str(rebuild_dir / "1")
    # survivors 0,2 already local to the rebuilder; the rest stream in
    for sid in (0, 2):
        shutil.copy(os.path.join(str(holder_dir), f"1{to_ext(sid)}"),
                    base + to_ext(sid))
    holder = FakeHolder(str(holder_dir))
    try:
        present = [i not in lost for i in range(k + m)]
        src = [i for i in range(k + m) if present[i]][:k]
        stats_ = GatherStats()
        readers = [LocalShardReader(base + to_ext(i), stats_)
                   if i in (0, 2)
                   else RemoteShardReader(1, i, [holder.url], stats_,
                                          hedge_ms=0)
                   for i in src]
        shard_size = os.path.getsize(base + to_ext(0))
        source = StripedGatherSource(readers, shard_size, slab=16 << 10,
                                     window=2, stats=stats_)
        out_stats = {}
        rebuilt = rebuild_ec_files_streaming(
            base, present, list(lost), source, codec=Codec(k, m),
            slab=16 << 10, stats=out_stats)
        assert sorted(rebuilt) == sorted(lost)
        for sid in lost:
            with open(base + to_ext(sid), "rb") as f:
                got = hashlib.sha256(f.read()).hexdigest()
            assert got == ref[sid], f"shard {sid} diverged"
        # only the rebuilt shards + the 2 local survivors on disk: the
        # remote survivors never landed as files
        shard_files = sorted(f for f in os.listdir(str(rebuild_dir))
                             if f.startswith("1.ec"))
        assert shard_files == sorted(
            f"1{to_ext(s)}" for s in set(lost) | {0, 2})
        assert out_stats["gather_stripes"] == -(-shard_size // (16 << 10))
        # local survivor reads count into the gather too (disk is part
        # of the gather plane): k rows per stripe
        assert out_stats["gather_bytes"] == shard_size * k
        assert 0.0 <= out_stats["overlap_frac"] <= 1.0
        assert out_stats["gather_remote_shards"] == k - 2
        # every reader, local or remote, wrote its own row of every
        # stripe's block; no hedge could arm, so none was copied
        assert out_stats["rows_in_place"] == \
            k * out_stats["gather_stripes"]
        assert out_stats["rows_copied"] == 0
    finally:
        holder.stop()


@pytest.mark.parametrize("backend", ["numpy", "tpu"])
def test_a_second_rebuild_takes_the_firsts_blocks(tmp_path, monkeypatch,
                                                  backend):
    """The consumer hands each stripe's block back once its output is
    drained (after the matmul on a host codec): a second rebuild is
    gathered into the first's memory and allocates none — the reply's
    ``slab_fresh_bytes`` stands still — with the same shards out."""
    from seaweedfs_tpu.ec import transport
    if backend == "tpu":
        from seaweedfs_tpu.ops.rs_tpu import TpuCodec as Codec
    else:
        Codec = NumpyCodec
    k, m, lost, slab = 6, 3, (0, 5, 8), 16 << 10
    holder_dir = tmp_path / "holder"
    holder_dir.mkdir()
    _, ref = _seed_shards(holder_dir, k, m, 500_000 + 11)
    base = str(tmp_path / "1")
    shutil.copy(os.path.join(str(holder_dir), f"1{to_ext(2)}"),
                base + to_ext(2))
    shard_size = os.path.getsize(base + to_ext(2))
    present = [i not in lost for i in range(k + m)]
    src = [i for i in range(k + m) if present[i]][:k]
    taken, real_take = [], transport._take_slab

    def noting_take(rows, width, **kw):
        out = real_take(rows, width, **kw)
        taken.append(out.base)
        return out

    monkeypatch.setattr(transport, "_take_slab", noting_take)
    holder = FakeHolder(str(holder_dir))
    transport._SLAB_POOL.clear()
    try:
        runs = []
        for _ in range(2):
            stats_ = GatherStats()
            readers = [LocalShardReader(base + to_ext(i), stats_)
                       if i == 2
                       else RemoteShardReader(1, i, [holder.url], stats_,
                                              hedge_ms=0)
                       for i in src]
            source = StripedGatherSource(readers, shard_size, slab=slab,
                                         window=2, stats=stats_)
            out_stats = {}
            first = len(taken)
            assert sorted(rebuild_ec_files_streaming(
                base, present, list(lost), source, codec=Codec(k, m),
                slab=slab, stats=out_stats)) == sorted(lost)
            for sid in lost:
                with open(base + to_ext(sid), "rb") as f:
                    assert hashlib.sha256(f.read()).hexdigest() == \
                        ref[sid], f"shard {sid} diverged"
                os.remove(base + to_ext(sid))
            runs.append((out_stats, taken[first:]))
        (one, blocks1), (two, blocks2) = runs
        stripes = -(-shard_size // slab)
        assert len(blocks1) == len(blocks2) == stripes > 4
        assert 0 < one["slab_fresh_bytes"] <= stripes * k * slab
        assert two["slab_fresh_bytes"] == 0
        assert all(any(b is a for a in blocks1) for b in blocks2)
        assert two["rows_in_place"] == k * stripes
    finally:
        transport._SLAB_POOL.clear()
        holder.stop()


# -- bounded window (satellite: memory stays O(window*slab)) ----------------

def test_bounded_gather_window():
    k, slab, window, n_stripes = 4, 8 << 10, 2, 12
    shard_size = slab * n_stripes
    stats = GatherStats()

    class SlowReader:
        remote = False

        def __init__(self):
            self.stats = stats

        def read_into(self, off, n, stripe_idx, dest):
            time.sleep(0.002)
            t = time.perf_counter()
            self.stats.add_fetch(n, t - 0.002, t)
            dest[:] = stripe_idx & 0xFF

    source = StripedGatherSource([SlowReader() for _ in range(k)],
                                 shard_size, slab=slab, window=window,
                                 stats=stats)
    for (idx, off, w), data in source.slabs():
        assert data.shape == (k, w)
        assert bool((data == (idx & 0xFF)).all())
        time.sleep(0.005)  # slow consumer: prefetch must NOT run ahead
    assert stats.stripes == n_stripes
    # in-flight + buffered gather memory never exceeded the window
    assert stats.peak_buffered <= window * k * slab


def test_streaming_rebuild_failure_leaves_no_partials(tmp_path):
    k, m, lost = 6, 3, (1, 7)
    base, _ = _seed_shards(tmp_path, k, m, 120_000)
    for sid in lost:
        os.remove(base + to_ext(sid))
    stats = GatherStats()

    class FlakyReader:
        remote = True

        def __init__(self, path):
            self.path = path
            self.stats = stats

        def read_into(self, off, n, stripe_idx, dest):
            if stripe_idx >= 1:
                raise HttpError(503, "holder went away")
            with open(self.path, "rb") as f:
                f.seek(off)
                f.readinto(dest)

    present = [i not in lost for i in range(k + m)]
    src = [i for i in range(k + m) if present[i]][:k]
    readers = [FlakyReader(base + to_ext(i)) for i in src]
    shard_size = os.path.getsize(base + to_ext(0))
    source = StripedGatherSource(readers, shard_size, slab=16 << 10,
                                 window=2, stats=stats)
    with pytest.raises(Exception):
        rebuild_ec_files_streaming(base, present, list(lost), source,
                                   codec=NumpyCodec(k, m), slab=16 << 10)
    # the half-written missing shards were removed — rebuild is all or
    # nothing on the rebuilder's disk
    for sid in lost:
        assert not os.path.exists(base + to_ext(sid))


# -- connection pool: idle-age eviction + churn counters --------------------

def test_pool_idle_eviction(tmp_path, monkeypatch):
    from seaweedfs_tpu.server import http_util as hu
    holder = FakeHolder(str(tmp_path))
    try:
        hu.clear_conn_pool()
        monkeypatch.setenv("SW_HTTP_POOL_MAX_IDLE_S", "0.05")
        before = hu.pool_stats_snapshot()
        http_call("GET", f"http://{holder.url}/ping")
        time.sleep(0.15)
        http_call("GET", f"http://{holder.url}/ping")
        after = hu.pool_stats_snapshot()
        assert after["evicted_idle"] - before["evicted_idle"] >= 1
        assert after["created"] - before["created"] >= 2
        # fresh sockets within the idle window DO get reused
        monkeypatch.setenv("SW_HTTP_POOL_MAX_IDLE_S", "60")
        http_call("GET", f"http://{holder.url}/ping")
        http_call("GET", f"http://{holder.url}/ping")
        assert hu.pool_stats_snapshot()["reused"] - \
            after["reused"] >= 1
    finally:
        hu.clear_conn_pool()
        holder.stop()


def test_observe_gather_metrics():
    from seaweedfs_tpu.stats import metrics
    before = metrics.VOLUME_EC_GATHER_COUNTER.value("bytes")
    metrics.observe_gather({
        "gather_bytes": 1 << 20, "gather_fetches": 16,
        "gather_stripes": 4, "gather_retries": 1, "hedges_fired": 2,
        "hedges_won": 1, "gather_busy_s": 0.25, "gather_mbps": 120.5,
        "overlap_frac": 0.42})
    assert metrics.VOLUME_EC_GATHER_COUNTER.value("bytes") - before \
        == 1 << 20
    assert metrics.VOLUME_EC_OVERLAP_FRAC_GAUGE.value() == 0.42
    assert metrics.VOLUME_EC_GATHER_MBPS_GAUGE.value() == 120.5
    render = metrics.VOLUME_SERVER_GATHER.render()
    assert 'ec_gather_total{kind="bytes"}' in render
    assert "ec_overlap_frac" in render


# -- end-to-end: streaming ec.rebuild over a live cluster -------------------

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


def test_cluster_streaming_rebuild_end_to_end(cluster3):
    from seaweedfs_tpu.client import operation as op
    from seaweedfs_tpu.shell.command_env import CommandEnv
    from seaweedfs_tpu.shell.command_ec import do_ec_rebuild
    import io
    master, servers = cluster3
    rng = np.random.default_rng(5)
    fid = None
    for i in range(12):
        data = rng.integers(0, 256, 150_000).astype(np.uint8).tobytes()
        fid = op.upload_data(master.url, data, filename=f"f{i}",
                             collection="sg")
    vid = int(fid.split(",")[0])
    env = CommandEnv(master.url, out=io.StringIO())
    from seaweedfs_tpu.shell.command_env import run_command
    assert run_command(env, f"ec.encode -volumeId {vid}")

    # numpy oracle: sha256 of every shard right after the encode
    files = _cluster_shard_files(servers)
    assert sorted(files) == list(range(14))
    oracle = {}
    for sid, paths in files.items():
        with open(paths[0], "rb") as f:
            oracle[sid] = hashlib.sha256(f.read()).hexdigest()

    # ranged-read satellite against a REAL holder: suffix range -> 206
    # with Content-Range + Accept-Ranges; unsatisfiable -> 416
    holder_vs = next(vs for vs in servers
                     if vs.store.find_ec_volume(vid) is not None)
    some_sid = holder_vs.store.find_ec_volume(vid).shard_ids()[0]
    total = holder_vs.store.find_ec_volume(vid).shards[some_sid].size
    conn = http.client.HTTPConnection("127.0.0.1", holder_vs.port)
    try:
        conn.request("GET", f"/admin/ec/shard_read?volume={vid}"
                            f"&shard={some_sid}",
                     headers={"Range": "bytes=-5"})
        resp = conn.getresponse()
        body = resp.read()
        assert resp.status == 206
        assert len(body) == 5
        assert resp.getheader("Accept-Ranges") == "bytes"
        assert resp.getheader("Content-Range") == \
            f"bytes {total - 5}-{total - 1}/{total}"
        conn.request("GET", f"/admin/ec/shard_read?volume={vid}"
                            f"&shard={some_sid}",
                     headers={"Range": f"bytes={total + 10}-"})
        resp = conn.getresponse()
        resp.read()
        assert resp.status == 416
    finally:
        conn.close()

    # destroy a mixed set of shards on the biggest holder
    victim = max(servers,
                 key=lambda vs: len(vs.store.find_ec_volume(vid).shards)
                 if vs.store.find_ec_volume(vid) else 0)
    held = victim.store.find_ec_volume(vid).shard_ids()
    to_lose = held[:4]
    victim.store.unmount_ec_shards(vid, to_lose)
    for loc in victim.store.locations:
        for sid in to_lose:
            for f in os.listdir(loc.directory):
                if f.endswith(to_ext(sid)):
                    os.remove(os.path.join(loc.directory, f))
    victim.heartbeat_once()

    deadline = time.time() + 10
    while time.time() < deadline:
        info = env.ec_volumes().get(str(vid))
        shards = {int(s): urls for s, urls in info["shards"].items()}
        if all(s not in shards or victim.url not in shards[s]
               for s in to_lose):
            break
        time.sleep(0.2)
    missing = [s for s in range(14) if s not in shards]
    assert sorted(missing) == sorted(to_lose)

    timings = {}
    do_ec_rebuild(env, vid, "sg", shards, missing, timings=timings)

    # overlap telemetry rode the response into the shell timings
    assert "overlap_frac" in timings
    assert timings["gather_stripes"] >= 1
    assert timings["gather_bytes"] > 0
    assert timings["gathered_shards"] >= 1

    # every shard is back, bit-identical to the oracle, and each shard
    # exists EXACTLY once cluster-wide: the streaming rebuild left no
    # temp survivor copies on the rebuilder
    files_after = _cluster_shard_files(servers)
    assert sorted(files_after) == list(range(14))
    for sid, paths in files_after.items():
        assert len(paths) == 1, \
            f"shard {sid} duplicated: {paths} (temp copy leaked?)"
        with open(paths[0], "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest() == oracle[sid], \
                f"shard {sid} diverged from the oracle"

    # the cluster still serves the data through EC reads
    got = http_call("GET", f"http://{servers[0].url}/{fid}")
    assert got == data
