"""Batched degraded-read serving tier (ISSUE: fused-dispatch
reconstruct-on-read): the DegradedReadEngine behind
volume_server._reconstruct_shard_range — request coalescing into one
fused decode dispatch per batch, exactly-k survivor gather through the
reader stack, one-row decode via codec.lost_row_coeffs, the bounded
reconstructed-slab LRU with mount-hook invalidation, the
SW_EC_DEGRADED_READ_TIMEOUT_S forget-on-timeout fix in
_read_shard_from_holders, the ec_degraded_* metric families, the
`volume.ec.degraded` shell status line, and the live-cluster drill:
bit-identical degraded reads, warm re-reads that never touch survivors,
503 once fewer than k shards remain, and the naive per-read fallback
(SW_EC_DEGRADED_MODE=naive) staying bit-identical while bypassing the
engine."""

import hashlib
import io
import http.client
import os
import threading
import time
import types

import numpy as np
import pytest

from seaweedfs_tpu.ec import to_ext
from seaweedfs_tpu.ec.degraded import (DegradedReadEngine, SlabCache,
                                       degraded_mode,
                                       degraded_read_timeout_s)
from seaweedfs_tpu.ec.ec_volume import EcShardNotFound
from seaweedfs_tpu.ops.codec import NumpyCodec, host_matmul

K, M = 10, 4


def _codec(backend, **kw):
    if backend == "numpy":
        return NumpyCodec(K, M)
    if backend == "tpu":
        from seaweedfs_tpu.ops.rs_tpu import TpuCodec
        return TpuCodec(K, M, **kw)
    from seaweedfs_tpu.parallel.mesh_codec import MeshCodec
    return MeshCodec(K, M, **kw)


# -- engine-level harness: real shard files, fake store ---------------------

class _FakeShard:
    def __init__(self, path):
        self.path = path

    @property
    def size(self):
        return os.path.getsize(self.path)

    def read_at(self, off, n):
        with open(self.path, "rb") as f:
            f.seek(off)
            return f.read(n)


class _FakeEv:
    def __init__(self, shards):
        self.shards = shards


class _FakeStore:
    def __init__(self, ev):
        self.ev = ev

    def find_ec_volume(self, vid):
        return self.ev


def _seed(tmp_path, w=131_077, lost=3, keep=None, seed=5):
    """Write RS(10,4) shard files for a (K, w) payload; returns
    (shard array, {sid: path}). w deliberately not slab-aligned so the
    tail zero-pad path is always exercised."""
    rng = np.random.default_rng(seed)
    shards = NumpyCodec(K, M).encode_to_all(
        rng.integers(0, 256, (K, w), dtype=np.uint8))
    paths = {}
    for i in range(K + M):
        p = str(tmp_path / f"1{to_ext(i)}")
        shards[i].tofile(p)
        paths[i] = p
    return shards, paths


def _engine(tmp_path, codec, lost=3, keep=None, slab=4096, batch_ms=0.0,
            cache_bytes=None, w=131_077):
    shards, paths = _seed(tmp_path, w=w, lost=lost)
    survivors = [i for i in range(K + M) if i != lost
                 and (keep is None or i in keep)]
    ev = _FakeEv({i: _FakeShard(paths[i]) for i in survivors})
    eng = DegradedReadEngine(
        store=_FakeStore(ev), locations=lambda vid: {},
        codec=lambda ev: codec, slab=slab, batch_ms=batch_ms,
        cache_bytes=cache_bytes)
    return eng, shards, lost


def _expect(shards, lost, off, size):
    """Reference bytes with the past-tail zero pad local reads apply."""
    raw = shards[lost][off:off + size].tobytes()
    return raw + b"\x00" * (size - len(raw))


@pytest.mark.parametrize("backend", ["numpy", "tpu", "mesh"])
def test_degraded_engine_bit_identity(tmp_path, backend):
    eng, shards, lost = _engine(tmp_path, _codec(backend))
    w = shards.shape[1]
    # cross-slab, slab-aligned, sub-slab, tail-overhanging, full-shard
    for off, size in [(0, 100), (4096, 4096), (4000, 9000),
                      (w - 50, 200), (0, w), (w + 10, 64)]:
        assert eng.read(1, lost, off, size) == \
            _expect(shards, lost, off, size), (backend, off, size)
    snap = eng.snapshot()
    # exactly-k contract: every batch gathered K survivor rows, never
    # the TOTAL_SHARDS-1 fan-out of the legacy loop
    assert snap["survivor_rows"] == K * snap["batches"]
    assert snap["errors"] == 0


def test_degraded_engine_coalesces_concurrent_reads(tmp_path):
    eng, shards, lost = _engine(tmp_path, _codec("numpy"), batch_ms=120)
    n = 8
    barrier = threading.Barrier(n)
    results, errs = {}, []

    def reader(i):
        off, size = i * 13_000 + 7, 5_000 + i * 11
        try:
            barrier.wait(timeout=10)
            results[i] = (eng.read(1, lost, off, size) ==
                          _expect(shards, lost, off, size))
        except Exception as e:  # noqa: BLE001 - assert below
            errs.append(e)

    threads = [threading.Thread(target=reader, args=(i,))
               for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errs
    assert all(results[i] for i in range(n))
    snap = eng.snapshot()
    assert snap["reads"] == n
    # the coalescing contract: concurrent same-shard reads share a
    # batch (>= 2 coalesced; with the 120 ms window all 8 in practice)
    assert snap["max_batch_requests"] >= 2
    assert snap["batches"] < n
    assert snap["batched_requests"] == n
    # one fused gather+decode per batch, exactly k rows each
    assert snap["survivor_rows"] == K * snap["batches"]


def test_degraded_engine_cache_hit_and_invalidate(tmp_path):
    eng, shards, lost = _engine(tmp_path, _codec("numpy"))
    assert eng.read(1, lost, 8_000, 10_000) == \
        _expect(shards, lost, 8_000, 10_000)
    snap = eng.snapshot()
    assert snap["cache_entries"] > 0
    fetched = snap["survivor_bytes"]
    # warm re-read: slab LRU serves it, zero survivor traffic
    assert eng.read(1, lost, 8_000, 10_000) == \
        _expect(shards, lost, 8_000, 10_000)
    snap = eng.snapshot()
    assert snap["survivor_bytes"] == fetched
    assert snap["cache_hits"] > 0
    # mount-hook invalidation: cold again afterwards
    eng.invalidate(1)
    assert eng.snapshot()["cache_entries"] == 0
    assert eng.read(1, lost, 8_000, 10_000) == \
        _expect(shards, lost, 8_000, 10_000)
    assert eng.snapshot()["survivor_bytes"] > fetched


def test_degraded_engine_insufficient_survivors(tmp_path):
    # 9 reachable < k=10: must refuse, not return garbage
    eng, _, lost = _engine(tmp_path, _codec("numpy"),
                           keep=list(range(10)))
    with pytest.raises(EcShardNotFound):
        eng.read(1, lost, 0, 128)
    assert eng.snapshot()["errors"] == 1


@pytest.mark.parametrize("backend", ["tpu", "mesh"])
def test_degraded_engine_device_crossover(tmp_path, backend):
    # force the crossover low so a wide batch takes the fused device
    # dispatch and a narrow one stays on the host LUT walk
    codec = _codec(backend, small_dispatch_bytes=1024)
    eng, shards, lost = _engine(tmp_path, codec, slab=16_384)
    assert eng.read(1, lost, 0, 80_000) == \
        _expect(shards, lost, 0, 80_000)
    assert eng.snapshot()["device_dispatches"] >= 1
    # the 5-byte tail slab is far below the crossover: host path
    assert eng.read(1, lost, 131_073, 64) == \
        _expect(shards, lost, 131_073, 64)
    snap = eng.snapshot()
    assert snap["host_dispatches"] >= 1
    assert snap["errors"] == 0


def test_degraded_readahead_prefetch_and_hits(tmp_path):
    eng, shards, lost = _engine(tmp_path, _codec("numpy"))
    eng.readahead = 2
    # one slab requested, two neighbors ride the same batch
    assert eng.read(1, lost, 0, 4096) == _expect(shards, lost, 0, 4096)
    snap = eng.snapshot()
    assert snap["readahead_slabs"] == 2
    assert snap["readahead_hits"] == 0
    fetched = snap["survivor_bytes"]
    # the sequential next read is served by the prefetched slab — no
    # new survivor traffic, and the hit is attributed to readahead
    assert eng.read(1, lost, 4096, 4096) == \
        _expect(shards, lost, 4096, 4096)
    snap = eng.snapshot()
    assert snap["survivor_bytes"] == fetched
    assert snap["readahead_hits"] == 1
    assert snap["readahead_hit_ratio"] == 0.5
    # readahead=0 disables the widening entirely
    eng0, shards0, lost0 = _engine(tmp_path, _codec("numpy"))
    eng0.readahead = 0
    eng0.read(1, lost0, 0, 4096)
    assert eng0.snapshot()["readahead_slabs"] == 0
    # a disabled cache can never serve a prefetch: don't waste the work
    engc, shardsc, lostc = _engine(tmp_path, _codec("numpy"),
                                   cache_bytes=0)
    engc.readahead = 2
    engc.read(1, lostc, 0, 4096)
    assert engc.snapshot()["readahead_slabs"] == 0


def test_degraded_readahead_env_knob(monkeypatch):
    from seaweedfs_tpu.ec.degraded import degraded_readahead_slabs
    monkeypatch.delenv("SW_EC_DEGRADED_READAHEAD_SLABS", raising=False)
    assert degraded_readahead_slabs() == 1
    monkeypatch.setenv("SW_EC_DEGRADED_READAHEAD_SLABS", "3")
    assert degraded_readahead_slabs() == 3
    monkeypatch.setenv("SW_EC_DEGRADED_READAHEAD_SLABS", "-2")
    assert degraded_readahead_slabs() == 0
    monkeypatch.setenv("SW_EC_DEGRADED_READAHEAD_SLABS", "junk")
    assert degraded_readahead_slabs() == 1


def test_degraded_dispatch_honors_live_override(tmp_path):
    """The SW_EC_SMALL_DISPATCH_AUTO fitted crossover steers the batch
    host/device decision live — no codec reconstruction."""
    from seaweedfs_tpu.ops.codec import set_small_dispatch_override
    codec = _codec("tpu", small_dispatch_bytes=1024)
    eng, shards, lost = _engine(tmp_path, codec, slab=16_384)
    set_small_dispatch_override(1 << 28)
    try:
        assert eng.read(1, lost, 0, 80_000) == \
            _expect(shards, lost, 0, 80_000)
        snap = eng.snapshot()
        assert snap["device_dispatches"] == 0
        assert snap["host_dispatches"] >= 1
    finally:
        set_small_dispatch_override(None)


def test_slab_cache_lru_budget_and_invalidate():
    c = SlabCache(max_bytes=10_000)
    c.put((1, 0, 0), b"a" * 4_000)
    c.put((1, 0, 1), b"b" * 4_000)
    c.put((1, 1, 0), b"c" * 4_000)   # over budget: (1,0,0) evicted
    assert c.get((1, 0, 0)) is None
    assert c.get((1, 0, 1)) == b"b" * 4_000
    assert c.evictions == 1
    assert c.put((1, 2, 0), b"x" * 20_000) is None  # larger than budget
    assert c.get((1, 2, 0)) is None
    assert c.invalidate(1, shard_ids=[1]) == 1
    assert c.get((1, 1, 0)) is None
    assert c.get((1, 0, 1)) == b"b" * 4_000
    c.invalidate(1)
    assert c.stats() == (0, 0)
    # disabled cache never stores
    off = SlabCache(max_bytes=0)
    off.put((1, 0, 0), b"zz")
    assert off.get((1, 0, 0)) is None


def test_lost_row_coeffs_single_row_decode():
    codec = NumpyCodec(K, M)
    rng = np.random.default_rng(3)
    shards = codec.encode_to_all(
        rng.integers(0, 256, (K, 997), dtype=np.uint8))
    lost = 6
    present = tuple(i != lost for i in range(K + M))
    src, row = codec.lost_row_coeffs(present, lost)
    assert len(src) == K and row.shape == (1, K)
    out = host_matmul(row, np.stack([shards[s] for s in src]))
    assert np.array_equal(out[0], shards[lost])
    with pytest.raises(ValueError):
        codec.lost_row_coeffs(present, (lost + 1) % (K + M))


# -- env knobs --------------------------------------------------------------

def test_degraded_env_knobs(monkeypatch):
    monkeypatch.delenv("SW_EC_DEGRADED_READ_TIMEOUT_S", raising=False)
    assert degraded_read_timeout_s() == 10.0
    monkeypatch.setenv("SW_EC_DEGRADED_READ_TIMEOUT_S", "3.5")
    assert degraded_read_timeout_s() == 3.5
    monkeypatch.setenv("SW_EC_DEGRADED_READ_TIMEOUT_S", "0")
    assert degraded_read_timeout_s() == 0.1    # floored, never zero
    monkeypatch.setenv("SW_EC_DEGRADED_READ_TIMEOUT_S", "junk")
    assert degraded_read_timeout_s() == 10.0
    monkeypatch.delenv("SW_EC_DEGRADED_MODE", raising=False)
    assert degraded_mode() == "batch"
    monkeypatch.setenv("SW_EC_DEGRADED_MODE", " Naive ")
    assert degraded_mode() == "naive"


def test_read_shard_from_holders_timeout_and_forget(monkeypatch):
    """Satellite fix: the per-holder fetch budget comes from
    SW_EC_DEGRADED_READ_TIMEOUT_S (not the old hardcoded 30 s) and a
    socket-level timeout forgets the holder like an HTTP error."""
    from seaweedfs_tpu.server import volume_server as vsmod
    seen = []

    def dead_http_call(method, url, timeout=None, **kw):
        seen.append(timeout)
        raise OSError("timed out")

    monkeypatch.setattr(vsmod, "http_call", dead_http_call)
    monkeypatch.setenv("SW_EC_DEGRADED_READ_TIMEOUT_S", "3.5")
    forgotten = []
    stub = types.SimpleNamespace(
        url="me:8080",
        _ec_shard_locations=lambda vid: {2: ["me:8080", "h1:1", "h2:2"]},
        _ec_loc_cache=types.SimpleNamespace(
            forget=lambda vid, sid, h: forgotten.append((vid, sid, h))))
    got = vsmod.VolumeServer._read_shard_from_holders(stub, 7, 2, 0, 64)
    assert got is None
    assert seen == [3.5, 3.5]          # self skipped, env timeout used
    assert forgotten == [(7, 2, "h1:1"), (7, 2, "h2:2")]


# -- metrics mirror ---------------------------------------------------------

def test_observe_degraded_metrics(tmp_path):
    from seaweedfs_tpu.stats import metrics
    eng, shards, lost = _engine(tmp_path, _codec("numpy"))
    eng.read(1, lost, 0, 9_000)
    eng.read(1, lost, 0, 9_000)      # warm: drives the hit ratio gauge
    metrics.observe_degraded(eng.snapshot())
    c = metrics.VOLUME_EC_DEGRADED_COUNTER
    # a set_total mirror: this engine's own count, whatever an earlier
    # engine of the process (another test file on this worker) left
    assert c.value("reads") == 2
    assert c.value("batches") >= 1
    assert c.value("survivor_bytes") > 0
    # set_total mirror is idempotent for an unchanged snapshot
    metrics.observe_degraded(eng.snapshot())
    assert c.value("reads") == 2
    render = metrics.VOLUME_SERVER_GATHER.render()
    assert 'ec_degraded_total{kind="reads"}' in render
    assert 'ec_degraded_total{kind="cache_hits"}' in render
    assert "ec_degraded_read_seconds" in render
    assert "ec_degraded_batch_width" in render
    assert "ec_degraded_cache_hit_ratio" in render


# -- live cluster: degraded serving drill -----------------------------------

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


def _lose_shards(env, victim, vid, to_lose):
    victim.store.unmount_ec_shards(vid, to_lose)
    for loc in victim.store.locations:
        for sid in to_lose:
            for f in os.listdir(loc.directory):
                if f.endswith(to_ext(sid)):
                    os.remove(os.path.join(loc.directory, f))
    victim.heartbeat_once()
    from conftest import wait_until

    def victim_dropped():
        info = env.ec_volumes().get(str(vid)) or {"shards": {}}
        shards = {int(s): urls for s, urls in info["shards"].items()}
        if all(s not in shards or victim.url not in shards[s]
               for s in to_lose):
            return (shards,)  # 1-tuple: truthy even for an empty map
        return None

    got = wait_until(victim_dropped, timeout=10)
    assert got, f"master never dropped shards {to_lose}"
    return got[0]


def _get(vs, fid):
    host, port = vs.url.rsplit(":", 1)
    conn = http.client.HTTPConnection(host, int(port))
    try:
        conn.request("GET", f"/{fid}")
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def test_cluster_degraded_read_end_to_end(cluster3, monkeypatch):
    from seaweedfs_tpu.client import operation as op
    from seaweedfs_tpu.shell.command_env import CommandEnv, run_command
    master, servers = cluster3
    rng = np.random.default_rng(17)
    payloads = {}
    for i in range(12):
        data = rng.integers(0, 256, 150_000).astype(np.uint8).tobytes()
        fid = op.upload_data(master.url, data, filename=f"d{i}",
                             collection="dg")
        payloads[fid] = data
    # assignment round-robins over several volumes; drill the one that
    # got the most needles (its first needle sits at offset 0 → shard 0)
    by_vid = {}
    for f in payloads:
        by_vid.setdefault(int(f.split(",")[0]), []).append(f)
    vid = max(by_vid, key=lambda v: len(by_vid[v]))
    payloads = {f: payloads[f] for f in by_vid[vid]}
    assert len(payloads) >= 2
    env = CommandEnv(master.url, out=io.StringIO())
    assert run_command(env, f"ec.encode -volumeId {vid}")

    # needle data starts at byte 0 of the volume, so data shard 0
    # always carries needles — that is the shard we kill
    lost_sid = 0
    victim = next(vs for vs in servers
                  if (ev := vs.store.find_ec_volume(vid)) is not None
                  and lost_sid in ev.shards)
    serving = next(vs for vs in servers if vs is not victim
                   and vs.store.find_ec_volume(vid) is not None)

    # healthy baseline through the serving server
    for f, want in payloads.items():
        status, got = _get(serving, f)
        assert status == 200 and got == want

    _lose_shards(env, victim, vid, [lost_sid])
    serving._ec_loc_cache.invalidate(vid)

    # every needle still reads bit-identically; the ones on the lost
    # shard go through the DegradedReadEngine
    degraded_fids = []
    for f, want in payloads.items():
        before = serving.degraded.snapshot()["reads"]
        status, got = _get(serving, f)
        assert status == 200 and got == want, f
        if serving.degraded.snapshot()["reads"] > before:
            degraded_fids.append(f)
    assert degraded_fids, "no needle landed on the lost shard"
    snap = serving.degraded.snapshot()
    assert snap["errors"] == 0
    # exactly-k gather on a live cluster too
    assert snap["survivor_rows"] == K * snap["batches"]
    assert snap["survivor_bytes"] > 0

    # -- coalescing under concurrency -----------------------------------
    hot = degraded_fids[0]
    serving.degraded.invalidate(vid)          # force a cold batch
    serving.degraded.batch_s = 0.15
    try:
        barrier = threading.Barrier(6)
        outs, errs = [], []

        def drill():
            try:
                barrier.wait(timeout=10)
                outs.append(_get(serving, hot))
            except Exception as e:  # noqa: BLE001 - assert below
                errs.append(e)

        base = serving.degraded.snapshot()
        threads = [threading.Thread(target=drill) for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        serving.degraded.batch_s = 0.0
    assert not errs
    assert all(s == 200 and b == payloads[hot] for s, b in outs)
    snap = serving.degraded.snapshot()
    assert snap["max_batch_requests"] >= 2, \
        "concurrent reads of one lost shard never coalesced"
    assert snap["batches"] - base["batches"] < \
        snap["reads"] - base["reads"]

    # -- warm re-read: served from the slab LRU, no survivor traffic ----
    fetched = snap["survivor_bytes"]
    status, got = _get(serving, hot)
    assert status == 200 and got == payloads[hot]
    snap = serving.degraded.snapshot()
    assert snap["survivor_bytes"] == fetched
    assert snap["cache_hits"] > 0

    # -- shard (re-)mount invalidates that shard's cached slabs ---------
    # (the hook now also re-syncs the native plane and drops its slab
    # cache before the engine's — see _invalidate_reconstructions)
    assert serving.store.on_ec_mount == serving._on_ec_mount
    assert snap["cache_entries"] > 0
    own = next(iter(serving.store.find_ec_volume(vid).shards))
    serving.degraded.cache.put((vid, own, 0), b"stale" * 40)
    serving.store.unmount_ec_shards(vid, [own])
    serving.store.mount_ec_shards(vid, "dg", [own])
    # the re-registered shard's slabs are gone; the still-lost shard's
    # slabs (bit-identical to the dead shard) survive
    assert serving.degraded.cache.get((vid, own, 0)) is None
    assert serving.degraded.snapshot()["cache_entries"] > 0

    # -- naive per-read fallback: bit-identical, engine bypassed --------
    monkeypatch.setenv("SW_EC_DEGRADED_MODE", "naive")
    before = serving.degraded.snapshot()["reads"]
    status, got = _get(serving, hot)
    assert status == 200 and got == payloads[hot]
    assert serving.degraded.snapshot()["reads"] == before
    monkeypatch.delenv("SW_EC_DEGRADED_MODE")

    # -- shell status line ----------------------------------------------
    env.out = io.StringIO()
    assert run_command(env, "volume.ec.degraded")
    text = env.out.getvalue()
    assert serving.url in text
    assert "reads=" in text and "hit_ratio=" in text

    # -- fewer than k survivors: 503, not garbage ------------------------
    remaining = {}
    for vs in servers:
        ev = vs.store.find_ec_volume(vid)
        if ev is not None:
            for s in ev.shards:
                remaining.setdefault(s, vs)
    doom = [s for s in sorted(remaining) if s != lost_sid][:4]
    assert len(doom) == 4
    for s in doom:
        _lose_shards(env, remaining[s], vid, [s])
    for vs in servers:
        vs._ec_loc_cache.invalidate(vid)
        vs.degraded.invalidate(vid)
    status, _ = _get(serving, hot)
    assert status == 503
