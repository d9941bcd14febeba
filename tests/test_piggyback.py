"""Piggybacked sub-chunk EC layout (ISSUE: cut-set-optimal single-shard
repair): the gated pairwise-coupled construction in ops/codec
(piggyback_plan / piggyback_repair_plan / piggyback_decode_plan), shard
files staying bit-identical across numpy/tpu/mesh backends and
sync/pipelined encode, plane repair downloading <= 0.55 * k * shard
for RS(10,4) while rebuilding the lost shard bit-identically, the
`/admin/ec/shard_plane_read` half-plane protocol (ranged offset= form,
416/404/400 errors), layout sidecar round-trips (.vif authoritative,
trailing .ecx tag byte fallback to the default geometry), the bounded
plan-cache LRU behind the ec_plan_cache_* families, the ec_piggyback_*
metric families, and the cross-layout coexistence drill: one flat and
one piggyback volume served by the same cluster — scrub, degraded
reads, trace repair on the flat volume, plane repair on the piggyback
one — with flat behavior byte-identical to before.
"""

import hashlib
import http.client
import json
import os
import time

import numpy as np
import pytest

from seaweedfs_tpu.ec import to_ext, transport, write_ec_files
from seaweedfs_tpu.ec.constants import SMALL_BLOCK_SIZE, TOTAL_SHARDS
from seaweedfs_tpu.ec.decoder import rebuild_ec_file_piggyback
from seaweedfs_tpu.ec.encoder import rebuild_ec_files
from seaweedfs_tpu.ec.gather import (GatherStats, LocalPlaneReader,
                                     PlaneGatherSource)
from seaweedfs_tpu.ec.layout import (ECX_TAG_PIGGYBACK, LAYOUT_FLAT,
                                     LAYOUT_PIGGYBACK, LayoutInfo,
                                     ecx_record_bytes, read_ecx_tag,
                                     volume_layout,
                                     write_layout_sidecars)
from seaweedfs_tpu.ops.codec import (NumpyCodec, pb_plane_slice,
                                     piggyback_plan,
                                     piggyback_repair_plan,
                                     piggyback_supported,
                                     plan_cache_stats)

K, M = 10, 4
# small geometry so tests stay fast: window=512 divides by alpha=32
LB, SB = 4096, 512


def _codec(backend):
    if backend == "numpy":
        return NumpyCodec(K, M)
    if backend == "tpu":
        from seaweedfs_tpu.ops.rs_tpu import TpuCodec
        return TpuCodec(K, M)
    from seaweedfs_tpu.parallel.mesh_codec import MeshCodec
    return MeshCodec(K, M)


def _seed_pb(dirpath, codec=None, nbytes=77_003, seed=11,
             pipelined=False):
    """Piggyback-layout RS(10,4) shard files for volume 1; nbytes is
    deliberately NOT divisible by the stripe so the window-padded tail
    path is always exercised. Returns (base, shard size)."""
    rng = np.random.default_rng(seed)
    base = os.path.join(str(dirpath), "1")
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, nbytes, dtype=np.uint8).tobytes())
    write_ec_files(base, codec=codec or NumpyCodec(K, M),
                   large_block=LB, small_block=SB, slab=3000,
                   pipelined=pipelined, layout="piggyback")
    os.remove(base + ".dat")
    return base, os.path.getsize(base + to_ext(0))


# -- plan layer --------------------------------------------------------------

@pytest.mark.parametrize("k,m", [(10, 4), (6, 3), (20, 4)])
def test_piggyback_plan_geometry_and_frac(k, m):
    assert piggyback_supported(k, m)
    p = piggyback_plan(k, m)
    assert p.npairs == min(k // 2, 5)
    assert p.alpha == 1 << p.npairs
    assert p.coupled == 2 * p.npairs
    # the construction's repair bandwidth for a coupled shard:
    # k-1 data helpers + 2 parities, each shipping half a shard
    assert abs(p.repair_frac - (k + 1) / (2 * k)) < 1e-12
    # same args -> the process-global LRU returns the cached object
    assert piggyback_plan(k, m) is p


def test_rs_10_4_frac_is_cut_set_grade():
    # the acceptance number: 0.55 * k * shard, vs 0.69 trace / 1.0 full
    p = piggyback_plan(K, M)
    assert p.repair_frac == 0.55
    for lost in range(p.coupled):
        rp = piggyback_repair_plan(K, M, lost)
        assert rp.frac == 0.55
        assert len(rp.helpers) == K + 1
        assert rp.matrix.shape == (p.alpha, (K + 1) * p.alpha // 2)


def test_plan_cache_lru_and_stats():
    before = plan_cache_stats()
    piggyback_plan(K, M)
    piggyback_plan(K, M)
    piggyback_repair_plan(K, M, 3)
    piggyback_repair_plan(K, M, 3)
    after = plan_cache_stats()
    assert after["events"]["hits"] > before["events"]["hits"]
    assert after["entries"]["piggyback"] >= 1
    assert after["entries"]["piggyback_repair"] >= 1
    # the export path: families land on the volume registry
    from seaweedfs_tpu.stats import metrics
    metrics.observe_plan_cache(after)
    render = metrics.VOLUME_SERVER_GATHER.render()
    assert "ec_plan_cache_events_total" in render
    assert 'ec_plan_cache_entries{cache="piggyback"}' in render


# -- encode: backend/pipeline identity, flat data bytes unchanged ------------

@pytest.mark.usefixtures("private_packed_programs")
@pytest.mark.parametrize("backend", ["numpy", "tpu", "mesh"])
def test_piggyback_encode_identity(tmp_path, backend):
    oracle_dir = tmp_path / "oracle"
    oracle_dir.mkdir()
    obase, _ = _seed_pb(oracle_dir)  # numpy sync reference
    dev_dir = tmp_path / backend
    dev_dir.mkdir()
    base, _ = _seed_pb(dev_dir, codec=_codec(backend),
                       pipelined=(backend != "numpy"))
    for i in range(TOTAL_SHARDS):
        with open(obase + to_ext(i), "rb") as f:
            want = f.read()
        with open(base + to_ext(i), "rb") as f:
            got = f.read()
        assert got == want, f"shard {i} diverged on {backend}"


def test_piggyback_data_shards_equal_flat(tmp_path):
    """Only parity rows differ between layouts — data shards are the
    same verbatim systematic split, so a layout migration never
    rewrites data bytes."""
    flat_dir = tmp_path / "flat"
    flat_dir.mkdir()
    rng = np.random.default_rng(11)
    payload = rng.integers(0, 256, 77_003, dtype=np.uint8).tobytes()
    fbase = os.path.join(str(flat_dir), "1")
    with open(fbase + ".dat", "wb") as f:
        f.write(payload)
    write_ec_files(fbase, codec=NumpyCodec(K, M), large_block=LB,
                   small_block=SB, slab=3000, pipelined=False)
    pb_dir = tmp_path / "pb"
    pb_dir.mkdir()
    pbase, _ = _seed_pb(pb_dir)
    parities_differ = 0
    for i in range(TOTAL_SHARDS):
        with open(fbase + to_ext(i), "rb") as f:
            flat = f.read()
        with open(pbase + to_ext(i), "rb") as f:
            pb = f.read()
        if i < K:
            assert flat == pb, f"data shard {i} changed under piggyback"
        elif flat != pb:
            parities_differ += 1
    assert parities_differ == M  # coupled parity actually differs


# -- plane repair: <= 0.55 * k * shard, bit-identical ------------------------

@pytest.mark.usefixtures("private_packed_programs")
@pytest.mark.parametrize("backend", ["numpy", "tpu", "mesh"])
def test_plane_repair_frac_and_bit_identity(tmp_path, backend):
    base, shard_size = _seed_pb(tmp_path)
    p = piggyback_plan(K, M)
    codec = _codec(backend)
    for lost in (0, 7):  # both halves of the coupled prefix
        with open(base + to_ext(lost), "rb") as f:
            want = f.read()
        os.remove(base + to_ext(lost))
        rplan = piggyback_repair_plan(K, M, lost)
        gstats = GatherStats()
        readers = [LocalPlaneReader(base + to_ext(h), p.alpha, SB,
                                    rplan.plane_bit, rplan.plane_side,
                                    gstats)
                   for h in rplan.helpers]
        source = PlaneGatherSource(readers, shard_size, rplan, SB,
                                   slab=2048, stats=gstats)
        stats = {}
        rebuilt = rebuild_ec_file_piggyback(
            base, lost, source, rplan, SB, codec=codec,
            slab=source.slab, stats=stats)
        assert rebuilt == [lost]
        with open(base + to_ext(lost), "rb") as f:
            assert f.read() == want, (backend, lost)
        # the acceptance bound: measured repair download, not a claim
        assert stats["repair_mode"] == "piggyback"
        assert stats["repair_helpers"] == K + 1
        assert stats["repair_bytes"] == gstats.bytes
        assert stats["repair_bytes"] <= 0.55 * K * shard_size
        assert stats["repair_bytes_frac"] == pytest.approx(0.55)
        # the helpers' half-planes are restacked into a pooled block by
        # the gather's _assemble: no row is landed or copied by a
        # reader, and the block goes back once its output is drained
        assert stats["rows_in_place"] == stats["rows_copied"] == 0
        assert len(transport._SLAB_POOL) >= 1
        if backend == "numpy" and lost == 7:
            assert stats["slab_fresh_bytes"] == 0   # shard 0's block


def test_plane_repair_failure_removes_partial(tmp_path):
    base, shard_size = _seed_pb(tmp_path)
    p = piggyback_plan(K, M)
    lost = 2
    os.remove(base + to_ext(lost))
    rplan = piggyback_repair_plan(K, M, lost)

    class Boom(LocalPlaneReader):
        def read(self, off, n, stripe_idx=0):
            if off > 0:
                raise IOError("helper died mid-stream")
            return super().read(off, n, stripe_idx)

    readers = [Boom(base + to_ext(h), p.alpha, SB, rplan.plane_bit,
                    rplan.plane_side) for h in rplan.helpers]
    source = PlaneGatherSource(readers, shard_size, rplan, SB,
                               slab=1024)
    with pytest.raises(Exception):
        rebuild_ec_file_piggyback(base, lost, source, rplan, SB,
                                  codec=NumpyCodec(K, M),
                                  slab=source.slab)
    assert not os.path.exists(base + to_ext(lost))  # all-or-nothing


# -- full coupled decode: multi-loss, parity + data --------------------------

@pytest.mark.usefixtures("private_packed_programs")
@pytest.mark.parametrize("backend", ["numpy", "tpu"])
def test_piggyback_full_rebuild_multi_loss(tmp_path, backend):
    base, _ = _seed_pb(tmp_path)
    digests = {}
    for i in range(TOTAL_SHARDS):
        with open(base + to_ext(i), "rb") as f:
            digests[i] = hashlib.sha256(f.read()).hexdigest()
    li = LayoutInfo(LAYOUT_PIGGYBACK, window=SB,
                    pairs=piggyback_plan(K, M).npairs)
    for i in (0, 7, 12):  # 2 coupled data + 1 parity
        os.remove(base + to_ext(i))
    rebuilt = rebuild_ec_files(base, codec=_codec(backend), slab=3000,
                               layout=li)
    assert sorted(rebuilt) == [0, 7, 12]
    for i in range(TOTAL_SHARDS):
        with open(base + to_ext(i), "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest() == digests[i], \
                f"shard {i} diverged after {backend} coupled decode"


# -- layout sidecars ---------------------------------------------------------

def test_sidecar_roundtrip(tmp_path):
    base = os.path.join(str(tmp_path), "7")
    record = 16
    with open(base + ".ecx", "wb") as f:
        f.write(b"\x5a" * (record * 9))  # 9 whole index records
    write_layout_sidecars(base, LAYOUT_PIGGYBACK, window=SB, pairs=5,
                          record_size=record, version=3)
    # the trailing version byte resolves to the layout name and stays
    # invisible to record arithmetic
    assert read_ecx_tag(base, record_size=record) == LAYOUT_PIGGYBACK
    with open(base + ".ecx", "rb") as f:
        raw = f.read()
    assert raw[-1] == ECX_TAG_PIGGYBACK and len(raw) == record * 9 + 1
    assert ecx_record_bytes(base + ".ecx", record) == record * 9
    # .vif is authoritative: custom window survives the round-trip
    li = volume_layout(base, K, record_size=record)
    assert li.piggyback and li.layout == LAYOUT_PIGGYBACK
    assert li.window == SB and li.pairs == 5 and li.alpha == 32
    with open(base + ".vif", encoding="utf-8") as f:
        vif = json.load(f)
    assert vif["ec_layout"] == LAYOUT_PIGGYBACK
    assert vif["version"] == 3
    # tag-only fallback (sidecar .vif lost): DEFAULT geometry
    os.remove(base + ".vif")
    li2 = volume_layout(base, K, record_size=record)
    assert li2.piggyback
    assert li2.window == SMALL_BLOCK_SIZE
    assert li2.pairs == min(K // 2, 5)
    # a flat volume (no tag, no .vif keys) stays flat
    base2 = os.path.join(str(tmp_path), "8")
    with open(base2 + ".ecx", "wb") as f:
        f.write(b"\x11" * (record * 4))
    li3 = volume_layout(base2, K, record_size=record)
    assert not li3.piggyback and li3.layout == LAYOUT_FLAT


# -- metrics export ----------------------------------------------------------

def test_observe_piggyback_metrics():
    from seaweedfs_tpu.stats import metrics
    c = metrics.VOLUME_EC_PIGGYBACK_COUNTER
    before = {k: c.value(k) for k in
              ("plane_rebuilds", "plane_bytes", "baseline_bytes")}
    metrics.observe_repair({
        "repair_mode": "piggyback", "repair_bytes": 550_000,
        "repair_baseline_bytes": 1_000_000, "repair_bytes_frac": 0.55,
        "gather_busy_s": 0.1})
    assert c.value("plane_rebuilds") - before["plane_rebuilds"] == 1
    assert c.value("plane_bytes") - before["plane_bytes"] == 550_000
    assert c.value("baseline_bytes") - before["baseline_bytes"] \
        == 1_000_000
    assert metrics.VOLUME_EC_PIGGYBACK_BYTES_FRAC_GAUGE.value() == 0.55
    render = metrics.VOLUME_SERVER_GATHER.render()
    assert 'ec_piggyback_total{kind="plane_rebuilds"}' in render
    assert "ec_piggyback_bytes_frac" in render


# -- cross-layout coexistence: live cluster drill ----------------------------

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


def _cluster_shard_files(servers, vid):
    out = {}
    for vs in servers:
        for loc in vs.store.locations:
            for fname in os.listdir(loc.directory):
                stem = fname.split(".")[0]  # "<collection>_<vid>"
                if stem != str(vid) and not stem.endswith(f"_{vid}"):
                    continue
                for sid in range(TOTAL_SHARDS):
                    if fname.endswith(to_ext(sid)):
                        out.setdefault(sid, []).append(
                            os.path.join(loc.directory, fname))
    return out


def _lose_shard(env, victim, vid, sid):
    victim.store.unmount_ec_shards(vid, [sid])
    for loc in victim.store.locations:
        for f in os.listdir(loc.directory):
            stem = f.split(".")[0]
            if (stem == str(vid) or stem.endswith(f"_{vid}")) \
                    and f.endswith(to_ext(sid)):
                os.remove(os.path.join(loc.directory, f))
    victim.heartbeat_once()
    deadline = time.time() + 10
    while time.time() < deadline:
        info = env.ec_volumes().get(str(vid)) or {"shards": {}}
        shards = {int(s): urls for s, urls in info["shards"].items()}
        if sid not in shards or victim.url not in shards[sid]:
            return shards
        time.sleep(0.2)
    raise AssertionError(f"master never dropped shard {sid}")


def _fill_volume(master_url, collection, seed):
    from seaweedfs_tpu.client import operation as op
    rng = np.random.default_rng(seed)
    fid = None
    payload = None
    for i in range(12):
        payload = rng.integers(0, 256, 150_000).astype(
            np.uint8).tobytes()
        fid = op.upload_data(master_url, payload, filename=f"c{i}",
                             collection=collection)
    return int(fid.split(",")[0]), fid, payload


def test_cluster_flat_and_piggyback_coexist(cluster3):
    import io

    from seaweedfs_tpu.client import operation as op
    from seaweedfs_tpu.server.http_util import http_call
    from seaweedfs_tpu.shell.command_env import CommandEnv, run_command
    from seaweedfs_tpu.shell.command_ec import do_ec_rebuild
    master, servers = cluster3
    env = CommandEnv(master.url, out=io.StringIO())

    # volume A: flat (default knob untouched)
    vid_a, fid_a, data_a = _fill_volume(master.url, "flat", 9)
    assert run_command(env, f"ec.encode -volumeId {vid_a}")
    # volume B: piggyback via the env knob the store reads at encode
    vid_b, fid_b, data_b = _fill_volume(master.url, "pb", 10)
    os.environ["SW_EC_LAYOUT"] = "piggyback"
    try:
        assert run_command(env, f"ec.encode -volumeId {vid_b}")
    finally:
        os.environ.pop("SW_EC_LAYOUT", None)

    files_a = _cluster_shard_files(servers, vid_a)
    files_b = _cluster_shard_files(servers, vid_b)
    assert sorted(files_a) == list(range(TOTAL_SHARDS))
    assert sorted(files_b) == list(range(TOTAL_SHARDS))
    oracle = {}
    for sid, paths in files_b.items():
        with open(paths[0], "rb") as f:
            oracle[sid] = hashlib.sha256(f.read()).hexdigest()

    # sidecars: B carries the layout version byte + .vif keys, A stays
    # bare flat — both resolved per-volume, coexisting on the same disks
    holder_b = next(vs for vs in servers
                    if vs.store.find_ec_volume(vid_b) is not None)
    ev_b = holder_b.store.find_ec_volume(vid_b)
    li_b = holder_b.store._volume_layout(ev_b.base_name)
    assert li_b.piggyback and li_b.window == SMALL_BLOCK_SIZE
    holder_a = next(vs for vs in servers
                    if vs.store.find_ec_volume(vid_a) is not None)
    ev_a = holder_a.store.find_ec_volume(vid_a)
    assert not holder_a.store._volume_layout(ev_a.base_name).piggyback

    # -- shard_plane_read protocol against a REAL holder -------------------
    some_sid = ev_b.shard_ids()[0]
    total = ev_b.shards[some_sid].size
    alpha = li_b.alpha
    wnd = li_b.window
    shard_path = ev_b.shards[some_sid].path
    with open(shard_path, "rb") as f:
        head = np.frombuffer(f.read(wnd), dtype=np.uint8)
    conn = http.client.HTTPConnection("127.0.0.1", holder_b.port)
    try:
        # ranged half-plane read: offset= + geometry -> plane bytes
        conn.request("POST", f"/admin/ec/shard_plane_read?volume={vid_b}"
                             f"&shard={some_sid}&offset=0&size={wnd}"
                             f"&alpha={alpha}&window={wnd}&bit=2&side=1")
        resp = conn.getresponse()
        body = resp.read()
        assert resp.status == 200
        assert resp.getheader("X-Plane-Alpha") == str(alpha)
        expect = pb_plane_slice(head, alpha, wnd, 2, 1)
        assert body == expect.tobytes()
        # beyond the shard -> 416
        conn.request("POST", f"/admin/ec/shard_plane_read?volume={vid_b}"
                             f"&shard={some_sid}&offset={total}"
                             f"&size={wnd}&alpha={alpha}&window={wnd}"
                             f"&bit=0&side=0")
        resp = conn.getresponse()
        resp.read()
        assert resp.status == 416
        # bad geometry (alpha not a power of two) -> 400
        conn.request("POST", f"/admin/ec/shard_plane_read?volume={vid_b}"
                             f"&shard={some_sid}&offset=0&size={wnd}"
                             f"&alpha=31&window={wnd}&bit=0&side=0")
        resp = conn.getresponse()
        resp.read()
        assert resp.status == 400
        # missing params -> 400
        conn.request("POST", f"/admin/ec/shard_plane_read?volume={vid_b}"
                             f"&shard={some_sid}")
        resp = conn.getresponse()
        resp.read()
        assert resp.status == 400
        # a shard this holder does not have -> 404
        not_held = next(s for s in range(TOTAL_SHARDS)
                        if s not in ev_b.shards)
        conn.request("POST", f"/admin/ec/shard_plane_read?volume={vid_b}"
                             f"&shard={not_held}&offset=0&size={wnd}"
                             f"&alpha={alpha}&window={wnd}&bit=0&side=0")
        resp = conn.getresponse()
        resp.read()
        assert resp.status == 404
    finally:
        conn.close()

    # -- scrub walks BOTH layouts clean in one drill -----------------------
    res_b = holder_b.scrub.scrub_volume(vid_b, force=True)
    assert res_b["clean"], res_b
    res_a = holder_a.scrub.scrub_volume(vid_a, force=True)
    assert res_a["clean"], res_a

    # -- single-shard loss on the piggyback volume -------------------------
    victim = next(vs for vs in servers
                  if vs.store.find_ec_volume(vid_b) is not None
                  and any(s < K for s in
                          vs.store.find_ec_volume(vid_b).shard_ids()))
    lost = next(s for s in victim.store.find_ec_volume(vid_b)
                .shard_ids() if s < K)
    shards = _lose_shard(env, victim, vid_b, lost)
    # degraded read serves through the coupled decode while the shard
    # is still missing
    assert http_call("GET",
                     f"http://{servers[0].url}/{fid_b}") == data_b
    # forcing the flat-only strategy on a piggyback volume is a loud
    # error, not silent wrong math (asserted at the rebuilder's admin
    # route)
    from seaweedfs_tpu.server.http_util import HttpError, post_json
    rebuilder = next(vs.url for vs in servers if vs.url != victim.url)
    with pytest.raises(HttpError):
        post_json(f"http://{rebuilder}/admin/ec/rebuild"
                  f"?volume={vid_b}&collection=pb",
                  {"sources": {str(s): u for s, u in shards.items()},
                   "repair": "trace"})
    # `-repair auto` picks the plane repair and hits the 0.55 floor
    timings = {}
    do_ec_rebuild(env, vid_b, "pb", shards, [lost], timings=timings,
                  repair="auto")
    assert timings["repair_mode"] == "piggyback"
    assert "repair_fallback" not in timings
    assert timings["repair_helpers"] == K + 1
    assert timings["repair_bytes"] <= 0.55 * K * \
        timings["repair_baseline_bytes"] / K
    assert timings["repair_bytes_frac"] == pytest.approx(0.55)
    files_after = _cluster_shard_files(servers, vid_b)
    assert sorted(files_after) == list(range(TOTAL_SHARDS))
    for sid, paths in files_after.items():
        with open(paths[0], "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest() == oracle[sid], \
                f"shard {sid} diverged after plane repair"

    # -- flat volume: trace repair still picked, bytes unchanged -----------
    victim_a = next(vs for vs in servers
                    if vs.store.find_ec_volume(vid_a) is not None)
    lost_a = victim_a.store.find_ec_volume(vid_a).shard_ids()[0]
    shards_a = _lose_shard(env, victim_a, vid_a, lost_a)
    timings_a = {}
    do_ec_rebuild(env, vid_a, "flat", shards_a, [lost_a],
                  timings=timings_a, repair="auto")
    assert timings_a["repair_mode"] == "trace"
    assert op.read_file(master.url, fid_a) == data_a

    # the new families are on the scrape after a plane repair
    scrape = http_call("GET", f"http://{rebuilder}/metrics").decode()
    assert "ec_piggyback_total" in scrape
    assert "ec_plan_cache_entries" in scrape
