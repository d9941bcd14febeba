"""Data-integrity observability plane (ISSUE: device-accelerated EC
scrub + telemetry-prioritized repair queue): codec.syndrome_plan's
H = [P | I_m] parity-check rows, single-error attribution via
locate_corrupt_shard, the ScrubEngine (one fused dispatch per slab on
the device path, host LUT walk below the crossover, .scrub sidecar
state, lowest-shard ownership election), the master's RepairQueue
(corruption > lost shard > at-risk holder, dedup, retry backoff,
time-to-re-protection accounting), the ec_scrub_* / repair_queue_*
metric families, and the live-cluster story: a flipped byte on disk is
detected with zero false positives, drained through
/admin/ec/scrub_repair, and the restored volume reads bit-identically
with a finite TTR on the incident."""

import io
import json
import os
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu.ec import to_ext
from seaweedfs_tpu.ec.scrub import (ScrubEngine, locate_corrupt_shard,
                                    scrub_idle_s, scrub_rate_mbps,
                                    scrub_slab_bytes)
from seaweedfs_tpu.ops import gf256
from seaweedfs_tpu.ops.codec import (NumpyCodec, dispatch_threshold,
                                     host_matmul,
                                     set_small_dispatch_override)
from seaweedfs_tpu.stats.repair_queue import PRIORITIES, RepairQueue

K, M = 10, 4
TOTAL = K + M


def _codec(backend, **kw):
    if backend == "numpy":
        return NumpyCodec(K, M)
    from seaweedfs_tpu.ops.rs_tpu import TpuCodec
    return TpuCodec(K, M, **kw)


# -- syndrome math ----------------------------------------------------------

@pytest.mark.parametrize("backend", ["numpy", "tpu"])
def test_syndrome_plan_zero_iff_consistent(backend):
    codec = _codec(backend)
    h = codec.syndrome_plan()
    assert h.shape == (M, TOTAL) and h.dtype == np.uint8
    assert h is codec.syndrome_plan()          # cached, no re-planning
    # identity block: parity shards enter the check with coefficient 1
    assert np.array_equal(h[:, K:], np.eye(M, dtype=np.uint8))
    rng = np.random.default_rng(7)
    shards = NumpyCodec(K, M).encode_to_all(
        rng.integers(0, 256, (K, 2048), dtype=np.uint8))
    syn = host_matmul(h, shards)
    assert not syn.any(), "clean codeword must have a zero syndrome"
    # one flipped byte lights up exactly that column
    shards[3, 777] ^= 0x40
    syn = host_matmul(h, shards)
    assert np.flatnonzero(syn.any(axis=0)).tolist() == [777]


@pytest.mark.parametrize("sid", [0, 3, K, TOTAL - 1])
def test_locate_corrupt_shard_data_and_parity(sid):
    h = NumpyCodec(K, M).syndrome_plan()
    e = 0x5A
    syn = np.array([gf256.MUL_TABLE[int(h[i][sid])][e]
                    for i in range(M)], dtype=np.uint8)
    assert locate_corrupt_shard(h, syn) == sid
    # the all-zero syndrome names nobody
    assert locate_corrupt_shard(h, np.zeros(M, np.uint8)) == -1


# -- engine-level harness: real shard files, fake store ---------------------

class _Shard:
    def __init__(self, path):
        self.path = path

    @property
    def size(self):
        return os.path.getsize(self.path)


class _Ev:
    def __init__(self, shards, base_name, collection="s"):
        self.shards = shards
        self.base_name = base_name
        self.collection = collection


class _Loc:
    def __init__(self, ev, vid=1):
        self.ec_volumes = {vid: ev}


class _Store:
    def __init__(self, ev, vid=1):
        self.ev = ev
        self.vid = vid
        self.locations = [_Loc(ev, vid)]

    def find_ec_volume(self, vid):
        return self.ev if vid == self.vid else None


def _seed(tmp_path, w=40_000, seed=5):
    rng = np.random.default_rng(seed)
    shards = NumpyCodec(K, M).encode_to_all(
        rng.integers(0, 256, (K, w), dtype=np.uint8))
    paths = {}
    for i in range(TOTAL):
        p = str(tmp_path / f"1{to_ext(i)}")
        shards[i].tofile(p)
        paths[i] = p
    return shards, paths


def _engine(tmp_path, codec, slab=8192, w=40_000, local=None,
            locations=None, on_finding=None, rate_mbps=0.0):
    _, paths = _seed(tmp_path, w=w)
    sids = sorted(local) if local is not None else range(TOTAL)
    ev = _Ev({i: _Shard(paths[i]) for i in sids},
             base_name=str(tmp_path / "1"))
    eng = ScrubEngine(
        store=_Store(ev), locations=locations or (lambda vid: {}),
        codec=lambda ev: codec, self_url=lambda: "me:8080",
        on_finding=on_finding, rate_mbps=rate_mbps, idle_s=0,
        slab=slab)
    return eng, ev, paths


def test_scrub_clean_volume_and_sidecar_state(tmp_path):
    eng, ev, _ = _engine(tmp_path, NumpyCodec(K, M))
    res = eng.scrub_volume(1, force=True)
    assert res["clean"] and res["corrupt_shards"] == []
    assert res["slabs"] == (40_000 + 8191) // 8192
    snap = eng.snapshot()
    assert snap["findings"] == 0 and snap["corrupt_slabs"] == 0
    assert snap["bytes_verified"] == 40_000 * TOTAL
    assert snap["host_dispatches"] == res["slabs"]    # numpy: host-only
    assert snap["device_dispatches"] == 0
    # durable per-shard state next to the shard sidecars
    with open(ev.base_name + ".scrub", encoding="utf-8") as f:
        state = json.load(f)
    assert state["passes"] == 1
    assert state["shards"]["0"]["syndrome_failures"] == 0
    assert state["shards"]["13"]["bytes_verified"] == 40_000
    eng.scrub_volume(1, force=True)
    with open(ev.base_name + ".scrub", encoding="utf-8") as f:
        assert json.load(f)["passes"] == 2


@pytest.mark.parametrize("sid", [2, K + 1])
def test_scrub_detects_single_flipped_byte(tmp_path, sid):
    findings = []
    eng, _, paths = _engine(tmp_path, NumpyCodec(K, M),
                            on_finding=lambda f: findings.append(f) or
                            True)
    off = 12_345
    with open(paths[sid], "r+b") as f:
        f.seek(off)
        b = f.read(1)
        f.seek(off)
        f.write(bytes([b[0] ^ 0x01]))
    res = eng.scrub_volume(1, force=True)
    assert not res["clean"]
    assert res["corrupt_shards"] == [sid]          # pinned to the shard
    assert res["corrupt_slabs"] == [off // 8192]   # and to the slab
    assert res["corrupt_columns"] == 1             # zero false positives
    assert len(findings) == 1
    assert findings[0]["volume"] == 1 and findings[0]["shards"] == [sid]
    snap = eng.snapshot()
    assert snap["findings"] == 1 and snap["report_failures"] == 0
    assert snap["volumes"]["1"]["corrupt_shards"] == [sid]


def test_scrub_device_path_one_fused_dispatch_per_slab(tmp_path):
    from seaweedfs_tpu.ops import telemetry
    codec = _codec("tpu", small_dispatch_bytes=1024)
    eng, _, _ = _engine(tmp_path, codec)          # slab 8192 >= crossover
    before = telemetry.STATS.snapshot()
    res = eng.scrub_volume(1, force=True)
    moved = telemetry.delta(before)
    assert res["clean"]
    # THE fused-dispatch contract: one device dispatch per slab, never
    # a per-shard or per-column fan-out
    assert moved["dispatches"] == res["slabs"]
    assert eng.snapshot()["device_dispatches"] == res["slabs"]
    assert eng.snapshot()["host_dispatches"] == 0


def test_scrub_below_crossover_stays_on_host(tmp_path):
    codec = _codec("tpu", small_dispatch_bytes=1 << 30)
    eng, _, _ = _engine(tmp_path, codec)
    res = eng.scrub_volume(1, force=True)
    assert res["clean"]
    snap = eng.snapshot()
    assert snap["host_dispatches"] == res["slabs"]
    assert snap["device_dispatches"] == 0


def test_scrub_ownership_election_and_force(tmp_path):
    # this server holds shards 1.. but the map knows shard 0 lives
    # elsewhere: the lowest-shard holder scrubs, we skip
    eng, _, _ = _engine(
        tmp_path, NumpyCodec(K, M), local=range(1, TOTAL),
        locations=lambda vid: {0: ["other:8080"]})
    res = eng.scrub_volume(1)
    assert res["skipped"] == "not_owner"
    assert eng.snapshot()["skipped_not_owner"] == 1
    # a manual trigger (POST /admin/ec/scrub) bypasses the election —
    # but shard 0 has a holder, so the stripe gathers remotely; drop
    # the holder instead and the volume is skipped as missing
    eng2, _, _ = _engine(tmp_path, NumpyCodec(K, M),
                         local=range(1, TOTAL))
    res = eng2.scrub_volume(1, force=True)
    assert res["skipped"] == "missing_shards" and res["missing"] == [0]
    assert eng2.snapshot()["skipped_missing"] == 1


def test_scrub_run_pass_summary(tmp_path):
    eng, _, _ = _engine(tmp_path, NumpyCodec(K, M))
    out = eng.run_pass(force=True)
    assert out["volumes"] == 1 and out["findings"] == 0
    snap = eng.snapshot()
    assert snap["passes"] == 1 and snap["volumes_scrubbed"] == 1
    assert snap["last_pass_mbps"] > 0


def test_scrub_env_knobs(monkeypatch):
    for env in ("SW_EC_SCRUB_RATE_MBPS", "SW_EC_SCRUB_IDLE_S",
                "SW_EC_SCRUB_SLAB_BYTES"):
        monkeypatch.delenv(env, raising=False)
    assert scrub_rate_mbps() == 8.0
    assert scrub_idle_s() == 300.0
    assert scrub_slab_bytes() == 1 << 20
    monkeypatch.setenv("SW_EC_SCRUB_RATE_MBPS", "junk")
    assert scrub_rate_mbps() == 8.0
    monkeypatch.setenv("SW_EC_SCRUB_RATE_MBPS", "0")
    assert scrub_rate_mbps() == 0.0              # unpaced
    monkeypatch.setenv("SW_EC_SCRUB_IDLE_S", "0")
    assert scrub_idle_s() == 0.0                 # loop disabled
    monkeypatch.setenv("SW_EC_SCRUB_SLAB_BYTES", "17")
    assert scrub_slab_bytes() == 4096            # floored
    # idle_s <= 0 means start() must not spawn the loop thread
    eng = ScrubEngine(store=None, locations=lambda v: {},
                      codec=lambda ev: None, self_url=lambda: "",
                      idle_s=0)
    eng.start()
    assert eng._thread is None


def test_dispatch_threshold_live_override(tmp_path):
    """SW_EC_SMALL_DISPATCH_AUTO wiring: a fitted override installed at
    runtime steers the scrub host/device decision without
    reconstructing the codec; host-only codecs never delegate."""
    codec = _codec("tpu", small_dispatch_bytes=1024)
    assert dispatch_threshold(codec) == 1024
    assert dispatch_threshold(NumpyCodec(K, M)) == 0
    set_small_dispatch_override(1 << 28)
    try:
        assert dispatch_threshold(codec) == 1 << 28
        eng, _, _ = _engine(tmp_path, codec)  # slab far below override
        res = eng.scrub_volume(1, force=True)
        snap = eng.snapshot()
        assert snap["host_dispatches"] == res["slabs"]
        assert snap["device_dispatches"] == 0
    finally:
        set_small_dispatch_override(None)
    assert dispatch_threshold(codec) == 1024


# -- repair queue -----------------------------------------------------------

def test_repair_queue_priority_dedup_backoff_ttr():
    q = RepairQueue()
    assert PRIORITIES["corruption"] < PRIORITIES["lost_shard"] \
        < PRIORITIES["at_risk_holder"]
    q.report("lost_shard", volume=1, shard=3, detected_at=100.0)
    q.report("at_risk_holder", holder="h:1", detected_at=50.0)
    q.report("corruption", volume=2, shard=5, detected_at=200.0)
    # duplicate report keeps the FIRST detection time
    q.report("corruption", volume=2, shard=5, detected_at=999.0)
    snap = q.snapshot()
    assert snap["counters"]["duplicates"] == 1
    assert len(snap["open"]) == 3
    # corruption first despite being detected last; advisory at-risk
    # incidents are never handed to the drain
    inc = q.next_incident()
    assert inc.kind == "corruption" and inc.detected_at == 200.0
    assert inc.attempts == 1
    # a failed attempt backs the incident off; the queue moves on
    q.attempt_failed(inc, "holder down")
    nxt = q.next_incident()
    assert nxt.kind == "lost_shard" and nxt.volume == 1
    q.resolve("lost_shard", volume=1, shard=3, via="rebuild")
    assert q.next_incident() is None    # corruption still backing off
    done = next(i for i in q.snapshot()["resolved_recent"]
                if i["kind"] == "lost_shard")
    assert done["time_to_re_protection_s"] > 0
    ttr = q.ttr_stats()
    assert ttr["count"] == 1 and ttr["p50_s"] == ttr["max_s"]
    depth = q.depth_by_kind()
    assert depth["corruption"] == 1 and depth["at_risk_holder"] == 1
    assert q.snapshot()["counters"]["resolved"] == 1


def test_repair_scan_ignores_mid_encode_holes(monkeypatch):
    """A streaming encode registers shards incrementally; holes in a
    stripe the master has never seen complete are not losses and must
    not fire doomed rebuilds at a half-built volume."""
    monkeypatch.setenv("SW_REPAIR_INTERVAL_S", "0")   # no loop thread
    from seaweedfs_tpu.ec import TOTAL_SHARDS
    from seaweedfs_tpu.server.master import MasterServer
    master = MasterServer(port=0, pulse_seconds=1)

    class _N:
        def __init__(self, url):
            self.url = url

    try:
        # 4 of 14 registered: mid-encode, no incidents
        master.topology.ec_shard_map[7] = \
            [[_N("h:1")] if s < 4 else [] for s in range(TOTAL_SHARDS)]
        master._repair_scan()
        assert not master.repair_queue.snapshot()["open"]
        # complete once, then a hole: now it IS a loss
        master.topology.ec_shard_map[7] = \
            [[_N("h:1")] for _ in range(TOTAL_SHARDS)]
        master._repair_scan()
        master.topology.ec_shard_map[7][5] = []
        master._repair_scan()
        open_incs = master.repair_queue.snapshot()["open"]
        assert [(i["kind"], i["volume"], i["shard"])
                for i in open_incs] == [("lost_shard", 7, 5)]
        # volume dropped entirely: incident resolves as moot
        del master.topology.ec_shard_map[7]
        master._repair_scan()
        assert not master.repair_queue.snapshot()["open"]
        assert 7 not in master._repair_seen_complete
    finally:
        master.stop()


# -- metrics mirrors --------------------------------------------------------

def test_observe_scrub_and_repair_queue_metrics(tmp_path):
    from seaweedfs_tpu.stats import metrics
    eng, _, _ = _engine(tmp_path, NumpyCodec(K, M))
    eng.run_pass(force=True)
    before = metrics.VOLUME_EC_SCRUB_COUNTER.value("slabs")
    metrics.observe_scrub(eng.snapshot())
    c = metrics.VOLUME_EC_SCRUB_COUNTER
    assert c.value("slabs") - before == 5
    assert c.value("bytes_verified") > 0
    # idempotent set_total mirror, like the other gather families
    metrics.observe_scrub(eng.snapshot())
    assert c.value("slabs") - before == 5
    render = metrics.VOLUME_SERVER_GATHER.render()
    assert 'ec_scrub_total{kind="bytes_verified"}' in render
    assert "ec_scrub_mbps" in render
    assert "ec_scrub_last_pass_unixtime" in render

    q = RepairQueue()
    q.report("corruption", volume=1, shard=2, detected_at=time.time())
    q.resolve("corruption", volume=1, shard=2, via="scrub_repair")
    metrics.observe_repair_queue(q.snapshot())
    render = metrics.MASTER_GATHER.render()
    assert 'repair_queue_incidents_total{kind="all",event="reported"} 1' \
        in render
    assert 'repair_queue_incidents_total{kind="all",event="resolved"} 1' \
        in render
    assert 'repair_queue_open{kind="corruption"} 0' in render
    assert 'repair_queue_ttr_seconds{quantile="p99"}' in render


# -- live cluster: detect -> queue -> repair -> re-protect ------------------

@pytest.fixture
def cluster3(tmp_path, monkeypatch):
    # fast repair loop, no background scrub (tests trigger explicitly),
    # unpaced scrub so the pass is instant
    monkeypatch.setenv("SW_REPAIR_INTERVAL_S", "0.3")
    monkeypatch.setenv("SW_EC_SCRUB_IDLE_S", "0")
    monkeypatch.setenv("SW_EC_SCRUB_RATE_MBPS", "0")
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    master = MasterServer(port=0, pulse_seconds=1).start()
    servers = [
        VolumeServer(port=0, directories=[str(tmp_path / f"v{i}")],
                     master_url=master.url, pulse_seconds=1,
                     max_volume_counts=[30], ec_backend="numpy").start()
        for i in range(3)]
    yield master, servers
    # master first so the repair loop stops scanning before holders vanish
    master.stop()
    for vs in servers:
        vs.stop()


def _poll(pred, what, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        got = pred()
        if got is not None:
            return got
        time.sleep(0.1)
    raise AssertionError(f"{what} not observed within {timeout}s")


def test_cluster_scrub_detect_repair_end_to_end(cluster3):
    from seaweedfs_tpu.client import operation as op
    from seaweedfs_tpu.server.http_util import (get_json, http_call,
                                                post_json)
    from seaweedfs_tpu.shell.command_env import CommandEnv, run_command
    master, servers = cluster3
    rng = np.random.default_rng(29)
    payloads = {}
    for i in range(10):
        data = rng.integers(0, 256, 120_000).astype(np.uint8).tobytes()
        fid = op.upload_data(master.url, data, filename=f"s{i}",
                             collection="sc")
        payloads[fid] = data
    by_vid = {}
    for f in payloads:
        by_vid.setdefault(int(f.split(",")[0]), []).append(f)
    vid = max(by_vid, key=lambda v: len(by_vid[v]))
    env = CommandEnv(master.url, out=io.StringIO())
    assert run_command(env, f"ec.encode -volumeId {vid}")

    def shard_map():
        out = get_json(f"http://{master.url}/cluster/ec_lookup"
                       f"?volumeId={vid}")
        got = {int(s): urls for s, urls in out["shards"].items()}
        return got if set(got) == set(range(TOTAL)) else None

    _poll(shard_map, "all shards registered")

    # scrub everything while healthy (manual trigger bypasses the
    # ownership election, so every holder verifies the full stripe —
    # local shards off disk, the rest through the remote reader stack):
    # ZERO false positives
    scrubbed = 0
    for vs in servers:
        post_json(f"http://{vs.url}/admin/ec/scrub")
        snap = get_json(f"http://{vs.url}/admin/ec/scrub_status")
        assert snap["findings"] == 0 and snap["corrupt_slabs"] == 0
        scrubbed += snap["volumes_scrubbed"]
    assert scrubbed >= len(servers)     # each holder verified the stripe
    assert not get_json(f"http://{master.url}/cluster/repairs")["open"]

    # flip ONE byte in a shard file behind the server's back
    victim = next(vs for vs in servers
                  if vs.store.find_ec_volume(vid) is not None)
    ev = victim.store.find_ec_volume(vid)
    sid = sorted(ev.shards)[0]
    path = ev.base_name + to_ext(sid)
    with open(path, "r+b") as f:
        f.seek(os.path.getsize(path) // 2)
        b = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([b[0] ^ 0x80]))

    res = post_json(f"http://{victim.url}/admin/ec/scrub?volume={vid}")
    assert not res["clean"] and res["corrupt_shards"] == [sid]

    # the finding reached the master's queue at top priority ...
    def incident():
        view = get_json(f"http://{master.url}/cluster/repairs")
        for inc in view["open"] + view["resolved_recent"]:
            if inc["kind"] == "corruption" and inc["volume"] == vid:
                return inc
        return None

    assert _poll(incident, "corruption incident")["shard"] == sid

    # ... and the repair loop quarantines + rebuilds the shard, with a
    # finite time-to-re-protection stamped on the resolved incident
    def resolved():
        view = get_json(f"http://{master.url}/cluster/repairs")
        for inc in view["resolved_recent"]:
            if inc["kind"] == "corruption" and inc["volume"] == vid:
                return inc
        return None

    inc = _poll(resolved, "corruption repair", timeout=60)
    assert inc["via"] == "scrub_repair"
    assert 0 < inc["time_to_re_protection_s"] < 120
    ttr = get_json(f"http://{master.url}/cluster/repairs"
                   )["time_to_re_protection"]
    assert ttr["count"] >= 1 and ttr["p99_s"] > 0

    # bit-identical after repair, and a re-scrub comes back clean
    for f, want in payloads.items():
        if int(f.split(",")[0]) != vid:
            continue
        got = http_call("GET", f"http://{servers[0].url}/{f}",
                        timeout=30)
        assert got == want, f

    def rescrub_clean():
        out = post_json(f"http://{victim.url}/admin/ec/scrub"
                        f"?volume={vid}")
        return True if out.get("clean") else None

    _poll(rescrub_clean, "clean re-scrub after repair", timeout=30)

    # lost shard: destroyed everywhere -> the master's scan opens a
    # lost_shard incident and the drain rebuilds + mounts it
    lose = max(shard_map())
    for holder in shard_map()[lose]:
        post_json(f"http://{holder}/admin/ec/unmount?volume={vid}"
                  f"&shards={lose}")
        post_json(f"http://{holder}/admin/ec/delete_shards"
                  f"?volume={vid}&collection=sc&shards={lose}")

    def lost_resolved():
        view = get_json(f"http://{master.url}/cluster/repairs"
                        f"?refresh=1")
        for inc in view["resolved_recent"]:
            if inc["kind"] == "lost_shard" and inc["volume"] == vid \
                    and inc["shard"] == lose:
                return inc
        return None

    inc = _poll(lost_resolved, "lost-shard repair", timeout=60)
    assert inc["time_to_re_protection_s"] > 0

    # /cluster/health folds the queue summary for the dashboard
    health = get_json(f"http://{master.url}/cluster/health")
    assert "repairs" in health
    assert health["repairs"]["time_to_re_protection"]["count"] >= 2

    # the filer proxies the integrity view for its clients
    from seaweedfs_tpu.server.filer_server import FilerServer
    filer = FilerServer(port=0, master_url=master.url).start()
    try:
        view = get_json(f"http://{filer.url}/stats/integrity")
        assert view["counters"]["resolved"] >= 2
    finally:
        filer.stop()

    # shell surfaces: queue view and per-server scrub status
    env.out = io.StringIO()
    assert run_command(env, "cluster.repairs -refresh false")
    text = env.out.getvalue()
    assert "cluster.repairs:" in text and "ttr" in text
    env.out = io.StringIO()
    assert run_command(env, "volume.ec.scrub")
    text = env.out.getvalue()
    assert victim.url in text and "passes=" in text

    # direct quarantine+rebuild of a (healthy) shard on its holder:
    # the scrub_repair route drops the local file and streams a fresh
    # copy back from the surviving k, sources self-derived when the
    # caller supplies none
    m = _poll(shard_map, "map complete after lost-shard repair")
    sid2 = next(s for s in sorted(m)
                if victim.url in m[s])
    out = post_json(f"http://{victim.url}/admin/ec/scrub_repair"
                    f"?volume={vid}&shard={sid2}&collection=sc", {})
    assert sid2 in out["rebuilt"] and sid2 in out["mounted"]
    for f in by_vid[vid]:
        got = http_call("GET", f"http://{victim.url}/{f}", timeout=30)
        assert got == payloads[f], f


def test_volume_server_status_and_sidecar_cleanup(cluster3):
    """Scrub status folds into /status and the .scrub sidecar dies
    with the volume (destroy + delete_shards both reap it)."""
    from seaweedfs_tpu.client import operation as op
    from seaweedfs_tpu.server.http_util import get_json, post_json
    from seaweedfs_tpu.shell.command_env import CommandEnv, run_command
    master, servers = cluster3
    rng = np.random.default_rng(31)
    fid = op.upload_data(master.url,
                         rng.integers(0, 256, 64_000)
                         .astype(np.uint8).tobytes(),
                         filename="x", collection="sc2")
    vid = int(fid.split(",")[0])
    env = CommandEnv(master.url, out=io.StringIO())
    assert run_command(env, f"ec.encode -volumeId {vid}")
    holder = next(vs for vs in servers
                  if vs.store.find_ec_volume(vid) is not None)
    post_json(f"http://{holder.url}/admin/ec/scrub?volume={vid}")
    ev = holder.store.find_ec_volume(vid)
    assert os.path.exists(ev.base_name + ".scrub")
    status = get_json(f"http://{holder.url}/status")
    assert "ec_scrub" in status
    assert status["ec_scrub"]["slab_bytes"] > 0
    sids = sorted(ev.shards)
    post_json(f"http://{holder.url}/admin/ec/delete_shards"
              f"?volume={vid}&collection=sc2"
              f"&shards={','.join(map(str, sids))}")
    assert not os.path.exists(ev.base_name + ".scrub")
