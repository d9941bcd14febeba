"""Four volume servers, a chip each: a collection coded and re-protected
with one volume in flight a chip, decoded apart from where it is stored.

The scenario on the CPU (tests/conftest.py offers eight host devices):
master + four volume servers with `ec_backend="tpu-own"`, which take the
process's local devices in turn; one volume of ~2 MiB uploaded and cloned
under four further ids (hard links, mounted: what
benchmarks/kinds/rebuild_fanned.py does at 8 x 1 GiB); `ec.encode
-collection`; then each server in turn loses every shard it holds and one
`ec.rebuild -collection` puts them back. The shell keeps one volume in
flight per distinct chip and, where the server placement names for the
rebuilt shards has its chip taken, has another server gather and decode
and send the shards to it. The tests read what that left: files, replies,
spans, counters, and the order of the shell's calls.
"""

import hashlib
import io
import os
import shutil
import threading

import numpy as np
import pytest

from seaweedfs_tpu.ops import device_stats, telemetry
from seaweedfs_tpu.shell import command_ec
from seaweedfs_tpu.util import tracing

from conftest import wait_until

COLLECTION = "fan"
VOLUMES = 5
K, M, TOTAL = 10, 4, 14


def sha(path: str) -> str:
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def reference_shas(dat: str, k: int, m: int, tmp) -> list:
    """sha256 of the k + m shards of `dat` by the numpy codec."""
    from seaweedfs_tpu.ec.constants import to_ext
    from seaweedfs_tpu.ec.encoder import write_ec_files
    from seaweedfs_tpu.ops import get_codec
    base = str(tmp / f"plain{k}_{m}")
    shutil.copy(dat, base + ".dat")
    write_ec_files(base, codec=get_codec(k, m, backend="numpy"))
    return [sha(base + to_ext(sid)) for sid in range(k + m)]


class Cluster:
    """Master + volume servers in this process, and the shell's env
    with every node call and every stats reply kept."""

    def __init__(self, tmp, backend: str, servers: int = 4,
                 collection: str = COLLECTION):
        from seaweedfs_tpu.server.master import MasterServer
        from seaweedfs_tpu.server.volume_server import VolumeServer
        from seaweedfs_tpu.shell.command_env import CommandEnv
        import seaweedfs_tpu.shell  # noqa: F401 - registers the commands
        self.collection = collection
        # the master's own repair loop would put lost shards back before
        # the shell's ec.rebuild does
        self._was = os.environ.get("SW_REPAIR_INTERVAL_S")
        os.environ["SW_REPAIR_INTERVAL_S"] = "0"
        self.master = MasterServer(
            port=0, volume_size_limit_mb=4, pulse_seconds=1,
            growth_counts={1: 1}).start()
        self.dirs = [str(tmp / f"v{i}") for i in range(servers)]
        self.servers = [VolumeServer(
            port=0, directories=[d], master_url=self.master.url,
            pulse_seconds=1, max_volume_counts=[20],
            ec_backend=backend).start() for d in self.dirs]
        self.urls = [vs.url for vs in self.servers]
        cluster = self

        class EveryCall(CommandEnv):
            def node_post(self, node, path, timeout=None, body=None):
                entry = {"node": node, "route": path.split("?")[0],
                         "path": path, "thread": threading.get_ident()}
                cluster.calls.append(entry)
                got = super().node_post(node, path, timeout, body)
                if isinstance(got, dict) and got.get("stats"):
                    cluster.replies.append((entry["route"], node,
                                            got["stats"]))
                return got

        self.calls, self.replies = [], []
        self.env = EveryCall(self.master.url, out=io.StringIO())
        assert wait_until(
            lambda: len(self.env.cluster_nodes()) == servers)

    def stop(self):
        for vs in self.servers:
            vs.stop()
        self.master.stop()
        if self._was is None:
            os.environ.pop("SW_REPAIR_INTERVAL_S", None)
        else:
            os.environ["SW_REPAIR_INTERVAL_S"] = self._was

    def shell(self, name, *args) -> dict:
        from seaweedfs_tpu.shell.command_env import COMMANDS
        self.calls, self.replies = [], []
        tel = telemetry.STATS.snapshot()
        dev = device_stats.DEVICE_STATS.snapshot()["dispatches"]
        COMMANDS[name](self.env, list(args))
        now = telemetry.STATS.snapshot()
        dev_now = device_stats.DEVICE_STATS.snapshot()["dispatches"]
        return {"calls": self.calls, "replies": self.replies,
                "out": self.env.out.getvalue(),
                "counters": {f: now[f] - tel[f] for f in (
                    "rebuild_delivered_bytes", "rebuild_local_bytes",
                    "dispatches")},
                "jit": {e: n - dev.get(e, 0) for e, n in dev_now.items()
                        if n - dev.get(e, 0)}}

    def status(self, vids) -> dict:
        known = self.env.ec_volumes()
        return {vid: {int(s): urls for s, urls in
                      (known.get(str(vid)) or {}).get("shards", {}).items()
                      if urls} for vid in vids}

    def whole(self, vids, total=TOTAL) -> bool:
        return all(sorted(shards) == list(range(total))
                   for shards in self.status(vids).values())

    def above(self, vids, m=M) -> int:
        count = 0
        for shards in self.status(vids).values():
            held = {}
            for urls in shards.values():
                for url in urls:
                    held[url] = held.get(url, 0) + 1
            count += sum(n > m for n in held.values())
        return count

    def shard_files(self, vids, total=TOTAL) -> dict:
        """(vid, sid) -> (server, sha256); a shard twice fails."""
        from seaweedfs_tpu.ec.constants import to_ext
        found = {}
        for vid in vids:
            for n, d in enumerate(self.dirs):
                for sid in range(total):
                    path = os.path.join(
                        d, f"{self.collection}_{vid}" + to_ext(sid))
                    if os.path.exists(path):
                        assert (vid, sid) not in found, "a shard twice"
                        found[vid, sid] = (n, sha(path))
        return found

    def leftovers(self) -> list:
        """`.part` stages anywhere, and sidecars of a volume on a
        server that has none of its shards."""
        out = []
        for d in self.dirs:
            names = os.listdir(d)
            out += [os.path.join(d, n) for n in names
                    if n.endswith(".part")]
            for n in names:
                stem, ext = os.path.splitext(n)
                if ext in (".ecx", ".vif", ".ecj") and not any(
                        o.startswith(stem + ".ec") and o[-2:].isdigit()
                        for o in names):
                    out.append(os.path.join(d, n))
        return out

    def lose(self, vids, server: int) -> dict:
        from seaweedfs_tpu.server.http_util import post_json
        url = self.urls[server]
        lost = {vid: sorted(s for s, urls in shards.items() if url in urls)
                for vid, shards in self.status(vids).items()}
        for vid, sids in lost.items():
            if sids:
                post_json(f"http://{url}/admin/ec/delete_shards?volume="
                          f"{vid}&collection={self.collection}"
                          f"&shards={','.join(map(str, sids))}")
        assert wait_until(lambda: not any(
            url in urls for shards in self.status(vids).values()
            for urls in shards.values()))
        return lost

    def upload(self, nbytes: int = 2 << 20, needle: int = 64 << 10):
        """One volume of seeded needles; returns (vid, kept base)."""
        from seaweedfs_tpu.client import operation as op
        a = op.assign(self.master.url, collection=self.collection)
        vid = int(a["fid"].split(",")[0])
        rng = np.random.default_rng(45)
        for i in range(nbytes // needle):
            op.upload(a["url"], f"{vid},{i + 1:x}00000001", rng.integers(
                0, 256, needle + (i % 17 - 8) * 16).astype(
                    np.uint8).tobytes(), filename=f"f{i}")
        home = next(d for d in self.dirs if os.path.exists(
            os.path.join(d, f"{self.collection}_{vid}.dat")))
        return vid, os.path.join(home, f"{self.collection}_{vid}")

    def clone(self, kept: str, vid: int, server: int):
        from seaweedfs_tpu.server.http_util import post_json
        to = os.path.join(self.dirs[server], f"{self.collection}_{vid}")
        for ext in (".dat", ".idx"):
            os.link(kept + ext, to + ext)
        assert post_json(f"http://{self.urls[server]}/admin/volume/mount"
                         f"?volume={vid}").get("mounted")


def sealed_collection(cluster, tmp, volumes: int):
    """One uploaded volume and `volumes - 1` clones over the servers in
    turn, all at the master at full size; returns (vids, kept base)."""
    vid, base = cluster.upload()
    kept = str(tmp / "kept")
    for ext in (".dat", ".idx"):
        shutil.copy(base + ext, kept + ext)
    dat_bytes = os.path.getsize(kept + ".dat")
    vids = [vid + n for n in range(volumes)]
    for n, clone in enumerate(vids[1:]):
        cluster.clone(kept, clone, n % len(cluster.servers))
    assert wait_until(lambda: all(
        any(r.get("size") == dat_bytes for r in
            cluster.env.all_volumes().get(str(v), [])) for v in vids))
    return vids, kept


@pytest.fixture(scope="module")
def fanned(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("fanned")
    cluster = Cluster(tmp, "tpu-own")
    spans = []
    out = {"cluster": cluster, "urls": cluster.urls, "spans": spans}
    try:
        out["nodes"] = cluster.env.cluster_nodes()
        from seaweedfs_tpu.server.http_util import get_json
        out["status"] = [get_json(f"http://{u}/status")
                         for u in cluster.urls]
        out["devices"] = [get_json(f"http://{u}/admin/devices")
                          for u in cluster.urls]
        vids, kept = sealed_collection(cluster, tmp, VOLUMES)
        out.update(vids=vids, kept=kept)
        out["want"] = reference_shas(kept + ".dat", K, M, tmp)
        tracing.add_finish_hook(spans.append)
        out["encode"] = cluster.shell(
            "ec.encode", "-collection", COLLECTION,
            "-fullPercent", "0.45", "-quietFor", "0")
        assert wait_until(lambda: cluster.whole(vids))
        out["above_m"] = [cluster.above(vids)]
        out["encoded"] = cluster.shard_files(vids)
        out["losses"] = []
        for server in range(4):
            lost = cluster.lose(vids, server)
            got = cluster.shell("ec.rebuild", "-collection", COLLECTION)
            # what the command's return promises, before anything waits
            got["files"] = cluster.shard_files(vids)
            got["leftovers"] = cluster.leftovers()
            assert wait_until(lambda: cluster.whole(vids))
            out["above_m"].append(cluster.above(vids))
            out["losses"].append({"server": server, "lost": lost, **got,
                                  "holders": cluster.status(vids)})
        tracing.remove_finish_hook(spans.append)
    except BaseException:
        tracing.remove_finish_hook(spans.append)
        cluster.stop()
        raise
    yield out
    cluster.stop()


# -- a server has a chip ------------------------------------------------------

def test_four_servers_hold_four_distinct_devices(fanned):
    devices = [s["device"] for s in fanned["status"]]
    assert all(d["platform"] == "cpu" and d["kind"] for d in devices)
    assert len({d["index"] for d in devices}) == 4
    assert len({d["chip"] for d in devices}) == 4
    # the master passes them on, the shell reads its lanes from them
    by_url = {n["url"]: n["device"] for n in fanned["nodes"]}
    assert [by_url[u] for u in fanned["urls"]] == devices
    chips = command_ec.chips_of(fanned["nodes"])
    assert len(set(chips.values())) == 4
    # the inventory is the process's, `own` the store's entry in it
    for dev, got in zip(devices, fanned["devices"]):
        assert got["own"] == dev
        assert got["inventory"]["initialized"]
        assert dev["index"] in [d["id"] for d in
                                got["inventory"]["devices"]]


def test_every_codec_of_a_store_computes_on_its_chip(fanned):
    store = fanned["cluster"].servers[2].store
    want = store.device()["index"]
    for geometry in ((10, 4), (6, 3)):
        codec = store.codec_for(*geometry)
        assert codec.backend == "tpu-own"
        index, device = codec.device
        assert index == want and device.id == want
        fn, const, put = codec.device_fn(codec.matrix[codec.k:], 512)
        assert const.devices() == {device}
        assert put(np.zeros((codec.k, 512), np.uint8)).devices() == {device}
        assert fn.device == want


# -- the collection in lanes --------------------------------------------------

def test_the_encode_codes_every_volume_bit_identically(fanned):
    assert len(fanned["encoded"]) == VOLUMES * TOTAL
    for (vid, sid), (_, got) in fanned["encoded"].items():
        assert got == fanned["want"][sid], (vid, sid)
    assert fanned["above_m"][0] == 0
    replies = [r for route, _, r in fanned["encode"]["replies"]
               if route == "/admin/ec/generate"]
    assert len(replies) == VOLUMES
    # the volumes lie on four servers, so on four chips
    devs = {e for e in fanned["encode"]["jit"] if e.startswith("dev")}
    assert len(devs) >= 2
    whole = [s for s in fanned["spans"]
             if s["name"] == "ec.encode.collection"]
    assert len(whole) == 1 and whole[0]["tags"]["volumes"] == VOLUMES


def _overlapping(spans: list) -> int:
    """The most of `spans` open at one moment."""
    edges = sorted([(s["start"], 1) for s in spans] +
                   [(s["start"] + s["duration_s"], -1) for s in spans],
                   key=lambda e: (e[0], e[1]))
    most = now = 0
    for _, step in edges:
        now += step
        most = max(most, now)
    return most


def _volume_spans(fanned, name: str, command: dict) -> list:
    whole = [s for s in fanned["spans"]
             if s["name"] == name + ".collection"]
    tids = {s["trace_id"] for s in whole}
    return [s for s in fanned["spans"] if s["name"] == name
            and s["tags"].get("command") in tids]


def test_volumes_are_in_flight_together_one_a_chip(fanned):
    for name in ("ec.encode", "ec.rebuild"):
        spans = _volume_spans(fanned, name, fanned)
        assert len(spans) == VOLUMES * (1 if name == "ec.encode" else 4)
        assert 2 <= _overlapping(spans) <= 4
    # never two on one chip: the volume spans of one chip do not overlap
    by_chip = {}
    for s in _volume_spans(fanned, "ec.rebuild", fanned):
        by_chip.setdefault(s["tags"]["device"], []).append(s)
    assert len(by_chip) >= 2 and "" not in by_chip
    assert all(_overlapping(group) == 1 for group in by_chip.values())


@pytest.mark.parametrize("server", range(4))
def test_a_lost_server_is_rebuilt_on_it_by_other_chips(fanned, server):
    loss = fanned["losses"][server]
    assert all(len(sids) in (3, 4) for sids in loss["lost"].values())
    # every shard back, bit-identical, the moment the command returned,
    # and the lost ones on the emptied server (placement's choice)
    assert {key: got for key, (_, got) in loss["files"].items()} == \
        {key: got for key, (_, got) in fanned["encoded"].items()}
    for vid, sids in loss["lost"].items():
        assert all(loss["files"][vid, s][0] == server for s in sids)
        assert all(loss["holders"][vid][s] == [fanned["urls"][server]]
                   for s in sids)
    assert loss["leftovers"] == []
    replies = [(node, r) for route, node, r in loss["replies"]
               if route == "/admin/ec/rebuild"]
    assert len(replies) == VOLUMES
    assert all(r["repair_mode"] == "full" and
               r["operand"] == [len(r["lost"]), K] for _, r in replies)
    # at least one volume decoded off its target, and delivered to it
    target = fanned["urls"][server]
    off = [(node, r) for node, r in replies if node != target]
    assert off and all(r["delivered_to"] == target and
                       r["phases"]["deliver"] > 0 for _, r in off)
    assert all("delivered_to" not in r for node, r in replies
               if node == target)
    assert len({e for e in loss["jit"] if e.startswith("dev")}) >= 2
    assert sum(n for e, n in loss["jit"].items() if e.startswith("dev")) \
        == loss["jit"]["rs_tpu._packed_fn"] == \
        loss["counters"]["dispatches"]
    # the bytes by where they went
    shard = os.path.getsize(os.path.join(
        fanned["cluster"].dirs[server], f"{COLLECTION}_{fanned['vids'][0]}"
        f".ec{loss['lost'][fanned['vids'][0]][0]:02d}"))
    assert loss["counters"]["rebuild_delivered_bytes"] == shard * sum(
        len(r["lost"]) for _, r in off)
    assert loss["counters"]["rebuild_delivered_bytes"] + \
        loss["counters"]["rebuild_local_bytes"] == shard * sum(
            len(sids) for sids in loss["lost"].values())


def test_no_holder_is_above_m_after_any_command(fanned):
    assert fanned["above_m"] == [0] * 5


def test_the_spans_say_who_computed_for_whom(fanned):
    spans = _volume_spans(fanned, "ec.rebuild", fanned)
    chips = command_ec.chips_of(fanned["nodes"])
    for s in spans:
        tags = s["tags"]
        assert tags["target"] in fanned["urls"]
        assert tags["device"] == chips[tags["computed_on"]]
    off = [s for s in spans
           if s["tags"]["computed_on"] != s["tags"]["target"]]
    assert off
    # the deliver stage hangs under the computing node's stream
    streams = {s["span_id"]: s for s in fanned["spans"]
               if s["name"] == "ec.rebuild.stream"}
    delivers = [s for s in fanned["spans"]
                if s["name"] == "ec.rebuild.deliver"]
    assert delivers and all(d["parent_id"] in streams for d in delivers)
    assert {streams[d["parent_id"]]["trace_id"] for d in delivers} == \
        {s["trace_id"] for s in off}
    assert all("deliver_to" in streams[d["parent_id"]]["tags"]
               for d in delivers)
    # a delivered volume writes nothing locally
    writes = {s["trace_id"] for s in fanned["spans"]
              if s["name"] == "ec.rebuild.write"}
    assert not writes & {s["trace_id"] for s in off}
    whole = [s for s in fanned["spans"]
             if s["name"] == "ec.rebuild.collection"]
    assert [w["tags"]["volumes"] for w in whole] == [VOLUMES] * 4


# -- one volume, and other geometries -----------------------------------------

def test_one_volume_by_id_is_rebuilt_on_its_target(fanned, tmp_path):
    """`ec.encode -volumeId` and an `ec.rebuild` of one volume: there is
    nothing to fan, the target computes, as it always did."""
    cluster = fanned["cluster"]
    vid = max(fanned["vids"]) + 10
    cluster.clone(fanned["kept"], vid, 1)
    assert wait_until(
        lambda: str(vid) in cluster.env.all_volumes())
    cluster.shell("ec.encode", "-volumeId", str(vid))
    assert wait_until(lambda: cluster.whole([vid]))
    lost = cluster.lose([vid], 2)[vid]
    got = cluster.shell("ec.rebuild", "-collection", COLLECTION)
    rebuilds = [c for c in got["calls"] if c["route"] == "/admin/ec/rebuild"]
    assert [c["node"] for c in rebuilds] == [fanned["urls"][2]]
    files = cluster.shard_files([vid])
    assert all(files[vid, s] == (2, fanned["want"][s]) for s in lost)
    assert got["counters"]["rebuild_delivered_bytes"] == 0
    assert cluster.leftovers() == []


def test_rs_6_3_is_decoded_on_another_chip_and_delivered(fanned, tmp_path):
    """A volume of another geometry through the same path: its codec is
    the store's own chip's too, and its rebuilt shards are delivered."""
    cluster = fanned["cluster"]
    vid = max(fanned["vids"]) + 20
    cluster.clone(fanned["kept"], vid, 0)
    assert wait_until(lambda: str(vid) in cluster.env.all_volumes())
    cluster.shell("ec.encode", "-volumeId", str(vid), "-geometry", "6,3")
    assert wait_until(lambda: cluster.whole([vid], 9))
    want = reference_shas(fanned["kept"] + ".dat", 6, 3, tmp_path)
    holders = cluster.status([vid])[vid]
    target = max(range(4), key=lambda n: sum(
        cluster.urls[n] in urls for urls in holders.values()))
    lost = cluster.lose([vid], target)[vid]
    assert len(lost) >= 2
    shards = cluster.status([vid])[vid]
    node = cluster.urls[(target + 1) % 4]
    before = device_stats.DEVICE_STATS.snapshot()["dispatches"]
    timings = {}
    command_ec.do_ec_rebuild(
        cluster.env, vid, COLLECTION, shards, lost, timings=timings,
        placement=(node, cluster.urls[target]))
    assert timings["delivered_to"] == cluster.urls[target]
    assert (timings["k"], timings["m"]) == (6, 3)
    files = cluster.shard_files([vid], 9)
    assert all(files[vid, s] == (target, want[s]) for s in lost)
    assert all(files[vid, s][1] == want[s] for s in range(9))
    assert wait_until(lambda: cluster.whole([vid], 9))
    assert cluster.above([vid], 3) == 0
    now = device_stats.DEVICE_STATS.snapshot()["dispatches"]
    moved = {e for e, n in now.items() if n - before.get(e, 0)
             and e.startswith("dev")}
    assert moved == {f"dev{cluster.servers[(target + 1) % 4].store.device()['index']}"}
    assert cluster.leftovers() == []


def test_a_computing_node_that_dies_leaves_nothing_partial(
        fanned, monkeypatch):
    """The node that decodes for another fails once its first rows are
    on the target, and (being dead) aborts nothing: the shell clears
    the target's partial shards and has the target rebuild the volume
    itself."""
    from seaweedfs_tpu.ec import spread
    cluster = fanned["cluster"]
    vid = fanned["vids"][0]
    lost = cluster.lose([vid], 3)[vid]
    target, node = cluster.urls[3], cluster.urls[0]
    staged = []

    def finish(self):
        # every row is queued; what the lanes sent is on the target
        for w in self.workers:
            self._put(w, spread._SENTINEL)
        for w in self.workers:
            w.join()
        staged.extend(p for p in os.listdir(cluster.dirs[3])
                      if p.endswith(".part"))
        raise RuntimeError("the computing node is gone")

    monkeypatch.setattr(spread.RebuiltShardSink, "finish", finish)
    monkeypatch.setattr(spread.RebuiltShardSink, "abort",
                        lambda self: None)
    spans = []
    tracing.add_finish_hook(spans.append)
    try:
        command_ec.do_ec_rebuild(
            cluster.env, vid, COLLECTION, cluster.status([vid])[vid], lost,
            placement=(node, target))
    finally:
        tracing.remove_finish_hook(spans.append)
    assert len(staged) == len(lost)      # it had got that far
    assert cluster.leftovers() == []
    files = cluster.shard_files([vid])
    assert all(files[vid, s] == (3, fanned["want"][s]) for s in lost)
    assert wait_until(lambda: cluster.whole([vid]))
    root = [s for s in spans if s["name"] == "ec.rebuild"][-1]
    assert root["tags"]["fallback"] == "target"
    assert root["tags"]["computed_on"] == root["tags"]["target"] == target
    assert "on " + target in cluster.env.out.getvalue()


def test_a_piggyback_volume_is_not_delivered(fanned):
    """The store refuses the route it was not given: the shell's retry
    on the target is what a piggyback volume gets."""
    from seaweedfs_tpu.storage.volume import VolumeError
    from seaweedfs_tpu.ec.layout import LayoutInfo
    store = fanned["cluster"].servers[0].store
    vid = fanned["vids"][1]
    shards = fanned["cluster"].status([vid])[vid]
    mine = fanned["urls"][0]
    sources = {s: urls for s, urls in shards.items() if mine not in urls}
    for s in sorted(sources)[:2]:
        del sources[s]      # as if these two were the lost ones
    was = store._volume_layout
    store._volume_layout = lambda base: LayoutInfo(
        layout="piggyback", window=was(base).window, pairs=5)
    try:
        with pytest.raises(VolumeError, match="flat full gather"):
            store.rebuild_ec_shards_streaming(
                vid, COLLECTION, sources=sources,
                deliver_to=fanned["urls"][1])
    finally:
        store._volume_layout = was
    assert fanned["cluster"].leftovers() == []


# -- one chip: what the commands always did -----------------------------------

@pytest.fixture(params=["tpu", "numpy"])
def one_chip(request, tmp_path):
    cluster = Cluster(tmp_path, request.param, collection="one")
    try:
        vids, kept = sealed_collection(cluster, tmp_path, 3)
        yield cluster, vids, kept
    finally:
        cluster.stop()


def test_on_one_chip_a_rebuild_walks_its_volumes_an_encode_its_servers(
        one_chip):
    cluster, vids, kept = one_chip
    nodes = cluster.env.cluster_nodes()
    assert all("device" not in n for n in nodes)
    assert set(command_ec.chips_of(nodes).values()) == {""}
    home = {int(v): r[0]["url"]
            for v, r in cluster.env.all_volumes().items()}
    enc = cluster.shell("ec.encode", "-collection", "one",
                        "-fullPercent", "0.45", "-quietFor", "0")
    assert wait_until(lambda: cluster.whole(vids))
    me = threading.get_ident()
    # an encode's lanes are the servers the .dat files lie on, whatever
    # chip they share (tests/test_encode_lanes.py has the single server,
    # on the caller's thread): each volume whole before the next of ITS
    # server begins, each by today's calls in today's order
    assert len({home[v] for v in vids}) > 1
    assert me not in {c["thread"] for c in enc["calls"]}
    generated = [int(c["path"].split("volume=")[1].split("&")[0])
                 for c in enc["calls"]
                 if c["route"] == "/admin/ec/generate"]
    assert sorted(generated) == sorted(vids)
    per_volume, per_server = {}, {}
    for c in enc["calls"]:
        vid = int(c["path"].split("volume=")[1].split("&")[0])
        per_volume.setdefault(vid, []).append(c["route"])
        walked = per_server.setdefault(home[vid], [])
        if not walked or walked[-1] != vid:
            walked.append(vid)
    for walked in per_server.values():      # never interleaved
        assert len(walked) == len(set(walked))
    for routes in per_volume.values():
        assert routes[0] == "/admin/volume/readonly"
        assert routes[1] == "/admin/ec/generate"
        assert routes[-1] == "/admin/delete_volume"
    lost = cluster.lose(vids, 1)
    ec_order = [int(v) for v in cluster.env.ec_volumes()]
    reb = cluster.shell("ec.rebuild", "-collection", "one")
    assert [(c["route"], c["node"]) for c in reb["calls"]] == [
        step for v in ec_order if lost.get(v) for step in (
            ("/admin/ec/rebuild", cluster.urls[1]),
            ("/admin/ec/mount", cluster.urls[1]))]
    assert {c["thread"] for c in reb["calls"]} == {me}
    bodies_have_no_target = all(
        "delivered_to" not in r for _, _, r in reb["replies"])
    assert bodies_have_no_target
    assert reb["counters"]["rebuild_delivered_bytes"] == 0
    assert reb["counters"]["rebuild_local_bytes"] > 0
    assert not [e for e in reb["jit"] if e.startswith("dev")]
    assert wait_until(lambda: cluster.whole(vids))
    assert cluster.above(vids) == 0 and cluster.leftovers() == []


# -- the scheduler alone ------------------------------------------------------

def _nodes(*chips, free=10.0):
    return [{"url": f"n{i}", "free": free,
             **({"device": {"chip": c}} if c else {})}
            for i, c in enumerate(chips)]


@pytest.mark.parametrize("chips,lanes", [
    (("a", "b", "c", "d"), 4),      # a chip each
    (("a", "a", "b", "b"), 2),      # two servers a chip
    (("", "", "", ""), 1),          # nobody names one: they share it
    (("a", "a", "a"), 1),           # one process on `tpu-own`, one chip
    (("a", "", ""), 2),             # the unnamed share one beside it
])
def test_the_lanes_are_the_clusters_distinct_chips(chips, lanes):
    assert len(set(command_ec.chips_of(_nodes(*chips)).values())) == lanes


@pytest.mark.parametrize("busy,missing,want", [
    # the target's chip is free: it computes for itself
    ({"a": 0, "b": 1, "c": 0, "d": 0}, [0, 4, 8, 12], ("n0", "n0")),
    # taken: the free chip whose node holds most survivors
    ({"a": 1, "b": 1, "c": 0, "d": 0}, [0, 4, 8, 12], ("n2", "n0")),
    ({"a": 1, "b": 0, "c": 0, "d": 0}, [0, 4, 8, 12], ("n1", "n0")),
    # a single shard's routes read and write on the target: it waits
    ({"a": 1, "b": 0, "c": 0, "d": 0}, [0], None),
    ({"a": 0, "b": 1, "c": 1, "d": 1}, [0], ("n0", "n0")),
])
def test_where_a_volume_is_decoded(busy, missing, want):
    nodes = _nodes("a", "b", "c", "d")
    # n0 lost its shards; n1 and n2 hold four, n3 three; n2 before n3
    shards = {s: [f"n{1 + s % 3}"] for s in range(14) if s not in missing}
    shards = {s: urls for s, urls in shards.items()}
    held = {}
    for urls in shards.values():
        held[urls[0]] = held.get(urls[0], 0) + 1
    got = command_ec.place_rebuild(nodes, command_ec.chips_of(nodes), busy,
                                   shards, missing)
    if want is not None and want[0] != want[1]:
        # of the free chips, the node with most survivors
        free = [n["url"] for n in nodes if not busy[n["device"]["chip"]]]
        assert held.get(got[0], 0) == max(held.get(u, 0) for u in free)
        assert got[1] == want[1] and not busy[
            command_ec.chips_of(nodes)[got[0]]]
    else:
        assert got == want


def test_the_lanes_run_one_job_a_chip_in_order():
    chips = {"n0": "a", "n1": "b", "n2": "b"}
    gate = threading.Event()
    started, running, most = [], [0], [0]
    lock = threading.Lock()

    def place(job, busy):
        node = "n0" if not busy["a"] else "n1" if not busy["b"] else None
        return node and (node,)

    def run(job, placement):
        with lock:
            started.append((job, placement[0]))
            running[0] += 1
            most[0] = max(most[0], running[0])
        if len(started) >= 2:
            gate.set()
        assert gate.wait(10)
        with lock:
            running[0] -= 1

    command_ec.run_in_lanes(list(range(6)), chips, place, run)
    assert [job for job, _ in started] == list(range(6))
    assert most[0] == 2     # two chips, though three nodes
    assert {node for _, node in started} == {"n0", "n1"}


def test_on_one_lane_nothing_is_placed_and_no_thread_started():
    seen = []
    command_ec.run_in_lanes(
        [1, 2, 3], {"n0": "", "n1": ""},
        lambda job, busy: pytest.fail("placed"),
        lambda job, placement: seen.append(
            (job, placement, threading.get_ident())))
    assert seen == [(n, None, threading.get_ident()) for n in (1, 2, 3)]


def test_a_failed_volume_stops_new_ones_and_is_raised():
    chips = {"n0": "a", "n1": "b"}
    ran = []

    def place(job, busy):
        free = [u for u, c in chips.items() if not busy[c]]
        return (free[0],) if free else None

    def run(job, placement):
        ran.append(job)
        if job == 1:
            raise ValueError("volume 1")

    with pytest.raises(ValueError, match="volume 1"):
        command_ec.run_in_lanes(list(range(8)), chips, place, run)
    assert 1 in ran and len(ran) < 8
