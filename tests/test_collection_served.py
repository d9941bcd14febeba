"""A collection of small-needle volumes coded and re-protected by the two
commands an operator types, through the served path.

One scenario on the CPU backend (master + four volume servers with
`ec_backend="tpu"` under JAX_PLATFORMS=cpu): one volume of ~2 MiB of
~4 KiB needles is uploaded, every 16th needle deleted, and cloned under
five further ids over the four servers (hard links, mounted: what
benchmarks/kinds/seal_collection.py does at 32 x 123 MiB). Then
`ec.encode -collection`, each server lost in turn with one `ec.rebuild`
after it, and one more loss rebuilt with the master's free counts made to
tie. The tests read what that left: files, replies, spans and counters.
"""

import hashlib
import io
import os
import shutil
import struct

import numpy as np
import pytest

from seaweedfs_tpu.ops import telemetry
from seaweedfs_tpu.shell.command_ec import pick_rebuilder
from seaweedfs_tpu.util import tracing

from conftest import wait_until

COLLECTION = "cs"
VOLUMES = 6
NEEDLES = 512
K, M, TOTAL = 10, 4, 14
RECORD = struct.Struct(">QII")


def plain_ecx(idx_path: str) -> bytes:
    """What a sealed volume's `.ecx` must be, written without the
    program: the `.idx` log read in order, a later record of a key
    replacing the earlier, a tombstone or a zero offset removing it,
    then the live keys ascending."""
    live = {}
    with open(idx_path, "rb") as f:
        log = f.read()
    assert len(log) % RECORD.size == 0
    for at in range(0, len(log), RECORD.size):
        key, offset, size = RECORD.unpack_from(log, at)
        if size == 0xFFFFFFFF or offset == 0:
            live.pop(key, None)
        else:
            live[key] = (offset, size)
    return b"".join(RECORD.pack(key, *live[key]) for key in sorted(live))


def sha(path: str) -> str:
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    from seaweedfs_tpu.client import operation as op
    from seaweedfs_tpu.ec.constants import to_ext
    from seaweedfs_tpu.server.http_util import post_json
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.shell.command_env import COMMANDS, CommandEnv
    import seaweedfs_tpu.shell  # noqa: F401 - registers the commands

    tmp = tmp_path_factory.mktemp("collection")
    # the master's own repair loop would put lost shards back before the
    # shell's ec.rebuild does
    was = os.environ.get("SW_REPAIR_INTERVAL_S")
    os.environ["SW_REPAIR_INTERVAL_S"] = "0"
    master = MasterServer(port=0, volume_size_limit_mb=4, pulse_seconds=1,
                          growth_counts={1: 1}).start()
    dirs = [str(tmp / f"v{i}") for i in range(4)]
    servers = [VolumeServer(
        port=0, directories=[d], master_url=master.url, pulse_seconds=1,
        max_volume_counts=[20], ec_backend="tpu").start() for d in dirs]
    spans, out = [], {"dirs": dirs, "urls": [vs.url for vs in servers]}

    class EveryReply(CommandEnv):
        """The shell's env, keeping every node's stats reply."""
        replies = []
        tie = None      # the node list `cluster_nodes` answers with

        def node_post(self, node, path, timeout=None, body=None):
            got = super().node_post(node, path, timeout, body)
            if isinstance(got, dict) and got.get("stats"):
                self.replies.append((path.split("?")[0], got["stats"]))
            return got

        def cluster_nodes(self):
            return self.tie or super().cluster_nodes()

    env = EveryReply(master.url, out=io.StringIO())

    def shell(name, *args):
        env.replies = []
        before = telemetry.STATS.snapshot()
        COMMANDS[name](env, list(args))
        now = telemetry.STATS.snapshot()
        return {"replies": env.replies, "counters": {
            f: now[f] - before[f] for f in ("index_entries", "index_us")}}

    def status(vids):
        known = env.ec_volumes()
        return {vid: {int(s): urls for s, urls in
                      (known.get(str(vid)) or {}).get("shards", {}).items()
                      if urls} for vid in vids}

    def whole(vids):
        return all(sorted(shards) == list(range(TOTAL))
                   for shards in status(vids).values())

    def above_m(vids):
        count = 0
        for shards in status(vids).values():
            held = {}
            for urls in shards.values():
                for url in urls:
                    held[url] = held.get(url, 0) + 1
            count += sum(n > M for n in held.values())
        return count

    def shard_shas(vids):
        found = {}
        for vid in vids:
            for d in dirs:
                for sid in range(TOTAL):
                    path = os.path.join(d, f"{COLLECTION}_{vid}" +
                                        to_ext(sid))
                    if os.path.exists(path):
                        assert (vid, sid) not in found, "a shard twice"
                        found[vid, sid] = sha(path)
        return found

    def lose(vids, server):
        url = servers[server].url
        lost = {vid: sorted(s for s, urls in shards.items() if url in urls)
                for vid, shards in status(vids).items()}
        for vid, sids in lost.items():
            post_json(f"http://{url}/admin/ec/delete_shards?volume={vid}"
                      f"&collection={COLLECTION}"
                      f"&shards={','.join(map(str, sids))}")
        assert wait_until(lambda: not any(
            url in urls for shards in status(vids).values()
            for urls in shards.values()))
        return lost

    try:
        assert wait_until(lambda: len(env.cluster_nodes()) == 4)
        a = op.assign(master.url, collection=COLLECTION)
        vid = int(a["fid"].split(",")[0])
        rng = np.random.default_rng(42)
        fids = [f"{vid},{i + 1:x}00000001" for i in range(NEEDLES)]
        for i, fid in enumerate(fids):
            op.upload(a["url"], fid, rng.integers(
                0, 256, 4096 + (i % 33 - 16) * 16).astype(
                    np.uint8).tobytes(), filename=f"f{i}")
        for fid in fids[15::16]:
            assert op.delete_file(master.url, fid)
        home = next(d for d in dirs if os.path.exists(
            os.path.join(d, f"{COLLECTION}_{vid}.dat")))
        base = os.path.join(home, f"{COLLECTION}_{vid}")
        kept = str(tmp / "kept")
        for ext in (".dat", ".idx"):
            shutil.copy(base + ext, kept + ext)
        dat_bytes = os.path.getsize(kept + ".dat")
        vids = [vid + n for n in range(VOLUMES)]
        for n, clone in enumerate(vids[1:]):
            to = os.path.join(dirs[n % 4], f"{COLLECTION}_{clone}")
            for ext in (".dat", ".idx"):
                os.link(kept + ext, to + ext)
            assert post_json(f"http://{servers[n % 4].url}/admin/volume/"
                             f"mount?volume={clone}").get("mounted")
        assert wait_until(lambda: all(
            any(r.get("size") == dat_bytes for r in
                env.all_volumes().get(str(v), [])) for v in vids))
        out.update(vids=vids, kept=kept, dat_bytes=dat_bytes,
                   homes={int(v): r[0]["url"]
                          for v, r in env.all_volumes().items()})

        tracing.add_finish_hook(spans.append)
        out["encode"] = shell("ec.encode", "-collection", COLLECTION,
                              "-fullPercent", "0.45", "-quietFor", "0")
        out["dats_left"] = [p for d in dirs for p in os.listdir(d)
                            if p.endswith(".dat")]
        assert wait_until(lambda: whole(vids))
        out["holders"] = status(vids)
        out["above_m"] = [above_m(vids)]
        out["encoded"] = shard_shas(vids)
        out["ecx"] = {(d, v): sha(os.path.join(d, f"{COLLECTION}_{v}.ecx"))
                      for d in dirs for v in vids}
        out["losses"] = []
        for server in range(4):
            lost = lose(vids, server)
            got = shell("ec.rebuild", "-collection", COLLECTION)
            assert wait_until(lambda: whole(vids))
            out["above_m"].append(above_m(vids))
            out["losses"].append({
                "server": server, "lost": lost, **got,
                "shas": shard_shas(vids), "holders": status(vids),
                "ecx": {v: sha(os.path.join(
                    dirs[server], f"{COLLECTION}_{v}.ecx")) for v in vids}})
        tracing.remove_finish_hook(spans.append)
        # once more, with the master's free counts made to tie and a
        # surviving holder first in its list: the freest node says
        # nothing then, the volume's placement everything
        lost = lose(vids, 0)
        nodes = CommandEnv.cluster_nodes(env)
        env.tie = sorted(({**n, "free": 10.0} for n in nodes),
                         key=lambda n: n["url"] == servers[0].url)
        shell("ec.rebuild", "-collection", COLLECTION)
        env.tie = None
        assert wait_until(lambda: whole(vids))
        out["tied"] = {"lost": lost, "above_m": above_m(vids),
                       "holders": status(vids), "shas": shard_shas(vids)}
        from seaweedfs_tpu.server.http_util import http_call
        out["scrape"] = http_call(
            "GET", f"http://{servers[0].url}/metrics").decode()
    finally:
        tracing.remove_finish_hook(spans.append)
        for vs in servers:
            vs.stop()
        master.stop()
        if was is None:
            os.environ.pop("SW_REPAIR_INTERVAL_S", None)
        else:
            os.environ["SW_REPAIR_INTERVAL_S"] = was
    out["spans"] = spans
    return out


def _replies(command: dict, route: str) -> list:
    return [stats for path, stats in command["replies"] if path == route]


def test_encode_by_collection_codes_every_volume_and_leaves_no_dat(served):
    assert served["dats_left"] == []
    replies = _replies(served["encode"], "/admin/ec/generate")
    assert len(replies) == VOLUMES
    assert all(r["shards"] == TOTAL and r["operand"] == [M, K]
               for r in replies)
    for vid, shards in served["holders"].items():
        assert sorted(shards) == list(range(TOTAL))
        by_holder = {}
        for sid, urls in shards.items():
            assert len(urls) == 1
            by_holder.setdefault(urls[0], []).append(sid)
        assert sorted(map(len, by_holder.values())) == [3, 3, 4, 4]


def test_every_holders_ecx_is_the_plain_sort_of_the_log(served):
    want = plain_ecx(served["kept"] + ".idx")
    # the log has the deletes in it: the index is not the log sorted
    assert len(want) == 16 * (NEEDLES - NEEDLES // 16)
    assert os.path.getsize(served["kept"] + ".idx") == \
        16 * (NEEDLES + NEEDLES // 16)
    assert len(served["ecx"]) == 4 * VOLUMES
    assert set(served["ecx"].values()) == {hashlib.sha256(want).hexdigest()}


def test_all_fourteen_shards_are_the_numpy_codecs(served, tmp_path):
    from seaweedfs_tpu.ec.constants import to_ext
    from seaweedfs_tpu.ec.encoder import write_ec_files
    from seaweedfs_tpu.ops import get_codec
    base = str(tmp_path / "plain")
    shutil.copy(served["kept"] + ".dat", base + ".dat")
    write_ec_files(base, codec=get_codec(K, M, backend="numpy"))
    want = [sha(base + to_ext(sid)) for sid in range(TOTAL)]
    assert len(served["encoded"]) == VOLUMES * TOTAL
    for (vid, sid), got in served["encoded"].items():
        assert got == want[sid], (vid, sid)


@pytest.mark.parametrize("server", range(4))
def test_a_lost_server_is_rebuilt_by_one_command_bit_identically(
        served, server):
    loss = served["losses"][server]
    # it held three or four shards of every volume, and all came back
    assert all(len(sids) in (3, 4) for sids in loss["lost"].values())
    assert loss["shas"] == served["encoded"]
    replies = _replies(loss, "/admin/ec/rebuild")
    assert len(replies) == VOLUMES
    assert sorted(sorted(r["lost"]) for r in replies) == \
        sorted(loss["lost"].values())
    assert all(r["repair_mode"] == "full" and
               r["operand"] == [len(r["lost"]), K] and
               "repair_fallback" not in r for r in replies)
    # rebuilt where they were lost: on the emptied server, which had no
    # index of the volume left and pulled the one every holder has
    url = served["urls"][server]
    for vid, sids in loss["lost"].items():
        assert all(loss["holders"][vid][s] == [url] for s in sids)
    assert set(loss["ecx"].values()) == set(served["ecx"].values())


def test_no_holder_is_above_m_after_any_command(served):
    assert served["above_m"] == [0] * 5


def test_no_holder_is_above_m_when_the_free_counts_tie(served):
    """The rebuilder's choice: with every node as free as the next, and a
    surviving holder first in the master's list, the node with most free
    slots is one that holds three or four shards of the volume; rebuilt
    there, the lost three or four put it above m."""
    tied = served["tied"]
    assert tied["above_m"] == 0
    assert tied["shas"] == served["encoded"]
    url = served["urls"][0]
    for vid, sids in tied["lost"].items():
        assert sids and all(tied["holders"][vid][s] == [url] for s in sids)


def _nodes(*free):
    return [{"url": f"n{i}", "free": f} for i, f in enumerate(free)]


@pytest.mark.parametrize("nodes,held,want", [
    # the emptied holder, though a neighbour is freer
    (_nodes(40.0, 52.8, 52.9, 52.9), {"n1": 4, "n2": 3, "n3": 3}, "n0"),
    # free counts tied: still the one that holds none
    (_nodes(9.0, 9.0, 9.0, 9.0), {"n0": 4, "n1": 4, "n3": 3}, "n2"),
    # nobody holds none: the fewest, then the freest among those
    (_nodes(5.0, 6.0, 7.0), {"n0": 5, "n1": 4, "n2": 4}, "n2"),
    # a node with no free slot is passed over while another has one
    (_nodes(0.0, 3.0, 2.0), {"n1": 5, "n2": 4}, "n2"),
    # no node has a free slot: the rule still names one
    (_nodes(0.0, 0.0), {"n0": 7, "n1": 6}, "n1"),
])
def test_the_rebuilder_is_chosen_by_the_volumes_placement(nodes, held, want):
    shards, sid = {}, 0
    for url, count in held.items():
        for _ in range(count):
            shards[sid] = [url]
            sid += 1
    assert pick_rebuilder(nodes, shards) == want


def test_the_index_build_is_a_stage_a_phase_and_two_counters(served):
    spans = served["spans"]
    streams = {s["span_id"]: s for s in spans
               if s["name"] == "ec.encode.stream"}
    built = [s for s in spans if s["name"] == "ec.encode.index"]
    assert len(built) == len(streams) == VOLUMES
    live = NEEDLES - NEEDLES // 16
    for s in built:
        assert s["parent_id"] in streams
        assert s["tags"]["entries"] == live
        assert s["tags"]["tombstones"] == NEEDLES // 16
        assert s["tags"]["bytes"] == 16 * live
    replies = _replies(served["encode"], "/admin/ec/generate")
    assert served["encode"]["counters"]["index_entries"] == VOLUMES * live
    assert served["encode"]["counters"]["index_us"] > 0
    # a reply's counters are the PROCESS's movement while its volume ran
    # (ops/telemetry.delta), and since PR 50 the four servers of this
    # process code a volume each at once: its own build and those of the
    # volumes beside it, never less
    for r in replies:
        assert r["index_entries"] >= live and \
            r["index_entries"] % live == 0 and r["index_us"] > 0
    assert sum(r["index_entries"] for r in replies) >= VOLUMES * live
    # what a reply says of its own stream is its own: the stage
    for r, s in zip(sorted(replies, key=lambda r: r["phases"]["index"]),
                    sorted(built, key=lambda s: s["duration_s"])):
        assert r["phases"]["index"] == r["stage_max_s"]["index"] == \
            pytest.approx(s["duration_s"], abs=2e-6)
    # the rebuilds build no index, they pull it: no count moves
    assert all(loss["counters"] == {"index_entries": 0, "index_us": 0}
               for loss in served["losses"])
    assert 'ec_device_telemetry_total{kind="index_entries"}' in \
        served["scrape"]
    assert 'ec_device_telemetry_total{kind="index_us"}' in served["scrape"]


def test_a_rebuilders_pull_of_the_index_is_a_stage(served):
    spans = served["spans"]
    streams = {s["span_id"] for s in spans
               if s["name"] == "ec.rebuild.stream"}
    pulls = [s for s in spans if s["name"] == "ec.rebuild.index"]
    assert len(pulls) == len(streams) == 4 * VOLUMES
    live = NEEDLES - NEEDLES // 16
    for s in pulls:
        assert s["parent_id"] in streams
        # the emptied server had neither file left
        assert s["tags"]["files"][:2] == [".ecx", ".vif"]
        assert s["tags"]["bytes"] > 16 * live


def test_each_command_has_a_span_that_counts_its_volumes(served):
    spans = served["spans"]
    whole, = [s for s in spans if s["name"] == "ec.encode.collection"]
    assert whole["parent_id"] is None
    roots = [s for s in spans if s["name"] == "ec.encode"]
    assert len(roots) == VOLUMES
    # PR 50: the servers that could code a volume at once (the .dat files
    # lie on all four) and how many did, over the command
    inflight = whole["tags"].pop("volumes_inflight_mean")
    assert whole["tags"] == {"collection": COLLECTION, "volumes": VOLUMES,
                             "bytes": VOLUMES * served["dat_bytes"],
                             "lanes": 4}
    assert 1.0 < inflight <= 4.0 and inflight == pytest.approx(
        sum(s["duration_s"] for s in roots) / whole["duration_s"], rel=0.1)
    # one trace an operation, as before: each volume's root is a root,
    # of a trace of its own, and names the command's
    assert len({s["trace_id"] for s in roots} | {whole["trace_id"]}) == \
        VOLUMES + 1
    assert all(s["parent_id"] is None and
               s["tags"]["command"] == whole["trace_id"] for s in roots)
    rebuilds = [s for s in spans if s["name"] == "ec.rebuild.collection"]
    assert len(rebuilds) == 4
    for whole, loss in zip(rebuilds, served["losses"]):
        assert whole["tags"]["volumes"] == VOLUMES
        assert whole["tags"]["bytes"] == sum(
            r["rebuilt_bytes"] for r in _replies(loss, "/admin/ec/rebuild"))
        roots = [s for s in spans if s["name"] == "ec.rebuild" and
                 s["tags"].get("command") == whole["trace_id"]]
        assert len(roots) == VOLUMES
        assert all(s["parent_id"] is None and
                   s["trace_id"] != whole["trace_id"] for s in roots)


def test_a_single_volume_encode_leaves_no_command_span(served):
    # (`ec.encode -volumeId` is the volume's own root and nothing over it:
    # every span of that name here came from the one -collection command)
    assert len([s for s in served["spans"]
                if s["name"] == "ec.encode.collection"]) == 1


# -- servers that share a chip: a rebuild has one volume in flight (PR 45), ----
# -- an encode one per server a .dat lies on (PR 50) --------------------------

def _volumes_of(served, name: str) -> list:
    commands = {s["trace_id"] for s in served["spans"]
                if s["name"] == name + ".collection"}
    return sorted((s for s in served["spans"] if s["name"] == name
                   and s["tags"].get("command") in commands),
                  key=lambda s: s["start"])


@pytest.mark.parametrize("name", ["ec.encode", "ec.rebuild"])
def test_on_one_chip_a_volume_starts_when_the_last_has_ended(served, name):
    """The four servers are one process on `-ec.backend tpu`: none names a
    chip, an `ec.rebuild` reads one lane from the cluster, and its
    volumes follow each other as they always did. An `ec.encode`'s lanes
    are the servers the `.dat` files lie on: the volumes of ONE server
    follow each other (tests/test_encode_lanes.py has the rest)."""
    volumes = _volumes_of(served, name)
    assert len(volumes) == VOLUMES * (1 if name == "ec.encode" else 4)
    lanes = {}
    for span in volumes:
        lanes.setdefault(served["homes"][span["tags"]["volume"]]
                         if name == "ec.encode" else "", []).append(span)
    assert len(lanes) == (4 if name == "ec.encode" else 1)
    for walked in lanes.values():
        for before, after in zip(walked, walked[1:]):
            assert before["start"] + before["duration_s"] <= \
                after["start"] + 1e-6


def test_on_one_chip_the_target_decodes_for_itself(served):
    for span in _volumes_of(served, "ec.rebuild"):
        assert span["tags"]["computed_on"] == span["tags"]["target"]
        assert span["tags"]["device"] == ""
    assert not [s for s in served["spans"]
                if s["name"] == "ec.rebuild.deliver"]
    for loss in served["losses"]:
        assert all("delivered_to" not in r
                   for r in _replies(loss, "/admin/ec/rebuild"))
    # the two counters are on the scrape, beside the other telemetry
    for kind in ("rebuild_delivered_bytes", "rebuild_local_bytes"):
        assert f'ec_device_telemetry_total{{kind="{kind}"}}' in \
            served["scrape"]
