"""`ec.encode -collection` keeps one volume in flight per SOURCE SERVER.

A volume is coded on the server its `.dat` lies on, and what a volume
occupies there is that server's (its freeze, index build, read, spread
lanes), so the command's lanes are the servers that hold the collection,
whatever chips they compute on: four servers on one chip have four
volumes in flight, a single server one, a `-volumeId` call one. Where
there are lanes every volume's holders are decided before the first
volume starts, by one thread, in job order, so the shards land where the
serial order would have put them.

The scenarios run on the CPU (`numpy` backend: the servers name no chip
and share one): master + four volume servers in this process, one volume
uploaded and its files cloned under eight further ids, two a server.
"""

import os
import shutil
import threading

import pytest

from seaweedfs_tpu.ops import telemetry
from seaweedfs_tpu.server.http_util import HttpError, post_json
from seaweedfs_tpu.shell import command_ec
from seaweedfs_tpu.util import tracing

from conftest import wait_until
from test_fanned_served import TOTAL, Cluster

VOLUMES = 8
FLAGS = ("-fullPercent", "0.45", "-quietFor", "0")


def clones(cluster, tmp, volumes: int = VOLUMES) -> list:
    """`volumes` clones of one uploaded volume, on the servers in turn
    (volume n on server n mod 4), the uploaded one gone: every cluster
    built this way has the same volumes on the same servers."""
    first, base = cluster.upload()
    kept = str(tmp / f"kept-{cluster.collection}")
    for ext in (".dat", ".idx"):
        shutil.copy(base + ext, kept + ext)
    home = next(u for u, d in zip(cluster.urls, cluster.dirs)
                if base.startswith(d))
    post_json(f"http://{home}/admin/delete_volume?volume={first}")
    vids = [first + 1 + n for n in range(volumes)]
    for n, vid in enumerate(vids):
        cluster.clone(kept, vid, n % len(cluster.servers))
    size = os.path.getsize(kept + ".dat")
    assert wait_until(lambda: all(
        any(r.get("size") == size for r in
            cluster.env.all_volumes().get(str(v), [])) for v in vids)
        and str(first) not in cluster.env.all_volumes())
    return vids


def placement(cluster, vids) -> dict:
    """(n-th volume, shard id) -> the server's place in the cluster."""
    return {(vids.index(vid), sid): server for (vid, sid), (server, _)
            in cluster.shard_files(vids).items()}


def dats(cluster, vids) -> list:
    return [vid for vid in vids for d in cluster.dirs if os.path.exists(
        os.path.join(d, f"{cluster.collection}_{vid}.dat"))]


@pytest.fixture(scope="module")
def lanes_and_serial(tmp_path_factory):
    """Two clusters laid out alike: one coded by one `ec.encode
    -collection` (volumes in lanes), one volume after volume by
    `-volumeId` calls in the same order (the serial run)."""
    out, clusters = {}, []
    try:
        for name in ("lanes", "serial"):
            tmp = tmp_path_factory.mktemp(name)
            cluster = Cluster(tmp, "numpy", collection="hot")
            clusters.append(cluster)
            vids = clones(cluster, tmp)
            spans = []
            tracing.add_finish_hook(spans.append)
            moved = telemetry.STATS.snapshot()
            try:
                if name == "lanes":
                    got = cluster.shell("ec.encode", "-collection", "hot",
                                        *FLAGS)
                else:
                    for vid in vids:
                        got = cluster.shell("ec.encode", "-volumeId",
                                            str(vid))
            finally:
                tracing.remove_finish_hook(spans.append)
            # what the command's return promises, before anything waits
            left = dats(cluster, vids)
            assert wait_until(lambda: cluster.whole(vids))
            out[name] = {"cluster": cluster, "vids": vids, "got": got,
                         "spans": spans, "dats_left": left,
                         "moved": telemetry.delta(moved),
                         "placement": placement(cluster, vids)}
        yield out
    finally:
        for cluster in clusters:
            cluster.stop()


def test_every_volume_is_coded_and_the_originals_are_gone(lanes_and_serial):
    run = lanes_and_serial["lanes"]
    cluster, vids = run["cluster"], run["vids"]
    assert run["dats_left"] == []
    assert sorted(run["placement"]) == [
        (n, sid) for n in range(VOLUMES) for sid in range(TOTAL)]
    assert cluster.above(vids) == 0 and cluster.leftovers() == []
    for vid in vids:
        assert f"volume {vid}: ec encoded, original removed" in \
            run["got"]["out"]
    # concurrent volumes write whole lines
    assert all(line.startswith("volume ") for line in
               run["got"]["out"].splitlines())


def test_the_shards_land_where_the_serial_order_puts_them(lanes_and_serial):
    lanes, serial = lanes_and_serial["lanes"], lanes_and_serial["serial"]
    assert lanes["placement"] == serial["placement"]
    # and not because every volume lies alike: the order a volume's
    # holders are taken in follows the free counts the earlier ones left
    firsts = {tuple(lanes["placement"][n, sid] for sid in range(4))
              for n in range(VOLUMES)}
    assert len(firsts) > 1


def _encode_spans(run) -> tuple:
    whole = [s for s in run["spans"] if s["name"] == "ec.encode.collection"]
    assert len(whole) == 1
    volumes = [s for s in run["spans"] if s["name"] == "ec.encode"
               and s["tags"].get("command") == whole[0]["trace_id"]]
    return whole[0], volumes


def test_one_volume_in_flight_per_source_server(lanes_and_serial):
    run = lanes_and_serial["lanes"]
    vids = run["vids"]
    whole, volumes = _encode_spans(run)
    assert sorted(s["tags"]["volume"] for s in volumes) == vids
    by_home = {}
    for s in volumes:
        home = vids.index(s["tags"]["volume"]) % 4
        by_home.setdefault(home, []).append(
            (s["start"], s["start"] + s["duration_s"]))
    assert sorted(by_home) == [0, 1, 2, 3]
    for spans in by_home.values():
        spans.sort()
        assert all(later[0] >= earlier[1]
                   for earlier, later in zip(spans, spans[1:]))
    # and the servers did run beside each other
    edges = sorted([(s["start"], 1) for s in volumes] +
                   [(s["start"] + s["duration_s"], -1) for s in volumes])
    most = running = 0
    for _, step in edges:
        running += step
        most = max(most, running)
    assert 2 <= most <= 4
    # every generate call of the command came from a volume's own thread
    # (a thread's ident may be handed to a later one)
    threads = {c["thread"] for c in run["got"]["calls"]
               if c["route"] == "/admin/ec/generate"}
    assert threading.get_ident() not in threads and len(threads) >= 2


def test_the_command_says_how_many_ran_beside_each_other(lanes_and_serial):
    run = lanes_and_serial["lanes"]
    whole, volumes = _encode_spans(run)
    assert whole["tags"]["lanes"] == 4
    assert whole["tags"]["volumes"] == VOLUMES
    mean = whole["tags"]["volumes_inflight_mean"]
    assert 1.0 < mean <= 4.0
    # the tag is the volumes' summed seconds over the command's
    assert mean == pytest.approx(
        sum(s["duration_s"] for s in volumes) / whole["duration_s"],
        rel=0.1)
    moved = run["moved"]
    assert moved["collection_encode_us"] == pytest.approx(
        whole["duration_s"] * 1e6, rel=0.05)
    assert moved["collection_encode_inflight_us"] / \
        moved["collection_encode_us"] == pytest.approx(mean, rel=0.01)
    # a volume by its id is no collection command: nothing counted
    assert lanes_and_serial["serial"]["moved"]["collection_encode_us"] == 0


def test_the_plan_follows_the_free_counts_the_earlier_volumes_leave():
    """The holders of every volume are planned before the first starts,
    from the master's list as the command began."""
    nodes = [{"url": f"n{i}", "free": 18} for i in range(4)]
    jobs = [(vid, [f"n{vid % 4}"]) for vid in range(8)]
    plan, no_room = command_ec.plan_encode_placements(nodes, jobs, (10, 4))
    assert no_room is None and sorted(plan) == list(range(8))
    # the first volume: round-robin from the master's order, 4+4+3+3
    assignment, spares = plan[0]
    assert assignment == [f"n{i % 4}" for i in range(14)] and spares == []
    # the second: its home freed a slot, the others filled by 3 or 4
    # tenths, and the order follows
    assert plan[1][0][:4] == ["n0", "n2", "n3", "n1"]
    for assignment, _ in plan.values():
        assert sorted(assignment.count(u) for u in set(assignment)) == \
            [3, 3, 4, 4]


def test_a_volume_with_no_room_ends_the_plan_where_the_serial_order_would():
    # room for one volume's fourteen shards (1.4 slots), not for two
    nodes = [{"url": f"n{i}", "free": 0.5} for i in range(4)]
    jobs = [(1, []), (2, []), (3, [])]
    plan, no_room = command_ec.plan_encode_placements(nodes, jobs, (10, 4))
    assert sorted(plan) == [1]
    assert isinstance(no_room, ValueError)
    # a replica dropped gives its slot back: then there is room
    plan, no_room = command_ec.plan_encode_placements(
        nodes, [(1, ["n0", "n1"]), (2, [])], (10, 4))
    assert sorted(plan) == [1, 2] and no_room is None


# -- the scheduler alone, with a key its caller gives -------------------------

def test_a_job_occupies_the_lane_its_caller_names():
    """Six nodes on three lanes the caller names (two nodes a lane): one
    job a lane, each job on the node `place` picked, in job order."""
    lane_of = {f"n{i}": f"lane{i // 2}" for i in range(6)}
    gate = threading.Event()
    lock = threading.Lock()
    started, running, most = [], {}, [0]

    def place(job, busy):
        node = f"n{job % 6}"
        return None if busy[lane_of[node]] else (node, job * job)

    def run(job, got):
        node, extra = got
        assert extra == job * job
        with lock:
            started.append(job)
            running[lane_of[node]] = running.get(lane_of[node], 0) + 1
            assert running[lane_of[node]] == 1
            most[0] = max(most[0], sum(running.values()))
        if len(started) >= 3:
            gate.set()
        assert gate.wait(10)
        with lock:
            running[lane_of[node]] -= 1

    command_ec.run_in_lanes(list(range(12)), lane_of, place, run)
    assert sorted(started) == list(range(12))
    assert started[:3] == [0, 2, 4]    # 1, 3, 5 wait for their lanes
    assert most[0] == 3


def test_of_two_errors_the_first_in_job_order_is_raised_and_the_rest_finish():
    lane_of = {f"n{i}": f"n{i}" for i in range(3)}
    second_failed = threading.Event()
    finished = []

    def place(job, busy):
        node = f"n{job % 3}"
        return None if busy[node] else (node,)

    def run(job, got):
        if job == 0:            # in flight when both errors happen
            assert second_failed.wait(10)
            finished.append(job)
        elif job == 1:          # fails last, raised first
            assert second_failed.wait(10)
            raise ValueError("volume 1")
        elif job == 2:
            second_failed.set()
            raise KeyError("volume 2")
        else:
            finished.append(job)

    with pytest.raises(ValueError, match="volume 1"):
        command_ec.run_in_lanes(list(range(9)), lane_of, place, run)
    assert 0 in finished and len(finished) < 7


@pytest.mark.parametrize("lane_of,jobs", [
    ({"n0": "n0"}, [1, 2, 3]),                  # a single server
    ({"n0": "x", "n1": "x", "n2": "x"}, [1, 2, 3]),     # one lane named
    ({}, [1]),                                  # nothing to key by
])
def test_one_lane_is_the_callers_thread_whatever_the_key(lane_of, jobs):
    seen = []
    command_ec.run_in_lanes(
        jobs, lane_of, lambda job, busy: pytest.fail("placed"),
        lambda job, got: seen.append((job, got, threading.get_ident())))
    assert seen == [(n, None, threading.get_ident()) for n in jobs]


def test_many_lanes_under_a_short_switch_interval_lose_no_update():
    """More lanes than cores, jobs of no length, the interpreter made to
    switch threads every few microseconds: every job runs once, a lane
    never holds two, and the scheduler ends with nothing counted busy
    (a lost update of its table would start a second job in a lane or
    leave the last ones waiting for ever)."""
    import sys
    import time
    lane_of = {f"n{i}": f"lane{i % 24}" for i in range(48)}
    lock = threading.Lock()
    running, ran, doubled = {}, [], []

    def place(job, busy):
        node = f"n{job % 48}"
        return None if busy[lane_of[node]] else (node,)

    def run(job, got):
        lane = lane_of[got[0]]
        with lock:
            running[lane] = running.get(lane, 0) + 1
            if running[lane] > 1:
                doubled.append(job)
        time.sleep(0)
        with lock:
            running[lane] -= 1
            ran.append(job)

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    done = threading.Event()

    def whole():
        command_ec.run_in_lanes(list(range(600)), lane_of, place, run)
        done.set()

    try:
        t = threading.Thread(target=whole, daemon=True)
        t.start()
        assert done.wait(60)
    finally:
        sys.setswitchinterval(was)
    assert sorted(ran) == list(range(600)) and doubled == []
    assert not any(running.values())


# -- a failing volume among concurrent ones -----------------------------------

def test_a_failed_volume_is_unwound_while_its_neighbours_complete(tmp_path):
    cluster = Cluster(tmp_path, "numpy", collection="hot")
    try:
        vids = clones(cluster, tmp_path)
        doomed = vids[1]
        sound = cluster.env.node_post
        held = threading.Event()

        def failing(node, path, timeout=None, body=None):
            if path.startswith("/admin/ec/mount") and \
                    f"volume={doomed}&" in path:
                held.set()
                raise HttpError(500, f"mount of volume {doomed} refused")
            if path.startswith("/admin/ec/mount"):
                # the neighbours are still in flight when it fails
                assert held.wait(30)
            return sound(node, path, timeout, body)

        cluster.env.node_post = failing
        try:
            with pytest.raises(RuntimeError, match="refused"):
                cluster.shell("ec.encode", "-collection", "hot", *FLAGS)
        finally:
            del cluster.env.node_post
        # the failed volume: its .dat where it was, writable again, no
        # shard and no stage of it anywhere
        assert dats(cluster, [doomed]) == [doomed]
        home = cluster.servers[vids.index(doomed) % 4]
        assert home.store.find_volume(doomed).readonly is False
        names = [n for d in cluster.dirs for n in os.listdir(d)]
        assert not [n for n in names if n.startswith(f"hot_{doomed}.ec")
                    or n.endswith(".part")]
        assert cluster.leftovers() == []
        # its neighbours: coded whole or never begun, none in between
        others = [v for v in vids if v != doomed]
        coded = [v for v in others if v not in dats(cluster, others)]
        assert wait_until(lambda: cluster.whole(coded))
        assert set(vids[:4]) - {doomed} <= set(coded)
        for vid in set(others) - set(coded):
            n = vids.index(vid)
            assert cluster.servers[n % 4].store.find_volume(
                vid).readonly is False
            assert not [x for x in names if x.startswith(f"hot_{vid}.ec")]
        assert cluster.above(coded) == 0
    finally:
        cluster.stop()


# -- one lane: what the command always did ------------------------------------

@pytest.mark.parametrize("servers,how", [
    (1, "collection"),          # a single server holds the collection
    (4, "volume_ids"),          # volumes named one by one
    (4, "collection_of_one"),   # a collection that selects one volume
])
def test_one_source_server_starts_no_thread(tmp_path, servers, how):
    cluster = Cluster(tmp_path, "numpy", servers=servers, collection="one")
    try:
        vids = clones(cluster, tmp_path, 1 if how == "collection_of_one"
                      else 3)
        order = [int(v) for v in cluster.env.all_volumes()]
        before = {t.ident for t in threading.enumerate()}
        named = []
        spawn = threading.Thread.start

        def start(thread):
            named.append(thread.name)
            spawn(thread)

        threading.Thread.start = start
        try:
            if how == "volume_ids":
                calls = []
                for vid in order:
                    calls += cluster.shell("ec.encode", "-volumeId",
                                           str(vid))["calls"]
            else:
                calls = cluster.shell("ec.encode", "-collection", "one",
                                      *FLAGS)["calls"]
        finally:
            threading.Thread.start = spawn
        assert not [n for n in named if n.startswith("ec-volume-")]
        assert before        # the servers' threads were there already
        me = threading.get_ident()
        # a volume after the other, each whole before the next begins, on
        # the caller's thread but for the mounts' fan-out
        assert {c["thread"] for c in calls
                if c["route"] not in ("/admin/ec/mount",
                                      "/admin/ec/copy")} == {me}
        generated = [int(c["path"].split("volume=")[1].split("&")[0])
                     for c in calls if c["route"] == "/admin/ec/generate"]
        assert generated == [v for v in order if v in vids]
        per_volume = []
        for c in calls:
            vid = int(c["path"].split("volume=")[1].split("&")[0])
            if not per_volume or per_volume[-1][0] != vid:
                per_volume.append((vid, []))
            per_volume[-1][1].append(c["route"])
        assert [v for v, _ in per_volume] == generated  # never interleaved
        for _, routes in per_volume:
            assert routes[0] == "/admin/volume/readonly"
            assert routes[1] == "/admin/ec/generate"
            assert routes[-1] == "/admin/delete_volume"
        assert wait_until(lambda: cluster.whole(vids))
        assert dats(cluster, vids) == [] and cluster.leftovers() == []
    finally:
        cluster.stop()
