"""The operator's warm-tier lifecycle, back to back and one at a time:

    ec.encode -volumeId v  ->  14 shards at the master  ->  lose shards
    ->  loss at the master  ->  ec.rebuild -collection c  ->  14 shards

Only the two shell commands are timed; waits, deletions and checks sit
between them. The first check comes the moment a command has returned,
before any wait: every shard it made is on a holder's disk at its full
size, and after ec.encode the `.dat` is gone. Every volume of the window is the one sealed volume of
set-up under a further volume id (hard links of its `.dat`/`.idx`, mounted
on the servers in turn), so one reference pass serves them all and the
supply never runs dry. Which shards a rebuild has lost is the traffic
file's to say (`lose_sets`, taken in turn), never the seed's: every run
of a cell does the same work.
"""

import glob
import os
import time

from lib import cluster as cl
from lib import datagen, observe, reference


def prepare(run) -> dict:
    config, traffic, cluster = run.config, run.traffic, run.cluster
    sets = [sorted(int(s) for s in lost) for lost in traffic["lose_sets"]]
    cl.check(sets and all(
        0 < len(lost) <= cluster.m and len(set(lost)) == len(lost) and
        0 <= lost[0] and lost[-1] < cluster.total for lost in sets),
        f"lose_sets {sets}: each has to name 1 to {cluster.m} of the "
        f"{cluster.total} shards")
    state = {"cycles": [], "sets": sets, "rebuilds": 0}
    sizes = datagen.needle_sizes(traffic["needles"],
                                 int(config["volume_mib"]) << 20,
                                 run.seed, 0)
    t0 = time.perf_counter()
    volume = cluster.upload_volume(run.seed, sizes)
    state["kept"] = cluster.keep_sealed(
        volume["vid"], os.path.join(run.workdir, "sealed"))
    state["dat_bytes"] = os.path.getsize(state["kept"] + ".dat")
    state["shard_bytes"] = reference.shard_bytes(state["dat_bytes"],
                                                 cluster.k)
    state["next_vid"] = volume["vid"] + 1
    run.emit({"phase": "upload", "needles": len(sizes),
              "payload_bytes": int(sizes.sum()),
              "dat_bytes": state["dat_bytes"], "lose_sets": sets,
              "seconds": time.perf_counter() - t0})
    # warm-up: the same two commands on the uploaded volume itself, which
    # compiles (or finds in the cache) every shape the window uses: a
    # rebuild's is one whichever set of that many shards is lost
    state["warm"] = _cycle(run, state, volume["vid"], sets[0], timed=False)
    return state


def _timed(run, op: str, nbytes: int, rows: int, timed: bool, *args):
    """One shell command under the host's clock, with the counters it
    moved and the stats its computing node replied with."""
    cluster = run.cluster
    before = observe.counters_now()
    with run.tracer.mark(op):
        t0 = time.perf_counter()
        try:
            replies = cluster.shell(op, *args)
            error = None
        except Exception as e:  # noqa: BLE001 - counted as a failed op
            replies, error = {}, f"{type(e).__name__}: {e}"
        wall = time.perf_counter() - t0
    record = {"op": op, "wall_s": wall, "bytes": nbytes, "replies": replies,
              "counters": observe.counters_delta(before,
                                                 observe.counters_now()),
              "traced": run.tracer.active, "error": error,
              "rows": rows, "k": cluster.k}
    if timed:
        run.ops.append(record)
    run.emit({"phase": op, "timed": timed, "wall_s": wall,
              "mbps": nbytes / wall / 1e6, "error": error,
              "node": {route: {key: stats.get(key) for key in
                               ("backend", "phases")}
                       for route, stats in replies.items()},
              "counters": record["counters"]})
    return record


def _landed(run, state, cycle, op: str, vid: int, sids) -> bool:
    """What the command's return promises, looked at before anything
    waits: the shards `sids` on disk at full size, no `.dat` left."""
    cluster = run.cluster
    short = cluster.shards_short_on_disk(vid, sids, state["shard_bytes"])
    dats = [p for d in cluster.dirs for p in glob.glob(
        os.path.join(d, f"{cluster.collection}_{vid}.dat"))]
    cycle["not_landed"] += len(short) + len(dats)
    if short or dats:
        cycle["error"] = (f"{op} of volume {vid} returned with shards "
                          f"{short} not on disk at {state['shard_bytes']} "
                          f"bytes, .dat left: {len(dats)}")
        run.emit({"phase": "not_landed", "error": cycle["error"]})
    return not (short or dats)


def _cycle(run, state, vid: int, lost: list, timed: bool) -> dict:
    cluster = run.cluster
    every = set(range(cluster.total))
    cycle = {"vid": vid, "lost": lost, "encoded": None, "rebuilt": None,
             "error": None, "raised": False, "not_landed": 0}
    state["cycles"].append(cycle)
    enc = _timed(run, "ec.encode", state["dat_bytes"], cluster.m, timed,
                 "-volumeId", str(vid))
    if enc["error"]:
        cycle["error"], cycle["raised"] = enc["error"], True
        return cycle
    if not _landed(run, state, cycle, "ec.encode", vid, sorted(every)):
        return cycle
    with run.tracer.mark("check_and_lose"):
        cluster.wait_shards(vid, every, f"14 shards of volume {vid}")
        files = cluster.shard_files(vid)
        cycle["encoded"] = reference.sha256_files(
            [files[s] for s in range(cluster.total)])
        cluster.delete_shards(vid, lost)
    reb = _timed(run, "ec.rebuild", state["shard_bytes"] * len(lost),
                 len(lost), timed, "-collection", cluster.collection)
    if reb["error"]:
        cycle["error"], cycle["raised"] = reb["error"], True
        return cycle
    if not _landed(run, state, cycle, "ec.rebuild", vid, lost):
        return cycle
    with run.tracer.mark("check_and_drop"):
        cluster.wait_shards(vid, every, f"14 shards of {vid} after rebuild")
        files = cluster.shard_files(vid)
        cl.check(set(files) == every, f"shard files of {vid} after rebuild: "
                 f"{sorted(files)}")
        cycle["rebuilt"] = dict(zip(lost, reference.sha256_files(
            [files[s] for s in lost])))
        # the volume is done: drop its shards so disk use stays bounded
        # and the next ec.rebuild finds nothing of it
        cluster.delete_shards(vid, sorted(every))
    return cycle


def window(run, state):
    cluster = run.cluster
    deadline = time.perf_counter() + run.seconds
    run.tracer.start()
    n = 0
    while time.perf_counter() < deadline:
        vid = state["next_vid"]
        state["next_vid"] += 1
        with run.tracer.mark("clone_and_mount"):
            cluster.clone_sealed(state["kept"], vid,
                                 n % len(cluster.servers))
        # the n-th rebuild of the window loses the file's n-th set
        lost = state["sets"][state["rebuilds"] % len(state["sets"])]
        state["rebuilds"] += 1
        cycle = _cycle(run, state, vid, lost, timed=True)
        run.tracer.stop()       # the trace covers the first whole cycle
        n += 1
        if cycle["error"]:
            break


def verify(run, state):
    """Outside the timed ops: every encoded volume's 14 shards against the
    plain reference, every rebuilt shard against the encoded one."""
    cluster = run.cluster
    t0 = time.perf_counter()
    want = reference.shard_shas(state["kept"] + ".dat", cluster.k, cluster.m)
    differing, rebuilt_differing, raised, not_landed = 0, 0, 0, 0
    for cycle in state["cycles"]:
        # a command that raised or whose shards had not landed left
        # nothing to compare: its own check counts it, not these two
        bad_enc = 0 if cycle["encoded"] is None else sum(
            got != ref for got, ref in zip(cycle["encoded"], want))
        bad_reb = 0 if cycle["rebuilt"] is None else sum(
            sha != cycle["encoded"][sid]
            for sid, sha in cycle["rebuilt"].items())
        differing += bad_enc
        rebuilt_differing += bad_reb
        raised += cycle["raised"]
        not_landed += cycle["not_landed"]
        if cycle is not state["warm"]:
            encoded = cycle["encoded"] is not None
            run.attempted += 1 + encoded
            run.failed += (bad_enc > 0 or not encoded) + \
                (encoded and (bad_reb > 0 or cycle["rebuilt"] is None))
    run.check("shards_differing_from_reference", differing, 0,
              differing == 0)
    run.check("rebuilt_shards_differing_from_encoded", rebuilt_differing, 0,
              rebuilt_differing == 0)
    run.check("commands_that_raised", raised, 0, raised == 0)
    run.check("shards_not_on_disk_when_command_returned", not_landed, 0,
              not_landed == 0)
    run.emit({"phase": "verify", "cycles": len(state["cycles"]),
              "lost": [c["lost"] for c in state["cycles"]],
              "reference_s": time.perf_counter() - t0})


def end_to_end(run, state, window_s: float) -> dict:
    out = {}
    for name, op in (("encode_mbps", "ec.encode"),
                     ("rebuild_mbps", "ec.rebuild")):
        done = [r for r in run.ops if r["op"] == op and not r["error"]]
        walls = [r["wall_s"] for r in done]
        if done:
            out[name] = sum(r["bytes"] for r in done) / sum(walls) / 1e6
        run.emit({"phase": "op_walls", "op": op, "count": len(done),
                  "wall_s": walls})
    return out

