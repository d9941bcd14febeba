"""A warm tier whose recoveries are single shards, back to back and one
volume at a time:

    ec.encode -volumeId v  ->  14 shards at the master
    then `repairs_per_seal` times:
        one data shard lost  ->  loss at the master
        ->  ec.rebuild -collection c  ->  14 shards

Only the shell commands are timed; waits, deletions and checks sit between
them, as in `seal_rebuild.py`, whose loop this is but for three things.
The plain reference is the module the configuration's `layout` names
(`lib/reference.py` flat, `lib/reference_piggyback.py` piggyback). Shards
are lost one at a time, in the order the traffic file names (`lost_order`,
never the seed's: every run of a cell repairs the same sequence), so that
`ec.rebuild`, given no `-repair` flag, has to take the layout's
single-shard route by itself (the traffic mix's `routes`): a reply that
names another route, or carries a `repair_fallback`, or gathered more of
k x shard than the route may, makes the run not correct — a fall-back to
the full gather is a wrong result here, not a slower one. And each record
carries the `work` its operation needs by its equation
(lib/roofline_terms.py, from the configuration: the roofline count reads
that) beside the `operand` its computing node replied with (`rows`, `k`:
the matrix it dispatched, zeros and padding included).
"""

import glob
import os
import resource
import time

from lib import cluster as cl
from lib import controls, datagen, observe, reference, reference_piggyback
from lib import roofline_terms

REFERENCES = {"flat": reference, "piggyback": reference_piggyback}
NODE_ROUTE = {"ec.encode": "/admin/ec/generate",
              "ec.rebuild": "/admin/ec/rebuild"}


def _refuse_a_program_without_the_routes():
    """The cell reads what a program older than it does not report: the
    operand of its dispatches and the route of a repair (counted in
    ops/telemetry as `repair_route`). Such a program cannot be measured
    here; say so at once, before anything is started."""
    from seaweedfs_tpu.ops import telemetry
    if not hasattr(telemetry.STATS, "add_repair_route"):
        raise SystemExit(
            "benchmarks/kinds/seal_repair.py: this program reports neither "
            "the operand of its dispatches nor its repair routes "
            "(ops/telemetry has no repair_route): the single-shard-repair "
            "cells cannot be measured on it")


_refuse_a_program_without_the_routes()


# -- controls of this mix, added to the table run.py looks them up in -------

def force_full_gather():
    """Every ec.rebuild is told to take the full gather: the shards come
    out the same; the route check sees it, and the byte check counts the
    k whole shards it pulls."""
    os.environ["SW_EC_REPAIR_MODE"] = "full"


def corrupt_piggyback_theta():
    """The coupling coefficients theta_j of every piggyback plan the
    program builds are another seed's: a code that still repairs itself,
    and is not the one the configuration states."""
    from seaweedfs_tpu.ops import codec
    sound = codec._pb_build

    def broken(k, m, matrix_kind, matrix, theta_seed, cap):
        return sound(k, m, matrix_kind, matrix, theta_seed + 7, cap)

    codec._pb_build = broken


def gather_every_range_twice():
    """Every helper's range of a single-shard repair is asked for twice
    and the first answer dropped, as a gather that hedges every read and
    keeps both would: the shard comes out the same by the same route, and
    the repair moved twice the bytes its route may."""
    from seaweedfs_tpu.ec import gather
    for reader in (gather.RemoteRepairReader, gather.LocalRepairReader,
                   gather.RemotePlaneReader, gather.LocalPlaneReader):
        def twice(self, off, n, stripe_idx=0, sound=reader.read):
            sound(self, off, n, stripe_idx)
            return sound(self, off, n, stripe_idx)

        reader.read = twice


controls.CONTROLS.update(force_full_gather=force_full_gather,
                         corrupt_piggyback_theta=corrupt_piggyback_theta,
                         gather_every_range_twice=gather_every_range_twice)


# -- the loop ---------------------------------------------------------------

def prepare(run) -> dict:
    config, traffic, cluster = run.config, run.traffic, run.cluster
    order = [int(s) for s in traffic["lost_order"]]
    cl.check(len(order) > 1 and all(
        0 <= sid < cluster.k and sid != order[n - 1]
        for n, sid in enumerate(order)),
        f"lost_order {order}: data shards (under {cluster.k}), no two "
        f"neighbours the same, the last and the first neither")
    state = {"cycles": [], "ref": REFERENCES[config["layout"]],
             "route": traffic["routes"][config["layout"]],
             "repairs": int(traffic["repairs_per_seal"]),
             "order": order, "losses": 0}
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
              "dat_bytes": state["dat_bytes"],
              "layout": config["layout"], "route": state["route"],
              "repairs_per_seal": state["repairs"], "lost_order": order,
              "seconds": time.perf_counter() - t0})
    # warm-up: the same commands on the uploaded volume itself, which
    # compiles (or finds in the cache) every shape the window uses: the
    # encode's, and the repair's, which is one shape whichever shard is
    # lost (compiles_in_window holds the program to that); then the plan
    # of every shard the window may lose
    state["warm"] = _cycle(run, state, volume["vid"], timed=False,
                           deadline=None)
    state["losses"] = 0     # the window starts the order anew
    if state["route"]["repair_mode"] == "trace":
        _warm_trace_plans(run)
    return state


def _warm_trace_plans(run):
    """What a server that has been up for a while holds and a new process
    does not: the trace plan of each data shard lost alone. The program
    searches one the first time a (lost, helpers) pair is seen (0.5 s of
    a 4 s repair) and keeps it for the life of the process (ops/codec's
    plan cache); the window measures the server after that, whichever
    shards the mix names. Asked of the program's own planner with what a
    store passes it, once a data shard; the half-plane route's plans take
    no search (0.04 ms) and need none."""
    from seaweedfs_tpu.ops import codec as planner
    cluster = run.cluster
    codec = cluster.servers[0].store.codec
    t0 = time.perf_counter()
    for lost in range(cluster.k):
        planner.repair_plan(
            cluster.k, cluster.m, lost,
            survivors=[s for s in range(cluster.total) if s != lost],
            matrix_kind=codec.matrix_kind, matrix=codec.matrix)
    run.emit({"phase": "warm_plans", "plans": cluster.k,
              "seconds": time.perf_counter() - t0})


def _timed(run, op: str, nbytes: int, timed: bool, *args):
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
    node = replies.get(NODE_ROUTE[op]) or {}
    rows, k = node.get("operand") or (0, 1)
    record = {"op": op, "wall_s": wall, "bytes": nbytes, "replies": replies,
              "counters": observe.counters_delta(before,
                                                 observe.counters_now()),
              "traced": run.tracer.active, "error": error,
              "rows": int(rows), "k": int(k)}
    if timed:
        run.ops.append(record)
    run.emit({"phase": op, "timed": timed, "wall_s": wall,
              "mbps": nbytes / wall / 1e6, "error": error,
              "node": {route: {key: stats.get(key) for key in (
                  "backend", "phases", "operand", "repair_mode",
                  "repair_bytes", "repair_baseline_bytes",
                  "repair_fallback")}
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


def _cycle(run, state, vid: int, timed: bool, deadline) -> dict:
    """One sealed volume: the encode, then its repairs. The warm-up
    (no deadline) makes one repair, of the order's first shard; a cycle
    of the window loses the next ones of the order (the n-th repair of
    the window the n-th entry, round and round) and stops losing shards
    once the time is up, so that the command in flight then is the last
    one."""
    cluster = run.cluster
    every = set(range(cluster.total))
    cycle = {"vid": vid, "encoded": None, "repairs": [], "error": None,
             "raised": False, "not_landed": 0, "lost": []}
    state["cycles"].append(cycle)
    enc = _timed(run, "ec.encode", state["dat_bytes"], timed,
                 "-volumeId", str(vid))
    enc["work"] = roofline_terms.encode_work(run.config,
                                             state["shard_bytes"])
    if enc["error"]:
        cycle["error"], cycle["raised"] = enc["error"], True
        return cycle
    if not _landed(run, state, cycle, "ec.encode", vid, sorted(every)):
        return cycle
    with run.tracer.mark("check"):
        cluster.wait_shards(vid, every, f"14 shards of volume {vid}")
        files = cluster.shard_files(vid)
        cycle["encoded"] = reference.sha256_files(
            [files[s] for s in range(cluster.total)])
    for _ in range(state["repairs"] if deadline else 1):
        if deadline and time.perf_counter() >= deadline:
            break
        sid = state["order"][state["losses"] % len(state["order"])]
        state["losses"] += 1
        cycle["lost"].append(sid)
        repair = {"sid": sid, "sha": None, "reply": None}
        cycle["repairs"].append(repair)
        with run.tracer.mark("lose"):
            cluster.delete_shards(vid, [sid])
        reb = _timed(run, "ec.rebuild", state["shard_bytes"], timed,
                     "-collection", cluster.collection)
        if reb["error"]:
            cycle["error"], cycle["raised"] = reb["error"], True
            return cycle
        repair["reply"] = reb["replies"].get(NODE_ROUTE["ec.rebuild"]) or {}
        reb["work"] = roofline_terms.repair_work(
            run.config, state["shard_bytes"], repair["reply"])
        if not _landed(run, state, cycle, "ec.rebuild", vid, [sid]):
            return cycle
        with run.tracer.mark("check"):
            cluster.wait_shards(vid, every,
                                f"14 shards of {vid} after rebuild")
            files = cluster.shard_files(vid)
            cl.check(set(files) == every, f"shard files of {vid} after "
                     f"rebuild: {sorted(files)}")
            repair["sha"] = reference.sha256_file(files[sid])
    with run.tracer.mark("drop"):
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
        cycle = _cycle(run, state, vid, timed=True, deadline=deadline)
        run.tracer.stop()       # the trace covers the first whole cycle
        n += 1
        if cycle["error"]:
            break


def _off_route(reply: dict, route: dict) -> bool:
    return reply.get("repair_mode") != route["repair_mode"] or \
        bool(reply.get("repair_fallback"))


def _bytes_share(reply: dict) -> float:
    """The share of k x shard a repair gathered, by its node's own
    account. A reply with no such account is the full gather's, which
    pulls k whole shards: a share of 1, over either route's limit."""
    if not reply.get("repair_baseline_bytes"):
        return 1.0
    return reply.get("repair_bytes", 0) / reply["repair_baseline_bytes"]


def verify(run, state):
    """Outside the timed ops: every encoded volume's 14 shards and every
    repaired shard against the plain reference, every repaired shard
    against the encoded one, every repair's route and bytes."""
    cluster, route = run.cluster, state["route"]
    t0 = time.perf_counter()
    want = state["ref"].shard_shas(state["kept"] + ".dat", cluster.k,
                                   cluster.m)
    differing = rebuilt_differing = raised = not_landed = off_route = 0
    shares = []
    for cycle in state["cycles"]:
        # a command that raised or whose shards had not landed left
        # nothing to compare: its own check counts it, not these
        encoded = cycle["encoded"] is not None
        bad_enc = encoded and sum(
            got != ref for got, ref in zip(cycle["encoded"], want))
        done = [r for r in cycle["repairs"] if r["sha"] is not None]
        bad_ref = sum(r["sha"] != want[r["sid"]] for r in done)
        bad_reb = sum(r["sha"] != cycle["encoded"][r["sid"]] for r in done)
        differing += bad_enc + bad_ref
        rebuilt_differing += bad_reb
        raised += cycle["raised"]
        not_landed += cycle["not_landed"]
        replied = [r["reply"] for r in cycle["repairs"]
                   if r["reply"] is not None]
        off_route += sum(_off_route(reply, route) for reply in replied)
        shares += map(_bytes_share, replied)
        if cycle is not state["warm"]:
            run.attempted += 1 + (len(cycle["lost"]) if encoded else 0)
            run.failed += (bad_enc > 0 or not encoded) + \
                (encoded and len(cycle["lost"]) - len(done)) + \
                sum(r["sha"] != want[r["sid"]] or
                    r["sha"] != cycle["encoded"][r["sid"]] for r in done)
    run.check("shards_differing_from_reference", differing, 0,
              differing == 0)
    run.check("rebuilt_shards_differing_from_encoded", rebuilt_differing, 0,
              rebuilt_differing == 0)
    run.check("commands_that_raised", raised, 0, raised == 0)
    run.check("shards_not_on_disk_when_command_returned", not_landed, 0,
              not_landed == 0)
    run.check("repairs_off_the_configured_route", off_route, 0,
              off_route == 0)
    limit = route["repair_bytes_share_at_most"]
    worst = max(shares, default=0.0)
    run.check("repair_bytes_share_at_most", worst, limit, worst <= limit)
    run.emit({"phase": "verify", "cycles": len(state["cycles"]),
              "repairs": sum(len(c["repairs"]) for c in state["cycles"]),
              "route": route["repair_mode"], "lost": [
                  c["lost"] for c in state["cycles"]],
              "reference": state["ref"].__name__,
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
    run.emit({"phase": "memory", "max_rss_bytes": 1024 * resource.getrusage(
        resource.RUSAGE_SELF).ru_maxrss})
    if run.tracer.enabled:
        # what kernel_terms_roofline is counted from, op by op: the work
        # the operation needs by its equation, beside the operand its
        # node dispatched for it (the reader prints the bounds)
        run.emit({"phase": "roofline", "ops": [
            {"op": r["op"], "operand": [r["rows"], r["k"]],
             "work": r.get("work")} for r in run.ops if r["traced"]]})
    return out
