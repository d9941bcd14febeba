"""A sealed hot-tier collection coded and re-protected by the commands the
master's maintenance script types, back to back and a collection at a
time:

    ec.encode -collection c -fullPercent f -quietFor 0
        ->  14 shards of every volume at the master, 4+4+3+3
    then `losses_per_seal` times:
        every shard one server holds, of all the volumes, lost
        ->  the loss at the master
        ->  ec.rebuild -collection c  ->  14 shards of every volume

Only the two shell commands are timed, each once for the whole
collection; clones, waits, deletions and checks sit between them. The
loop is `seal_holder_loss_flat.py`'s with a collection where that has a
volume (`seal_repair.py`'s `_timed` and `end_to_end` are used as they
are, so the two metrics keep their definition: bytes of the command's
work over the command's wall). No command names a volume id. A loss is a
whole server, named by the traffic file by its place in the cluster and
never drawn from the seed: the n-th loss of the window takes
`lost_servers[n mod 4]`; what it takes of each volume (3 or 4 shards) is
counted, not assumed.

A command replies once a volume, and `lib/cluster.RecordingEnv` keeps the
last reply a route: the kind records every one itself, around
`cluster.env.node_post`, and holds each volume's to the checks of the
flat holder-loss mix. What the readers sum (`phases`, the spread's and
the gather's accounts) is summed over the command's volumes into the
record; a stage's longest interval stays the longest; the operand's rows
are the mean over the volumes, which counts the command's columns
exactly (every volume of a collection has the same shard size).

Beside the shards the cell holds the program to the index a sealed
volume is read through: the `.ecx` on every holder of every volume,
after the encode and again after every rebuild (the rebuilder pulled
its own from a holder), against `lib/reference_index.py`. After every command, rebuilds too, the master
is asked that no holder has more than m shards of any volume.
"""

import glob
import os
import re
import time
from concurrent.futures import ThreadPoolExecutor

from kinds import seal_repair
from lib import cluster as cl
from lib import controls, datagen, reference, reference_index
from lib import roofline_terms

ENCODE = seal_repair.NODE_ROUTE["ec.encode"]
REBUILD = seal_repair.NODE_ROUTE["ec.rebuild"]
VOLUME = re.compile(r"[?&]volume=(\d+)")
# reply fields that say what a volume is, not what a command did: no sum
GEOMETRY = ("k", "m", "shards", "shard_size")


def _refuse_a_program_without_the_index_account():
    """The cell reads what a program older than it does not report: the
    `.ecx` build as a stage of the encode (counted in ops/telemetry as
    `index_entries`), a span of each whole command, and a rebuilder
    chosen with the volume's placement in hand. Such a program cannot be
    measured here; say so at once, before anything is started."""
    from seaweedfs_tpu.ops import telemetry
    if "index_entries" not in telemetry.STATS.snapshot():
        raise SystemExit(
            "benchmarks/kinds/seal_collection.py: this program accounts "
            "for no .ecx build and no whole command (ops/telemetry has no "
            "index_entries, ec.encode -collection leaves no span): the "
            "sealed-collection cell cannot be measured on it")


_refuse_a_program_without_the_index_account()


# -- the control of this mix, added to the table run.py looks it up in ------

def keep_tombstones_in_ecx():
    """The index builder forgets that a delete removes a key: every
    needle deleted before the seal stays in the `.ecx` with the offset
    and size it had, and a GET would serve it. The shards are what they
    were. Breaks "the .ecx is what its .idx log must leave"."""
    from seaweedfs_tpu.storage import needle_map
    needle_map.MemDb.delete = lambda self, nid: None


controls.CONTROLS.update(keep_tombstones_in_ecx=keep_tombstones_in_ecx)


# -- every reply of a command -----------------------------------------------

def _merged(replies: list) -> dict:
    """One record's worth of a command's replies on one route: numbers
    and the tables of numbers the readers sum are summed over the
    volumes, a stage's longest interval stays the longest, anything else
    is the last volume's."""
    out = {}
    for stats in replies:
        for key, value in stats.items():
            if key == "stage_max_s" and isinstance(value, dict):
                held = out.setdefault(key, {})
                for stage, secs in value.items():
                    held[stage] = max(held.get(stage, 0.0), secs)
            elif isinstance(value, dict) and all(
                    isinstance(v, (int, float)) for v in value.values()):
                held = out.setdefault(key, {})
                for name, n in value.items():
                    held[name] = held.get(name, 0) + n
            elif isinstance(value, (int, float)) and \
                    not isinstance(value, bool) and key not in GEOMETRY:
                out[key] = out.get(key, 0) + value
            else:
                out[key] = value
    return out


def _command(run, op: str, nbytes: int, timed: bool, *args) -> dict:
    """`seal_repair._timed` with every node reply of the command kept
    (`volumes`: route -> volume id -> stats) and merged into the record
    the readers read."""
    env = run.cluster.env
    seen = []
    sound = env.node_post

    def recording(node, path, timeout=None, body=None):
        out = sound(node, path, timeout, body)
        found = VOLUME.search(path)
        if found and isinstance(out, dict) and out.get("stats"):
            seen.append((path.split("?")[0], int(found.group(1)),
                         out["stats"]))
        return out

    env.node_post = recording
    try:
        record = seal_repair._timed(run, op, nbytes, timed, *args)
    finally:
        del env.node_post
    record["volumes"] = {}
    for route, vid, stats in seen:
        record["volumes"].setdefault(route, {})[vid] = stats
    for route, by_volume in record["volumes"].items():
        record["replies"][route] = _merged(list(by_volume.values()))
    mine = record["volumes"].get(seal_repair.NODE_ROUTE[op], {})
    operands = [stats["operand"] for stats in mine.values()
                if stats.get("operand")]
    if operands:
        record["rows"] = sum(o[0] for o in operands) / len(operands)
        record["k"] = int(operands[0][1])
    run.emit({"phase": "collection", "op": op, "timed": timed,
              "volumes": len(mine), "wall_s": record["wall_s"],
              "index_entries": record["counters"].get(
                  "telemetry.index_entries", 0),
              "index_us": record["counters"].get("telemetry.index_us", 0),
              "dispatches": record["counters"].get(
                  "telemetry.dispatches", 0),
              "operand_rows": sorted({o[0] for o in operands}),
              "stream_s": round(sum(stats.get("stream_s", 0.0)
                                    for stats in mine.values()), 3)})
    return record


# -- the cluster's side -----------------------------------------------------

def _ec_status(cluster, vids) -> dict:
    """vid -> {sid: [holder urls]} of the volumes `vids`, as the master
    has them now."""
    known = cluster.env.ec_volumes()
    return {vid: {int(s): urls for s, urls in
                  (known.get(str(vid)) or {}).get("shards", {}).items()
                  if urls} for vid in vids}


def _wait_whole(cluster, vids, what: str):
    every = set(range(cluster.total))
    cl.poll(lambda: all(set(shards) == every for shards in
                        _ec_status(cluster, vids).values()), what)


def _above_m(cluster, vids) -> int:
    """Holders with more than m shards of a volume, over the volumes."""
    count = 0
    for shards in _ec_status(cluster, vids).values():
        held = {}
        for urls in shards.values():
            for url in urls:
                held[url] = held.get(url, 0) + 1
        count += sum(n > cluster.m for n in held.values())
    return count


def _lose_server(cluster, vids, server: int) -> dict:
    """Every shard one server holds of the volumes `vids` dropped from it
    (its disk is gone); waits until the master has seen the loss.
    Returns vid -> the shard ids it held."""
    from seaweedfs_tpu.server.http_util import post_json
    url = cluster.servers[server].url
    lost = {vid: sorted(s for s, urls in shards.items() if url in urls)
            for vid, shards in _ec_status(cluster, vids).items()}
    for vid, sids in lost.items():
        if sids:
            post_json(f"http://{url}/admin/ec/delete_shards?volume={vid}"
                      f"&collection={cluster.collection}"
                      f"&shards={','.join(map(str, sids))}")
    cl.poll(lambda: not any(url in urls for shards in
                            _ec_status(cluster, vids).values()
                            for urls in shards.values()),
            f"loss of server {server}'s shards at the master")
    left = glob.glob(os.path.join(
        cluster.dirs[server], f"{cluster.collection}_*.ec*"))
    cl.check(not [p for p in left if any(
        os.path.basename(p).startswith(f"{cluster.collection}_{vid}.")
        for vid in vids)], f"server {server} still has files of the "
                           f"collection: {left[:4]}")
    return lost


def _ecx_shas(cluster, vids) -> list:
    """sha256 of the `.ecx` of every volume of `vids` on every server
    the master lists as a holder of it; None where a holder has none."""
    dirs = {vs.url: d for vs, d in zip(cluster.servers, cluster.dirs)}
    paths = [os.path.join(dirs[url], f"{cluster.collection}_{vid}.ecx")
             for vid, shards in _ec_status(cluster, vids).items()
             for url in sorted({u for urls in shards.values() for u in urls})]
    found = iter(reference.sha256_files(
        [p for p in paths if os.path.exists(p)]))
    return [next(found) if os.path.exists(p) else None for p in paths]


def _shard_shas(cluster, shards_of: dict) -> dict:
    """vid -> sha256 of its shards `shards_of[vid]`, in that order; every
    volume has to have each of its k + m shard files exactly once."""
    paths = []
    for vid, sids in shards_of.items():
        files = cluster.shard_files(vid)
        cl.check(sorted(files) == list(range(cluster.total)),
                 f"shard files of volume {vid}: {sorted(files)}")
        paths += [files[s] for s in sids]
    shas = iter(reference.sha256_files(paths))
    return {vid: [next(shas) for _ in sids]
            for vid, sids in shards_of.items()}


def _landed(run, state, cycle, op: str, shards_of: dict) -> bool:
    """`seal_repair._landed` for every volume of the command, looked at
    before anything waits."""
    for vid, sids in shards_of.items():
        if not seal_repair._landed(run, state, cycle, op, vid, sids):
            return False
    return True


# -- the loop ---------------------------------------------------------------

def prepare(run) -> dict:
    config, traffic, cluster = run.config, run.traffic, run.cluster
    servers = [int(s) for s in traffic["lost_servers"]]
    cl.check(sorted(servers) == list(range(len(cluster.servers))),
             f"lost_servers {servers} does not name each of the "
             f"{len(cluster.servers)} servers once")
    cl.check(config["layout"] == "flat", "the mix is a flat volume's")
    cl.check(len(cluster.servers) * cluster.m >= cluster.total,
             "too few servers for none to hold more than m shards")
    state = {"cycles": [], "servers": servers, "losses": 0,
             "volumes": int(config["volumes"]),
             "per_seal": int(traffic["losses_per_seal"]),
             "encode_flags": list(traffic["encode_flags"]),
             "gather_limit": float(traffic["gathered_shards_at_most"])}
    # the configuration's needles a volume; a rehearsal's smaller volume
    # (--volume-mib) holds what fits of them
    payload = min(int(config["needles_per_volume"]) *
                  int(traffic["needles"]["bytes"]),
                  int(config["volume_mib"]) << 20)
    sizes = datagen.needle_sizes(traffic["needles"], payload, run.seed, 0)
    t0 = time.perf_counter()
    volume = cluster.upload_volume(run.seed, sizes)
    t1 = time.perf_counter()
    deleted = _delete_needles(cluster, volume["fids"],
                              int(traffic["delete_every"]))
    t2 = time.perf_counter()
    vid = volume["vid"]
    state["kept"] = cluster.keep_sealed(
        vid, os.path.join(run.workdir, "sealed"))
    state["dat_bytes"] = os.path.getsize(state["kept"] + ".dat")
    state["shard_bytes"] = reference.shard_bytes(state["dat_bytes"],
                                                 cluster.k)
    state["next_vid"] = vid + 1
    # `ec.encode -collection` selects by the size the master has of a
    # volume, which is a heartbeat behind the last upload
    cl.poll(lambda: any(r.get("size") == state["dat_bytes"] for r in
                        cluster.env.all_volumes().get(str(vid), [])),
            f"volume {vid} at its full size at the master")
    state["ecx"] = reference_index.ecx_account(state["kept"] + ".idx")
    run.emit({"phase": "upload", "needles": len(sizes), "deleted": deleted,
              "payload_bytes": int(sizes.sum()),
              "dat_bytes": state["dat_bytes"],
              "shard_bytes": state["shard_bytes"],
              "idx_records": os.path.getsize(state["kept"] + ".idx") // 16,
              "ecx_entries": state["ecx"]["entries"],
              "volumes": state["volumes"], "lost_servers": servers,
              "losses_per_seal": state["per_seal"],
              "upload_s": t1 - t0, "delete_s": t2 - t1})
    # warm-up: a collection of `warm_volumes`, the uploaded volume and
    # clones of it on the other servers, which loses each server once:
    # the same widths (every volume is a clone) and both decode operands
    # (every volume lies 4+4+3+3, and each server is lost once), so every
    # program the window runs is compiled (or found in the cache) before
    # it opens (compiles_in_window holds the program to that)
    home = next(n for n, d in enumerate(cluster.dirs) if glob.glob(
        os.path.join(d, f"{cluster.collection}_{vid}.dat")))
    others = [n for n in range(len(cluster.servers)) if n != home]
    t3 = time.perf_counter()
    vids = [vid] + _clone(run, state, int(traffic["warm_volumes"]) - 1,
                          servers=others)
    state["warm"] = _cycle(run, state, vids, timed=False, deadline=None,
                           losses=servers)
    run.emit({"phase": "warm_up", "volumes": len(vids),
              "losses": len(servers), "seconds": time.perf_counter() - t3})
    return state


def _delete_needles(cluster, fids: list, every: int) -> int:
    """Every `every`-th needle deleted the way a client does it."""
    from seaweedfs_tpu.client import operation as op
    cache = op.VidCache(cluster.master.url)
    doomed = fids[every - 1::every]
    with ThreadPoolExecutor(8) as pool:
        done = list(pool.map(
            lambda fid: op.delete_file(cluster.master.url, fid, cache),
            doomed))
    cl.check(all(done), f"{done.count(False)} of {len(doomed)} deletes "
                        f"were refused")
    return len(doomed)


def _clone(run, state, count: int, servers=None) -> list:
    """`count` further volumes of the collection: the kept files under
    the next ids, on the servers in turn."""
    cluster = run.cluster
    servers = servers or list(range(len(cluster.servers)))
    vids = []
    for n in range(count):
        vid = state["next_vid"]
        state["next_vid"] += 1
        cluster.clone_sealed(state["kept"], vid, servers[n % len(servers)])
        vids.append(vid)
    return vids


def _cycle(run, state, vids: list, timed: bool, deadline,
           losses=None) -> dict:
    """One sealed collection: the encode, then its server losses. The
    warm-up is told which servers to lose; a cycle of the window takes
    the next ones in order and stops losing once the time is up, so that
    the command in flight then is the last one."""
    cluster, config = run.cluster, run.config
    every = list(range(cluster.total))
    cycle = {"vids": vids, "encoded": None, "encode_replies": None,
             "ecx": [], "rebuilds": [], "error": None, "raised": False,
             "not_landed": 0, "above_m": 0}
    state["cycles"].append(cycle)
    enc = _command(run, "ec.encode", state["dat_bytes"] * len(vids), timed,
                   "-collection", cluster.collection,
                   *state["encode_flags"])
    work = roofline_terms.encode_work(config, state["shard_bytes"])
    enc["work"] = {**work, "columns": work["columns"] * len(vids)}
    if enc["error"]:
        cycle["error"], cycle["raised"] = enc["error"], True
        return cycle
    cycle["encode_replies"] = enc["volumes"].get(ENCODE, {})
    if not _landed(run, state, cycle, "ec.encode",
                   {vid: every for vid in vids}):
        return cycle
    with run.tracer.mark("check"):
        _wait_whole(cluster, vids, f"{cluster.total} shards of each of "
                                   f"{len(vids)} volumes")
        cycle["above_m"] += _above_m(cluster, vids)
        cycle["encoded"] = _shard_shas(cluster, {vid: every
                                                 for vid in vids})
        cycle["ecx"] += _ecx_shas(cluster, vids)
    for n in range(state["per_seal"] if losses is None else len(losses)):
        if deadline and time.perf_counter() >= deadline:
            break
        if losses is None:
            server = state["servers"][state["losses"] %
                                      len(state["servers"])]
            state["losses"] += 1
        else:
            server = losses[n]
        rebuild = {"server": server, "lost": None, "shas": None,
                   "replies": None}
        cycle["rebuilds"].append(rebuild)
        with run.tracer.mark("lose"):
            rebuild["lost"] = lost = _lose_server(cluster, vids, server)
        count = sum(len(sids) for sids in lost.values())
        reb = _command(run, "ec.rebuild", state["shard_bytes"] * count,
                       timed, "-collection", cluster.collection)
        # the decode by its equation, a byte column of a stripe: k
        # survivor bytes in, the lost ones out, a dense (lost, k) block
        # of the inverse; over the command, the mean of its volumes
        reb["work"] = {"columns": state["shard_bytes"] * len(vids),
                       "column_bytes": cluster.k + count / len(vids),
                       "column_terms": cluster.k * count / len(vids)}
        if reb["error"]:
            cycle["error"], cycle["raised"] = reb["error"], True
            return cycle
        rebuild["replies"] = reb["volumes"].get(REBUILD, {})
        if not _landed(run, state, cycle, "ec.rebuild", lost):
            return cycle
        with run.tracer.mark("check"):
            _wait_whole(cluster, vids, f"{cluster.total} shards of each "
                                       f"volume after the rebuild")
            cycle["above_m"] += _above_m(cluster, vids)
            rebuild["shas"] = _shard_shas(cluster, lost)
            # the rebuilder pulled its index from a holder: every
            # holder's again, the rebuilder's among them
            cycle["ecx"] += _ecx_shas(cluster, vids)
    with run.tracer.mark("drop"):
        # the collection is done: drop its shards so disk use stays
        # bounded and the next ec.rebuild finds nothing of it
        for server in range(len(cluster.servers)):
            _lose_server(cluster, vids, server)
    return cycle


def window(run, state):
    deadline = time.perf_counter() + run.seconds
    run.tracer.start()
    while time.perf_counter() < deadline:
        with run.tracer.mark("clone_and_mount"):
            vids = _clone(run, state, state["volumes"])
        cycle = _cycle(run, state, vids, timed=True, deadline=deadline)
        run.tracer.stop()       # the trace covers the first whole cycle
        if cycle["error"]:
            break


def _off_the_full_gather(reply: dict, lost: list, cluster) -> bool:
    return reply.get("repair_mode") != "full" or \
        sorted(reply.get("lost") or []) != lost or \
        (reply.get("k"), reply.get("m")) != (cluster.k, cluster.m) or \
        list(reply.get("operand") or []) != [len(lost), cluster.k] or \
        bool(reply.get("repair_fallback"))


def verify(run, state):
    """Outside the timed ops: every volume's k + m encoded shards against
    the plain reference, every rebuilt shard against the encoded one (and
    so against the reference's), every `.ecx` against the index
    reference, every volume's rebuild reply (route, lost set, operand,
    gathered bytes), and what the master said of the holders after every
    command."""
    cluster = run.cluster
    t0 = time.perf_counter()
    want = reference.shard_shas(state["kept"] + ".dat", cluster.k, cluster.m)
    differing = rebuilt_differing = raised = not_landed = 0
    above_m = off_route = ecx_differing = 0
    gathered = []
    for cycle in state["cycles"]:
        # a command that raised or whose shards had not landed left
        # nothing to compare: its own check counts it, not these
        encoded = cycle["encoded"] is not None
        bad_enc = encoded and sum(
            got != ref for shas in cycle["encoded"].values()
            for got, ref in zip(shas, want))
        done = [r for r in cycle["rebuilds"] if r["shas"] is not None]
        wrong = []      # by rebuild: (differs from encoded, from reference)
        for r in done:
            bad_reb = bad_ref = 0
            for vid, sids in r["lost"].items():
                for sid, sha in zip(sids, r["shas"][vid]):
                    if sha != cycle["encoded"][vid][sid]:
                        bad_reb += 1
                    elif sha != want[sid]:
                        bad_ref += 1
            wrong.append((bad_reb, bad_ref))
        differing += bad_enc + sum(ref for _, ref in wrong)
        rebuilt_differing += sum(reb for reb, _ in wrong)
        raised += cycle["raised"]
        not_landed += cycle["not_landed"]
        above_m += cycle["above_m"]
        ecx_differing += sum(sha != state["ecx"]["sha256"]
                             for sha in cycle["ecx"])
        for r in cycle["rebuilds"]:
            if r["replies"] is None:
                continue
            for vid, sids in r["lost"].items():
                # a volume with no reply, or a reply with no byte
                # account, cannot say it gathered k shards only: counted
                # off the route, and as every shard there is
                reply = r["replies"].get(vid) or {}
                off_route += _off_the_full_gather(reply, sids, cluster)
                gathered.append(
                    reply["repair_bytes"] / state["shard_bytes"]
                    if reply.get("repair_bytes") else float(cluster.total))
        if cycle is not state["warm"]:
            started = len(cycle["rebuilds"]) if encoded else 0
            run.attempted += 1 + started
            run.failed += (bad_enc > 0 or not encoded) + \
                (started - len(done)) + sum(
                    reb + ref > 0 for reb, ref in wrong)
    run.check("shards_differing_from_reference", differing, 0,
              differing == 0)
    run.check("rebuilt_shards_differing_from_encoded", rebuilt_differing, 0,
              rebuilt_differing == 0)
    run.check("commands_that_raised", raised, 0, raised == 0)
    run.check("shards_not_on_disk_when_command_returned", not_landed, 0,
              not_landed == 0)
    run.check("holders_above_m_shards", above_m, 0, above_m == 0)
    run.check("rebuilds_off_the_full_gather", off_route, 0, off_route == 0)
    worst = max(gathered, default=0.0)
    run.check("gathered_shards_at_most", worst, state["gather_limit"],
              worst <= state["gather_limit"])
    run.check("ecx_files_differing_from_reference", ecx_differing, 0,
              ecx_differing == 0)
    run.emit({"phase": "verify", "cycles": len(state["cycles"]),
              "volumes": [len(c["vids"]) for c in state["cycles"]],
              "rebuilds": sum(len(c["rebuilds"]) for c in state["cycles"]),
              "lost": [[r["server"] for r in c["rebuilds"]]
                       for c in state["cycles"]],
              "lost_shards": [[sorted({len(sids) for sids in
                                       (r["lost"] or {}).values()})
                               for r in c["rebuilds"]]
                              for c in state["cycles"]],
              "ecx_files": sum(len(c["ecx"]) for c in state["cycles"]),
              "ecx_entries": state["ecx"]["entries"],
              "reference": [reference.__name__, reference_index.__name__],
              "reference_s": time.perf_counter() - t0})


end_to_end = seal_repair.end_to_end
