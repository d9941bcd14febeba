"""A standing warm collection re-protected after a disk's loss, on a host
that runs a volume server a chip:

    set-up:  ec.encode -collection c -fullPercent f -quietFor 0   (once)
             every server's disk lost and rebuilt once            (warm-up)
    window:  every shard server lost_servers[n mod 4] holds, of all the
             volumes, lost  ->  the loss at the master
             ->  ec.rebuild -collection c  ->  14 shards of every volume
             ... back to back until the time is up; no encode

Only `ec.rebuild` is timed, once for the whole collection a loss
(`seal_repair._timed` through `seal_collection._command`, so
`rebuild_mbps` keeps its definition: the lost shards' bytes over the
command's wall; the cell reports no `encode_mbps`). Inside a command the
shell keeps one volume in flight per distinct chip the cluster's servers
report and, where the server placement names for the rebuilt shards has
its chip taken, has another server gather, decode and deliver them
(shell/command_ec.run_in_lanes, place_rebuild). The kind holds every
command to what placement promises whoever computed: the rebuilt shards
on the emptied server's own disk at full size the moment the command
returns, no holder above m and 14 shards a volume at the master, the
flat full gather of k shards a volume. The bytes are compared once, after
the window: every shard file of every volume with the plain reference
(`lib/reference.py`, the flat cells') and with the encoded one of set-up;
by then every file on disk has been rebuilt at least once.
"""

import glob
import os
import time

from kinds import seal_collection as sc
from kinds import seal_repair
from lib import cluster as cl
from lib import controls, datagen, reference

REBUILD = seal_repair.NODE_ROUTE["ec.rebuild"]
DEVICE = "jit.dispatches.dev"


def _refuse_a_program_without_a_chip_a_server():
    """The cell runs what a program older than it cannot: volume servers
    that each compute on a chip of their own (`-ec.backend tpu-own`), a
    shell that keeps a volume in flight a chip, and a rebuild whose
    decode runs apart from where its shards are stored (counted in
    ops/telemetry as `rebuild_delivered_bytes`). Such a program cannot
    be measured here; say so at once, before anything is started."""
    from seaweedfs_tpu.ops import telemetry
    if "rebuild_delivered_bytes" not in telemetry.STATS.snapshot():
        raise SystemExit(
            "benchmarks/kinds/rebuild_fanned.py: this program has no "
            "-ec.backend tpu-own and delivers no rebuilt shard to another "
            "node (ops/telemetry has no rebuild_delivered_bytes): the "
            "fanned-rebuild cell cannot be measured on it")


_refuse_a_program_without_a_chip_a_server()


# -- the control of this mix, added to the table run.py looks it up in ------

def deliver_to_wrong_node():
    """A volume decoded off its target stays where it was decoded: the
    shell takes the computing node for the home of the rebuilt shards,
    as a program whose rebuilder is also the storage node would once it
    fans a loss out. The shards are sound; they lie on a server that
    already holds three or four of the volume. Breaks "the rebuilt
    shards are on the server placement names" and "no server holds more
    than m"."""
    from seaweedfs_tpu.shell import command_ec
    sound = command_ec.place_rebuild

    def wrong(nodes, chips, busy, shards, missing):
        placed = sound(nodes, chips, busy, shards, missing)
        return placed and (placed[0], placed[0])

    command_ec.place_rebuild = wrong


controls.CONTROLS.update(deliver_to_wrong_node=deliver_to_wrong_node)


# -- set-up -----------------------------------------------------------------

def prepare(run) -> dict:
    config, traffic, cluster = run.config, run.traffic, run.cluster
    servers = [int(s) for s in traffic["lost_servers"]]
    cl.check(sorted(servers) == list(range(len(cluster.servers))),
             f"lost_servers {servers} does not name each of the "
             f"{len(cluster.servers)} servers once")
    cl.check(config["layout"] == "flat", "the mix is a flat volume's")
    cl.check(len(cluster.servers) * cluster.m >= cluster.total,
             "too few servers for none to hold more than m shards")
    chips = {n["url"]: (n.get("device") or {}).get("chip")
             for n in cluster.env.cluster_nodes()}
    cl.check(None not in chips.values() and
             len(set(chips.values())) == len(cluster.servers),
             f"the cell needs a chip a server; the servers report {chips}")
    state = {"commands": [], "servers": servers, "chips": chips,
             "volumes": int(config["volumes"]),
             "traced": int(traffic["traced_commands"]),
             "gather_limit": float(traffic["gathered_shards_at_most"])}
    sizes = datagen.needle_sizes(traffic["needles"],
                                 int(config["volume_mib"]) << 20,
                                 run.seed, 0)
    t0 = time.perf_counter()
    volume = cluster.upload_volume(run.seed, sizes)
    t1 = time.perf_counter()
    vid = volume["vid"]
    state["kept"] = cluster.keep_sealed(
        vid, os.path.join(run.workdir, "sealed"))
    state["dat_bytes"] = os.path.getsize(state["kept"] + ".dat")
    state["shard_bytes"] = reference.shard_bytes(state["dat_bytes"],
                                                 cluster.k)
    # the collection is the clones, two a server; the uploaded volume has
    # served (its bytes are kept) and goes, as a sealed volume goes once
    # it is coded
    from seaweedfs_tpu.server.http_util import post_json
    home = next(n for n, d in enumerate(cluster.dirs) if glob.glob(
        os.path.join(d, f"{cluster.collection}_{vid}.dat")))
    post_json(f"http://{cluster.servers[home].url}/admin/delete_volume"
              f"?volume={vid}")
    cl.poll(lambda: str(vid) not in cluster.env.all_volumes(),
            f"volume {vid} gone from the master")
    state["next_vid"] = vid + 1
    state["vids"] = vids = sc._clone(run, state, state["volumes"])
    cl.poll(lambda: all(any(
        r.get("size") == state["dat_bytes"]
        for r in cluster.env.all_volumes().get(str(v), [])) for v in vids),
        f"{len(vids)} volumes at their full size at the master")
    t2 = time.perf_counter()
    run.emit({"phase": "upload", "needles": len(sizes),
              "payload_bytes": int(sizes.sum()),
              "dat_bytes": state["dat_bytes"],
              "shard_bytes": state["shard_bytes"],
              "volumes": len(vids), "lost_servers": servers,
              "chips": sorted(set(chips.values())),
              "upload_s": t1 - t0, "clone_s": t2 - t1})
    # the one encode of the collection's life: by collection, in lanes
    every = list(range(cluster.total))
    enc = sc._command(run, "ec.encode", state["dat_bytes"] * len(vids),
                      False, "-collection", cluster.collection,
                      *traffic["encode_flags"])
    probe = {"error": None, "not_landed": 0}
    if enc["error"] or not sc._landed(
            run, state, probe, "ec.encode", {v: every for v in vids}):
        raise cl.BenchFailure(
            f"set-up's ec.encode: {enc['error'] or probe['error']}")
    sc._wait_whole(cluster, vids, f"{cluster.total} shards of each of "
                                  f"{len(vids)} volumes")
    state["above_m_after_encode"] = sc._above_m(cluster, vids)
    state["encoded"] = sc._shard_shas(cluster, {v: every for v in vids})
    t3 = time.perf_counter()
    run.emit({"phase": "sealed", "volumes": len(vids),
              "encode_wall_s": enc["wall_s"],
              "encode_mbps": enc["bytes"] / enc["wall_s"] / 1e6,
              "in_flight": _in_flight(enc),
              "check_s": t3 - t2 - enc["wall_s"]})
    # warm-up: every server's disk lost and rebuilt once, by the window's
    # own loop, so every chip has compiled the decodes the window
    # dispatches (compiles_in_window holds the program to none)
    # (a command that breaks its promise here stops the run as one of
    # the window would: the checks count it, and nothing is timed)
    state["sound"] = _warm_up(run, state)
    state["warm"] = len(state["commands"])
    return state


WARM_UP_ROUNDS = 4


def _warm_up(run, state) -> bool:
    """Which volume a chip decodes is the scheduler's turn of the
    moment, so one round of losses need not have shown every chip every
    operand (a holder of three shards, of four) at every width. The
    round is repeated, through `ec.rebuild` and nothing else, until one
    compiles nothing: two rounds where the first showed every chip
    everything, as it all but always does. False where a command broke
    its promise."""
    t0 = time.perf_counter()
    compiled = rounds = 0
    moved = 1
    while moved and rounds < WARM_UP_ROUNDS:
        rounds += 1
        moved = 0
        for server in state["servers"]:
            if not _lose_and_rebuild(run, state, server, timed=False):
                return False
            moved += state["commands"][-1]["compiles"]
        compiled += moved
    run.emit({"phase": "warm_up", "rounds": rounds,
              "losses": len(state["commands"]),
              "programs_compiled": compiled, "compiled_last_round": moved,
              "seconds": time.perf_counter() - t0})
    return True


# -- a loss and its command -------------------------------------------------

def _in_flight(record: dict) -> dict:
    """What a command's node replies and counters say of its lanes."""
    by_chip = {name[len(DEVICE) - 3:]: n
               for name, n in record["counters"].items()
               if name.startswith(DEVICE)}
    mine = record["volumes"].get(seal_repair.NODE_ROUTE[record["op"]], {})
    return {"volumes": len(mine), "dispatches_by_chip": by_chip,
            "delivered": sum(1 for s in mine.values()
                             if s.get("delivered_to"))}


def _off_target(cluster, server: int, lost: dict, nbytes: int) -> list:
    """Of the shards `lost` (vid -> shard ids), those not in the emptied
    server's own directory at full size right now."""
    out = []
    for vid, sids in lost.items():
        for sid in sids:
            path = os.path.join(cluster.dirs[server],
                                f"{cluster.collection}_{vid}.ec{sid:02d}")
            if not os.path.exists(path) or os.path.getsize(path) != nbytes:
                out.append((vid, sid))
    return out


def _lose_and_rebuild(run, state, server: int, timed: bool) -> bool:
    """One disk lost and one `ec.rebuild -collection` after it; False
    where the command did not do what its return promises (the loop
    stops there, and the checks count it)."""
    cluster, vids = run.cluster, state["vids"]
    command = {"server": server, "lost": {}, "replies": None,
               "error": None, "raised": False, "not_landed": 0,
               "off_target": 0, "above_m": 0, "timed": timed,
               "compiles": 0}
    state["commands"].append(command)
    with run.tracer.mark("lose"):
        command["lost"] = lost = sc._lose_server(cluster, vids, server)
    count = sum(len(sids) for sids in lost.values())
    reb = sc._command(run, "ec.rebuild", state["shard_bytes"] * count,
                      timed, "-collection", cluster.collection)
    # the decode by its equation, a byte column of a stripe: k survivor
    # bytes in, the lost ones out, a dense (lost, k) block of the inverse;
    # over the command, the mean of its volumes
    reb["work"] = {"columns": state["shard_bytes"] * len(vids),
                   "column_bytes": cluster.k + count / len(vids),
                   "column_terms": cluster.k * count / len(vids)}
    if reb["error"]:
        command["error"], command["raised"] = reb["error"], True
        return False
    command["replies"] = reb["volumes"].get(REBUILD, {})
    command["compiles"] = reb["counters"].get("jit.compiles", 0)
    # what the command's return promises, looked at before anything
    # waits: on the TARGET's disk, whoever decoded
    astray = _off_target(cluster, server, lost, state["shard_bytes"])
    command["off_target"] = len(astray)
    if not sc._landed(run, state, command, "ec.rebuild", lost) or astray:
        command["error"] = command["error"] or (
            f"ec.rebuild returned with shards {astray[:4]} not on server "
            f"{server}'s disk")
        run.emit({"phase": "off_target", "server": server,
                  "shards": astray[:8], "error": command["error"]})
        return False
    with run.tracer.mark("check"):
        sc._wait_whole(cluster, vids, f"{cluster.total} shards of each "
                                      f"volume after the rebuild")
        command["above_m"] = sc._above_m(cluster, vids)
        url = cluster.servers[server].url
        held = sc._ec_status(cluster, vids)
        command["off_target"] += sum(
            held[vid].get(sid) != [url]
            for vid, sids in lost.items() for sid in sids)
    run.emit({"phase": "fanned", "timed": timed, "server": server,
              "lost_shards": count, **_in_flight(reb)})
    return not command["off_target"]


def window(run, state):
    deadline = time.perf_counter() + run.seconds
    run.tracer.start()
    n = 0
    while state["sound"] and time.perf_counter() < deadline:
        ok = _lose_and_rebuild(
            run, state, state["servers"][n % len(state["servers"])],
            timed=True)
        n += 1
        if n == state["traced"]:
            run.tracer.stop()   # the trace covers each server lost once
        if not ok:
            break


# -- the checks -------------------------------------------------------------

def verify(run, state):
    """Outside the timed commands: every shard file of every volume
    against the plain reference and against the encoded one (every file
    has been rebuilt at least once by now), every volume's reply of
    every command (route, lost set, operand, gathered bytes), where the
    rebuilt shards lay when each command returned, and what the master
    said of the holders after each."""
    cluster, vids = run.cluster, state["vids"]
    t0 = time.perf_counter()
    want = reference.shard_shas(state["kept"] + ".dat", cluster.k, cluster.m)
    every = list(range(cluster.total))
    commands = state["commands"]
    sound = all(c["error"] is None for c in commands)
    differing = rebuilt_differing = 0
    wrong_servers = set()
    if sound:
        # a command that raised or whose shards had not landed left
        # nothing to compare: its own check counts it, not these
        now = sc._shard_shas(cluster, {v: every for v in vids})
        last_lost = {}      # (vid, sid) -> the server it was last lost with
        for c in commands:
            for vid, sids in c["lost"].items():
                for sid in sids:
                    last_lost[vid, sid] = c["server"]
        for vid in vids:
            for sid in every:
                bad_ref = now[vid][sid] != want[sid]
                bad_enc = (vid, sid) in last_lost and \
                    now[vid][sid] != state["encoded"][vid][sid]
                differing += bad_ref
                rebuilt_differing += bad_enc
                if (bad_ref or bad_enc) and (vid, sid) in last_lost:
                    wrong_servers.add(last_lost[vid, sid])
    encoded_differing = sum(
        got != ref for shas in state["encoded"].values()
        for got, ref in zip(shas, want))
    raised = sum(c["raised"] for c in commands)
    not_landed = sum(c["not_landed"] for c in commands)
    off_target = sum(c["off_target"] for c in commands)
    above_m = state["above_m_after_encode"] + \
        sum(c["above_m"] for c in commands)
    off_route, gathered = 0, []
    for c in commands:
        if c["replies"] is None:
            continue
        for vid, sids in c["lost"].items():
            # a volume with no reply, or a reply with no byte account,
            # cannot say it gathered k shards only: counted off the
            # route, and as every shard there is
            reply = c["replies"].get(vid) or {}
            off_route += sc._off_the_full_gather(reply, sids, cluster)
            gathered.append(
                reply["repair_bytes"] / state["shard_bytes"]
                if reply.get("repair_bytes") else float(cluster.total))
    timed = [c for c in commands if c["timed"]]
    last_of = {c["server"]: c for c in timed}
    run.attempted += len(timed)
    run.failed += sum(
        c["error"] is not None or c["off_target"] > 0 or c["above_m"] > 0
        or (last_of[c["server"]] is c and c["server"] in wrong_servers)
        for c in timed)
    by_chip = {name: n for name, n in run.counters.items()
               if name.startswith(DEVICE)}
    run.check("shards_differing_from_reference",
              differing + encoded_differing, 0,
              differing + encoded_differing == 0)
    run.check("rebuilt_shards_differing_from_encoded", rebuilt_differing, 0,
              rebuilt_differing == 0)
    run.check("commands_that_raised", raised, 0, raised == 0)
    run.check("shards_not_on_disk_when_command_returned", not_landed, 0,
              not_landed == 0)
    run.check("rebuilt_shards_off_the_emptied_server", off_target, 0,
              off_target == 0)
    run.check("holders_above_m_shards", above_m, 0, above_m == 0)
    run.check("rebuilds_off_the_full_gather", off_route, 0, off_route == 0)
    worst = max(gathered, default=0.0)
    run.check("gathered_shards_at_most", worst, state["gather_limit"],
              worst <= state["gather_limit"])
    run.check("chips_that_dispatched_at_least", len(by_chip),
              len(cluster.servers), len(by_chip) >= len(cluster.servers))
    run.emit({"phase": "verify", "commands": len(timed),
              "warm_up_commands": state["warm"],
              "lost": [c["server"] for c in timed],
              "lost_shards": [sorted({len(s) for s in c["lost"].values()})
                              for c in timed],
              "delivered": [sum(1 for r in (c["replies"] or {}).values()
                                if r.get("delivered_to")) for c in timed],
              "dispatches_by_chip": by_chip,
              "compared": sound, "reference": reference.__name__,
              "reference_s": time.perf_counter() - t0})


end_to_end = seal_repair.end_to_end
