"""A small warm tier coded RS(6,3) on three holders that keeps losing one
of them, back to back and one volume at a time:

    ec.encode -volumeId v -geometry 6,3  ->  9 shards at the master, 3+3+3
    then `losses_per_seal` times:
        every shard of one holder set lost  ->  loss at the master
        ->  ec.rebuild -collection c  ->  9 shards

Only the shell commands are timed; waits, deletions and checks sit between
them. The loop is `seal_holder_loss.py`'s for a flat volume of a geometry
of its own (`seal_repair.py`'s `_timed`, `_landed` and `end_to_end` are
used as they are): no piggyback reference, no coupled plan to warm. The
geometry is the configuration's (`data_shards`, `parity_shards`) and
reaches the program the way an operator's does, as the `-geometry` flag of
`ec.encode`; every later command has to find it on the volume. A loss is a
whole holder set, named by the traffic file and never drawn from the seed:
the n-th loss of the window takes `holder_sets[n mod 3]`, so every run
does the same work. `ec.rebuild`, given no `-repair` flag, then has to
take the flat full gather as its route: a reply that names another route,
another set of lost shards, another geometry than the configuration's or
another operand than (lost, k), or that carries a `repair_fallback`, or a
gather of more than k whole shards, makes the run not correct; so does an
`ec.encode` reply that names another geometry or another number of shards
than k + m. After each encode the master is asked that no holder has more
than m shards, the deployment's reason for RS(6,3) on three servers.

A wrong shard file is counted once: a rebuilt shard that differs from the
one `ec.encode` wrote under `rebuilt_shards_differing_from_encoded`, and
under `shards_differing_from_reference` the encoded shards and the rebuilt
ones that agree with a wrong encoded one (the reference is an encoder; a
rebuilt shard equal to a sound encoded shard is the reference's).
"""

import os
import time

from kinds import seal_repair
from lib import cluster as cl
from lib import datagen, reference, roofline_terms

ENCODE = seal_repair.NODE_ROUTE["ec.encode"]
REBUILD = seal_repair.NODE_ROUTE["ec.rebuild"]


def _refuse_a_program_without_a_volumes_own_geometry():
    """The cell codes its volumes RS(6,3) through `ec.encode -geometry`
    and holds every command to that geometry: a program older than the
    flag would code 10 + 4 under an RS(6,3) name (the program that has it
    counts `geometry_dispatches` in ops/telemetry). Such a program cannot
    be measured here; say so at once, before anything is started."""
    from seaweedfs_tpu.ops import telemetry
    if "geometry_dispatches" not in telemetry.STATS.snapshot():
        raise SystemExit(
            "benchmarks/kinds/seal_holder_loss_flat.py: this program codes "
            "every volume 10 + 4 (ec.encode takes no -geometry, ops/telemetry "
            "has no geometry_dispatches): the RS(6,3) holder-loss cell "
            "cannot be measured on it")


_refuse_a_program_without_a_volumes_own_geometry()


# -- the loop ---------------------------------------------------------------

def prepare(run) -> dict:
    config, traffic, cluster = run.config, run.traffic, run.cluster
    sets = [sorted(int(s) for s in held) for held in traffic["holder_sets"]]
    cl.check(sorted(s for held in sets for s in held) ==
             list(range(cluster.total)) and
             len(sets) == len(cluster.servers) and
             all(len(held) <= cluster.m for held in sets),
             f"holder_sets {sets} is no partition of the {cluster.total} "
             f"shards over {len(cluster.servers)} servers with none above "
             f"m = {cluster.m}")
    cl.check(config["layout"] == "flat", "the mix is a flat volume's")
    state = {"cycles": [], "sets": sets, "losses": 0,
             "geometry": f"{cluster.k},{cluster.m}",
             "per_seal": int(traffic["losses_per_seal"]),
             "gather_limit": float(traffic["gathered_shards_at_most"])}
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
              "dat_bytes": state["dat_bytes"], "holder_sets": sets,
              "geometry": state["geometry"],
              "losses_per_seal": state["per_seal"],
              "seconds": time.perf_counter() - t0})
    # warm-up: the same commands on the uploaded volume itself, losing
    # one set: the encode's (m, k) operand and a holder's (lost, k)
    # decode are one shape (every set is as large as m), so both
    # programs are compiled (or found in the cache) before the window
    # opens (compiles_in_window holds the program to that)
    state["warm"] = _cycle(run, state, volume["vid"], timed=False,
                           deadline=None, losses=[0])
    return state


def _cycle(run, state, vid: int, timed: bool, deadline, losses=None) -> dict:
    """One sealed volume: the encode, then its holder losses. The
    warm-up is told which sets to lose; a cycle of the window takes the
    next ones in order and stops losing once the time is up, so that the
    command in flight then is the last one."""
    cluster, config = run.cluster, run.config
    every = set(range(cluster.total))
    cycle = {"vid": vid, "encoded": None, "encode_reply": None,
             "rebuilds": [], "error": None, "raised": False,
             "not_landed": 0, "above_m": 0}
    state["cycles"].append(cycle)
    enc = seal_repair._timed(run, "ec.encode", state["dat_bytes"], timed,
                             "-volumeId", str(vid),
                             "-geometry", state["geometry"])
    enc["work"] = roofline_terms.encode_work(config, state["shard_bytes"])
    if enc["error"]:
        cycle["error"], cycle["raised"] = enc["error"], True
        return cycle
    cycle["encode_reply"] = enc["replies"].get(ENCODE) or {}
    if not seal_repair._landed(run, state, cycle, "ec.encode", vid,
                               sorted(every)):
        return cycle
    with run.tracer.mark("check"):
        cluster.wait_shards(vid, every,
                            f"{cluster.total} shards of volume {vid}")
        held = {}
        for urls in cluster.ec_lookup(vid).values():
            for url in urls:
                held[url] = held.get(url, 0) + 1
        cycle["above_m"] = sum(n > cluster.m for n in held.values())
        files = cluster.shard_files(vid)
        cl.check(set(files) == every, f"shard files of {vid} after "
                 f"encode: {sorted(files)}")
        cycle["encoded"] = reference.sha256_files(
            [files[s] for s in range(cluster.total)])
    for n in range(state["per_seal"] if losses is None else len(losses)):
        if deadline and time.perf_counter() >= deadline:
            break
        if losses is None:
            which = state["losses"] % len(state["sets"])
            state["losses"] += 1
        else:
            which = losses[n]
        lost = state["sets"][which]
        rebuild = {"set": which, "lost": lost, "shas": None, "reply": None}
        cycle["rebuilds"].append(rebuild)
        with run.tracer.mark("lose"):
            cluster.delete_shards(vid, lost)
        reb = seal_repair._timed(run, "ec.rebuild",
                                 state["shard_bytes"] * len(lost), timed,
                                 "-collection", cluster.collection)
        # the decode by its equation, a byte column of the stripe: k
        # survivor bytes in, the lost ones out, a dense (lost, k) block
        # of the inverse (the flat encode's count, lib/roofline_terms.py)
        reb["work"] = {"columns": state["shard_bytes"],
                       "column_bytes": cluster.k + len(lost),
                       "column_terms": len(lost) * cluster.k}
        if reb["error"]:
            cycle["error"], cycle["raised"] = reb["error"], True
            return cycle
        rebuild["reply"] = reb["replies"].get(REBUILD) or {}
        if not seal_repair._landed(run, state, cycle, "ec.rebuild", vid,
                                   lost):
            return cycle
        with run.tracer.mark("check"):
            cluster.wait_shards(vid, every, f"{cluster.total} shards of "
                                            f"{vid} after rebuild")
            files = cluster.shard_files(vid)
            cl.check(set(files) == every, f"shard files of {vid} after "
                     f"rebuild: {sorted(files)}")
            rebuild["shas"] = reference.sha256_files(
                [files[s] for s in lost])
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


def _off_the_geometry(reply: dict, cluster) -> bool:
    return (reply.get("k"), reply.get("m")) != (cluster.k, cluster.m)


def _encode_off_the_geometry(reply: dict, cluster) -> bool:
    return _off_the_geometry(reply, cluster) or \
        reply.get("shards") != cluster.total or \
        list(reply.get("operand") or []) != [cluster.m, cluster.k]


def _off_the_full_gather(rebuild: dict, cluster) -> bool:
    reply, lost = rebuild["reply"], rebuild["lost"]
    return reply.get("repair_mode") != "full" or \
        sorted(reply.get("lost") or []) != lost or \
        _off_the_geometry(reply, cluster) or \
        list(reply.get("operand") or []) != [len(lost), cluster.k] or \
        bool(reply.get("repair_fallback"))


def verify(run, state):
    """Outside the timed ops: every encoded volume's k + m shards against
    the plain reference at the configuration's geometry, every rebuilt
    shard against the encoded one (and so against the reference's), every
    reply's geometry, every rebuild's route, lost set, operand and
    gathered bytes, every encode's spread over the holders."""
    cluster = run.cluster
    t0 = time.perf_counter()
    want = reference.shard_shas(state["kept"] + ".dat", cluster.k, cluster.m)
    differing = rebuilt_differing = raised = not_landed = 0
    above_m = off_route = off_geometry = 0
    gathered = []
    for cycle in state["cycles"]:
        # a command that raised or whose shards had not landed left
        # nothing to compare: its own check counts it, not these
        encoded = cycle["encoded"] is not None
        bad_enc = encoded and sum(
            got != ref for got, ref in zip(cycle["encoded"], want))
        done = [r for r in cycle["rebuilds"] if r["shas"] is not None]
        bad_reb = bad_ref = 0
        for r in done:
            for sid, sha in zip(r["lost"], r["shas"]):
                if sha != cycle["encoded"][sid]:
                    bad_reb += 1
                elif sha != want[sid]:
                    bad_ref += 1
        differing += bad_enc + bad_ref
        rebuilt_differing += bad_reb
        raised += cycle["raised"]
        not_landed += cycle["not_landed"]
        above_m += cycle["above_m"]
        if cycle["encode_reply"] is not None:
            off_geometry += _encode_off_the_geometry(cycle["encode_reply"],
                                                     cluster)
        replied = [r for r in cycle["rebuilds"] if r["reply"] is not None]
        off_route += sum(_off_the_full_gather(r, cluster) for r in replied)
        # a reply with no byte account cannot say it gathered k shards
        # only: counted as every shard there is
        gathered += [r["reply"]["repair_bytes"] / state["shard_bytes"]
                     if r["reply"].get("repair_bytes")
                     else float(cluster.total) for r in replied]
        if cycle is not state["warm"]:
            started = len(cycle["rebuilds"]) if encoded else 0
            run.attempted += 1 + started
            run.failed += (bad_enc > 0 or not encoded) + \
                (started - len(done)) + sum(
                    any(sha != cycle["encoded"][sid] or sha != want[sid]
                        for sid, sha in zip(r["lost"], r["shas"]))
                    for r in done)
    run.check("shards_differing_from_reference", differing, 0,
              differing == 0)
    run.check("rebuilt_shards_differing_from_encoded", rebuilt_differing, 0,
              rebuilt_differing == 0)
    run.check("commands_that_raised", raised, 0, raised == 0)
    run.check("shards_not_on_disk_when_command_returned", not_landed, 0,
              not_landed == 0)
    run.check("holders_above_m_shards", above_m, 0, above_m == 0)
    run.check("rebuilds_off_the_full_gather", off_route, 0, off_route == 0)
    run.check("commands_off_the_configured_geometry", off_geometry, 0,
              off_geometry == 0)
    worst = max(gathered, default=0.0)
    run.check("gathered_shards_at_most", worst, state["gather_limit"],
              worst <= state["gather_limit"])
    run.emit({"phase": "verify", "cycles": len(state["cycles"]),
              "rebuilds": sum(len(c["rebuilds"]) for c in state["cycles"]),
              "lost": [[r["lost"] for r in c["rebuilds"]]
                       for c in state["cycles"]],
              "geometry": state["geometry"],
              "reference": reference.__name__,
              "reference_s": time.perf_counter() - t0})


end_to_end = seal_repair.end_to_end
